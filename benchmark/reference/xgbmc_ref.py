"""The plain reference of the ``xgbmc`` kind: what the deployment must
answer, computed with nothing of the program in it.

``margins`` walks every tree of the generator's own arrays
(benchmark/lib/xgbmc.py, XGBoost's model form: complete trees, ``x <
thr`` to the left) in float32 comparisons and sums each class's leaves
in float64; ``probabilities`` is the chain's link on those sums,
``y_k = base_score + 1.0·m_k`` and a softmax with the largest ``y``
subtracted, as JPMML-XGBoost writes a multi-class classifier. No rank
wire, no bucketizer, no kernel. The rows are walked in chunks on the
process's cores (21,000 trees a row: a few seconds for 4,096 rows).
"""

from __future__ import annotations

import concurrent.futures
import os

import numpy as np

_CHUNK = 64


def _walk(g, X: np.ndarray) -> np.ndarray:
    """float64 [n, C] for one chunk of rows."""
    R, C, inner = g.feat.shape
    T, F = R * C, X.shape[1]
    feat = g.feat.reshape(-1).astype(np.intp)
    thr = g.thr.reshape(-1)
    leaf = g.leaf.reshape(-1).astype(np.float64)
    at_tree = (np.arange(T, dtype=np.intp) * inner)[None, :]
    at_leaf = (np.arange(T, dtype=np.intp) * (inner + 1) - inner)[None, :]
    at_row = (np.arange(X.shape[0], dtype=np.intp) * F)[:, None]
    x = X.reshape(-1)
    node = np.zeros((X.shape[0], T), np.intp)
    for _ in range(g.depth):
        at = at_tree + node
        go_left = np.take(x, at_row + np.take(feat, at)) < np.take(thr, at)
        node = 2 * node + 2 - go_left
    # trees are round-major: tree r·C + k is round r's tree of class k
    return np.take(leaf, at_leaf + node).reshape(-1, R, C).sum(axis=1)


def margins(g, X: np.ndarray) -> np.ndarray:
    """``g``: lib.xgbmc.XgbmcArrays · ``X`` float32 [n, F] (no NaN) →
    float64 [n, C]: each class's sum of its trees' leaves."""
    X = np.ascontiguousarray(X, np.float32)
    starts = range(0, X.shape[0], _CHUNK)
    workers = max(1, min(8, len(os.sched_getaffinity(0))))
    with concurrent.futures.ThreadPoolExecutor(workers) as ex:
        parts = list(ex.map(lambda lo: _walk(g, X[lo:lo + _CHUNK]), starts))
    if not parts:
        return np.zeros((0, g.leaf.shape[1]), np.float64)
    return np.concatenate(parts)


def probabilities(g, X: np.ndarray) -> np.ndarray:
    """float64 [n, C]: the probability of each class."""
    y = g.base_score + margins(g, X)
    e = np.exp(y - y.max(axis=1, keepdims=True))
    return e / e.sum(axis=1, keepdims=True)


def scores(g, X: np.ndarray) -> np.ndarray:
    """float64 [n]: the largest probability, what the deployment folds
    into a key's state."""
    return probabilities(g, X).max(axis=1)
