"""The plain reference of the ``lgbm`` kind: what the deployment must
answer, computed with nothing of the program in it.

``margins`` walks every tree of the generator's own arrays
(benchmark/lib/lgbm.py, LightGBM's model form) in float32 comparisons
and sums the leaves in float64; ``scores`` is the chain's link on that
sum, the probability of category "1": ``1 / (1 + exp(-(b + a·m)))``
with the logit table's coefficient ``a`` = 1 and intercept ``b`` = 0,
as JPMML-LightGBM writes a binary classifier. No rank wire, no
bucketizer, no kernel.
"""

from __future__ import annotations

import numpy as np


def margins(g, X: np.ndarray, chunk: int = 4096) -> np.ndarray:
    """``g``: lib.lgbm.LgbmArrays · ``X`` float32 [n, F] (no NaN) →
    float64 [n]: the sum of every tree's leaf."""
    X = np.asarray(X, np.float32)
    n, T = X.shape[0], g.feat.shape[0]
    out = np.empty(n, np.float64)
    trees = np.arange(T)[None, :]
    for lo in range(0, n, chunk):
        xb = X[lo:lo + chunk]
        rows = np.arange(xb.shape[0])[:, None]
        node = np.zeros((xb.shape[0], T), np.int64)
        while (node >= 0).any():
            inner = node >= 0
            at = np.where(inner, node, 0)
            go_left = xb[rows, g.feat[trees, at]] <= g.thr[trees, at]
            nxt = np.where(go_left, g.left[trees, at], g.right[trees, at])
            node = np.where(inner, nxt, node)
        out[lo:lo + chunk] = g.leaf[trees, ~node].sum(axis=1)
    return out


def scores(g, X: np.ndarray) -> np.ndarray:
    """float64 [n]: the probability of category "1"."""
    return 1.0 / (1.0 + np.exp(-margins(g, X)))
