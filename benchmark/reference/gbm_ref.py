"""The plain reference: what the deployment must answer, computed with
nothing of the program in it.

``scores`` is a numpy tree walk over the generator's own arrays
(benchmark/lib/gbm.py) in float32 comparisons and a float64 sum — no
rank wire, no bucketizer, no kernel. The keyed state's reference is
``reference/state_ref.py``.
"""

from __future__ import annotations

import numpy as np


def scores(g, X: np.ndarray, chunk: int = 8192) -> np.ndarray:
    """``g``: lib.gbm.GbmArrays · ``X`` float32 [n, F] (no NaN) →
    float64 [n]."""
    X = np.asarray(X, np.float32)
    n, T = X.shape[0], g.feat.shape[0]
    n_inner = g.feat.shape[1]
    out = np.empty(n, np.float64)
    trees = np.arange(T)[None, :]
    for lo in range(0, n, chunk):
        xb = X[lo:lo + chunk]
        rows = np.arange(xb.shape[0])[:, None]
        node = np.zeros((xb.shape[0], T), np.int64)
        for _ in range(g.depth):
            f = g.feat[trees, node]
            right = xb[rows, f] >= g.thr[trees, node]
            node = 2 * node + 1 + right
        out[lo:lo + chunk] = (
            g.leaf[trees, node - n_inner].sum(axis=1) + g.base_score
        )
    return out
