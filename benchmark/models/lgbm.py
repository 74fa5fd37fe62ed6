"""Model kind ``lgbm``: LightGBM's Higgs comparison forest, grown
leaf-wise from the seed (``lib/lgbm.py``), as JPMML-LightGBM writes a
binary classifier (a ``modelChain``: the trees' sum, then a logit
``RegressionModel``), and its plain reference (``reference/lgbm_ref.py``:
a float64 walk of the generator's arrays, then the logistic link). The
score a deployment delivers, and folds into the key's state, is the
probability of category "1".

The interface is ``models/gbm.py``'s.
"""

from functools import lru_cache

import numpy as np

from lib import keys, lgbm
from reference import lgbm_ref

# A delivered probability p = σ(m), m summed by the rank wire from 500
# bf16 hi+lo leaves in float32 (compile/qtrees.py _split_bf16: ~2**-17
# relative a leaf): m is off by up to ~2e-5 on a forest of this size, and
# σ' = p(1 - p) ≤ 1/4 passes at most a quarter of that on to p; σ's
# float32 exp and division add ~1e-7.
# SCORE_ATOL 2e-5: four times the most that leaves p.
# SCORE_RTOL 0: p's error, p(1 - p)·dm, is absolute and shrinks at both
# tails; a relative term would only loosen the limit where p is near 1.
# The control, every leaf in bf16 alone (the precision below the
# configuration's), misses it about 150 times (benchmark/tests).
SCORE_RTOL, SCORE_ATOL = 0.0, 2e-5

# records the walk is counted on (forest_shape): seeded, as the stream
WALK_SAMPLE_ROWS, WALK_SEED = 4096, 0


def generate(seed: int, model: dict):
    g = lgbm.gen_arrays(
        seed, model["n_trees"], model["num_leaves"], model["n_features"],
        model["max_bin"], model["learning_rate"],
    )
    d = g.depths()
    print(f"# lgbm forest: {g.feat.shape[0]} trees of {g.leaf.shape[1]} "
          f"leaves, depth largest {int(d.max())}, mean {float(d.mean()):.2f},"
          f" {int((d > 10).sum())} trees deeper than 10", flush=True)
    return g


write_pmml = lgbm.write_pmml
reference_scores = lgbm_ref.scores


def walk_rows(n_features: int, n: int = WALK_SAMPLE_ROWS,
              seed: int = WALK_SEED) -> np.ndarray:
    """Rows as the stream makes them: N(0, 1.5) features, the key's
    float32 in column 0 (lib/stream.py)."""
    rng = np.random.default_rng([seed, 0])
    X = rng.normal(0.0, lgbm.FEATURE_SD, size=(n, n_features)).astype(
        np.float32)
    X[:, 0] = keys.rank_to_f32(rng.integers(0, 150_000_000, n))
    return X


def mean_walk(g, X) -> float:
    """The comparisons a record makes in a tree, in the mean over the
    records and the trees."""
    T = g.feat.shape[0]
    trees = np.arange(T)[None, :]
    rows = np.arange(X.shape[0])[:, None]
    node = np.zeros((X.shape[0], T), np.int64)
    steps = np.zeros((X.shape[0], T), np.int64)
    while (node >= 0).any():
        inner = node >= 0
        at = np.where(inner, node, 0)
        go_left = X[rows, g.feat[trees, at]] <= g.thr[trees, at]
        nxt = np.where(go_left, g.left[trees, at], g.right[trees, at])
        steps += inner
        node = np.where(inner, nxt, node)
    return float(steps.mean())


@lru_cache(maxsize=4)
def _walk(n_trees, num_leaves, n_features, max_bin, learning_rate):
    g = lgbm.gen_arrays(WALK_SEED, n_trees, num_leaves, n_features, max_bin,
                        learning_rate)
    return mean_walk(g, walk_rows(n_features))


def forest_shape(model: dict):
    """Leaf-wise trees of ``num_leaves`` leaves, ``num_leaves - 1``
    splits each. Their depth varies from leaf to leaf, so ``walk`` is
    not a depth but the mean path the stream's records take: counted
    on 4,096 rows like the stream's through the forest of seed 0. A
    forest of another seed walks within a few hundredths of it (the
    trees grow by the same rule), and the largest depth would count
    work the algorithm does not do."""
    walk = _walk(model["n_trees"], model["num_leaves"], model["n_features"],
                 model["max_bin"], model["learning_rate"])
    t, leaves = model["n_trees"], model["num_leaves"]
    return t, t * (leaves - 1), t * leaves, walk
