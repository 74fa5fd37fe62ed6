"""Model kind ``xgbmc``: XGBoost's multi-class forest of its Covertype
example, drawn from the seed (``lib/xgbmc.py``), as JPMML-XGBoost writes
a multi-class classifier (a ``modelChain``: one tree sum a class, then
a softmax ``RegressionModel``), and its plain reference
(``reference/xgbmc_ref.py``: a float64 walk of the generator's arrays,
then the softmax). The deployment delivers every class's probability
and the label; the score it folds into the key's state, and hands the
benchmark's sink, is the largest probability.

The interface is ``models/gbm.py``'s, with ``probabilities(handle, X)``
besides for the warm-up check that holds every class.
"""

from lib import xgbmc
from reference import xgbmc_ref

# A delivered probability p_k = softmax(b + m)_k, each m_k summed by the
# rank wire from 3,000 bf16 hi+lo leaves in float32 (compile/qtrees.py
# _split_bf16: ~2**-17 relative a leaf, leaves of 0.3 falling to 0.017).
# The leaves' rounding alone moves a probability by up to 5.8e-6 on
# 1,024 stream rows (two seeds, numpy); the float32 sums and the float32
# exp and division add less than that.
# SCORE_ATOL 5e-5: about eight times the most the leaves' rounding
# leaves, with room for the sums' order on the chip.
# SCORE_RTOL 0: a probability's error, p_k·(dm_k - Σ p_j dm_j), is
# absolute and shrinks at both ends; a relative term would only loosen
# the limit where p is near 1.
# The control, every leaf in bf16 alone (the precision below the
# configuration's), misses it 65 to 70 times (benchmark/tests).
SCORE_RTOL, SCORE_ATOL = 0.0, 5e-5


def generate(seed: int, model: dict):
    """``n_trees`` is the forest's, rounds × classes: XGBoost grows a tree
    a class a round (``rehearse.py`` cuts it, and the rounds with it)."""
    g = xgbmc.gen_arrays(
        seed, max(1, model["n_trees"] // model["n_classes"]),
        model["n_classes"], model["max_depth"],
        model["n_features"], model["n_quantitative"], model["max_bin"],
        model["eta"], model["base_score"],
    )
    R, C, inner = g.feat.shape
    print(f"# xgbmc forest: {R} rounds x {C} classes = {R * C} trees of "
          f"depth {g.depth} ({inner} splits, {inner + 1} leaves each)",
          flush=True)
    return g


write_pmml = xgbmc.write_pmml
reference_scores = xgbmc_ref.scores
probabilities = xgbmc_ref.probabilities


def forest_shape(model: dict):
    """Complete trees of ``max_depth``: every record compares once a
    level, so the mean walk of the stream's rows is the depth
    (``benchmark/tests`` counts it on seeded stream rows)."""
    t, d = model["n_trees"], model["max_depth"]
    return t, t * (2 ** d - 1), t * 2 ** d, d
