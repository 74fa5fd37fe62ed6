"""Model kind ``gbm``: a seeded gradient-boosted forest as a PMML
MiningModel (``lib/gbm.py``, a copy of the program's own generator in
array form) and its plain reference (``reference/gbm_ref.py``, a numpy
tree walk over those arrays).

A model kind is one file ``models/<kind>.py``, named by a
configuration's ``model_kind``, with:

- ``generate(seed, model) -> handle``: the model from the seed and the
  configuration's ``model`` object;
- ``write_pmml(handle, out_dir) -> path``: the document the program
  parses;
- ``reference_scores(handle, X) -> float64[n]``: what the deployment
  must answer for float32 rows ``X``, with nothing of the program in
  it;
- ``SCORE_RTOL``, ``SCORE_ATOL``: how far a delivered score may lie
  from the reference, from the precision the configuration states;
- ``forest_shape(model) -> (trees, inner nodes, leaves, walk)``: the
  forest as ``lib/roofline.py`` counts it, nodes and leaves summed over
  the trees, ``walk`` the comparisons a record makes in one tree. A kind
  whose trees are not complete (grown leaf-wise, pruned) states its own
  ``walk`` here and says why: the mean path its records take, since a
  longer one counts work the algorithm does not do.
"""

from lib import gbm
from reference import gbm_ref

# The rank wire carries each leaf as a bf16 hi+lo pair (2**-17
# relative, compile/qtrees.py _split_bf16) and adds 500 of them in
# float32, which leaves ~2e-5 absolute on sums of magnitude 2-9
# (measured 2.2e-5). The program's own tests hold the kernel to rtol
# 1e-4 / atol 1e-5 against its XLA twin, which shares that quantisation
# (tests/test_qtrees_pallas.py:41); against an exact walk the absolute
# floor is 5e-5. A bf16-only sum would miss by ~4e-3.
SCORE_RTOL, SCORE_ATOL = 1e-4, 5e-5


def generate(seed: int, model: dict):
    return gbm.gen_arrays(
        seed, model["n_trees"], model["depth"], model["n_features"],
        model["hist_bins"], model["base_score"],
    )


write_pmml = gbm.write_pmml
reference_scores = gbm_ref.scores


def forest_shape(model: dict):
    """Complete trees of ``depth``: every record compares once a level."""
    t, d = model["n_trees"], model["depth"]
    return t, t * (2 ** d - 1), t * 2 ** d, d
