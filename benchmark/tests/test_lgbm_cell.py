"""The ``lgbm255_keyed`` cell's own pieces: the model kind ``lgbm``
(generator, PMML writer, plain reference, tolerance, forest shape) held
to the program and to its two controls, and the manifest with the cell
in it."""

import dataclasses
import json
import os
import sys
import tempfile

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for p in (ROOT, BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)

from lib import byname, lgbm, manifest  # noqa: E402
from lib.stream import Stream  # noqa: E402

CELL = "lgbm255_keyed.kafka_saturated"
ZIPF = {"kind": "zipf", "a": 1.1}


def kind():
    return byname.load("models", "lgbm")


def config():
    with open(os.path.join(BENCH, "configs", "lgbm255_keyed.json")) as fh:
        return json.load(fh)


def stream_rows(seed, n):
    return Stream(seed, 28, 150_000_000, ZIPF, 4096).rows(0, n)


def miss(model, got, ref):
    return float((np.abs(np.asarray(got, np.float64) - ref) / (
        model.SCORE_ATOL + model.SCORE_RTOL * np.abs(ref))).max())


def test_the_configuration_is_at_published_widths():
    m = config()["model"]
    assert m == {"n_trees": 500, "num_leaves": 255, "n_features": 28,
                 "max_bin": 255, "learning_rate": 0.1}
    assert config()["expected_backends"]["kernel_backend"] == "xla"


@pytest.mark.parametrize("seed", [2**31 + 41, 9])
def test_the_trees_grow_leaf_wise_past_depth_ten(seed):
    g = lgbm.gen_arrays(seed, 40, 255, 28, 255, 0.1)
    assert g.feat.shape == (40, 254) and g.leaf.shape == (40, 255)
    d = g.depths()
    assert (d > 10).any() and d.max() <= 127
    cuts = lgbm.bin_cuts(255)
    assert cuts.size == 254 and np.isin(g.thr, cuts).all()
    # every leaf is reached exactly once from its tree's root
    for t in range(g.feat.shape[0]):
        kids = np.concatenate([g.left[t], g.right[t]])
        assert sorted((~kids[kids < 0]).tolist()) == list(range(255))
        assert sorted(kids[kids >= 0].tolist()) == list(range(1, 254))


@pytest.mark.parametrize("seed", [2**31 + 43, 3])
def test_the_program_scores_the_kind_within_its_tolerance(seed):
    """The kind's PMML through ``compile_pmml`` and the rank wire (the
    XLA twin on the CPU), a cut of the forest to 24 trees: every
    probability within the limit of the plain reference, and the rank
    wire's scorer the chain's."""
    from flink_jpmml_tpu.compile import compile_pmml
    from flink_jpmml_tpu.pmml import parse_pmml_file

    model = kind()
    g = model.generate(seed, dict(config()["model"], n_trees=24))
    with tempfile.TemporaryDirectory() as d:
        cm = compile_pmml(parse_pmml_file(model.write_pmml(g, d)))
    q = cm.quantized_scorer()
    assert q is not None and q.labels == ("1", "0") and q.event_class == 0
    X = stream_rows(seed, 512)
    got = np.asarray(q.predict_wire(q.wire.encode(X)))
    assert got.shape == (512,)
    assert miss(model, got, model.reference_scores(g, X)) <= 1.0


@pytest.mark.parametrize("seed", [2**31 + 47, 5])
def test_the_controls_fail_the_score_limit(seed):
    """At the cell's own model size: the reference with every leaf
    rounded to bfloat16 (the precision below the configuration's bf16
    hi+lo pair) misses the limit by ten times and more; the raw margin,
    delivered without the chain's link, misses it; the hi+lo pair, what
    the configuration states, holds it."""
    import ml_dtypes

    model = kind()
    g = model.generate(seed, config()["model"])
    X = stream_rows(seed, 4096)
    ref = model.reference_scores(g, X)

    def with_leaves(leaf32):
        lowered = dataclasses.replace(g, leaf=leaf32.astype(np.float64))
        return model.reference_scores(lowered, X).astype(np.float32)

    hi = g.leaf.astype(ml_dtypes.bfloat16).astype(np.float32)
    lo = (g.leaf - hi).astype(ml_dtypes.bfloat16).astype(np.float32)
    assert miss(model, with_leaves(hi), ref) >= 10.0
    assert miss(model, model.lgbm_ref.margins(g, X), ref) > 1.0
    assert miss(model, with_leaves(hi + lo), ref) < 0.5


def test_the_walk_is_counted_by_hand():
    """Two trees over 3 features. Tree 0: root ``f1 <= 0`` → leaf 0, else
    ``f2 <= 0`` → leaf 1, else leaf 2. Tree 1: root ``f2 <= 1`` → node 1,
    else leaf 2; node 1 ``f1 <= -1`` → leaf 0, else leaf 1. Record a
    (f1 = -2, f2 = 2) walks 1 + 1, b (1, 0) walks 2 + 2, c (1, 3) walks
    2 + 1: 9 comparisons over 6 tree walks."""
    model = kind()
    g = lgbm.LgbmArrays(
        feat=np.array([[1, 2], [2, 1]], np.int32),
        thr=np.array([[0.0, 0.0], [1.0, -1.0]], np.float32),
        left=np.array([[~0, ~1], [1, ~0]], np.int32),
        right=np.array([[1, ~2], [~2, ~1]], np.int32),
        leaf=np.zeros((2, 3)), n_features=3)
    X = np.array([[0, -2, 2], [0, 1, 0], [0, 1, 3]], np.float32)
    assert model.mean_walk(g, X) == 9 / 6
    assert g.depths().tolist() == [2, 2]


def test_forest_shape_counts_splits_leaves_and_the_stream_walk():
    model = kind()
    m = {"n_trees": 3, "num_leaves": 7, "n_features": 5, "max_bin": 255,
         "learning_rate": 0.1}
    t, inner, leaves, walk = model.forest_shape(m)
    assert (t, inner, leaves) == (3, 18, 21)
    g = lgbm.gen_arrays(model.WALK_SEED, 3, 7, 5, 255, 0.1)
    assert walk == model.mean_walk(g, model.walk_rows(5))
    assert 1.0 <= walk <= 6.0


def test_the_manifest_with_the_cell_keeps_its_rules():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        m = json.load(fh)
    assert manifest.manifest_faults(m) == []
    assert CELL in [w["name"] for w in m["workloads"]]
    own = [x["name"] for x in m["per_layer"] if x["workloads"] == [CELL]]
    assert sorted(own) == [f"forest_{s}_ms_per_dispatch.lgbm"
                           for s in ("leaves", "paths", "ranks")]
    for x in m["end_to_end"] + m["per_layer"]:
        if manifest.ONE_CHIP in x.get("workloads", ()):
            assert CELL in x["workloads"], x["name"]
