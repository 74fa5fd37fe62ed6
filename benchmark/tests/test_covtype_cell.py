"""The ``xgb3000_covtype_keyed`` cell's own pieces: the model kind
``xgbmc`` (generator, PMML writer, plain reference, tolerance, forest
shape) held to the program and to its control, its readers on a
synthetic window, the manifest with the cell in it, and the cell whole
at a tiny size on the CPU, clean and with planted faults that
``correct`` has to catch and ``broken`` has to name."""

import argparse
import dataclasses
import json
import os
import re
import sys
import tempfile

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for p in (ROOT, BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)

from lib import byname, lgbm, manifest, xgbmc  # noqa: E402
from lib.stream import Stream  # noqa: E402

COVTYPE_CELL = "xgb3000_covtype_keyed.kafka_saturated"
COVTYPE_LOAD_FAULTS = ("least_lead_records.harness",
                       "least_lead_records.producer")


def covtype_kind():
    return byname.load("models", "xgbmc")


def covtype_config():
    with open(os.path.join(BENCH, "configs",
                           "xgb3000_covtype_keyed.json")) as fh:
        return json.load(fh)


def covtype_rows(seed, n):
    return Stream(seed, 54, 150_000_000, {"kind": "zipf", "a": 1.1},
                  16384).rows(0, n)


def covtype_miss(model, got, ref):
    return float((np.abs(np.asarray(got, np.float64) - ref) / (
        model.SCORE_ATOL + model.SCORE_RTOL * np.abs(ref))).max())


def test_the_covertype_forest_is_at_its_published_widths():
    cfg = covtype_config()
    m = cfg["model"]
    assert m == {"n_trees": 21000, "n_classes": 7, "max_depth": 6,
                 "n_features": 54, "n_quantitative": 10, "max_bin": 255,
                 "eta": 0.3, "base_score": 0.5}
    assert m["n_trees"] == 3000 * m["n_classes"]  # num_round 3000
    assert set(cfg["reduced"]) == {"chips", "max_bin"}
    assert cfg["expected_backends"]["kernel_backend"] == "xla"
    assert cfg["path"] == "block_classes"


@pytest.mark.parametrize("seed", [2**31 + 51, 4])
def test_the_covertype_trees_are_complete_on_the_wires_cuts(seed):
    g = xgbmc.gen_arrays(seed, 30, 7, 6, 54, 10, 255, 0.3, 0.5)
    assert g.feat.shape == (30, 7, 63) and g.leaf.shape == (30, 7, 64)
    assert g.depth == 6 and g.leaf.dtype == np.float32
    quant = g.feat < 10
    assert np.isin(g.thr[quant], lgbm.bin_cuts(255)).all()
    assert (g.thr[~quant] == xgbmc.INDICATOR_CUT).all()
    assert lgbm.bin_cuts(255).size == 254  # the uint8 wire's most
    # the leaves shrink with the rounds
    assert np.abs(g.leaf[-1]).mean() < np.abs(g.leaf[0]).mean()


@pytest.mark.parametrize("seed", [2**31 + 53, 6])
def test_the_program_scores_every_covertype_class_within_tolerance(seed):
    """The kind's PMML through ``compile_pmml`` and the rank wire (the
    XLA twin on the CPU), a cut of the forest to 10 rounds: every class's
    probability within the limit of the plain reference, the label its
    argmax, on a uint8 wire."""
    from flink_jpmml_tpu.compile import compile_pmml
    from flink_jpmml_tpu.pmml import parse_pmml_file

    model = covtype_kind()
    g = model.generate(seed, dict(covtype_config()["model"], n_trees=70))
    with tempfile.TemporaryDirectory() as d:
        cm = compile_pmml(parse_pmml_file(model.write_pmml(g, d)))
    q = cm.quantized_scorer()
    assert q is not None and q.labels == tuple(str(k) for k in range(7))
    assert q.wire.dtype is np.uint8
    X = covtype_rows(seed, 512)
    top, probs, label = (np.asarray(a) for a in q.predict_wire(
        q.wire.encode(X)))
    ref = model.probabilities(g, X)
    assert probs.shape == (512, 7)
    assert covtype_miss(model, probs, ref) <= 1.0
    assert covtype_miss(model, top, model.reference_scores(g, X)) <= 1.0
    assert (label == ref.argmax(axis=1)).all()


@pytest.mark.parametrize("seed", [2**31 + 57, 8])
def test_the_bf16_control_fails_the_class_limit(seed):
    """At the cell's own model size: the reference with every leaf
    rounded to bfloat16 (the precision below the configuration's bf16
    hi+lo pair) misses the limit by ten times and more; the hi+lo pair,
    what the configuration states, holds it with room."""
    import ml_dtypes

    model = covtype_kind()
    g = model.generate(seed, covtype_config()["model"])
    X = covtype_rows(seed, 256)
    ref = model.probabilities(g, X)

    def with_leaves(leaf):
        return model.probabilities(dataclasses.replace(g, leaf=leaf), X)

    hi = g.leaf.astype(ml_dtypes.bfloat16).astype(np.float64)
    lo = (g.leaf - hi).astype(ml_dtypes.bfloat16).astype(np.float64)
    assert covtype_miss(model, with_leaves(hi), ref) >= 10.0
    assert covtype_miss(model, with_leaves(hi + lo), ref) < 0.5


def test_the_covertype_reference_walks_by_hand():
    """One round of two classes, depth 1 over 2 features: class 0 splits
    ``f0 < 0`` (leaves 1, -1), class 1 ``f1 < 2`` (leaves 0.5, 3), base
    0.5 in both tables, so it cancels."""
    model = covtype_kind()
    g = xgbmc.XgbmcArrays(
        feat=np.array([[[0], [1]]], np.int32),
        thr=np.array([[[0.0], [2.0]]], np.float32),
        leaf=np.array([[[1.0, -1.0], [0.5, 3.0]]], np.float32),
        base_score=0.5, n_features=2)
    X = np.array([[-1.0, 1.0], [1.0, 5.0]], np.float32)
    m = model.xgbmc_ref.margins(g, X)
    assert m.tolist() == [[1.0, 0.5], [-1.0, 3.0]]
    p = model.probabilities(g, X)
    e = np.exp(m - m.max(axis=1, keepdims=True))
    assert np.allclose(p, e / e.sum(axis=1, keepdims=True))


def test_the_covertype_shape_is_complete_trees():
    model = covtype_kind()
    t, inner, leaves, walk = model.forest_shape(covtype_config()["model"])
    assert (t, inner, leaves, walk) == (21000, 21000 * 63, 21000 * 64, 6)


def test_the_manifest_takes_the_covertype_cell():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        m = json.load(fh)
    assert manifest.manifest_faults(m) == []
    cells = [w["name"] for w in m["workloads"]]
    assert cells[-1] == COVTYPE_CELL
    own = [x["name"] for x in m["per_layer"] if x["workloads"] == [
        COVTYPE_CELL]]
    assert sorted(own) == [
        "forest_leaves_ms_per_dispatch.covtype",
        "forest_link_ms_per_dispatch.covtype",
        "forest_paths_ms_per_dispatch.covtype",
        "readback_bytes_per_rec.covtype"]
    for x in m["end_to_end"] + m["per_layer"]:
        if manifest.ONE_CHIP in x.get("workloads", ()):
            assert COVTYPE_CELL in x["workloads"], x["name"]
    for name in ("forest_kernel_roofline", "scoring_program_roofline",
                 "records_per_s"):
        x = next(x for x in m["end_to_end"] + m["per_layer"]
                 if x["name"] == name)
        assert COVTYPE_CELL in x["workloads"]


_NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}")
_UNIT = re.compile(r"[A-Za-z0-9_/%.-]{1,16}")
_ENTRY_KEYS = {
    "configs": {"name", "source", "file", "reduced", "why"},
    "workloads": {"name", "config", "traffic", "chips", "why"},
    "end_to_end": {"name", "unit", "better", "bound", "source"},
    "per_layer": {"name", "unit", "better", "source", "layer", "moves"},
}


def _one_line(text):
    return (isinstance(text, str) and 1 <= len(text) <= 200
            and text.isprintable())


@pytest.mark.parametrize("section", sorted(_ENTRY_KEYS))
def test_every_manifest_entry_keeps_the_form(section):
    # the form a manifest is held to before any run: names, units, the
    # one-line texts of at most 200 characters, and no key beyond these
    path = os.path.join(ROOT, "BENCHMARK.json")
    assert os.path.getsize(path) <= 64 * 1024
    with open(path) as fh:
        m = json.load(fh)
    assert all(_one_line(w) for w in m["command"])
    entries = m[section]
    assert len({e["name"] for e in entries}) == len(entries)
    for e in entries:
        keys = _ENTRY_KEYS[section]
        extra = {"workloads"} if section in ("end_to_end", "per_layer") else set()
        assert keys <= set(e) <= keys | extra, e["name"]
        assert _NAME.fullmatch(e["name"]), e["name"]
        for text in ("why", "layer", "source"):
            if text in e and section != "end_to_end":
                assert _one_line(e[text]), (e["name"], text, len(e[text]))
        if section == "configs":
            assert len(e["reduced"]) <= 16
            assert all(_NAME.fullmatch(k) for k in e["reduced"]), e["name"]
        elif section == "workloads":
            assert _NAME.fullmatch(e["config"]) and _NAME.fullmatch(
                e["traffic"]) and e["chips"] in (1, 4), e["name"]
        else:
            assert _UNIT.fullmatch(e["unit"]), e["name"]
            assert e["better"] in ("lower", "higher"), e["name"]
            assert e["source"] in ("device_trace", "program_span",
                                   "program_counter", "host_clock")


def _covtype_ctx(counters0, counters1, scopes=None):
    return {
        "snap0": {"counters": counters0, "histograms": {}},
        "snap1": {"counters": counters1, "histograms": {}},
        "batches": [(1.0, 65536)],
        "trace": None if scopes is None else {"scopes": scopes},
    }


def test_the_covtype_readers_on_a_synthetic_window():
    def read(name, ctx):
        return byname.load("layer_metrics", name).read(ctx)

    ctx = _covtype_ctx({"readback_bytes": 1000, "records_out": 10},
                       {"readback_bytes": 1000 + 36 * 131072,
                        "records_out": 10 + 131072})
    assert read("readback_bytes_per_rec.covtype", ctx) == 36.0
    # a program without the counter (the parent) reports nothing
    assert read("readback_bytes_per_rec.covtype", _covtype_ctx(
        {"records_out": 0}, {"records_out": 65536})) is None
    # two executions of the scoring program: the scopes' self times
    scopes = {"jit_state_fn(1)": {"n": 2, "seconds": {
        "fjt.forest": 0.5, "fjt.forest.paths": 0.4,
        "fjt.forest.leaves": 0.06, "fjt.forest.link": 0.002}},
        "jit_renorm(2)": {"n": 1, "seconds": {"fjt.forest.link": 9.0}}}
    ctx = _covtype_ctx({}, {}, scopes)
    assert read("forest_paths_ms_per_dispatch.covtype", ctx) == \
        pytest.approx(200.0)
    assert read("forest_leaves_ms_per_dispatch.covtype", ctx) == \
        pytest.approx(30.0)
    assert read("forest_link_ms_per_dispatch.covtype", ctx) == \
        pytest.approx(1.0)
    # a link the chip fused into another scope's op: nothing to read
    del scopes["jit_state_fn(1)"]["seconds"]["fjt.forest.link"]
    assert read("forest_link_ms_per_dispatch.covtype", ctx) is None


# -- the cell, whole, on the CPU ---------------------------------------------

def covtype_tiny_run():
    import rehearse
    import run

    assert COVTYPE_CELL in rehearse.cells()
    args = argparse.Namespace(workload=COVTYPE_CELL, seed=2**31 + 59,
                              seconds=1.0, trace=0)
    return run.run_cell(args, overrides=rehearse.TINY, on_chip=False)


def plant_covtype(monkeypatch, fault):
    """``drop_class``: the program's leaves of class 1 zeroed, as if its
    trees were left out; ``bf16``: every leaf in bf16 alone, the lo half
    of the program's pair zeroed; ``swap_labels``: classes 0 and 1
    swapped in what the warm-up delivers (their probabilities and the
    labels), the label's probability left as it was."""
    from flink_jpmml_tpu.compile import qtrees
    from flink_jpmml_tpu.runtime import block

    if fault in ("drop_class", "bf16"):
        real = qtrees._split_bf16

        def split(v):
            hi, lo = real(v)
            if fault == "bf16":
                return hi, np.zeros_like(lo)
            per = v.shape[0] // 7  # tiny: 2 rounds of the 7 classes
            hi, lo = hi.copy(), lo.copy()
            hi[per:2 * per], lo[per:2 * per] = 0, 0
            return hi, lo

        monkeypatch.setattr(qtrees, "_split_bf16", split)
    elif fault == "swap_labels":
        real = block.BlockPipelineBase._emit

        def emit(self, out, n, first_off, decode):
            if isinstance(out, tuple) and first_off < 4196:
                top, probs, label = (np.asarray(a) for a in out)
                probs = probs[:, [1, 0, 2, 3, 4, 5, 6]]
                label = np.choose(label, [1, 0, 2, 3, 4, 5, 6]).astype(
                    label.dtype)
                out = (top, probs, label)
            real(self, out, n, first_off, decode)

        monkeypatch.setattr(block.BlockPipelineBase, "_emit", emit)


@pytest.mark.parametrize("fault,named", [
    (None, set()),
    ("drop_class", {"warmup_score_miss_over_tol"}),
    ("bf16", {"warmup_score_miss_over_tol"}),
    ("swap_labels", {"warmup_class_prob_miss_over_tol",
                     "warmup_labels_differing"}),
])
def test_the_covtype_cell_catches_each_planted_fault(monkeypatch, fault,
                                                     named):
    plant_covtype(monkeypatch, fault)
    res = covtype_tiny_run()
    broken = {n for n in res["broken"] if n not in COVTYPE_LOAD_FAULTS}
    c = res["compared"]
    if fault is None:
        assert res["correct"] and broken == set() and res["failed"] == 0
        assert c["warmup_class_prob_miss_over_tol"]["value"] < 0.5
        assert c["warmup_labels_differing"]["value"] == 0
        assert res["metrics"] == {}
        return
    assert res["correct"] is False
    assert named <= broken, broken
    if fault == "swap_labels":
        # the label's probability is still right: only the whole output
        # shows the fault
        assert c["warmup_score_miss_over_tol"]["holds"]
    assert list(c)[:len(res["broken"])] == res["broken"]
