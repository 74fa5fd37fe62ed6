"""The rank wire takes a multi-class classifier as JPMML-XGBoost writes
it: a ``modelChain`` of one tree sum a class, each into its own field,
then a softmax ``RegressionModel`` whose table k is affine in the k-th
field (compile/qtrees.py ``_match_chain``). The program sums each class
over its own group of trees and runs the softmax, the label's argmax
and the gather of its probability in the same program, and returns the
classification triple.

The chain here is the benchmark's own generator (benchmark/lib/xgbmc.py)
at a small size: 3 classes × 12 rounds of complete depth-4 trees over 10
features, held to its plain reference (benchmark/reference/xgbmc_ref.py)
on every backend, and to the f32 path."""

import os
import sys
import tempfile

import numpy as np
import pytest

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from lib import xgbmc  # noqa: E402
from reference import xgbmc_ref  # noqa: E402

from flink_jpmml_tpu.compile import compile_pmml, qtrees  # noqa: E402
from flink_jpmml_tpu.pmml import parse_pmml  # noqa: E402
from flink_jpmml_tpu.runtime.state import (  # noqa: E402
    COL_COUNT, COL_SUM, StateSpec)

ROUNDS, CLASSES, DEPTH, FEATURES = 12, 3, 4, 10
# the wire's leaves are bf16 hi+lo pairs summed in float32
ATOL = 1e-5


@pytest.fixture(scope="module")
def forest():
    return xgbmc.gen_arrays(2**31 + 45, ROUNDS, CLASSES, DEPTH, FEATURES, 4,
                            255, 0.3, 0.5)


@pytest.fixture(scope="module")
def text(forest):
    with tempfile.TemporaryDirectory() as d:
        with open(xgbmc.write_pmml(forest, d)) as fh:
            return fh.read()


@pytest.fixture(scope="module")
def X():
    rng = np.random.default_rng(45)
    return rng.normal(0.0, 1.5, size=(256, FEATURES)).astype(np.float32)


def triple(q, X):
    value, probs, label = (np.asarray(a)[:X.shape[0]]
                           for a in q.predict_wire(q.wire.encode(X)))
    return value, probs, label


def _scanned(monkeypatch, q):
    """A budget of 8 records a step, in the form the twin runs."""
    m = q._meta
    monkeypatch.setattr(qtrees, "_device_budget", lambda: qtrees.twin_bytes(
        8, int(m["trees"]), int(m["splits"]), int(m["leaves"]),
        q.twin_depth))


@pytest.mark.parametrize("backend", ["twin", "scanned_twin", "pallas"])
def test_every_backend_gives_every_class_the_references(
        forest, text, X, monkeypatch, backend):
    doc = parse_pmml(text)
    if backend == "scanned_twin":
        _scanned(monkeypatch, qtrees.build_quantized_scorer(doc))
    q = qtrees.build_quantized_scorer(
        doc, batch_size=256 if backend == "pallas" else None,
        backend="pallas" if backend == "pallas" else "xla",
        pallas_interpret=backend == "pallas")
    assert q is not None and q.backend == (
        "pallas" if backend == "pallas" else "xla")
    assert q.labels == ("0", "1", "2") and q.event_class is None
    assert q.twin_rows(256) == {"twin": 256, "scanned_twin": 8,
                                "pallas": None}[backend]
    value, probs, label = triple(q, X)
    ref = xgbmc_ref.probabilities(forest, X)
    assert probs.shape == (256, CLASSES) and label.dtype == np.int32
    assert np.abs(probs - ref).max() < ATOL
    assert (label == ref.argmax(axis=1)).all()
    assert (value == probs[np.arange(256), label]).all()


def test_the_twin_scanned_is_the_twin_bit_for_bit(text, X, monkeypatch):
    doc = parse_pmml(text)
    whole = qtrees.build_quantized_scorer(doc)
    _scanned(monkeypatch, whole)
    stepped = qtrees.build_quantized_scorer(doc)
    for a, b in zip(triple(whole, X), triple(stepped, X)):
        assert a.tobytes() == b.tobytes()


def test_the_f32_path_and_decode_agree_with_the_reference(forest, text, X):
    cm = compile_pmml(parse_pmml(text))
    ref = xgbmc_ref.probabilities(forest, X)
    for preds, tol in ((cm.score_dense(X), 1e-6),
                       (cm.quantized_scorer().score(X), ATOL)):
        got = np.array([[p.target.probabilities[c] for c in ("0", "1", "2")]
                        for p in preds])
        assert np.abs(got - ref).max() < tol
        assert [p.target.label for p in preds] == [
            str(k) for k in ref.argmax(axis=1)]
        assert [p.score.value for p in preds] == pytest.approx(
            ref.max(axis=1), abs=tol)


def test_the_state_folds_the_top_probability(forest, text):
    import jax

    from flink_jpmml_tpu.runtime.block import BlockPipeline, FiniteBlockSource

    B, blocks, keys = 32, 4, 5
    rng = np.random.default_rng(17)
    data = rng.normal(0.0, 1.5, size=(B * blocks, FEATURES)).astype(
        np.float32)
    data[:, 0] = rng.integers(0, keys, size=B * blocks).astype(np.float32)
    seen = {}
    cm = compile_pmml(parse_pmml(text), batch_size=B)
    pipe = BlockPipeline(
        FiniteBlockSource(data, block_size=B), cm,
        lambda out, n, first: seen.update(
            {first: [np.asarray(a)[:n] for a in out]}),
        use_native=False, in_flight=1,
        state=StateSpec(capacity=64, key_col=0),
    )
    assert pipe.backend == "rank_wire_xla"
    pipe.run_until_exhausted(timeout=60.0)
    top, probs, label = (np.concatenate([seen[k][i] for k in sorted(seen)])
                         for i in range(3))
    ref = xgbmc_ref.probabilities(forest, data)
    assert probs.shape == (B * blocks, CLASSES)
    assert np.abs(probs - ref).max() < ATOL
    assert np.abs(top - ref.max(axis=1)).max() < ATOL
    assert (label == ref.argmax(axis=1)).all()
    # the pipeline counts what each dispatch brought back: the triple
    snap = pipe.metrics.struct_snapshot()["counters"]
    assert snap["readback_bytes"] == B * blocks * (4 + 4 * CLASSES + 4)
    t = pipe._state
    jax.block_until_ready(t.values)
    vals = np.asarray(t.values)
    for k in range(keys):
        kh = int(t.hash_keys(np.array([k]))[0])
        slot = int(np.flatnonzero(t._occ & (t._keys == kh))[0])
        mine = data[:, 0] == k
        assert vals[slot, COL_COUNT] == mine.sum()
        assert vals[slot, COL_SUM] == pytest.approx(top[mine].sum(), rel=1e-5)


def _first_segment(text, body):
    head = '<Segment id="1"><True /><MiningModel'
    assert text.count(head) == 1
    return text.replace(head, f'<Segment id="1">{body}<MiningModel')


@pytest.mark.parametrize("form", [
    "another_link", "a_table_over_two_fields", "a_guarded_segment",
    "fewer_tables_than_sums", "uneven_classes"])
def test_other_chains_stay_off_the_rank_wire(text, X, form):
    """Each planted form is a document the f32 path still scores, and the
    rank wire refuses: another link, a table that reads a second field, a
    class segment whose predicate is not ``<True/>``, one table fewer
    than there are sums, and a class with one tree fewer than the rest
    (the program sums a class over an equal group of trees)."""
    if form == "another_link":
        doc = text.replace('normalizationMethod="softmax"',
                           'normalizationMethod="simplemax"')
    elif form == "a_table_over_two_fields":
        doc = text.replace(
            '<NumericPredictor name="xgbValue(0)" coefficient="1.0" />',
            '<NumericPredictor name="xgbValue(0)" coefficient="1.0" />'
            '<NumericPredictor name="f1" coefficient="0.5" />').replace(
            '<MiningField name="xgbValue(0)" />',
            '<MiningField name="xgbValue(0)" /><MiningField name="f1" />')
    elif form == "a_guarded_segment":
        doc = _first_segment(
            text, '<SimplePredicate field="f1" operator="lessThan" '
            'value="9" />')
    elif form == "fewer_tables_than_sums":
        last = text.rindex("<RegressionTable")
        doc = text[:last] + text[text.index("</RegressionTable>", last)
                                 + len("</RegressionTable>"):]
    else:
        first_tree = text.index('<Segment id="1"><True /><TreeModel')
        end = text.index("</Segment>\n", first_tree) + len("</Segment>\n")
        doc = text[:first_tree] + text[end:]
    assert doc != text
    cm = compile_pmml(parse_pmml(doc))
    assert cm.quantized_scorer() is None
    assert len(cm.score_dense(X[:4])) == 4
