"""Quantized rank-wire fast path (qtrees.py) vs the f32 path and oracle.

The wire must be *bit-exact* on split decisions (integer rank compares
reproduce the float compares) — only the final leaf-value contraction uses
a bf16 hi+lo split, so values match the f32 path to ~1e-4 relative.
"""

import tempfile
import xml.etree.ElementTree as ET

import numpy as np
import pytest

from assets.generate import gen_gbm
from flink_jpmml_tpu.compile import compile_pmml
from flink_jpmml_tpu.compile.qtrees import build_quantized_scorer
from flink_jpmml_tpu.pmml import parse_pmml, parse_pmml_file
from flink_jpmml_tpu.pmml.interp import evaluate


def _gbm(tmp_path, **kw):
    path = gen_gbm(str(tmp_path), n_trees=kw.pop("n_trees", 40),
                   depth=kw.pop("depth", 4), n_features=kw.pop("n_features", 8),
                   **kw)
    return parse_pmml_file(path)


def _rand_X(rng, n, F, missing_rate=0.0):
    X = rng.normal(0.0, 1.5, size=(n, F)).astype(np.float32)
    if missing_rate:
        X[rng.random(size=X.shape) < missing_rate] = np.nan
    return X


def _parity(doc, X, rtol=1e-4, atol=1e-5):
    cm = compile_pmml(doc)
    q = cm.quantized_scorer()
    assert q is not None
    M = np.isnan(X)
    Xf = np.nan_to_num(X, nan=0.0)
    ref = np.asarray(cm.predict(Xf, M).value, np.float32)
    got = np.asarray(q.predict_wire(q.wire.encode(X)), np.float32)
    np.testing.assert_allclose(got, ref, rtol=rtol, atol=atol)
    return cm, q


class TestEligibility:
    def test_hist_gbm_gets_u8_wire(self, tmp_path):
        doc = _gbm(tmp_path)
        q = build_quantized_scorer(doc)
        assert q is not None
        assert q.wire.dtype is np.uint8
        assert q.wire.bytes_per_record == 8  # 8 features x u8

    def test_continuous_thresholds_still_eligible(self, tmp_path):
        # 40 trees x 15 splits over 8 features ≈ 75 cuts/feature < 254
        doc = _gbm(tmp_path, hist_bins=None)
        q = build_quantized_scorer(doc)
        assert q is not None and q.wire.dtype is np.uint8

    def test_u16_fallback_when_over_254_cuts(self, tmp_path):
        # 300 deep trees on 2 features → >254 distinct cuts per feature
        doc = _gbm(tmp_path, n_trees=300, depth=5, n_features=2,
                   hist_bins=None)
        q = build_quantized_scorer(doc)
        assert q is not None
        assert q.wire.dtype is np.uint16
        rng = np.random.default_rng(3)
        _parity(doc, _rand_X(rng, 64, 2, missing_rate=0.1))

    def test_halting_strategy_probe_returns_none(self):
        # missingValueStrategy=lastPrediction needs the iterative f32
        # backend; the probe must degrade to None, never raise (a raise
        # here used to crash StaticScorer/DynamicScorer pipelines)
        xml = """<PMML xmlns="http://www.dmg.org/PMML-4_3" version="4.3">
          <Header/>
          <DataDictionary numberOfFields="2">
            <DataField name="a" optype="continuous" dataType="double"/>
            <DataField name="y" optype="continuous" dataType="double"/>
          </DataDictionary>
          <MiningModel functionName="regression">
            <MiningSchema>
              <MiningField name="y" usageType="target"/>
              <MiningField name="a"/>
            </MiningSchema>
            <Segmentation multipleModelMethod="sum">
              <Segment><True/>
                <TreeModel functionName="regression"
                           missingValueStrategy="lastPrediction">
                  <MiningSchema>
                    <MiningField name="y" usageType="target"/>
                    <MiningField name="a"/>
                  </MiningSchema>
                  <Node score="0.5"><True/>
                    <Node score="1.0">
                      <SimplePredicate field="a" operator="lessThan" value="0"/>
                    </Node>
                    <Node score="2.0">
                      <SimplePredicate field="a" operator="greaterOrEqual" value="0"/>
                    </Node>
                  </Node>
                </TreeModel>
              </Segment>
            </Segmentation>
          </MiningModel></PMML>"""
        doc = parse_pmml(xml)
        assert build_quantized_scorer(doc) is None
        cm = compile_pmml(doc)
        assert cm.quantized_scorer() is None  # outside the contract
        # and the f32 path still scores it (incl. the halt semantics)
        [pred] = cm.score_records([{"a": 1.0}])
        assert pred.score.value == pytest.approx(2.0)
        [pred] = cm.score_records([{}])
        assert pred.score.value == pytest.approx(0.5)

    def test_probe_that_raises_propagates(self, tmp_path, monkeypatch):
        # a document outside the fast path's contract returns None; a
        # probe that RAISES is a defect and must not become a silent
        # f32 scorer, on any backend
        from flink_jpmml_tpu.compile import qtrees

        def boom(*a, **kw):
            raise RuntimeError("rank-wire build broke")

        monkeypatch.setattr(qtrees, "build_quantized_scorer", boom)
        cm = compile_pmml(
            _gbm(tmp_path, n_trees=5, depth=3, n_features=4), batch_size=32
        )
        with pytest.raises(RuntimeError, match="rank-wire build broke"):
            cm.quantized_scorer()

    def test_classification_not_eligible(self):
        xml = """<PMML xmlns="http://www.dmg.org/PMML-4_3" version="4.3">
          <Header/>
          <DataDictionary numberOfFields="2">
            <DataField name="a" optype="continuous" dataType="double"/>
            <DataField name="y" optype="categorical" dataType="string">
              <Value value="p"/><Value value="q"/></DataField>
          </DataDictionary>
          <TreeModel functionName="classification" splitCharacteristic="binarySplit">
            <MiningSchema>
              <MiningField name="y" usageType="target"/>
              <MiningField name="a"/>
            </MiningSchema>
            <Node id="0"><True/>
              <Node id="1" score="p"><SimplePredicate field="a" operator="lessThan" value="0"/></Node>
              <Node id="2" score="q"><SimplePredicate field="a" operator="greaterOrEqual" value="0"/></Node>
            </Node>
          </TreeModel></PMML>"""
        assert build_quantized_scorer(parse_pmml(xml)) is None


class TestParity:
    def test_clean_batch_matches_f32_path(self, tmp_path):
        doc = _gbm(tmp_path, n_trees=60, depth=6, n_features=16)
        rng = np.random.default_rng(0)
        _parity(doc, _rand_X(rng, 256, 16))

    def test_missing_values_follow_default_child(self, tmp_path):
        doc = _gbm(tmp_path)
        rng = np.random.default_rng(1)
        _parity(doc, _rand_X(rng, 256, 8, missing_rate=0.25))

    def test_values_on_exact_thresholds(self, tmp_path):
        # records sitting exactly on cut values — the strict/inclusive
        # boundary handling must match the float comparisons bit-for-bit
        doc = _gbm(tmp_path, n_trees=30)
        cm = compile_pmml(doc)
        q = cm.quantized_scorer()
        cuts = np.concatenate([c for c in q.wire.cuts if len(c)])
        rng = np.random.default_rng(2)
        X = rng.choice(cuts, size=(512, 8)).astype(np.float32)
        M = np.zeros(X.shape, bool)
        ref = np.asarray(cm.predict(X, M).value, np.float32)
        got = np.asarray(q.predict_wire(q.wire.encode(X)), np.float32)
        np.testing.assert_allclose(got, ref, rtol=1e-4, atol=1e-5)

    def test_matches_oracle_interpreter(self, tmp_path):
        doc = _gbm(tmp_path, n_trees=12, depth=3, n_features=4)
        q = build_quantized_scorer(doc)
        rng = np.random.default_rng(4)
        X = _rand_X(rng, 16, 4, missing_rate=0.2)
        got = np.asarray(q.predict_wire(q.wire.encode(X)), np.float32)
        fields = doc.active_fields
        for i in range(X.shape[0]):
            rec = {
                f: float(X[i, j])
                for j, f in enumerate(fields)
                if not np.isnan(X[i, j])
            }
            exp = evaluate(doc, rec)
            np.testing.assert_allclose(
                got[i], float(exp.value), rtol=1e-4, atol=1e-5
            )

    def test_all_four_operators(self):
        # one tree per comparison operator, summed
        def tree(op, thr):
            return f"""<Segment><True/>
              <TreeModel functionName="regression" missingValueStrategy="defaultChild" splitCharacteristic="binarySplit">
                <MiningSchema><MiningField name="y" usageType="target"/><MiningField name="a"/></MiningSchema>
                <Node id="0" defaultChild="1"><True/>
                  <Node id="1" score="1.5"><SimplePredicate field="a" operator="{op}" value="{thr}"/></Node>
                  <Node id="2" score="-2.5"><True/></Node>
                </Node>
              </TreeModel></Segment>"""

        xml = f"""<PMML xmlns="http://www.dmg.org/PMML-4_3" version="4.3">
          <Header/>
          <DataDictionary numberOfFields="2">
            <DataField name="a" optype="continuous" dataType="double"/>
            <DataField name="y" optype="continuous" dataType="double"/>
          </DataDictionary>
          <MiningModel functionName="regression">
            <MiningSchema>
              <MiningField name="y" usageType="target"/>
              <MiningField name="a"/>
            </MiningSchema>
            <Segmentation multipleModelMethod="sum">
              {tree('lessThan', 0.5)}{tree('lessOrEqual', 0.5)}
              {tree('greaterThan', -0.25)}{tree('greaterOrEqual', -0.25)}
            </Segmentation>
          </MiningModel></PMML>"""
        doc = parse_pmml(xml)
        X = np.array(
            [[0.5], [0.49999997], [0.50000006], [-0.25], [-0.2500001],
             [-0.24999999], [0.0], [np.nan]],
            np.float32,
        )
        _parity(doc, X)

    def test_weighted_average_and_average(self, tmp_path):
        for method, wattr in (("average", ""), ("weightedAverage", "")):
            doc = _gbm(tmp_path, n_trees=10, name=f"m_{method}.pmml")
            # rewrite the segmentation method (+ weights for weightedAverage)
            import xml.etree.ElementTree as ET  # noqa: PLC0415

            ns = "http://www.dmg.org/PMML-4_3"
            t = ET.parse(f"{tmp_path}/m_{method}.pmml")
            seg = t.getroot().find(f".//{{{ns}}}Segmentation")
            seg.set("multipleModelMethod", method)
            if method == "weightedAverage":
                for k, s in enumerate(seg.findall(f"{{{ns}}}Segment")):
                    s.set("weight", str(0.5 + 0.1 * k))
            out = f"{tmp_path}/m2_{method}.pmml"
            t.write(out)
            doc = parse_pmml_file(out)
            rng = np.random.default_rng(5)
            _parity(doc, _rand_X(rng, 128, 8, missing_rate=0.1))


class TestWireFormat:
    def test_sentinel_reserved(self, tmp_path):
        doc = _gbm(tmp_path)
        q = build_quantized_scorer(doc)
        X = _rand_X(np.random.default_rng(6), 64, 8, missing_rate=0.3)
        Xq = q.wire.encode(X)
        assert Xq[np.isnan(X)].min() == q.wire.sentinel
        assert (Xq[~np.isnan(X)] < q.wire.sentinel).all()

    def test_explicit_mask_marks_missing(self, tmp_path):
        doc = _gbm(tmp_path)
        q = build_quantized_scorer(doc)
        X = np.zeros((4, 8), np.float32)
        M = np.zeros((4, 8), bool)
        M[0, 0] = True
        Xq = q.wire.encode(X, M)
        assert Xq[0, 0] == q.wire.sentinel and Xq[1, 0] != q.wire.sentinel

    def test_score_decodes_predictions(self, tmp_path):
        doc = _gbm(tmp_path)
        cm = compile_pmml(doc)
        q = cm.quantized_scorer()
        X = _rand_X(np.random.default_rng(7), 10, 8)
        preds = q.score(X)
        assert len(preds) == 10
        ref = cm.score_dense(X)
        for a, b in zip(preds, ref):
            assert abs(a.score.value - b.score.value) < 1e-3


class TestNativeBucketizer:
    """The lockstep pow2 kernel (the ONE native encode path) vs the numpy
    searchsorted reference — the same parity the fallback in
    QuantizedWire.encode guarantees."""

    @staticmethod
    def _numpy_ref(w, X, M=None):
        Xr = np.asarray(X, np.float32)
        miss = np.isnan(Xr)
        if M is not None:
            miss = miss | M
        if w.has_repl.any():
            use = miss & w.has_repl[None, :]
            Xr = np.where(use, w.repl[None, :], Xr)
            miss = miss & ~w.has_repl[None, :]
        ref = np.empty(Xr.shape, w.dtype)
        for j, cuts in enumerate(w.cuts):
            ref[:, j] = np.searchsorted(cuts, Xr[:, j], side="left")
        ref[miss] = w.sentinel
        return ref

    def test_native_matches_numpy(self, tmp_path):
        from flink_jpmml_tpu.runtime import native

        if not native.available():
            pytest.skip(f"native plane unavailable: {native.build_error()}")
        doc = _gbm(tmp_path, n_trees=30, depth=5, n_features=12)
        q = build_quantized_scorer(doc)
        w = q.wire
        rng = np.random.default_rng(8)
        X = _rand_X(rng, 4096, 12, missing_rate=0.15)
        # edge rows: exact cut hits, +/-inf, all-NaN
        X[0, :] = [w.cuts[j][0] if len(w.cuts[j]) else 0.0 for j in range(12)]
        X[1, :] = np.inf
        X[2, :] = -np.inf
        X[3, :] = np.nan
        padded, L = w._pow2_tables()
        assert L & (L - 1) == 0  # power of two
        got = native.bucketize_pow2(
            X, padded, L, w.repl, w.has_repl.astype(np.uint8), w.dtype
        )
        np.testing.assert_array_equal(got, self._numpy_ref(w, X))

    def test_native_randomized_table_shapes(self, tmp_path):
        """Sweep ensemble shapes so L covers several powers of two."""
        from flink_jpmml_tpu.runtime import native

        if not native.available():
            pytest.skip("native plane unavailable")
        rng = np.random.default_rng(11)
        for trees, depth, f in ((1, 2, 3), (5, 3, 4), (60, 6, 6)):
            doc = _gbm(tmp_path, n_trees=trees, depth=depth, n_features=f)
            w = build_quantized_scorer(doc).wire
            X = _rand_X(rng, 512, f, missing_rate=0.2)
            padded, L = w._pow2_tables()
            got = native.bucketize_pow2(
                X, padded, L, w.repl, w.has_repl.astype(np.uint8), w.dtype
            )
            np.testing.assert_array_equal(got, self._numpy_ref(w, X))

    def test_skewed_tables_take_ragged_path(self):
        """One huge cut table among tiny ones: the pow2 dispatch bails
        (padding blowup) and the ragged kernel produces identical ranks."""
        from flink_jpmml_tpu.compile.qtrees import QuantizedWire
        from flink_jpmml_tpu.runtime import native

        if not native.available():
            pytest.skip("native plane unavailable")
        rng = np.random.default_rng(4)
        F = 8
        cuts = (np.sort(rng.normal(0, 5, size=900)).astype(np.float32),) + tuple(
            np.sort(rng.normal(0, 5, size=int(k))).astype(np.float32)
            for k in rng.integers(1, 4, size=F - 1)
        )
        w = QuantizedWire(
            fields=tuple(f"f{i}" for i in range(F)),
            cuts=cuts,
            dtype=np.uint16,
            sentinel=65535,
            repl=np.zeros(F, np.float32),
            has_repl=np.zeros(F, bool),
        )
        padded, L = w._pow2_tables()
        assert padded is None  # skew heuristic chose ragged
        X = rng.normal(0, 5, size=(2048, F)).astype(np.float32)
        X[0, 0] = np.nan
        got = w.encode(X)
        np.testing.assert_array_equal(got, self._numpy_ref(w, X))

    def test_native_mask_and_single_thread(self, tmp_path):
        from flink_jpmml_tpu.runtime import native

        if not native.available():
            pytest.skip("native plane unavailable")
        doc = _gbm(tmp_path)
        q = build_quantized_scorer(doc)
        w = q.wire
        X = np.zeros((8, 8), np.float32)
        M = np.zeros((8, 8), bool)
        M[2, 3] = True
        padded, L = w._pow2_tables()
        got = native.bucketize_pow2(
            X, padded, L, w.repl, w.has_repl.astype(np.uint8), w.dtype,
            mask=M, n_threads=1,
        )
        assert got[2, 3] == w.sentinel
        assert (got[0] != w.sentinel).all()
        np.testing.assert_array_equal(got, self._numpy_ref(w, X, M))


def _forest_xml(method="majorityVote", weighted=False, n_trees=7, seed=21):
    rng = np.random.default_rng(seed)
    segs = []
    for t in range(n_trees):
        w = f' weight="{0.5 + 0.25 * t}"' if weighted else ""
        f1, f2 = rng.integers(0, 4, size=2)
        t1, t2 = rng.normal(0, 1, size=2)
        labs = rng.choice(["p", "q", "r"], size=3)
        segs.append(f"""<Segment{w}><True/>
          <TreeModel functionName="classification" missingValueStrategy="defaultChild" splitCharacteristic="binarySplit">
            <MiningSchema><MiningField name="y" usageType="target"/>
              <MiningField name="f0"/><MiningField name="f1"/>
              <MiningField name="f2"/><MiningField name="f3"/></MiningSchema>
            <Node id="0" defaultChild="1"><True/>
              <Node id="1" defaultChild="3">
                <SimplePredicate field="f{f1}" operator="lessThan" value="{t1:.6f}"/>
                <Node id="3" score="{labs[0]}"><SimplePredicate field="f{f2}" operator="lessThan" value="{t2:.6f}"/></Node>
                <Node id="4" score="{labs[1]}"><SimplePredicate field="f{f2}" operator="greaterOrEqual" value="{t2:.6f}"/></Node>
              </Node>
              <Node id="2" score="{labs[2]}"><SimplePredicate field="f{f1}" operator="greaterOrEqual" value="{t1:.6f}"/></Node>
            </Node>
          </TreeModel></Segment>""")
    return f"""<PMML xmlns="http://www.dmg.org/PMML-4_3" version="4.3">
      <Header/>
      <DataDictionary numberOfFields="5">
        <DataField name="f0" optype="continuous" dataType="double"/>
        <DataField name="f1" optype="continuous" dataType="double"/>
        <DataField name="f2" optype="continuous" dataType="double"/>
        <DataField name="f3" optype="continuous" dataType="double"/>
        <DataField name="y" optype="categorical" dataType="string">
          <Value value="p"/><Value value="q"/><Value value="r"/></DataField>
      </DataDictionary>
      <MiningModel functionName="classification">
        <MiningSchema><MiningField name="y" usageType="target"/>
          <MiningField name="f0"/><MiningField name="f1"/>
          <MiningField name="f2"/><MiningField name="f3"/></MiningSchema>
        <Segmentation multipleModelMethod="{method}">{''.join(segs)}</Segmentation>
      </MiningModel></PMML>"""


class TestClassificationWire:
    def _parity_cls(self, xml, n=256, missing_rate=0.15, seed=5):
        doc = parse_pmml(xml)
        cm = compile_pmml(doc)
        q = cm.quantized_scorer()
        assert q is not None and q.is_classification
        rng = np.random.default_rng(seed)
        X = _rand_X(rng, n, 4, missing_rate=missing_rate)
        M = np.isnan(X)
        ref = cm.predict(np.nan_to_num(X, nan=0.0), M)
        got_v, got_p, got_l = q.predict_wire(q.wire.encode(X))
        np.testing.assert_array_equal(
            np.asarray(got_l), np.asarray(ref.label_idx)
        )
        np.testing.assert_allclose(
            np.asarray(got_p), np.asarray(ref.probs), rtol=1e-3, atol=1e-4
        )
        np.testing.assert_allclose(
            np.asarray(got_v), np.asarray(ref.value), rtol=1e-3, atol=1e-4
        )

    def test_majority_vote_forest(self):
        self._parity_cls(_forest_xml("majorityVote"))

    def test_weighted_majority_vote(self):
        self._parity_cls(_forest_xml("weightedMajorityVote", weighted=True))

    def test_scorer_decode_labels(self):
        doc = parse_pmml(_forest_xml("majorityVote"))
        q = build_quantized_scorer(doc)
        rng = np.random.default_rng(9)
        X = _rand_X(rng, 16, 4)
        preds = q.score(X)
        cm = compile_pmml(doc)
        exp = cm.score_dense(X)
        for a, b in zip(preds, exp):
            assert a.target.label == b.target.label
            assert abs(a.score.value - b.score.value) < 1e-3
