"""Layout-catalogue byte-parity suite (compile/layouts.py, ISSUE 11).

Every catalogue variant — breadth-first SoA split order, uint8/uint16
threshold-rank wire packing, the Pallas multi-tree megakernel — must
score BYTE-IDENTICALLY to the reference packing across NaN, explicit
missing masks, ±inf cells, and mining-schema
``missingValueReplacement`` inputs (the test_fused_encode.py pattern),
including interpret-mode Pallas. The variants change memory layout,
never math: BFS permutes the reduced S axis (integer/exact-f32 sums),
the wire pack round-trips ranks exactly, the megakernel accumulates
groups in the same ascending order as the grid."""

import numpy as np
import pytest

from assets.generate import gen_gbm
from flink_jpmml_tpu.compile import layouts
from flink_jpmml_tpu.compile.gtrees import pack_general
from flink_jpmml_tpu.compile.qtrees import QuantizedWire, build_quantized_scorer
from flink_jpmml_tpu.pmml import parse_pmml, parse_pmml_file

from test_qtrees import _forest_xml
from test_qtrees_chain import N_FEATURES, chain_pmml, leafwise_forest


def _doc(tmp_path, **kw):
    return parse_pmml_file(gen_gbm(str(tmp_path), **kw))


def _adversarial_X(rng, n, f, missing_rate=0.25):
    """The satellite's input grid: NaN, ±inf, and ordinary values."""
    X = rng.normal(0.0, 1.5, size=(n, f)).astype(np.float32)
    X[rng.random(size=X.shape) < missing_rate] = np.nan
    X[0, 0] = np.inf
    X[1, f - 1] = -np.inf
    return X


def stump_forest_xml(n_a=300, n_b=5):
    """A sum forest of depth-1 stumps with skewed cut cardinality:
    feature ``a`` carries ``n_a`` distinct thresholds (>254 → uint16
    wire), feature ``b`` only ``n_b`` — the mixed-width shape the wire
    pack exists for."""
    segs = []
    i = 0
    for field, n in (("a", n_a), ("b", n_b)):
        for k in range(n):
            thr = round(-3.0 + 6.0 * (k + 1) / (n + 1), 6)
            i += 1
            segs.append(f"""
      <Segment><True/>
        <TreeModel functionName="regression"
                   missingValueStrategy="defaultChild"
                   splitCharacteristic="binarySplit">
          <MiningSchema><MiningField name="y" usageType="target"/>
            <MiningField name="a"/><MiningField name="b"/></MiningSchema>
          <Node id="r" defaultChild="l"><True/>
            <Node id="l" score="{0.01 * i}">
              <SimplePredicate field="{field}" operator="lessOrEqual"
                               value="{thr}"/></Node>
            <Node id="g" score="{-0.01 * i}">
              <SimplePredicate field="{field}" operator="greaterThan"
                               value="{thr}"/></Node>
          </Node>
        </TreeModel>
      </Segment>""")
    return f"""<PMML xmlns="http://www.dmg.org/PMML-4_3" version="4.3">
  <Header/>
  <DataDictionary numberOfFields="3">
    <DataField name="a" optype="continuous" dataType="double"/>
    <DataField name="b" optype="continuous" dataType="double"/>
    <DataField name="y" optype="continuous" dataType="double"/>
  </DataDictionary>
  <MiningModel functionName="regression">
    <MiningSchema><MiningField name="y" usageType="target"/>
      <MiningField name="a"/><MiningField name="b"/></MiningSchema>
    <Segmentation multipleModelMethod="sum">{''.join(segs)}
    </Segmentation>
  </MiningModel></PMML>"""


_REPL_XML = """<PMML xmlns="http://www.dmg.org/PMML-4_3" version="4.3">
  <Header/>
  <DataDictionary numberOfFields="3">
    <DataField name="a" optype="continuous" dataType="double"/>
    <DataField name="b" optype="continuous" dataType="double"/>
    <DataField name="y" optype="continuous" dataType="double"/>
  </DataDictionary>
  <TreeModel functionName="regression" missingValueStrategy="defaultChild"
             splitCharacteristic="binarySplit">
    <MiningSchema>
      <MiningField name="y" usageType="target"/>
      <MiningField name="a" missingValueReplacement="0.25"/>
      <MiningField name="b"/>
    </MiningSchema>
    <Node id="0" defaultChild="1"><True/>
      <Node id="1" defaultChild="3">
        <SimplePredicate field="a" operator="lessThan" value="0.1"/>
        <Node id="3" score="1.5">
          <SimplePredicate field="b" operator="lessOrEqual" value="-0.2"/>
        </Node>
        <Node id="4" score="-2.0">
          <SimplePredicate field="b" operator="greaterThan" value="-0.2"/>
        </Node>
      </Node>
      <Node id="2" score="3.0">
        <SimplePredicate field="a" operator="greaterOrEqual" value="0.1"/>
      </Node>
    </Node>
  </TreeModel></PMML>"""


class TestBfsSplitOrder:
    def test_order_is_descending_reach(self):
        # a 3-split depth-2 tree: root reaches 4 leaves, each child 2
        P = np.zeros((1, 3, 4), np.int8)
        P[0, 2] = [1, 1, -1, -1]     # root (slot 2 on purpose)
        P[0, 0] = [1, -1, 0, 0]      # left child
        P[0, 1] = [0, 0, 1, -1]      # right child
        perm = layouts.bfs_split_order(P)
        assert perm[0].tolist() == [2, 0, 1]

    def test_xla_bfs_bit_exact(self, tmp_path):
        # the BFS order permutes the path form's splits: leaf-wise trees
        # deeper than a heap takes. A shallow complete forest runs the
        # complete form, breadth-first already, and has no bfs variant
        shallow = build_quantized_scorer(
            _doc(tmp_path, n_trees=15, depth=4, n_features=6),
            batch_size=64, backend="xla")
        assert shallow.twin_depth == 4
        assert shallow.build_variant("bfs") is None
        doc = parse_pmml(chain_pmml(leafwise_forest()))
        q_ref = build_quantized_scorer(doc, batch_size=64, backend="xla")
        q = build_quantized_scorer(doc, batch_size=64, backend="xla")
        assert q.twin_depth == 0
        built = q.build_variant("bfs")
        assert built is not None
        q.adopt_variant(built, "bfs")
        assert q.layout == "bfs"
        rng = np.random.default_rng(0)
        for n in (64, 64 - 9, 2 * 64 + 7):
            X = _adversarial_X(rng, n, N_FEATURES)
            ref = np.asarray(
                q_ref.predict_wire(q_ref.wire.encode(X)), np.float32
            )
            got = np.asarray(q.predict_wire(q.wire.encode(X)), np.float32)
            np.testing.assert_array_equal(got, ref)

    def test_missing_value_replacement_bit_exact(self):
        doc = parse_pmml(_REPL_XML)
        q_ref = build_quantized_scorer(doc, batch_size=8)
        q = build_quantized_scorer(doc, batch_size=8)
        q.adopt_variant(q.build_variant("bfs"), "bfs")
        X = np.array(
            [[np.nan, -0.5], [np.nan, 0.5], [0.0, np.nan], [2.0, -1.0]],
            np.float32,
        )
        np.testing.assert_array_equal(
            np.asarray(q.predict_wire(q.wire.encode(X)), np.float32),
            np.asarray(q_ref.predict_wire(q_ref.wire.encode(X)), np.float32),
        )


class TestWirePack:
    def test_plan_none_for_uint8_wire(self, tmp_path):
        doc = _doc(tmp_path, n_trees=10, depth=3, n_features=4)
        q = build_quantized_scorer(doc, batch_size=32)
        assert q.wire.dtype is np.uint8
        assert layouts.plan_wire_pack(q.wire) is None

    def test_plan_none_when_nothing_fits_uint8(self):
        cuts = tuple(
            np.linspace(-1, 1, 300).astype(np.float32) for _ in range(2)
        )
        wire = QuantizedWire(
            fields=("a", "b"), cuts=cuts, dtype=np.uint16, sentinel=65535,
            repl=np.zeros((2,), np.float32),
            has_repl=np.zeros((2,), bool),
        )
        assert layouts.plan_wire_pack(wire) is None

    def test_pack_roundtrip_exact(self):
        cuts = (
            np.linspace(-1, 1, 300).astype(np.float32),
            np.linspace(-1, 1, 5).astype(np.float32),
        )
        wire = QuantizedWire(
            fields=("a", "b"), cuts=cuts, dtype=np.uint16, sentinel=65535,
            repl=np.zeros((2,), np.float32),
            has_repl=np.zeros((2,), bool),
        )
        wp = layouts.plan_wire_pack(wire)
        assert wp is not None and wp.width == 3  # 2 + 1 bytes
        rng = np.random.default_rng(1)
        codes = np.stack(
            [
                rng.integers(0, 301, size=64).astype(np.uint16),
                rng.integers(0, 6, size=64).astype(np.uint16),
            ],
            axis=1,
        )
        codes[0] = [65535, 65535]  # the sentinel survives both widths
        codes[1, 0] = 255  # a rank that collides with uint8's marker
        np.testing.assert_array_equal(
            wp.unpack_host(wp.pack(codes)), codes.astype(np.int64)
        )

    def test_scoring_bit_exact_and_fewer_bytes(self):
        doc = parse_pmml(stump_forest_xml())
        q_ref = build_quantized_scorer(doc, batch_size=32, backend="xla")
        assert q_ref.wire.dtype is np.uint16
        rng = np.random.default_rng(2)
        X = _adversarial_X(rng, 32, 2)
        ref = np.asarray(q_ref.predict_wire(q_ref.wire.encode(X)), np.float32)
        for lay in ("wirepack", "bfs_wirepack"):
            q = build_quantized_scorer(doc, batch_size=32, backend="xla")
            built = q.build_variant(lay)
            assert built is not None, lay
            q.adopt_variant(built, lay)
            got = np.asarray(q.predict_wire(q.wire.encode(X)), np.float32)
            np.testing.assert_array_equal(got, ref)
            # the point of the layout: fewer staged bytes than the
            # all-uint16 wire (3 vs 4 here)
            assert q.staged_bytes_per_record < q_ref.staged_bytes_per_record

    def test_odd_batches_through_pad_wire(self):
        doc = parse_pmml(stump_forest_xml())
        q_ref = build_quantized_scorer(doc, batch_size=32, backend="xla")
        q = build_quantized_scorer(doc, batch_size=32, backend="xla")
        q.adopt_variant(q.build_variant("wirepack"), "wirepack")
        rng = np.random.default_rng(3)
        for n in (20, 32, 77):
            X = _adversarial_X(rng, n, 2)
            ref = [p.score.value for p in q_ref.score(X)]
            got = [p.score.value for p in q.score(X)]
            assert got == ref

    def test_dispatch_helper_accounts_packed_bytes(self):
        from flink_jpmml_tpu.runtime.pipeline import dispatch_quantized
        from flink_jpmml_tpu.utils.metrics import MetricsRegistry

        doc = parse_pmml(stump_forest_xml())
        q = build_quantized_scorer(doc, batch_size=32, backend="xla")
        q.adopt_variant(q.build_variant("wirepack"), "wirepack")
        rng = np.random.default_rng(4)
        X = rng.normal(size=(32, 2)).astype(np.float32)
        m = MetricsRegistry()
        dispatch_quantized(q, X, metrics=m)
        # 3 packed bytes per record, not 4 uint16 wire bytes
        assert m.counter("h2d_bytes").get() == 32 * 3


class TestPallasMegakernel:
    def _pallas(self, doc, batch, **kw):
        q = build_quantized_scorer(
            doc, batch_size=batch, backend="pallas", pallas_interpret=True,
            **kw,
        )
        assert q is not None and q.backend == "pallas"
        return q

    @pytest.mark.parametrize("lay", ["mega", "bfs", "mega_bfs"])
    def test_regression_bit_exact(self, tmp_path, lay):
        doc = _doc(tmp_path, n_trees=13, depth=3, n_features=4)
        B = 32
        q_ref = self._pallas(doc, B)
        q = self._pallas(doc, B)
        built = q.build_variant(lay)
        assert built is not None, lay
        q.adopt_variant(built, lay)
        rng = np.random.default_rng(5)
        for n in (B, 2 * B):  # 2*B exercises the scan (K > 1) path too
            X = _adversarial_X(rng, n, 4, missing_rate=0.2)
            Xq = q.wire.encode(X)
            np.testing.assert_array_equal(
                np.asarray(q.predict_wire(Xq), np.float32),
                np.asarray(q_ref.predict_wire(Xq), np.float32),
            )

    @pytest.mark.parametrize(
        "method,weighted,n_trees",
        [
            ("majorityVote", False, 8),
            # non-integer vote tables: f32 sums are NOT association-
            # free here, so this pins the megakernel's accumulation
            # order against the grid kernel (caught live: acc+hi+lo
            # drifted 1 ULP from acc+(hi+lo))
            ("weightedMajorityVote", True, 48),
        ],
    )
    def test_classification_votes_bit_exact(self, method, weighted, n_trees):
        doc = parse_pmml(
            _forest_xml(method, weighted=weighted, n_trees=n_trees)
        )
        B = 32
        q_ref = self._pallas(doc, B)
        assert q_ref.is_classification
        q = self._pallas(doc, B)
        q.adopt_variant(q.build_variant("mega"), "mega")
        rng = np.random.default_rng(6)
        X = _adversarial_X(rng, B, 4, missing_rate=0.2)
        Xq = q.wire.encode(X)
        rv, rp, rl = q_ref.predict_wire(Xq)
        mv, mp, ml = q.predict_wire(Xq)
        np.testing.assert_array_equal(np.asarray(ml), np.asarray(rl))
        np.testing.assert_array_equal(np.asarray(mp), np.asarray(rp))
        np.testing.assert_array_equal(np.asarray(mv), np.asarray(rv))

    def test_fused_encode_composes_with_mega(self, tmp_path):
        # the fused featurize stage rides the variant's params: one
        # dispatch covers encode+pad+score through the megakernel too
        doc = _doc(tmp_path, n_trees=13, depth=3, n_features=4)
        B = 32
        q = self._pallas(doc, B)
        q.adopt_variant(q.build_variant("mega"), "mega")
        assert q.supports_fused
        rng = np.random.default_rng(7)
        X = _adversarial_X(rng, B, 4, missing_rate=0.15)
        host = np.asarray(q.predict_wire(q.wire.encode(X)), np.float32)
        fused = np.asarray(q.predict_fused(X), np.float32)
        np.testing.assert_array_equal(fused, host)

    def test_wirepack_ineligible_on_pallas(self, tmp_path):
        doc = _doc(tmp_path, n_trees=10, depth=3, n_features=4)
        q = self._pallas(doc, 32)
        assert q.build_variant("wirepack") is None


class TestGtreesBfsLayout:
    def test_bfs_order_levels(self):
        # pre-order rows of a depth-2 binary tree: 0,(1,(2,3)),(4,(5,6))
        children = [[1, 4], [2, 3], [], [], [5, 6], [], []]
        assert layouts.bfs_order(children) == [0, 1, 4, 2, 3, 5, 6]

    def test_pack_general_rows_are_breadth_first(self):
        from flink_jpmml_tpu.compile.common import LowerCtx, build_codecs
        from flink_jpmml_tpu.utils.config import CompileConfig

        doc = parse_pmml(_REPL_XML)
        model = doc.model
        ctx = LowerCtx(
            field_index={f: i for i, f in enumerate(doc.active_fields)},
            codecs=build_codecs(doc.data_dictionary),
            config=CompileConfig(),
        )
        params, meta = pack_general([model], ctx)
        # root at 0; every parent index precedes its children (BFS)
        child_idx = params["child_idx"][0]
        is_leaf = params["is_leaf"][0]
        for ni in range(meta["N"]):
            if is_leaf[ni]:
                continue
            for c in child_idx[ni]:
                if c != ni:  # self-loops pad empty child slots
                    assert c > ni
