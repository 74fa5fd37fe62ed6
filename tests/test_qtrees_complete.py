"""The XLA twin's complete form (compile/qtrees.py): a forest of shallow
trees laid out as complete heaps (``complete_heap``), each tree's leaf
picked by a select tree over its split compares (``_select_tree``) in
place of the path-matrix contraction, where the shape says so
(``complete_depth``).

The forests here are drawn from a seed: pruned trees of depth at most 5
(a leaf above the deepest level pads its subtree), all four comparison
operators, the default child on either side, thresholds and records on
one grid (so that ``<`` and ``<=`` part on equal values), and missing
values. Every tree picks, in both forms, the same leaf value bit for
bit; each document's scores are held to a plain walk, to the f32 path,
and, for the benchmark's own generators, to ``benchmark/reference``."""

import os
import sys
import tempfile

import numpy as np
import pytest

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from lib import gbm as gbm_gen  # noqa: E402
from lib import xgbmc  # noqa: E402
from reference import gbm_ref, xgbmc_ref  # noqa: E402

from flink_jpmml_tpu.compile import compile_pmml, qtrees  # noqa: E402
from flink_jpmml_tpu.pmml import parse_pmml  # noqa: E402

from test_qtrees_chain import (  # noqa: E402
    chain_tree_pmml, leafwise_forest, vote_pmml)

F, DEPTH, CLASSES = 4, 5, 3
GRID = np.linspace(-1.5, 1.5, 13).astype(np.float32)
# the PMML operator of a left child, and its complement on the right
OPS = ("lessThan", "lessOrEqual", "greaterThan", "greaterOrEqual")
_NOT = {"lessThan": "greaterOrEqual", "lessOrEqual": "greaterThan",
        "greaterThan": "lessOrEqual", "greaterOrEqual": "lessThan"}


def random_tree(rng, depth=DEPTH, prune=0.2):
    """A tree as nested tuples: a leaf is its float value, a split
    ``(field, op, threshold, default_left, left, right)``. The root
    always splits, and one path reaches ``depth``."""
    def grow(d, deep):
        if d == depth or (d and not deep and rng.random() < prune):
            return float(np.float32(rng.normal(0.0, 0.5)))
        go_deep = deep and bool(rng.integers(2))
        return (int(rng.integers(F)), OPS[rng.integers(4)],
                float(rng.choice(GRID)), bool(rng.integers(2)),
                grow(d + 1, deep and go_deep),
                grow(d + 1, deep and not go_deep))

    return grow(0, True)


def walk(tree, x):
    while isinstance(tree, tuple):
        f, op, thr, dl, left, right = tree
        v = x[f]
        if np.isnan(v):
            go = dl
        else:
            go = {"lessThan": v < thr, "lessOrEqual": v <= thr,
                  "greaterThan": v > thr, "greaterOrEqual": v >= thr}[op]
        tree = left if go else right
    return tree


def margins(trees, X):
    return np.array([[walk(t, x) for t in trees] for x in X], np.float64)


def tree_xml(tree, pred, ids):
    nid = f"n{next(ids)}"
    if not isinstance(tree, tuple):
        return f'<Node id="{nid}" score="{tree!r}">{pred}</Node>'
    f, op, thr, dl, left, right = tree
    lp = f'<SimplePredicate field="f{f}" operator="{op}" value="{thr!r}"/>'
    rp = (f'<SimplePredicate field="f{f}" operator="{_NOT[op]}" '
          f'value="{thr!r}"/>')
    lx, rx = tree_xml(left, lp, ids), tree_xml(right, rp, ids)
    default = (lx if dl else rx).split('"')[1]
    return f'<Node id="{nid}" defaultChild="{default}">{pred}{lx}{rx}</Node>'


def _schema(target=False):
    return ("<MiningSchema>" + (
        '<MiningField name="y" usageType="target"/>' if target else "")
        + "".join(f'<MiningField name="f{i}"/>' for i in range(F))
        + "</MiningSchema>")


def sum_xml(trees, name=None):
    """A sum of regression trees; ``name`` makes it a chain's segment
    model, its sum the output field ``name``."""
    import itertools

    ids = itertools.count()
    segs = "".join(
        f'<Segment id="s{i}"><True/><TreeModel functionName="regression" '
        f'missingValueStrategy="defaultChild">{_schema()}'
        f'{tree_xml(t, "<True/>", ids)}</TreeModel></Segment>'
        for i, t in enumerate(trees))
    out = (f'<Output><OutputField name="{name}" feature="predictedValue"/>'
           "</Output>" if name else "")
    return (f'<MiningModel functionName="regression">{_schema()}{out}'
            f'<Segmentation multipleModelMethod="sum">{segs}</Segmentation>'
            "</MiningModel>")


def _doc(body, categories=()):
    fields = "".join(f'<DataField name="f{i}" optype="continuous" '
                     'dataType="double"/>' for i in range(F))
    if categories:
        fields += ('<DataField name="y" optype="categorical" '
                   'dataType="integer">' + "".join(
                       f'<Value value="{c}"/>' for c in categories)
                   + "</DataField>")
    return (f'<PMML version="4.3"><DataDictionary>{fields}</DataDictionary>'
            f"{body}</PMML>")


def chain_xml(groups, norm):
    """JPMML's classifier chain over one tree sum a group: a logit over
    one sum (the event category "1" first), or a softmax over one a
    class."""
    segs = "".join(f'<Segment id="c{k}"><True/>{sum_xml(g, f"m{k}")}'
                   "</Segment>" for k, g in enumerate(groups))
    if norm == "logit":
        tables = ('<RegressionTable intercept="-0.25" targetCategory="1">'
                  '<NumericPredictor name="m0" coefficient="1.5"/>'
                  '</RegressionTable><RegressionTable intercept="0.0" '
                  'targetCategory="0"/>')
        cats = ("1", "0")
    else:
        tables = "".join(
            f'<RegressionTable intercept="{0.125 * k}" targetCategory="{k}">'
            f'<NumericPredictor name="m{k}" coefficient="1.0"/>'
            "</RegressionTable>" for k in range(len(groups)))
        cats = tuple(str(k) for k in range(len(groups)))
    fields = "".join(f'<MiningField name="m{k}"/>' for k in range(len(groups)))
    return _doc(
        '<MiningModel functionName="classification">' + _schema(True)
        + '<Segmentation multipleModelMethod="modelChain">' + segs
        + '<Segment id="link"><True/><RegressionModel functionName='
        f'"classification" normalizationMethod="{norm}"><MiningSchema>'
        f'<MiningField name="y" usageType="target"/>{fields}</MiningSchema>'
        f"{tables}</RegressionModel></Segment></Segmentation></MiningModel>",
        cats)


def forest_doc(kind, seed, n_trees=8):
    """→ (PMML text, the plain walk's answer for records ``X``)."""
    rng = np.random.default_rng(seed)
    if kind == "softmax":
        groups = [[random_tree(rng) for _ in range(n_trees // 2)]
                  for _ in range(CLASSES)]

        def want(X):
            y = np.stack([margins(g, X).sum(axis=1) + 0.125 * k
                          for k, g in enumerate(groups)], axis=1)
            e = np.exp(y - y.max(axis=1, keepdims=True))
            return e / e.sum(axis=1, keepdims=True)

        return chain_xml(groups, "softmax"), want
    trees = [random_tree(rng) for _ in range(n_trees)]
    if kind == "logit":
        return chain_xml([trees], "logit"), lambda X: 1.0 / (
            1.0 + np.exp(-(-0.25 + 1.5 * margins(trees, X).sum(axis=1))))
    return _doc(sum_xml(trees)), lambda X: margins(trees, X).sum(axis=1)


def records(seed, n=200):
    """Records on the thresholds' grid and between, a missing value in
    about one field of six."""
    rng = np.random.default_rng(seed)
    X = np.where(rng.random((n, F)) < 0.5, rng.choice(GRID, (n, F)),
                 rng.uniform(-2.0, 2.0, (n, F))).astype(np.float32)
    X[rng.random((n, F)) < 1 / 6] = np.nan
    return X


def both_forms(text, monkeypatch):
    doc = parse_pmml(text)
    heap = qtrees.build_quantized_scorer(doc)
    with monkeypatch.context() as m:
        m.setattr(qtrees, "complete_depth", lambda *a: 0)
        path = qtrees.build_quantized_scorer(doc)
    return heap, path


def path_picks(q, Xq):
    """Each tree's leaf value ``[B, T]`` as the path form picks it: the
    leaf whose path sum meets its count, from the scorer's own tables."""
    p = {k: np.asarray(v) for k, v in q.params.items()}
    xv = Xq[:, p["feat"]]
    go = np.where(xv == q.wire.sentinel, p["dleft"], xv <= p["qthr"])
    acc = np.einsum("bts,tsl->btl", np.where(go, 1, -1),
                    p["P_i8"].astype(np.int64))
    hit = acc == p["count_i8"].astype(np.int64)[None]
    assert (hit.sum(axis=2) == 1).all()
    vals = p["vhi"].astype(np.float32) + p["vlo"].astype(np.float32)
    return vals[np.arange(vals.shape[0])[None, :], hit.argmax(axis=2)]


def heap_picks(q, Xq):
    """The same by the complete form's tables and its select tree."""
    p = {k: np.asarray(v) for k, v in q.params.items()}
    xv = Xq[:, p["heap_feat"]]
    go = np.where(xv == q.wire.sentinel, p["heap_dleft"],
                  xv <= p["heap_qthr"])
    return np.asarray(qtrees._select_tree(
        [go[:, n] for n in range(go.shape[1])], p["heap_vals"]))


def outputs(q, Xq):
    import jax

    return [np.asarray(a) for a in jax.tree_util.tree_leaves(
        q.predict_wire(Xq))]


@pytest.mark.parametrize("seed", [1, 2, 3])
@pytest.mark.parametrize("kind", ["sum", "logit", "softmax"])
def test_every_tree_picks_the_path_forms_leaf_bit_for_bit(
        kind, seed, monkeypatch):
    text, want = forest_doc(kind, seed)
    heap, path = both_forms(text, monkeypatch)
    assert heap.twin_depth == DEPTH and path.twin_depth == 0
    assert "P_i8" not in heap.params and "heap_vals" not in path.params
    X = records(seed)
    Xq = heap.wire.encode(X)
    assert (Xq == heap.wire.sentinel).any()
    assert heap_picks(heap, Xq).tobytes() == path_picks(path, Xq).tobytes()
    got, ref = outputs(heap, Xq), outputs(path, Xq)
    # within the model kind's limit (``benchmark/models``): a GBM's sum,
    # a logit chain's probability, a softmax chain's class probabilities
    ref_x = want(X)
    limit = {"sum": 5e-5 + 1e-4 * np.abs(ref_x), "logit": 2e-5,
             "softmax": 5e-5}[kind]
    assert (np.abs((got[1] if kind == "softmax" else got[0]) - ref_x)
            <= limit).all()
    for a, b in zip(got, ref):
        # the same leaves; only the order of the float32 sums may differ
        assert a.dtype == b.dtype and np.abs(
            a.astype(np.float64) - b).max() < 1e-6
    if kind == "softmax":
        assert (got[2] == ref[2]).all()


@pytest.mark.parametrize("seed", [4, 5])
def test_one_trees_value_is_the_path_forms_bit_for_bit(seed, monkeypatch):
    """A sum of one tree is that tree's leaf: both programs' scores are
    equal, bit for bit, every record, missing values included."""
    text, want = forest_doc("sum", seed, n_trees=1)
    heap, path = both_forms(text, monkeypatch)
    assert heap.twin_depth == DEPTH
    X = records(seed)
    a, b = outputs(heap, heap.wire.encode(X)), outputs(
        path, path.wire.encode(X))
    assert a[0].tobytes() == b[0].tobytes()
    ref = want(X)
    assert (np.abs(a[0] - ref) <= 5e-5 + 1e-4 * np.abs(ref)).all()


def test_a_vote_forest_takes_the_complete_form(monkeypatch):
    """A majority vote of shallow classifiers: each tree's class shares
    ride the heap's leaves; the label and shares are the path form's and
    the f32 path's."""
    shallow = [t for t in (leafwise_forest(seed=s, n_trees=1, n_leaves=8)[0]
                           for s in range(40)) if _lw_depth(t) <= 4][:5]
    assert len(shallow) == 5
    text = vote_pmml(shallow)
    heap, path = both_forms(text, monkeypatch)
    assert heap.twin_depth > 0 and path.twin_depth == 0
    X = np.random.default_rng(9).normal(0.0, 1.5, (128, 5)).astype(
        np.float32)
    X[::5, 2] = np.nan
    got, ref = outputs(heap, heap.wire.encode(X)), outputs(
        path, path.wire.encode(X))
    assert (got[2] == ref[2]).all()
    assert np.abs(got[1] - ref[1]).max() < 1e-6
    dense = compile_pmml(parse_pmml(text)).score_dense(X)
    assert [p.target.label for p in heap.decode(
        heap.predict_wire(heap.wire.encode(X)), 128)] == [
        p.target.label for p in dense]


def _lw_depth(tree):
    _, _, left, right, _ = tree

    def walk_(c):
        return 0 if c < 0 else 1 + max(walk_(left[c]), walk_(right[c]))

    return walk_(0)


@pytest.mark.parametrize("kind", ["gbm", "xgbmc"])
def test_the_benchmarks_forests_meet_their_references(kind):
    """The benchmark's own generators at a small size, complete trees of
    depth 6, through the complete form, against ``benchmark/reference``
    within the model kind's tolerance (``benchmark/models``: 1e-4
    relative and 5e-5 absolute for a GBM's score, 5e-5 for a class
    probability)."""
    with tempfile.TemporaryDirectory() as d:
        if kind == "gbm":
            g = gbm_gen.gen_arrays(2**31 + 46, 12, 6, 8, 32)
            text = open(gbm_gen.write_pmml(g, d)).read()
        else:
            g = xgbmc.gen_arrays(2**31 + 46, 4, 3, 6, 8, 4, 255, 0.3, 0.5)
            text = open(xgbmc.write_pmml(g, d)).read()
    q = compile_pmml(parse_pmml(text)).quantized_scorer()
    assert q.backend == "xla" and q.twin_depth == 6
    X = np.random.default_rng(46).normal(0.0, 1.5, (256, 8)).astype(
        np.float32)
    got = outputs(q, q.wire.encode(X))
    if kind == "gbm":
        ref = gbm_ref.scores(g, X)
        assert (np.abs(got[0] - ref) <= 5e-5 + 1e-4 * np.abs(ref)).all()
    else:
        ref = xgbmc_ref.probabilities(g, X)
        assert np.abs(got[1] - ref).max() < 5e-5
        assert (got[2] == ref.argmax(axis=1)).all()


def caterpillar_pmml():
    """One regression tree of 20 leaves and depth 10: ten splits down
    the left, nine of the ten right children split once more."""
    def node(d):
        if d == 10:
            return 1.0 * d
        right = (0, "lessOrEqual", 0.5, True, 0.25 * d, -0.25 * d) \
            if d < 9 else -1.0
        return (d % F, "lessOrEqual", float(GRID[d]), True, node(d + 1),
                right)

    return _doc(sum_xml([node(0)]))


@pytest.mark.parametrize("depth,S,L,want", [
    (6, 63, 64, 6),        # a complete depth-6 tree: 128 against 4,032
    (16, 254, 255, 0),     # leaf-wise, depth 16: 2**17 against 64,770
    (10, 19, 20, 0),       # 20 leaves at depth 10: 2,048 against 380
    (9, 511, 512, 0),      # past the unrolled select tree's depth
])
def test_the_form_is_read_from_the_shape(depth, S, L, want):
    assert qtrees.complete_depth(depth, S, L) == want


@pytest.mark.parametrize("doc,want", [
    ("complete6", 6), ("leafwise16", 0), ("caterpillar", 0), ("f32", None),
])
def test_the_gauge_says_the_form(doc, want):
    """A block pipeline sets ``qtrees_twin_depth`` once it adopts a twin
    scorer: the select tree's depth, or 0 for the path form; a pipeline
    off the rank wire sets nothing."""
    from flink_jpmml_tpu.runtime.block import BlockPipeline, FiniteBlockSource

    if doc == "leafwise16":
        # a leaf at each of 16 levels: 16 splits, 17 leaves
        text = chain_tree_pmml(16)
    elif doc == "caterpillar":
        text = caterpillar_pmml()
    else:
        with tempfile.TemporaryDirectory() as d:
            text = open(gbm_gen.write_pmml(
                gbm_gen.gen_arrays(7, 4, 6, 5, 16), d)).read()
    cm = compile_pmml(parse_pmml(text), batch_size=16)
    n = cm.field_space.arity
    data = np.random.default_rng(1).normal(size=(32, n)).astype(np.float32)
    pipe = BlockPipeline(FiniteBlockSource(data, block_size=16), cm,
                         lambda out, k, first: None, use_native=False,
                         in_flight=1, use_quantized=doc != "f32")
    pipe.run_until_exhausted(timeout=60.0)
    gauges = pipe.metrics.struct_snapshot()["gauges"]
    if want is None:
        assert pipe.backend == "f32" and "qtrees_twin_depth" not in gauges
    else:
        assert pipe.backend == "rank_wire_xla"
        assert gauges["qtrees_twin_depth"]["value"] == want


@pytest.mark.parametrize("n", [64, 100])
@pytest.mark.parametrize("kind", ["sum", "logit", "softmax"])
def test_the_scanned_complete_form_is_the_unscanned_bit_for_bit(
        kind, n, monkeypatch):
    """The budget of 8 records a step by the complete form's
    ``twin_bytes``: every output array equals the unscanned program's,
    bit for bit (a pad to whole steps where ``n`` is not a multiple)."""
    text, _ = forest_doc(kind, 7)
    doc = parse_pmml(text)
    whole = qtrees.build_quantized_scorer(doc)
    m = whole._meta
    monkeypatch.setattr(qtrees, "_device_budget", lambda: qtrees.twin_bytes(
        8, int(m["trees"]), int(m["splits"]), int(m["leaves"]),
        whole.twin_depth))
    stepped = qtrees.build_quantized_scorer(doc)
    assert whole.twin_depth == stepped.twin_depth == DEPTH
    assert stepped.twin_rows(n) == 8 and whole.twin_rows(n) == n
    Xq = whole.wire.encode(records(n, n))
    for a, b in zip(outputs(whole, Xq), outputs(stepped, Xq)):
        assert a.tobytes() == b.tobytes()


def test_twin_bytes_of_the_complete_form():
    T, S, L, D = 21000, 63, 64, 6
    per = T * (2 ** D - 1 + 4)
    tables = T * (6 * (2 ** D - 1) + 4 * 2 ** D)
    assert qtrees.twin_bytes(16, T, S, L, D) == 16 * per + tables
    # a 2**30 budget: steps of 512 records in the complete form, where
    # the path form's temporaries allow 256
    assert qtrees.twin_rows(65536, T, S, L, 2**30, D) == 512
    assert qtrees.twin_rows(65536, T, S, L, 2**30) == 256


def test_the_int8_rank_codes_are_the_gathers():
    """The chip's rank codes of a uint8 wire, every code 0 to 255 (the
    sentinel among them), equal ``Xq[:, feat]``."""
    import jax.numpy as jnp

    rng = np.random.default_rng(8)
    Xq = np.concatenate([np.arange(256, dtype=np.uint8)[:, None]] * 3
                        + [rng.integers(0, 256, (256, 2), dtype=np.uint8)],
                        axis=1)
    feat = rng.integers(0, 5, 40).astype(np.int32)
    got = np.asarray(qtrees._rank_codes_i8(jnp.asarray(Xq), jnp.asarray(feat)))
    assert got.dtype == np.uint8 and (got == Xq[:, feat]).all()
