"""The rank wire takes a GBM binary classifier as JPMML writes it: a
``modelChain`` whose first segment sums leaf-wise trees into one field
and whose second is a logit ``RegressionModel`` over it
(compile/qtrees.py ``_match_chain``), with trees of any depth the int8
path sum holds while their path matrices stay within a complete depth-10
tree's, and its XLA twin scans a batch in steps that keep its
temporaries under a share of the device's memory, bit for bit the
unscanned program.

The chain here is tiny: 6 trees of 15 leaves grown leaf-wise over 5
features, one of them a chain of 14 splits (deeper than the f32 path's
dense limit of 10)."""

import numpy as np
import pytest

from flink_jpmml_tpu.compile import compile_pmml, qtrees
from flink_jpmml_tpu.pmml import parse_pmml
from flink_jpmml_tpu.runtime.state import COL_COUNT, COL_SUM, StateSpec

N_FEATURES, N_TREES, N_LEAVES = 5, 6, 15
GRID = np.linspace(-2.0, 2.0, 41).astype(np.float32)


def leafwise_forest(seed=11, n_trees=N_TREES, n_leaves=N_LEAVES):
    """LightGBM's model form: per tree ``feat``, ``thr``, ``left``,
    ``right`` (a child ``c < 0`` is the leaf ``~c``) and ``leaf``. Each
    tree splits a leaf drawn from the seed until it has ``n_leaves``; the
    last tree always splits its newest right leaf (depth ``n_leaves - 1``)
    where it has N_LEAVES."""
    rng = np.random.default_rng(seed)
    trees = []
    for t in range(n_trees):
        inner = n_leaves - 1
        feat = rng.integers(N_FEATURES, size=inner)
        thr = rng.choice(GRID, size=inner)
        left = np.zeros(inner, np.int64)
        right = np.zeros(inner, np.int64)
        where, leaves = {0: None}, [0]
        for node in range(inner):
            lf = leaves[-1] if (t, n_leaves) == (
                N_TREES - 1, N_LEAVES) else leaves[rng.integers(len(leaves))]
            parent = where.pop(lf)
            if parent is not None:
                (left if parent[1] == 0 else right)[parent[0]] = node
            new = len(leaves)
            left[node], right[node] = ~lf, ~new
            where[lf], where[new] = (node, 0), (node, 1)
            leaves.append(new)
        leaf = 0.1 * rng.normal(size=n_leaves)
        trees.append((feat, thr, left, right, leaf))
    return trees


def depth_of(tree):
    feat, _, left, right, _ = tree

    def walk(c):
        return 0 if c < 0 else 1 + max(walk(left[c]), walk(right[c]))

    return walk(0)


def margins(trees, X):
    out = np.zeros(X.shape[0], np.float64)
    for feat, thr, left, right, leaf in trees:
        for r in range(X.shape[0]):
            c = 0
            while c >= 0:
                c = left[c] if X[r, feat[c]] <= thr[c] else right[c]
            out[r] += leaf[~c]
    return out


def _nid(c):
    return f"n{c}" if c >= 0 else f"l{~c}"


def tree_nodes(tree, c, pred, label=None):
    """A tree's nodes from node ``c`` down; ``label`` turns a leaf's
    value into a class (a vote forest's leaves)."""
    feat, thr, left, right, leaf = tree
    if c < 0:
        score = repr(float(leaf[~c])) if label is None else label(leaf[~c])
        return f'<Node id="{_nid(c)}" score="{score}">{pred}</Node>'
    f, v = int(feat[c]), float(thr[c])
    return (
        f'<Node id="{_nid(c)}" defaultChild="{_nid(left[c])}">{pred}'
        + tree_nodes(tree, int(left[c]), f'<SimplePredicate field="f{f}" '
                     f'operator="lessOrEqual" value="{v!r}"/>', label)
        + tree_nodes(tree, int(right[c]), f'<SimplePredicate field="f{f}" '
                     f'operator="greaterThan" value="{v!r}"/>', label)
        + "</Node>")


def _fields_xml(extra=""):
    return ('<DataDictionary>' + "".join(
        f'<DataField name="f{i}" optype="continuous" dataType="double"/>'
        for i in range(N_FEATURES)) + extra + "</DataDictionary>")


def vote_pmml(trees):
    """The same trees as a majority vote of classifiers: a leaf votes
    "a" where its value is positive, "b" elsewhere."""
    ms = "<MiningSchema>" + "".join(
        f'<MiningField name="f{i}"/>' for i in range(N_FEATURES)
    ) + "</MiningSchema>"
    segs = "".join(
        f'<Segment id="{i}"><True/><TreeModel functionName="classification" '
        f'missingValueStrategy="defaultChild">{ms}'
        + tree_nodes(t, 0, "<True/>", lambda v: "a" if v > 0 else "b")
        + "</TreeModel></Segment>" for i, t in enumerate(trees))
    return (f'<PMML version="4.3">{_fields_xml()}<MiningModel '
            f'functionName="classification">{ms}<Segmentation '
            f'multipleModelMethod="majorityVote">{segs}</Segmentation>'
            "</MiningModel></PMML>")


def chain_pmml(trees, norm="logit", second_term=False, guard=False,
               event_first=True):
    """The chain as JPMML-LightGBM writes it; the keywords plant forms
    the rank wire must refuse: another link, a table over two fields, a
    segment whose predicate is not ``<True/>``, the predictor on the
    second table."""
    fields = [f"f{i}" for i in range(N_FEATURES)]
    ms = "<MiningSchema>" + "".join(
        f'<MiningField name="{f}"/>' for f in fields) + "</MiningSchema>"

    segs = "".join(
        f'<Segment id="{i}"><True/><TreeModel functionName="regression" '
        f'missingValueStrategy="defaultChild">{ms}'
        f'{tree_nodes(t, 0, "<True/>")}</TreeModel></Segment>'
        for i, t in enumerate(trees))
    pred = ('<SimplePredicate field="f1" operator="lessThan" value="9"/>'
            if guard else "<True/>")
    term = '<NumericPredictor name="lgbmValue" coefficient="1.25"/>' + (
        '<NumericPredictor name="f0" coefficient="0.5"/>'
        if second_term else "")
    tables = [
        f'<RegressionTable intercept="-0.125" targetCategory="1">{term}'
        "</RegressionTable>",
        '<RegressionTable intercept="0.0" targetCategory="0"/>',
    ]
    if not event_first:
        tables.reverse()
    target = ('<DataField name="_target" optype="categorical" '
              'dataType="integer"><Value value="0"/><Value value="1"/>'
              "</DataField>")
    return (
        f'<PMML version="4.3">{_fields_xml(target)}<MiningModel functionName="classification"><MiningSchema>'
        '<MiningField name="_target" usageType="target"/>'
        + "".join(f'<MiningField name="{f}"/>' for f in fields)
        + '</MiningSchema><Segmentation multipleModelMethod="modelChain">'
        f'<Segment id="1">{pred}<MiningModel functionName="regression">'
        f'{ms}<Output><OutputField name="lgbmValue" feature="predictedValue"/>'
        '</Output><Segmentation multipleModelMethod="sum">'
        f"{segs}</Segmentation></MiningModel></Segment>"
        '<Segment id="2"><True/><RegressionModel functionName="classification"'
        f' normalizationMethod="{norm}"><MiningSchema>'
        '<MiningField name="_target" usageType="target"/>'
        '<MiningField name="lgbmValue"/><MiningField name="f0"/>'
        "</MiningSchema>" + "".join(tables)
        + "</RegressionModel></Segment></Segmentation></MiningModel></PMML>")


def event_probability(trees, X):
    return 1.0 / (1.0 + np.exp(-(-0.125 + 1.25 * margins(trees, X))))


@pytest.fixture(scope="module")
def trees():
    t = leafwise_forest()
    assert max(depth_of(x) for x in t) > 10
    return t


@pytest.fixture(scope="module")
def X():
    return np.random.default_rng(5).normal(
        0.0, 1.5, size=(300, N_FEATURES)).astype(np.float32)


def test_the_rank_wire_takes_the_chain(trees):
    q = compile_pmml(parse_pmml(chain_pmml(trees))).quantized_scorer()
    assert q is not None and q.backend == "xla"
    assert q.labels == ("1", "0") and q.event_class == 0
    assert q._pack_info is None  # one class's probability is not packed


def test_scores_labels_and_probabilities_are_the_f32_paths(trees, X):
    cm = compile_pmml(parse_pmml(chain_pmml(trees)))
    wire, f32 = cm.quantized_scorer().score(X), cm.score_dense(X)
    want = event_probability(trees, X)
    got = np.array([p.target.probabilities["1"] for p in wire])
    ref = np.array([p.target.probabilities["1"] for p in f32])
    # the wire's leaves are bf16 hi+lo pairs summed in float32
    assert np.abs(got - want).max() < 2e-6
    assert np.abs(got - ref).max() < 2e-6
    for w, f, p in zip(wire, f32, want):
        assert w.target.probabilities["0"] == pytest.approx(
            f.target.probabilities["0"], abs=2e-6)
        if abs(p - 0.5) > 1e-5:
            assert w.target.label == f.target.label == (
                "1" if p > 0.5 else "0")
        assert w.score.value == max(w.target.probabilities.values())


def test_the_state_folds_the_event_probability(trees):
    import jax

    from flink_jpmml_tpu.runtime.block import BlockPipeline, FiniteBlockSource

    B, blocks, keys = 32, 4, 5
    rng = np.random.default_rng(17)
    data = rng.normal(0.0, 1.5, size=(B * blocks, N_FEATURES)).astype(
        np.float32)
    data[:, 0] = rng.integers(0, keys, size=B * blocks).astype(np.float32)
    seen = {}
    cm = compile_pmml(parse_pmml(chain_pmml(trees)), batch_size=B)
    pipe = BlockPipeline(
        FiniteBlockSource(data, block_size=B), cm,
        lambda out, n, first: seen.update(
            {first: np.asarray(out, np.float64)[:n]}),
        use_native=False, in_flight=1,
        state=StateSpec(capacity=64, key_col=0),
    )
    assert pipe.backend == "rank_wire_xla"
    pipe.run_until_exhausted(timeout=60.0)
    got = np.concatenate([seen[k] for k in sorted(seen)])
    p = event_probability(trees, data)
    assert got.shape == (B * blocks,)
    assert np.abs(got - p).max() < 2e-6
    t = pipe._state
    jax.block_until_ready(t.values)
    vals = np.asarray(t.values)
    for k in range(keys):
        kh = int(t.hash_keys(np.array([k]))[0])
        slot = int(np.flatnonzero(t._occ & (t._keys == kh))[0])
        mine = data[:, 0] == k
        assert vals[slot, COL_COUNT] == mine.sum()
        assert vals[slot, COL_SUM] == pytest.approx(got[mine].sum(), rel=1e-5)


@pytest.mark.parametrize("form", [
    {"norm": "softmax"}, {"second_term": True}, {"guard": True},
    {"event_first": False},
])
def test_other_chains_stay_off_the_rank_wire(trees, form):
    cm = compile_pmml(parse_pmml(chain_pmml(trees, **form)))
    assert cm.quantized_scorer() is None


def chain_tree_pmml(depth):
    """One regression tree of ``depth`` splits in a row: at level i
    ``f{i % 3} <= i / depth`` is a leaf, the rest goes on."""
    def node(i):
        if i == depth:
            return ""
        thr, lf = repr(i / depth), f"L{i}"
        right = (f'<Node id="R{i}" score="{depth * 1.5}"><SimplePredicate '
                 f'field="f{i % 3}" operator="greaterThan" value="{thr}"/>'
                 "</Node>") if i == depth - 1 else (
            f'<Node id="R{i}" defaultChild="L{i + 1}"><SimplePredicate '
            f'field="f{i % 3}" operator="greaterThan" value="{thr}"/>'
            f"{node(i + 1)}</Node>")
        return (f'<Node id="{lf}" score="{i + 0.25}"><SimplePredicate '
                f'field="f{i % 3}" operator="lessOrEqual" value="{thr}"/>'
                f"</Node>{right}")

    return (
        '<PMML version="4.3"><DataDictionary>'
        + "".join(f'<DataField name="f{j}" optype="continuous" '
                  'dataType="double"/>' for j in range(3))
        + '</DataDictionary><TreeModel functionName="regression" '
        'missingValueStrategy="defaultChild"><MiningSchema>'
        + "".join(f'<MiningField name="f{j}"/>' for j in range(3))
        + '</MiningSchema><Node id="root" defaultChild="L0"><True/>'
        + node(0) + "</Node></TreeModel></PMML>")


@pytest.mark.parametrize("depth,taken", [(127, True), (128, False)])
def test_the_depth_gate_is_the_int8_sums(depth, taken):
    cm = compile_pmml(parse_pmml(chain_tree_pmml(depth)))
    q = cm.quantized_scorer()
    assert (q is not None) == taken
    if taken:
        X = np.random.default_rng(3).uniform(
            -0.1, 1.1, size=(64, 3)).astype(np.float32)
        got = [p.score.value for p in q.score(X)]
        want = [p.score.value for p in cm.score_dense(X)]
        assert got == pytest.approx(want, abs=1e-5)


@pytest.mark.parametrize("n_leaves,taken", [
    (1024, True), (1025, False), (4096, False)])
def test_a_trees_path_matrix_bounds_the_rank_wire(n_leaves, taken):
    """A leaf-wise tree of any depth the int8 sum holds is taken while
    its [S, L] path matrix holds at most a complete depth-10 tree's
    1,023 × 1,024 cells; one leaf more, or thousands, and the forest
    stays on the f32 path (refused before anything dense is packed)."""
    trees = leafwise_forest(seed=n_leaves, n_trees=2, n_leaves=n_leaves)
    assert max(depth_of(t) for t in trees) > 10
    cm = compile_pmml(parse_pmml(chain_pmml(trees)))
    assert (cm.quantized_scorer() is not None) == taken


def test_a_forest_whose_record_passes_the_budget_is_refused(
        trees, monkeypatch):
    q = compile_pmml(parse_pmml(chain_pmml(trees))).quantized_scorer()
    m = q._meta
    one = qtrees.twin_bytes(
        1, int(m["trees"]), int(m["splits"]), int(m["leaves"]))
    monkeypatch.setattr(qtrees, "_device_budget", lambda: one)
    assert compile_pmml(parse_pmml(chain_pmml(trees))).quantized_scorer()
    monkeypatch.setattr(qtrees, "_device_budget", lambda: one - 1)
    assert compile_pmml(parse_pmml(chain_pmml(trees))).quantized_scorer() \
        is None


def test_twin_rows_keeps_a_step_under_its_budget():
    T, S, L = 500, 254, 255
    per = T * (S + L)
    assert qtrees.twin_bytes(16, T, S, L) == 16 * per + 2 * T * S * L
    assert qtrees.twin_rows(65536, T, S, L, None) == 65536
    fit = qtrees.twin_bytes(16, T, S, L)
    assert qtrees.twin_rows(16, T, S, L, fit) == 16
    assert qtrees.twin_rows(16, T, S, L, fit - 1) == 8
    rows = qtrees.twin_rows(65536, T, S, L, 2**30)
    assert rows == 2048
    assert qtrees.twin_bytes(rows, T, S, L) <= 2**30 < qtrees.twin_bytes(
        2 * rows, T, S, L)
    assert qtrees.twin_rows(100, T, S, L, per // 2) == 1


@pytest.mark.parametrize("n", [64, 100])
@pytest.mark.parametrize("doc", ["chain", "vote"])
def test_the_chunked_twin_is_the_unchunked_bit_for_bit(
        trees, X, monkeypatch, doc, n):
    """The same document built twice on the CPU: once with no budget
    (the batch at once), once with a budget of 8 records a step (a pad
    to whole steps where ``n`` is not a multiple). Every output array
    is equal, bit for bit."""
    import jax

    parsed = parse_pmml(chain_pmml(trees) if doc == "chain"
                        else vote_pmml(trees))
    whole = compile_pmml(parsed).quantized_scorer()
    meta = whole._meta
    monkeypatch.setattr(qtrees, "_device_budget", lambda: qtrees.twin_bytes(
        8, int(meta["trees"]), int(meta["splits"]), int(meta["leaves"])))
    stepped = compile_pmml(parsed).quantized_scorer()
    assert stepped.twin_rows(n) == 8 and whole.twin_rows(n) == n
    F = len(whole.wire.fields)
    Xn = np.random.default_rng(n).normal(0.0, 1.5, size=(n, F)).astype(
        np.float32)
    Xq = whole.wire.encode(Xn)
    a = jax.tree_util.tree_leaves(whole.predict_wire(Xq))
    b = jax.tree_util.tree_leaves(stepped.predict_wire(Xq))
    assert len(a) == len(b)
    for u, v in zip(a, b):
        assert np.asarray(u).tobytes() == np.asarray(v).tobytes()


def _gather_codes(Xq, feat, dt):
    """The rank codes as a gather, ``Xq[:, feat]``: the twin's form
    before its codes were a one-hot contraction."""
    return Xq[:, feat]


@pytest.mark.parametrize("scanned", [False, True])
@pytest.mark.parametrize("doc", ["chain", "vote", "chain255"])
def test_the_rank_codes_are_the_gathers_bit_for_bit(
        trees, monkeypatch, doc, scanned):
    """The twin built twice on the CPU, its rank codes once the one-hot
    contraction (``qtrees._rank_codes``) and once the gather it
    replaced, over records with missing values: every output array is
    equal, bit for bit, the batch at once and scanned in steps of 8
    records. ``chain255`` has 254 splits a tree, as LightGBM's 255
    leaves make, a count no tile of the chip divides."""
    import jax

    if doc == "chain255":
        t = leafwise_forest(seed=255, n_trees=3, n_leaves=255)
        parsed = parse_pmml(chain_pmml(t))
    else:
        parsed = parse_pmml(chain_pmml(trees) if doc == "chain"
                            else vote_pmml(trees))
    n = 100
    meta = compile_pmml(parsed).quantized_scorer()._meta
    if doc == "chain255":
        assert meta["splits"] == 254
    if scanned:
        monkeypatch.setattr(qtrees, "_device_budget", lambda: qtrees.twin_bytes(
            8, int(meta["trees"]), int(meta["splits"]), int(meta["leaves"])))

    def outputs(codes):
        with monkeypatch.context() as m:
            m.setattr(qtrees, "_rank_codes", codes)
            q = compile_pmml(parsed).quantized_scorer()
            assert q.twin_rows(n) == (8 if scanned else n)
            F = len(q.wire.fields)
            Xn = np.random.default_rng(7).normal(
                0.0, 1.5, size=(n, F)).astype(np.float32)
            Xn[::7, 1] = np.nan
            Xq = q.wire.encode(Xn)
            assert (Xq == q.wire.sentinel).any()
            return jax.tree_util.tree_leaves(q.predict_wire(Xq))

    a = outputs(qtrees._rank_codes)
    b = outputs(_gather_codes)
    assert len(a) == len(b)
    for u, v in zip(a, b):
        assert np.asarray(u).tobytes() == np.asarray(v).tobytes()
