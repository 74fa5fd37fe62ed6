"""The keyed state table over a mesh (ISSUE 27): one table in ``D``
pieces, a dispatch sorted by owning chip on the host
(runtime/shuffle.py), the fold under ``shard_map``
(compile/statekernel.py), on the conftest's virtual CPU devices.

The same stream goes through the mesh pipeline and the one-chip
pipeline at D in {1, 2, 4}, and both are held to two plain references
written here in numpy: a dict tally of what the sink received (what
every key's row has to hold), and the ownership rule as stated — the
owner of a key is the owner of the slot the table's own lookup returns,
chip ``d`` owning the global slots ``[d·R, (d+1)·R)``, ``R =
⌈capacity/D⌉`` — with a tally of the stream's records by owner (what
the per-chip counters have to read). ``compare`` is the one comparison;
two planted faults (a record folded on a chip that does not own it, a
cut dispatch that re-sends its tail) show that it fails when it should.
"""

import numpy as np
import pytest

from flink_jpmml_tpu.obs import attr
from flink_jpmml_tpu.runtime import native
from flink_jpmml_tpu.runtime import shuffle as shuffle_mod
from flink_jpmml_tpu.runtime.state import (
    COL_COUNT,
    COL_DCOUNT,
    COL_LAST_T,
    COL_MAX,
    COL_MIN,
    COL_SQSUM,
    COL_SUM,
    STATE_WIDTH,
    KeyedStateTable,
    StateSpec,
)
from flink_jpmml_tpu.utils.metrics import MetricsRegistry

B = 32            # the model's batch: one scan chunk
CHUNKS = 8        # max_dispatch_chunks: a dispatch holds up to 256 records
CAPACITY = 1009   # prime: divides by no mesh width
WIDTHS = (1, 2, 4)


@pytest.fixture(scope="module")
def gbm(tmp_path_factory):
    from flink_jpmml_tpu.assets_gen import gen_gbm
    from flink_jpmml_tpu.compile import compile_pmml
    from flink_jpmml_tpu.pmml import parse_pmml_file

    tmp = tmp_path_factory.mktemp("state_mesh_gbm")
    path = gen_gbm(str(tmp), n_trees=5, depth=3, n_features=4)
    return compile_pmml(parse_pmml_file(path), batch_size=B)


def _mesh(D):
    import jax

    from flink_jpmml_tpu.parallel.mesh import make_mesh
    from flink_jpmml_tpu.utils.config import MeshConfig

    return make_mesh(MeshConfig(data=D, model=1), devices=jax.devices()[:D])


def _stream(n, keys, seed=5, hot=None):
    """``n`` records of 4 features, the key in column 0: zipf-skewed
    over ``keys`` keys, or one key (``hot``) throughout."""
    rng = np.random.default_rng(seed)
    X = rng.normal(0.0, 1.0, size=(n, 4)).astype(np.float32)
    X[:, 0] = (
        np.full(n, hot) if hot is not None
        else (rng.zipf(1.3, n) - 1) % keys
    ).astype(np.float32)
    return X


class Run:
    """One stream through one pipeline: what the sink received, the
    table, the counters."""

    def __init__(self, gbm, X, D=None, block=100, capacity=CAPACITY,
                 probe=16, **pipe_kw):
        from flink_jpmml_tpu.runtime.block import (
            BlockPipeline, FiniteBlockSource,
        )
        from flink_jpmml_tpu.utils.config import BatchConfig, RuntimeConfig

        self.X, self.D = X, D or 1
        self.metrics = MetricsRegistry()
        self.deliveries = []  # (first offset, scores), in delivery order
        self.pipe = BlockPipeline(
            FiniteBlockSource(X, block), gbm,
            lambda out, n, first: self.deliveries.append(
                (int(first), np.asarray(out)[:n].copy())),
            RuntimeConfig(batch=BatchConfig(
                size=B, deadline_us=500, queue_capacity=8 * B)),
            metrics=self.metrics, use_native=False,
            max_dispatch_chunks=CHUNKS,
            mesh=_mesh(D) if D else None,
            state=StateSpec(capacity=capacity, probe=probe),
            **pipe_kw,
        )
        self.table = self.pipe._state
        self.pipe.run_until_exhausted(timeout=120)
        self.counters = self.metrics.struct_snapshot()["counters"]

    @property
    def scores(self):
        return np.concatenate([s for _, s in self.deliveries])

    def lookup(self, keys):
        """The table's own routing, as a lookup of resident keys."""
        slots, reset, _, _ = self.table.assign_slots(
            self.table.hash_keys(keys), np.zeros(keys.size, np.int64))
        assert not reset.any()
        return slots


# -- the two plain references -------------------------------------------------

def tally_of(keys, scores):
    """Dict tally of what the sink received → {key: [count, sum]}."""
    out = {}
    for k, s in zip(keys.tolist(), scores.tolist()):
        c = out.setdefault(k, [0, 0.0])
        c[0] += 1
        c[1] += s
    return out


def owner_of(slots, capacity, D):
    """The stated rule, written out: → (chip, local row)."""
    R = -(-capacity // D)
    return slots // R, slots % R


def compare(run):
    """The mesh run against the references → a list of faults."""
    t, D = run.table, run.D
    faults = []
    at = 0
    for first, s in run.deliveries:
        if first != at:
            faults.append(f"delivery at {first}, expected {at}")
        at = first + s.size
    if at != run.X.shape[0]:
        faults.append(f"{at} offsets delivered of {run.X.shape[0]}")
        return faults
    keys = run.X[:, 0].astype(np.int64)
    tally = tally_of(keys, run.scores)
    uniq = np.array(sorted(tally), np.int64)
    slots = run.lookup(uniq)
    chip, row = owner_of(slots.astype(np.int64), t.capacity, D)
    if not (np.array_equal(t.locate(slots)[0], chip)
            and np.array_equal(t.locate(slots)[1], row)):
        faults.append("locate differs from the stated rule")
    whole = np.asarray(t.values).reshape(D, t.shard_rows, STATE_WIDTH)
    rows = whole[chip, row]
    want_n = np.array([tally[k][0] for k in uniq.tolist()], np.float64)
    want_s = np.array([tally[k][1] for k in uniq.tolist()], np.float64)
    if not np.array_equal(rows[:, COL_COUNT], want_n):
        faults.append("a key's count differs from the tally")
    # float32 running sums, one rounding a record folded
    tol = 1e-6 * want_n * np.maximum(np.abs(want_s), 1.0) + 1e-5
    if (np.abs(rows[:, COL_SUM] - want_s) > tol).any():
        faults.append("a key's score sum differs from the tally")
    # every row the stream did not touch is as it was born: zeros
    touched = np.zeros(whole.shape[:2], bool)
    touched[chip, row] = True
    if whole[~touched].any():
        faults.append("a row no key of the stream owns has moved")
    if D > 1:
        by_owner = np.bincount(
            chip[np.searchsorted(uniq, keys)], minlength=D)
        got = [run.counters.get(f'mesh_chip_records{{chip="{d.id}"}}', 0)
               for d in t.mesh.devices[:, 0]]
        if got != by_owner.tolist():
            faults.append(f"per-chip records {got}, the reference "
                          f"tallies {by_owner.tolist()}")
    return faults


# -- the layout ---------------------------------------------------------------

@pytest.mark.parametrize("D", (1, 2, 3, 4))
@pytest.mark.parametrize("capacity", (256, CAPACITY, 61001))
def test_locate_is_the_stated_rule(D, capacity):
    t = KeyedStateTable(StateSpec(capacity=capacity))
    t._set_layout(D)
    slots = np.arange(capacity)
    chip, row = t.locate(slots)
    want_chip, want_row = owner_of(slots, capacity, D)
    assert np.array_equal(chip, want_chip) and np.array_equal(row, want_row)
    assert chip.max() < D and row.max() < t.shard_slots == t.local_scratch
    assert t.shard_rows % 256 == 0 and t.shard_rows > t.local_scratch
    assert t.rows == D * t.shard_rows
    # one place a slot
    assert np.unique(chip.astype(np.int64) * t.shard_rows + row).size == capacity
    # the scratch slot is a chip's own
    assert [a.tolist() for a in t.locate(np.array([t.scratch]))] == [
        [0], [t.local_scratch]]
    if D == 1:
        assert np.array_equal(row, slots) and t.rows == -(-(capacity + 1) // 256) * 256


def test_born_on_the_mesh_without_a_host_copy():
    t = KeyedStateTable(StateSpec(capacity=CAPACITY), mesh=_mesh(4))
    assert t.n_shards == 4 and t._snap is None
    assert not isinstance(t.values, np.ndarray)
    assert t.values.shape == (t.rows, STATE_WIDTH)
    assert {s.data.shape for s in t.values.addressable_shards} == {
        (t.shard_rows, STATE_WIDTH)}
    assert not np.asarray(t.values).any()
    t.shard(t.mesh)  # already there: nothing moves
    assert t.generation == 0


# -- the scorer ---------------------------------------------------------------

@pytest.mark.parametrize("D", WIDTHS)
def test_mesh_scorer_scores_bit_identically(gbm, D):
    from flink_jpmml_tpu.parallel.sharding import mesh_sharded

    q1 = gbm.quantized_scorer()
    q = mesh_sharded(gbm, _mesh(D)).quantized_scorer()
    assert q is not None and q.mesh is not None and q.data_width == D
    assert q is gbm.quantized_scorer().on_mesh(q.mesh)  # built once
    X = _stream(3 * B * D + 7, keys=50)
    codes = q1.wire.encode(X)
    want = np.asarray(q1.predict_wire(codes))[: X.shape[0]]
    got = np.asarray(q.predict_wire(codes))[: X.shape[0]]
    assert got.tobytes() == want.tobytes()


# -- the pipeline -------------------------------------------------------------

@pytest.fixture(scope="module")
def one_chip(gbm):
    return Run(gbm, _stream(2000, keys=150))


@pytest.mark.parametrize("D", WIDTHS)
def test_mesh_pipeline_matches_one_chip_and_the_tally(gbm, one_chip, D):
    run = Run(gbm, one_chip.X, D=D)
    assert run.pipe.backend.startswith("rank_wire")
    assert compare(run) == []
    assert compare(one_chip) == []
    assert run.scores.tobytes() == one_chip.scores.tobytes()
    uniq = np.unique(run.X[:, 0].astype(np.int64))
    mine = run.table.read_rows(run.lookup(uniq))
    theirs = one_chip.table.read_rows(one_chip.lookup(uniq))
    assert np.array_equal(mine[:, COL_COUNT], theirs[:, COL_COUNT])
    # a chip adds its duplicates in bucket order, the one-chip fold in
    # batch order: the sums agree to one float32 rounding a record
    n = theirs[:, COL_COUNT].astype(np.float64)
    for col in (COL_SUM, COL_SQSUM):
        tol = 1e-6 * n * np.maximum(np.abs(theirs[:, col]), 1.0) + 1e-5
        assert (np.abs(mine[:, col].astype(np.float64) - theirs[:, col])
                <= tol).all()
    if D > 1:
        c = run.counters
        assert c["mesh_bucket_slots"] % (D * B) == 0
        assert c["mesh_bucket_slots"] - c["mesh_bucket_pad_records"] == 2000
        assert sum(v for k, v in c.items()
                   if k.startswith("mesh_chip_records")) == 2000
    for name in ("state_evictions", "state_overflow", "state_rollbacks"):
        assert run.counters.get(name, 0) == 0


@pytest.mark.parametrize("D", (2, 4))
def test_one_key_for_a_whole_stream_cuts_every_dispatch(gbm, D):
    """One key is all of the stream: its owner's bucket fills and cuts
    each dispatch at the largest bucket, the other chips fold nothing
    and their pieces of the table stay bit-identical (pad-only
    buckets)."""
    run = Run(gbm, _stream(1500, keys=1, hot=77), D=D)
    assert compare(run) == []
    most = max(1, CHUNKS // D) * B  # the largest bucket
    sizes = [s.size for _, s in run.deliveries]
    assert max(sizes) == most and run.counters["mesh_dispatch_cuts"] > 0
    chip = int(run.table.locate(run.lookup(np.array([77])))[0][0])
    whole = np.asarray(run.table.values).reshape(D, -1, STATE_WIDTH)
    for d in range(D):
        assert whole[d].any() == (d == chip)
    per_chip = sorted(v for k, v in run.counters.items()
                      if k.startswith("mesh_chip_records"))
    assert per_chip == [0] * (D - 1) + [1500]


@pytest.mark.parametrize("D", (2, 4))
def test_one_bucket_fills_while_the_others_wait(gbm, D):
    """A hot key among others: the dispatch is cut where the hot chip's
    bucket fills, the tail leads the next one, and every offset reaches
    the sink once and in order."""
    X = _stream(2400, keys=150)
    X[::2, 0] = 3.0  # every other record is key 3
    run = Run(gbm, X, D=D)
    assert compare(run) == []
    assert run.counters["mesh_dispatch_cuts"] > 0
    firsts = [f for f, _ in run.deliveries]
    assert firsts == sorted(firsts) and firsts[0] == 0


@pytest.mark.parametrize("plant", ("wrong_chip", "resend_tail"))
def test_the_comparison_catches_a_planted_fault(gbm, monkeypatch, plant):
    if plant == "wrong_chip":
        # every record that belongs on chip 1 is folded on chip 2, in
        # the row of the same number
        real = shuffle_mod._owners

        def owners(table, slots):
            chip, row = real(table, slots)
            return np.where(chip == 1, 2, chip).astype(np.uint8), row

        monkeypatch.setattr(shuffle_mod, "_owners", owners)
    else:
        # a cut dispatch leaves its last records held as well: the next
        # dispatch sends them again
        real = shuffle_mod._Held.move

        def move(self, other, lo, hi):
            real(self, other, max(0, lo - 5) if hi > lo else lo, hi)

        monkeypatch.setattr(shuffle_mod._Held, "move", move)
    X = _stream(2400, keys=150)
    X[::2, 0] = 3.0
    try:
        run = Run(gbm, X, D=4)
    except Exception as e:  # a fault may also stop the pipeline
        pytest.skip(f"the planted fault stopped the run: {e!r}")
    assert compare(run) != []


# -- direct dispatches: same batches, so derived rows compare too -------------

def _fold(gbm, table, X, batch=96, shuffled=None):
    """``X`` through direct dispatches of ``batch`` records; with
    ``shuffled`` (a Generator) each dispatch's records, offsets with
    them, go in a drawn order and its results are put back in the
    stream's."""
    import jax

    from flink_jpmml_tpu.runtime.pipeline import dispatch_quantized

    q = gbm.quantized_scorer()
    outs = []
    for lo in range(0, X.shape[0], batch):
        n = min(batch, X.shape[0] - lo)
        p = np.arange(n) if shuffled is None else shuffled.permutation(n)
        out, derived = dispatch_quantized(
            q, X[lo:lo + n][p], state=table, offsets=lo + p)
        back = np.argsort(p)
        outs.append((np.asarray(out)[:n][back], np.asarray(derived)[:n][back]))
    jax.block_until_ready(table.values)
    return (np.concatenate([o for o, _ in outs]),
            np.concatenate([d for _, d in outs]))


def _tables(D, **spec):
    spec = StateSpec(capacity=CAPACITY, probe=16, **spec)
    return (KeyedStateTable(spec, metrics=MetricsRegistry()),
            KeyedStateTable(spec, metrics=MetricsRegistry(), mesh=_mesh(D)))


@pytest.mark.parametrize("D", (2, 4))
def test_direct_dispatch_scores_and_derived_rows_agree(gbm, D):
    X = _stream(700, keys=60)
    one, many = _tables(D)
    s1, d1 = _fold(gbm, one, X)
    s2, d2 = _fold(gbm, many, X)
    assert s1.tobytes() == s2.tobytes()
    # derived rows read the table as of the dispatch's start
    assert np.allclose(d1, d2, rtol=1e-5, atol=1e-6)
    every = np.arange(CAPACITY)
    assert np.array_equal(one.read_rows(every)[:, COL_COUNT],
                          many.read_rows(every)[:, COL_COUNT])
    assert np.allclose(one.read_rows(every), many.read_rows(every),
                       rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("D", (2, 4))
def test_a_permuted_dispatch_folds_to_the_same_pieces(gbm, D):
    """Each chip groups its bucket by slot itself (compile/statekernel.py),
    so the order a dispatch's records arrive in decides nothing but
    the order of the float32 additions inside a group: scores go
    with their records byte for byte, counts, last_t and the extrema
    come out byte for byte (in the table and in the derived rows that
    read them), the sums inside float32's rounding."""
    X = _stream(700, keys=60)
    _, straight = _tables(D)
    _, permuted = _tables(D)
    s1, d1 = _fold(gbm, straight, X)
    s2, d2 = _fold(gbm, permuted, X, shuffled=np.random.default_rng(35))
    assert s1.tobytes() == s2.tobytes()
    seen_gap_min_max = [0, 5, 6, 7]  # state.DERIVED_FIELDS
    assert (d1[:, seen_gap_min_max].tobytes()
            == d2[:, seen_gap_min_max].tobytes())
    assert np.allclose(d1, d2, rtol=1e-5, atol=1e-6)
    every = np.arange(CAPACITY)
    a, b = straight.read_rows(every), permuted.read_rows(every)
    exact = [COL_COUNT, COL_LAST_T, COL_MIN, COL_MAX]
    assert a[:, exact].tobytes() == b[:, exact].tobytes()
    assert np.allclose(a, b, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("D", (2, 4))
def test_renorm_shard_by_shard_equals_the_one_chip_renorm(gbm, D):
    X = _stream(400, keys=60)
    one, many = _tables(D, decay=0.9, stride=4)
    _fold(gbm, one, X)
    _fold(gbm, many, X)
    for t in (one, many):
        epoch = t.epoch
        t.maybe_renorm(400 + 4 * 4096)
        assert t.epoch > epoch
    assert one.epoch == many.epoch
    assert not isinstance(many.values, np.ndarray)
    assert many.values.sharding.is_equivalent_to(
        many._zeros_on_mesh().sharding, 2)
    every = np.arange(CAPACITY)
    a, b = one.read_rows(every), many.read_rows(every)
    for col in (COL_DCOUNT, COL_LAST_T):
        assert np.allclose(a[:, col], b[:, col], rtol=1e-5, atol=1e-6)


def test_snapshot_restores_at_another_width(gbm, tmp_path):
    X = _stream(500, keys=60)
    _, many = _tables(4)
    _fold(gbm, many, X)
    name = many.save_sidecar(str(tmp_path))
    every = np.arange(CAPACITY)
    for D in (1, 2):
        t = KeyedStateTable(many.spec, metrics=MetricsRegistry(),
                            mesh=_mesh(D) if D > 1 else None)
        assert t.restore_sidecar(str(tmp_path), name)
        assert t.read_rows(every).tobytes() == many.read_rows(every).tobytes()
        assert t.resident == many.resident and t.applied_hi == 500


def test_migrate_four_to_three_keeps_every_key(gbm):
    X = _stream(900, keys=80)
    one, many = _tables(4)
    _fold(gbm, one, X[:500])
    _fold(gbm, many, X[:500])
    every = np.arange(CAPACITY)
    before = many.read_rows(every)
    many.migrate(_mesh(3))
    assert many.n_shards == 3 and many.generation == 1
    assert many.read_rows(every).tobytes() == before.tobytes()
    # and the fold goes on, on three chips
    s1 = [_fold(gbm, t, X[500:])[0] for t in (one, many)]
    assert s1[0].tobytes() == s1[1].tobytes()
    assert np.array_equal(one.read_rows(every)[:, COL_COUNT],
                          many.read_rows(every)[:, COL_COUNT])


def test_rollback_of_a_table_born_on_the_mesh_is_empty(gbm):
    _, many = _tables(4)
    _fold(gbm, many, _stream(300, keys=40))
    assert many.resident > 0
    many.rollback()
    assert many.resident == 0 and many.generation == 1
    assert not np.asarray(many.values).any() and many.n_shards == 4
    # nothing was ever snapshotted, so nothing counts as applied: the
    # same offsets fold again, into the empty table
    _fold(gbm, many, _stream(300, keys=40))
    assert many.read_rows(np.arange(CAPACITY))[:, COL_COUNT].sum() == 300


def test_rollback_after_a_poisoned_dispatch_keeps_snapshotted_state(gbm):
    _, many = _tables(4)
    X = _stream(600, keys=50)
    _fold(gbm, many, X[:300])
    every = np.arange(CAPACITY)
    many.snapshot()
    kept = many.read_rows(every)
    _fold(gbm, many, X[300:])  # lost with the poisoned buffer
    many.rollback()
    assert many.read_rows(every).tobytes() == kept.tobytes()
    assert many.applied_hi == 300 and many.skip_until == 300


def test_claims_held_for_pending_records_stay_out_of_a_snapshot():
    t = KeyedStateTable(StateSpec(capacity=CAPACITY))
    slots, reset, _ = t.route(t.hash_keys(np.arange(10)), np.arange(10))
    assert reset.all() and t.applied_hi == 0  # route leaves the clock alone
    t.hold_claims(slots[5:])
    snap = t.snapshot()
    assert int(snap["occ"].sum()) == 5 and snap["resident"] == 5
    assert t.resident == 10  # the live mirror keeps them
    t.unclaim(slots[5:])
    assert t.resident == 5 and not t._occ[slots[5:]].any()
    again, reset, _ = t.route(t.hash_keys(np.arange(10)), np.arange(10, 20))
    assert reset[5:].all() and not reset[:5].any()


# -- eviction while the shuffle holds a tail ------------------------------------

def _stolen(table, X, plan):
    """Records of a planned dispatch whose row the mirror now gives to
    another key: they would fold into that key's fresh row."""
    chip, row = plan.dest // plan.bucket, plan.slots[plan.dest]
    mine = row != table.local_scratch
    slot = chip.astype(np.int64) * table.shard_slots + row
    want = table.hash_keys(table.extract_keys(X[: plan.n]))
    return int((table._keys[slot[mine]] != want[mine]).sum())


@pytest.mark.parametrize("held_protected", (True, False))
def test_no_eviction_takes_a_row_a_held_record_points_to(
        held_protected, monkeypatch):
    """A cut dispatch leaves a tail that keeps the slots an earlier
    routing gave it. The next routing finds the probe window full and
    evicts: never a slot the tail points to, or that record would fold
    into the new key's row. The second case plants the fault (the
    routing is not told what is held) and the check has to see it."""
    if not held_protected:
        real = KeyedStateTable.route
        monkeypatch.setattr(
            KeyedStateTable, "route",
            lambda self, khash, offsets, held=None: real(self, khash, offsets))
    m = MetricsRegistry()
    t = KeyedStateTable(StateSpec(capacity=8, probe=8), metrics=m,
                        mesh=_mesh(2))
    sh = shuffle_mod.KeyShuffle(t, 4, 4, (1,), 16, m)
    ledger = attr.ledger_for(m)

    def block(keys, first):
        X = np.zeros((len(keys), 4), np.float32)
        X[:, 0] = keys
        sh.feed(X, np.arange(first, first + len(keys)))

    # eight keys fill the table; the fifth record of key 100 overflows
    # its chip's bucket of four, so the dispatch is cut there and the
    # tail holds a record of every key
    block([100] * 5 + list(range(101, 108)), 0)
    X, offs, n, plan = sh.take(ledger)
    assert n == 4 and plan.cut and sh.pending == 8 and t.resident == 8
    # four keys the table has never seen: the window is full
    block([200, 201, 202, 203], 12)
    stolen = 0
    while sh.pending:
        X, offs, n, plan = sh.take(ledger)
        stolen += _stolen(t, X, plan)
    c = m.struct_snapshot()["counters"]
    if held_protected:
        assert stolen == 0
        assert c["state_evictions"] == 0 and c["state_overflow"] == 4
    else:
        assert stolen > 0 and c["state_evictions"] > 0


def test_an_eviction_race_under_a_held_tail_drops_nobodys_state():
    """The race of ISSUE 34 through the shuffle on four chips, with a cut
    and a held tail (the mesh cell does not evict; a mesh deployment
    may). The table is full; its least recently touched slot is key
    103's, then 104..107's; a hot key cuts the dispatch and the tail
    holds the slots of 100, 101 and 102. Three keys the table has never
    seen arrive in ONE routing call: all name 103's slot, the smallest
    hash has it, the others choose again among 104..107's, nobody takes
    a held slot and nobody overflows."""
    m = MetricsRegistry()
    t = KeyedStateTable(StateSpec(capacity=8, probe=8), metrics=m,
                        mesh=_mesh(4))
    sh = shuffle_mod.KeyShuffle(t, 4, 4, (1,), 16, m)
    ledger = attr.ledger_for(m)

    def block(keys, first):
        X = np.zeros((len(keys), 4), np.float32)
        X[:, 0] = keys
        sh.feed(X, np.arange(first, first + len(keys)))

    old = t.hash_keys(np.arange(100, 108))
    home, _, _ = t.route(old, np.arange(8))
    t.route(old[4:], np.arange(8, 12))
    assert t.resident == 8 and sorted(home.tolist()) == list(range(8))
    block([100] * 5 + [101, 102], 12)
    X, offs, n, plan = sh.take(ledger)
    assert n == 4 and plan.cut and sh.pending == 3
    held = set(home[:3].tolist())
    block([200, 201, 202], 19)
    stolen = 0
    while sh.pending:
        X, offs, n, plan = sh.take(ledger)
        stolen += _stolen(t, X, plan)
    fresh = t.hash_keys(np.arange(200, 203))
    at = {int(h): int(np.flatnonzero(t._keys == h)[0]) for h in fresh}
    c = m.struct_snapshot()["counters"]
    assert stolen == 0
    assert (c["state_evictions"], c["state_overflow"]) == (3, 0)
    assert len(set(at.values())) == 3 and not held & set(at.values())
    assert at[int(fresh.min())] == int(home[3])
    assert np.isin(old[:3], t._keys).all() and old[3] not in t._keys


@pytest.mark.skipif(not native.available(), reason="no native library")
def test_route_pending_counts_what_the_native_pass_leaves_to_the_rounds():
    """Through the shuffle, cuts and held tails included: a stream whose
    keys are all resident leaves ``state_route_pending`` where it was
    (the native pass resolved every record), a churning one moves it."""
    m = MetricsRegistry()
    t = KeyedStateTable(StateSpec(capacity=64, probe=8), metrics=m,
                        mesh=_mesh(2))
    sh = shuffle_mod.KeyShuffle(t, 4, 4, (1,), 16, m)
    ledger = attr.ledger_for(m)

    def run(keys, first):
        X = np.zeros((len(keys), 4), np.float32)
        X[:, 0] = keys
        sh.feed(X, np.arange(first, first + len(keys)))
        cuts = 0
        while sh.pending:
            cuts += sh.take(ledger)[3].cut
        c = m.struct_snapshot()["counters"]
        return cuts, c["state_route_pending"], c["state_records"]

    keys = [100] * 5 + list(range(101, 108))  # the hot key cuts a dispatch
    cuts, fresh, _ = run(keys, 0)
    assert cuts and fresh == 12  # every record's key was new to the table
    cuts, pending, records = run(keys, 12)
    assert cuts and pending == fresh and records == 24
    assert m.struct_snapshot()["counters"]["state_hits"] == 12
    _, pending, _ = run(list(range(300, 312)), 24)
    assert pending == fresh + 12


def test_a_churning_stream_with_a_cut_tail_folds_on_its_own_rows(
        gbm, monkeypatch):
    """The pipeline whole, a table far smaller than the key population
    and a hot key that cuts every dispatch: at each dispatch every
    record's row is its own key's by the mirror, whatever was evicted
    meanwhile, and every offset reaches the sink once, in order."""
    real, seen = shuffle_mod.KeyShuffle.take, []

    def take(self, ledger):
        X, offs, n, plan = real(self, ledger)
        if plan is not None:
            seen.append(_stolen(self.table, X, plan))
        return X, offs, n, plan

    monkeypatch.setattr(shuffle_mod.KeyShuffle, "take", take)
    X = _stream(2400, keys=2000, seed=11)
    X[::3, 0] = 3.0
    run = Run(gbm, X, D=4, capacity=61, probe=4)
    assert len(seen) > 10 and sum(seen) == 0
    assert run.counters["state_evictions"] > 0
    assert run.counters["mesh_dispatch_cuts"] > 0
    firsts = [f for f, _ in run.deliveries]
    assert firsts == sorted(firsts) and run.scores.size == 2400


def test_a_poisoned_dispatch_on_the_mesh_rolls_back_and_never_folds(
        gbm, tmp_path, monkeypatch):
    """The never-delivered contract on the mesh pipeline: the poisoned
    record goes to the DLQ, every other offset reaches the sink once,
    the table goes back to its (empty) rollback point, what the shuffle
    held is routed again (``generation``), and no key is folded more
    often than the stream holds it."""
    import jax

    from flink_jpmml_tpu.runtime import faults
    from flink_jpmml_tpu.runtime.dlq import DeadLetterQueue

    monkeypatch.setenv("FJT_RETRY_BASE_S", "0.01")
    X = _stream(640, keys=12)
    X[::2, 0] = 3.0  # a hot key: dispatches are cut, the shuffle holds a tail
    poison = 300
    X[poison, 0] = 99.0  # a key no other record has
    m = MetricsRegistry()
    dlq = DeadLetterQueue(str(tmp_path / "dlq"), metrics=m)
    assert faults.install_from_env(f"poison_record:offset={poison}")
    try:
        run = Run(gbm, X, D=4, block=64, dlq=dlq, in_flight=1)
    finally:
        faults.clear()
    assert sorted(set(dlq.offsets())) == [poison]
    covered = np.zeros(640, np.int64)
    for first, s in run.deliveries:
        covered[first:first + s.size] += 1
    assert np.flatnonzero(covered != 1).tolist() == [poison]
    t = run.table
    jax.block_until_ready(t.values)
    assert run.counters["state_rollbacks"] >= 1 and t.generation >= 1
    held = t._keys[t._occ]
    assert int(t.hash_keys(np.array([99]))[0]) not in held.tolist()
    counts = t.read_rows(np.flatnonzero(t._occ))[:, COL_COUNT]
    kh, true = np.unique(t.hash_keys(X[:, 0].astype(np.int64)),
                         return_counts=True)
    true = dict(zip(kh.tolist(), true.tolist()))
    for k, c in zip(held.tolist(), counts.tolist()):
        assert c <= true[k], (k, c, true[k])
    assert counts.sum() > 0  # the fold went on after the recovery
