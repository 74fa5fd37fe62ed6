"""The fused state stage: lookup → derive → score → update, one jit.

This module grafts the keyed state plane (runtime/state.py) into the
EXISTING scoring dispatch. A state-armed dispatch runs ONE compiled
program per batch:

    out            = member kernel(params, X)        # unchanged
    sorted         = sort(slots)            # the dispatch, grouped by row
    derived[B, 8]  = gather(S, sorted) → session features
    S'             = set(S, one row a group, f(row, out, w, rel))

The state stage is pure XLA over the batch's slot vector, appended to
the scoring program, and composes with EVERY backend the scorer already
has: XLA, Pallas (the state ops wrap the scan-chunked kernel, outside
the Pallas grid), fused-encode, and cross-model packs. No new Pallas
kernel is warranted — but the work is O(batch) only where a write
covers WHOLE ROWS. On the chip the table is column-major,
``f32[rows, 8]{0,1:T(8,128)}``: one slot's row is one lane of an
(8, 128) tile. What the TPU compiler makes of a table write (read off a
v5e compile; tests/test_v5e_compile.py, PERF.md §5):

- a scatter of whole rows (``S.at[slots].set/.max/.min(rows8)``) stays
  a native scatter, in place on the donated buffer: ~6 ms for 65,536
  records at 200M slots, duplicates and all;
- a scatter into ONE column (``S.at[slots, c].max``, what last_t, min
  and max were until PR 25) flattens the table a column at a time into
  ``f32[rows * 8]``, scatters there and copies back: O(table) a
  dispatch, 9.6 GB of temporaries, ~430 of a dispatch's 665 ms;
- a scatter into a SLICE of columns (what the add of the five
  accumulator columns was until PR 28) is expanded to a ``while`` of
  one iteration a record, ~3.5 µs each: ~230 ms for 65,536 records.

So the table is touched twice a dispatch, and both times by whole rows
(PR 35; until then a reset, an add, a max and a min each walked it,
24.5 ms of a 65,536-record dispatch where this form takes 8.7; numbers:
a v5e, the deployment's table, PERF.md §6):

- the dispatch is sorted by slot on the chip. ONE gather reads the
  sorted rows (0.9–1.4 ms), and a group that carries a fresh-slot mark on
  any of its records starts from ``_INIT_ROW`` instead (a ``where`` on
  the gathered rows: no reset pass over the table);
- each group's five sums, two maxima and minimum are scanned along the
  sorted order in log2(B) shifted steps (``_group_scan``, ~0.5 ms), no
  table access, the total on the group's last record;
- ONE scatter sets ``combine(prior row, totals)`` for the last record
  of each group, whole rows, every other record's aim off the table
  and dropped. It costs ~92 ns an INDEX, dropped or not, aimed at one
  row or at many (6.0 ms of the 8.7): compacting the live rows to the
  front buys nothing, a shorter index vector would.

What the sort may carry is set by what it costs to COMPILE, not to run
(0.3 ms): the TPU compiler takes 4 s for an unstable sort of 65,536
``(slot, index)`` pairs, 8 s for the stable one, 40–185 s once a
float32 is among the keys or six operands ride along, and a pipeline
compiles one program a dispatch size. So the sort is ``(slot, index)``,
unstable, and score, rel, w and reset follow it through one ``[B, 4]``
row gather; ``derived`` goes back to arrival order through the sort's
inverse (a 1-D scatter of ``[B]``, which compiles to a native scatter;
a ``[B, 8]`` row scatter makes the compiler sort again).

Batch-consistent read semantics: every record's DERIVED features
reflect the table as of the BATCH start (the one gather, after the
reset), and the update is order-free where float32 allows: counts (a
group's length), last_t and the extrema are exact whatever the order;
the three score sums and the decayed count are float32 sums in the
order the sort leaves a group in, a function of the dispatch alone —
the same dispatch folds to the same bytes every time (replay-exact
across restarts, the checkpoint parity pin in bench --stateful), and a
permuted one to the same bytes but for the rounding of those sums.

Donation: when the caller donates, BOTH the staged batch and the state
buffer are donated (``donate_argnums=(1, 2)``) — the state update is
in-place on device, so steady-state state memory is one ``[rows, 8]``
buffer regardless of dispatch depth.

Bypassed records (shed replay below the exactly-once high-water, pad
rows) arrive with ``slot == scratch`` and weight 0: they are the
scratch row's group, read it (zeros → derived zeros) and write it, and
the program zeroes it before returning — by construction they cannot
mutate any key's state.

Over a mesh (a scorer from ``QuantizedScorer.on_mesh``) the same
``state_fn`` runs under ``shard_map`` on the data axis: forest
parameters replicated, the wire batch, the table and the four routing
operands sharded on their leading axis. A chip runs the forest and
``_state_step`` on its own piece of the table and its own records, with
LOCAL rows and a scratch row of its own (``KeyedStateTable.locate``;
the host sorts a dispatch by owner first, runtime/shuffle.py). No
collective is in the program (the sort is a chip's own) and no chip
sees another chip's rows; donation and the one in-place whole-row
scatter hold shard by shard (tests/test_v5e_compile.py).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from flink_jpmml_tpu.compile import common
from flink_jpmml_tpu.runtime.state import (
    COL_COUNT,
    COL_DCOUNT,
    COL_DSUM,
    COL_LAST_T,
    COL_MAX,
    COL_MIN,
    COL_SQSUM,
    COL_SUM,
    STATE_WIDTH,
)
from flink_jpmml_tpu.utils.exceptions import ModelCompilationException

# row written into freshly claimed slots before the batch gather:
# zero counts, ±inf extrema so the first min/max lands exactly
_INIT_ROW = (0.0, 0.0, 0.0, 0.0, 0.0, 0.0, float("inf"), float("-inf"))
# floor on the decayed-count denominator (a key whose decayed mass
# fully evaporated reads mean 0, not inf)
_DCOUNT_FLOOR = 1e-30


def _group_scan(op, x, idx, first):
    """Inclusive scan of ``x[..., B]`` with ``op`` inside each group of
    the sorted dispatch, in log2(B) shifted steps and no table access:
    record ``i`` takes in record ``i - d`` where that one is of its
    group (``first[i]`` is the group's first index). A group's total
    ends on its last record. The order of the partial results is a
    function of the sorted order alone."""
    d = 1
    while d < x.shape[-1]:
        x = jnp.where(idx - d >= first, op(x, jnp.roll(x, d, axis=-1)), x)
        d *= 2
    return x


def _state_step(S, score, slots, rel, w, reset, scratch, decay):
    """One batch's state transition (traced inside the dispatch jit).

    ``S[rows, 8]`` table · ``score[B]`` model outputs · ``slots[B]``
    row per record (``scratch`` = bypass) · ``rel[B]`` decay stride
    relative to the table epoch · ``w[B]`` product-form decay weight
    λ^-rel (0 for bypassed rows) · ``reset[B]`` fresh-slot marks →
    ``(derived[B, 8], S')``."""
    f32 = jnp.float32
    B = slots.shape[0]
    # the scopes are metadata on the traced ops (the op_name a device
    # trace shows them under); the lowered program is the same without
    with jax.named_scope("fjt.fold.gather"):
        idx = lax.iota(jnp.int32, B)
        # group the dispatch by slot: the slot and the record's index
        # are all the sort carries (what its compile costs: module
        # docstring), the other operands follow it by one gather
        slot_s, perm = lax.sort((slots, idx), num_keys=1, is_stable=False)
        ops = jnp.stack(
            [score.astype(f32), rel.astype(f32), w.astype(f32),
             reset.astype(f32)], axis=1,
        )[perm]
        score_s, rel_s, w_s, reset_s = (
            ops[:, 0], ops[:, 1], ops[:, 2], ops[:, 3] > 0
        )
        edge = slot_s[1:] != slot_s[:-1]
        start = jnp.concatenate([jnp.ones((1,), bool), edge])
        last = jnp.concatenate([edge, jnp.ones((1,), bool)])
        first = lax.cummax(jnp.where(start, idx, 0))
        end = lax.cummin(jnp.where(last, idx, B - 1), reverse=True)
        # a fresh slot re-initializes for its whole group, wherever in
        # it the mark sits: the nearest marked record before or after
        # lies inside the group
        fresh = (
            (lax.cummax(jnp.where(reset_s, idx, -1)) >= first)
            | (lax.cummin(jnp.where(reset_s, idx, B), reverse=True) <= end)
        )
        # THE read of the table: every record of a key reads its row as
        # of the batch's start, after the reset
        pre = jnp.where(
            fresh[:, None], jnp.asarray(_INIT_ROW, f32), S[slot_s]
        )
        count = pre[:, COL_COUNT]
        seen = count > 0
        safe = jnp.maximum(count, 1.0)
        mean = pre[:, COL_SUM] / safe
        var = jnp.maximum(pre[:, COL_SQSUM] / safe - mean * mean, 0.0)
        # product form: stored U = Σ λ^-rel_i, decayed count as of this
        # record's stride = U · λ^rel (≤ U); the decayed mean is the
        # ratio, where λ^rel cancels — epoch-independent by construction
        dcount = pre[:, COL_DCOUNT] * jnp.power(f32(decay), rel_s)
        dmean = pre[:, COL_DSUM] / jnp.maximum(
            pre[:, COL_DCOUNT], _DCOUNT_FLOOR
        )
        gap = rel_s - pre[:, COL_LAST_T]
        derived = jnp.stack(
            [count, mean, var, dcount, dmean, gap,
             pre[:, COL_MIN], pre[:, COL_MAX]],
            axis=1,
        )
        derived = jnp.where(seen[:, None], derived, f32(0.0))
        # back in arrival order, by the sort's inverse
        derived = derived[
            jnp.zeros_like(perm).at[perm].set(
                idx, unique_indices=True, mode="promise_in_bounds"
            )
        ]
    with jax.named_scope("fjt.fold.scatter"):
        # a group's totals, on its last record: float32 sums in the
        # sorted order (a count is the group's length, exact), the
        # extrema exact
        sums = _group_scan(
            jnp.add,
            jnp.stack([score_s, score_s * score_s, w_s, w_s * score_s]),
            idx, first,
        )
        tops = _group_scan(
            jnp.maximum, jnp.stack([rel_s, score_s]), idx, first
        )
        low = _group_scan(jnp.minimum, score_s, idx, first)
        new = {
            COL_COUNT: pre[:, COL_COUNT] + (idx - first + 1).astype(f32),
            COL_SUM: pre[:, COL_SUM] + sums[0],
            COL_SQSUM: pre[:, COL_SQSUM] + sums[1],
            COL_DCOUNT: pre[:, COL_DCOUNT] + sums[2],
            COL_DSUM: pre[:, COL_DSUM] + sums[3],
            COL_LAST_T: jnp.maximum(pre[:, COL_LAST_T], tops[0]),
            COL_MIN: jnp.minimum(pre[:, COL_MIN], low),
            COL_MAX: jnp.maximum(pre[:, COL_MAX], tops[1]),
        }
        # THE write: the last record of each group sets its row whole,
        # every other record's aim is off the table and dropped. A
        # whole-row set is the form the TPU keeps native and in place
        # (module docstring)
        S = S.at[jnp.where(last, slot_s, S.shape[0])].set(
            jnp.stack([new[c] for c in range(STATE_WIDTH)], axis=1),
            mode="drop",
        )
        # bypassed and pad records are the scratch row's group — zero
        # it so snapshots stay clean and the next batch's bypass reads
        # zeros
        S = S.at[scratch].set(jnp.zeros((STATE_WIDTH,), f32))
    return derived, S


def _score_of(out):
    """The scalar signal the state accumulates: the f32 value stream
    (classification outputs carry it as the triple's first element)."""
    return out[0] if isinstance(out, tuple) else out


def entry_for(q, kind: str, K: int, donate: bool,
              decay: float, scratch: int):
    """The state-armed jit entry for one QuantizedScorer →
    ``fn(params, X, S, slots, rel, w, reset) → (out, derived, S')``.

    ``kind`` selects the scoring body exactly as the stateless entries
    do — "wire" wraps the host-encoded kernel, "fused" the
    encode+score program — and ``K`` scan-chunks it for the Pallas
    fixed grid. Cached in the scorer's ``_multi_fns`` beside its
    stateless twins (``adopt_backend`` clears them together). For a
    scorer on a mesh, ``K`` counts a chip's chunks, ``scratch`` is the
    chip's own (``KeyedStateTable.local_scratch``) and the entry is
    the mesh form (module docstring)."""
    key = ("state", kind, int(K), bool(donate),
           int(scratch), float(decay))
    fn = q._multi_fns.get(key)
    if fn is not None:
        return fn
    if kind == "fused":
        if q._fused_inner is None:
            raise ModelCompilationException(
                "fused encode unavailable for this model; state "
                "dispatch needs the host-encode path"
            )
        base = q._fused_inner
    else:
        base = getattr(q._jit_fn, "__wrapped__", q._jit_fn)
    inner = base if K == 1 else q._scan_over(base, K)

    def state_fn(p, X, S, slots, rel, w, reset):
        with jax.named_scope("fjt.forest"):
            out = inner(p, X)
        derived, S2 = _state_step(
            S, _score_of(out), slots, rel, w, reset, scratch, decay
        )
        return out, derived, S2

    shardings = {}
    if getattr(q, "mesh", None) is not None:
        # shard_map keeps the function's name, so the module is still
        # jit_state_fn: device traces are read by it
        state_fn, (repl, data) = q.spmd(state_fn, 6), q.shardings()
        shardings = {
            "in_shardings": (repl,) + (data,) * 6, "out_shardings": data,
        }
    fn = jax.jit(
        state_fn, donate_argnums=(1, 2) if donate else (), **shardings
    )
    q._multi_fns[key] = fn
    return fn


def packed_entry(pack, donate: bool, decay: float, scratch: int,
                 member: int = 0):
    """PackedScorer twin: one launch scores ALL members and folds the
    designated ``member``'s value stream into the shared state table
    (the pack batch spans tenants over the SAME records; per-tenant
    state rides per-tenant tables on the solo path). →
    ``fn(params, Xp, S, slots, rel, w, reset) → (outs, derived, S')``
    with every member's output byte-identical to the stateless
    ``dispatch`` (the state stage only APPENDS ops)."""
    fns = getattr(pack, "_state_fns", None)
    if fns is None:
        fns = pack._state_fns = {}
    key = (int(member), bool(donate), int(scratch), float(decay))
    fn = fns.get(key)
    if fn is not None:
        return fn
    base = getattr(pack._jit_fn, "__wrapped__", pack._jit_fn)

    def state_fn(pps, Xp, S, slots, rel, w, reset):
        with jax.named_scope("fjt.forest"):
            outs = base(pps, Xp)
        derived, S2 = _state_step(
            S, _score_of(outs[member]), slots, rel, w, reset,
            scratch, decay,
        )
        return outs, derived, S2

    fn = jax.jit(
        state_fn, donate_argnums=(1, 2) if donate else ()
    )
    fns[key] = fn
    return fn


_renorm_fns = {}


def renorm_program(in_place: bool):
    """The jitted sweep ``S · mul + add`` over rows; ``in_place``
    donates the table (the output takes its buffer)."""
    fn = _renorm_fns.get(in_place)
    if fn is None:
        fn = _renorm_fns[in_place] = jax.jit(
            lambda s, m, a: s * m[None, :] + a[None, :],
            donate_argnums=(0,) if in_place else (),
        )
    return fn


def renorm(S, mul, add):
    """Epoch renormalization: ``S · mul + add`` broadcast over rows
    (one rare O(capacity) column op — see KeyedStateTable.maybe_renorm).

    A table in pieces over a mesh is swept IN PLACE (donated, wherever
    the backend honours donation): a sweep into a new buffer moves
    every chip's piece, the allocator puts it somewhere else each time,
    and the fold's per-record loop of the time (gone since PR 28) ran
    1–1.4% slower on some addresses than on others, so the chips' pace
    wandered from run to run and inside a run, stepping at every renorm
    (PERF.md §6, PR 27). The one-chip table's sweep is left as it was
    (its two buffers alternate between the same two places; ROADMAP
    S6)."""
    in_place = (
        isinstance(S, jax.Array)
        and len(S.sharding.device_set) > 1
        and not common.backend_is_cpu()
    )
    return renorm_program(in_place)(S, jnp.asarray(mul), jnp.asarray(add))
