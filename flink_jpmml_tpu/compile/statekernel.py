"""The fused state stage: lookup → derive → score → update, one jit.

This module grafts the keyed state plane (runtime/state.py) into the
EXISTING scoring dispatch. A state-armed dispatch runs ONE compiled
program per batch:

    out            = member kernel(params, X)        # unchanged
    derived[B, 8]  = gather(S, slots) → session features
    S'             = scatter-add/min/max(S, slots, f(out, w, rel))

The state stage is pure XLA gather/scatter over the batch's slot
vector, appended to the scoring program, and composes with EVERY
backend the scorer already has: XLA, Pallas (the state ops wrap the
scan-chunked kernel, outside the Pallas grid), fused-encode, and
cross-model packs. No new Pallas kernel is warranted — but the work is
O(batch) only where a write covers WHOLE ROWS. On the chip the table
is column-major, ``f32[rows, 8]{0,1:T(8,128)}``: one slot's row is one
lane of an (8, 128) tile. What the TPU compiler makes of a table write
(read off a v5e compile; tests/test_v5e_compile.py, PERF.md §5):

- a scatter of whole rows (``S.at[slots].set/.max/.min(rows8)``) stays
  a native scatter, in place on the donated buffer: ~5.5 ms for 65,536
  records at 200M slots, duplicates and all;
- a scatter into ONE column (``S.at[slots, c].max``, what last_t, min
  and max were until PR 25) flattens the table a column at a time into
  ``f32[rows * 8]``, scatters there and copies back: O(table) a
  dispatch, 9.6 GB of temporaries, ~430 of a dispatch's 665 ms. Those
  three are now two whole-row scatters whose other columns carry the
  operation's identity (−inf for max, +inf for min);
- a scatter into a SLICE of columns (what the add of the five
  accumulator columns was until PR 28) is expanded to a ``while`` of
  one iteration a record, ~3.5 µs each: ~230 ms for 65,536 records.
  The add is now a whole-row scatter too, its other three columns
  carrying −0.0 (``x + (−0.0)`` is ``x`` for every float32, −0.0 and
  ±inf included; +0.0 would turn a stored −0.0 into +0.0): no
  ``while`` is left in the program.

Batch-consistent read semantics: every record's DERIVED features
reflect the table as of the BATCH start (one gather before the
batch's updates commit), and the updates themselves are scatter-ADD /
-MIN / -MAX with product-form decay weights — commutative and
associative, so the committed state is independent of record order
within the batch and replay-exact across restarts (the checkpoint
parity pin in bench --stateful).

Donation: when the caller donates, BOTH the staged batch and the state
buffer are donated (``donate_argnums=(1, 2)``) — the state update is
in-place on device, so steady-state state memory is one ``[rows, 8]``
buffer regardless of dispatch depth.

Bypassed records (shed replay below the exactly-once high-water, pad
rows) arrive with ``slot == scratch`` and weight 0: they read the
scratch row (zeros → derived zeros) and their scatter contributions
land on the scratch row, which the program zeroes before returning —
by construction they cannot mutate any key's state.

Over a mesh (a scorer from ``QuantizedScorer.on_mesh``) the same
``state_fn`` runs under ``shard_map`` on the data axis: forest
parameters replicated, the wire batch, the table and the four routing
operands sharded on their leading axis. A chip runs the forest and
``_state_step`` on its own piece of the table and its own records, with
LOCAL rows and a scratch row of its own (``KeyedStateTable.locate``;
the host sorts a dispatch by owner first, runtime/shuffle.py). No
collective is in the program and no chip sees another chip's rows;
donation and the in-place whole-row scatters hold shard by shard
(tests/test_v5e_compile.py).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

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


def _rows(like, identity, cols):
    """``[B, STATE_WIDTH]`` update rows for one whole-row scatter:
    ``cols`` maps a column to its ``[B]`` values, every other column
    carries the scatter operation's ``identity``."""
    fill = jnp.full_like(like, identity)
    return jnp.stack(
        [cols.get(c, fill) for c in range(STATE_WIDTH)], axis=1
    )


def _state_step(S, score, slots, rel, w, reset, scratch, decay):
    """One batch's state transition (traced inside the dispatch jit).

    ``S[rows, 8]`` table · ``score[B]`` model outputs · ``slots[B]``
    row per record (``scratch`` = bypass) · ``rel[B]`` decay stride
    relative to the table epoch · ``w[B]`` product-form decay weight
    λ^-rel (0 for bypassed rows) · ``reset[B]`` fresh-slot marks →
    ``(derived[B, 8], S')``."""
    f32 = jnp.float32
    score = score.astype(f32)
    rel = rel.astype(f32)
    w = w.astype(f32)
    # the scopes are metadata on the traced ops (the op_name a device
    # trace shows them under); the lowered program is the same without
    with jax.named_scope("fjt.fold.gather"):
        init = jnp.asarray(_INIT_ROW, f32)
        # fresh slots re-initialize; rows with nothing to reset aim the
        # write at the scratch row (re-zeroed at the end regardless)
        sel = jnp.where(reset, slots, scratch)
        S = S.at[sel].set(init)
        pre = S[slots]
        count = pre[:, COL_COUNT]
        seen = count > 0
        safe = jnp.maximum(count, 1.0)
        mean = pre[:, COL_SUM] / safe
        var = jnp.maximum(pre[:, COL_SQSUM] / safe - mean * mean, 0.0)
        # product form: stored U = Σ λ^-rel_i, decayed count as of this
        # record's stride = U · λ^rel (≤ U); the decayed mean is the
        # ratio, where λ^rel cancels — epoch-independent by construction
        dcount = pre[:, COL_DCOUNT] * jnp.power(f32(decay), rel)
        dmean = pre[:, COL_DSUM] / jnp.maximum(
            pre[:, COL_DCOUNT], _DCOUNT_FLOOR
        )
        gap = rel - pre[:, COL_LAST_T]
        derived = jnp.stack(
            [count, mean, var, dcount, dmean, gap,
             pre[:, COL_MIN], pre[:, COL_MAX]],
            axis=1,
        )
        derived = jnp.where(seen[:, None], derived, f32(0.0))
    with jax.named_scope("fjt.fold.scatter"):
        # commutative scatter updates. The extrema are whole-row
        # scatters, native and in place on the TPU (module docstring):
        # a column an operation does not touch carries that operation's
        # identity — max(x, -inf) and min(x, +inf) are exact, also on a
        # fresh row's ±inf. The add's identity is -0.0, not 0.0:
        # x + (-0.0) is x bit for bit, a stored -0.0 minimum included
        S = S.at[slots].add(_rows(score, -0.0, {
            COL_COUNT: jnp.ones_like(score), COL_SUM: score,
            COL_SQSUM: score * score, COL_DCOUNT: w, COL_DSUM: w * score,
        }))
        S = S.at[slots].max(_rows(
            score, -jnp.inf, {COL_LAST_T: rel, COL_MAX: score}
        ))
        S = S.at[slots].min(_rows(score, jnp.inf, {COL_MIN: score}))
        # bypass/pad contributions all landed on the scratch row — zero
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
