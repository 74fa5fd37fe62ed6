"""Keyed per-record session state, device-resident and dispatch-fused.

ROADMAP item 3: real per-user serving (sessionization, decayed
counters, frequency capping) needs temporal context per key, and a
host-side dict lookup per record would crater the ~1M rec/s hot path
by orders of magnitude. The state plane keeps the per-key state vector
in ONE device buffer and fuses lookup → derive-features → score →
state-update into the existing scoring dispatch
(compile/statekernel.py): zero per-record host round-trips, one
dispatch per batch, O(1) memory per key.

Division of labor — host routes, device accumulates:

- **Host mirror (this module).** Slot assignment is open addressing
  over a fixed-capacity table, keyed by the SAME ``stable_hash`` the
  rollout split and lane routing use (``partitioner.stable_hash_vec``
  is its bit-identical vectorized twin), so canary/shard routing and
  state routing agree on every key by construction. The key → slot
  map (hashes, occupancy, LRU touch) lives in host numpy — it is
  metadata exactly like the ring's offsets — and ``assign_slots``
  resolves a whole batch in two steps (``route``). A record whose key
  is resident, found in its bounded linear probe window before any
  empty slot, is resolved by one native pass over the batch
  (``_native/fjt_native.cpp``, which also hashes the block's key
  column); the records it leaves go through rounds over their deduped
  keys (a second native call; vectorized numpy on a host that cannot
  build the library): probe the window, claim empties, evict the
  least-recently-touched slot when the window is full. A key that is
  not resident and finds its window full takes the slot of its window
  that was touched longest ago and not in this routing call; where
  several keys of one call want the same slot the smallest hash has
  it and the others choose again from what is left of their own
  windows, so a key that loses an eviction race keeps its state
  (``_claim_rounds`` states the rule in full). A key goes to the
  scratch row (``state_overflow``) only when every slot of its window
  was touched in this very call. Which step resolves a record follows
  from the mirror alone, and the rounds alone (the library missing)
  give the same answer. No device round trip is involved in routing.
- **Device values.** The table's VALUES — one fixed-width f32 vector
  per slot (counts, sums, decayed counters in product form, last-seen
  stride, min/max) — live in a single ``[rows, STATE_WIDTH]`` device
  buffer that only the fused kernel reads or writes: it groups the
  batch by slot, gathers each touched row once and sets it once, the
  group's adds, min and max folded in between. That is O(batch)
  only as far as the compiler keeps the scatter native: on the TPU
  the buffer is column-major, tiled ``T(8,128)`` (a slot's row is one
  lane of a tile), a whole-row scatter runs in place, and a write of
  part of a row used to cost a flat copy of the table, O(capacity) a
  dispatch, or a loop over the records (compile/statekernel.py says
  which write is which). The buffer is DONATED to each dispatch,
  so the update is in-place: steady-state state memory is one buffer,
  not one per in-flight batch.

Decayed counters ride in **product form**: a record at stride
``t = offset // stride`` contributes ``λ^(epoch - t)`` (≥ 1) to the
decayed count column, and the decayed value *as of* stride ``t`` is
``column · λ^(t - epoch)`` — a pure ADD per record, so updates
are order-independent and replay-exact, with a rare O(capacity)
renormalization sweep when the exponent range grows (``maybe_renorm``)
instead of an O(capacity) decay multiply per batch. Time is a pure
function of the record OFFSET, never of wall clock or batch shape, so
a checkpoint-restored replay derives byte-identical state.

Exactly-once state under at-least-once delivery: the snapshot records
``applied_hi`` (the highest offset folded into the table). On restore,
replayed records below it route to the scratch slot (read zeros, write
nothing) — state updates apply exactly once per offset even though the
sink may see the records twice. Shed batches never dispatch; DLQ'd /
recovery-path records score through the stateless entries — neither
ever mutates the table (the PR 8/12 never-delivered contract extended
to state).

Snapshots ride the PR 8 atomic-writer discipline: values + host mirror
in one ``.npz`` sidecar beside the checkpoints (tmp → fsync →
``os.replace`` → dir fsync), referenced by name from the checkpoint
JSON; the record path inlines a base64 payload for small tables. The
last snapshot is also kept in memory: a dispatch error with a donated
state buffer poisons the buffer, and ``rollback()`` restores the
snapshot (bounded, counted loss — ``state_rollbacks``) so the ladder
can keep serving statelessly.

Sharding: over a mesh the table is ONE table in ``D`` pieces, ``D``
the width of the mesh's data axis. A key's slot is what it always was
(probing from ``hash % capacity`` over the GLOBAL capacity), and chip
``d`` owns the global slots ``[d·R, (d+1)·R)``, ``R = ⌈capacity/D⌉``.
Each chip's piece of the buffer is ``shard_rows`` rows: its ``R`` slots,
then a scratch row of its own (local row ``R``: the pad rows and the
bypassed records of whatever the chip is handed land there), then zero
rows up to a multiple of 256. ``locate`` is that rule, global slot →
``(chip, local row)``, and the one place it is written down; with
``D = 1`` it is the identity and the buffer is the one-chip buffer. The
fold of a mesh runs under ``shard_map`` (compile/statekernel.py): a
chip sees its own piece and the local rows of its own records, which
the host sorts by owner before the dispatch (runtime/shuffle.py). A
table built with ``mesh=`` is born on the chips, zeros allocated per
shard and an empty rollback point: no table-sized host array on the
way. A degraded-mesh rebuild (``migrate``) changes ``D`` and therefore
the pieces, never a key's slot: rows move with their keys. Snapshots
hold the values slot-major, as a one-chip table lays them out, so one
taken at any ``D`` restores at any other.
"""

from __future__ import annotations

import base64
import contextlib
import io
import math
import os
from dataclasses import dataclass
from typing import Any, Callable, Dict, Optional

import numpy as np

from flink_jpmml_tpu.obs import attr
from flink_jpmml_tpu.obs import recorder as flight
from flink_jpmml_tpu.parallel.partitioner import stable_hash, stable_hash_vec
from flink_jpmml_tpu.runtime import native
from flink_jpmml_tpu.utils.exceptions import InputValidationException
from flink_jpmml_tpu.utils.metrics import MetricsRegistry

# one fixed-width state vector per key; the column layout is the
# kernel ABI (compile/statekernel.py) and the snapshot format
STATE_WIDTH = 8
COL_COUNT = 0      # records seen (add 1)
COL_SUM = 1        # sum of scores
COL_SQSUM = 2      # sum of score^2
COL_DCOUNT = 3     # decayed count, product form (add λ^-rel)
COL_DSUM = 4       # decayed score sum, product form
COL_LAST_T = 5     # last-seen stride relative to epoch (max)
COL_MIN = 6        # min score (+inf until first)
COL_MAX = 7        # max score (-inf until first)

# names of the DERIVED feature vector the fused kernel returns per
# record (the drift plane baselines these — state corruption is a
# drift alarm on the derived stream)
DERIVED_FIELDS = (
    "state_count", "state_mean", "state_var", "state_decayed_count",
    "state_decayed_mean", "state_gap", "state_min", "state_max",
)

# row padding of the buffer, and of each chip's piece of it: whole
# (8, 128) tiles of the chip's layout, whatever the capacity
_ROW_PAD = 256

_SNAPSHOT_VERSION = 1
_SNAPSHOT_KEEP = 3  # sidecar retention (the checkpoint writer keeps 3)
# payload-inline ceiling for the record path's checkpoint JSON: beyond
# this the table must snapshot to a sidecar file
_INLINE_CAP = 1 << 16


@dataclass(frozen=True)
class StateSpec:
    """Configuration of one keyed state table.

    ``key_col`` is the raw-batch column carrying the key on the block
    path (values are int-valued f32 — user/session ids); ``key_fn``
    extracts the key from a record on the record path (default: the
    ``key_field`` entry of a dict record). ``decay`` is the per-stride
    retention λ of the decayed counters — a record ``stride`` strides
    old weighs ``decay**strides``; one stride is ``stride`` record
    offsets, so decay half-lives are offset-denominated and replay
    deterministically."""

    capacity: int
    key_col: int = 0
    key_field: str = "key"
    key_fn: Optional[Callable[[Any], Any]] = None
    probe: int = 8
    decay: float = 0.999
    stride: int = 256

    def __post_init__(self):
        if self.capacity < 2:
            raise InputValidationException(
                f"state capacity must be >= 2: {self.capacity}"
            )
        if not (0.0 < self.decay < 1.0):
            raise InputValidationException(
                f"state decay must be in (0, 1): {self.decay}"
            )
        if self.probe < 1 or self.stride < 1:
            raise InputValidationException(
                "state probe and stride must be >= 1"
            )


_CAPACITY_ENV = "FJT_STATE_CAPACITY"
_PROBE_ENV = "FJT_STATE_PROBE"
_DECAY_ENV = "FJT_STATE_DECAY"
_STRIDE_ENV = "FJT_STATE_STRIDE"


def spec_from_env(capacity: int = 1 << 20, **overrides) -> StateSpec:
    """Build a :class:`StateSpec` from the ``FJT_STATE_*`` environment
    (bench/perf-smoke/fuzz sizing knobs; malformed values fall back to
    the defaults — tooling must not die on a typo'd env). Keyword
    overrides win over both."""

    def _env(name, cast, default):
        raw = os.environ.get(name)
        if raw:
            try:
                return cast(raw)
            except ValueError:
                pass
        return default

    kw = {
        "capacity": _env(_CAPACITY_ENV, int, capacity),
        "probe": _env(_PROBE_ENV, int, 8),
        "decay": _env(_DECAY_ENV, float, 0.999),
        "stride": _env(_STRIDE_ENV, int, 256),
    }
    kw.update(overrides)
    return StateSpec(**kw)


class KeyedStateTable:
    """Open-addressed device-resident per-key state (module docstring).

    One instance per pipeline; the score thread owns every call —
    single-threaded by the same contract as the ring."""

    def __init__(self, spec: StateSpec,
                 metrics: Optional[MetricsRegistry] = None,
                 mesh=None):
        self.spec = spec
        self.capacity = int(spec.capacity)
        self.scratch = self.capacity  # the bypass/padding slot
        self._mesh = None
        self._set_layout(1)
        # renorm trigger: keep λ^rel comfortably inside f32 —
        # exp(30) ≈ 1e13 of headroom against ~1e38
        self._renorm_every = max(
            16, min(4096, int(30.0 / -math.log(spec.decay)))
        )
        self.metrics = metrics or MetricsRegistry()
        m = self.metrics
        self._c_records = m.counter("state_records")
        self._c_hits = m.counter("state_hits")
        self._c_inserts = m.counter("state_inserts")
        self._c_evictions = m.counter("state_evictions")
        self._c_collisions = m.counter("state_collisions")
        self._c_overflow = m.counter("state_overflow")
        self._c_bypass = m.counter("state_bypass_records")
        # records ``route`` left to its claim rounds (0 on a stream of
        # resident keys: the native pass resolved every one)
        self._c_pending = m.counter("state_route_pending")
        self._ledger = attr.ledger_for(m)
        self._c_rollbacks = m.counter("state_rollbacks")
        self._g_resident = m.gauge("state_resident_keys")
        self._g_occupancy = m.gauge("state_occupancy_frac")
        self._g_hit_ratio = m.gauge("state_hit_ratio")
        # host mirror (routing metadata; never shipped per batch)
        self._keys = np.zeros(self.capacity, np.uint32)
        self._occ = np.zeros(self.capacity, bool)
        self._touch = np.zeros(self.capacity, np.int64)
        self._seq = 0
        self.resident = 0
        self.epoch = 0          # decay epoch, in strides
        self.applied_hi = 0     # exactly-once high-water (offsets)
        self.skip_until = 0     # restore sets: replayed offsets below
        # bypass the table (their updates already applied pre-crash)
        # bumped whenever routing done earlier stops being true of the
        # table (rollback, restore, a new layout): runtime/shuffle.py
        # re-routes what it holds
        self.generation = 0
        # slots claimed for records routed and not yet dispatched
        # (runtime/shuffle.py): a snapshot leaves them unclaimed
        self._unsettled = None
        self._bypass_depth = 0
        if mesh is not None and _data_width(mesh) > 1:
            # born on the chips: zeros allocated per shard, and the
            # rollback point is "empty" (None), not a copy
            self._mesh = mesh
            self._set_layout(_data_width(mesh))
            self.values = self._zeros_on_mesh()
            self._snap: Optional[Dict[str, Any]] = None
        else:
            # device values (numpy until first dispatch / shard())
            self.values = np.zeros((self.rows, STATE_WIDTH), np.float32)
            # in-memory rollback point (init = empty table)
            self._snap = self._host_snapshot()
            self.shard(mesh)
        # drift shims per model label (one handle set per model+table)
        self._shims: Dict[str, Any] = {}

    # -- layout ------------------------------------------------------------

    def _set_layout(self, n_shards: int) -> None:
        """One table in ``n_shards`` pieces (module docstring)."""
        self.n_shards = int(n_shards)
        self.shard_slots = -(-self.capacity // self.n_shards)
        self.shard_rows = _padded_rows(self.shard_slots)
        # a chip's own scratch row, as the fold on that chip knows it
        self.local_scratch = self.shard_slots
        self.rows = self.n_shards * self.shard_rows

    @property
    def mesh(self):
        """The mesh the value buffer is placed on (None: one device)."""
        return self._mesh

    def locate(self, slots):
        """Global slot → ``(chip, local row)``: chip ``d`` owns the
        slots ``[d·R, (d+1)·R)``, ``R = shard_slots``. The scratch slot
        belongs to no chip (whoever is handed the record uses its own):
        it comes back as chip 0, local row ``local_scratch``. With one
        shard this is the identity."""
        s = np.asarray(slots, np.int64)
        chip = s // self.shard_slots
        row = s - chip * self.shard_slots
        pad = s >= self.capacity
        if pad.any():
            chip = np.where(pad, 0, chip)
            row = np.where(pad, self.local_scratch, row)
        return chip.astype(np.int32), row.astype(np.int32)

    def read_rows(self, slots) -> np.ndarray:
        """The value rows of global ``slots`` on the host, through
        ``locate`` (blocks on in-flight updates)."""
        return self.read_local(*self.locate(slots))

    def read_local(self, chip, row) -> np.ndarray:
        """The value rows at ``(chip, local row)`` on the host, each
        read out of that chip's own piece of the buffer: no chip is
        asked for another's rows, and nothing table-sized moves."""
        chip, row = np.asarray(chip), np.asarray(row)
        if self.n_shards == 1:
            return np.asarray(self.values[row])
        out = np.zeros((chip.shape[0], STATE_WIDTH), np.float32)
        seen = set()
        for piece in self.values.addressable_shards:
            d = (piece.index[0].start or 0) // self.shard_rows
            mine = np.flatnonzero(chip == d)
            if d not in seen and mine.size:  # model-axis replicas agree
                out[mine] = np.asarray(piece.data[row[mine]])
            seen.add(d)
        return out

    # -- bypass ------------------------------------------------------------

    @property
    def bypassed(self) -> bool:
        """Is the table inside a stateless-scoring window (recovery
        redispatch, poison bisection)? Armed call sites check this and
        score through the stateless entries instead."""
        return self._bypass_depth > 0

    @contextlib.contextmanager
    def bypass(self):
        """Scope a stateless-scoring window: dispatches inside never
        touch the table (the recovery ladder and poison bisection both
        replay records — their scores must not double-apply state)."""
        self._bypass_depth += 1
        try:
            yield
        finally:
            self._bypass_depth -= 1

    # -- routing -----------------------------------------------------------

    def hash_keys(self, keys: np.ndarray) -> np.ndarray:
        """int64 keys → uint32 stable hashes (the lane-routing hash)."""
        return stable_hash_vec(np.asarray(keys, np.int64))

    def hash_records(self, records) -> np.ndarray:
        """Record-path twin: ``spec.key_fn`` (or the ``key_field`` of
        dict records) per record → uint32 stable hashes."""
        fn = self.spec.key_fn
        if fn is None:
            f = self.spec.key_field
            fn = lambda r: r.get(f, 0) if isinstance(r, dict) else r
        out = np.empty(len(records), np.uint32)
        for i, r in enumerate(records):
            out[i] = stable_hash(fn(r)) & 0xFFFFFFFF
        return out

    def extract_keys(self, X: np.ndarray) -> np.ndarray:
        """Block-path key column of a raw f32 batch → int64 keys."""
        col = np.asarray(X)[:, self.spec.key_col]
        return col.astype(np.int64)

    def hash_block(self, X: np.ndarray) -> np.ndarray:
        """Block-path key column of a raw f32 batch → uint32 stable
        hashes, ``hash_keys(extract_keys(X))`` bit for bit: one native
        pass over the column where it lies, where the library is built
        and ``X`` is a float32 block."""
        h = (
            native.state_hash_f32(X, self.spec.key_col)
            if native.available() else None
        )
        return self.hash_keys(self.extract_keys(X)) if h is None else h

    def assign_slots(self, khash: np.ndarray, offsets=None):
        """Resolve one batch of key hashes to table slots on the host:
        ``route`` (one native pass over the batch for the keys that are
        resident, the claim rounds for the rest), the
        exactly-once high-water and the decay operands — the per-batch
        routing cost beside the key hash.

        → ``(slots int32[B], reset bool[B], rel f32[B], w f32[B])``:
        ``slots`` are GLOBAL slots (``locate`` says where one lives;
        ``scratch`` for bypassed records), ``reset`` marks slots whose
        key is fresh this batch (the kernel re-initializes them before
        the gather), ``rel`` is the record's decay stride relative to
        the epoch and ``w`` its product-form weight λ^-rel. Replayed
        offsets below ``skip_until`` bypass (exactly-once state).

        Its three steps are public one by one for a caller that routes
        records before it knows which dispatch will fold them
        (runtime/shuffle.py): ``route``, ``mark_applied``,
        ``decay_operands``."""
        if offsets is None:
            B = np.asarray(khash).shape[0]
            offsets = np.arange(self.applied_hi, self.applied_hi + B,
                                dtype=np.int64)
        slots, reset, apply = self.route(khash, offsets)
        if apply.any():
            self.mark_applied(int(np.asarray(offsets)[apply].max()) + 1)
        rel, w = self.decay_operands(offsets, apply)
        return slots, reset, rel, w

    def mark_applied(self, hi: int) -> None:
        """Offsets below ``hi`` are folded (or riding a dispatch): the
        exactly-once high-water a snapshot records."""
        if hi > self.applied_hi:
            self.applied_hi = int(hi)

    def decay_operands(self, offsets, apply):
        """→ ``(rel f32[B], w f32[B])`` of records at ``offsets``
        against the epoch as it stands: the decay stride relative to it
        and the product-form weight λ^-rel, both 0 where ``apply`` is
        False."""
        offs = np.asarray(offsets, np.int64)
        rel_t = (offs // self.spec.stride) - self.epoch
        rel = np.where(apply, rel_t, 0).astype(np.float32)
        w = np.power(
            np.float32(self.spec.decay), -rel, dtype=np.float32
        )
        w = np.where(apply, w, np.float32(0.0)).astype(np.float32)
        return rel, w

    def route(self, khash: np.ndarray, offsets, held=None):
        """The slot resolution of ``assign_slots`` alone → ``(slots,
        reset, apply)``; ``apply`` is False for replayed offsets, which
        bypass. Claims and evicts in the host mirror and counts, and
        leaves ``applied_hi`` and the decay clock alone.

        A record whose key is resident (found in its probe window before
        any empty slot) is resolved by one native pass over the batch;
        what that pass leaves (fresh keys, a hole before the key's row,
        an exhausted window) goes through ``_claim_rounds``, as every
        record does where the native library cannot be built.
        ``state_route_pending`` counts the records left to the rounds,
        and the ledger's ``claim`` stage their time (inside the
        caller's ``route`` span). A key the rounds find no slot for in
        a full window evicts the least recently touched slot of that
        window that this call has not touched, and a key that loses
        such a slot to another key of the call chooses again: the rule
        is ``_claim_rounds``'. Only a key whose whole window this call
        has touched is answered with the scratch slot
        (``state_overflow``).

        ``held`` are the slots of records an earlier call routed and no
        dispatch has taken yet: they count as touched by this call, so
        no eviction of it hands one to another key (the held record
        would fold into that key's fresh row)."""
        khash = np.ascontiguousarray(khash, np.uint32)
        B = khash.shape[0]
        self._seq += 1
        seq = self._seq
        offs = np.asarray(offsets, np.int64)
        apply = offs >= self.skip_until
        n_bypass = B - int(np.count_nonzero(apply))
        slots = np.full(B, self.scratch, np.int32)
        reset = np.zeros(B, bool)
        collided = 0
        if B and native.available():
            # a hit changes neither ``_occ`` nor ``_keys`` and every
            # slot before it is occupied, so no claim or eviction below
            # can change what this resolved. It tells a key's first
            # record by a slot not yet stamped ``seq``: ``held`` is
            # stamped after it
            todo, collided = native.state_resolve(
                khash, apply, self._keys, self._occ, self._touch,
                self.spec.probe, seq, slots,
            )
        else:
            todo = np.flatnonzero(apply)
        if held is not None and len(held):
            hs = np.asarray(held, np.int64)
            self._touch[hs[hs < self.capacity]] = seq
        n_todo = todo.shape[0]
        if n_todo:
            # a stage of its own inside the caller's ``route`` span:
            # nothing is booked where the rounds do not run
            with self._ledger.span("claim", n=n_todo):
                slots[todo], reset[todo], c = self._claim_rounds(
                    khash[todo], seq)
            collided += c
            self._c_pending.inc(n_todo)
        if n_bypass < B:
            # a record with a slot applies, and one that is reset has a
            # slot: the hits are the rest of those with a slot
            self._c_hits.inc(int(np.count_nonzero(slots != self.scratch)
                                 - np.count_nonzero(reset)))
            self._c_collisions.inc(collided)
        self._c_records.inc(B)
        if n_bypass:
            self._c_bypass.inc(n_bypass)
        self._g_resident.set(float(self.resident))
        self._g_occupancy.set(self.resident / float(self.capacity))
        rec = self._c_records.value
        self._g_hit_ratio.set(
            self._c_hits.value / rec if rec else 0.0
        )
        return slots, reset, apply

    def _claim_rounds(self, khash: np.ndarray, seq: int):
        """Resolve records by vectorized rounds over their unique keys:
        probe a bounded linear window a slot a round (after the home
        slot a window that holds no empty slot is read whole: nothing
        can change in it), take a match, claim an empty, and evict
        from a window that is full →
        ``(slots int32, reset bool, collided)``, one of each a record,
        ``collided`` the unique keys not resolved at their home slot.

        **The eviction rule** (written down here and nowhere else). A
        routing call touches a slot when a record of it belongs to the
        key that lives there, when it gives the slot to a key, and when
        ``held`` names it; a touched slot carries the call's number as
        its stamp, newer than every stamp before. A key of the call
        that is not resident, and whose probe window has no empty slot,
        takes a slot from the key that lives there, whose row is reset
        before the gather:

        1. it names the slot of its own window that was touched longest
           ago, of those this call has not touched; of two slots with
           the same stamp, the one that comes first on the way from the
           key's home slot through its window;
        2. where several keys of the call name the same slot, the key
           with the smallest hash has it; the slot is then touched by
           this call and nobody's to name;
        3. every key that named a slot and did not get it names again,
           by 1, from what is left of its own window, until each key
           has a slot: losing a race costs a key nothing but its first
           choice;
        4. a key has nothing to name only when this call has touched
           every slot of its window. It alone is answered with the
           scratch slot, its records folded nowhere
           (``state_overflow``).

        An EMPTY slot is won as it always was: in probe order, one
        claimant a slot a round, the smallest hash first.

        Where the native library is built one call of it runs these
        rounds (``fjt_state_claim``); the numpy body below is the same
        rounds for a host without it, and what the tests hold the
        native form to, table and answers byte for byte."""
        if native.available():
            slots, reset, (ins, evicted, overflowed, collided) = (
                native.state_claim(
                    np.ascontiguousarray(khash, np.uint32), self._keys,
                    self._occ, self._touch, self.spec.probe, seq))
            self.resident += ins
            self._c_inserts.inc(ins)
            self._c_evictions.inc(evicted)
            self._c_overflow.inc(overflowed)
            return slots, reset, collided
        uk, inv = np.unique(khash, return_inverse=True)
        cap, probe = self.capacity, self.spec.probe
        base = uk.astype(np.int64) % cap
        slot_u = np.full(uk.shape[0], -1, np.int64)
        reset_u = np.zeros(uk.shape[0], bool)
        keys_h, occ, touch = self._keys, self._occ, self._touch
        collided = 0
        idx = np.arange(uk.shape[0])  # the keys still probing, ascending
        for p in range(probe):
            if not idx.size:
                break
            cand = (base[idx] + p) % cap
            taken = occ[cand]
            hit = taken & (keys_h[cand] == uk[idx])
            slot_u[idx[hit]] = cand[hit]
            # stamp at hit/claim time, not batch end: the evict
            # round must see THIS batch's slots as untouchable
            touch[cand[hit]] = seq
            e = np.flatnonzero(~taken)
            if e.size:
                # one claimant per empty slot per round (np.unique
                # keeps the first); losers keep probing
                _, first = np.unique(cand[e], return_index=True)
                win, c = idx[e[first]], cand[e[first]]
                slot_u[win] = c
                occ[c] = True
                keys_h[c] = uk[win]
                touch[c] = seq
                reset_u[win] = True
                self.resident += win.size
                self._c_inserts.inc(win.size)
            idx = idx[slot_u[idx] < 0]
            if p == 0:
                # catalogue semantic: home slot held by a DIFFERENT
                # key — a fresh key claiming its empty home slot is
                # not a collision, so count after the claim round
                collided = int(idx.size)
                # Whoever is off its home slot has its whole window
                # read at once. A window without an empty slot cannot
                # change in the rounds to come (they only claim
                # empties): its key is on the first slot that holds
                # its hash, or waits for the eviction; only a key that
                # sees an empty slot goes on a slot a round
                W = (base[idx, None] + np.arange(probe)[None, :]) % cap
                full = occ[W].all(axis=1)
                at = keys_h[W] == uk[idx, None]
                found = full & at.any(axis=1)
                s = W[found, at[found].argmax(axis=1)]
                slot_u[idx[found]] = s
                touch[s] = seq
                idx = idx[~full]
        # probe window exhausted: the eviction rule of the docstring
        pend = np.flatnonzero(slot_u < 0)
        W = (base[pend, None] + np.arange(probe)[None, :]) % cap
        while pend.size:
            # 1: argmin takes the first of equal stamps, in probe order
            vic = W[np.arange(pend.size), np.argmin(touch[W], axis=1)]
            named = touch[vic] < seq
            if not named.all():
                # 4: the oldest stamp of the window is this call's own
                self._c_overflow.inc(int(pend.size - named.sum()))
                pend, W, vic = pend[named], W[named], vic[named]
            # 2: ``pend`` ascends with the hash, and np.unique keeps
            # the first to name a slot
            _, first = np.unique(vic, return_index=True)
            win, c = pend[first], vic[first]
            keys_h[c] = uk[win]
            touch[c] = seq
            slot_u[win] = c
            reset_u[win] = True
            self._c_evictions.inc(win.size)
            # 3: the others go again
            lost = np.ones(pend.size, bool)
            lost[first] = False
            pend, W = pend[lost], W[lost]
        slot_r = np.where(slot_u >= 0, slot_u, np.int64(self.scratch))
        return slot_r[inv].astype(np.int32), reset_u[inv], collided

    def unclaim(self, slots) -> None:
        """Give back slots claimed by ``route`` for records that will
        never be folded (shed, served on a fallback tier): the reset
        that rode with them is lost, so the next record of such a key
        must claim again. A row nobody owns is re-initialized by
        whoever claims it next, so what was folded into it meanwhile
        cannot reach a key."""
        s = np.unique(np.asarray(slots, np.int64))
        s = s[s < self.capacity]
        s = s[self._occ[s]]
        self._occ[s] = False
        self.resident -= int(s.size)
        self._g_resident.set(float(self.resident))
        self._g_occupancy.set(self.resident / float(self.capacity))

    def hold_claims(self, slots) -> None:
        """Slots claimed for records routed but not yet dispatched
        (None: there are none). A snapshot taken meanwhile leaves them
        unclaimed: their rows are not reset yet."""
        self._unsettled = (
            None if slots is None or not len(slots)
            else np.unique(np.asarray(slots, np.int64))
        )

    def maybe_renorm(self, first_off: int) -> None:
        """Advance the decay epoch when the product-form exponents
        approach f32 range: multiply the decayed columns by λ^Δ and
        shift the last-seen strides by Δ (one O(capacity) device op,
        once per ``renorm_every`` strides — never per batch)."""
        t_first = int(first_off) // self.spec.stride
        delta = t_first - self.epoch
        if delta < self._renorm_every:
            return
        mul = np.ones(STATE_WIDTH, np.float32)
        mul[COL_DCOUNT] = mul[COL_DSUM] = np.float32(
            self.spec.decay
        ) ** np.float32(delta)
        add = np.zeros(STATE_WIDTH, np.float32)
        add[COL_LAST_T] = -np.float32(delta)
        from flink_jpmml_tpu.compile import statekernel

        self.values = statekernel.renorm(self.values, mul, add)
        self.epoch = t_first
        flight.record(
            "state_renorm", epoch=self.epoch, delta=delta,
        )

    # -- dispatch plumbing -------------------------------------------------

    def commit(self, new_values) -> None:
        """Adopt the fused dispatch's updated (donated-in-place) state
        buffer. The array may still be computing — the next dispatch
        chains on it device-side."""
        self.values = new_values

    def rollback(self) -> None:
        """Restore the last snapshot after a dispatch error poisoned
        the donated state buffer (bounded loss back to the snapshot;
        subsequent records re-enter cleanly)."""
        self._c_rollbacks.inc()
        snap = self._snap
        self.generation += 1
        self._unsettled = None
        if snap is None:
            # a table born on its mesh and never snapshotted: empty
            self._keys.fill(0)
            self._occ.fill(False)
            self._touch.fill(0)
            self.resident = self.epoch = self.applied_hi = 0
            self.values = self._zeros_on_mesh()
        else:
            self._keys = snap["keys"].copy()
            self._occ = snap["occ"].copy()
            self._touch = snap["touch"].copy()
            self.resident = int(snap["resident"])
            self.epoch = int(snap["epoch"])
            self.applied_hi = int(snap["applied_hi"])
            self._place(snap["values"])
        self.skip_until = max(self.skip_until, self.applied_hi)
        flight.record(
            "state_rollback", applied_hi=self.applied_hi,
            resident=self.resident,
        )

    # -- sharding / migration ---------------------------------------------

    def shard(self, mesh) -> None:
        """Place the table over ``mesh``: ``D`` pieces, ``D`` the width
        of its data axis (module docstring). Nothing to do where it
        lies there already; else the rows go through the host, which a
        table born on its mesh (``mesh=``) never pays."""
        if mesh is None:
            return
        if mesh == self._mesh and not isinstance(self.values, np.ndarray):
            return
        host = self._slot_major()
        self._mesh = mesh
        if _data_width(mesh) != self.n_shards:
            self._set_layout(_data_width(mesh))
            self.generation += 1
        self._place(host)

    def migrate(self, new_mesh) -> None:
        """Degraded-rebuild hook: re-place every row across the
        surviving chips. Slot = hash % capacity is mesh-independent,
        so chip loss moves state WITH its keys — no key loses its
        state vector (pinned in tests); which chip owns a slot follows
        the new width (``locate``)."""
        if new_mesh is None:
            return
        self.shard(new_mesh)
        flight.record(
            "state_migrate",
            data=_data_width(new_mesh),
            resident=self.resident,
        )

    def _zeros_on_mesh(self):
        """An empty value buffer, allocated on the mesh shard by
        shard."""
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P

        from flink_jpmml_tpu.parallel.mesh import DATA_AXIS

        return jax.jit(
            lambda: jnp.zeros((self.rows, STATE_WIDTH), jnp.float32),
            out_shardings=NamedSharding(self._mesh, P(DATA_AXIS, None)),
        )()

    def _slot_major(self) -> np.ndarray:
        """The values on the host as a one-chip table lays them out:
        slot ``s`` in row ``s``, zeros from the scratch row on."""
        v = np.asarray(self.values)
        if self.n_shards == 1:
            return v
        D, R = self.n_shards, self.shard_slots
        out = np.zeros(
            (_padded_rows(self.capacity), STATE_WIDTH), np.float32
        )
        out[: self.capacity] = v.reshape(D, self.shard_rows, STATE_WIDTH)[
            :, :R
        ].reshape(D * R, STATE_WIDTH)[: self.capacity]
        return out

    def _place(self, host: np.ndarray) -> None:
        """Adopt slot-major host values (``_slot_major``'s form, any
        row padding) under the layout and the mesh the table has."""
        if self._mesh is None:
            self.values = np.array(host, np.float32)
            return
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        from flink_jpmml_tpu.parallel.mesh import DATA_AXIS

        sharding = NamedSharding(self._mesh, P(DATA_AXIS, None))
        R, Rl = self.shard_slots, self.shard_rows
        cap = min(self.capacity, host.shape[0])

        def piece(idx):
            d = (idx[0].start or 0) // Rl
            out = np.zeros((Rl, STATE_WIDTH), np.float32)
            lo, hi = d * R, min((d + 1) * R, cap)
            if hi > lo:
                out[: hi - lo] = host[lo:hi]
            return out[:, idx[1]]

        self.values = jax.make_array_from_callback(
            (self.rows, STATE_WIDTH), sharding, piece
        )

    # -- snapshots ---------------------------------------------------------

    def _host_snapshot(self) -> Dict[str, Any]:
        occ, resident = self._occ.copy(), self.resident
        if self._unsettled is not None:
            # claimed for records no dispatch has folded yet: their
            # rows still hold what was there before (hold_claims)
            held = self._unsettled[occ[self._unsettled]]
            occ[held] = False
            resident -= int(held.size)
        values = self._slot_major()
        return {
            "version": _SNAPSHOT_VERSION,
            "capacity": self.capacity,
            "keys": self._keys.copy(),
            "occ": occ,
            "touch": self._touch.copy(),
            "resident": resident,
            "epoch": self.epoch,
            "applied_hi": self.applied_hi,
            "seq": self._seq,
            # slot-major, whatever the layout: restores at any width
            "values": values.copy() if self.n_shards == 1 else values,
        }

    def snapshot(self) -> Dict[str, Any]:
        """Materialize a consistent host snapshot (blocks on in-flight
        device updates — called on the score thread between batches)
        and pin it as the in-memory rollback point."""
        snap = self._host_snapshot()
        self._snap = snap
        return snap

    def save_sidecar(self, directory: str) -> Optional[str]:
        """Write the snapshot beside the checkpoints with the atomic-
        writer discipline (tmp → fsync → replace → dir fsync) →
        sidecar filename, or None when the write failed (checkpointing
        must degrade, not kill serving)."""
        snap = self.snapshot()
        name = f"state-{snap['applied_hi']:020d}.npz"
        path = os.path.join(directory, name)
        tmp = f"{path}.tmp-{os.getpid()}"
        try:
            os.makedirs(directory, exist_ok=True)
            with open(tmp, "wb") as f:
                np.savez(f, **_npz_payload(snap))
                f.flush()
                os.fsync(f.fileno())
            os.replace(tmp, path)
            dfd = os.open(directory, os.O_RDONLY)
            try:
                os.fsync(dfd)
            finally:
                os.close(dfd)
        except OSError:
            try:
                os.unlink(tmp)
            except OSError:
                pass
            return None
        self._gc_sidecars(directory, keep=name)
        return name

    @staticmethod
    def _gc_sidecars(directory: str, keep: str) -> None:
        try:
            snaps = sorted(
                f for f in os.listdir(directory)
                if f.startswith("state-") and f.endswith(".npz")
            )
        except OSError:
            return
        for f in snaps[:-_SNAPSHOT_KEEP]:
            if f != keep:
                try:
                    os.unlink(os.path.join(directory, f))
                except OSError:
                    pass

    def restore_sidecar(self, directory: str, name: str) -> bool:
        path = os.path.join(directory, name)
        try:
            with np.load(path) as z:
                snap = _from_npz(z)
        except (OSError, ValueError, KeyError):
            flight.record("state_restore_missing", file=name)
            return False
        return self._adopt_snapshot(snap)

    def to_payload(self) -> Dict[str, Any]:
        """Inline base64 snapshot for the record path's checkpoint
        JSON (small tables only — the block path uses sidecar files)."""
        if self.capacity > _INLINE_CAP:
            raise InputValidationException(
                f"state capacity {self.capacity} too large to inline "
                f"in a checkpoint (cap {_INLINE_CAP}); use a sidecar"
            )
        buf = io.BytesIO()
        np.savez(buf, **_npz_payload(self.snapshot()))
        return {
            "version": _SNAPSHOT_VERSION,
            "npz_b64": base64.b64encode(buf.getvalue()).decode("ascii"),
        }

    def from_payload(self, payload: Dict[str, Any]) -> bool:
        raw = payload.get("npz_b64")
        if not raw:
            return False
        try:
            with np.load(io.BytesIO(base64.b64decode(raw))) as z:
                snap = _from_npz(z)
        except (ValueError, KeyError):
            return False
        return self._adopt_snapshot(snap)

    def _adopt_snapshot(self, snap: Dict[str, Any]) -> bool:
        """→ False when the snapshot is refused (geometry mismatch):
        the caller must know the table stayed as it was — a True from
        a restore that silently no-opped would let replay double-fold
        decisions ride an empty table unnoticed."""
        if int(snap["capacity"]) != self.capacity:
            flight.record(
                "state_restore_mismatch",
                snapshot=int(snap["capacity"]), table=self.capacity,
            )
            return False
        self._keys = snap["keys"].astype(np.uint32)
        self._occ = snap["occ"].astype(bool)
        self._touch = snap["touch"].astype(np.int64)
        self.resident = int(snap["resident"])
        self.epoch = int(snap["epoch"])
        self._seq = int(snap.get("seq", 0))
        self.applied_hi = int(snap["applied_hi"])
        # exactly-once: replayed offsets below the snapshot's
        # high-water were already folded in — bypass them
        self.skip_until = self.applied_hi
        self.generation += 1
        self._unsettled = None
        values = snap["values"].astype(np.float32)
        rows1 = _padded_rows(self.capacity)
        if values.shape != (rows1, STATE_WIDTH):
            # snapshot from a different row padding: re-pad
            v = np.zeros((rows1, STATE_WIDTH), np.float32)
            n = min(values.shape[0], rows1)
            v[:n] = values[:n]
            values = v
        self._place(values)
        self._snap = self._host_snapshot()
        self._g_resident.set(float(self.resident))
        self._g_occupancy.set(self.resident / float(self.capacity))
        flight.record(
            "state_restore", applied_hi=self.applied_hi,
            resident=self.resident,
        )
        return True

    # -- drift on derived features ----------------------------------------

    def drift_shim(self, model_hash: Optional[str]):
        """A ``record_features``-compatible handle for the DERIVED
        feature stream: ``<model_hash>#state`` shares the model's
        content addressing, so a recompile keeps the same baseline
        and state corruption surfaces as feature drift."""
        label = f"{model_hash or 'state'}#state"
        shim = self._shims.get(label)
        if shim is None:
            shim = _DriftShim(label)
            self._shims[label] = shim
        return shim


class _DerivedWire:
    """Minimal wire facade over the derived feature vector: names for
    the drift handles, cut-less domains (derived features have no
    threshold tables — out-of-domain never fires)."""

    fields = DERIVED_FIELDS
    cuts = [[] for _ in DERIVED_FIELDS]


class _DriftShim:
    __slots__ = ("model_hash", "wire")

    def __init__(self, label: str):
        self.model_hash = label
        self.wire = _DerivedWire()


def _padded_rows(slots: int) -> int:
    """Rows of a buffer (or of one chip's piece of it) that holds
    ``slots`` slots: a scratch row more, up to a multiple of 256."""
    return -(-(slots + 1) // _ROW_PAD) * _ROW_PAD


def _data_width(mesh) -> int:
    from flink_jpmml_tpu.parallel.mesh import DATA_AXIS

    return int(mesh.shape.get(DATA_AXIS, 1))


def _npz_payload(snap: Dict[str, Any]) -> Dict[str, np.ndarray]:
    return {
        "version": np.int64(snap["version"]),
        "capacity": np.int64(snap["capacity"]),
        "keys": snap["keys"],
        "occ": snap["occ"],
        "touch": snap["touch"],
        "resident": np.int64(snap["resident"]),
        "epoch": np.int64(snap["epoch"]),
        "applied_hi": np.int64(snap["applied_hi"]),
        "seq": np.int64(snap["seq"]),
        "values": snap["values"],
    }


def _from_npz(z) -> Dict[str, Any]:
    return {
        "version": int(z["version"]),
        "capacity": int(z["capacity"]),
        "keys": z["keys"],
        "occ": z["occ"],
        "touch": z["touch"],
        "resident": int(z["resident"]),
        "epoch": int(z["epoch"]),
        "applied_hi": int(z["applied_hi"]),
        "seq": int(z["seq"]),
        "values": z["values"],
    }


def is_state_output(out) -> bool:
    """Is ``out`` a fused-state dispatch result ``(score_out,
    derived)``? Unambiguous: a regression score is 1-D, a
    classification output is a 3-tuple — never a 2-tuple whose second
    element is a ``[B, STATE_WIDTH]`` matrix."""
    return (
        type(out) is tuple
        and len(out) == 2
        and getattr(out[1], "ndim", 0) == 2
        and out[1].shape[-1] == STATE_WIDTH
        and (type(out[0]) is tuple or getattr(out[0], "ndim", 0) == 1)
    )


def split_output(out):
    """→ ``(score_out, derived_or_None)``."""
    if is_state_output(out):
        return out[0], out[1]
    return out, None


def record_derived(dplane, table: KeyedStateTable,
                   model_hash: Optional[str], derived, n: int) -> None:
    """Feed one batch's derived session features to the drift plane
    (sampled + budgeted inside ``record_features`` — the D2H fetch
    happens only for claimed batches)."""
    if dplane is None or derived is None or not n:
        return
    shim = table.drift_shim(model_hash)
    try:
        dplane.record_features(shim, np.asarray(derived)[:n], None)
    except Exception:
        pass  # observability must never kill delivery
