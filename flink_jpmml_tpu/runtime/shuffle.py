"""The keyed shuffle of a mesh: Flink's ``keyBy``, done by the score
thread.

A state table over ``D`` chips (runtime/state.py) is one table in ``D``
pieces, and a chip folds only records whose rows it owns. So before a
state-armed dispatch leaves the host, its records are sorted by owner:
``D`` buckets of one fixed size ``C`` (a whole number of the model's
scan chunks), each padded to ``C`` on that chip's own scratch row at
weight 0. The wire payload and the routing operands go to the device as
``[D·C, …]`` sharded on the data axis, so a chip receives its bucket
and nothing else, and the program (compile/statekernel.py, the mesh
form) needs no collective. Scores and derived rows come back in bucket
order and are put back in offset order before the sink
(``ShardPlan.unshard``).

**A bucket that fills cuts the dispatch.** Keys are skewed, so one
chip's bucket fills first. A dispatch takes the longest prefix of the
pending offsets in which no chip's bucket exceeds the largest ``C`` the
pipeline compiled; the rest stays pending, in arrival order, and leads
the next dispatch. Nothing is dropped, reordered at the sink or handed
to a chip that does not own its row, whatever the skew (one key may be
all of a dispatch: it then moves ``C`` records a dispatch). Device
shapes are the few bucket sizes (chunks a chip: powers of two up to
``max_dispatch_chunks // D``), whatever a dispatch holds.

Who owns a record is known only once its key has a slot, so records
are routed (``KeyedStateTable.route``) when they are drained, not when
they are dispatched. What is routed and still pending is the table's
business in four places, each one call here: ``mark_applied`` moves
the exactly-once high-water only when a dispatch takes the records;
``route(held=)`` keeps a later routing from evicting a slot a pending
record points to; ``hold_claims`` keeps slots claimed for pending
records out of a snapshot; and where the table goes back to a snapshot
or changes its layout (``generation``), everything pending is routed
again. A dispatch that is planned and then never launched (shed, served
by a fallback tier) gives its fresh claims back (``abandon``).
"""

from __future__ import annotations

from typing import Optional, Sequence

import numpy as np


class ShardPlan:
    """One dispatch sorted by owner: where each record sits in the
    ``[D·C]`` bucket layout (``dest``), the bucketed routing operands,
    and each chip's count."""

    __slots__ = (
        "n", "first_off", "chunks", "bucket", "n_shards", "dest",
        "counts", "cut", "applied_hi", "fresh", "slots", "rel", "w",
        "reset",
    )

    def place(self, rows: np.ndarray) -> np.ndarray:
        """The dispatch's rows (offset order) in bucket order, pad rows
        zero."""
        out = np.zeros(
            (self.n_shards * self.bucket,) + rows.shape[1:], rows.dtype
        )
        out[self.dest] = rows[: self.n]
        return out

    def unshard(self, out):
        """A device output in bucket order → host arrays in offset
        order, ``n`` long (a classification triple leaf by leaf)."""
        if isinstance(out, tuple):
            return tuple(self.unshard(o) for o in out)
        return np.asarray(out)[self.dest]


def _owners(table, slots: np.ndarray):
    """→ (chip, local row) of each record; a record on the scratch slot
    (bypassed, overflowed) goes to a chip by its position."""
    chip, row = table.locate(slots)
    pad = slots >= table.capacity
    if pad.any():
        chip = np.where(
            pad, np.arange(slots.shape[0]) % table.n_shards, chip
        )
    # a byte a record: numpy sorts bytes by counting, in O(n)
    return chip.astype(np.uint8), row


def _ranks(chip: np.ndarray, n_shards: int) -> np.ndarray:
    """A record's place among the records of its chip, in arrival
    order."""
    order = np.argsort(chip, kind="stable")
    counts = np.bincount(chip, minlength=n_shards)
    rank = np.empty(chip.shape[0], np.int64)
    rank[order] = np.arange(chip.shape[0]) - np.repeat(
        np.cumsum(counts) - counts, counts
    )
    return rank


def make_plan(table, slots, reset, rel, w, chunk: int,
              sizes: Optional[Sequence[int]] = None,
              first_off: int = 0, owners=None) -> ShardPlan:
    """Bucket ALL of ``slots`` (global, one a record, in offset order)
    by owner. ``sizes`` are the chunk counts a chip's bucket may have
    (ascending; the caller has made sure the largest fits); None takes
    as many chunks as the fullest chip needs. ``owners`` is ``(chip,
    local row, rank in chip)`` where the caller has them already."""
    n, D = int(slots.shape[0]), table.n_shards
    if owners is None:
        chip, row = _owners(table, slots)
        rank = _ranks(chip, D)
    else:
        chip, row, rank = owners
    counts = np.bincount(chip, minlength=D)
    need = max(1, -(-int(counts.max(initial=0)) // chunk))
    if sizes is not None:
        need = next(k for k in sizes if k >= need)
    p = ShardPlan()
    p.n, p.first_off, p.n_shards = n, int(first_off), D
    p.chunks, p.bucket = need, need * chunk
    p.counts, p.cut, p.applied_hi = counts, False, None
    p.dest = chip.astype(np.int64) * p.bucket + rank
    p.fresh = np.asarray(slots)[reset]
    total = D * p.bucket
    p.slots = np.full(total, table.local_scratch, np.int32)
    p.slots[p.dest] = row
    p.rel = np.zeros(total, np.float32)
    p.rel[p.dest] = rel
    p.w = np.zeros(total, np.float32)
    p.w[p.dest] = w
    p.reset = np.zeros(total, bool)
    p.reset[p.dest] = reset
    return p


class _Held:
    """Records drained and not yet dispatched, with their routing."""

    def __init__(self, cap: int, arity: int):
        self.X = np.empty((cap, arity), np.float32)
        self.offs = np.empty(cap, np.int64)
        self.slots = np.empty(cap, np.int32)
        self.reset = np.empty(cap, bool)
        self.apply = np.empty(cap, bool)

    def move(self, other: "_Held", lo: int, hi: int) -> None:
        for name in ("X", "offs", "slots", "reset", "apply"):
            getattr(other, name)[: hi - lo] = getattr(self, name)[lo:hi]


class KeyShuffle:
    """What a pipeline holds between its ring and a state-armed mesh
    dispatch (module docstring). One thread owns it: the score thread.

    ``chunk`` is the model's batch size, ``sizes`` the chunk counts a
    chip's bucket may have (ascending powers of two), ``target`` the
    records a dispatch may hold in all."""

    def __init__(self, table, arity: int, chunk: int,
                 sizes: Sequence[int], target: int, metrics):
        self.table = table
        self.chunk, self.sizes = int(chunk), tuple(sizes)
        self.target = int(target)
        cap = self.target + self.chunk
        self._held = (_Held(cap, arity), _Held(cap, arity))
        self._cur = 0
        self.pending = 0      # records held
        self._routed = 0      # of which routed, from the front
        self._gen = table.generation
        self._c_pad = metrics.counter("mesh_bucket_pad_records")
        self._c_cuts = metrics.counter("mesh_dispatch_cuts")
        self._c_slots = metrics.counter("mesh_bucket_slots")

    @property
    def room(self) -> int:
        return self.target - self.pending

    def feed(self, X: np.ndarray, offsets: np.ndarray) -> None:
        """Take a drained block (a view of the ring's buffer: copied)."""
        h, lo = self._held[self._cur], self.pending
        hi = lo + X.shape[0]
        h.X[lo:hi] = X
        h.offs[lo:hi] = offsets
        self.pending = hi

    def take(self, ledger):
        """→ ``(X, offsets, n, plan)``: the next dispatch, the longest
        prefix of what is held that its buckets can take; ``n == 0``
        where nothing is held. The arrays are views that stay whole
        until the take after next."""
        h, n, t = self._held[self._cur], self.pending, self.table
        if n == 0:
            return h.X[:0], h.offs[:0], 0, None
        first_off = int(h.offs[0])
        if t.generation != self._gen:
            # the table went back to a snapshot or changed its layout:
            # what was routed before is no longer true of it
            self._gen, self._routed = t.generation, 0
        lo = self._routed
        with ledger.span("route", first_off=first_off, n=n - lo):
            if lo < n:
                # the tail of a cut dispatch keeps the slots it has
                h.slots[lo:n], h.reset[lo:n], h.apply[lo:n] = t.route(
                    t.hash_block(h.X[lo:n]), h.offs[lo:n],
                    held=h.slots[:lo],
                )
                self._routed = n
            t.maybe_renorm(first_off)
        with ledger.span("shard", first_off=first_off) as sp:
            chip, row = _owners(t, h.slots[:n])
            rank = _ranks(chip, t.n_shards)
            full = rank >= self.sizes[-1] * self.chunk
            take = int(np.argmax(full)) if full.any() else n
            cut = take < n
            gap = np.flatnonzero(np.diff(h.offs[:take]) != 1)
            if gap.size:
                # one dispatch is one contiguous range of offsets
                take = int(gap[0]) + 1
            applied = h.apply[:take]
            rel, w = t.decay_operands(h.offs[:take], applied)
            plan = make_plan(
                t, h.slots[:take], h.reset[:take], rel, w, self.chunk,
                self.sizes, first_off,
                owners=(chip[:take], row[:take], rank[:take]),
            )
            plan.cut = cut
            if applied.any():
                plan.applied_hi = int(h.offs[:take][applied].max()) + 1
            # what is left leads the next dispatch, from the other buffer
            o, tail = self._held[1 - self._cur], n - take
            h.move(o, take, n)
            if tail and plan.fresh.size:
                # a key claimed in this routing is reset by the first
                # dispatch that folds a record of it, and by no other
                o.reset[:tail] &= ~np.isin(o.slots[:tail], plan.fresh)
            t.hold_claims(o.slots[:tail][o.reset[:tail]] if tail else None)
            self._cur ^= 1
            self.pending = self._routed = tail
            sp.note(n=take, cut=cut)
        total = plan.n_shards * plan.bucket
        self._c_slots.inc(total)
        self._c_pad.inc(total - take)
        if cut:
            self._c_cuts.inc()
        return h.X[:take], h.offs[:take], take, plan

    def abandon(self, plan: Optional[ShardPlan]) -> None:
        """A planned dispatch that is never launched: its fresh claims
        go back to the table."""
        if plan is not None and plan.fresh.size:
            self.table.unclaim(plan.fresh)
