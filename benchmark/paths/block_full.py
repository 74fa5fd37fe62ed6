"""Path builder ``block_full``: ``paths/block.py``'s wiring (the same
pipeline, table and programs: nothing of it is repeated here) with a
``fill_table`` of its own, for a deployment whose state table is FULL
when the run starts and whose key population moves (key mix ``latest``,
``lib/keymix/latest.py``: a key's rank is its age).

The table after ``fill_table``: every slot holds a key of the loaded
population (ranks below ``latest.loaded_of``), each within ``probe``
slots of ``hash % capacity`` (so the program's own lookup finds it: in
a table without an empty slot a key may sit anywhere in its window), the
newer ranks preferred; each slot's LRU stamp is ``1 + rank //
STAMP_GRAIN`` of its key (as the routing call that last touched the
key would have stamped it, had every call brought that many ranks of
recency; the table's next routing call
counts on from the newest stamp), every stamp written; and every row
written from the seed as ``lib/prefill.device_table`` writes it. That
is what a rank of the fleet looks like once it has been up longer than
its table takes to fill: the program never frees a slot.

``fill_full`` builds it in numpy, chunk by chunk on a few threads
(61 s for 300M keys on the chip's host), in four steps:

1. **hashes** of the loaded ranks (``prefill.crc32_of_ids``, held to
   the table's ``hash_keys`` on a sample), and of the ranks that share
   a hash only the newest: the table knows a key by its hash;
2. **one sort** by home block, the newest first inside a block. A block
   is half a window, 32 slots, so a key whose home lies in block ``k``
   is in reach of every slot of block ``k + 1``;
3. **block by block**: the slots of block ``k + 1`` go to the newest
   keys of home block ``k``, in an order turned by the block's number.
   Each neighbourhood keeps ITS newest keys, as eviction of the least
   recently touched slot of a window does; a key sits 1 to 63 slots from
   home, 32 in the mean;
4. **slot by slot** for what a thin home block left (one slot in a
   thousand): each takes a key not yet placed whose home lies in the
   window up to it, found through a table home → the newest such key
   (made anew around the holes that are left whenever it has given its
   one key a home away);
   and for the last handful, in whose reach no such key is left, the
   hole hops: it takes the key farthest ahead of it that may come back
   that far (or, failing that, the one farthest behind that may go on)
   and so moves to where keys are to spare.

A population that cannot fill the table (``rehearse.py`` lays a domain
of 45,000 over 61,001 slots on every cell) goes through the table's
public ``assign_slots``, oldest first, and is stamped the same way;
nothing is evicted there.

What is kept for the warm-up check (``warmup_checks/full_table_churn``)
is the benchmark's own record of the fill, ``Path.placed`` (per slot the
hash written there, whether one was, and its LRU stamp: the plain
reference's table at the first record), and ``Path.calls``, the
``(first offset, records)`` of every dispatch the sink received while
that record is kept: one dispatch is one routing call. The check takes
both and lets them go (``release``). The reaches into the mirror are
``lib/prefill.py``'s (``_keys``, ``_occ``, ``_touch``, ``_seq``,
``resident``): there is no public way to give a restored table its LRU
order short of a 6.4 GB ``.npz`` either.

The harness hands ``fill_table`` a plan of the configuration's
``resident_keys_at_start`` FIRST ranks (``run.py``: ``prefill.plan_fill``
on a thread before this file is loaded); a full table holds the newest
ranks, so the plan is dropped here, the configuration keeps that number
small, and what this fill makes resident is held to ``table_slots``
instead: every slot.
"""

from __future__ import annotations

import concurrent.futures
import json
import sys
import time

import numpy as np

from lib import byname, prefill
from lib import keys as keys_mod

latest = byname.load("lib/keymix", "latest")
CHUNK = 1 << 21
THREADS = 8
MAX_HOPS = 512  # of the last holes, each up to a window's length
STAMP_GRAIN = 65536  # ranks of recency a stamp: a dispatch's records at most


def _race_keeps_state() -> bool:
    """Does this checkout's program keep the state of a key that loses
    an eviction race? Two fresh keys with one home, a full window of
    three slots of which a third key's record has just touched one: the
    fresh keys want the same least-recently-touched victim, and each has
    to come away with a slot."""
    from flink_jpmml_tpu.runtime.state import KeyedStateTable, StateSpec

    t = KeyedStateTable(StateSpec(capacity=8, probe=3))
    t.route(np.array([0, 8, 16], np.uint32), np.arange(3))
    t.route(np.array([16], np.uint32), np.array([3]))
    slots, _, _ = t.route(np.array([16, 24, 32], np.uint32), np.arange(4, 7))
    return bool((slots != t.scratch).all())


def _homes(h: np.ndarray, cap: int) -> np.ndarray:
    return (h % np.uint32(cap)).astype(np.int64) if cap < 2 ** 32 else (
        h.astype(np.int64))


def _runs(starts: np.ndarray, lengths: np.ndarray) -> np.ndarray:
    """→ the indices ``starts[i] .. starts[i] + lengths[i] - 1``, run
    after run."""
    ends = np.cumsum(lengths)
    return np.repeat(starts - (ends - lengths), lengths) + np.arange(
        ends[-1] if ends.size else 0)


def _each(fn, starts) -> list:
    """``fn(start)`` for every start, a few at a time on threads:
    numpy's look-ups, sorts and integer arithmetic leave the
    interpreter lock, and the passes below write disjoint ranges."""
    with concurrent.futures.ThreadPoolExecutor(THREADS) as pool:
        return list(pool.map(fn, starts))


def _hashes(loaded: int) -> np.ndarray:
    """The table's hash of every loaded rank."""
    H = np.empty(loaded, np.uint32)

    def chunk(lo):
        H[lo:lo + CHUNK] = prefill.crc32_of_ids(
            prefill.ids_of_ranks(np.arange(lo, min(lo + CHUNK, loaded))))

    _each(chunk, range(0, loaded, CHUNK))
    return H


def _newest_of_each_hash(H: np.ndarray, packed: np.ndarray) -> np.ndarray:
    """→ bool a rank: is it the newest of its hash? The table knows a key
    by its hash, so of the ranks that share one only one can hold a
    slot. ``packed`` is work space, a uint64 a rank."""
    n = H.shape[0]

    def pack(lo):
        h = H[lo:lo + CHUNK]
        packed[lo:lo + CHUNK] = (h.astype(np.uint64) << np.uint64(32)) | (
            np.arange(lo, lo + h.shape[0], dtype=np.uint64))

    _each(pack, range(0, n, CHUNK))
    packed[:n].sort()
    keep = np.ones(n, bool)

    def strike(lo):
        p = packed[lo:min(lo + CHUNK + 1, n)]
        hash_ = p >> np.uint64(32)
        older = p[:-1][hash_[1:] == hash_[:-1]]
        keep[(older & np.uint64(0xFFFFFFFF)).astype(np.int64)] = False

    _each(strike, range(0, n, CHUNK))
    return keep


def _fill_the_rest(keys, occ, touch, H, left, probe: int) -> tuple:
    """The slots the block pass left empty, in place → (rounds slot by
    slot, hops of the last holes, times the spare keys near the holes
    were looked up again). ``left`` are the ranks not placed, home
    block by home block, the newest first inside a block."""
    cap, loaded = keys.shape[0], H.shape[0]
    # the slots a thin home block left: from the keys not placed whose
    # home lies in the window up to the slot, found through a table
    # home → the newest such key
    spare = np.full(cap, -1, np.int32)

    def respare(r):
        """home → the newest key of ``r`` (ascending ranks) with that
        home."""
        for lo in range(0, r.shape[0], CHUNK):  # the newest written last
            spare[_homes(H[r[lo:lo + CHUNK]], cap)] = r[lo:lo + CHUNK]

    # ``left`` runs home block by home block: where each block's keys
    # start, so that the keys in reach of a hole can be looked up again
    # once ``spare`` has given its one key a home away
    B = max(1, probe // 2)
    nb = -(-cap // B)
    starts = np.searchsorted(
        np.concatenate([_homes(H[left[lo:lo + CHUNK]], cap) // B
                        for lo in range(0, left.shape[0], CHUNK)]
                       or [np.empty(0, np.int64)]),
        np.arange(nb + 1))

    def respare_near(holes):
        """``spare`` made anew for the homes in reach of ``holes``."""
        b = np.unique((holes[:, None] // B - np.arange(3)[None, :]) % nb)
        r = left[_runs(starts[b], starts[b + 1] - starts[b])]
        respare(np.sort(r[~taken[r]]))

    for hi in range(left.shape[0], 0, -CHUNK):  # the newest written last
        r = left[max(0, hi - CHUNK):hi][::-1]
        spare[_homes(H[r], cap)] = r
    taken = np.zeros(loaded, bool)
    empties = np.flatnonzero(~occ)
    back = np.arange(probe, dtype=np.int64)[None, :]
    ahead = np.arange(1, probe, dtype=np.int64)[None, :]
    rnd = hops = respared = 0
    stale = False  # has a key been taken since ``spare`` was made?
    turned = np.empty(0, np.int64)  # holes that go back, not on
    while empties.size:
        todo = []
        for lo in range(0, empties.size, CHUNK // 16):
            e = empties[lo:lo + CHUNK // 16]
            with np.errstate(over="ignore"):
                start = prefill._mix(
                    np, e.astype(np.uint32), np.uint32(rnd)) % np.uint32(probe)
            homes = (e[:, None] - (start.astype(np.int64)[:, None] + back)
                     % probe) % cap
            has = spare[homes] >= 0
            col = np.argmax(has, axis=1)
            rows = np.flatnonzero(has[np.arange(e.size), col])
            home = homes[rows, col[rows]]
            # two empties may want one home's key: the first has it
            home, one = np.unique(home, return_index=True)
            rows = rows[one]
            k = spare[home].astype(np.int64)
            keys[e[rows]] = H[k]
            touch[e[rows]] = k - loaded
            occ[e[rows]] = taken[k] = True
            spare[home] = -1
            stale = stale or rows.size > 0
            lost = np.ones(e.size, bool)
            lost[rows] = False
            todo.append(e[lost])
        rnd, before, empties = rnd + 1, empties.size, np.concatenate(todo)
        stalled = empties.size == before
        if stalled and stale:
            # a home may have more keys than the one ``spare`` held
            respare_near(empties)
            stale, respared = False, respared + 1
        elif stalled and hops < MAX_HOPS and probe > 1:
            # no key to spare in reach of what is left: each hole takes
            # the key farthest ahead of it whose home lies in the hole's
            # window (it comes closer to home), and so moves on, towards
            # a neighbourhood that has keys to spare ...
            hops += 1
            e = empties
            src = (e[:, None] + ahead) % cap
            ok = occ[src] & ((e[:, None] - _homes(keys[src], cap)) % cap
                             < probe)
            # ... or, where no key ahead may come back that far, the key
            # farthest behind the hole that may go on to it; a hole that
            # has turned back keeps going back (the key it moved lies
            # ahead of it now)
            none = ~ok.any(axis=1) | np.isin(e, turned)
            src[none] = (e[none, None] - ahead) % cap
            ok[none] = occ[src[none]] & (
                (e[none, None] - _homes(keys[src[none]], cap)) % cap < probe)
            col = probe - 2 - np.argmax(ok[:, ::-1], axis=1)
            rows = np.flatnonzero(ok[np.arange(e.size), col])
            s_, one = np.unique(src[rows, col[rows]], return_index=True)
            rows = rows[one]
            keys[e[rows]], touch[e[rows]] = keys[s_], touch[s_]
            occ[e[rows]], occ[s_] = True, False
            turned = s_[none[rows]]
            stay = np.ones(e.size, bool)
            stay[rows] = False
            empties = np.sort(np.concatenate([e[stay], s_]))
            stale = True  # of the homes the holes have moved into
        elif stalled:
            raise RuntimeError(
                f"the loaded population ({loaded} keys) leaves "
                f"{empties.size} of {cap} slots without a key in reach: it "
                "neither fits the table nor fills it")
    return rnd, hops, respared


def _stamps(age: np.ndarray, loaded: int, grain: int) -> np.ndarray:
    """A slot's LRU stamp from its key's age (``rank - loaded``, below
    zero): ``1 + rank // grain``, so a stamp falls with its key's
    recency and none is the untouched zero."""
    return (1 + (age.astype(np.int64) + loaded) // grain).astype(np.int32)


def fill_full(cap: int, probe: int, loaded: int, grain: int, log) -> dict:
    """→ ``{"keys", "occ", "touch", "stats"}``: the full table's mirror
    for the loaded ranks ``[0, loaded)``, as the module docstring says;
    ``touch`` the stamps (``_stamps``).
    Raises where the population cannot fill the table. Every pass over
    the keys goes chunk by chunk: on the chip's host a fresh page costs
    more than the arithmetic on it (``lib/prefill.py``)."""
    t0 = time.monotonic()
    H = _hashes(loaded)
    t_hash = time.monotonic() - t0
    packed = np.empty(loaded, np.uint64)
    keep = _newest_of_each_hash(H, packed)
    t_twins = time.monotonic() - t0
    # blocks of half a window: a key whose home lies in block k is in
    # reach of every slot of block k + 1
    B = max(1, probe // 2)
    nb = -(-cap // B)
    starts = range(0, loaded, CHUNK)
    kept = np.cumsum([0] + _each(
        lambda lo: int(keep[lo:lo + CHUNK].sum()), starts))
    distinct = int(kept[-1])

    def pack(i):
        r = starts[i] + np.flatnonzero(keep[starts[i]:starts[i] + CHUNK])
        packed[kept[i]:kept[i + 1]] = (
            (_homes(H[r], cap) // B).astype(np.uint64) << np.uint64(32)
        ) | (loaded - 1 - r).astype(np.uint64)

    _each(pack, range(len(starts)))
    del keep
    packed = packed[:distinct]
    packed.sort()  # by home block, the newest first inside a block
    t_sorted = time.monotonic() - t0
    rank = np.empty(distinct, np.int32)

    def unpack(lo):
        p = packed[max(lo - 1, 0):lo + CHUNK]
        blk = p >> np.uint64(32)
        rank[lo:lo + CHUNK] = loaded - 1 - (
            packed[lo:lo + CHUNK] & np.uint64(0xFFFFFFFF)).astype(np.int64)
        return max(lo - 1, 0) + 1 + np.flatnonzero(blk[1:] != blk[:-1])

    # where each home block's keys start
    first = np.concatenate([np.zeros(1, np.int64)] + _each(
        unpack, range(0, distinct, CHUNK)))
    sizes = np.diff(np.concatenate([first, [distinct]]))
    to = (packed[first] >> np.uint64(32)).astype(np.int64) + 1
    to[to == nb] = 0
    del packed
    # the slots of block k + 1 go to the newest keys of home block k,
    # in an order turned by the block's number
    room = np.where(to == nb - 1, cap - B * (nb - 1), B)
    take = np.minimum(sizes, room)
    with np.errstate(over="ignore"):
        turn = (prefill._mix(np, to.astype(np.uint32), np.uint32(probe))
                % np.uint32(B)).astype(np.int64)
    keys = np.zeros(cap, np.uint32)
    occ = np.zeros(cap, bool)
    touch = np.zeros(cap, np.int32)  # a key's age: rank - loaded
    G = CHUNK // B  # home blocks a pass

    def place(g):
        f, n, sz = first[g:g + G], take[g:g + G], sizes[g:g + G]
        r = rank[_runs(f, n)]
        slot = np.repeat(to[g:g + G], n) * B + (
            _runs(turn[g:g + G], n)) % np.repeat(room[g:g + G], n)
        keys[slot], touch[slot] = H[r], r.astype(np.int64) - loaded
        occ[slot] = True
        return rank[_runs(f + n, sz - n)]  # not placed

    left = _each(place, range(0, first.size, G))
    log(f"table fill: {int(occ.sum())} of {cap} slots hold one of the newest "
        f"keys of the home block before them at {time.monotonic() - t0:.1f}s")
    t_blocks = time.monotonic() - t0
    del rank, first, sizes, to, room, take, turn
    left = np.concatenate(left)
    rnd, hops, respared = _fill_the_rest(keys, occ, touch, H, left, probe)
    del left
    stride = max(1, cap // (1 << 22))
    s = np.arange(0, cap, stride)
    disp = (s - _homes(keys[s], cap)) % cap
    stats = {
        "loaded_keys": loaded, "distinct_hashes": distinct,
        "resident": int(occ.sum()), "empty_slots": int(cap - occ.sum()),
        "displacement_max": int(disp.max()),
        "displacement_mean": float(disp.mean()),
        "displacement_octiles": np.bincount(
            disp * 8 // probe, minlength=8).tolist(),
        "oldest_resident_rank": int(touch.min()) + loaded,
        "slots_with_one_of_the_newest_keys_that_would_fill_the_table": float(
            (touch >= -cap).mean()),
        "slot_by_slot_rounds": rnd, "hops_of_the_last_holes": hops,
        "spare_keys_looked_up_again": respared,
        "hash_s": round(t_hash, 1), "one_rank_a_hash_s": round(t_twins, 1),
        "sorted_by_home_block_s": round(t_sorted, 1),
        "blocks_placed_s": round(t_blocks, 1),
        "fill_s": round(time.monotonic() - t0, 1),
    }
    return {"keys": keys, "occ": occ, "touch": _stamps(touch, loaded, grain),
            "stats": stats}


def fill_sparse(table, loaded: int, grain: int) -> dict:
    """A population the table has room for, through the table's public
    routing, oldest first; the record is what that routing answered."""
    cap = table.capacity
    keys, occ = np.zeros(cap, np.uint32), np.zeros(cap, bool)
    touch = np.zeros(cap, np.int64)
    # small calls: a call may not evict what it has itself touched, and
    # the keys of a large one would crowd each other out of their windows
    for lo in range(0, loaded, 512):
        r = np.arange(lo, min(lo + 512, loaded))
        h = table.hash_keys(keys_mod.rank_to_id(r))
        slots, _, _, _ = table.assign_slots(h, np.zeros(r.size, np.int64))
        if (slots == table.scratch).any():
            raise RuntimeError(
                f"the loaded population ({loaded} keys) overflows a table of "
                f"{cap} slots it cannot fill")
        keys[slots], occ[slots], touch[slots] = h, True, r - loaded
    stats = {"loaded_keys": loaded, "resident": int(occ.sum()),
             "empty_slots": int(cap - occ.sum())}
    touch = np.where(occ, _stamps(touch, loaded, grain), 0).astype(np.int32)
    return {"keys": keys, "occ": occ, "touch": touch, "stats": stats}


class Path(byname.load("paths", "block").Path):
    def __init__(self, cfg: dict, compiled, addr: dict, on_batch):
        def sink(first_off, n, scores, t_done):
            if self.placed is not None:
                self.calls.append((first_off, n))
            on_batch(first_off, n, scores, t_done)

        # Said before a table is built or 300M keys are hashed: a
        # checkout whose program breaks this deployment's guarantee
        # (the parent of the PR that brought it) ends here, with a
        # sentence and no result.
        if not _race_keeps_state():
            print("benchmark: path 'block_full': this checkout's program "
                  "sends a key that loses an eviction race to the scratch "
                  "row (state_overflow): it cannot keep this deployment's "
                  "guarantees on a full table", file=sys.stderr, flush=True)
            sys.exit(1)
        super().__init__(cfg, compiled, addr, sink)
        self.loaded = latest.loaded_of(int(cfg["key_domain"]))
        self.placed = None  # the benchmark's record of the fill
        self.calls = []     # (first offset, records) a dispatch delivered
        self.counters_after_fill = {}

    def release(self) -> None:
        """The warm-up check has read the record: let 1.8 GB go."""
        self.placed = None

    def fill_table(self, seed: int, plan: dict, log) -> None:
        import jax

        t = self.table
        prefill._needs(t)
        del plan  # the first ranks, linearly probed: not this table
        t.commit(prefill.device_table(seed, t.rows, t.capacity))
        sample = np.arange(0, self.loaded, max(1, self.loaded // 4096))
        sid = keys_mod.rank_to_id(sample)
        if not np.array_equal(t.hash_keys(sid), prefill.crc32_of_ids(
                prefill.ids_of_ranks(sample))):
            raise RuntimeError(
                "the benchmark's ids or hashes differ from the program's "
                "(keys.rank_to_id, KeyedStateTable.hash_keys)")
        if self.loaded > t.capacity:
            fill = fill_full(
                t.capacity, t.spec.probe, self.loaded, STAMP_GRAIN, log)
            if fill["stats"]["empty_slots"]:
                raise RuntimeError(
                    f"the fill left {fill['stats']['empty_slots']} of "
                    f"{t.capacity} slots empty: the deployment's table is "
                    "full at the first record")
            before = prefill.mirror_resident(t)
            t._keys[:], t._occ[:] = fill["keys"], fill["occ"]
            t.resident = fill["stats"]["resident"]
        else:
            before = prefill.mirror_resident(t)
            fill = fill_sparse(t, self.loaded, STAMP_GRAIN)
        # the LRU order: each slot as old as its key, every stamp
        # written, and the table's next routing call newer than all
        t._touch[:] = fill["touch"]
        t._seq = max(int(t._seq), int(fill["touch"].max()))
        log(f"table fill: {json.dumps(fill['stats'])}, probe window "
            f"{t.spec.probe}; slots stamped 1 to {t._seq}, a stamp "
            f"{STAMP_GRAIN} ranks of recency; the host mirror's resident "
            f"bytes {before} before and {prefill.mirror_resident(t)} after")
        self.placed = {k: fill[k] for k in ("keys", "occ", "touch")}
        self._hold_to_lookup(fill)
        # what the table has admitted by now is the fill's, not the stream's
        self.counters_after_fill = dict(
            t.metrics.struct_snapshot()["counters"])
        jax.block_until_ready(t.values)

    def _hold_to_lookup(self, fill: dict) -> None:
        """A sample of the record against the table's public lookup:
        each placed hash is found in the slot the record has, and
        nothing is admitted. The lookup stamps what it finds and counts
        a routing call: both are put back."""
        t = self.table
        slots = np.arange(0, t.capacity, max(1, t.capacity // 4096))
        slots = slots[fill["occ"][slots]]
        seq = t._seq
        c0 = dict(t.metrics.struct_snapshot()["counters"])
        got, reset, _, _ = t.assign_slots(
            fill["keys"][slots], np.zeros(slots.size, np.int64))
        c1 = t.metrics.struct_snapshot()["counters"]
        moved = sum(c1[n] - c0.get(n, 0) for n in (
            "state_inserts", "state_evictions", "state_overflow"))
        if moved or reset.any() or not np.array_equal(got, slots):
            raise RuntimeError(
                "the table's lookup does not find the keys where the "
                f"benchmark's fill put them ({int((got != slots).sum())} of "
                f"{slots.size} sampled, {moved} admitted)")
        t._touch[slots], t._seq = fill["touch"][slots], seq
        if t.resident != int(fill["occ"].sum()):
            raise RuntimeError(
                f"the table counts {t.resident} resident keys, the fill "
                f"placed {int(fill['occ'].sum())}")
