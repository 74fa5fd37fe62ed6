"""Keyed per-key state plane (ISSUE 19): the open-addressed
device-resident table (runtime/state.py) + the fused gather/fold stage
(compile/statekernel.py) behind ``dispatch_quantized(state=...)``.

Pins, in order: host slot routing under adversarial hash collisions
(probe windows, LRU eviction that never steals a slot touched this
batch, scratch overflow), the exactly-once replay guard, the fold
columns against hand-computed ground truth, armed-vs-stateless score
parity, checkpoint payload/sidecar roundtrips, degraded-mesh migration
parity on the conftest 8-device virtual mesh, and the never-delivered
contract extended to state: a DLQ'd batch must never leave folds in
the table (rollback-to-snapshot semantics, deterministic with no
checkpoint pinned)."""

import os
import sys

import numpy as np
import pytest

from flink_jpmml_tpu.parallel.partitioner import stable_hash
from flink_jpmml_tpu.runtime import native as native_mod
from flink_jpmml_tpu.runtime import state as state_mod
from flink_jpmml_tpu.runtime.state import (
    COL_COUNT,
    COL_DCOUNT,
    COL_DSUM,
    COL_LAST_T,
    COL_MAX,
    COL_MIN,
    COL_SQSUM,
    COL_SUM,
    KeyedStateTable,
    StateSpec,
)
from flink_jpmml_tpu.utils.exceptions import InputValidationException
from flink_jpmml_tpu.utils.metrics import MetricsRegistry


def _table(capacity=16, probe=4, **kw):
    m = MetricsRegistry()
    return KeyedStateTable(
        StateSpec(capacity=capacity, probe=probe, **kw), metrics=m
    ), m


def _colliding_keys(capacity, base, n, start=0):
    """n distinct int keys whose stable hashes all land on probe base
    ``base`` of a ``capacity``-slot table (brute-force: the adversarial
    suite the open addressing must survive)."""
    out, k = [], start
    while len(out) < n:
        t = KeyedStateTable(StateSpec(capacity=capacity))
        h = int(t.hash_keys(np.array([k]))[0])
        if h % capacity == base:
            out.append(k)
        k += 1
    return out


class TestSlotRouting:
    def test_hit_reuses_slot(self):
        t, m = _table()
        kh = t.hash_keys(np.array([5, 9, 5]))
        s1, r1, _, w1 = t.assign_slots(kh, np.arange(3))
        assert s1[0] == s1[2] != s1[1]
        assert r1.all()  # every key fresh this batch
        assert (w1 > 0).all()
        s2, r2, _, _ = t.assign_slots(kh, np.arange(3, 6))
        assert np.array_equal(s1, s2)
        assert not r2.any()
        c = m.struct_snapshot()["counters"]
        assert c["state_inserts"] == 2
        assert c["state_hits"] == 3
        assert t.resident == 2
        assert t.applied_hi == 6

    def test_spec_validation(self):
        with pytest.raises(InputValidationException):
            StateSpec(capacity=1)
        with pytest.raises(InputValidationException):
            StateSpec(capacity=8, decay=1.0)
        with pytest.raises(InputValidationException):
            StateSpec(capacity=8, probe=0)

    def test_collisions_probe_to_distinct_slots(self):
        cap = 32
        keys = _colliding_keys(cap, base=3, n=4)
        t, m = _table(capacity=cap, probe=8)
        kh = t.hash_keys(np.array(keys))
        slots, reset, _, _ = t.assign_slots(kh, np.arange(4))
        assert reset.all()
        assert len(set(slots.tolist())) == 4, slots
        # every slot inside the probe window off the shared base
        assert all((int(s) - 3) % cap < 8 for s in slots)
        c = m.struct_snapshot()["counters"]
        assert c["state_collisions"] == 3  # all but one pending at p=0

    def test_eviction_lru_never_this_batch(self):
        cap = 32
        a, b, c = _colliding_keys(cap, base=7, n=3)
        t, m = _table(capacity=cap, probe=2)
        t.assign_slots(t.hash_keys(np.array([a, b])), np.arange(2))
        slot_a = int(t.assign_slots(
            t.hash_keys(np.array([a])), np.array([2])
        )[0][0])  # refresh A: B becomes the LRU of the window
        slots_b1, _, _, _ = t.assign_slots(
            t.hash_keys(np.array([b])), np.array([3])
        )
        t.assign_slots(t.hash_keys(np.array([a])), np.array([4]))
        sc, rc, _, _ = t.assign_slots(
            t.hash_keys(np.array([c])), np.array([5])
        )
        # C landed by evicting LRU B — never A (fresher), never scratch
        assert int(sc[0]) == int(slots_b1[0]) != slot_a
        assert rc.all()
        assert m.struct_snapshot()["counters"]["state_evictions"] == 1
        # B returns as a fresh insert: its state was evicted with it
        sb, rb, _, _ = t.assign_slots(
            t.hash_keys(np.array([b])), np.array([6])
        )
        assert rb.all()

    def test_window_overflow_bypasses_to_scratch(self):
        cap = 32
        keys = _colliding_keys(cap, base=11, n=3)
        t, m = _table(capacity=cap, probe=2)
        kh = t.hash_keys(np.array(keys))
        slots, _, _, _ = t.assign_slots(kh, np.arange(3))
        # two claim the window; the third may not evict a slot touched
        # THIS batch — it overflows to the scratch row
        assert sorted(slots.tolist())[:2] != [t.scratch, t.scratch]
        assert int(slots.max()) == t.scratch
        c = m.struct_snapshot()["counters"]
        assert c["state_overflow"] == 1
        assert c["state_evictions"] == 0

    def test_replay_below_skip_until_bypasses(self):
        t, m = _table()
        kh = t.hash_keys(np.array([1, 2, 3]))
        t.assign_slots(kh, np.arange(3))
        assert t.applied_hi == 3
        t.skip_until = 3
        s2, r2, _, w2 = t.assign_slots(kh, np.arange(3))
        assert (s2 == t.scratch).all()
        assert not r2.any()
        assert (w2 == 0).all()
        assert t.applied_hi == 3
        c = m.struct_snapshot()["counters"]
        assert c["state_bypass_records"] == 3
        # fresh offsets past the guard fold again
        s3, _, _, w3 = t.assign_slots(kh, np.arange(3, 6))
        assert (s3 != t.scratch).all()
        assert (w3 > 0).all()

    def test_bypass_context(self):
        """``bypass()`` is a CALL-SITE contract: armed dispatch paths
        check ``table.bypassed`` and score stateless — the table never
        gates ``assign_slots`` itself.  Assert the flag's scoping and
        nesting, and that it survives an exception in the window."""
        t, _ = _table()
        assert not t.bypassed
        with t.bypass():
            assert t.bypassed
            with t.bypass():  # recovery ladder inside poison bisection
                assert t.bypassed
            assert t.bypassed
        assert not t.bypassed
        with pytest.raises(RuntimeError):
            with t.bypass():
                raise RuntimeError("redispatch blew up")
        assert not t.bypassed

    def test_hash_matches_scalar_stable_hash(self):
        t, _ = _table()
        for k in (-128, -1, 0, 1, 7, 2**40, -(2**40)):
            assert int(t.hash_keys(np.array([k]))[0]) == (
                stable_hash(k) & 0xFFFFFFFF
            ), k


# -- the native pass against the rounds alone (ISSUE 31) -----------------------
#
# ``route`` resolves resident keys in one native pass and leaves the rest
# to its numpy rounds; with the library masked the rounds take every
# record, which is the code as it was. Two tables get the same calls, one
# each way, and everything a caller or a snapshot can see has to agree
# after every call. A case is a list of calls ``(khash, first offset)``
# with optional ``held`` slots, a ``skip_until`` and an ``unclaim`` before
# the call. Capacity 16, probe 4: hash ``h`` has its home at ``h % 16``.

_COUNTERS = (
    "state_records", "state_hits", "state_inserts", "state_evictions",
    "state_overflow", "state_bypass_records", "state_collisions",
)


def _call(khash, first, **kw):
    return dict(khash=np.asarray(khash, np.uint32), first=first, **kw)


_AT_3 = [3, 19, 35, 51]  # four keys whose home is slot 3: a full window
_PARITY_CASES = {
    # every record of the later calls finds its key: nothing is pending
    "all_hits": ([_call([1, 2, 7, 2], 0), _call([2, 7, 1, 1, 7], 4),
                  _call([7], 9)], 4),
    "fresh_keys_into_empty_homes": ([_call([0, 5, 9, 15], 0)], 4),
    "two_fresh_keys_contend_for_one_empty_slot": (
        [_call([19, 3, 35], 0), _call([3, 35, 19], 3)], 3),
    # 19 sits at slot 4 behind 3: one collision a call, however many records
    "home_held_by_another_key": (
        [_call([3, 19], 0), _call([19, 19, 3, 19], 2)], 2),
    "duplicate_fresh_key_resets_every_record": (
        [_call([5, 5, 5, 6], 0), _call([5, 6], 4)], 4),
    # 19's slot is given back: 35, behind it, meets the hole before its
    # own row and claims the hole, as the rounds always did
    "hole_from_unclaim_before_a_resident_key": (
        [_call([3, 19, 35], 0), _call([35, 3], 3, unclaim=[4]),
         _call([35, 19, 3], 5)], 5),
    "window_wraps_past_capacity": (
        [_call([15, 31, 47, 14], 0), _call([47, 31, 15, 14, 63], 4)], 5),
    "exhausted_window_evicts_the_least_recent": (
        [_call(_AT_3, 0), _call([19, 35, 51], 4), _call([67], 7),
         _call([3, 67], 8)], 6),
    # 67 and 83 both want the one slot this call has not touched
    "eviction_race_lost_overflows_to_scratch": (
        [_call(_AT_3, 0), _call([3, 19, 35, 67, 83], 4)], 6),
    "held_slots_are_safe_from_eviction": (
        [_call(_AT_3, 0), _call([67, 83], 4, held=[3, 4, 5]),
         _call([99], 6, held=[3, 4, 5, 6, 16])], 7),
    "offsets_under_skip_until_bypass": (
        [_call([1, 2, 3], 0), _call([1, 2, 3, 4, 1], 1, skip_until=4),
         _call([4, 1], 6)], 4),
    "empty_batch": ([_call([], 0), _call([8], 0), _call([], 1)], 1),
}


def _random_stream():
    """200 calls of keys from a population larger than the table, slots
    given back as fast as they fill so that it runs near load 0.9 with
    probe 8: hits, claims, holes, evictions and overflows all the way."""
    rng = np.random.default_rng(31)
    calls, first = [], 0
    for i in range(200):
        n = int(rng.integers(1, 48))
        kw = {"unclaim": rng.integers(0, 64, 12)}
        if i % 7 == 3:
            kw["held"] = rng.integers(0, 64, 4)
        if i % 13 == 6:
            kw["skip_until"] = first + n // 2
        calls.append(_call(rng.integers(0, 100, n) * 2654435761 % 2**32,
                           first, **kw))
        first += n
    return calls


def _route_both_ways(case, capacity, probe, monkeypatch, tables=None):
    """→ the native table's ``state_route_pending`` at the end.
    ``tables``: two ``(table, metrics)`` alike to start from, where not
    from empty ones."""
    (a, ma), (b, mb) = tables or (
        _table(capacity, probe) for _ in range(2))
    for i, call in enumerate(case):
        kh = call["khash"]
        offs = np.arange(call["first"], call["first"] + kh.size)
        out = []
        for t, masked in ((a, False), (b, True)):
            if "unclaim" in call:
                t.unclaim(call["unclaim"])
            t.skip_until = call.get("skip_until", t.skip_until)
            with monkeypatch.context() as mp:
                if masked:
                    mp.setattr(native_mod, "available", lambda: False)
                out.append(t.route(kh, offs, held=call.get("held")))
        for name, x, y in zip(("slots", "reset", "apply"), *out):
            assert np.array_equal(x, y), (i, name, x, y)
        for name in ("_keys", "_occ", "_touch"):
            assert np.array_equal(getattr(a, name), getattr(b, name)), (
                i, name)
        assert a.resident == b.resident, i
        ca, cb = (m.struct_snapshot()["counters"] for m in (ma, mb))
        for name in _COUNTERS:
            assert ca[name] == cb[name], (i, name, ca[name], cb[name])
    # masked, every record that applies is left to the rounds
    assert cb["state_route_pending"] == (
        cb["state_records"] - cb["state_bypass_records"])
    return ca["state_route_pending"]


@pytest.mark.skipif(not native_mod.available(), reason="no native library")
class TestNativeRouteParity:
    @pytest.mark.parametrize("name", sorted(_PARITY_CASES))
    def test_native_pass_and_rounds_equal_the_rounds_alone(
            self, name, monkeypatch):
        case, pending = _PARITY_CASES[name]
        assert _route_both_ways(case, 16, 4, monkeypatch) == pending

    def test_random_stream_at_load_09(self, monkeypatch):
        pending = _route_both_ways(_random_stream(), 64, 8, monkeypatch)
        assert 0 < pending < 200 * 48


# -- the eviction race on a full table (ISSUE 34) -----------------------------
#
# Keys of one call whose windows share their least recently touched slot
# all name it. The smallest hash has it; the others name again from what
# is left of their own windows, and only a key whose whole window this
# call has touched goes to scratch (``_claim_rounds``' docstring). Every
# case runs both ways (``_route_both_ways``: native pass + rounds against
# the rounds alone, slots, resets, mirror and counters after every call)
# and is then held to the slots the rule gives, written out by hand.
# Capacity 16, probe 4; ``_AT_3`` fills the window 3..6 in call 1 and call
# 2 touches all of it but slot 3, so slot 3 is every newcomer's first
# choice and slots 4, 5, 6 (equal stamps) follow in probe order.

_FULL_AT_3 = [_call(_AT_3, 0), _call([19, 35, 51], 4)]
_RACE_CASES = {
    # 67 < 83: 67 has slot 3, 83 names again and takes 4
    "two_fresh_keys_one_victim": (
        _call([67, 83, 67], 7), [3, 4, 3], [67, 83, 35, 51], (2, 0)),
    "three_fresh_keys_one_victim": (
        _call([99, 67, 83], 7), [5, 3, 4], [67, 83, 99, 51], (3, 0)),
    # 51's record touches slot 6: the three fresh keys share 3, 4, 5
    "a_resident_key_of_the_call_is_nobodys_victim": (
        _call([51, 99, 83, 67], 7), [6, 5, 4, 3], [67, 83, 99, 51], (3, 0)),
    # slot 3 is held: 67's first choice is 4, 83 loses it and takes 5
    "a_victim_stamped_by_held": (
        _call([83, 67], 7, held=[3]), [5, 4], [3, 67, 83, 51], (2, 0)),
    # five fresh keys, four slots: 131 finds its whole window touched
    "a_window_wholly_touched_in_the_call_overflows": (
        _call([131, 67, 83, 99, 115], 7), [16, 3, 4, 5, 6],
        [67, 83, 99, 115], (4, 1)),
}


class TestEvictionRace:
    @pytest.mark.parametrize("name", sorted(_RACE_CASES))
    def test_a_loser_goes_again_in_its_own_window(self, name, monkeypatch):
        race, slots, window, (evicted, overflowed) = _RACE_CASES[name]
        if native_mod.available():
            _route_both_ways(_FULL_AT_3 + [race], 16, 4, monkeypatch)
        t, m = _table(16, 4)
        for call in _FULL_AT_3:
            t.route(call["khash"], np.arange(
                call["first"], call["first"] + call["khash"].size))
        seq = t._seq + 1
        kh = race["khash"]
        got, reset, _ = t.route(
            kh, np.arange(7, 7 + kh.size), held=race.get("held"))
        assert got.tolist() == slots
        fresh = np.isin(kh, [67, 83, 99, 115]) & (got != t.scratch)
        assert np.array_equal(reset, fresh)
        assert t._keys[3:7].tolist() == window and t._occ[3:7].all()
        touched = sorted(set(slots) - {16} | set(race.get("held", [])))
        assert (t._touch[touched] == seq).all()
        assert (np.delete(t._touch[3:7], np.array(touched) - 3) < seq).all()
        c = m.struct_snapshot()["counters"]
        assert (c["state_evictions"], c["state_overflow"]) == (
            evicted, overflowed)
        assert c["state_inserts"] == 4 and t.resident == 4

    def test_the_rounds_book_a_claim_stage_only_when_they_run(self):
        t, m = _table(capacity=8, probe=3)
        key = 'stage_seconds{stage="claim"}'
        t.route(np.array([0, 8], np.uint32), np.arange(2))
        n1 = m.struct_snapshot()["histograms"][key]["n"]
        t.route(np.array([0, 8, 8], np.uint32), np.arange(2, 5))
        h = m.struct_snapshot()["histograms"][key]
        # resident keys alone: the native pass leaves nothing, no span
        assert h["n"] == (n1 if native_mod.available() else n1 + 1)


# -- the rounds, natively (ISSUE 37) -------------------------------------------
#
# Where the library is built ``_claim_rounds`` is one native call; its numpy
# body is the same rounds. Both get the same mirror and the same records
# (every record of the call, so the rounds' own hits run too) and have to
# leave the same table, answers and counts. A case is a mirror written
# slot by slot ``{slot: (hash, stamp)}``, the call's hashes and its
# ``held`` slots; capacity 16 and probe 4 unless it says otherwise, and the
# call's stamp is 9, newer than every stamp of a mirror.

_SEQ = 9
# the window 3..6 of the keys at home 3, and 7 behind it: every slot the
# keys at homes 3 and 4 can name is taken, the stamps rising with the slot
_FULL_3_TO_7 = {3: (3, 1), 4: (19, 2), 5: (35, 3), 6: (51, 4), 7: (7, 5)}
_CLAIM_CASES = {
    # 51 holds slot 3 and 4..7 are empty: 4 takes its home and 20 the slot
    # behind it, 3 reaches the first slot nobody took in round 3, and 19
    # and 35, behind it all the way, evict: 19 has 51's slot and 35, whose
    # window is this call's by then, overflows
    "empty_slots_contended_round_by_round": dict(
        mirror={3: (51, 1)}, khash=[35, 4, 19, 20, 3, 19],
        slots=[16, 4, 3, 5, 6, 3], window={3: 19, 4: 4, 5: 20, 6: 3},
        counts=(3, 1, 1), collided=4),
    # 67, 83 and 99 name slot 3 and 67 has it; 116, the LARGEST hash, names
    # slot 4 in the same round and has it, though it is 83's second
    # choice: 83 and 99 name again from 5 and 6
    "three_keys_name_one_victim": dict(
        mirror=_FULL_3_TO_7, khash=[116, 99, 83, 67],
        slots=[4, 6, 5, 3], window={3: 67, 4: 116, 5: 83, 6: 99, 7: 7},
        counts=(0, 4, 0), collided=4),
    # slots 3 and 5 are held for records not dispatched yet: 67 and 83
    # share 4 and 6
    "held_slots_are_nobodys_victim": dict(
        mirror=_FULL_3_TO_7, khash=[83, 67], held=[3, 5, 16],
        slots=[6, 4], window={3: 3, 4: 67, 5: 35, 6: 83},
        counts=(0, 2, 0), collided=2),
    # 19's record touches slot 4; four fresh keys want the three slots left
    "a_window_this_call_has_touched_whole": dict(
        mirror=_FULL_3_TO_7, khash=[131, 19, 67, 83, 99],
        slots=[16, 4, 3, 5, 6], window={3: 67, 4: 19, 5: 83, 6: 99},
        counts=(0, 3, 1), collided=5),
    # home 14: the window is 14, 15, 0, 1, and 46's record touches slot 0:
    # the stamps of the other three fall 14, 15, 1
    "a_window_that_wraps_the_end_of_the_table": dict(
        mirror={14: (14, 4), 15: (30, 3), 0: (46, 1), 1: (62, 2)},
        khash=[78, 46, 94, 110], slots=[1, 0, 15, 14],
        window={14: 110, 15: 94, 0: 46, 1: 78},
        counts=(0, 3, 0), collided=4),
    # three slots, a window of five: slots 1 and 2 are named twice
    "a_window_wider_than_the_table": dict(
        capacity=3, probe=5, mirror={0: (3, 2), 1: (1, 3), 2: (2, 1)},
        khash=[4, 7, 10, 13], slots=[2, 0, 1, 3],
        window={0: 7, 1: 10, 2: 4}, counts=(0, 3, 1), collided=4),
    "a_hole_in_a_window_wider_than_the_table": dict(
        capacity=3, probe=5, mirror={1: (1, 3)},
        khash=[4, 7, 1], slots=[2, 0, 1],
        window={0: 7, 1: 1, 2: 4}, counts=(2, 0, 0), collided=2),
    "no_record": dict(mirror=_FULL_3_TO_7, khash=[], slots=[],
                      window={3: 3}, counts=(0, 0, 0), collided=0),
}


def _with_mirror(mirror, capacity=16, probe=4):
    t, m = _table(capacity, probe)
    for s, (h, stamp) in mirror.items():
        t._keys[s], t._occ[s], t._touch[s] = h, True, stamp
    t.resident, t._seq = len(mirror), _SEQ - 1
    return t, m


def _claim_both_ways(mirror, khash, monkeypatch, held=(), capacity=16,
                     probe=4):
    """→ the native side's ``(table, slots, reset, collided, counters)``,
    the numpy body's held equal to it."""
    kh = np.asarray(khash, np.uint32)
    out = []
    for masked in (False, True):
        t, m = _with_mirror(mirror, capacity, probe)
        hs = np.asarray(held, np.int64)
        t._touch[hs[hs < capacity]] = _SEQ  # as ``route`` stamps them
        with monkeypatch.context() as mp:
            if masked:
                mp.setattr(native_mod, "available", lambda: False)
            slots, reset, collided = t._claim_rounds(kh, _SEQ)
        assert slots.dtype == np.int32 and reset.dtype == bool
        c = m.struct_snapshot()["counters"]
        out.append((t, slots, reset, int(collided), tuple(int(c[n]) for n in (
            "state_inserts", "state_evictions", "state_overflow"))))
    (a, *got), (b, *want) = out
    for name, x, y in zip(("slots", "reset", "collided", "counters"),
                          got, want):
        assert np.array_equal(x, y), (name, x, y)
    for name in ("_keys", "_occ", "_touch"):
        assert np.array_equal(getattr(a, name), getattr(b, name)), name
    assert a.resident == b.resident
    return out[0]


def _awarded_while_naming(t, khash, seq):
    """The eviction of a FULL table in ONE ascending pass a round, each
    key awarded its slot the moment it names it: not the rule. A key then
    sees the stamps of the smaller hashes of its own round and takes its
    second choice a round early."""
    cap, probe = t.capacity, t.spec.probe
    for h in sorted(set(np.asarray(khash).tolist())):
        W = [(h % cap + p) % cap for p in range(probe)]
        c = min(W, key=lambda s: t._touch[s])  # the first of equal stamps
        assert t._touch[c] < seq
        t._keys[c], t._touch[c] = h, seq


@pytest.mark.skipif(not native_mod.available(), reason="no native library")
class TestNativeRounds:
    @pytest.mark.parametrize("name", sorted(_CLAIM_CASES))
    def test_the_native_rounds_leave_the_numpy_bodys_table(
            self, name, monkeypatch):
        case = dict(_CLAIM_CASES[name])
        want = {k: case.pop(k) for k in (
            "slots", "window", "counts", "collided")}
        t, slots, reset, collided, counts = _claim_both_ways(
            monkeypatch=monkeypatch, **case)
        assert slots.tolist() == want["slots"]
        assert {s: int(t._keys[s]) for s in want["window"]} == want["window"]
        assert (counts, collided) == (want["counts"], want["collided"])
        # a record is reset where its key was given its slot by this call
        was = {h for h, _ in case["mirror"].values()}
        fresh = [h not in was and s != t.scratch
                 for h, s in zip(case["khash"], want["slots"])]
        assert reset.tolist() == fresh
        assert t.resident == len(case["mirror"]) + counts[0]
        # every slot answered is stamped by the call, and no other but held
        stamped = set(np.flatnonzero(t._touch == _SEQ).tolist())
        held = {s for s in case.get("held", ()) if s < t.capacity}
        assert stamped == (set(want["slots"]) - {t.scratch}) | held

    @pytest.mark.parametrize("name", sorted(_CLAIM_CASES))
    def test_through_route_as_through_the_rounds_alone(
            self, name, monkeypatch):
        case = _CLAIM_CASES[name]
        geometry = (case.get("capacity", 16), case.get("probe", 4))
        tables = [_with_mirror(case["mirror"], *geometry) for _ in range(2)]
        kh = np.asarray(case["khash"], np.uint32)
        _route_both_ways(
            [_call(kh, 0, held=case.get("held")), _call(kh[::-1], kh.size)],
            *geometry, monkeypatch, tables=tables)
        assert {s: int(tables[0][0]._keys[s]) for s in case["window"]} == (
            case["window"])

    def test_two_passes_a_round_are_told_from_one(self, monkeypatch):
        """The case of three keys and one victim is built so that an
        award made in the naming pass gives ANOTHER table: 83 would see
        67's stamp on slot 3, name slot 4 a round early and take it from
        116, which the rule gives it to."""
        case = _CLAIM_CASES["three_keys_name_one_victim"]
        t, *_ = _claim_both_ways(
            case["mirror"], case["khash"], monkeypatch)
        one, _ = _with_mirror(case["mirror"])
        _awarded_while_naming(one, case["khash"], _SEQ)
        assert one._keys[3:8].tolist() == [67, 83, 99, 116, 7]
        assert t._keys[3:8].tolist() == [67, 116, 83, 99, 7]
        # and the plain reference, which knows nothing of either, agrees
        # with the two passes
        if _BENCH not in sys.path:
            sys.path.insert(0, _BENCH)
        from reference import churn_ref

        ref, _ = _with_mirror(case["mirror"])
        answer = churn_ref.WindowTable(
            ref._keys, ref._occ, ref._touch, 4).call(case["khash"])
        assert ref._keys[3:8].tolist() == [67, 116, 83, 99, 7]
        assert answer[83] == (5, True) and answer[116] == (4, True)

    def test_random_tables_and_calls(self, monkeypatch):
        """300 mirrors of 2 to 40 slots, windows of 1 to 12 (wider than
        the table too), holes and stamps at random, calls of 0 to 60
        records of which half are resident keys."""
        rng = np.random.default_rng(37)
        seen = np.zeros(3, np.int64)
        for _ in range(300):
            cap, probe = int(rng.integers(2, 41)), int(rng.integers(1, 13))
            mirror = {}
            for h in rng.choice(400, int(cap * rng.random()), replace=False):
                for p in range(min(probe, cap)):
                    if (h + p) % cap not in mirror:
                        mirror[(int(h) + p) % cap] = (
                            int(h), int(rng.integers(1, 6)))
                        break
            resident = [h for h, _ in mirror.values()] or [0]
            n = int(rng.integers(0, 61))
            khash = np.where(rng.random(n) < 0.5, rng.choice(resident, n),
                             rng.integers(0, 400, n))
            held = rng.integers(0, cap + 1, int(rng.integers(0, 4)))
            seen += _claim_both_ways(
                mirror, khash, monkeypatch, held, cap, probe)[4]
        assert (seen > 100).all()  # inserts, evictions, overflows


# A "latest" stream (YCSB workload D's key arrival, the benchmark's key
# mix) over a FULL table at probe 8, 200 routing calls, against the
# benchmark's plain reference (``benchmark/reference/churn_ref.py``: plain
# loops, nothing of this module in it) under the same rule: the same keys
# admitted and evicted, every key in the reference's slot, and every
# key's row (count, score sum) as the reference says, with the fold
# written out in numpy (reset rows zeroed, then one add a record).

_BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")


def _parent_claim_rounds(self, khash, seq):
    """``_claim_rounds`` as it was before ISSUE 34, for the probing of a
    FULL table (no empty slot, so no claim): a key that loses an
    eviction race overflows to scratch."""
    uk, inv = np.unique(khash, return_inverse=True)
    base = uk.astype(np.int64) % self.capacity
    W = (base[:, None] + np.arange(self.spec.probe)[None, :]) % self.capacity
    at = self._occ[W] & (self._keys[W] == uk[:, None])
    slot_u = np.where(at.any(axis=1), W[np.arange(uk.size), at.argmax(1)], -1)
    self._touch[slot_u[slot_u >= 0]] = seq
    reset_u = np.zeros(uk.size, bool)
    pend = np.flatnonzero(slot_u < 0)
    if pend.size:
        vic = W[pend, np.argmin(self._touch[W[pend]], axis=1)]
        fresh_vic = self._touch[vic] < seq
        _, first = np.unique(vic, return_index=True)
        winner = np.zeros(pend.size, bool)
        winner[first] = True
        winner &= fresh_vic
        win, c = pend[winner], vic[winner]
        self._keys[c], self._touch[c] = uk[win], seq
        slot_u[win], reset_u[win] = c, True
        self._c_evictions.inc(win.size)
        self._c_overflow.inc(int(pend.size - win.size))
    slot_r = np.where(slot_u >= 0, slot_u, np.int64(self.scratch))
    return slot_r[inv].astype(np.int32), reset_u[inv], int(
        (slot_u != base).sum())


def _latest_stream_against_the_reference(seed):
    """→ what differs between the table and the plain reference after
    200 calls: ``{name: count}``."""
    if _BENCH not in sys.path:
        sys.path.insert(0, _BENCH)
    from lib import byname, keys as keys_mod, prefill
    from reference import churn_ref

    latest = byname.load("lib/keymix", "latest")
    fill_full = byname.load("paths", "block_full").fill_full
    cap, probe, domain = 4093, 8, 16000
    loaded = latest.loaded_of(domain)
    fill = fill_full(cap, probe, loaded, 64, lambda line: None)
    t, m = _table(cap, probe)
    t._keys[:], t._occ[:], t._touch[:] = fill["keys"], fill["occ"], fill["touch"]
    t.resident, t._seq = cap, int(fill["touch"].max())
    ref = churn_ref.WindowTable(
        fill["keys"].copy(), fill["occ"].copy(), fill["touch"].copy(), probe)
    want = churn_ref.Rows()
    first_rows = prefill.initial_rows(seed, np.arange(cap + 1))[:, :2].astype(
        np.float64)
    first_rows[cap] = 0.0
    rows = first_rows.copy()
    rng = np.random.default_rng([34, seed])
    n = 20000
    mix = {"kind": "latest", "insert_share": 0.05, "zipf_constant": 0.99}
    kh = t.hash_keys(keys_mod.rank_to_id(latest.ranks(0, seed, domain, mix, n)))
    scores = rng.normal(size=n)
    cuts = np.sort(rng.choice(np.arange(1, n), 199, replace=False))
    for lo, hi in zip(np.r_[0, cuts], np.r_[cuts, n]):
        slots, reset, _ = t.route(kh[lo:hi], np.arange(lo, hi))
        rows[np.unique(slots[reset])] = 0.0
        np.add.at(rows, slots, np.stack(
            [np.ones(hi - lo), scores[lo:hi]], axis=1))
        want.fold(ref, kh[lo:hi], scores[lo:hi])
    c = m.struct_snapshot()["counters"]
    hashes = sorted(want.slot)
    slot = np.array([want.slot[h] for h in hashes])
    want_n, want_s = want.expected(hashes, first_rows[slot])
    return {
        "admitted": int(c["state_inserts"] + c["state_evictions"]
                        - ref.admitted),
        "evicted": int(c["state_evictions"] - ref.evicted),
        "overflowed": int(c["state_overflow"]), "evictions": ref.evicted,
        "keys_elsewhere": int((t._keys[slot] != np.array(
            hashes, np.uint32)).sum()),
        "counts": int((rows[slot, 0] != want_n).sum()),
        "sums": int((np.abs(rows[slot, 1] - want_s) > 1e-9).sum()),
        "scratch_records": int(rows[cap, 0]),
    }


@pytest.mark.parametrize("rounds", ["native", "numpy"])
@pytest.mark.parametrize("seed", [3, 2**31 + 17])
def test_a_latest_stream_over_a_full_table_equals_the_plain_reference(
        seed, rounds, monkeypatch):
    if rounds == "numpy":
        monkeypatch.setattr(native_mod, "available", lambda: False)
    elif not native_mod.available():
        pytest.skip("no native library")
    d = _latest_stream_against_the_reference(seed)
    assert d.pop("evictions") > 1000
    assert d == dict.fromkeys(d, 0)


def test_the_parents_rounds_fail_the_plain_reference(monkeypatch):
    """The same comparison on ``_claim_rounds`` as it was: keys that lose
    an eviction race overflow, their records land on the scratch row, and
    admissions, slots and rows part from the reference's."""
    monkeypatch.setattr(native_mod, "available", lambda: False)
    monkeypatch.setattr(KeyedStateTable, "_claim_rounds", _parent_claim_rounds)
    d = _latest_stream_against_the_reference(3)
    assert d["overflowed"] > 0 and d["scratch_records"] > 0
    assert d["admitted"] != 0 and d["counts"] > 0


@pytest.fixture(scope="module")
def gbm(tmp_path_factory):
    from flink_jpmml_tpu.assets_gen import gen_gbm
    from flink_jpmml_tpu.compile import compile_pmml
    from flink_jpmml_tpu.pmml import parse_pmml_file

    tmp = tmp_path_factory.mktemp("state_gbm")
    path = gen_gbm(str(tmp), n_trees=5, depth=3, n_features=4)
    return compile_pmml(parse_pmml_file(path), batch_size=32)


def _batches(n_batches, keys, seed=11, B=32, feats=4):
    rng = np.random.default_rng(seed)
    X = rng.normal(0.0, 1.0, size=(n_batches * B, feats)).astype(
        np.float32
    )
    X[:, 0] = rng.integers(0, keys, size=n_batches * B).astype(
        np.float32
    )
    return [
        (X[i * B: (i + 1) * B], np.arange(i * B, (i + 1) * B))
        for i in range(n_batches)
    ]


class TestFusedFold:
    def test_armed_scores_match_stateless(self, gbm):
        import jax

        from flink_jpmml_tpu.runtime.pipeline import dispatch_quantized

        q = gbm.quantized_scorer()
        t, _ = _table(capacity=64)
        (X, offs) = _batches(1, keys=8)[0]
        plain = np.asarray(dispatch_quantized(q, X))
        res = dispatch_quantized(q, X, state=t, offsets=offs)
        assert state_mod.is_state_output(res)
        out, derived = state_mod.split_output(res)
        jax.block_until_ready(out)
        assert np.array_equal(np.asarray(out), plain)
        d = np.asarray(derived)
        assert d.shape == (32, len(state_mod.DERIVED_FIELDS))
        # derived features gather PRE-update: a key's first record of
        # the stream sees count 0
        first_rows = [
            int(np.flatnonzero(X[:, 0] == k)[0])
            for k in np.unique(X[:, 0])
        ]
        assert all(d[r, 0] == 0.0 for r in first_rows)

    def test_fold_columns_ground_truth(self, gbm):
        import jax

        from flink_jpmml_tpu.runtime.pipeline import dispatch_quantized

        q = gbm.quantized_scorer()
        t, m = _table(capacity=64)
        batches = _batches(2, keys=1, seed=3)  # a single key: col 0
        for X, offs in batches:
            X[:, 0] = 7.0
        scores = np.concatenate([
            np.asarray(dispatch_quantized(q, X)).ravel()
            for X, _ in batches
        ])
        for X, offs in batches:
            dispatch_quantized(q, X, state=t, offsets=offs)
        jax.block_until_ready(t.values)
        kh = int(t.hash_keys(np.array([7]))[0])
        slot = int(np.flatnonzero(t._occ & (t._keys == kh))[0])
        v = np.asarray(t.values)[slot]
        assert v[COL_COUNT] == 64.0
        # offsets 0..63 sit inside stride 0: every product-form weight
        # is exactly 1, so the decayed count equals the plain count
        assert v[COL_DCOUNT] == 64.0
        assert v[COL_LAST_T] == 0.0
        assert v[COL_MIN] == scores.min()
        assert v[COL_MAX] == scores.max()
        np.testing.assert_allclose(
            v[COL_SUM], scores.sum(dtype=np.float64), rtol=1e-5
        )
        # scratch row stays zero: padding/bypass can never leak state
        assert not np.asarray(t.values)[t.scratch].any()
        assert m.struct_snapshot()["counters"]["state_records"] == 64

    def test_donate_matches_copy_fold(self, gbm):
        import jax

        from flink_jpmml_tpu.runtime.pipeline import dispatch_quantized

        q = gbm.quantized_scorer()
        batches = _batches(3, keys=16, seed=5)
        tables = []
        for donate in (False, True):
            t, _ = _table(capacity=64)
            for X, offs in batches:
                dispatch_quantized(
                    q, X.copy(), state=t, offsets=offs, donate=donate,
                )
            jax.block_until_ready(t.values)
            tables.append(np.asarray(t.values).copy())
        assert tables[0].tobytes() == tables[1].tobytes()


_ROWS, _SCRATCH, _DECAY = 1024, 1000, 0.999
_INIT = np.array([0, 0, 0, 0, 0, 0, np.inf, -np.inf], np.float32)
_EXACT_COLS = [COL_COUNT, COL_LAST_T, COL_MIN, COL_MAX]
_SUM_COLS = [COL_SUM, COL_SQSUM, COL_DCOUNT, COL_DSUM]


def _prior_table(rng):
    """A ``[_ROWS, 8]`` table as earlier dispatches leave it: rows
    0..199 hold folded state, the rest are fresh (±inf extrema), the
    scratch row zero."""
    S = np.tile(_INIT, (_ROWS, 1))
    n = rng.integers(1, 40, size=200).astype(np.float32)
    mean = rng.normal(0.0, 2.0, size=200).astype(np.float32)
    S[:200, COL_COUNT] = n
    S[:200, COL_SUM] = n * mean
    S[:200, COL_SQSUM] = n * (mean * mean + 1.0)
    S[:200, COL_DCOUNT] = 0.9 * n
    S[:200, COL_DSUM] = 0.9 * n * mean
    S[:200, COL_LAST_T] = rng.integers(0, 5, size=200)
    S[:200, COL_MIN] = mean - 2.0
    S[:200, COL_MAX] = mean + 2.0
    S[_SCRATCH] = 0.0
    return S


def _heavy_duplicates(rng, B):
    # four fifths of the dispatch on one slot, the rest on a few others
    slots = np.where(
        rng.random(B) < 0.8, 3, rng.integers(0, 12, size=B)
    )
    return slots, np.zeros(B, bool), np.zeros(B, bool)


def _fresh_slots(rng, B):
    # every record lands on a freshly claimed slot: half of them rows
    # that held another key's state, half rows that held ±inf already
    slots = np.concatenate([
        rng.integers(100, 130, size=B // 2),
        rng.integers(300, 330, size=B - B // 2),
    ])
    return slots, np.ones(B, bool), np.zeros(B, bool)


def _bypassed_rows(rng, B):
    # shed replays and pad rows ride the scratch slot with weight 0,
    # beside live records (some on fresh slots)
    slots = rng.integers(0, 400, size=B)
    bypass = rng.random(B) < 0.4
    return slots, (slots >= 200) & ~bypass, bypass


def _scratch_only(rng, B):
    return np.zeros(B, np.int64), np.zeros(B, bool), np.ones(B, bool)


def _late_reset_mark(rng, B):
    # a freshly claimed slot whose mark rides ONE record of its group,
    # never the first to arrive: the whole group starts from _INIT
    slots = rng.integers(100, 130, size=B)
    reset = np.zeros(B, bool)
    for s in np.unique(slots)[::2]:
        reset[rng.choice(np.flatnonzero(slots == s)[1:])] = True
    return slots, reset, np.zeros(B, bool)


def _one_slot(rng, B):
    return np.full(B, 7), np.zeros(B, bool), np.zeros(B, bool)


def _no_duplicate(rng, B):
    # every record a group of its own (as many as the table has rows
    # for), a third of them on fresh slots
    slots = rng.permutation(_SCRATCH)[:B]
    return slots, slots >= 650, np.zeros(slots.shape[0], bool)


_CASES = pytest.mark.parametrize(
    "case",
    [_heavy_duplicates, _fresh_slots, _bypassed_rows, _scratch_only,
     _late_reset_mark, _one_slot, _no_duplicate],
    ids=lambda f: f.__name__.strip("_"),
)


def _numpy_fold(S, score, slots, rel, w, reset):
    """The same transition in float64 numpy, one ufunc.at a column."""
    S = S.astype(np.float64)
    S[np.where(reset, slots, _SCRATCH)] = _INIT
    pre = S[slots]
    n = np.maximum(pre[:, COL_COUNT], 1.0)
    mean = pre[:, COL_SUM] / n
    derived = np.stack([
        pre[:, COL_COUNT], mean,
        np.maximum(pre[:, COL_SQSUM] / n - mean * mean, 0.0),
        pre[:, COL_DCOUNT] * np.power(_DECAY, rel),
        pre[:, COL_DSUM] / np.maximum(pre[:, COL_DCOUNT], 1e-30),
        rel - pre[:, COL_LAST_T], pre[:, COL_MIN], pre[:, COL_MAX],
    ], axis=1)
    derived[pre[:, COL_COUNT] <= 0] = 0.0
    terms = np.zeros((len(slots), 8))
    terms[:, COL_COUNT] = 1.0
    terms[:, COL_SUM] = score
    terms[:, COL_SQSUM] = score * score
    terms[:, COL_DCOUNT] = w
    terms[:, COL_DSUM] = w * score
    np.add.at(S, slots, terms)
    # what float32 summation in any order may lose, per row and column
    mass = np.abs(S)
    np.add.at(mass, slots, np.abs(terms))
    np.maximum.at(S[:, COL_LAST_T], slots, rel)
    np.minimum.at(S[:, COL_MIN], slots, score)
    np.maximum.at(S[:, COL_MAX], slots, score)
    S[_SCRATCH] = 0.0
    return derived, S, (S[:, COL_COUNT] + 2.0)[:, None] * mass


def _dispatch(case, rng, B):
    """One dispatch's operands for a ``case`` above →
    ``(score, slots, rel, w, reset)`` as ``_state_step`` takes them,
    and the bypass marks."""
    slots, reset, bypass = case(rng, B)
    B = slots.shape[0]  # a case may have fewer records than asked for
    slots = np.where(bypass, _SCRATCH, slots).astype(np.int32)
    score = rng.normal(0.0, 3.0, size=B).astype(np.float32)
    rel = rng.integers(3, 9, size=B).astype(np.float32)
    w = np.where(
        bypass, 0.0, np.power(_DECAY, -rel.astype(np.float64))
    ).astype(np.float32)
    return (score, slots, rel, w, reset), bypass


def _jit_step():
    import jax

    from flink_jpmml_tpu.compile import statekernel

    return jax.jit(
        lambda S, *a: statekernel._state_step(S, *a, _SCRATCH, _DECAY)
    )


class TestStateStep:
    @_CASES
    def test_matches_float64_numpy_fold(self, case):
        rng = np.random.default_rng(25)
        (score, slots, rel, w, reset), bypass = _dispatch(case, rng, 512)
        S0 = _prior_table(rng)
        derived, S1 = (np.asarray(a) for a in _jit_step()(
            S0, score, slots, rel, w, reset
        ))
        want_d, want, lost = _numpy_fold(
            S0, score.astype(np.float64), slots,
            rel.astype(np.float64), w.astype(np.float64), reset,
        )
        eps = np.finfo(np.float32).eps
        # counts, last_t and the extrema are exact (±inf of a row no
        # record reached included); the sums are float32 sums
        assert np.array_equal(S1[:, _EXACT_COLS], want[:, _EXACT_COLS])
        assert (
            np.abs(S1[:, _SUM_COLS] - want[:, _SUM_COLS])
            <= eps * lost[:, _SUM_COLS]
        ).all()
        assert not S1[_SCRATCH].any()
        untouched = np.setdiff1d(np.arange(_ROWS), slots)
        assert S1[untouched].tobytes() == S0[untouched].tobytes()
        # derived features read the table as of the batch start
        assert np.array_equal(derived[:, [0, 5, 6, 7]],
                              want_d[:, [0, 5, 6, 7]])
        np.testing.assert_allclose(
            derived[:, [1, 3, 4]], want_d[:, [1, 3, 4]], rtol=1e-5
        )
        np.testing.assert_allclose(
            derived[:, 2], want_d[:, 2], rtol=1e-4, atol=1e-4
        )
        assert not derived[bypass].any()

    @pytest.mark.parametrize(
        "last_t, lo, hi, sign",
        [
            (np.inf, -np.inf, np.inf, 1.0),
            (-0.0, -0.0, np.inf, 1.0),  # min(-0.0, score > 0) holds
            (-0.0, -np.inf, -0.0, -1.0),  # max(-0.0, score < 0) holds
        ],
        ids=["inf", "neg_zero_min", "neg_zero_max"],
    )
    def test_add_returns_the_columns_it_does_not_own(
            self, last_t, lo, hi, sign):
        """The add scatters whole rows, so ``COL_LAST_T``, ``COL_MIN``
        and ``COL_MAX`` ride it with the add's identity, −0.0: where no
        extremum of the batch moves them they come back byte for byte,
        ±inf and a stored −0.0 included (``−0.0 + 0.0`` is ``+0.0``: an
        identity of +0.0 would flip that sign bit)."""
        rng = np.random.default_rng(28)
        B, rows = 96, np.arange(20, 28)
        S0 = _prior_table(rng)
        S0[rows, COL_LAST_T], S0[rows, COL_MIN], S0[rows, COL_MAX] = (
            last_t, lo, hi)
        slots = rng.choice(rows, size=B).astype(np.int32)
        score = (sign * rng.uniform(0.5, 3.0, size=B)).astype(np.float32)
        rel = np.full(B, -1.0, np.float32)  # never above a -0.0 last_t
        w = np.power(_DECAY, -rel.astype(np.float64)).astype(np.float32)
        _, S1 = _jit_step()(S0, score, slots, rel, w, np.zeros(B, bool))
        S1 = np.asarray(S1)
        kept = [COL_LAST_T, COL_MIN, COL_MAX]
        assert S1[:, kept].tobytes() == S0[:, kept].tobytes()
        assert np.array_equal(
            S1[:, COL_COUNT] - S0[:, COL_COUNT],
            np.bincount(slots, minlength=_ROWS),
        )
        assert (S1[rows, COL_SUM] != S0[rows, COL_SUM]).all()

    @_CASES
    def test_same_batch_twice_gives_the_same_bytes(self, case):
        """Replay-exact: the sort that groups a dispatch by slot may
        put a slot's duplicates in another order than they arrived in,
        but the same dispatch folded into the same table sums them in
        the SAME order every time (here on the CPU; PERF.md §6, PR 35
        has the chip's reading)."""
        rng = np.random.default_rng(28)
        operands, _ = _dispatch(case, rng, 4096)
        S0, step = _prior_table(rng), _jit_step()
        once, twice = (
            [np.asarray(a).tobytes() for a in step(S0, *operands)]
            for _ in range(2)
        )
        assert once == twice

    @pytest.mark.parametrize("terms", ["dyadic", "floats"])
    @pytest.mark.parametrize(
        "case",
        [_heavy_duplicates, _bypassed_rows, _late_reset_mark, _no_duplicate],
        ids=lambda f: f.__name__.strip("_"),
    )
    def test_a_permuted_dispatch_folds_to_the_same_table(self, case, terms):
        """The fold groups a dispatch by slot itself, so the order its
        records arrive in decides nothing but the order of the float32
        additions inside a group. ``derived`` goes with the records
        byte for byte (it reads the table as of the batch's start);
        counts, last_t and the extrema come out byte for byte; the sums
        byte for byte wherever float32 addition is exact (``dyadic``:
        scores in 1/8ths, weights 1, 2 or 4) and inside the fold's
        error bound otherwise (``floats``: the in-group order follows
        an unstable sort of (slot, index), which a permutation moves)."""
        rng = np.random.default_rng(35)
        (score, slots, rel, w, reset), _ = _dispatch(case, rng, 1024)
        S0 = _prior_table(rng)
        if terms == "dyadic":
            score = (rng.integers(-64, 65, size=score.shape) / 8.0).astype(
                np.float32)
            w = np.where(w > 0, np.exp2(rng.integers(0, 3, size=w.shape)),
                         0.0).astype(np.float32)
            S0[:200, _SUM_COLS] = np.round(S0[:200, _SUM_COLS] * 64) / 64
        step = _jit_step()
        d1, S1 = (np.asarray(a) for a in step(S0, score, slots, rel, w, reset))
        p = rng.permutation(slots.shape[0])
        d2, S2 = (np.asarray(a) for a in step(
            S0, score[p], slots[p], rel[p], w[p], reset[p]))
        assert d2.tobytes() == d1[p].tobytes()
        assert S2[:, _EXACT_COLS].tobytes() == S1[:, _EXACT_COLS].tobytes()
        if terms == "dyadic":
            assert S2.tobytes() == S1.tobytes()
        else:
            _, _, lost = _numpy_fold(
                S0, score.astype(np.float64), slots, rel.astype(np.float64),
                w.astype(np.float64), reset)
            assert (
                np.abs(S2[:, _SUM_COLS] - S1[:, _SUM_COLS])
                <= 2 * np.finfo(np.float32).eps * lost[:, _SUM_COLS]
            ).all()

    def test_the_table_is_written_once_and_in_whole_rows(self):
        """Tripwire: on the TPU the table is column-major, tiled
        (8, 128), and a scatter into part of a row is lowered to a flat
        copy of the whole table (one column) or a loop over the records
        (a slice of columns: the add of the five accumulator columns
        until PR 28). The fold has ONE gather that reads the table and
        ONE scatter of records into it (PR 35), and that scatter and
        the scratch row's zeroing carry an update window of all 8
        columns."""
        import re

        import jax
        import jax.numpy as jnp

        B, f32 = 64, jnp.float32
        sds = jax.ShapeDtypeStruct
        module = _jit_step().lower(
            sds((_ROWS, 8), f32), sds((B,), f32), sds((B,), jnp.int32),
            sds((B,), f32), sds((B,), f32), sds((B,), jnp.bool_),
        ).compiler_ir("stablehlo")
        windows, gathers = [], 0

        def walk(op):
            nonlocal gathers
            for region in op.regions:
                for block in region.blocks:
                    for o in block.operations:
                        walk(o.operation)
                        name = o.operation.name
                        if name not in ("stablehlo.scatter",
                                        "stablehlo.gather"):
                            continue
                        if list(o.operands[0].type.shape) != [_ROWS, 8]:
                            continue
                        if name == "stablehlo.gather":
                            gathers += 1
                            continue
                        upd = o.operands[2].type
                        # an empty window is left out of the text
                        m = re.search(
                            r"update_window_dims = \[([\d, ]*)\]",
                            str(o.attributes["scatter_dimension_numbers"]),
                        )
                        windows.append(int(np.prod([
                            upd.shape[int(d)]
                            for d in re.findall(r"\d+", m.group(1) if m else "")
                        ])))

        walk(module.operation)
        # the groups' rows, and the scratch row's zeroing
        assert windows == [8, 8], windows
        assert gathers == 1


class TestNamedScopes:
    def test_scopes_are_metadata_only(self, gbm, monkeypatch):
        """``fjt.forest`` / ``fjt.fold.gather`` / ``fjt.fold.scatter``
        (compile/statekernel.py) name the program's parts on a device
        trace and change nothing else: the lowered program without
        its location metadata is byte-identical with and without
        them."""
        import contextlib

        import jax

        from flink_jpmml_tpu.compile import statekernel

        q = gbm.quantized_scorer()
        t, _ = _table(capacity=64)
        (X, offs) = _batches(1, keys=8)[0]
        payload, K = q.pad_wire(q.wire.encode(X))
        slots, reset, rel, w = t.assign_slots(
            t.hash_keys(t.extract_keys(X)), offs
        )

        def lowered():
            for k in [k for k in q._multi_fns if k[0] == "state"]:
                del q._multi_fns[k]
            fn = statekernel.entry_for(
                q, "wire", K, False, t.spec.decay, t.scratch
            )
            low = fn.lower(
                q.params, payload, t.values, slots, rel, w, reset
            )
            # the program as lowered, without and with its locations
            # (the op names a device trace shows)
            return low.as_text(), low.as_text(debug_info=True)

        scoped = lowered()
        for scope in ("fjt.forest", "fjt.fold.gather", "fjt.fold.scatter"):
            assert f"/{scope}/" in scoped[1], scope
        monkeypatch.setattr(
            jax, "named_scope", lambda name: contextlib.nullcontext()
        )
        bare = lowered()
        monkeypatch.undo()
        assert "fjt." not in bare[1]
        assert "fjt." not in scoped[0]
        assert scoped[0] == bare[0]


class TestCheckpointRoundtrip:
    def _folded(self, gbm, capacity=64):
        import jax

        from flink_jpmml_tpu.runtime.pipeline import dispatch_quantized

        q = gbm.quantized_scorer()
        t, _ = _table(capacity=capacity)
        for X, offs in _batches(2, keys=12, seed=9):
            dispatch_quantized(q, X, state=t, offsets=offs)
        jax.block_until_ready(t.values)
        return t

    def test_payload_roundtrip_byte_exact(self, gbm):
        t = self._folded(gbm)
        p = t.to_payload()
        t2, _ = _table(capacity=64)
        assert t2.from_payload(p)
        assert (
            np.asarray(t2.values).tobytes()
            == np.asarray(t.values).tobytes()
        )
        assert np.array_equal(t2._keys, t._keys)
        assert np.array_equal(t2._occ, t._occ)
        assert t2.resident == t.resident
        # restore arms the exactly-once replay guard
        assert t2.skip_until == t.applied_hi == 64

    def test_sidecar_roundtrip_byte_exact(self, gbm, tmp_path):
        t = self._folded(gbm)
        name = t.save_sidecar(str(tmp_path))
        assert name is not None and (tmp_path / name).exists()
        t2, _ = _table(capacity=64)
        assert t2.restore_sidecar(str(tmp_path), name)
        assert (
            np.asarray(t2.values).tobytes()
            == np.asarray(t.values).tobytes()
        )
        assert t2.skip_until == t.applied_hi
        # a second fold on the restored table must keep working
        from flink_jpmml_tpu.runtime.pipeline import dispatch_quantized

        q = gbm.quantized_scorer()
        X, offs = _batches(3, keys=12, seed=9)[2]
        dispatch_quantized(q, X, state=t2, offsets=offs)

    def test_capacity_mismatch_refused(self, gbm):
        t = self._folded(gbm)
        t2, _ = _table(capacity=128)
        assert not t2.from_payload(t.to_payload())


class TestMeshMigration:
    def test_degraded_migration_preserves_every_key(self, gbm):
        import jax

        from flink_jpmml_tpu.parallel.mesh import make_mesh
        from flink_jpmml_tpu.runtime.pipeline import dispatch_quantized
        from flink_jpmml_tpu.utils.config import MeshConfig

        q = gbm.quantized_scorer()
        t, _ = _table(capacity=256)
        for X, offs in _batches(2, keys=40, seed=21):
            dispatch_quantized(q, X, state=t, offsets=offs)
        jax.block_until_ready(t.values)
        every = np.arange(t.capacity)
        before = t.read_rows(every)
        resident = t.resident
        t.shard(make_mesh(MeshConfig(data=4, model=2)))
        assert (t.n_shards, t.shard_slots, t.shard_rows) == (4, 64, 256)
        assert t.read_rows(every).tobytes() == before.tobytes()
        # chip loss: the rebuilt mesh spans half the data axis — every
        # surviving key's row re-places byte-identically (slot = hash %
        # capacity is mesh-independent; which chip holds it follows the
        # width: ``locate``)
        t.migrate(
            make_mesh(MeshConfig(data=2, model=2), allow_subset=True)
        )
        assert t.n_shards == 2
        assert t.read_rows(every).tobytes() == before.tobytes()
        assert t.resident == resident
        # and the fold keeps running on the migrated placement
        X, offs = _batches(3, keys=40, seed=21)[2]
        dispatch_quantized(q, X, state=t, offsets=offs)
        jax.block_until_ready(t.values)
        after = t.read_rows(every)
        assert after[:, COL_COUNT].sum() > before[:, COL_COUNT].sum()


class TestNeverDelivered:
    def test_dlq_batch_never_folds(self, gbm, tmp_path, monkeypatch):
        """The PR 8/12 never-delivered contract extended to state: the
        poisoned record is quarantined to the DLQ, never delivered, and
        provably never folded (its unique key is absent from the
        table).  A rollback sheds the in-flight fold window back to the
        last snapshot (here the initial EMPTY table) and suspect-mode
        probation keeps trailing batches stateless, so we assert fold
        INVARIANTS — per-key ≤ stream ground truth, whole armed
        batches only — not an exact batch suffix, which would pin the
        probation-window tuning into the contract."""
        import jax

        from flink_jpmml_tpu.runtime import faults
        from flink_jpmml_tpu.runtime.block import (
            BlockPipeline, FiniteBlockSource,
        )
        from flink_jpmml_tpu.runtime.dlq import DeadLetterQueue

        monkeypatch.setenv("FJT_RETRY_BASE_S", "0.01")
        B, blocks, keys = 32, 5, 6
        rng = np.random.default_rng(17)
        data = rng.normal(0.0, 1.0, size=(B * blocks, 4)).astype(
            np.float32
        )
        data[:, 0] = rng.integers(0, keys, size=B * blocks).astype(
            np.float32
        )
        poison = 70  # batch 2 ([64, 96)): batches 0-1 roll back
        # the quarantined record gets a key NO other record has, so
        # "never folded" is checkable as key-absence from the table
        data[poison, 0] = 99.0
        seen = []
        m = MetricsRegistry()
        dlq = DeadLetterQueue(str(tmp_path / "dlq"), metrics=m)
        assert faults.install_from_env(
            f"poison_record:offset={poison}"
        )
        try:
            pipe = BlockPipeline(
                FiniteBlockSource(data, block_size=B), gbm,
                lambda out, n, first_off: seen.append((first_off, n)),
                metrics=m,
                use_native=False,
                in_flight=1,
                dlq=dlq,
                state=StateSpec(capacity=64, key_col=0),
            )
            pipe.run_until_exhausted(timeout=60.0)
        finally:
            faults.clear()
        assert sorted(set(dlq.offsets())) == [poison]
        covered = np.zeros(B * blocks, np.int64)
        for off, n in seen:
            covered[off: off + n] += 1
        assert sorted(np.flatnonzero(covered == 0).tolist()) == [poison]
        t = pipe._state
        jax.block_until_ready(t.values)
        folded_keys = t._keys[t._occ]
        vals = np.asarray(t.values)[: t.capacity]
        folded = dict(zip(
            folded_keys.tolist(),
            vals[t._occ, COL_COUNT].tolist(),
        ))
        # the quarantined record's key never reached the table
        poison_hash = int(t.hash_keys(np.array([99]))[0])
        assert poison_hash not in folded
        # per-key no-over-fold vs. stream ground truth
        kh = t.hash_keys(data[:, 0].astype(np.int64))
        uk, n = np.unique(kh, return_counts=True)
        true_counts = dict(zip(uk.tolist(), n.tolist()))
        for k, cnt in folded.items():
            assert cnt <= true_counts[k], (k, cnt, true_counts[k])
        # folds land as whole armed batches: at least one batch made
        # it through after recovery, and never a partial batch
        total = sum(folded.values())
        assert total >= B and total % B == 0, folded
        c = m.struct_snapshot()["counters"]
        assert c["state_rollbacks"] >= 1
