"""Tests of what the churn deployment (``gbm500_keyed_churn``) brings to
the benchmark: its key mix (YCSB workload D's "read latest"), the fill
of a FULL table, its plain reference of a table that evicts, its
readers, and its cell run whole on the CPU: with a population that
exceeds the slots, so that the table is full and every admission
evicts; at the rehearsal's tiny size, where the table cannot fill and
nothing is evicted; and with each of three faults planted in the
program's claim rounds, which ``correct`` has to catch and ``broken``
to name: a key admitted on a row that was not zeroed, a loser of an
eviction race sent to the scratch row, an eviction that takes a slot
touched in the same call. No device number comes out of these.

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests -q -p no:cacheprovider
"""

import argparse
import hashlib
import json
import os
import sys

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for p in (ROOT, BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)

from lib import byname, prefill  # noqa: E402
from lib.manifest import manifest_faults  # noqa: E402
from lib.stream import BLOCK, Stream  # noqa: E402
from reference import churn_ref  # noqa: E402

CELL = "gbm500_keyed_churn.kafka_saturated"
LOAD_FAULTS = ("least_lead_records.harness", "least_lead_records.producer")
MIX = {"kind": "latest", "insert_share": 0.05, "zipf_constant": 0.99}
FULL_DOMAIN = 320000000
LOADED = 300000000

latest = byname.load("lib/keymix", "latest")
block_full = byname.load("paths", "block_full")


def load_config(name):
    with open(os.path.join(BENCH, "configs", f"{name}.json")) as fh:
        return json.load(fh)


# -- the key mix -----------------------------------------------------------

def test_zeta_in_closed_form_is_the_sum():
    for n in (10, 65536, 10**6):
        exact = float(np.sum(np.arange(1, n + 1, dtype=np.float64) ** -0.99))
        assert latest.zeta(n, 0.99) == pytest.approx(exact, rel=1e-12)
    assert latest.zeta(LOADED, 0.99) == pytest.approx(22.1310, abs=1e-3)


@pytest.mark.parametrize("domain", [45000, FULL_DOMAIN])
def test_a_block_is_pure_and_holds_exactly_its_inserts(domain):
    loaded = latest.loaded_of(domain)
    assert loaded == domain - domain // 16
    assert latest.inserts_a_block(MIX, BLOCK) == 3277
    for b in (0, 3):
        r = latest.ranks(b, 2**31 + 9, domain, MIX, BLOCK)
        again = latest.ranks(b, 2**31 + 9, domain, dict(MIX), BLOCK)
        assert np.array_equal(r, again)
        assert r.dtype == np.int64 and r.min() >= 0 and r.max() < domain
        assert not np.array_equal(
            r, latest.ranks(b, 2**31 + 10, domain, MIX, BLOCK))
        if domain == FULL_DOMAIN:
            # the j-th insert of the stream is rank loaded + j: 5% of
            # the block's records carry a key no record before the
            # block carried, one rank after another
            first = loaded + b * 3277
            new = np.flatnonzero(r >= first)
            assert np.array_equal(np.unique(r[new]),
                                  np.arange(first, first + 3277))
            assert r[new[0]] == first
    # the stream takes it by name, whatever the chunking
    s = Stream(7, 4, domain, MIX, 64)
    whole = s.ranks(BLOCK - 5, 2 * BLOCK + 5)
    assert np.array_equal(whole[5:5 + BLOCK], latest.ranks(
        1, 7, domain, MIX, BLOCK))


def test_the_digest_of_two_blocks_is_pinned():
    got = hashlib.sha256(b"".join(
        latest.ranks(b, 2**31 + 5, FULL_DOMAIN, MIX, BLOCK).tobytes()
        for b in (0, 7))).hexdigest()
    assert got == PINNED_DIGEST


def test_reads_follow_the_head():
    """Of the records that bring no new key, the share that goes to the
    newest key of the moment is 1/zeta (4.5%: the zipfian's head lies
    over recency), and two in five reads fall on a key born inside the
    last block."""
    at_head = reads = young = 0
    for b in range(4):
        r = latest.ranks(b, 2**31 + 77, FULL_DOMAIN, MIX, BLOCK)
        before = np.maximum.accumulate(
            np.concatenate([[LOADED - 1 + b * 3277], r[:-1]]))
        insert, newest = r > before, np.maximum(r, before)
        assert insert.sum() == 3277
        reads += int((~insert).sum())
        at_head += int(((r == newest) & ~insert).sum())
        young += int(((newest - r < 3277) & ~insert).sum())
    share = 1.0 / latest.zeta(LOADED, 0.99)
    assert 0.9 * share < at_head / reads < 1.1 * share
    assert 0.30 < young / reads < 0.50


# -- the fill --------------------------------------------------------------

def _table(capacity, probe=64):
    from flink_jpmml_tpu.runtime.state import KeyedStateTable, StateSpec

    return KeyedStateTable(StateSpec(capacity=capacity, probe=probe))


def _hashes(loaded):
    return prefill.crc32_of_ids(prefill.ids_of_ranks(np.arange(loaded)))


@pytest.mark.parametrize("cap,loaded,probe,grain", [
    (61001, 95367, 64, 64), (4093, 6500, 64, 1), (4093, 12000, 8, 256)])
def test_a_full_fill_leaves_no_slot_empty_and_stamps_fall_with_recency(
        cap, loaded, probe, grain):
    lines = []
    f = block_full.fill_full(cap, probe, loaded, grain, lines.append)
    keys, occ, touch = f["keys"], f["occ"], f["touch"]
    assert occ.all() and f["stats"]["empty_slots"] == 0
    assert f["stats"]["resident"] == cap
    assert ((np.arange(cap) - keys.astype(np.int64) % cap) % cap < probe).all()
    assert np.unique(keys).size == cap  # no hash twice
    # every stamp is written (none is the untouched zero) and is its
    # key's recency, a stamp ``grain`` ranks
    H = _hashes(loaded)
    rank_of = dict(zip(H.tolist(), range(loaded)))  # the newest of a hash
    rank = np.array([rank_of[h] for h in keys.tolist()])
    assert np.array_equal(touch, 1 + rank // grain) and (touch > 0).all()
    # the newer ranks are preferred, slot by slot
    assert np.median(rank) > loaded - cap
    # the table's public lookup finds every key where the record has it
    t = _table(cap, probe)
    t._keys[:], t._occ[:], t._touch[:] = keys, occ, touch
    t.resident, t._seq = cap, int(touch.max())
    slots, reset, _, _ = t.assign_slots(keys, np.zeros(cap, np.int64))
    assert np.array_equal(slots, np.arange(cap)) and not reset.any()
    assert any("of the home block before them" in s for s in lines)
    # nearly every slot holds one of the keys a strict newest-first
    # fill would have kept (each neighbourhood keeps ITS newest)
    assert f["stats"][
        "slots_with_one_of_the_newest_keys_that_would_fill_the_table"] > (
            0.9 if probe == 64 else 0.75)  # 4 homes are a small sample


def test_a_population_that_cannot_fill_the_table_is_placed_whole():
    t = _table(61001)
    f = block_full.fill_sparse(t, 42915, 64)
    assert f["stats"]["resident"] == t.resident == int(f["occ"].sum())
    assert f["stats"]["empty_slots"] == 61001 - t.resident > 0
    at = {int(h): s for s, h in enumerate(f["keys"].tolist()) if f["occ"][s]}
    slot = np.array([at[h] for h in _hashes(42915).tolist()])
    assert np.array_equal(f["touch"][slot], 1 + np.arange(42915) // 64)
    assert (f["touch"][~f["occ"]] == 0).all()


def test_a_population_that_neither_fits_nor_fills_is_refused():
    with pytest.raises(RuntimeError, match="neither fits"):
        block_full.fill_full(4093, 8, 4200, 64, lambda s: None)


# -- the plain reference ------------------------------------------------------

def _windows(stamps):
    """Capacity 16, probe 4, full: slot ``s`` holds hash ``s``."""
    return churn_ref.WindowTable(
        np.arange(16, dtype=np.uint32), np.ones(16, bool),
        np.array(stamps, np.int32), 4)


def test_the_reference_gives_a_contended_slot_to_the_smallest_hash():
    # slot 3 is the least recent of the window 3..6; 4, 5, 6 tie
    t = _windows([9] * 3 + [1, 2, 2, 2] + [9] * 9)
    got = t.call([83, 67, 99, 67])
    assert got == {67: (3, True), 83: (4, True), 99: (5, True)}
    assert (t.admitted, t.evicted, t.overflowed) == (3, 3, 0)
    assert t.thrown_out == [3, 4, 5] and t.keys[3:7].tolist() == [
        67, 83, 99, 6]
    assert t.stamp[3:7].tolist() == [10, 10, 10, 2]
    # 6 is resident and of the call: nobody's victim; 131 finds its
    # whole window touched by this call and goes to scratch
    got = t.call([6, 115, 131, 147, 163])
    assert got[6] == (6, False) and got[163] == (16, False)
    assert sorted(got[h][0] for h in (115, 131, 147)) == [3, 4, 5]
    assert t.overflowed == 1 and t.evicted == 6


def test_the_reference_keeps_held_slots_and_claims_empties_in_probe_order():
    t = _windows([5] * 16)
    assert t.call([19, 35], held=[3]) == {19: (4, True), 35: (5, True)}
    t = churn_ref.WindowTable(
        np.zeros(16, np.uint32), np.zeros(16, bool), np.zeros(16, np.int32), 4)
    # three keys of one home: one claimant a slot a round, smallest first
    assert t.call([35, 3, 19]) == {3: (3, True), 19: (4, True), 35: (5, True)}
    assert t.call([19, 51]) == {19: (4, False), 51: (6, True)}
    assert (t.admitted, t.evicted) == (4, 0)


def test_rows_restart_from_zero_at_a_keys_last_admission():
    t = _windows(list(range(16)))
    rows = churn_ref.Rows()
    rows.fold(t, [3, 3, 19], [1.0, 2.0, 5.0])  # 19 evicts slot 4 (hash 4)
    rows.fold(t, [4, 3], [7.0, 3.0])           # 4 comes back, on slot 5
    assert rows.slot == {3: 3, 19: 4, 4: 5} and rows.admitted == {19, 4}
    first = np.array([[2.0, 10.0], [9.0, 9.0], [9.0, 9.0]])
    n, s = rows.expected([3, 19, 4], first)
    assert n.tolist() == [5.0, 1.0, 1.0] and s.tolist() == [16.0, 5.0, 7.0]
    rows.fold(t, [35], [1.0])  # evicts the least recent of 3..6: slot 6
    rows.fold(t, [51], [1.0])  # ... and then slot 4: 19 has no row any more
    assert 19 not in rows.slot and rows.slot[51] == 4


# -- the configuration and the manifest -------------------------------------

def test_the_deployment_states_its_guarantees():
    one, churn = load_config("gbm500_keyed"), load_config("gbm500_keyed_churn")
    assert churn["must_stay_zero"] == [
        n for n in one["must_stay_zero"] if n != "state_evictions"]
    assert "state_overflow" in churn["must_stay_zero"]
    assert any("state_evictions" in g for g in churn["guarantees"])
    assert any("smallest hash" in g and "name again" in g
               for g in churn["guarantees"])  # the rule, repeated
    for k in ("model", "state", "pipeline", "compile_batch", "table_slots",
              "warmup_records", "expected_backends", "chips"):
        assert churn[k] == one[k], k  # the same compiled program
    assert churn["key_domain"] == FULL_DOMAIN <= 0x5F000000 - 0x4B000000
    assert latest.loaded_of(churn["key_domain"]) == LOADED
    assert LOADED >= 1.5 * churn["table_slots"]
    assert churn["table_slots"] * 32 >= 4 * 2**30
    assert churn["resident_keys_at_start"] <= BLOCK  # the path's fill rules
    assert "SUPERSEDES" in churn["assumed"]["resident_keys_at_start"]
    assert block_full.STAMP_GRAIN == BLOCK
    assert set(churn["reduced"]) == {
        "chips", "key_domain", "checkpoint_in_window"}
    with open(os.path.join(BENCH, "traffic",
                           "kafka_saturated.gbm500_keyed_churn.json")) as fh:
        overlay = json.load(fh)
    assert set(overlay) == {"why", "key_mix"} and overlay["key_mix"] == MIX


def test_the_manifest_takes_the_cell_as_entries_alone():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        manifest = json.load(fh)
    # where the cell stands and which lists name it: the manifest's rules
    assert manifest_faults(manifest) == []
    cell = next(w for w in manifest["workloads"] if w["name"] == CELL)
    assert (cell["chips"], cell["traffic"]) == (1, "kafka_saturated")
    assert len(cell["why"]) <= 200
    entry = next(c for c in manifest["configs"]
                 if c["name"] == "gbm500_keyed_churn")
    assert len(entry["source"]) <= 200 and len(entry["why"]) <= 200
    # its own metrics are the state layer's, and each has its reader here
    own = {m["name"]: m for m in manifest["per_layer"]
           if m["name"].endswith(".churn")}
    assert set(CHURN_READERS) <= set(own)
    for m in own.values():
        assert (m["layer"], m["moves"]) == ("state", "records_per_s")


# -- the readers -------------------------------------------------------------

CHURN_READERS = (
    "claim_us_per_krec.churn", "route_pending_frac.churn",
    "state_admissions_per_krec.churn", "state_evictions_per_krec.churn",
)


@pytest.mark.parametrize("name", CHURN_READERS)
def test_a_reader_finds_nothing_in_a_program_without_the_state_plane(name):
    snap = {"counters": {"records_out": 10.0, "batches": 2.0},
            "histograms": {}, "ts": 1.0}
    ctx = {"snap0": snap, "snap1": dict(snap, ts=2.0), "batches": [(1.5, 10)],
           "trace": None, "cfg": {}, "peaks": {}}
    assert byname.load("layer_metrics", name).read(ctx) is None


def test_churn_readers_on_a_recorded_window():
    c0 = {"state_records": 1000.0, "state_inserts": 10.0,
          "state_evictions": 0.0, "state_route_pending": 0.0}
    c1 = {"state_records": 5000.0, "state_inserts": 10.0,
          "state_evictions": 300.0, "state_route_pending": 600.0}
    ctx = {
        "snap0": {"counters": c0, "histograms": {}, "ts": 0.0},
        "snap1": {"counters": c1, "histograms": {
            'stage_seconds{stage="claim"}': {"sum": 0.002, "n": 4}},
            "ts": 1.0},
        "batches": [(0.5, 4000)], "trace": None, "cfg": {}, "peaks": {},
    }

    def read(name):
        return byname.load("layer_metrics", name).read(ctx)

    assert read("claim_us_per_krec.churn") == pytest.approx(500.0)
    assert read("route_pending_frac.churn") == pytest.approx(15.0)
    assert read("state_admissions_per_krec.churn") == pytest.approx(75.0)
    assert read("state_evictions_per_krec.churn") == pytest.approx(75.0)


# -- the cell, whole, on the CPU ---------------------------------------------

def tiny_churn_run(evicting=True, trace=0):
    import rehearse
    import run

    assert CELL in rehearse.cells()
    overrides = rehearse.TINY
    if evicting:
        # 93,750 keys loaded over 61,001 slots: the table is full from
        # the first record and every admission evicts (two stamps in
        # all at this size: the rule's ties do the choosing)
        overrides = {"cfg": dict(rehearse.TINY["cfg"], key_domain=100000),
                     "traffic": rehearse.TINY["traffic"]}
    args = argparse.Namespace(workload=CELL, seed=2**31 + 43, seconds=1.0,
                              trace=trace)
    return run.run_cell(args, overrides=overrides, on_chip=False)


def broken(res):
    return sorted(n for n in res["broken"] if n not in LOAD_FAULTS)


@pytest.mark.parametrize("evicting", [True, False])
def test_the_churn_cell_runs_whole_on_the_cpu(evicting):
    res = tiny_churn_run(evicting)
    c = res["compared"]
    assert broken(res) == [] and res["failed"] == 0
    for name in ("state_overflow_in_warmup", "state_keys_not_resident",
                 "state_rows_off_the_record", "state_counts_differing",
                 "state_admissions_under_new_keys", "offsets_lost",
                 "offsets_duplicated", "counter.state_overflow",
                 "compilations_in_window"):
        assert c[name]["value"] == 0, name
    assert "counter.state_evictions" not in c
    adm, evicted = c["state_admitted_in_warmup"], c["state_evicted_in_warmup"]
    assert adm["value"] == adm["limit"] > 0
    assert evicted["value"] == evicted["limit"]
    # a table that is not full claims empty slots
    assert evicted["value"] == (adm["value"] if evicting else 0)
    assert res["metrics"] == {}


def plant(monkeypatch, fault):
    """Wrap the program's claim rounds (what ``route`` leaves to them:
    every key the table has not got). The fault is planted wherever the
    rounds admit a key, which only the stream's calls do, and the
    path's own probe of the race on a table of eight slots: left alone,
    or the run would end there with the probe's sentence."""
    from flink_jpmml_tpu.runtime import state as state_mod

    real = state_mod.KeyedStateTable._claim_rounds
    planted = []

    def claim_rounds(self, khash, seq):
        keys0, touch0 = self._keys.copy(), self._touch.copy()
        slots, reset, collided = real(self, khash, seq)
        if not reset.any() or self.capacity < 64:
            return slots, reset, collided
        if fault == "not_zeroed":
            planted.append(int(reset.sum()))
            return slots, np.zeros_like(reset), collided
        cap, probe = self.capacity, self.spec.probe
        for h in np.unique(khash[reset])[::-1].tolist():  # largest hash first
            mine = khash == h
            a = int(slots[mine][0])
            if keys0[a] == h:
                continue  # it claimed an empty slot: no race to lose
            if fault == "loser_to_scratch":
                # as the program did before: the old key keeps the slot,
                # the newcomer's records are folded on the scratch row
                self._keys[a], self._touch[a] = keys0[a], touch0[a]
                slots[mine], reset[mine] = self.scratch, False
                self._c_overflow.inc(1)
                planted.append(h)
                break
            # "touched_in_call": the newcomer takes a slot of its window
            # that a resident key's record touched in this very call
            W = (h % cap + np.arange(probe)) % cap
            hit = W[(self._touch[W] == seq) & (self._keys[W] == keys0[W])
                    & (W != a)]
            if hit.size:
                r = int(hit[0])
                self._keys[a], self._touch[a] = keys0[a], touch0[a]
                self._keys[r] = h
                slots[mine] = r
                planted.append(h)
                break
        return slots, reset, collided

    monkeypatch.setattr(state_mod.KeyedStateTable, "_claim_rounds",
                        claim_rounds)
    return planted


@pytest.mark.parametrize("fault,named", [
    ("not_zeroed", {"state_counts_differing"}),
    ("loser_to_scratch", {"state_overflow_in_warmup", "counter.state_overflow",
                          "state_keys_not_resident"}),
    ("touched_in_call", {"state_rows_off_the_record",
                         "state_keys_not_resident"}),
])
def test_a_planted_fault_is_not_correct_and_names_itself(
        monkeypatch, fault, named):
    planted = plant(monkeypatch, fault)
    res = tiny_churn_run()
    assert planted
    assert res["correct"] is False
    assert named <= set(broken(res)), broken(res)
    assert res["compared"]["warmup_score_miss_over_tol"]["holds"]
    # the broken first, each beside its limit
    assert list(res["compared"])[:len(res["broken"])] == res["broken"]


PINNED_DIGEST = (
    "cc3b8f02b27646ed67519d985e97f17bb94114479be8ce51999b2b2659e9c656")
