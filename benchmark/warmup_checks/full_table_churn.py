"""Warm-up check ``full_table_churn``: what a table that was FULL when
the run started (``paths/block_full.py``) must hold after a warm-up
stream that brings keys nobody has seen (key mix ``latest``), by the
plain reference alone (``reference/churn_ref.py``): a table of bounded
probe windows under the deployment's stated eviction rule, started
from the benchmark's own record of the fill (which hash the fill put in
which slot, and each slot's stamp) and taken through the warm-up stream
call by call, one dispatch the sink received being one routing call
(``Path.calls``).

The reference says which keys the table had to admit and which it had
to throw out, where every key of the stream lives at the end, and what
its row holds: the row its slot started with plus the tally of all its
records if the table never admitted it, the tally since its last
admission alone if it did. Held to it are the table's own counters over
the warm-up (admissions, evictions, ``state_overflow``), the table's
public lookup of every key the reference still has (found, in the
reference's slot, nothing admitted), and the rows on the device (counts
exact, score sums to ``all_resident``'s tolerance). Keys of the stream
that share the table's uint32 hash with another key of the stream are
counted and left out of the rows, as in ``all_resident``.

``warmup_checks/all_resident.py`` says what a warm-up check is.
"""

from __future__ import annotations

import numpy as np

from lib import byname, prefill
from lib.stream import BLOCK
from reference import churn_ref

latest = byname.load("lib/keymix", "latest")
ADMITTED = ("state_inserts", "state_evictions")  # evictions last


def check(run: dict):
    import jax.numpy as jnp
    from flink_jpmml_tpu.runtime.state import COL_COUNT, COL_SUM

    assert (COL_COUNT, COL_SUM) == (0, 1)  # churn_ref.Rows.expected's columns
    log, path, n_warm = run["log"], run["path"], run["n_warm"]
    stream, table = run["stream"], path.table
    probe = int(run["cfg"]["state"]["probe"])
    faults = []
    loaded = latest.loaded_of(stream.domain)
    calls, at = list(path.calls), 0
    for first, n in calls:
        if first != at:
            break
        at += n
    if at < n_warm:
        faults.append(f"the sink's deliveries cover the warm-up stream in "
                      f"order only up to offset {at} of {n_warm}")
    ranks, ids = stream.ranks(0, n_warm), stream.ids(0, n_warm)
    khash = table.hash_keys(ids)
    # the reference: the fill's record, then the stream call by call
    ref = churn_ref.WindowTable(
        path.placed["keys"], path.placed["occ"], path.placed["touch"], probe)
    rows = churn_ref.Rows()
    for first, n in calls:
        hi = min(first + n, n_warm)
        rows.fold(ref, khash[first:hi], run["scores"][first:hi])
    path.release()
    # the table's own counters over the warm-up
    c = table.metrics.struct_snapshot()["counters"]
    admitted, evicted, overflow = (
        int(sum(c[n] - path.counters_after_fill.get(n, 0) for n in names))
        for names in (ADMITTED, ADMITTED[1:], ("state_overflow",)))
    never_seen = int(np.unique(ranks[ranks >= loaded]).size)
    uniq, first_at = np.unique(ids, return_index=True)
    uhash = khash[first_at]
    _, inv, cnt = np.unique(uhash, return_inverse=True, return_counts=True)
    twin = cnt[inv] > 1
    shared, n_shared = set(uhash[twin].tolist()), int(twin.sum())
    shared_allowed = max(4, uniq.size // 1000)
    log(f"state: {len(calls)} routing calls, {uniq.size} distinct keys in "
        f"the warm-up stream ({n_shared} share a uint32 hash: left out of "
        f"the rows), {never_seen} keys the mix says nobody had seen "
        f"({latest.inserts_a_block(stream.mix, BLOCK)} a block of {BLOCK}); "
        f"the reference admitted {ref.admitted} ({ref.evicted} by eviction) "
        f"and overflowed {ref.overflowed}, the table admitted {admitted} "
        f"({evicted} by eviction) and overflowed {overflow}")
    if n_shared > shared_allowed:
        faults.append(f"{n_shared} keys share a hash")
    if (admitted, evicted) != (ref.admitted, ref.evicted):
        faults.append(
            f"the table admitted {admitted} keys over the warm-up, {evicted} "
            f"by eviction; the reference {ref.admitted} and {ref.evicted}")
    if admitted < never_seen:
        faults.append(f"{admitted} admissions for {never_seen} new keys")
    if overflow or ref.overflowed:
        faults.append(f"state_overflow moved by {overflow} over the warm-up "
                      f"(the reference: {ref.overflowed})")
    # the table's own routing, as a lookup, of every key the reference
    # still has: all are resident, so nothing is admitted and only LRU
    # stamps move
    hashes = np.array(sorted(
        h for h, s in rows.slot.items() if s != ref.scratch), np.uint32)
    want = np.array([rows.slot[h] for h in hashes.tolist()], np.int64)
    now, reset, _, _ = table.assign_slots(
        hashes, np.zeros(hashes.size, np.int64))
    c2 = table.metrics.struct_snapshot()["counters"]
    not_resident = int(sum(c2[n] - c[n] for n in ADMITTED)) + int(
        reset.sum()) + int((now == table.scratch).sum())
    if not_resident:
        faults.append(f"{not_resident} keys of the warm-up stream that the "
                      "reference still has are no longer resident")
    off_record = int((now != want).sum())
    if off_record:
        faults.append(f"{off_record} keys live in another slot than the "
                      "reference's rule gives them")
    # the rows on the device, where the reference has each key
    own = np.array([h not in shared for h in hashes.tolist()], bool)
    hashes, want = hashes[own], want[own]
    got = np.asarray(table.values[jnp.asarray(want)])
    want_n, want_s = rows.expected(
        hashes.tolist(), prefill.initial_rows(run["seed"], want))
    bad_n = got[:, COL_COUNT] != want_n
    # float32 running sums: one rounding per record folded
    tol = 1e-6 * want_n * np.maximum(np.abs(want_s), 1.0) + 1e-4
    miss = np.abs(got[:, COL_SUM] - want_s) / tol
    zeroed = sum(h in rows.admitted for h in hashes.tolist())
    log(f"state: {int(bad_n.sum())} counts and {int((miss > 1).sum())} score "
        f"sums differ from the reference over {hashes.size} keys, {zeroed} "
        f"of them admitted on a zeroed row (largest count "
        f"{int(want_n.max(initial=0))})")
    if bad_n.any() or (miss > 1).any():
        faults.append("table rows differ from the reference's")
    return faults, [
        ("state_keys_sharing_hash", n_shared, shared_allowed),
        ("state_admitted_in_warmup", admitted, ref.admitted,
         admitted == ref.admitted),
        ("state_evicted_in_warmup", evicted, ref.evicted,
         evicted == ref.evicted),
        ("state_admissions_under_new_keys", max(0, never_seen - admitted), 0),
        ("state_overflow_in_warmup", overflow, 0),
        ("state_keys_not_resident", not_resident, 0),
        ("state_rows_off_the_record", off_record, 0),
        ("state_counts_differing", int(bad_n.sum()), 0),
        ("state_sum_miss_over_tol", float(miss.max(initial=0.0)), 1.0),
    ]
