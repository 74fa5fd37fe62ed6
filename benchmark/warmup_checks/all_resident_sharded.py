"""Warm-up check ``all_resident_sharded``: what ``all_resident`` checks
(each key's count and score sum against the row its slot started with
plus a dict tally of what the sink received; nothing inserted or
reset), for a table that lies over several chips — and that it lies
there by the stated rule. What a sharded deployment brings beside its
configuration and its path builder is this file:

- rows are read where the REFERENCE says they are
  (``reference/shard_ref.owner``: chip ``slot // ⌈capacity/D⌉``, row
  ``slot % ⌈capacity/D⌉``), out of that chip's piece of the buffer, so
  a row folded on a chip that does not own it reads as the untouched
  first row and fails the counts; the table's own public map
  (``KeyedStateTable.locate``) is held to the same rule;
- the program's per-chip counters (``mesh_chip_records{chip=…}``:
  records folded on a chip) equal the reference's tally of the warm-up
  stream's records by owner, exactly. Nothing else has been folded by
  then: the fill and the null dispatches of ``warm_shapes`` go past
  the pipeline.
"""

from __future__ import annotations

import numpy as np

from lib import prefill
from reference import shard_ref, state_ref


def check(run: dict):
    from flink_jpmml_tpu.runtime.state import COL_COUNT, COL_SUM

    log, table, n_warm = run["log"], run["path"].table, run["n_warm"]
    D = int(run["cfg"]["chips"])
    faults = []
    ids = run["stream"].ids(0, n_warm)
    tally = state_ref.KeyTally()
    tally.fold(ids, run["scores"])
    uniq = np.fromiter(tally.count.keys(), np.int64, len(tally.count))
    uniq.sort()
    khash = table.hash_keys(uniq)
    _, inv, cnt = np.unique(khash, return_inverse=True, return_counts=True)
    shared = cnt[inv] > 1
    shared_allowed = max(4, uniq.size // 1000)
    log(f"state: {uniq.size} distinct keys in the warm-up stream, "
        f"{int(shared.sum())} share a uint32 hash: left out of the sums")
    if shared.sum() > shared_allowed:
        faults.append(f"{int(shared.sum())} keys share a hash")
    c0 = table.metrics.struct_snapshot()["counters"]
    # the table's own routing, as a lookup: every key is resident, so
    # nothing is inserted (checked) and only LRU stamps move
    slots, reset, _, _ = table.assign_slots(
        khash, np.zeros(uniq.size, np.int64)
    )
    c1 = table.metrics.struct_snapshot()["counters"]
    not_resident = int(c1["state_inserts"] - c0["state_inserts"]) + int(
        reset.sum()) + int((slots == table.scratch).sum())
    if not_resident:
        faults.append("warm-up keys were not all resident in the table")
    # where the reference says each key's row lies, and the table's map
    chip, row = shard_ref.owner(slots, table.capacity, D)
    got_chip, got_row = table.locate(slots)
    off_rule = int((got_chip != chip).sum() + (got_row != row).sum())
    if off_rule or table.n_shards != D:
        faults.append("the table's slot → (chip, row) map is not the "
                      "stated rule")
    # per-chip records folded against the tally by owner (shared
    # hashes share a row, so an owner: every record counts)
    want_by_chip = shard_ref.tally_by_owner(
        slots[np.searchsorted(uniq, ids)], table.capacity, D)
    got_by_chip = [
        int(c1.get(f'mesh_chip_records{{chip="{d.id}"}}', -1))
        for d in table.mesh.devices[:, 0]
    ]
    log(f"state: records folded per chip {got_by_chip}, the reference "
        f"tallies {want_by_chip}")
    chip_miss = int(sum(abs(a - b) for a, b in zip(
        got_by_chip, want_by_chip)))
    if chip_miss:
        faults.append("per-chip records differ from the tally by owner")
    keep = ~shared
    uniq, slots, chip, row = uniq[keep], slots[keep], chip[keep], row[keep]
    rows = table.read_local(chip, row)  # each out of its chip's piece
    first = np.asarray(prefill.initial_rows(run["seed"], slots), np.float64)
    want_n = first[:, COL_COUNT] + np.array(
        [tally.count[k] for k in uniq.tolist()], np.float64)
    want_s = first[:, COL_SUM] + np.array(
        [tally.total[k] for k in uniq.tolist()], np.float64)
    bad_n = rows[:, COL_COUNT] != want_n
    # float32 running sums: one rounding per record folded
    tol = 1e-6 * want_n * np.maximum(np.abs(want_s), 1.0) + 1e-4
    miss = np.abs(rows[:, COL_SUM] - want_s) / tol
    log(f"state: {int(bad_n.sum())} counts and {int((miss > 1).sum())} score "
        f"sums differ from the tally over {uniq.size} keys on "
        f"{np.unique(chip).size} chips (largest count {int(want_n.max())})")
    if bad_n.any() or (miss > 1).any():
        faults.append("table rows differ from the reference tally")
    return faults, [
        ("state_keys_sharing_hash", int(shared.sum()), shared_allowed),
        ("state_keys_not_resident", not_resident, 0),
        ("state_rows_off_the_stated_rule", off_rule, 0),
        ("state_chip_records_off_tally", chip_miss, 0),
        ("state_counts_differing", int(bad_n.sum()), 0),
        ("state_sum_miss_over_tol", float(miss.max()), 1.0),
    ]
