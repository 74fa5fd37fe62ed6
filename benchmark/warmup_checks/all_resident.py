"""Warm-up check ``all_resident``: the state a deployment whose whole
key domain is resident when the run starts must hold after the warm-up
stream. Each key's table row (count, score sum) against the row its
slot started with (``lib.prefill.initial_rows``) plus a dict tally of
what the sink received; nothing may have been inserted or reset. Left
out are keys of the stream that share the table's uint32 hash with
another key of the stream.

A warm-up check is one file ``warmup_checks/<name>.py``, named by a
configuration's ``warmup_check``, with ``check(run) -> (faults,
compared)``: ``faults`` a list of sentences (empty: the state is
right), ``compared`` a list of ``(name, number, limit)``, each number
that decided it beside the most it may be. ``run`` is a plain dict: ``seed``,
``stream`` (``lib.stream.Stream``), ``scores`` (float64, the delivered
score of every warm-up offset), ``n_warm``, ``path`` (the path builder:
``table``, ``metrics``), ``cfg``, ``log``. A deployment with inserts,
expiry or a sharded table brings its own file.
"""

from __future__ import annotations

import numpy as np

from lib import prefill
from reference import state_ref


def check(run: dict):
    import jax.numpy as jnp
    from flink_jpmml_tpu.runtime.state import COL_COUNT, COL_SUM

    log, table, n_warm = run["log"], run["path"].table, run["n_warm"]
    faults = []
    tally = state_ref.KeyTally()
    tally.fold(run["stream"].ids(0, n_warm), run["scores"])
    uniq = np.fromiter(tally.count.keys(), np.int64, len(tally.count))
    khash = table.hash_keys(uniq)
    _, inv, cnt = np.unique(khash, return_inverse=True, return_counts=True)
    shared = cnt[inv] > 1
    shared_allowed = max(4, uniq.size // 1000)
    log(f"state: {uniq.size} distinct keys in the warm-up stream, "
        f"{int(shared.sum())} share a uint32 hash: left out")
    if shared.sum() > shared_allowed:
        faults.append(f"{int(shared.sum())} keys share a hash")
    uniq, khash = uniq[~shared], khash[~shared]
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
    rows = np.asarray(table.values[jnp.asarray(slots)])
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
        f"sums differ from the tally over {uniq.size} keys (largest count "
        f"{int(want_n.max())})")
    if bad_n.any() or (miss > 1).any():
        faults.append("table rows differ from the reference tally")
    return faults, [
        ("state_keys_sharing_hash", int(shared.sum()), shared_allowed),
        ("state_keys_not_resident", not_resident, 0),
        ("state_counts_differing", int(bad_n.sum()), 0),
        ("state_sum_miss_over_tol", float(miss.max()), 1.0),
    ]
