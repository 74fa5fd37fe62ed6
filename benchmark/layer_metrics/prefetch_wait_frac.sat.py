"""Ingest: share of the window the ring-feed thread spent waiting on the
prefetch hand-off (stage ledger, prefetch_wait self-time)."""
from lib.readers import stage_delta


def read(ctx):
    d = stage_delta(ctx, "prefetch_wait")
    return None if d is None else 100.0 * d[0] / ctx["window_s"]
