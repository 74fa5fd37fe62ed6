"""Ingest: fetch + decode self-time (stage ledger, the prefetch sidecar
thread) per thousand records delivered."""
from lib.readers import us_per_krec


def read(ctx):
    return us_per_krec(ctx, "fetch", "decode")
