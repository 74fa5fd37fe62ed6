"""State: share of the window's records whose key was already resident
(``state_hits`` over ``state_records``)."""
from lib.readers import counter_delta


def read(ctx):
    hits, recs = counter_delta(ctx, "state_hits"), counter_delta(ctx, "state_records")
    return 100.0 * hits / recs if hits is not None and recs else None
