"""State: share of the window's records that ``route``'s native pass
left to the claim rounds (``state_route_pending`` over
``state_records``): every record of a key the table has not got, one a
record and not one a key. A program without the counter reports
nothing."""
from lib.readers import counter_delta


def read(ctx):
    left, recs = counter_delta(ctx, "state_route_pending"), counter_delta(ctx, "state_records")
    return 100.0 * left / recs if left is not None and recs else None
