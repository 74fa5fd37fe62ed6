"""State: distinct keys of a dispatch whose home slot another key holds
(``state_collisions``), per thousand records: how far the host's slot
routing has to probe in the filled table."""
from lib.readers import counter_delta


def read(ctx):
    hit, recs = counter_delta(ctx, "state_collisions"), counter_delta(ctx, "state_records")
    return 1000.0 * hit / recs if hit is not None and recs else None
