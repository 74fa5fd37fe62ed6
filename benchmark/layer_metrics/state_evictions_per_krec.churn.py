"""State: keys whose row the table took for another key
(``state_evictions``: the least recently touched slot of a full probe
window) per thousand records of the window."""
from lib.readers import counter_delta


def read(ctx):
    ev, recs = counter_delta(ctx, "state_evictions"), counter_delta(ctx, "state_records")
    return 1000.0 * ev / recs if ev is not None and recs else None
