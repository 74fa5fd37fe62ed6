"""State: keys the table admitted (``state_inserts`` into an empty slot
plus ``state_evictions`` out of a full window) per thousand records of
the window. The ``latest`` mix fixes its floor: 50 keys nobody has seen
in every thousand records; what is above it are keys the full table had
let go of."""
from lib.readers import counter_delta


def read(ctx):
    ins, ev = counter_delta(ctx, "state_inserts"), counter_delta(ctx, "state_evictions")
    recs = counter_delta(ctx, "state_records")
    return 1000.0 * (ins + ev) / recs if None not in (ins, ev) and recs else None
