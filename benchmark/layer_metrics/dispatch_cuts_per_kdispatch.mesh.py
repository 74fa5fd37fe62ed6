"""Pipeline: of a thousand dispatches of the window, how many were cut
short because one chip's bucket filled (``mesh_dispatch_cuts`` over
``batches``): the rest of what was drained led the next dispatch."""
from lib.readers import counter_delta


def read(ctx):
    cuts = counter_delta(ctx, "mesh_dispatch_cuts")
    n = counter_delta(ctx, "batches")
    if cuts is None or not n:
        return None
    return 1000.0 * cuts / n
