"""Pipeline: share of its time the score thread stood blocked on the
device: the ledger's ``queue_wait`` (a full in-flight window) plus
``readback`` (every other wait on a dispatch), over the time between
the two snapshots the deltas come from (their own ``ts``): the harness
takes the second when its main loop gets out, which in a traced run is
seconds after the window's end, so the window's length would read
shares above 100%."""
from lib.readers import stage_delta


def read(ctx):
    parts = [stage_delta(ctx, s) for s in ("queue_wait", "readback")]
    between = float(ctx["snap1"]["ts"]) - float(ctx["snap0"]["ts"])
    if all(p is None for p in parts) or between <= 0:
        return None
    return 100.0 * sum(p[0] for p in parts if p) / between
