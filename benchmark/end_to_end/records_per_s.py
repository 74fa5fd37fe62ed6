"""Records delivered to the sink inside the window, each offset once,
over the window's seconds. The sink sees whole dispatches, so the
records of a dispatch that straddles an edge of the window are shared
by time (``lib.readers.records_between``)."""
from lib.readers import records_between


def read(ctx):
    w0, w1 = ctx["window"]
    n = records_between(ctx, w0, w1)
    return n / (w1 - w0) if n > 0 else None
