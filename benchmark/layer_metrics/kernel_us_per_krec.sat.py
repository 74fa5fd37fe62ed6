"""Kernels: device time of the state-armed scoring program (the Pallas
forest kernel and the XLA state fold, one module in the trace) per
thousand records: the mean duration of its executions in the traced
stretch over the window's records per dispatch."""
from lib.readers import program_mean_s, records_per_dispatch


def read(ctx):
    mean, rpd = program_mean_s(ctx), records_per_dispatch(ctx)
    if mean is None or not rpd:
        return None
    return mean * 1e6 / (rpd / 1000.0)
