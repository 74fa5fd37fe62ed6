"""Kernels: the least time the chip could take for what the algorithm
needs in one dispatch (lib/roofline.py, lib/peaks.py) over the traced
time of the scoring program. The binding roof goes on an earlier line."""
from lib import roofline
from lib.readers import program_mean_s, records_per_dispatch


def read(ctx):
    mean, rpd = program_mean_s(ctx), records_per_dispatch(ctx)
    if mean is None or not rpd:
        return None
    least, roof = roofline.least_seconds(ctx["cfg"], rpd, ctx["peaks"])
    print(f"# scoring_program_roofline: binding roof {roof}, least "
          f"{least * 1e6:.2f} us for {rpd:.0f} records a dispatch",
          flush=True)
    return 100.0 * least / mean
