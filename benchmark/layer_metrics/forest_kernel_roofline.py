"""Kernels: the least time the chip could take for the forest alone
on one dispatch (lib/roofline.py: the comparisons and adds of the walk,
the forest once, the rank-wire row in and the score out) over the
traced self time under the scope ``fjt.forest``. The binding roof goes
on an earlier line."""
from lib import roofline
from lib.readers import records_per_dispatch, scope_ms_per_dispatch


def read(ctx):
    ms, rpd = scope_ms_per_dispatch(ctx, "fjt.forest"), records_per_dispatch(ctx)
    if not ms or not rpd:
        return None
    least, roof = roofline.forest_least_seconds(ctx["cfg"], rpd, ctx["peaks"])
    print(f"# forest_kernel_roofline: binding roof {roof}, least "
          f"{least * 1e6:.2f} us for {rpd:.0f} records a dispatch",
          flush=True)
    return 100.0 * least / (ms / 1e3)
