"""Kernels: the whole step's share of ONE chip's peak, on the chip that
sets the pace: the least time a chip could take for what the algorithm
needs for the hot chip's records of a dispatch (lib/roofline.py on
lib/peaks.py; the dispatch's records times ``hot_chip_share.mesh``),
over the mean time of the scoring program on the chip that spent the
most time in it. A chip cannot fold its own records faster than that
least time, so the share cannot pass 100%. The two one-chip roofline
readers divide a whole dispatch's needs by one chip's time and would
read four times too high here."""
from lib import byname, roofline
from lib.readers import PROGRAM_PREFIX, records_per_dispatch


def read(ctx):
    hot = byname.load("layer_metrics", "hot_chip_share.mesh").read(ctx)
    spread = byname.load("layer_metrics", "chip_busy_spread.mesh")
    runs, rpd = spread.by_chip(ctx.get("trace")), records_per_dispatch(ctx)
    if hot is None or not runs or not rpd:
        return None
    run = max(runs, key=spread.program_seconds)
    times = [d for name, _, d in run if name.startswith(PROGRAM_PREFIX)]
    if not times:
        return None
    mean = sum(times) / len(times)
    least, roof = roofline.least_seconds(
        ctx["cfg"], rpd * hot / 100.0, ctx["peaks"])
    print(f"# scoring_program_roofline.mesh: binding roof {roof}, least "
          f"{least * 1e6:.2f} us for the hot chip's {rpd * hot / 100.0:.0f} "
          f"records of a dispatch, {mean * 1e3:.2f} ms on that chip",
          flush=True)
    return 100.0 * least / mean
