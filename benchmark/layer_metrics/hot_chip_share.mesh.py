"""State: the busiest chip's share of the records folded in the window
(``mesh_chip_records{chip=…}``: records folded ON a chip, each on the
chip that owns its key's row). 25 on four chips where the keys fall
evenly; the hot chip sets the device's pace."""
from lib.readers import counter_delta

PREFIX = "mesh_chip_records{"


def per_chip(ctx):
    """→ records folded in the window, a chip (None: no such counter)."""
    names = [n for n in ctx["snap1"]["counters"] if n.startswith(PREFIX)]
    return [counter_delta(ctx, n) for n in sorted(names)] or None


def read(ctx):
    per = per_chip(ctx)
    if not per or not sum(per):
        return None
    return 100.0 * max(per) / sum(per)
