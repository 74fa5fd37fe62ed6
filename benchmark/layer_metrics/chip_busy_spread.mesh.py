"""Device: the busiest chip's busy share of the traced stretch less
the idlest's, in points of a per cent. Busy is the time a chip spent
inside whole executions of any program (its ``XLA Modules`` events):
the reducer gives the ops' busy time only as the mean over the chips.
0 where the chips are loaded alike; under skewed keys the hot chip
works while the others wait for the next dispatch."""
from lib.readers import PROGRAM_PREFIX


def by_chip(trace):
    """The reducer's ``modules`` chip by chip. It appends one device
    plane after another, each in order of start time, so a start that
    lies before its predecessor's opens the next plane → a list, one
    entry a chip, of ``[(name, start_s, seconds), ...]``; None where
    that does not come out as one run a traced device."""
    runs = []
    for ev in (trace or {}).get("modules", []):
        if not runs or ev[1] < runs[-1][-1][1]:
            runs.append([])
        runs[-1].append(ev)
    return runs if runs and len(runs) == trace.get("devices") else None


def program_seconds(run):
    return sum(d for name, _, d in run if name.startswith(PROGRAM_PREFIX))


def read(ctx):
    tr = ctx.get("trace")
    runs = by_chip(tr)
    if not runs or len(runs) < 2 or not tr.get("window_s"):
        return None
    busy = [sum(d for _, _, d in run) for run in runs]
    return 100.0 * (max(busy) - min(busy)) / tr["window_s"]
