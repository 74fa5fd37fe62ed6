"""The chips' idle time split by what the program's host threads were
doing: the overlap of the traced stretch's idle gaps (``reduce_trace``'s
``idle_gaps``, every chip's, all of them) with the union of the host
spans of some names, less the union of others. Divided by chips ×
stretch, the base of the idle share, so a reading is in points of it.
Unlike ``xtrace.attribute_gaps`` nothing is named for one span: a gap
that half a dozen short spans cover is split among them by the time
each covers."""

from __future__ import annotations

from typing import Iterable, Optional

import numpy as np

# the score thread blocked on its ring: a program that books no such
# stage has not split its drain's wait from its copies, and reports
# nothing; a traced stretch in which the thread never waited holds no
# such span, and its chips were starved for none of their idle time
STARVED = ("fjt.drain_wait",)
STARVED_STAGE = 'stage_seconds{stage="drain_wait"}'


def union(intervals) -> np.ndarray:
    """Sorted, disjoint ``[[start, end], ...]`` covering ``intervals``."""
    iv = np.asarray(intervals, np.float64).reshape(-1, 2)
    out = []
    for s, e in sorted(iv.tolist()):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        elif e > s:
            out.append([s, e])
    return np.asarray(out, np.float64).reshape(-1, 2)


def _inside(u: np.ndarray, t: np.ndarray) -> np.ndarray:
    k = np.searchsorted(u[:, 0], t, side="right") - 1
    return (k >= 0) & (t < u[np.maximum(k, 0), 1])


def minus(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """The disjoint union ``a`` less the disjoint union ``b``."""
    if not len(a) or not len(b):
        return a
    cuts = np.unique(np.concatenate([a.ravel(), b.ravel()]))
    mid = 0.5 * (cuts[:-1] + cuts[1:])
    keep = _inside(a, mid) & ~_inside(b, mid)
    return union(np.stack([cuts[:-1][keep], cuts[1:][keep]], 1))


def covered(u: np.ndarray, gaps) -> float:
    """Seconds of the disjoint union ``u`` that lie inside ``gaps``
    (disjoint on each chip; the chips' gaps may overlap each other, and
    each counts on its own)."""
    g = np.asarray(gaps, np.float64).reshape(-1, 2)
    if not len(u) or not len(g):
        return 0.0
    cum = np.concatenate([[0.0], np.cumsum(u[:, 1] - u[:, 0])])

    def before(t):  # seconds of ``u`` up to the instant ``t``
        k = np.searchsorted(u[:, 0], t, side="right")
        over = np.maximum(u[np.maximum(k - 1, 0), 1] - t, 0.0)
        return cum[k] - np.where(k > 0, over, 0.0)

    return float(np.sum(before(g[:, 1]) - before(g[:, 0])))


def idle_points(ctx: dict, names: Iterable[str],
                less: Iterable[str] = ()) -> Optional[float]:
    """Of the chips' traced idle time, the share spent under a host span
    named in ``names`` and none named in ``less``, in % of chips ×
    stretch; None without a trace or where the program has no
    ``STARVED`` span, in its stage ledger or in the trace."""
    tr = ctx.get("trace") or {}
    if not tr.get("devices") or not tr.get("window_s"):
        return None
    spans = tr.get("host_spans", [])
    booked = STARVED_STAGE in (ctx.get("snap1") or {}).get("histograms", {})
    if not booked and not any(nm in STARVED for nm, _, _ in spans):
        return None
    names, less = set(names), set(less)
    u = minus(union([(s, e) for nm, s, e in spans if nm in names]),
              union([(s, e) for nm, s, e in spans if nm in less]))
    return 100.0 * covered(u, tr["idle_gaps"]) / (
        tr["devices"] * tr["window_s"])
