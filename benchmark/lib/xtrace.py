"""Reduction of a JAX profiler trace (``*.xplane.pb``) to device
numbers, with nothing but ``jax.profiler.ProfileData``.

What the first chip trace of this repo showed (PERF.md §6, PR 23):
device planes are named ``/device:TPU:<n>``; their ``XLA Ops`` line
holds one event per executed HLO op, nested (a ``%while`` spans the ops
of its body); their ``XLA Modules`` line holds one event per executed
program, named ``jit_<function>(<fingerprint>)``. Host threads are the
lines of ``/host:CPU``; a ``jax.profiler.TraceAnnotation`` shows up
there under its own name. All planes share one clock, in nanoseconds.

- busy time is the union of the ``XLA Ops`` intervals, clipped to the
  window, averaged over the device planes;
- time by op is *self* time: an op's duration minus the ops nested in
  it, so the list adds up to the busy time;
- an idle gap is a maximal stretch of the window with no op running.
"""

from __future__ import annotations

import glob
import os
from typing import Dict, List, Optional, Tuple

import numpy as np

DEVICE_PLANE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"


def find_xplane(trace_dir: str) -> Optional[str]:
    hits = sorted(glob.glob(
        os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")
    ))
    return hits[-1] if hits else None


def _op_label(name: str) -> str:
    """``%fusion.3 = f32[...] fusion(...)`` → ``%fusion.3``."""
    return name.split(" = ", 1)[0][:120]


def _union(starts: np.ndarray, ends: np.ndarray):
    """Sorted, merged intervals of possibly nested/overlapping ones."""
    if starts.size == 0:
        return np.empty(0), np.empty(0)
    order = np.argsort(starts, kind="stable")
    s, e = starts[order], ends[order]
    run_end = np.maximum.accumulate(e)
    new = np.empty(s.size, bool)
    new[0] = True
    new[1:] = s[1:] > run_end[:-1]
    first = np.flatnonzero(new)
    last = np.append(first[1:] - 1, s.size - 1)
    return s[first], run_end[last]


def _self_times(starts, ends) -> np.ndarray:
    """Duration of each interval minus the intervals nested directly in
    it (events of one line nest properly: a stack sweep)."""
    order = np.lexsort((-ends, starts))
    self_t = (ends - starts).astype(np.float64)
    stack: List[int] = []
    for i in order.tolist():
        while stack and ends[stack[-1]] <= starts[i]:
            stack.pop()
        if stack:
            self_t[stack[-1]] -= ends[i] - starts[i]
        stack.append(i)
    return self_t


def reduce_trace(path: str, window_name: Optional[str] = None,
                 span_prefix: str = "bench.") -> dict:
    """→ ``{"devices", "window_s", "busy_s", "idle_share", "device_ops",
    "modules", "idle_gaps", "host_spans"}``; times in seconds.

    ``window_name``: a host annotation whose extent is the window; by
    default the window runs from the first to the last device event.
    ``host_spans`` are the host annotations whose name starts with
    ``span_prefix``, as ``(name, start_s, end_s)`` on the trace clock.
    """
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    dev: Dict[str, dict] = {}
    host_spans: List[Tuple[str, float, float]] = []
    window = None
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PLANE_PREFIX):
            rec = dev.setdefault(plane.name, {"ops": None, "modules": []})
            for line in plane.lines:
                if line.name == OPS_LINE:
                    s, d, names = [], [], []
                    for e in line.events:
                        s.append(e.start_ns)
                        d.append(e.duration_ns)
                        names.append(e.name)
                    rec["ops"] = (np.asarray(s, np.float64),
                                  np.asarray(d, np.float64), names)
                elif line.name == MODULES_LINE:
                    rec["modules"] = [
                        (e.name, e.start_ns, e.duration_ns)
                        for e in line.events
                    ]
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                for e in line.events:
                    if e.name == window_name:
                        window = (e.start_ns, e.start_ns + e.duration_ns)
                    elif e.name.startswith(span_prefix):
                        host_spans.append((
                            e.name, e.start_ns,
                            e.start_ns + e.duration_ns,
                        ))
    dev = {k: v for k, v in dev.items() if v["ops"] is not None
           and v["ops"][0].size}
    if not dev:
        return {"devices": 0}
    if window is None:
        window = (
            min(v["ops"][0].min() for v in dev.values()),
            max((v["ops"][0] + v["ops"][1]).max() for v in dev.values()),
        )
    w0, w1 = window
    busy, by_op, gaps, modules = [], {}, [], []
    for name, rec in sorted(dev.items()):
        s, d, names = rec["ops"]
        e = s + d
        self_t = _self_times(s, e)
        keep = (e > w0) & (s < w1)
        for i in np.flatnonzero(keep).tolist():
            lab = _op_label(names[i])
            by_op[lab] = by_op.get(lab, 0.0) + self_t[i]
        us, ue = _union(np.clip(s[keep], w0, w1), np.clip(e[keep], w0, w1))
        busy.append(float((ue - us).sum()))
        edges_s = np.concatenate([[w0], ue])
        edges_e = np.concatenate([us, [w1]])
        for a, b in zip(edges_s.tolist(), edges_e.tolist()):
            if b > a:
                gaps.append((a, b))
        modules += [
            (m, ms, md) for m, ms, md in rec["modules"]
            if ms >= w0 and ms + md <= w1
        ]
    n = len(dev)
    window_s = float(w1 - w0) / 1e9
    busy_s = float(sum(busy)) / n / 1e9
    gaps.sort(key=lambda g: g[0] - g[1])
    return {
        "devices": n,
        "window_s": window_s,
        "busy_s": busy_s,
        "idle_share": 1.0 - busy_s / window_s if window_s > 0 else None,
        "device_ops": sorted(
            ((k, float(v) / n / 1e9) for k, v in by_op.items()),
            key=lambda kv: -kv[1],
        ),
        "modules": [(m, ms / 1e9, md / 1e9) for m, ms, md in modules],
        "idle_gaps": [(a / 1e9, b / 1e9) for a, b in gaps],
        "host_spans": [(nm, a / 1e9, b / 1e9) for nm, a, b in host_spans],
    }


def attribute_gaps(red: dict, top: int = 10, rest: str = "pipeline"):
    """Longest idle gaps, each named for the benchmark's own host span
    that covers most of it (``rest`` where none does), summed by name →
    ``[[name, seconds], ...]``, at most ``top`` entries."""
    spans = red.get("host_spans", [])
    out: Dict[str, float] = {}
    for a, b in red.get("idle_gaps", [])[:200]:
        best, cover = rest, 0.0
        for nm, s, e in spans:
            c = min(b, e) - max(a, s)
            if c > cover and c >= 0.5 * (b - a):
                best, cover = nm, c
        out[best] = out.get(best, 0.0) + float(b - a)
    return [[k, v] for k, v in sorted(out.items(), key=lambda kv: -kv[1])][:top]
