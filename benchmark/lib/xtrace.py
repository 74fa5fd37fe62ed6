"""Reduction of a JAX profiler trace (``*.xplane.pb``) to device
numbers, with ``jax.profiler.ProfileData`` and, for the one thing it
does not hand out (the stats of an op's *event metadata*, where the
``jax.named_scope`` an op was traced under arrives as ``tf_op``), a
reader of the protobuf wire format that looks at nothing else.

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
- an idle gap is a maximal stretch of the window with no op running;
- time by named scope is the self time of the ops of each program
  execution that lies wholly inside the window, booked under every
  component of the op's ``tf_op`` path but the last (the primitive):
  ``jit(state_fn)/fjt.fold.scatter/scatter-add:`` counts under
  ``jit(state_fn)`` and ``fjt.fold.scatter``; an op with no ``tf_op``
  counts under ``NO_SCOPE``. Per program, so a reader divides by that
  program's executions.
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
SCOPE_STAT = "tf_op"
NO_SCOPE = "(no scope)"


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


def _varint(buf, pos: int):
    out = shift = 0
    while True:
        b = buf[pos]
        pos += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, pos
        shift += 7


def _fields(buf):
    """(field number, wire type, value) of one protobuf message; a
    length-delimited value is a memoryview, never a copy."""
    pos, end = 0, len(buf)
    while pos < end:
        key, pos = _varint(buf, pos)
        wt = key & 7
        if wt == 0:
            val, pos = _varint(buf, pos)
        elif wt == 2:
            n, pos = _varint(buf, pos)
            val, pos = buf[pos:pos + n], pos + n
        elif wt == 1:
            val, pos = buf[pos:pos + 8], pos + 8
        elif wt == 5:
            val, pos = buf[pos:pos + 4], pos + 4
        else:
            raise ValueError(f"xplane: wire type {wt} at byte {pos}")
        yield key >> 3, wt, val


def _map_entry(buf):
    key = val = None
    for f, _, v in _fields(buf):
        if f == 1:
            key = v
        elif f == 2:
            val = v
    return key, val


def event_scopes(path: str, stat: str = SCOPE_STAT) -> Dict[str, Dict[str, str]]:
    """→ ``{device plane: {event name: the string stat ``stat`` of that
    event's metadata}}``. XSpace.planes = 1; XPlane: name = 2, lines =
    3 (skipped whole), event_metadata = 4 and stat_metadata = 5 (maps:
    key 1, value 2); XEventMetadata: name = 2, stats = 5;
    XStatMetadata: name = 2; XStat: metadata_id = 1, str_value = 5,
    ref_value = 7 (the id of a stat metadata whose name is the string)
    (tsl/profiler/protobuf/xplane.proto)."""
    with open(path, "rb") as fh:
        space = memoryview(fh.read())
    out: Dict[str, Dict[str, str]] = {}
    for f, _, plane in _fields(space):
        if f != 1:
            continue
        name, events, stats = "", [], {}
        for pf, _, v in _fields(plane):
            if pf == 2:
                name = bytes(v).decode("utf-8", "replace")
            elif pf == 4:
                events.append(_map_entry(v)[1])
            elif pf == 5:
                k, md = _map_entry(v)
                stats[k] = next((bytes(x).decode("utf-8", "replace")
                                 for mf, _, x in _fields(md) if mf == 2), "")
        if not name.startswith(DEVICE_PLANE_PREFIX):
            continue
        scopes = out.setdefault(name, {})
        for md in events:
            ev_name, found = "", None
            for mf, _, v in _fields(md):
                if mf == 2:
                    ev_name = bytes(v).decode("utf-8", "replace")
                elif mf == 5:
                    st = {sf: sv for sf, _, sv in _fields(v)}
                    if stats.get(st.get(1)) == stat:
                        found = (bytes(st[5]).decode("utf-8", "replace")
                                 if 5 in st else stats.get(st.get(7)))
            if found:
                scopes[ev_name] = found
    return out


def _scope_path(tf_op: Optional[str]) -> List[str]:
    parts = [p for p in (tf_op or "").split("/") if p]
    return parts[:-1] or [NO_SCOPE]


def reduce_trace(path: str, window_name: Optional[str] = None,
                 span_prefix: str = "bench.") -> dict:
    """→ ``{"devices", "window_s", "busy_s", "idle_share", "device_ops",
    "modules", "idle_gaps", "host_spans", "scopes"}``; times in seconds.
    ``scopes``: ``{program name: {"n": executions wholly inside the
    window, "seconds": {scope: self time of their ops}}}``, summed over
    the device planes.

    ``window_name``: a host annotation whose extent is the window, cut
    at the last device event; by default the window runs from the first
    to the last device event.
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
    # The devices' part of a trace stops before the host annotation
    # that names the window does (2-240 us on the chip, PERF.md §3):
    # what ran after that is not known, so the window ends where the
    # devices' record ends, not in an idle gap nobody saw.
    w1 = min(w1, max((v["ops"][0] + v["ops"][1]).max() for v in dev.values()))
    busy, by_op, gaps, modules = [], {}, [], []
    tf_ops = event_scopes(path)
    scopes: Dict[str, dict] = {}
    for name, rec in sorted(dev.items()):
        s, d, names = rec["ops"]
        e = s + d
        self_t = _self_times(s, e)
        whole = _whole_executions(rec["modules"], (w0, w1))
        _book_scopes(scopes, whole, s, e, self_t, names,
                     tf_ops.get(name, {}))
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
        modules += [(m, a, b - a) for a, b, m in whole]
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
        "scopes": scopes,
    }


def _whole_executions(modules, window):
    """Program executions that lie wholly inside the window, as sorted
    ``(start, end, name)``. The device's part of a trace stops up to
    some milliseconds before the host annotation that names the window
    does (PERF.md §5), and the execution it cuts is written as an event
    that ends there, inside the window: the last execution of a device
    is taken for that one and left out, whole or not."""
    w0, w1 = window
    runs = sorted((ms, ms + md, m) for m, ms, md in modules)[:-1]
    return [r for r in runs if r[0] >= w0 and r[1] <= w1]


def _book_scopes(scopes, whole, s, e, self_t, names, tf_op_of):
    """Adds one device plane's share to ``scopes`` (see
    ``reduce_trace``): an op belongs to the execution whose interval
    holds it."""
    if not whole:
        return
    starts = np.array([a for a, _, _ in whole], np.float64)
    ends = np.array([b for _, b, _ in whole], np.float64)
    at = np.searchsorted(starts, s, side="right") - 1
    inside = np.flatnonzero((at >= 0) & (e <= ends[np.maximum(at, 0)]))
    for _, _, m in whole:
        scopes.setdefault(m, {"n": 0, "seconds": {}})["n"] += 1
    code_of: Dict[str, int] = {}
    codes = np.fromiter(
        (code_of.setdefault(names[i], len(code_of)) for i in inside.tolist()),
        np.int64, inside.size)
    programs = sorted({m for _, _, m in whole})
    prog = np.array([programs.index(m) for _, _, m in whole])[at[inside]]
    sums = np.bincount(prog * len(code_of) + codes, weights=self_t[inside],
                       minlength=len(programs) * len(code_of))
    for nm, c in code_of.items():
        for k, m in enumerate(programs):
            t = float(sums[k * len(code_of) + c]) / 1e9
            if t:
                secs = scopes[m]["seconds"]
                for part in _scope_path(tf_op_of.get(nm)):
                    secs[part] = secs.get(part, 0.0) + t


def attribute_gaps(red: dict, top: int = 10, rest: str = "pipeline"):
    """Longest idle gaps, each named for the innermost host span over
    it: of the spans that cover half of the gap or more, the shortest
    (``rest`` where none does), summed by name → ``[[name, seconds],
    ...]``, at most ``top`` entries. Spans of several threads overlap;
    the shortest one that still covers the gap is the stage whose end
    the device was waiting for, not a long wait on another thread that
    happens to span it."""
    spans = red.get("host_spans", [])
    out: Dict[str, float] = {}
    for a, b in red.get("idle_gaps", [])[:200]:
        best, length = rest, None
        for nm, s, e in spans:
            if min(b, e) - max(a, s) >= 0.5 * (b - a) and (
                    length is None or e - s < length):
                best, length = nm, e - s
        out[best] = out.get(best, 0.0) + float(b - a)
    return [[k, v] for k, v in sorted(out.items(), key=lambda kv: -kv[1])][:top]
