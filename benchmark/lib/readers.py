"""What a metric reader gets, and the few sums most of them need.

A reader is a file ``benchmark/end_to_end/<name>.py`` or
``benchmark/layer_metrics/<name>.py`` with one function,
``read(ctx) -> float | None``; ``None`` leaves the metric out of the
line. ``ctx`` is a plain dict:

- ``window``: ``(start, end)`` of the measured window on the host's
  monotonic clock, and ``window_s``, its length;
- ``deliveries``: the sink's account of the whole run, one
  ``(t_done, n_records)`` per delivered dispatch, in delivery order,
  up to the first delivery after the window's end;
- ``batches``: those of ``deliveries`` that fall inside the window;
- ``snap0`` / ``snap1``: the program's ``MetricsRegistry.struct_snapshot()``
  at the window's start and end (counters, gauges, stage histograms);
- ``gen``: the load generator's own account (least backlog);
- ``trace``: ``lib.xtrace.reduce_trace`` of the traced stretch (host
  spans: the program's, ``SPAN_PREFIX``), or ``None`` in an untraced
  run;
- ``setup_s``; ``cfg`` / ``traffic``: the cell's two files;
- ``peaks``: the chip's row of ``lib.peaks``.
"""

from __future__ import annotations

from typing import Optional, Tuple

PROGRAM_PREFIX = "jit_state_fn"  # the state-armed scoring program's module
SPAN_PREFIX = "fjt."  # the program's host spans and named scopes


def counter_delta(ctx: dict, name: str) -> Optional[float]:
    c0 = ctx["snap0"]["counters"].get(name)
    c1 = ctx["snap1"]["counters"].get(name)
    if c1 is None:
        return None
    return float(c1) - float(c0 or 0.0)


def stage_delta(ctx: dict, stage: str) -> Optional[Tuple[float, int]]:
    """Self-time and observation count the stage ledger (obs/attr.py)
    booked for ``stage`` inside the window."""
    key = f'stage_seconds{{stage="{stage}"}}'
    h1 = ctx["snap1"]["histograms"].get(key)
    if h1 is None:
        return None
    h0 = ctx["snap0"]["histograms"].get(key) or {"sum": 0.0, "n": 0}
    return float(h1["sum"]) - float(h0["sum"]), int(h1["n"]) - int(h0["n"])


def stage_sums(ctx: dict) -> dict:
    """``{stage: (self-time in seconds, observations)}`` for every stage
    the ledger knows at the window's end, over the window."""
    head = 'stage_seconds{stage="'
    return {k[len(head):-2]: stage_delta(ctx, k[len(head):-2])
            for k in ctx["snap1"]["histograms"] if k.startswith(head)}


def window_records(ctx: dict) -> int:
    return int(sum(n for _, n in ctx["batches"]))


def records_between(ctx: dict, t0: float, t1: float) -> float:
    """Records delivered between two instants, a dispatch's records
    spread evenly over the time since the delivery before it: the sink
    sees whole dispatches (65,536 records, one every 0.7 s on the first
    cell), and a count of whole deliveries would jump by one dispatch
    with the phase of the window's edges. A stall anywhere, at an edge
    too, stretches the dispatch it delays and lowers the count. Time
    after the last delivery is credited nothing."""
    import numpy as np

    t = np.array([d[0] for d in ctx["deliveries"]], np.float64)
    done = np.cumsum([d[1] for d in ctx["deliveries"]], dtype=np.float64)
    at0, at1 = np.interp([t0, t1], t, done)
    return float(at1 - at0)


def us_per_krec(ctx: dict, *stages: str) -> Optional[float]:
    recs = window_records(ctx)
    parts = [stage_delta(ctx, s) for s in stages]
    if not recs or any(p is None for p in parts):
        return None
    return sum(p[0] for p in parts) * 1e6 / (recs / 1000.0)


def program_events(ctx: dict):
    """Durations (s) of the scoring program's executions that lie wholly
    inside the traced stretch."""
    tr = ctx.get("trace")
    if not tr:
        return []
    return [d for name, _, d in tr.get("modules", [])
            if name.startswith(PROGRAM_PREFIX)]


def program_mean_s(ctx: dict) -> Optional[float]:
    ev = program_events(ctx)
    return sum(ev) / len(ev) if ev else None


def program_mean_ms(ctx: dict) -> Optional[float]:
    mean = program_mean_s(ctx)
    return None if mean is None else 1e3 * mean


def scope_ms_per_dispatch(ctx: dict, *scopes: str) -> Optional[float]:
    """Device self time under the named scopes (``jax.named_scope``)
    per execution of the scoring program, over the executions that lie
    wholly inside the traced stretch. A program that names none of them
    reports nothing."""
    tr = ctx.get("trace") or {}
    n, secs, seen = 0, 0.0, False
    for name, rec in tr.get("scopes", {}).items():
        if name.startswith(PROGRAM_PREFIX):
            n += rec["n"]
            seen = seen or any(s in rec["seconds"] for s in scopes)
            secs += sum(rec["seconds"].get(s, 0.0) for s in scopes)
    return 1e3 * secs / n if n and seen else None


def records_per_dispatch(ctx: dict) -> Optional[float]:
    recs, n = counter_delta(ctx, "records_out"), counter_delta(ctx, "batches")
    if not recs or not n:
        return None
    return recs / n
