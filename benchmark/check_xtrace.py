#!/usr/bin/env python3
"""Checks ``lib/xtrace.py`` against a small recorded trace.

    python3 benchmark/check_xtrace.py

``testdata/v5e_state_dispatch_cut.xplane.pb`` is a cut of the first
trace this repo took on the chip (my chip run, PR 23: three state-armed
dispatches of 16384 records on a 2**20-slot table, one TPU v5 lite): the
first two ``jit_state_fn`` executions with the first 3.3 ms of each
one's ops, the first 0.25 ms of each one's scatter loop (a ``%while``
with the ops of its body nested in it), and the two host annotations.
The reducer's numpy sweeps are held to a brute-force recomputation in
plain Python and to the numbers read off the trace by hand.

``testdata/v5e_scoped_stretch_cut.xplane.pb`` is a cut of a traced
stretch of the cell itself (my chip run, PR 25: ``run.py --trace 1``,
seed 2147483909, 200,000,000 slots, dispatches of 65,536): executions
5-8 of ``jit_state_fn`` in the stretch with every op outside a
``%while`` and the first 0.3 ms of each loop's body, the last one cut
3 ms in as the end of a trace cuts it; the event metadata of those ops
with their ``tf_op`` (the named scopes ``fjt.forest``,
``fjt.fold.gather``, ``fjt.fold.scatter``); and the program's ``fjt.``
spans of the three host threads over that time, with the two idle gaps
of 42.6 and 61.5 ms that follow a profiler sample. It pins what
``ProfileData`` does not hand out (the wire reader's scopes), the time
by scope of whole executions, and the names of the idle gaps.
"""

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

from lib import xtrace  # noqa: E402

TRACE = os.path.join(HERE, "testdata", "v5e_state_dispatch_cut.xplane.pb")
# read off the cut by hand (ns → s)
PINNED = {
    "devices": 1,
    "busy_s": 0.12355632,
    "window_s": 0.14659305,
    "top_op": "%while.4",
    "host_spans": 2,
}


SCOPED = os.path.join(HERE, "testdata", "v5e_scoped_stretch_cut.xplane.pb")
SCOPED_PINNED = {
    "program": "jit_state_fn(17445565215118956508)",
    "executions": 3,  # the fourth is cut by the trace's end
    "window_s": 0.925565432,
    "window_s_annotated": 1.939770483,
    "busy_s": 0.821450103,
    # seconds over the three executions; a loop's own time carries no
    # tf_op, and the cut keeps 0.3 ms of each loop's body
    "scopes": {
        "fjt.forest": 0.017137452, "fjt.fold.gather": 0.021108467,
        "fjt.fold.scatter": 0.03460544, xtrace.NO_SCOPE: 0.742451518,
    },
    "tf_ops": 35,
    "kernel": ("%closed_call.4 = ",
               "jit(state_fn)/fjt.forest/while/body/closed_call/pallas_call:"),
    "gaps": ("fjt.route", 0.104112509),
}


def check_scoped(close) -> None:
    red = xtrace.reduce_trace(SCOPED, span_prefix="fjt.")
    pin = SCOPED_PINNED
    close(red["window_s"], pin["window_s"], "scoped cut: window_s")
    close(red["busy_s"], pin["busy_s"], "scoped cut: busy_s")
    sc = red["scopes"].get(pin["program"])
    if not sc or sc["n"] != pin["executions"] or len(red["modules"]) != sc["n"]:
        sys.exit(f"check_xtrace: whole executions: {red['scopes']}")
    for scope, secs in pin["scopes"].items():
        close(sc["seconds"].get(scope, 0.0), secs, f"time under {scope}")
    # every op is booked once, under the program's own scope or under
    # none; an execution starts 0.3 us before its first op
    booked = sc["seconds"]["jit(state_fn)"] + sc["seconds"][xtrace.NO_SCOPE]
    if not 0 <= sum(d for _, _, d in red["modules"]) - booked < 2e-6:
        sys.exit(f"check_xtrace: {booked} s booked by scope")
    tf_ops = xtrace.event_scopes(SCOPED)["/device:TPU:0"]
    if len(tf_ops) != pin["tf_ops"]:
        sys.exit(f"check_xtrace: {len(tf_ops)} ops carry a tf_op")
    kernel = [v for k, v in tf_ops.items() if k.startswith(pin["kernel"][0])]
    if kernel != [pin["kernel"][1]]:
        sys.exit(f"check_xtrace: the kernel's scope: {kernel}")
    gaps = xtrace.attribute_gaps(red)
    if gaps[0][0] != pin["gaps"][0] or any(
            not k.startswith("fjt.") for k, _ in gaps):
        sys.exit(f"check_xtrace: idle gaps are named {gaps}")
    close(gaps[0][1], pin["gaps"][1], "the two long gaps under fjt.route")
    # under the host's annotation (2.45 s long) the window ends at the
    # last device event, 1.94 s in: nothing is known of the device after
    close(xtrace.reduce_trace(SCOPED, window_name="bench.window")["window_s"],
          pin["window_s_annotated"], "scoped cut: the window's clipped end")
    # with the benchmark's own prefix no program span is seen
    if any(k.startswith("fjt.") for k, _ in
           xtrace.attribute_gaps(xtrace.reduce_trace(SCOPED))):
        sys.exit("check_xtrace: span_prefix is not honoured")


def brute_force(path):
    """Busy time as a sweep over sorted interval edges, and self time by
    subtracting every directly nested child, in plain Python."""
    from jax.profiler import ProfileData

    ev = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith(xtrace.DEVICE_PLANE_PREFIX):
            for line in plane.lines:
                if line.name == xtrace.OPS_LINE:
                    ev = [(e.start_ns, e.start_ns + e.duration_ns, e.name)
                          for e in line.events]
    edges = sorted([(s, 1) for s, _, _ in ev] + [(e, -1) for _, e, _ in ev],
                   key=lambda x: (x[0], -x[1]))
    busy, depth, since = 0.0, 0, None
    for t, d in edges:
        if depth == 0 and d == 1:
            since = t
        depth += d
        if depth == 0:
            busy += t - since
    self_by = {}
    for s, e, name in ev:
        inside = [(s2, e2) for s2, e2, _ in ev
                  if s <= s2 and e2 <= e and (s2, e2) != (s, e)]
        direct = [
            (s2, e2) for s2, e2 in inside
            if not any(s3 <= s2 and e2 <= e3 and (s3, e3) != (s2, e2)
                       for s3, e3 in inside)
        ]
        lab = name.split(" = ", 1)[0]
        self_by[lab] = self_by.get(lab, 0.0) + (e - s) - sum(
            e2 - s2 for s2, e2 in direct)
    w0, w1 = min(s for s, _, _ in ev), max(e for _, e, _ in ev)
    return busy / 1e9, (w1 - w0) / 1e9, {k: v / 1e9 for k, v in self_by.items()}


def main() -> None:
    red = xtrace.reduce_trace(TRACE)
    busy, window, self_by = brute_force(TRACE)

    def close(a, b, what):
        if abs(a - b) > 1e-9 * max(1.0, abs(b)):
            sys.exit(f"check_xtrace: {what}: {a!r} != {b!r}")

    check_scoped(close)

    close(red["busy_s"], busy, "busy_s against the edge sweep")
    close(red["window_s"], window, "window_s against min/max")
    for name, secs in red["device_ops"]:
        close(secs, self_by[name], f"self time of {name}")
    close(sum(s for _, s in red["device_ops"]), red["busy_s"],
          "self times add up to the busy time")
    close(red["busy_s"], PINNED["busy_s"], "pinned busy_s")
    close(red["window_s"], PINNED["window_s"], "pinned window_s")
    if red["devices"] != PINNED["devices"]:
        sys.exit("check_xtrace: device planes")
    if red["device_ops"][0][0] != PINNED["top_op"]:
        sys.exit(f"check_xtrace: top op {red['device_ops'][0][0]}")
    if len(red["host_spans"]) != PINNED["host_spans"]:
        sys.exit("check_xtrace: host annotations")
    gap = red["idle_gaps"][0]
    close(gap[1] - gap[0], red["window_s"] - red["busy_s"] - sum(
        b - a for a, b in red["idle_gaps"][1:]), "gaps fill the idle time")
    # the window named by an annotation clips the ops to it
    win = xtrace.reduce_trace(TRACE, window_name="bench.dispatch")
    if not (0 < win["busy_s"] < win["window_s"] < red["window_s"]):
        sys.exit("check_xtrace: annotation window")
    # only executions that lie wholly inside the window are kept: at the
    # ops' own extent neither program does (each starts before its first op)
    if red["modules"]:
        sys.exit("check_xtrace: a program execution cut by the window was kept")
    print(f"check_xtrace: ok (busy {red['busy_s']:.6f} s of "
          f"{red['window_s']:.6f} s, {len(red['device_ops'])} op names)")


if __name__ == "__main__":
    main()
