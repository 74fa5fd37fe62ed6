"""Telemetry history plane (ISSUE 18): the exact delta-frame codec,
THE merge (fleet aggregation across sources == downsampling across
time, bitwise), durable segment rings with torn-tail tolerance,
counter-reset fallback, range-query semantics, the cardinality
governor at zoo scale, and the bench-trend regression tripwire.

The heavyweight incident drill (SIGKILL mid-incident, reconstruction
from durable frames alone) lives in ``bench.py --history-drill``;
these are the fast algebraic pins it relies on.
"""

import json
import os
import random
import signal
import subprocess
import sys
import time
from fractions import Fraction

import pytest

from flink_jpmml_tpu.obs import history
from flink_jpmml_tpu.utils.metrics import MetricsRegistry, govern_struct

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# synthetic snapshots / frames
# ---------------------------------------------------------------------------


def _struct(ts, uptime, counters=None, gauges=None):
    return {
        "ts": float(ts),
        "uptime_s": float(uptime),
        "counters": dict(counters or {}),
        "gauges": {
            n: {"value": float(v), "max": float(v)}
            for n, v in (gauges or {}).items()
        },
        "histograms": {},
    }


def _frame(src, t0, t1, counters, gauges=None, res=1.0):
    """One delta frame whose counter deltas are exactly ``counters``."""
    prev = _struct(t0, 1.0, {n: 0.0 for n in counters})
    cur = _struct(t1, 1.0 + (t1 - t0), counters, gauges)
    return history.capture_frame(prev, cur, src, res, t0=t0, t1=t1)


# adversarial float values: non-representable decimal sums, huge/tiny
# magnitude mixes that float addition would absorb or reorder
_ADVERSARIAL = [0.1, 0.2, 0.3, 1e-17, 1e17, 3.333333333333333, 7.0]


# ---------------------------------------------------------------------------
# exact wire codec
# ---------------------------------------------------------------------------


def test_wire_codec_is_exact():
    total = Fraction(0)
    for v in _ADVERSARIAL * 3:
        total += history._dec(v)
    wire = history._enc(total)
    assert history._dec(wire) == total
    # the float projection is the nearest float, not the identity
    assert abs(history.wire_float(wire) - float(total)) <= abs(
        float(total)
    ) * 1e-15
    # a plain dyadic float stays a plain float on the wire
    assert history._enc(Fraction(0.5)) == 0.5
    # ten 0.1s sum exactly, where fsum/float addition would not
    s = sum((history._dec(0.1) for _ in range(10)), Fraction(0))
    assert s == Fraction(0.1) * 10


# ---------------------------------------------------------------------------
# THE merge: associative + commutative, bitwise
# ---------------------------------------------------------------------------


def _adversarial_frames():
    frames = []
    for si, src in enumerate(("w0", "w1", "w2", "w3")):
        for slot in range(3):
            t0 = float(slot)
            counters = {
                "records_out": _ADVERSARIAL[(si + slot) % len(_ADVERSARIAL)],
                "shed_records": _ADVERSARIAL[(si * 3 + slot) % len(_ADVERSARIAL)],
            }
            gauges = {"queue_depth": float(si) + 0.1 * slot}
            frames.append(
                _frame(src, t0, t0 + 1.0, counters, gauges=gauges)
            )
    return frames


def test_merge_bitwise_invariant_under_adversarial_orderings():
    frames = _adversarial_frames()
    baseline = history.canonical(history.merge_frames(frames))
    for seed in (0, 7, 11, 23, 41):
        shuffled = list(frames)
        random.Random(seed).shuffle(shuffled)
        assert (
            history.canonical(history.merge_frames(shuffled)) == baseline
        ), f"merge not order-invariant (seed {seed})"


def test_merge_bitwise_associative_under_adversarial_groupings():
    frames = _adversarial_frames()
    baseline = history.canonical(history.merge_frames(frames))
    for seed in (3, 13, 29):
        rng = random.Random(seed)
        shuffled = list(frames)
        rng.shuffle(shuffled)
        # random binary grouping: merge random sub-groups, then merge
        # the partials — nested merge must equal the flat merge bitwise
        partials = []
        i = 0
        while i < len(shuffled):
            k = rng.randint(1, 4)
            partials.append(history.merge_frames(shuffled[i:i + k]))
            i += k
        rng.shuffle(partials)
        assert (
            history.canonical(history.merge_frames(partials)) == baseline
        ), f"merge not associative (seed {seed})"


def test_downsample_cascade_equals_direct_bitwise():
    # fine frames on a 0.5s grid over 0..20s, two sources
    frames = []
    for src in ("w0", "w1"):
        for i in range(40):
            t0 = i * 0.5
            frames.append(
                _frame(
                    src, t0, t0 + 0.5,
                    {"records_out": _ADVERSARIAL[i % len(_ADVERSARIAL)]},
                    gauges={"queue_depth": float(i % 5)},
                    res=0.5,
                )
            )
    direct = history.downsample(frames, 5.0)
    cascaded = history.downsample(history.downsample(frames, 1.0), 5.0)
    assert len(direct) == len(cascaded) == 4
    for d, c in zip(direct, cascaded):
        assert history.canonical(d) == history.canonical(c)


def test_gauge_merge_semantics():
    a = _frame("w0", 0.0, 1.0, {}, gauges={"queue_depth": 3.0})
    b = _frame("w1", 0.0, 1.0, {}, gauges={"queue_depth": 5.0})
    m = history.merge_frames([a, b])
    g = m["gauges"]["queue_depth"]
    assert g["min"] == 3.0 and g["max"] == 5.0
    assert set(g["last"]) == {"w0", "w1"}
    # default (sum-merged) gauge: the combined last is the fleet sum
    assert history.combined_last("queue_depth", g["last"]) == 8.0


# ---------------------------------------------------------------------------
# counter-reset fallback
# ---------------------------------------------------------------------------


def test_counter_reset_falls_back_to_cumulative():
    prev = _struct(10.0, 50.0, {"records_out": 100.0})
    cur = _struct(11.0, 51.0, {"records_out": 40.0})  # went backwards
    f = history.capture_frame(prev, cur, "w0", 1.0)
    assert history.wire_float(f["counters"]["records_out"]) == 40.0
    assert f["resets"] == 1

    # a backwards uptime flips EVERY family into the fallback at once,
    # even ones whose cumulative advanced across the restart boundary
    prev = _struct(10.0, 50.0, {"records_out": 60.0, "batches": 9.0})
    cur = _struct(11.0, 2.0, {"records_out": 70.0, "batches": 12.0})
    f = history.capture_frame(prev, cur, "w0", 1.0)
    assert history.wire_float(f["counters"]["records_out"]) == 70.0
    assert history.wire_float(f["counters"]["batches"]) == 12.0
    assert f["resets"] == 2

    # the normal path is a true delta
    prev = _struct(10.0, 50.0, {"records_out": 60.0})
    cur = _struct(11.0, 51.0, {"records_out": 70.0})
    f = history.capture_frame(prev, cur, "w0", 1.0)
    assert history.wire_float(f["counters"]["records_out"]) == 10.0
    assert f["resets"] == 0


# ---------------------------------------------------------------------------
# durable rings: retention under a byte budget, torn tails
# ---------------------------------------------------------------------------


def test_ring_retention_under_byte_budget(tmp_path):
    m = MetricsRegistry()
    store = history.HistoryStore(
        str(tmp_path), metrics=m, max_bytes=48 * 1024,
        resolutions=(1.0,), segment_bytes=4096,
    )
    for i in range(600):
        store.append(
            _frame("w0", float(i), float(i + 1), {"records_out": 1.0 * i})
        )
    store.close()
    assert store.bytes_total() <= 48 * 1024 + 4096  # budget + open tail
    frames = history.read_frames(str(tmp_path))
    assert frames, "retention emptied the store"
    # the OLDEST segments were dropped, the newest survive
    assert frames[0]["t0"] > 0.0
    assert frames[-1]["t0"] == 599.0
    snap = m.struct_snapshot()
    assert snap["counters"]['history_dropped{reason="ring_gc"}'] > 0
    assert snap["counters"]["history_frames"] == 600.0


def test_torn_tail_and_garbage_lines_are_skipped(tmp_path):
    store = history.HistoryStore(str(tmp_path), resolutions=(1.0,))
    for i in range(5):
        store.append(
            _frame("w0", float(i), float(i + 1), {"records_out": 2.0})
        )
    store.close()
    segs = sorted(
        p for p in os.listdir(str(tmp_path)) if p.endswith(".jsonl")
    )
    with open(os.path.join(str(tmp_path), segs[-1]), "a") as f:
        f.write('not json at all\n')
        f.write('{"v":1,"src":"w0","res":1.0,"t0":99.0,"t1":')  # torn
    frames = history.read_frames(str(tmp_path))
    assert len(frames) == 5
    assert all(f["t0"] < 99.0 for f in frames)


_KILL_CHILD = r"""
import sys, time
from flink_jpmml_tpu.obs import history
d = sys.argv[1]
store = history.HistoryStore(d, resolutions=(1.0,))
i = 0
while True:
    prev = {"ts": float(i), "uptime_s": 1.0,
            "counters": {"records_out": float(i)}, "gauges": {},
            "histograms": {}}
    cur = {"ts": float(i + 1), "uptime_s": 2.0,
           "counters": {"records_out": float(i + 1)}, "gauges": {},
           "histograms": {}}
    store.append(history.capture_frame(prev, cur, "w0", 1.0))
    i += 1
    time.sleep(0.002)
"""


def test_sigkill_mid_append_leaves_a_readable_store(tmp_path):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.Popen(
        [sys.executable, "-c", _KILL_CHILD, str(tmp_path)],
        cwd=REPO, env=env,
        stdout=subprocess.DEVNULL, stderr=subprocess.PIPE,
    )
    try:
        deadline = time.monotonic() + 20.0
        while time.monotonic() < deadline:
            if proc.poll() is not None:
                raise AssertionError(
                    "writer died early: "
                    + proc.stderr.read().decode(errors="replace")[-2000:]
                )
            if len(history.read_frames(str(tmp_path))) >= 5:
                break
            time.sleep(0.02)
        os.kill(proc.pid, signal.SIGKILL)
        proc.wait(timeout=10)
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait(timeout=10)
    frames = history.read_frames(str(tmp_path))
    assert len(frames) >= 5
    # each surviving frame is whole: delta of exactly one record
    for f in frames:
        assert history.wire_float(f["counters"]["records_out"]) == 1.0
    # and the survivors are a contiguous prefix of the write order
    t0s = [f["t0"] for f in frames]
    assert t0s == sorted(t0s)
    assert t0s == [float(i) for i in range(len(t0s))]


# ---------------------------------------------------------------------------
# range-query semantics (the /history contract)
# ---------------------------------------------------------------------------


def _populated_store(tmp_path):
    store = history.HistoryStore(str(tmp_path), resolutions=(1.0, 5.0))
    fine = []
    for src in ("w0", "w1"):
        for i in range(10):
            fine.append(
                _frame(
                    src, float(i), float(i + 1),
                    {"records_out": 3.0, "records_in": 4.0},
                    gauges={"queue_depth": float(i)},
                )
            )
    for f in fine:
        store.append(f)
    for f in history.downsample(fine, 5.0):
        store.append(f)
    # a supervisor-side aggregate frame, distinct so leaks are visible
    store.append(
        _frame(history.FLEET_SRC, 0.0, 10.0, {"records_out": 60.0})
    )
    store.close()
    return fine


def test_query_range_step_and_source_semantics(tmp_path):
    _populated_store(tmp_path)
    d = str(tmp_path)

    # default read EXCLUDES the _fleet aggregate (it double-counts)
    p = history.query(d, step=1.0)
    assert p["frames"]
    assert all(
        history.FLEET_SRC not in f["src"].split("+")
        for f in p["frames"]
    )
    total = sum(
        history.wire_float(f["counters"]["records_out"])
        for f in p["frames"]
    )
    assert total == 60.0  # 2 sources x 10 slots x 3

    # ...but the aggregate is reachable by explicit ask
    p = history.query(d, sources=[history.FLEET_SRC])
    assert len(p["frames"]) == 1
    assert history.wire_float(
        p["frames"][0]["counters"]["records_out"]
    ) == 60.0

    # step picks the coarsest stored resolution that still resolves it
    assert history.query(d, step=5.0)["res"] == 5.0
    assert history.query(d, step=1.0)["res"] == 1.0
    assert history.query(d, step=7.0)["res"] == 5.0

    # start/end bound the window
    p = history.query(d, start=3.0, end=6.0, step=1.0)
    assert all(
        f["t1"] >= 3.0 and f["t0"] <= 6.0 for f in p["frames"]
    )
    assert p["frames"]

    # a step-window merge folds both sources into one frame per slot
    p = history.query(d, step=5.0, start=0.0, end=10.0)
    assert len(p["frames"]) == 2
    for f in p["frames"]:
        assert history.wire_float(f["counters"]["records_out"]) == 30.0

    # name projection trims sections and emits plotting series
    p = history.query(d, names=["records_out"], step=1.0)
    for f in p["frames"]:
        assert set(f["counters"]) <= {"records_out"}
        assert not f["gauges"]
    assert "records_out" in p.get("series", {})


def test_query_params_decodes_http_query_strings():
    qargs = history.query_params(
        {
            "name": ["records_out,headroom_frac"],
            "source": ["w0"],
            "start": ["3.0"],
            "end": ["9"],
            "step": ["5"],
        }
    )
    assert qargs["names"] == ["records_out", "headroom_frac"]
    assert qargs["sources"] == ["w0"]
    assert qargs["start"] == 3.0 and qargs["end"] == 9.0
    assert qargs["step"] == 5.0


def test_replay_cli_json_on_a_directory(tmp_path, capsys):
    _populated_store(tmp_path)
    from flink_jpmml_tpu import cli

    rc = cli.replay_main(
        [str(tmp_path), "--step", "1", "--json", "--panel", "none"]
    )
    assert rc == 0
    payload = json.loads(capsys.readouterr().out)
    assert payload["frames"]
    assert payload["resolutions"] == [1.0, 5.0]


# ---------------------------------------------------------------------------
# cardinality governor at zoo scale
# ---------------------------------------------------------------------------


def test_governor_bounds_1000_tenants_with_exact_totals():
    zoo = 1000
    m = MetricsRegistry()
    for i in range(zoo):
        # prebuilt name: tests synthesize members of the catalogued
        # tenant_records family, the serving plane owns the literal
        name = 'tenant_records{model="z%04d"}' % i
        m.counter(name).inc(i + 1)
    snap = m.struct_snapshot()
    governed = govern_struct(snap, max_series=8)
    tenant = {
        n: v for n, v in governed["counters"].items()
        if n.startswith("tenant_records{")
    }
    assert len(tenant) == 8
    other = tenant.pop('tenant_records{model="_other"}')
    # the heaviest tenants survive by name; the tail folds exactly
    assert 'tenant_records{model="z0999"}' in tenant
    assert 'tenant_records{model="z0000"}' not in tenant
    assert sum(tenant.values()) + other == zoo * (zoo + 1) / 2
    # the input is never mutated
    assert len(
        [n for n in snap["counters"] if n.startswith("tenant_records{")]
    ) == zoo


def test_govern_frame_matches_struct_governor_exactly():
    zoo = 1000
    counters = {
        'tenant_records{model="z%04d"}' % i: float(i + 1)
        for i in range(zoo)
    }
    frame = _frame("w0", 0.0, 1.0, counters)
    governed = history.govern_frame(frame, max_series=8)
    tenant = {
        n: v for n, v in governed["counters"].items()
        if n.startswith("tenant_records{")
    }
    assert len(tenant) == 8
    assert 'tenant_records{model="_other"}' in tenant
    total = sum(
        (history._dec(v) for v in tenant.values()), Fraction(0)
    )
    assert total == Fraction(zoo * (zoo + 1), 2)
    # ungoverned input frame is untouched
    assert len(frame["counters"]) == zoo
    # governed frames still merge bitwise-deterministically
    a = history.canonical(history.merge_frames([governed, governed]))
    b = history.canonical(
        history.merge_frames([governed, dict(governed)])
    )
    assert a == b
