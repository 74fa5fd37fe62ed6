"""The five readers of ISSUE 39 on synthetic snapshots and a synthetic
reduced trace whose overlaps are known by hand (CPU, no device):

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests -q -p no:cacheprovider
"""

import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from lib import byname, xtrace  # noqa: E402


def read(name, ctx):
    return byname.load("layer_metrics", name).read(ctx)


def hist(seconds, n=1):
    return {"sum": seconds, "n": n}


def ctx_of(stages=None, counters=None, trace=None, between=2.0,
           records=10_000):
    key = 'stage_seconds{stage="%s"}'
    return {
        "snap0": {"ts": 100.0, "counters": {}, "histograms": {}},
        "snap1": {"ts": 100.0 + between, "counters": dict(counters or {}),
                  "histograms": {key % k: hist(v)
                                 for k, v in (stages or {}).items()}},
        "batches": [(1.0, records)],
        "trace": trace,
    }


def trace_of(gaps, spans, devices=1, window_s=1.0):
    return {"devices": devices, "window_s": window_s, "idle_gaps": gaps,
            "host_spans": spans}


def test_drain_wait_frac_divides_by_the_time_between_the_snapshots():
    assert read("drain_wait_frac.sat",
                ctx_of({"drain_wait": 0.5, "drain": 0.9})) == pytest.approx(25.0)
    assert read("drain_wait_frac.sat", ctx_of({"drain": 0.9})) is None


def test_renorm_reads_zero_where_the_program_has_the_stages_and_none_fired():
    assert read("renorm_us_per_krec.sat", ctx_of(
        {"drain_wait": 0.1, "route": 1.0, "renorm": 0.002},
        {"state_renorms": 3})) == pytest.approx(200.0)
    assert read("renorm_us_per_krec.sat", ctx_of(
        {"drain_wait": 0.1, "route": 1.0}, {"state_renorms": 0})) == 0.0
    # a stateless block pipeline spends nothing on renorms
    assert read("renorm_us_per_krec.sat", ctx_of({"drain_wait": 0.1})) == 0.0
    # the parent: none of PR 39's stages → nothing
    assert read("renorm_us_per_krec.sat", ctx_of({"route": 1.0})) is None


def test_telemetry_per_thousand_records():
    assert read("telemetry_us_per_krec.sat",
                ctx_of({"telemetry": 0.1})) == pytest.approx(10_000.0)
    assert read("telemetry_us_per_krec.sat", ctx_of({"sink": 0.1})) is None


def test_starved_is_the_overlap_with_the_ring_wait():
    tr = trace_of([(0.1, 0.3), (0.5, 0.6)],
                  [("fjt.drain", 0.0, 0.25), ("fjt.drain_wait", 0.0, 0.2),
                   ("fjt.drain", 0.55, 0.72), ("fjt.drain_wait", 0.55, 0.7),
                   ("fjt.fetch", 0.0, 1.0)])
    ctx = ctx_of(trace=tr)
    assert read("idle_starved_frac.sat", ctx) == pytest.approx(15.0)
    # drain less its wait: 0.2–0.25 of the first gap, 0.7–0.72 is busy
    assert read("idle_assembling_frac.sat", ctx) == pytest.approx(5.0)


def test_a_gap_on_one_chip_of_four_counts_in_points_of_the_idle_share():
    tr = trace_of([(0.2, 0.6)], [("fjt.drain_wait", 0.1, 0.3),
                                 ("fjt.shard", 0.3, 0.5)], devices=4)
    ctx = ctx_of(trace=tr)
    assert read("idle_starved_frac.sat", ctx) == pytest.approx(2.5)
    assert read("idle_assembling_frac.sat", ctx) == pytest.approx(5.0)


def test_a_gap_longer_than_any_span_is_split_among_them():
    spans = [("fjt.dispatch", 0.0, 1.0),
             ("fjt.route", 0.05, 0.2), ("fjt.renorm", 0.06, 0.1),
             ("fjt.shard", 0.2, 0.35), ("fjt.encode", 0.35, 0.5),
             ("fjt.h2d", 0.5, 0.55), ("fjt.h2d.put", 0.5, 0.52),
             ("fjt.drain", 0.6, 0.9), ("fjt.drain_wait", 0.6, 0.8)]
    tr = trace_of([(0.0, 1.0)], spans)
    ctx = ctx_of(trace=tr)
    assert read("idle_starved_frac.sat", ctx) == pytest.approx(20.0)
    # route ∪ shard ∪ encode ∪ h2d ∪ drain = 0.05–0.55, 0.6–0.9: 0.8 s,
    # less the renorm's 0.04 and the wait's 0.2
    assert read("idle_assembling_frac.sat", ctx) == pytest.approx(56.0)
    # the harness's breakdown names such a gap for one span, and finds
    # none that covers half of it but the whole iteration's
    assert xtrace.attribute_gaps(tr)[0][0] == "fjt.dispatch"
    assert xtrace.attribute_gaps(
        trace_of([(0.0, 1.0)], spans[1:]))[0][0] == "pipeline"


def test_every_gap_is_read_not_the_first_two_hundred():
    gaps = [(k * 0.002, k * 0.002 + 0.001) for k in range(400)]
    tr = trace_of(gaps, [("fjt.drain_wait", 0.0, 1.0)])
    assert read("idle_starved_frac.sat", ctx_of(trace=tr)) == pytest.approx(
        40.0)


def test_a_stretch_in_which_the_thread_never_waited_reads_zero_starved():
    """The mesh cell's score thread finds its ring full for seconds on
    end: a traced stretch may hold no ``fjt.drain_wait`` at all, where
    the program's stage ledger has booked the stage all the same."""
    tr = trace_of([(0.0, 1.0)], [("fjt.drain", 0.2, 0.5),
                                 ("fjt.shard", 0.5, 0.6)], devices=2)
    ctx = ctx_of({"drain_wait": 0.002, "drain": 0.5}, trace=tr)
    assert read("idle_starved_frac.sat", ctx) == 0.0
    assert read("idle_assembling_frac.sat", ctx) == pytest.approx(20.0)


@pytest.mark.parametrize("name", ["idle_starved_frac.sat",
                                  "idle_assembling_frac.sat"])
def test_a_program_without_the_wait_span_or_a_trace_reports_nothing(name):
    tr = trace_of([(0.0, 1.0)], [("fjt.drain", 0.0, 1.0)])
    assert read(name, ctx_of(trace=tr)) is None
    assert read(name, ctx_of(trace=None)) is None
    assert read(name, ctx_of(trace={"devices": 0})) is None
