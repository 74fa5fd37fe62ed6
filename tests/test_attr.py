"""Latency attribution & continuous device-profiling plane (ISSUE 6):
the per-batch stage ledger (obs/attr.py), the sampled device profiler
and kernel cost ledger (obs/profiler.py), the SLO burn-rate tracker
(obs/slo.py), the buffered span writer's bounded-loss contract
(obs/spans.py), and the fjt-top renderer (cli.py).

The profiler and SLO tracker take injectable clocks, the ledger is
plain dict+histogram work, and fjt-top consumes struct dumps: those
run jax-free and in milliseconds. The span classes at the end run small
real pipelines (ISSUE 24): ``StageLedger.span`` on the profiler's
clock, and the per-thread no-overlap check of a starved pipeline.
"""

import json
import os
import re

import pytest

from flink_jpmml_tpu.obs import attr, profiler, recorder, slo, spans
from flink_jpmml_tpu.obs.server import prometheus_text
from flink_jpmml_tpu.utils.metrics import (
    Histogram,
    MetricsRegistry,
    merge_structs,
)


class FakeClock:
    def __init__(self, t: float = 0.0):
        self.t = float(t)

    def __call__(self) -> float:
        return self.t

    def advance(self, dt: float) -> None:
        self.t += dt


# ---------------------------------------------------------------------------
# Stage ledger
# ---------------------------------------------------------------------------


class TestStageLedger:
    def test_observe_lands_in_stage_family(self):
        m = MetricsRegistry()
        led = attr.StageLedger(m)
        led.observe("sink", 0.002)
        led.observe("sink", 0.004)
        led.observe("encode", 0.001)
        snap = m.struct_snapshot()
        h = Histogram.from_state(
            snap["histograms"][attr.stage_metric_name("sink")]
        )
        assert h.count() == 2
        assert attr.stage_metric_name("encode") in snap["histograms"]

    def test_ledger_for_is_per_registry_singleton(self):
        a, b = MetricsRegistry(), MetricsRegistry()
        assert attr.ledger_for(a) is attr.ledger_for(a)
        assert attr.ledger_for(a) is not attr.ledger_for(b)
        assert attr.ledger_for(None) is None

    def test_merge_associativity(self):
        """Fleet aggregation of stage_seconds must associate exactly —
        (a ⊕ b) ⊕ c == a ⊕ (b ⊕ c) per stage histogram — or two
        supervisors merging in different orders would disagree."""
        regs = [MetricsRegistry() for _ in range(3)]
        obs = [
            [("sink", 0.001), ("encode", 0.03), ("sink", 2.0)],
            [("sink", 0.5), ("readback", 0.004)],
            [("encode", 0.00002), ("sink", 0.009), ("queue_wait", 1.1)],
        ]
        for m, rows in zip(regs, obs):
            led = attr.StageLedger(m)
            for stage, v in rows:
                led.observe(stage, v)
        a, b, c = [m.struct_snapshot() for m in regs]
        left = merge_structs([merge_structs([a, b]), c])
        right = merge_structs([a, merge_structs([b, c])])
        stages = {
            n for n in left["histograms"] if n.startswith("stage_seconds")
        }
        assert stages == {
            n for n in right["histograms"] if n.startswith("stage_seconds")
        }
        assert len(stages) == 4
        for n in stages:
            hl = Histogram.from_state(left["histograms"][n])
            hr = Histogram.from_state(right["histograms"][n])
            assert hl.state()["counts"] == hr.state()["counts"]
            assert hl.count() == hr.count()
            assert hl.sum() == pytest.approx(hr.sum())
            for q in (0.5, 0.99, 0.999):
                assert hl.quantile(q) == hr.quantile(q)

    def test_fleet_gauge_merge_semantics(self):
        """Ratio/boolean gauges must not SUM across the fleet: two
        workers at 5.8% MFU are not an 11.6% fleet, and one breached
        worker among two must breach the aggregate ``slo_ok``."""
        a, b = MetricsRegistry(), MetricsRegistry()
        a.gauge("device_mfu").set(0.058)
        b.gauge("device_mfu").set(0.031)
        a.gauge("slo_ok").set(1.0)
        b.gauge("slo_ok").set(0.0)  # b is breached
        a.gauge('slo_burn_rate{window="300"}').set(0.5)
        b.gauge('slo_burn_rate{window="300"}').set(20.0)
        a.gauge("inflight_depth").set(2)  # totals still sum
        b.gauge("inflight_depth").set(3)
        g = merge_structs([a.struct_snapshot(), b.struct_snapshot()])["gauges"]
        assert g["device_mfu"]["value"] == 0.058  # worst/busiest, not sum
        assert g["slo_ok"]["value"] == 0.0  # any breached → breached
        assert g['slo_burn_rate{window="300"}']["value"] == 20.0
        assert g["inflight_depth"]["value"] == 5

    def test_registry_cache_does_not_leak(self):
        """ledger_for/profiler_for cache per-registry on weak keys; the
        cached value must not strongly reference the registry or every
        ephemeral bench/test registry lives forever."""
        import gc
        import weakref

        m = MetricsRegistry()
        attr.ledger_for(m).observe("sink", 0.001)
        profiler.profiler_for(m)
        ref = weakref.ref(m)
        del m
        gc.collect()
        assert ref() is None

    def test_exemplar_merge_keeps_worst_per_bucket(self):
        a, b = Histogram(), Histogram()
        a.observe(0.4, exemplar="tid-a")
        b.observe(0.5, exemplar="tid-b")  # same bucket, worse value
        a.merge(b)
        (ex,) = a.exemplars().values()
        assert ex[0] == "tid-b" and ex[1] == 0.5

    def test_observe_keeps_worst_exemplar_per_bucket(self):
        """A later rate-limited re-capture with a SMALLER same-bucket
        value must not displace the worst offender's trace link —
        observe() promises the same worst-per-bucket semantics merge()
        and fjt-top's 'worst observed per bucket' rendering do."""
        h = Histogram()
        assert h.bucket_index(0.35) == h.bucket_index(0.5)
        h.observe(0.5, exemplar="tid-worst")
        h.observe(0.35, exemplar="tid-later-smaller")
        (ex,) = h.exemplars().values()
        assert ex[0] == "tid-worst" and ex[1] == 0.5
        h.observe(0.55, exemplar="tid-worse")  # genuinely worse: wins
        (ex,) = h.exemplars().values()
        assert ex[0] == "tid-worse" and ex[1] == 0.55

    def test_summary_shares_and_quantiles(self):
        m = MetricsRegistry()
        led = attr.StageLedger(m)
        for _ in range(10):
            led.observe("sink", 0.001)
        led.observe("encode", 0.09)
        s = attr.summary(m)
        assert set(s) == {"sink", "encode"}
        assert s["sink"]["n"] == 10
        assert s["encode"]["share"] == pytest.approx(0.9, abs=0.01)
        assert sum(row["share"] for row in s.values()) == pytest.approx(
            1.0, abs=0.01
        )
        # struct-dump input renders identically to the live registry
        assert attr.summary(m.struct_snapshot()) == s
        assert attr.summary(MetricsRegistry()) is None
        assert attr.summary({}) is None

    def test_a_nested_stage_is_no_term_of_the_total(self):
        """``claim`` lies inside ``route`` (ISSUE 34): its time is
        route's too, so the shares are of the total without it."""
        m = MetricsRegistry()
        led = attr.StageLedger(m)
        led.observe("route", 0.06)
        led.observe("claim", 0.03)
        led.observe("encode", 0.04)
        s = attr.summary(m)
        assert attr.NESTED_IN["claim"] == "route"
        assert attr.STAGE_THREADS["claim"] == attr.STAGE_THREADS["route"]
        assert s["route"]["share"] == pytest.approx(0.6, abs=0.01)
        assert s["claim"]["share"] == pytest.approx(0.3, abs=0.01)
        assert s["route"]["share"] + s["encode"]["share"] == pytest.approx(
            1.0, abs=0.01)


class TestExemplarFlightLinkage:
    def test_top_bucket_observation_links_scrape_to_flight(self):
        """The acceptance path: a tail observation produces (1) a
        trace-id'd latency_exemplar flight event, (2) the same trace id
        on the histogram's top bucket, and (3) an OpenMetrics exemplar
        suffix on the rendered _bucket line — all three resolve to each
        other."""
        m = MetricsRegistry()
        led = attr.StageLedger(m)
        led.observe("sink", 0.75)  # first obs is always a top-bucket
        h = m.histogram(attr.stage_metric_name("sink"))
        exs = h.exemplars()
        assert len(exs) == 1
        (tid, val, _ts) = next(iter(exs.values()))
        assert val == 0.75
        flight_tids = {
            e["trace_id"]
            for e in recorder.events()
            if e.get("kind") == "latency_exemplar"
        }
        assert tid in flight_tids
        text = prometheus_text({None: m}, openmetrics=True)
        scraped = re.findall(r'# \{trace_id="([^"]+)"\} ([\d.e+-]+)', text)
        assert (tid, "0.75") in scraped
        # a classic (non-negotiated) scrape must stay exemplar-free:
        # the 0.0.4 text format does not admit them
        assert "trace_id" not in prometheus_text({None: m})

    def test_repeat_same_bucket_is_rate_limited(self):
        m = MetricsRegistry()
        led = attr.StageLedger(m)
        before = len(
            [e for e in recorder.events() if e.get("kind") == "latency_exemplar"]
        )
        for _ in range(50):
            led.observe("sink", 0.75)  # same bucket, within 1s
        after = len(
            [e for e in recorder.events() if e.get("kind") == "latency_exemplar"]
        )
        assert after - before == 1  # only the first captured

    def test_queue_wait_stall_event(self, monkeypatch):
        monkeypatch.setenv("FJT_SLO_TARGET_MS", "100")  # threshold 50ms
        m = MetricsRegistry()
        led = attr.StageLedger(m)  # env read at construction

        def stalls():
            return [
                e for e in recorder.events() if e.get("kind") == "stage_stall"
            ]

        n0 = len(stalls())
        led.observe("queue_wait", 0.2)
        assert len(stalls()) == n0 + 1
        ev = stalls()[-1]
        assert ev["stage"] == "queue_wait" and ev["seconds"] == 0.2
        led.observe("queue_wait", 0.3)  # within the 1s min period
        assert len(stalls()) == n0 + 1
        led.observe("queue_wait", 0.04)  # under threshold: never
        assert len(stalls()) == n0 + 1
        # no deadline configured → inert
        monkeypatch.delenv("FJT_SLO_TARGET_MS")
        led2 = attr.StageLedger(MetricsRegistry())
        led2.observe("queue_wait", 99.0)
        assert len(stalls()) == n0 + 1


# ---------------------------------------------------------------------------
# Device profiler: rate limiter + kernel cost ledger
# ---------------------------------------------------------------------------


def _profile(records=64):
    return {
        "records": records,
        "flops_per_record": 1280.0,
        "bytes_per_record": 6.0,
        "model": "m1",
        "backend": "xla",
    }


class TestDeviceProfilerRateLimiter:
    def _prof(self, tmp_path, clk, interval=1.0, budget=0.01):
        m = MetricsRegistry()
        ledger = profiler.KernelCostLedger(
            path=str(tmp_path / "kc.json"), flush_interval_s=0.0, clock=clk
        )
        return m, profiler.DeviceProfiler(
            m, interval_s=interval, overhead_budget=budget,
            clock=clk, cost_ledger=ledger,
        )

    def test_interval_gate(self, tmp_path):
        clk = FakeClock(0.0)
        _, prof = self._prof(tmp_path, clk)
        assert not prof.should_sample()  # 0s since "last": not yet due
        clk.advance(1.0)
        assert prof.should_sample()  # claims the slot
        assert not prof.should_sample()  # same instant: claimed
        clk.advance(0.5)
        assert not prof.should_sample()
        clk.advance(0.5)
        assert prof.should_sample()

    def test_overhead_budget_gate(self, tmp_path):
        """A sample whose serialization cost dwarfs the budget pauses
        sampling until wall clock amortizes it back under 1%."""
        clk = FakeClock(0.0)
        _, prof = self._prof(tmp_path, clk)
        clk.advance(1.0)
        assert prof.should_sample()
        prof.record_sample(0.4, _profile(), overhead_s=0.5)
        clk.advance(1.0)  # t=2: 0.5/2 = 25% ≫ 1%
        assert not prof.should_sample()
        clk.t = 49.0  # 0.5/49 ≈ 1.02% > 1%
        assert not prof.should_sample()
        clk.t = 51.0  # 0.5/51 ≈ 0.98% ≤ 1%
        assert prof.should_sample()

    def test_overhead_stays_bounded_over_a_run(self, tmp_path):
        """Simulated hour at one claim attempt per 100ms, each sample
        costing 80ms: granted samples must keep cumulative sampling
        overhead ≤ budget + one sample's worth of slack."""
        clk = FakeClock(0.0)
        _, prof = self._prof(tmp_path, clk)
        per_sample = 0.08
        spent = 0.0
        for _ in range(36_000):
            clk.advance(0.1)
            if prof.should_sample():
                prof.record_sample(
                    per_sample, _profile(), overhead_s=per_sample
                )
                spent += per_sample
        assert spent / clk.t <= 0.01 + per_sample / clk.t
        assert spent > 0  # the limiter throttles, it doesn't starve

    def test_supplied_peaks_register_the_roofline_gauges(
        self, tmp_path, monkeypatch
    ):
        monkeypatch.setenv("FJT_PROF_PEAKS", "1e12,1e11")
        m, prof = self._prof(tmp_path, FakeClock(10.0))
        prof.record_sample(0.001, _profile(records=1000), overhead_s=0.002)
        g = m.struct_snapshot()["gauges"]
        assert g["device_mfu"]["value"] > 0
        assert g["device_membw_util"]["value"] > 0

    def test_disabled_by_env(self, tmp_path, monkeypatch):
        monkeypatch.setenv("FJT_PROF_SAMPLE", "off")
        prof = profiler.DeviceProfiler(
            MetricsRegistry(),
            cost_ledger=profiler.KernelCostLedger(
                path=str(tmp_path / "kc.json")
            ),
        )
        assert not prof.enabled
        assert not prof.should_sample()

    def test_a_deployment_states_its_interval_before_the_pipeline(
            self, monkeypatch):
        """``profiler_for(metrics, interval_s=)`` sets the interval of
        the registry's profiler where it creates it (a deployment on
        four chips samples every two minutes: benchmark/paths/mesh.py);
        the pipeline's later ``profiler_for(metrics)`` gets that one."""
        monkeypatch.delenv("FJT_PROF_SAMPLE", raising=False)
        m = MetricsRegistry()
        prof = profiler.profiler_for(m, interval_s=120.0)
        assert prof._interval == 120.0
        assert profiler.profiler_for(m) is prof
        assert profiler.profiler_for(m, interval_s=1.0)._interval == 120.0
        assert prof.should_sample() and not prof.should_sample()
        assert profiler.profiler_for(MetricsRegistry())._interval == 1.0

    def test_sample_feeds_gauges_and_device_stage(self, tmp_path):
        clk = FakeClock(10.0)
        m, prof = self._prof(tmp_path, clk)
        prof.record_sample(0.001, _profile(records=1000), overhead_s=0.002)
        snap = m.struct_snapshot()
        assert snap["counters"]["device_samples"] == 1
        assert snap["gauges"]["device_ns_per_record"]["value"] == pytest.approx(
            1000.0
        )
        assert snap["gauges"]["flops_per_record"]["value"] == 1280.0
        # unknown (CPU) device kind → no roofline gauges at all
        assert "device_mfu" not in snap["gauges"]
        assert "device_membw_util" not in snap["gauges"]
        h = Histogram.from_state(
            snap["histograms"][attr.stage_metric_name("device")]
        )
        assert h.count() == 1


class TestKernelCostLedger:
    def test_persist_merge_and_corrupt_tolerance(self, tmp_path):
        path = tmp_path / "kernel_costs.json"
        # a foreign process's entry already on disk must survive
        path.write_text(json.dumps(
            {"version": 1, "entries": {"other|xla": {"samples": 3}}}
        ))
        led = profiler.KernelCostLedger(
            path=str(path), flush_interval_s=0.0
        )
        led.update("m1", "xla", 0.001, 1000, 1280.0, 6.0)
        data = json.loads(path.read_text())
        assert set(data["entries"]) == {"other|xla", "m1|xla"}
        e = data["entries"]["m1|xla"]
        assert e["samples"] == 1
        assert e["device_s_per_record"] == pytest.approx(1e-6)
        assert e["rec_s"] == pytest.approx(1e6)
        # EWMA folds the second sample rather than replacing
        led.update("m1", "xla", 0.002, 1000, 1280.0, 6.0)
        e2 = json.loads(path.read_text())["entries"]["m1|xla"]
        assert e2["samples"] == 2
        assert 1e-6 < e2["device_s_per_record"] < 2e-6
        # corrupt disk state: overwritten, never raises
        path.write_text("{nope")
        led.update("m2", "xla", 0.001, 10, None, None)
        data = json.loads(path.read_text())
        assert "m2|xla" in data["entries"]

    def test_flush_rate_limited(self, tmp_path):
        clk = FakeClock(0.0)
        path = tmp_path / "kc.json"
        led = profiler.KernelCostLedger(
            path=str(path), flush_interval_s=5.0, clock=clk
        )
        clk.advance(10.0)
        led.update("m1", "xla", 0.001, 100, None, None)  # due → writes
        assert path.exists()
        mtime = path.stat().st_mtime_ns
        clk.advance(1.0)
        led.update("m1", "xla", 0.001, 100, None, None)  # not due
        assert path.stat().st_mtime_ns == mtime
        led.flush()  # explicit flush always writes the dirty state
        assert json.loads(path.read_text())["entries"]["m1|xla"]["samples"] == 2


class TestRoofline:
    def test_known_chip_and_unknown(self, monkeypatch):
        monkeypatch.delenv("FJT_PROF_PEAKS", raising=False)
        assert profiler.chip_peaks("TPU v4") == (275e12, 1228e9)
        assert profiler.chip_peaks("TPU v5 lite") == (197e12, 819e9)
        assert profiler.chip_peaks("weird chip", strict=True) is None
        assert profiler.chip_peaks("weird chip") is None

    def test_peaks_env_supplies_an_unknown_chip(self, monkeypatch):
        monkeypatch.setenv("FJT_PROF_PEAKS", "2e12,5e11")
        assert profiler.chip_peaks("weird chip") == (2e12, 5e11)
        assert profiler.chip_peaks("weird chip", strict=True) is None
        monkeypatch.setenv("FJT_PROF_PEAKS", "garbage")
        assert profiler.chip_peaks("weird chip") is None

    def test_roofline_math(self):
        mfu, membw = profiler.roofline(1e6, 1280.0, 6.0, (1e12, 1e9))
        assert mfu == pytest.approx(1.28e-3)
        assert membw == pytest.approx(6e-3)
        assert profiler.roofline(0.0, 1.0, 1.0, (1e12, 1e9)) == (None, None)
        assert profiler.roofline(1e6, None, None, (1e12, 1e9)) == (None, None)

    def test_dispatch_profile_f32_fallback_is_honest(self):
        prof = attr.dispatch_profile(object(), 32)
        assert prof["records"] == 32
        assert prof["flops_per_record"] is None
        assert prof["bytes_per_record"] is None


class TestDispatcherSampling:
    """The sampled device-timing bracket inside OverlappedDispatcher
    (the launch-path integration of obs/profiler.py)."""

    class _Leaf:
        def __init__(self, fail=None):
            self.fail = fail

        def block_until_ready(self):
            if self.fail is not None:
                raise self.fail

    def _disp(self, tmp_path, interval=0.0):
        from flink_jpmml_tpu.runtime.pipeline import OverlappedDispatcher

        m = MetricsRegistry()
        # interval 0 disables; a tiny positive interval samples every
        # launch once the clock has moved at all
        prof = profiler.DeviceProfiler(
            m, interval_s=interval,
            cost_ledger=profiler.KernelCostLedger(
                path=str(tmp_path / "kc.json")
            ),
        )
        return m, OverlappedDispatcher(depth=2, metrics=m, profiler=prof)

    def test_sampled_launch_feeds_profiler(self, tmp_path):
        m, disp = self._disp(tmp_path, interval=1e-9)
        for _ in range(3):
            disp.launch(lambda: self._Leaf(), profile=_profile())
        disp.close()
        snap = m.struct_snapshot()
        assert snap["counters"]["device_samples"] >= 1
        assert attr.stage_metric_name("device") in snap["histograms"]
        assert snap["gauges"]["device_ns_per_record"]["value"] > 0

    def test_device_sample_excludes_dispatch_host_time(self, tmp_path):
        """The sampling bracket times only the post-dispatch wait:
        dispatch_fn's host work (featurize/staging on the host-encode
        path) runs before the kernel is queued, so folding it in would
        book host time as device time — inflating device_ns_per_record
        and double-booking what dispatch_quantized already attributed
        to encode/h2d."""
        import time as _time

        m, disp = self._disp(tmp_path, interval=1e-9)

        class _SlowReady:
            def block_until_ready(self):
                _time.sleep(0.02)

        def dispatch():
            _time.sleep(0.08)  # host featurize/staging stand-in
            return _SlowReady()

        disp.launch(dispatch, profile=_profile())
        snap = m.struct_snapshot()
        dev = snap["histograms"][attr.stage_metric_name("device")]
        assert dev["n"] == 1
        assert 0.02 <= dev["sum"] < 0.06, (
            f"device sample {dev['sum']:.3f}s books dispatch host time"
        )
        disp.close()

    def test_no_profile_means_no_sample(self, tmp_path):
        m, disp = self._disp(tmp_path, interval=1e-9)
        disp.launch(lambda: self._Leaf())  # profile-less launch
        disp.close()
        assert m.struct_snapshot()["counters"].get("device_samples", 0) == 0

    def test_poisoned_inflight_batch_never_leaks_into_launch(self, tmp_path):
        """The sampler's window drain touches OLDER batches' handles; a
        poisoned one must surface its error at finish_oldest (right
        meta, right caller), never out of a later launch()."""
        m, disp = self._disp(tmp_path, interval=1e-9)
        boom = RuntimeError("device says no")
        disp.launch(lambda: self._Leaf(fail=boom), meta="bad")
        # this launch drains the window for its sample: must NOT raise
        h2 = disp.launch(lambda: self._Leaf(), meta="good", profile=_profile())
        with pytest.raises(RuntimeError, match="device says no"):
            disp.finish_oldest()
        out, meta = disp.finish_oldest()
        assert meta == "good"
        disp.close()
        assert h2.done

    def test_queue_wait_excludes_completion_callback(self, tmp_path):
        """The overflow wait books ONLY the blocking device wait as
        queue_wait — the complete callback (sink, checkpoint) that
        finish_oldest runs afterwards books its own stage, never
        inflating queue_wait (one interval, one stage)."""
        import time as _time

        from flink_jpmml_tpu.runtime.pipeline import OverlappedDispatcher

        m = MetricsRegistry()
        disp = OverlappedDispatcher(
            depth=1, metrics=m,
            complete=lambda out, meta: _time.sleep(0.02),
        )
        disp._profiler = None
        for i in range(4):
            disp.launch(lambda: self._Leaf(), meta=i)
        disp.close()
        q = Histogram.from_state(
            m.struct_snapshot()["histograms"][
                attr.stage_metric_name("queue_wait")
            ]
        )
        assert q.count() == 3  # launches 2..4 overflowed depth-1
        # 3 × 20ms sink sleeps must NOT land in queue_wait: the waits
        # themselves are no-op block_until_ready calls
        assert q.sum() < 0.01

    def test_depth0_books_readback_not_queue_wait(self):
        """A depth-0 dispatcher (in_flight=1, the latency operating
        point) has no window for a ready batch to wait in: launch's
        immediate drain of its own just-dispatched batch is readback.
        Booking it as queue_wait would read as 'window too shallow'
        (and fire stage_stall events) on every batch of a normal
        synchronous pipeline."""
        from flink_jpmml_tpu.runtime.pipeline import OverlappedDispatcher

        m = MetricsRegistry()
        disp = OverlappedDispatcher(depth=0, metrics=m)
        disp._profiler = None
        for i in range(4):
            disp.launch(lambda: self._Leaf(), meta=i)
        disp.close()
        snap = m.struct_snapshot()
        rname = attr.stage_metric_name("readback")
        assert Histogram.from_state(snap["histograms"][rname]).count() == 4
        assert attr.stage_metric_name("queue_wait") not in snap["histograms"]

    def test_queue_wait_attribution_on_full_window(self, tmp_path):
        m, disp = self._disp(tmp_path, interval=0.0)
        for i in range(5):
            disp.launch(lambda: self._Leaf(), meta=i)
        disp.close()
        snap = m.struct_snapshot()
        qname = attr.stage_metric_name("queue_wait")
        rname = attr.stage_metric_name("readback")
        # launches 3..5 overflowed the depth-2 window → queue_wait;
        # close() drains the remaining two → readback
        assert Histogram.from_state(snap["histograms"][qname]).count() == 3
        assert Histogram.from_state(snap["histograms"][rname]).count() == 2


# ---------------------------------------------------------------------------
# One span per stage: histogram, chrome span and profiler annotation
# ---------------------------------------------------------------------------


def _fjt_events(xplane_dir):
    """→ [(name, thread line, start_ns, duration_ns, stats)] of the
    ``fjt.*`` annotations on ``/host:CPU`` of a profiler session."""
    import glob

    from jax.profiler import ProfileData

    (xp,) = glob.glob(
        os.path.join(xplane_dir, "plugins", "profile", "*", "*.xplane.pb")
    )
    out = []
    for plane in ProfileData.from_file(xp).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(attr.ANNOTATION_PREFIX):
                    out.append((
                        e.name, line.name, e.start_ns, e.duration_ns,
                        dict(e.stats),
                    ))
    return out


_SPAN_BATCH = 64


@pytest.fixture(scope="module")
def gbm(tmp_path_factory):
    """The small compiled GBM both span classes run their pipelines on."""
    from assets.generate import gen_gbm
    from flink_jpmml_tpu.compile import compile_pmml
    from flink_jpmml_tpu.pmml import parse_pmml_file

    tmp = tmp_path_factory.mktemp("span_gbm")
    doc = parse_pmml_file(
        gen_gbm(str(tmp), n_trees=10, depth=3, n_features=4)
    )
    return compile_pmml(doc, batch_size=_SPAN_BATCH)


class TestStageSpans:
    """``StageLedger.span`` at the block path's sites: every interval is
    booked once, under one name, into the stage histogram and — while a
    profiler session runs — onto ``/host:CPU`` of its xplane."""

    B, BLOCKS = _SPAN_BATCH, 12

    def _run(self, gbm, keyed):
        import numpy as np

        from flink_jpmml_tpu.runtime.block import (
            BlockPipeline, FiniteBlockSource,
        )
        from flink_jpmml_tpu.runtime.state import StateSpec

        rng = np.random.default_rng(24)
        data = rng.normal(0.0, 1.0, size=(self.B * self.BLOCKS, 4)).astype(
            np.float32
        )
        data[:, 0] = rng.integers(0, 9, size=data.shape[0])
        delivered = []
        m = MetricsRegistry()
        pipe = BlockPipeline(
            FiniteBlockSource(data, block_size=self.B), gbm,
            lambda out, n, first_off: delivered.append((first_off, n)),
            metrics=m, use_native=False, in_flight=2,
            state=StateSpec(capacity=64, key_col=0) if keyed else None,
        )
        pipe.run_until_exhausted(timeout=60.0)
        assert sum(n for _, n in delivered) == data.shape[0]
        return m.struct_snapshot()["histograms"], delivered

    @pytest.mark.parametrize("keyed", [False, True],
                             ids=["stateless", "keyed"])
    def test_spans_lie_on_the_profilers_clock(
        self, gbm, keyed, tmp_path, monkeypatch
    ):
        import jax

        monkeypatch.delenv("FJT_TRACE_DIR", raising=False)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
        try:
            hists, delivered = self._run(gbm, keyed)
        finally:
            jax.profiler.stop_trace()
        events = _fjt_events(str(tmp_path))
        by_stage = {}
        for name, _, _, dur, _ in events:
            st = by_stage.setdefault(name, [0, 0.0])
            st[0] += 1
            st[1] += dur / 1e9
        want = {"fjt.drain", "fjt.encode", "fjt.h2d", "fjt.sink"}
        if keyed:
            want.add("fjt.route")
        assert want <= set(by_stage), sorted(by_stage)
        assert {"fjt.queue_wait", "fjt.readback"} & set(by_stage)
        if not keyed:
            assert "fjt.route" not in by_stage
        # the children of h2d are on the trace and in no histogram
        assert "fjt.h2d.launch" in by_stage
        assert attr.stage_metric_name("h2d.launch") not in hists
        # histogram and trace hold the same intervals: equal counts,
        # and sums within 5% or 1 ms
        for stage in attr.STAGES:
            if stage == "device":
                continue  # a sampled duration, the one observe() caller
            h = hists.get(attr.stage_metric_name(stage))
            n_span, s_span = by_stage.get("fjt." + stage, (0, 0.0))
            assert n_span == (h["n"] if h else 0), stage
            if h:
                assert abs(s_span - h["sum"]) <= max(
                    0.05 * h["sum"], 1e-3
                ), (stage, s_span, h["sum"])
        # one dispatch, one first_off, on every span it owns; the
        # score thread did all of them
        per_dispatch = ["fjt.encode", "fjt.h2d", "fjt.sink"] + (
            ["fjt.route"] if keyed else []
        )
        firsts = {
            name: sorted(
                st["first_off"] for nm, _, _, _, st in events if nm == name
            )
            for name in per_dispatch
        }
        assert firsts["fjt.sink"] == sorted(f for f, _ in delivered)
        for name in per_dispatch:
            assert firsts[name] == firsts["fjt.sink"], name
        waited = sorted(
            st["first_off"] for nm, _, _, _, st in events
            if nm in ("fjt.queue_wait", "fjt.readback")
        )
        assert waited == firsts["fjt.sink"]
        assert len({ln for nm, ln, _, _, _ in events
                    if nm in per_dispatch + ["fjt.drain"]}) == 1
        drained = sum(
            st["n"] for nm, _, _, _, st in events if nm == "fjt.drain"
        )
        assert drained == self.B * self.BLOCKS

    def test_histograms_fill_without_a_session(
        self, gbm, tmp_path, monkeypatch
    ):
        monkeypatch.delenv("FJT_TRACE_DIR", raising=False)
        hists, delivered = self._run(gbm, keyed=True)
        for stage in ("drain", "encode", "route", "h2d", "sink", "commit"):
            h = hists[attr.stage_metric_name(stage)]
            assert h["n"] >= len(delivered), stage
        waits = sum(
            hists.get(attr.stage_metric_name(s), {"n": 0})["n"]
            for s in ("queue_wait", "readback")
        )
        assert waits == len(delivered)
        assert not list(tmp_path.iterdir())  # nothing armed, no file

    def test_chrome_span_carries_the_stage_name(
        self, gbm, tmp_path, monkeypatch
    ):
        """With ``FJT_TRACE_DIR`` set the chrome span of a stage has the
        name of its histogram (``encode``/``h2d``, not the old
        ``featurize``/``h2d_dispatch``) and the dispatch's identity."""
        monkeypatch.setenv("FJT_TRACE_DIR", str(tmp_path))
        try:
            hists, delivered = self._run(gbm, keyed=True)
        finally:
            spans.flush()
        (f,) = [p for p in os.listdir(tmp_path) if p.endswith(".json")]
        evs = [
            e for e in _span_events(os.path.join(tmp_path, f))
            if e.get("ph") == "X" and not e["name"].endswith("_exemplar")
        ]
        names = {e["name"] for e in evs}
        assert {"drain", "encode", "route", "h2d", "sink", "commit"} <= names
        assert not names & {"featurize", "h2d_dispatch"}
        for stage in ("encode", "route", "h2d", "sink"):
            got = sorted(
                e["args"]["first_off"] for e in evs if e["name"] == stage
            )
            assert got == sorted(f for f, _ in delivered), stage
            assert len(got) == hists[attr.stage_metric_name(stage)]["n"]
        assert all(
            e["args"]["bytes"] > 0 for e in evs if e["name"] == "h2d"
        )

    def test_unbooked_ledger_spans_book_nothing(self):
        """A caller without a registry still gets the span (chrome,
        profiler); no histogram exists to fill."""
        with attr.UNBOOKED.span("encode", n=3) as sp:
            pass
        assert sp.seconds >= 0.0 and not attr.UNBOOKED._hists
        assert attr.ledger_for(None) is None

    def test_begin_end_books_like_with(self):
        m = MetricsRegistry()
        led = attr.ledger_for(m)
        sp = led.begin("drain")
        sp.note(n=7)
        dt = sp.end()
        with led.span("drain", n=1):
            pass
        h = m.struct_snapshot()["histograms"][attr.stage_metric_name("drain")]
        assert h["n"] == 2 and h["sum"] >= dt
        assert sp.args == {"n": 7}

    def test_prof_sample_books_the_samplers_two_waits(self, tmp_path):
        """A sampled launch books its window drain and its bracket wait
        as ``prof_sample`` — two intervals — and ``dispatch_fn``'s own
        time in neither."""
        import time as _time

        from flink_jpmml_tpu.runtime.pipeline import OverlappedDispatcher

        m = MetricsRegistry()
        prof = profiler.DeviceProfiler(
            m, interval_s=1e-9,
            cost_ledger=profiler.KernelCostLedger(
                path=str(tmp_path / "kc.json")
            ),
        )
        disp = OverlappedDispatcher(depth=2, metrics=m, profiler=prof)

        class _SlowReady:
            def block_until_ready(self):
                _time.sleep(0.02)

        def dispatch():
            _time.sleep(0.08)
            return _SlowReady()

        disp.launch(dispatch, profile=_profile(),
                    ident={"first_off": 0, "n": 64})
        h = m.struct_snapshot()["histograms"][
            attr.stage_metric_name("prof_sample")
        ]
        assert h["n"] == 2
        assert 0.02 <= h["sum"] < 0.06, h["sum"]
        disp.close()


class TestStarvedPipelineBooksNoMoreThanWallTime:
    """PERF.md's doubt: on a starved pipeline fetch/decode/
    prefetch_wait read more than wall time. Per thread, the spans of a
    pipeline fed by a slow producer must not overlap, and must sum to
    at most the wall time."""

    def test_spans_of_a_thread_do_not_overlap(
        self, gbm, tmp_path, monkeypatch
    ):
        import threading
        import time as _time

        import numpy as np

        from flink_jpmml_tpu.runtime.block import BlockPipeline
        from flink_jpmml_tpu.runtime.kafka import (
            KafkaBlockSource, MiniKafkaBroker,
        )

        trace_dir = tmp_path / "spans"
        monkeypatch.setenv("FJT_TRACE_DIR", str(trace_dir))
        rng = np.random.default_rng(5)
        broker = MiniKafkaBroker(topic="starved")
        m = MetricsRegistry()
        delivered = []
        src = KafkaBlockSource(
            broker.host, broker.port, "starved", n_cols=4, max_wait_ms=20,
            metrics=m,
        )
        pipe = BlockPipeline(
            src, gbm,
            lambda out, n, first_off: delivered.append(n),
            metrics=m, use_native=False, in_flight=2, prefetch=True,
        )
        stop = threading.Event()

        def produce():  # 16 records every 25 ms: far under the pipeline
            while not stop.is_set():
                broker.append_rows(
                    rng.normal(size=(16, 4)).astype(np.float32)
                )
                _time.sleep(0.025)

        feeder = threading.Thread(target=produce, daemon=True)
        t0 = _time.monotonic()
        try:
            pipe.start()
            feeder.start()
            _time.sleep(1.0)
        finally:
            stop.set()
            feeder.join(timeout=5.0)
            pipe.stop()
            pipe.join(timeout=30.0)
            src.close()
            broker.close()
            spans.flush()
        wall = _time.monotonic() - t0
        assert not feeder.is_alive() and sum(delivered) > 0
        (f,) = [p for p in os.listdir(trace_dir) if p.endswith(".json")]
        by_tid = {}
        for e in _span_events(os.path.join(trace_dir, f)):
            if e.get("ph") == "X" and e["name"] in attr.STAGES:
                by_tid.setdefault(e["tid"], []).append(
                    (e["ts"], e["ts"] + e["dur"], e["name"])
                )
        stages = {nm for evs in by_tid.values() for _, _, nm in evs}
        assert {"fetch", "decode", "prefetch_wait", "drain"} <= stages
        assert len(by_tid) >= 3  # sidecar, ring feed, score thread
        for tid, evs in by_tid.items():
            evs.sort()
            for (_, e0, n0), (s1, _, n1) in zip(evs, evs[1:]):
                # the span file rounds to 0.1 us
                assert s1 >= e0 - 0.2, (tid, n0, n1, e0, s1)
            total = sum(e - s for s, e, _ in evs) / 1e6
            assert total <= wall, (tid, total, wall)
        # and the histograms say the same: no stage above the wall time
        for stage, h in (
            (s, m.struct_snapshot()["histograms"].get(
                attr.stage_metric_name(s)))
            for s in ("fetch", "decode", "prefetch_wait")
        ):
            assert h is not None and h["sum"] <= wall, (stage, h)


# ---------------------------------------------------------------------------
# SLO burn-rate tracker
# ---------------------------------------------------------------------------


class TestSLOTracker:
    def _tracker(self, clk, **kw):
        m = MetricsRegistry()
        kw.setdefault("deadline_s", 0.01)
        kw.setdefault("objective", 0.9)  # budget 0.1: burns stay small
        kw.setdefault("windows", ((10.0, 2.0), (60.0, 1.5)))
        t = slo.SLOTracker(
            m, source="batch_latency_s", clock=clk, interval_s=1.0, **kw
        )
        return m, t

    def _observe(self, m, good=0, bad=0):
        h = m.histogram("batch_latency_s")
        for _ in range(good):
            h.observe(0.001)
        for _ in range(bad):
            h.observe(0.1)

    def test_inert_without_deadline(self, monkeypatch):
        monkeypatch.delenv("FJT_SLO_TARGET_MS", raising=False)
        m = MetricsRegistry()
        t = slo.SLOTracker(m, deadline_s=None)
        assert not t.enabled
        assert t.maybe_tick() is None and t.tick() is None
        assert t.health() == {}
        assert "slo_ok" not in m.struct_snapshot()["gauges"]

    def test_breach_and_clear_transitions(self):
        """The promote/clear drill: all-good baseline → a fast burn
        breaches (flight event, slo_ok 0, counter), recovery clears
        (flight event, slo_ok 1) — and the breach needed EVERY
        evaluable window over threshold."""
        clk = FakeClock(1000.0)
        m, t = self._tracker(clk)
        ev0 = len(recorder.events())
        self._observe(m, good=100)
        t.tick()  # baseline frame; no window evaluable yet
        assert not t.breached
        clk.advance(6.0)  # ≥ half the 10s window: cold-start fallback
        self._observe(m, bad=100)
        out = t.tick()
        assert out["transition"] == "breach" and t.breached
        snap = m.struct_snapshot()
        assert snap["gauges"]["slo_ok"]["value"] == 0.0
        assert snap["counters"]["slo_breaches"] == 1
        assert snap["gauges"]['slo_burn_rate{window="10"}']["value"] > 2.0
        kinds = [e["kind"] for e in recorder.events()[ev0:]]
        assert "slo_breach" in kinds and "slo_clear" not in kinds
        assert t.health()["slo"]["ok"] is False
        # recovery: a flood of good observations drains the burn
        clk.advance(6.0)
        self._observe(m, good=2000)
        out = t.tick()
        assert out["transition"] == "clear" and not t.breached
        snap = m.struct_snapshot()
        assert snap["gauges"]["slo_ok"]["value"] == 1.0
        assert snap["counters"]["slo_breaches"] == 1  # transitions, not ticks
        kinds = [e["kind"] for e in recorder.events()[ev0:]]
        assert "slo_clear" in kinds
        assert t.health()["slo"]["ok"] is True

    def test_multi_window_and_semantics(self):
        """A short-window blip alone must NOT breach once the long
        window is evaluable and healthy — the whole point of the
        multi-window shape."""
        clk = FakeClock(0.0)
        m, t = self._tracker(clk, windows=((10.0, 2.0), (60.0, 1.5)))
        self._observe(m, good=10_000)
        t.tick()
        # make both windows evaluable with a healthy history
        for _ in range(7):
            clk.advance(10.0)
            self._observe(m, good=100)
            t.tick()
        # a blip: 50 bad in the last 10s window (short burn ~4.5x > 2,
        # long burn over 1100 obs ~0.45 < 1.5)
        clk.advance(10.0)
        self._observe(m, good=50, bad=50)
        out = t.tick()
        assert out["burns"][10.0] > 2.0  # short window IS violating
        assert out["burns"][60.0] < 1.5
        assert not out["breached"]  # the long window held the page back

    def test_maybe_tick_rate_limit(self):
        clk = FakeClock(5.0)
        m, t = self._tracker(clk)
        self._observe(m, good=10)
        assert t.maybe_tick() is not None
        assert t.maybe_tick() is None  # same instant
        clk.advance(1.01)
        assert t.maybe_tick() is not None

    def test_health_fn_composes(self):
        clk = FakeClock(0.0)
        _, t = self._tracker(clk)
        fn = t.health_fn(lambda: {"ok": True, "depth": 2})
        out = fn()
        assert out["ok"] is True and out["depth"] == 2
        assert out["slo"]["deadline_ms"] == 10.0

    def test_env_window_parsing(self, monkeypatch):
        monkeypatch.setenv("FJT_SLO_WINDOWS", "5:10,60:2,junk,0:3")
        assert slo._env_windows() == ((5.0, 10.0), (60.0, 2.0))
        monkeypatch.setenv("FJT_SLO_WINDOWS", "all junk")
        assert slo._env_windows() == slo._DEFAULT_WINDOWS


# ---------------------------------------------------------------------------
# Buffered span writer: bounded crash loss
# ---------------------------------------------------------------------------


def _span_events(path):
    raw = open(path, encoding="utf-8").read()
    return json.loads(raw.rstrip().rstrip(",") + "]")


class TestSpanBuffering:
    def test_crash_loss_bounded_at_buffer_events(self, tmp_path):
        """The contract the buffered writer trades on: an abrupt kill
        loses at most ``buffer_events`` events — everything before the
        last buffer fill is already on disk."""
        w = spans.SpanWriter(
            str(tmp_path / "t.trace.json"),
            buffer_events=8, flush_interval_s=1e9,
        )
        for i in range(7):
            w.emit("s", float(i), 0.001)
        assert _span_events(w.path) == []  # buffered, none on disk yet
        w.emit("s", 7.0, 0.001)  # 8th fills the buffer → flush
        assert len(_span_events(w.path)) == 8
        for i in range(30):
            w.emit("s", float(8 + i), 0.001)
        # a crash NOW loses only what's in the buffer: < buffer_events
        on_disk = len(_span_events(w.path))
        assert 38 - on_disk < 8
        w.flush()
        assert len(_span_events(w.path)) == 38
        w.close()

    def test_interval_flush(self, tmp_path):
        w = spans.SpanWriter(
            str(tmp_path / "t.trace.json"),
            buffer_events=10_000, flush_interval_s=0.0,
        )
        w.emit("s", 0.0, 0.001)
        assert len(_span_events(w.path)) == 1  # interval 0: every emit
        w.close()

    def test_close_flushes(self, tmp_path):
        w = spans.SpanWriter(
            str(tmp_path / "t.trace.json"),
            buffer_events=100, flush_interval_s=1e9,
        )
        w.emit("s", 0.0, 0.001)
        w.close()
        assert len(_span_events(w.path)) == 1

    def test_flight_dump_flushes_spans(self, tmp_path, monkeypatch):
        """The postmortem contract: a flight-recorder dump flushes the
        buffered span writer so the trace file ends at the dump."""
        monkeypatch.setenv("FJT_TRACE_DIR", str(tmp_path))
        monkeypatch.setattr(spans, "_writer", None)
        monkeypatch.setattr(spans, "_writer_dir", None)
        try:
            spans.emit("pre_crash", 1.0, 0.5)
            w = spans.writer()
            r = recorder.FlightRecorder()
            r.record("worker_death", pid=123)
            assert r.dump(path=str(tmp_path / "f.jsonl")) is not None
            names = [e["name"] for e in _span_events(w.path)]
            assert "pre_crash" in names
        finally:
            spans._writer.close()
            monkeypatch.setattr(spans, "_writer", None)

    def test_module_flush_without_writer_is_noop(self, monkeypatch):
        monkeypatch.setattr(spans, "_writer", None)
        monkeypatch.delenv("FJT_TRACE_DIR", raising=False)
        spans.flush()  # must not create a writer or raise
        assert spans._writer is None


# ---------------------------------------------------------------------------
# fjt-top
# ---------------------------------------------------------------------------


class TestFjtTop:
    def _struct(self):
        m = MetricsRegistry()
        led = attr.StageLedger(m)
        for _ in range(20):
            led.observe("sink", 0.001)
        led.observe("readback", 0.08)
        m.gauge("device_mfu").set(0.058)
        m.gauge("device_membw_util").set(0.0001)
        m.gauge("device_ns_per_record").set(920.0)
        m.gauge("slo_ok").set(1.0)
        m.gauge('slo_burn_rate{window="300"}').set(0.25)
        return m.struct_snapshot()

    def test_renders_struct_dump(self, tmp_path, capsys):
        from flink_jpmml_tpu.cli import top_main

        dump = tmp_path / "varz.json"
        dump.write_text(json.dumps(self._struct()))
        assert top_main([str(dump)]) == 0
        out = capsys.readouterr().out
        assert "readback" in out and "sink" in out
        # ranked by total: readback (80ms) above sink (20ms)
        assert out.index("readback") < out.index("sink")
        assert "mfu   5.80%" in out
        assert "slo      OK" in out and "300s: 0.25x" in out

    def test_renders_bench_artifact_and_varz_mapping(self, tmp_path, capsys):
        from flink_jpmml_tpu.cli import top_main

        s = self._struct()
        # a /varz-style {label: struct} mapping: aggregate + one worker
        dump = tmp_path / "fleet.json"
        dump.write_text(json.dumps({"": s, "w0": s}))
        assert top_main([str(dump)]) == 0
        out = capsys.readouterr().out
        assert "== aggregate ==" in out and "== w0 ==" in out
        assert top_main([str(dump), "--worker", "w0"]) == 0
        out = capsys.readouterr().out
        assert "== w0 ==" in out and "== aggregate ==" not in out
        # a bench artifact embedding varz, incl. the driver's
        # {"parsed": <bench line>} wrapper form
        art = tmp_path / "BENCH.json"
        art.write_text(json.dumps({"metric": "x", "varz": s}))
        assert top_main([str(art)]) == 0
        out = capsys.readouterr().out
        assert "sink" in out
        # the headline varz struct renders ONCE, as the aggregate —
        # not a second time under a bogus "varz" label
        assert "== aggregate ==" in out and "== varz ==" not in out
        wrapped = tmp_path / "BENCH_r9.json"
        wrapped.write_text(
            json.dumps({"rc": 0, "parsed": {"metric": "x", "varz": s}})
        )
        assert top_main([str(wrapped)]) == 0
        assert "sink" in capsys.readouterr().out

    def test_empty_struct_says_so(self, tmp_path, capsys):
        from flink_jpmml_tpu.cli import top_main

        dump = tmp_path / "varz.json"
        dump.write_text(json.dumps({"counters": {}, "histograms": {}}))
        assert top_main([str(dump)]) == 0
        assert "no stage attribution" in capsys.readouterr().out

    def test_rejects_garbage(self, tmp_path):
        from flink_jpmml_tpu.cli import top_main

        p = tmp_path / "nope.json"
        p.write_text("[1, 2]")
        with pytest.raises(SystemExit):
            top_main([str(p)])
        with pytest.raises(SystemExit):
            top_main([str(tmp_path / "missing.json")])


class TestFjtTopFreshness:
    """The --freshness panel (ISSUE 7): obs/freshness.py +
    obs/pressure.py rendered as one operator view."""

    def _struct(self, diverging=False):
        m = MetricsRegistry()
        m.gauge("pressure").set(0.72)
        m.gauge("pressure_ring").set(0.72)
        m.gauge("pressure_window").set(0.10)
        m.gauge("pressure_wait").set(0.05)
        m.counter("pressure_breaches").inc(2)
        m.gauge("lag_drain_eta_s").set(12.5)
        m.gauge("lag_trend").set(-340.0)
        m.gauge("lag_diverging").set(1.0 if diverging else 0.0)
        m.gauge("watermark_ts").set(1_700_000_000.0)
        m.gauge('watermark_lag_s{partition="0"}').set(1.25)
        m.gauge('watermark_lag_s{partition="1"}').set(0.4)
        m.gauge('kafka_lag{partition="0"}').set(5000.0)
        m.gauge('kafka_lag_age_s{partition="0"}').set(0.3)
        h = m.histogram("record_staleness_s")
        for v in (0.5, 0.8, 1.4, 2.0):
            h.observe(v)
        return m.struct_snapshot()

    def test_renders_panel(self, tmp_path, capsys):
        from flink_jpmml_tpu.cli import top_main

        dump = tmp_path / "varz.json"
        dump.write_text(json.dumps(self._struct()))
        assert top_main([str(dump), "--freshness"]) == 0
        out = capsys.readouterr().out
        assert "freshness" in out
        assert "pressure  0.72" in out
        assert "ring 0.72" in out and "breaches 2" in out
        assert "eta 12.5s" in out and "-340.0 rec/s" in out
        assert "stale" in out and "p99" in out
        # per-partition table: both partitions, missing cells dashed
        assert re.search(r"^0\s+1\.250\s+5,000\s+0\.3$", out, re.M)
        assert re.search(r"^1\s+0\.400\s+-\s+-$", out, re.M)

    def test_diverging_renders_loudly(self, tmp_path, capsys):
        from flink_jpmml_tpu.cli import top_main

        dump = tmp_path / "varz.json"
        dump.write_text(json.dumps(self._struct(diverging=True)))
        assert top_main([str(dump), "--freshness"]) == 0
        out = capsys.readouterr().out
        assert "DIVERGING" in out
        assert "12.5s" not in out  # a frozen ETA must not read as live

    def test_empty_struct_says_so(self, tmp_path, capsys):
        from flink_jpmml_tpu.cli import top_main

        dump = tmp_path / "varz.json"
        dump.write_text(json.dumps({"counters": {}, "gauges": {}}))
        assert top_main([str(dump), "--freshness"]) == 0
        assert "no freshness telemetry" in capsys.readouterr().out

    def test_fleet_mapping_renders_each_source(self, tmp_path, capsys):
        from flink_jpmml_tpu.cli import top_main

        s = self._struct()
        dump = tmp_path / "fleet.json"
        dump.write_text(json.dumps({"": s, "w0": s}))
        assert top_main([str(dump), "--freshness"]) == 0
        out = capsys.readouterr().out
        assert "== aggregate · freshness ==" in out
        assert "== w0 · freshness ==" in out


class TestFjtTopWatch:
    """--watch N: the operator-console loop re-renders from a live
    source and retries through fetch failures instead of exiting."""

    def _interrupt_after(self, monkeypatch, n):
        import time as time_mod

        calls = {"n": 0}

        def fake_sleep(secs):
            calls["n"] += 1
            if calls["n"] >= n:
                raise KeyboardInterrupt

        monkeypatch.setattr(time_mod, "sleep", fake_sleep)

    def test_watch_rerenders_until_interrupted(
        self, tmp_path, capsys, monkeypatch
    ):
        from flink_jpmml_tpu.cli import top_main

        m = MetricsRegistry()
        attr.StageLedger(m).observe("sink", 0.002)
        dump = tmp_path / "varz.json"
        dump.write_text(json.dumps(m.struct_snapshot()))
        self._interrupt_after(monkeypatch, 2)
        with pytest.raises(KeyboardInterrupt):
            top_main([str(dump), "--watch", "0.01"])
        out = capsys.readouterr().out
        assert out.count("sink") >= 2  # rendered once per cycle

    def test_watch_retries_through_fetch_failures(
        self, tmp_path, capsys, monkeypatch
    ):
        from flink_jpmml_tpu.cli import top_main

        self._interrupt_after(monkeypatch, 2)
        with pytest.raises(KeyboardInterrupt):
            top_main([str(tmp_path / "gone.json"), "--watch", "0.01"])
        err = capsys.readouterr().err
        assert "retrying" in err  # noted, not fatal — twice
        assert err.count("retrying") == 2

    def test_watch_retries_missing_worker_label(
        self, tmp_path, capsys, monkeypatch
    ):
        from flink_jpmml_tpu.cli import top_main

        m = MetricsRegistry()
        attr.StageLedger(m).observe("sink", 0.002)
        dump = tmp_path / "fleet.json"
        dump.write_text(json.dumps({"": m.struct_snapshot()}))
        self._interrupt_after(monkeypatch, 1)
        with pytest.raises(KeyboardInterrupt):
            top_main([str(dump), "--watch", "0.01", "--worker", "w9"])
        err = capsys.readouterr().err
        assert "w9" in err and "retrying" in err

    def test_watch_validation(self, tmp_path):
        from flink_jpmml_tpu.cli import top_main

        with pytest.raises(SystemExit):
            top_main([str(tmp_path / "x.json"), "--watch", "0"])
        with pytest.raises(SystemExit):
            top_main([str(tmp_path / "x.json"), "--watch", "-2"])

    def test_watermark_only_struct_renders_without_fallback(
        self, tmp_path, capsys
    ):
        from flink_jpmml_tpu.cli import top_main

        m = MetricsRegistry()
        m.gauge("watermark_ts").set(1_700_000_000.0)
        dump = tmp_path / "varz.json"
        dump.write_text(json.dumps(m.struct_snapshot()))
        assert top_main([str(dump), "--freshness"]) == 0
        out = capsys.readouterr().out
        assert "low-watermark" in out
        assert "no freshness telemetry" not in out

    def test_empty_staleness_histogram_is_not_telemetry(
        self, tmp_path, capsys
    ):
        """freshness_for registers record_staleness_s eagerly; an
        all-empty registry that merely touched the tracker must still
        say 'no freshness telemetry' (review finding, pinned)."""
        from flink_jpmml_tpu.cli import top_main
        from flink_jpmml_tpu.obs.freshness import freshness_for

        m = MetricsRegistry()
        freshness_for(m)  # registers the (empty) staleness histogram
        dump = tmp_path / "varz.json"
        dump.write_text(json.dumps(m.struct_snapshot()))
        assert top_main([str(dump), "--freshness"]) == 0
        assert "no freshness telemetry" in capsys.readouterr().out
