"""Device-fault resilience & degraded-mode serving (ISSUE 15).

The contracts under test:

- classification (runtime/devfault.py): injected device faults and
  XLA runtime errors classify into the OOM / transient / chip-loss
  fault kinds; record poison NEVER classifies as a device fault;
- the recovery ladder on both hot paths: transient errors re-dispatch
  the host-retained staging copy, OOM bisects the BATCH SIZE and feeds
  the AdaptiveBatcher cap, persistent streaks trip the circuit breaker
  onto the host fallback tier, chip loss escalates;
- the headline pin: a sick device never quarantines clean records —
  the DLQ stays empty under device faults, while genuine poison still
  lands there exactly;
- checkpoint ENOSPC degrade: a full disk suspends checkpointing
  (gauge + flight events) and serving continues; space returning
  resumes the cadence automatically;
- degraded mesh (parallel/): a data×model mesh minus one chip rebuilds
  over the survivors with identical predictions — testable in tier-1
  thanks to the conftest's 8-device virtual CPU mesh.
"""

import os
import time

import numpy as np
import pytest

from flink_jpmml_tpu.runtime import devfault, faults
from flink_jpmml_tpu.serving import failover as failover_mod
from flink_jpmml_tpu.utils.metrics import MetricsRegistry


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.clear()
    yield
    faults.clear()


@pytest.fixture(autouse=True)
def _fast_ladder(monkeypatch):
    """Fast retry/breaker geometry: the ladders' sleeps must not
    dominate the tier-1 wall clock."""
    monkeypatch.setenv("FJT_RETRY_BASE_S", "0.005")
    monkeypatch.setenv("FJT_FAILOVER_COOLDOWN_S", "0.05")
    monkeypatch.setenv("FJT_FAILOVER_GREENS", "1")


@pytest.fixture(scope="module")
def gbm(tmp_path_factory):
    from flink_jpmml_tpu.assets_gen import gen_gbm
    from flink_jpmml_tpu.compile import compile_pmml
    from flink_jpmml_tpu.pmml import parse_pmml_file

    tmp = tmp_path_factory.mktemp("devfault-gbm")
    pmml = gen_gbm(str(tmp), n_trees=4, depth=3, n_features=5)
    return compile_pmml(parse_pmml_file(pmml), batch_size=32)


def _data(n, seed=0, cols=5):
    rng = np.random.default_rng(seed)
    return rng.normal(0, 1.0, size=(n, cols)).astype(np.float32)


def _block_pipe(gbm, sink, tmp_path, metrics=None, ckpt=True, **kw):
    from flink_jpmml_tpu.runtime.block import BlockPipeline
    from flink_jpmml_tpu.runtime.checkpoint import CheckpointManager
    from flink_jpmml_tpu.utils.config import BatchConfig, RuntimeConfig

    return BlockPipeline(
        kw.pop("source"), gbm, sink,
        RuntimeConfig(
            batch=BatchConfig(size=32, deadline_us=500),
            checkpoint_interval_s=kw.pop("ckpt_interval", 0.05),
        ),
        metrics=metrics or MetricsRegistry(),
        checkpoint=(
            CheckpointManager(str(tmp_path / "ck")) if ckpt else None
        ),
        use_native=False,
        **kw,
    )


def _coverage(emitted, n):
    cov = np.zeros(n, np.int64)
    for off, cnt in emitted:
        cov[off: off + cnt] += 1
    return cov


# ---------------------------------------------------------------------------
# classification
# ---------------------------------------------------------------------------


class TestClassify:
    def test_injected_kinds(self):
        assert devfault.classify(faults.InjectedDeviceOOM()) == (
            devfault.KIND_OOM
        )
        assert devfault.classify(faults.InjectedDeviceError()) == (
            devfault.KIND_ERROR
        )
        assert devfault.classify(faults.InjectedChipLoss()) == (
            devfault.KIND_LOST
        )

    def test_record_poison_never_classifies(self):
        assert devfault.classify(ValueError("bad record")) is None
        assert devfault.classify(
            faults.InjectedPoisonRecord([7])
        ) is None
        assert devfault.classify(KeyError("x")) is None
        # a host MemoryError is not a DEVICE fault
        assert devfault.classify(MemoryError()) is None

    def test_real_xla_runtime_errors(self):
        from jax.errors import JaxRuntimeError

        assert devfault.classify(
            JaxRuntimeError(
                "RESOURCE_EXHAUSTED: Out of memory allocating "
                "1073741824 bytes"
            )
        ) == devfault.KIND_OOM
        assert devfault.classify(
            JaxRuntimeError("INTERNAL: Failed to execute XLA runtime")
        ) == devfault.KIND_ERROR
        assert devfault.classify(
            JaxRuntimeError("UNAVAILABLE: device lost: core halted")
        ) == devfault.KIND_LOST

    def test_mosaic_compile_refusal_classifies_as_device_error(self):
        # the compiler's own sentence from the v5e (PR 21, a 512-row
        # score block). Today it lands in the TRANSIENT kind — a
        # deterministic compile refusal that the ladder will retry and
        # then serve from the fallback tier; recorded, not endorsed
        from jax.errors import JaxRuntimeError

        assert devfault.classify(
            JaxRuntimeError(
                "INVALID_ARGUMENT: Mosaic failed to compile TPU kernel: "
                "Failed to verify layout for Mosaic kernel operand 9: "
                "XLA layout ({0:T(1024)S(1)}) does not match Mosaic "
                "layout ({0:T(512)S(1)}) for an operand of shape "
                "f32[16384]."
            )
        ) == devfault.KIND_ERROR


# ---------------------------------------------------------------------------
# the circuit breaker
# ---------------------------------------------------------------------------


class TestCircuitBreaker:
    def test_lifecycle(self):
        clock = {"t": 0.0}
        m = MetricsRegistry()
        b = failover_mod.CircuitBreaker(
            m, key="m1", fail_threshold=3, cooldown_s=1.0,
            probe_greens=2, clock=lambda: clock["t"],
        )
        g = m.gauge('failover_state{model="m1"}')
        assert b.allow_dispatch()
        b.record_failure()
        b.record_failure()
        assert b.state == failover_mod.STATE_CLOSED
        b.record_failure()  # third consecutive: OPEN
        assert b.state == failover_mod.STATE_OPEN
        assert g.get() == failover_mod.STATE_OPEN
        assert not b.allow_dispatch()  # cooldown pending
        clock["t"] = 1.5
        assert b.allow_dispatch()  # flips half-open: this is a probe
        assert b.state == failover_mod.STATE_HALF_OPEN
        b.record_success()
        assert b.state == failover_mod.STATE_HALF_OPEN  # 1 of 2 greens
        b.record_success()
        assert b.state == failover_mod.STATE_CLOSED  # promoted back
        assert g.get() == failover_mod.STATE_CLOSED

    def test_probe_failure_reopens(self):
        clock = {"t": 0.0}
        b = failover_mod.CircuitBreaker(
            None, fail_threshold=1, cooldown_s=1.0, probe_greens=2,
            clock=lambda: clock["t"],
        )
        b.record_failure()
        assert b.state == failover_mod.STATE_OPEN
        clock["t"] = 1.5
        assert b.allow_dispatch()
        b.record_success()  # one green...
        b.record_failure()  # ...then the probe fails: re-open
        assert b.state == failover_mod.STATE_OPEN
        assert not b.allow_dispatch()  # cooldown restarted at t=1.5
        clock["t"] = 3.0
        assert b.allow_dispatch()
        b.record_success()
        b.record_success()
        assert b.state == failover_mod.STATE_CLOSED

    def test_success_streak_clears_strikes(self):
        b = failover_mod.CircuitBreaker(None, fail_threshold=2)
        b.record_failure()
        b.record_success()  # streak broken
        b.record_failure()
        assert b.state == failover_mod.STATE_CLOSED


class TestAdaptiveBatcherOOMCap:
    def test_cap_applies_without_deadline(self):
        from flink_jpmml_tpu.serving.overload import AdaptiveBatcher

        b = AdaptiveBatcher(metrics=MetricsRegistry(), min_records=16)
        assert b.max_records() is None  # no deadline, no cap
        assert b.note_oom_cap(128) == 128
        assert b.max_records() == 128
        # the cap only ever shrinks
        assert b.note_oom_cap(256) == 128
        assert b.note_oom_cap(64) == 64
        assert b.max_records() == 64
        # min_records floors it
        assert b.note_oom_cap(1) == 16


# ---------------------------------------------------------------------------
# the fallback tier
# ---------------------------------------------------------------------------


class TestFallbackTier:
    def test_rank_wire_parity(self, gbm):
        from flink_jpmml_tpu.runtime.block import BoundScorer

        bound = BoundScorer("static", gbm, use_quantized=True)
        assert bound.q is not None and bound.q.backend == "xla"
        tier = failover_mod.FallbackTier()
        assert tier.supports(bound)
        X = _data(32, seed=3)
        out_host = tier.score_bound(bound, X)
        device = bound.q.score(X)
        host = bound.q.decode(out_host, 32)
        assert [p.score.value for p in host] == pytest.approx(
            [p.score.value for p in device]
        )

    def test_f32_parity(self, gbm):
        from flink_jpmml_tpu.runtime.block import BoundScorer

        bound = BoundScorer("static", gbm, use_quantized=False)
        assert bound.q is None
        tier = failover_mod.FallbackTier()
        assert tier.supports(bound)
        X = _data(32, seed=4)
        out_host = tier.score_bound(bound, X)
        host = bound.decode(out_host, 32)
        M = np.zeros_like(X, bool)
        device = gbm.decode(gbm.predict(X, M), 32)
        assert [p.score.value for p in host] == pytest.approx(
            [p.score.value for p in device]
        )

    def test_pallas_unsupported(self, gbm):
        class FakePallasBound:
            class q:
                backend = "pallas"

        tier = failover_mod.FallbackTier()
        assert not tier.supports(FakePallasBound())
        with pytest.raises(failover_mod.FallbackUnavailable):
            tier.score_bound(FakePallasBound(), _data(4))


# ---------------------------------------------------------------------------
# block-path recovery ladder
# ---------------------------------------------------------------------------


class TestBlockLadder:
    def test_transient_error_redispatches_no_quarantine(
        self, gbm, tmp_path
    ):
        from flink_jpmml_tpu.runtime.block import FiniteBlockSource
        from flink_jpmml_tpu.runtime.dlq import DeadLetterQueue

        N = 640
        emitted = []
        faults.inject("device_error", site="device_readback", n=1)
        m = MetricsRegistry()
        pipe = _block_pipe(
            gbm, lambda o, n, f: emitted.append((f, n)), tmp_path,
            metrics=m, source=FiniteBlockSource(_data(N), 32),
            max_dispatch_chunks=1,
        )
        pipe.run_until_exhausted(timeout=60)
        cov = _coverage(emitted, N)
        assert (cov == 1).all()
        c = m.struct_snapshot()["counters"]
        assert c.get("redispatch_records", 0) >= 32
        assert c.get('device_fault_total{kind="device_error"}', 0) >= 1
        assert c.get("fallback_records", 0) == 0  # ladder step 1 won
        assert list(
            DeadLetterQueue(str(tmp_path / "ck" / "dlq")).offsets()
        ) == []

    def test_persistent_error_fails_over_then_recloses(
        self, gbm, tmp_path
    ):
        """The headline drill at test scale: a persistent device-error
        streak trips the breaker onto the fallback tier (serving
        continues), then green probes CLOSE the circuit again — pinned
        with an infinite source and deadline polling so CI load cannot
        race the breaker lifecycle."""
        from flink_jpmml_tpu.runtime.block import CyclingBlockSource
        from flink_jpmml_tpu.runtime.dlq import DeadLetterQueue

        emitted = []
        faults.inject("device_error", site="device_readback", n=7)
        faults.inject("dispatch_delay", delay_ms=2)
        m = MetricsRegistry()
        pipe = _block_pipe(
            gbm, lambda o, n, f: emitted.append((f, n)), tmp_path,
            metrics=m, source=CyclingBlockSource(_data(2048), 32),
            max_dispatch_chunks=1,
        )
        pipe.start()
        try:
            deadline = time.monotonic() + 30.0
            saw_open = False
            while time.monotonic() < deadline:
                if pipe._error is not None:
                    raise pipe._error
                g = m.struct_snapshot()["gauges"]
                state = g.get(
                    'failover_state{model="static"}', {}
                ).get("value")
                if state == failover_mod.STATE_OPEN:
                    saw_open = True
                if saw_open and state == failover_mod.STATE_CLOSED:
                    break
                time.sleep(0.01)
        finally:
            pipe.stop()
            pipe.join(timeout=30)
        assert saw_open, "circuit never opened"
        g = m.struct_snapshot()["gauges"]
        assert g['failover_state{model="static"}']["value"] == (
            failover_mod.STATE_CLOSED
        ), "circuit did not re-close after the outage"
        c = m.struct_snapshot()["counters"]
        assert c.get("fallback_records", 0) > 0
        # zero loss, in-order, no duplication across the whole window
        offs = [o for o, _ in emitted]
        assert offs == sorted(offs)
        cov = _coverage(emitted, int(pipe.committed_offset))
        assert (cov[: int(pipe.committed_offset)] == 1).all()
        assert list(
            DeadLetterQueue(str(tmp_path / "ck" / "dlq")).offsets()
        ) == []

    def test_oom_bisects_and_feeds_the_batcher(self, gbm, tmp_path):
        from flink_jpmml_tpu.runtime.block import FiniteBlockSource
        from flink_jpmml_tpu.runtime.dlq import DeadLetterQueue
        from flink_jpmml_tpu.serving.overload import AdaptiveBatcher

        N = 1280
        emitted = []
        # a 3-deep OOM streak: the full aggregate fails, the redispatch
        # fails, one half fails — the bisection must actually split
        faults.inject("device_oom", site="device_dispatch", n=3)
        m = MetricsRegistry()
        batcher = AdaptiveBatcher(metrics=m, min_records=16)
        pipe = _block_pipe(
            gbm, lambda o, n, f: emitted.append((f, n)), tmp_path,
            metrics=m, source=FiniteBlockSource(_data(N), 32),
            batcher=batcher, max_dispatch_chunks=4,
        )
        pipe.run_until_exhausted(timeout=60)
        cov = _coverage(emitted, N)
        assert (cov == 1).all()
        c = m.struct_snapshot()["counters"]
        assert c.get("oom_shrinks", 0) >= 1
        assert c.get('device_fault_total{kind="device_oom"}', 0) >= 1
        assert batcher.max_records() is not None  # standing cap
        assert list(
            DeadLetterQueue(str(tmp_path / "ck" / "dlq")).offsets()
        ) == []

    def test_chip_loss_escalates(self, gbm, tmp_path):
        from flink_jpmml_tpu.runtime.block import FiniteBlockSource

        faults.inject("chip_loss", n=1)
        pipe = _block_pipe(
            gbm, lambda o, n, f: None, tmp_path,
            source=FiniteBlockSource(_data(320), 32),
            max_dispatch_chunks=1,
        )
        with pytest.raises(faults.InjectedChipLoss):
            pipe.run_until_exhausted(timeout=60)

    def test_poison_still_quarantines_exactly_beside_device_faults(
        self, gbm, tmp_path
    ):
        """Composition pin: genuine record poison lands in the DLQ
        exactly while concurrent device errors land NOWHERE."""
        from flink_jpmml_tpu.runtime.block import FiniteBlockSource
        from flink_jpmml_tpu.runtime.dlq import DeadLetterQueue

        N = 640
        emitted = []
        faults.inject("poison_record", offset=100)
        faults.inject("device_error", site="device_readback", n=4)
        pipe = _block_pipe(
            gbm, lambda o, n, f: emitted.append((f, n)), tmp_path,
            source=FiniteBlockSource(_data(N), 32),
            max_dispatch_chunks=1,
        )
        pipe.run_until_exhausted(timeout=60)
        dlq = sorted(set(
            DeadLetterQueue(str(tmp_path / "ck" / "dlq")).offsets()
        ))
        assert dlq == [100]
        cov = _coverage(emitted, N)
        assert (cov[:100] == 1).all() and (cov[101:] == 1).all()
        assert cov[100] == 0  # quarantined, never sunk

    def test_poison_during_open_circuit_isolates_on_the_tier(
        self, gbm, tmp_path
    ):
        """An OPEN circuit must not exempt poison from the DLQ
        contract: the fallback tier fires the same score_batch site
        and the suspect scan bisects ON the tier."""
        from flink_jpmml_tpu.runtime.block import FiniteBlockSource
        from flink_jpmml_tpu.runtime.dlq import DeadLetterQueue

        N = 640
        emitted = []
        # enough fires that the circuit is open when offset 320's
        # batch arrives on the fallback path
        faults.inject("device_error", site="device_readback", n=50)
        faults.inject("poison_record", offset=320)
        pipe = _block_pipe(
            gbm, lambda o, n, f: emitted.append((f, n)), tmp_path,
            source=FiniteBlockSource(_data(N), 32),
            max_dispatch_chunks=1,
        )
        pipe.run_until_exhausted(timeout=60)
        dlq = sorted(set(
            DeadLetterQueue(str(tmp_path / "ck" / "dlq")).offsets()
        ))
        assert dlq == [320]
        cov = _coverage(emitted, N)
        assert cov[320] == 0
        assert (np.delete(cov, 320) == 1).all()

    def test_fail_fast_without_plane(self, gbm, tmp_path):
        """No DLQ, no FJT_FAILOVER: the historical contract — a device
        error kills the worker (the supervisor's jurisdiction)."""
        from flink_jpmml_tpu.runtime.block import FiniteBlockSource

        assert not os.environ.get("FJT_FAILOVER")
        faults.inject("device_error", site="device_readback", n=1)
        pipe = _block_pipe(
            gbm, lambda o, n, f: None, tmp_path, ckpt=False,
            source=FiniteBlockSource(_data(320), 32),
            max_dispatch_chunks=1,
        )
        assert pipe._failover is None
        with pytest.raises(faults.InjectedDeviceError):
            pipe.run_until_exhausted(timeout=60)


# ---------------------------------------------------------------------------
# record-path (engine) ladder
# ---------------------------------------------------------------------------


def _record_pipe(gbm, records, tmp_path=None, metrics=None):
    from flink_jpmml_tpu.runtime.checkpoint import CheckpointManager
    from flink_jpmml_tpu.runtime.engine import Pipeline, StaticScorer
    from flink_jpmml_tpu.runtime.sinks import CollectSink
    from flink_jpmml_tpu.runtime.sources import InMemorySource
    from flink_jpmml_tpu.utils.config import BatchConfig, RuntimeConfig

    sink = CollectSink()
    pipe = Pipeline(
        InMemorySource(records),
        StaticScorer(gbm),
        sink,
        RuntimeConfig(
            batch=BatchConfig(size=16, deadline_us=500),
            checkpoint_interval_s=0.05,
        ),
        metrics=metrics or MetricsRegistry(),
        checkpoint=(
            CheckpointManager(str(tmp_path / "ck"))
            if tmp_path is not None else None
        ),
    )
    return pipe, sink


class TestEngineLadder:
    def test_transient_error_redispatches(self, gbm, tmp_path):
        records = [list(map(float, row)) for row in _data(96, seed=7)]
        faults.inject("device_error", site="device_readback", n=1)
        m = MetricsRegistry()
        pipe, sink = _record_pipe(
            gbm, records, tmp_path=tmp_path, metrics=m
        )
        pipe.run_until_exhausted(timeout=60)
        assert len(sink.items) == 96
        c = m.struct_snapshot()["counters"]
        assert c.get("redispatch_records", 0) >= 1
        assert c.get('device_fault_total{kind="device_error"}', 0) >= 1

    def test_unarmed_record_path_fails_fast(self, gbm):
        """No DLQ, no FJT_FAILOVER: the record path keeps the
        historical contract too — a device error kills the worker."""
        records = [list(map(float, row)) for row in _data(48, seed=15)]
        faults.inject("device_error", site="device_readback", n=1)
        pipe, _sink = _record_pipe(gbm, records)
        with pytest.raises(faults.InjectedDeviceError):
            pipe.run_until_exhausted(timeout=60)

    def test_device_error_never_quarantines(self, gbm, tmp_path):
        from flink_jpmml_tpu.runtime.dlq import DeadLetterQueue

        records = [list(map(float, row)) for row in _data(96, seed=8)]
        faults.inject("device_error", site="device_readback", n=1)
        pipe, sink = _record_pipe(gbm, records, tmp_path=tmp_path)
        pipe.run_until_exhausted(timeout=60)
        assert len(sink.items) == 96
        assert list(
            DeadLetterQueue(str(tmp_path / "ck" / "dlq")).offsets()
        ) == []

    def test_oom_bisects_below_half(self, gbm, tmp_path):
        """A device that only fits a QUARTER of the micro-batch must
        still converge (size halves per OOM seen, and halvings don't
        spend the transient-retry budget)."""
        records = [list(map(float, row)) for row in _data(64, seed=9)]
        # 3 OOMs: full batch, the half, the quarter — success at 1/8
        faults.inject("device_oom", site="device_readback", n=3)
        m = MetricsRegistry()
        pipe, sink = _record_pipe(
            gbm, records, tmp_path=tmp_path, metrics=m
        )
        pipe.run_until_exhausted(timeout=60)
        assert len(sink.items) == 64
        assert m.struct_snapshot()["counters"].get(
            'device_fault_total{kind="device_oom"}', 0
        ) >= 2

    def test_chip_loss_escalates(self, gbm, tmp_path):
        records = [list(map(float, row)) for row in _data(64, seed=10)]
        faults.inject("chip_loss", n=1)
        pipe, sink = _record_pipe(gbm, records, tmp_path=tmp_path)
        with pytest.raises(faults.InjectedChipLoss):
            pipe.run_until_exhausted(timeout=60)


class TestDynamicScorerRedispatch:
    def test_group_redispatch(self, tmp_path):
        import pathlib

        from flink_jpmml_tpu.models.control import AddMessage
        from flink_jpmml_tpu.runtime.sources import ControlSource
        from flink_jpmml_tpu.serving.scorer import DynamicScorer

        xml = """<PMML xmlns="http://www.dmg.org/PMML-4_3" version="4.3">
  <Header/>
  <DataDictionary numberOfFields="2">
    <DataField name="a" optype="continuous" dataType="double"/>
    <DataField name="y" optype="continuous" dataType="double"/>
  </DataDictionary>
  <RegressionModel functionName="regression">
    <MiningSchema>
      <MiningField name="y" usageType="target"/>
      <MiningField name="a"/>
    </MiningSchema>
    <RegressionTable intercept="3.5"/>
  </RegressionModel></PMML>"""
        p = pathlib.Path(tmp_path, "c.pmml")
        p.write_text(xml)
        ctrl = ControlSource()
        m = MetricsRegistry()
        sc = DynamicScorer(control=ctrl, batch_size=4, metrics=m)
        ctrl.push(AddMessage("m", 1, str(p), timestamp=1.0))
        out = sc.finish(sc.submit([("m", {"a": 0.0})]))
        assert out[0][0].score.value == pytest.approx(3.5)
        # now a transient device fault on the NEXT batch's readback
        faults.inject("device_error", site="device_readback", n=1)
        out = sc.finish(
            sc.submit([("m", {"a": 0.0}), ("m", {"a": 1.0})])
        )
        assert [p_.score.value for p_, _ in out] == pytest.approx(
            [3.5, 3.5]
        )
        c = m.struct_snapshot()["counters"]
        assert c.get("redispatch_records", 0) >= 2
        assert c.get('device_fault_total{kind="device_error"}', 0) >= 1


# ---------------------------------------------------------------------------
# checkpoint ENOSPC degrade
# ---------------------------------------------------------------------------


class TestCheckpointEnospcDegrade:
    def test_suspends_then_resumes(self, gbm, tmp_path, monkeypatch):
        from flink_jpmml_tpu.obs import recorder as flight
        from flink_jpmml_tpu.runtime.block import FiniteBlockSource

        monkeypatch.setenv("FJT_RETRY_MAX", "2")
        monkeypatch.setenv("FJT_RETRY_BASE_S", "0.001")
        N = 960
        # errno=28 (ENOSPC), persistent for the first 8 save attempts,
        # then "space returns": the plane must suspend, keep serving,
        # and resume without intervention
        faults.inject("checkpoint_fail", errno=28, n=8)
        emitted = []
        m = MetricsRegistry()
        pipe = _block_pipe(
            gbm, lambda o, n, f: emitted.append((f, n)), tmp_path,
            metrics=m, source=FiniteBlockSource(_data(N), 32),
            ckpt_interval=0.0,  # save every batch: fast convergence
            max_dispatch_chunks=1,
        )
        pipe.run_until_exhausted(timeout=60)
        cov = _coverage(emitted, N)
        assert (cov == 1).all()  # serving never stopped
        kinds = [e["kind"] for e in flight.events()]
        assert "checkpoint_suspended" in kinds
        assert "checkpoint_resumed" in kinds
        g = m.struct_snapshot()["gauges"]
        assert g.get("checkpoint_suspended", {}).get("value") == 0.0
        # the cadence resumed: a checkpoint landed with the final offset
        from flink_jpmml_tpu.runtime.checkpoint import CheckpointManager

        st = CheckpointManager(str(tmp_path / "ck")).load_latest()
        assert st is not None and int(st["source_offset"]) == N

    def test_non_enospc_still_raises(self, gbm, tmp_path, monkeypatch):
        from flink_jpmml_tpu.runtime.block import FiniteBlockSource
        from flink_jpmml_tpu.utils.exceptions import CheckpointException

        monkeypatch.setenv("FJT_RETRY_MAX", "2")
        monkeypatch.setenv("FJT_RETRY_BASE_S", "0.001")
        faults.inject("checkpoint_fail")  # persistent, no errno
        pipe = _block_pipe(
            gbm, lambda o, n, f: None, tmp_path,
            source=FiniteBlockSource(_data(320), 32),
            ckpt_interval=0.0, max_dispatch_chunks=1,
        )
        with pytest.raises(CheckpointException):
            pipe.run_until_exhausted(timeout=60)


# ---------------------------------------------------------------------------
# degraded mesh (the conftest's 8-device virtual CPU mesh)
# ---------------------------------------------------------------------------


class TestDegradedMesh:
    def test_dp_mesh_minus_one_chip(self, gbm):
        import jax

        from flink_jpmml_tpu.parallel.mesh import make_mesh
        from flink_jpmml_tpu.parallel.sharding import dp_sharded

        if len(jax.devices()) < 8:
            pytest.skip("needs the 8-device virtual CPU mesh")
        mesh = make_mesh()
        sm = dp_sharded(gbm, mesh)
        assert sm.batch_divisor == 8
        X = _data(28, seed=11)  # ≤ the compiled batch on both meshes
        want = [p.score.value for p in sm.score_dense(X)]
        degraded = sm.without_devices([mesh.devices.flat[3]])
        assert degraded.batch_divisor == 7
        lost_id = mesh.devices.flat[3].id
        assert all(
            d.id != lost_id for d in degraded.mesh.devices.flat
        )
        got = [p.score.value for p in degraded.score_dense(X)]
        assert got == pytest.approx(want)

    def test_tp_mesh_preserves_model_axis(self, gbm):
        import jax

        from flink_jpmml_tpu.parallel.mesh import MODEL_AXIS, make_mesh
        from flink_jpmml_tpu.parallel.sharding import (
            degraded_mesh, mesh_sharded,
        )
        from flink_jpmml_tpu.utils.config import MeshConfig

        if len(jax.devices()) < 8:
            pytest.skip("needs the 8-device virtual CPU mesh")
        mesh = make_mesh(MeshConfig(data=4, model=2))
        m2 = degraded_mesh(mesh, [mesh.devices.flat[0]])
        assert m2.shape[MODEL_AXIS] == 2
        assert m2.shape["data"] == 3  # 7 survivors // model 2
        sm = mesh_sharded(gbm, mesh)
        degraded = sm.without_devices([mesh.devices.flat[0]])
        assert degraded.mesh.shape["data"] == 3
        X = _data(24, seed=12)  # ≤ the compiled batch on both meshes
        want = [p.score.value for p in sm.score_dense(X)]
        got = [p.score.value for p in degraded.score_dense(X)]
        assert got == pytest.approx(want)

    def test_unsurvivable_mesh_raises(self):
        import jax

        from flink_jpmml_tpu.parallel.mesh import make_mesh
        from flink_jpmml_tpu.parallel.sharding import degraded_mesh
        from flink_jpmml_tpu.utils.exceptions import FlinkJpmmlTpuError

        if len(jax.devices()) < 8:
            pytest.skip("needs the 8-device virtual CPU mesh")
        mesh = make_mesh()
        with pytest.raises(FlinkJpmmlTpuError):
            degraded_mesh(mesh, list(mesh.devices.flat))

    def test_per_chip_metrics_merge_exactly(self):
        """The DrJAX discipline that makes degraded-mesh mode cheap:
        per-chip telemetry merges EXACTLY, so the fleet view of a
        7-chip mesh is just the merge over 7 structs — no
        rebaselining. Histogram buckets add bitwise."""
        from flink_jpmml_tpu.utils.metrics import merge_structs

        regs = [MetricsRegistry() for _ in range(8)]
        rng = np.random.default_rng(13)
        for r in regs:
            h = r.histogram("batch_latency_s")
            for v in rng.exponential(0.01, size=50):
                h.observe(float(v))
            r.counter("records_out").inc(100)
        full = merge_structs([r.struct_snapshot() for r in regs])
        minus_one = merge_structs(
            [r.struct_snapshot() for r in regs[:7]]
        )
        assert full["counters"]["records_out"] == 800
        assert minus_one["counters"]["records_out"] == 700
        # re-merging the lost chip's struct back restores the full
        # view bit-for-bit: merge is associative and lossless
        readded = merge_structs(
            [minus_one, regs[7].struct_snapshot()]
        )
        assert readded["histograms"]["batch_latency_s"] == (
            full["histograms"]["batch_latency_s"]
        )

    def test_device_health_transitions(self):
        import jax

        from flink_jpmml_tpu.parallel.health import DeviceHealth

        devs = jax.devices()
        lost_cb, rec_cb = [], []
        m = MetricsRegistry()
        dh = DeviceHealth(
            metrics=m, on_lost=lost_cb.append, on_recover=rec_cb.append
        ).watch(devs)
        assert dh.mark_lost(devs[0], error=faults.InjectedChipLoss())
        assert not dh.mark_lost(devs[0])  # idempotent transition
        assert lost_cb == [devs[0]]
        assert m.gauge("mesh_lost_devices").get() == 1.0
        assert devs[0] not in dh.alive()
        assert dh.survivors(devs) == list(devs[1:])
        assert dh.mark_recovered(devs[0])
        assert rec_cb == [devs[0]]
        assert m.gauge("mesh_lost_devices").get() == 0.0


# ---------------------------------------------------------------------------
# grammar + summary surfaces
# ---------------------------------------------------------------------------


class TestFaultGrammar:
    def test_device_kind_sites(self):
        fs = faults.parse_spec(
            "device_error:site=device_dispatch:n=2,"
            "device_oom:n=1,chip_loss:after_s=1"
        )
        assert [f.kind for f in fs] == [
            "device_error", "device_oom", "chip_loss",
        ]
        assert fs[0].site == "device_dispatch"
        assert fs[1].site == "device_readback"  # default: readback

    def test_device_kind_rejects_foreign_site(self):
        with pytest.raises(ValueError):
            faults.parse_spec("device_error:site=kafka_fetch")
        with pytest.raises(ValueError):
            faults.parse_spec("slow_fetch:site=device_readback")

    def test_checkpoint_fail_errno(self):
        (f,) = faults.parse_spec("checkpoint_fail:errno=28")
        with pytest.raises(faults.InjectedCheckpointFailure) as ei:
            f.act()
        assert ei.value.errno == 28

    def test_worker_crash_may_target_device_sites(self):
        (f,) = faults.parse_spec(
            "worker_crash:site=device_readback:n=0"
        )
        assert f.site == "device_readback"


class TestFailoverSummary:
    def test_summary_fields(self):
        m = MetricsRegistry()
        plane = failover_mod.FailoverPlane(m)
        plane.breaker_for("m1").record_failure()
        plane.note_fallback(64, "m1")
        plane.redispatch_records.inc(32)
        plane.oom_shrinks.inc()
        m.counter('device_fault_total{kind="device_error"}').inc(3)
        m.counter("records_out").inc(640)
        s = failover_mod.summary(m.struct_snapshot())
        assert s["states"] == {"m1": "closed"}
        assert s["fallback_records"] == 64
        assert s["redispatch_records"] == 32
        assert s["oom_shrinks"] == 1
        assert s["device_faults"] == {"device_error": 3.0}
        assert s["fallback_share"] == pytest.approx(0.1)

    def test_top_panel_renders(self, capsys):
        import io

        from flink_jpmml_tpu import cli

        m = MetricsRegistry()
        plane = failover_mod.FailoverPlane(m)
        b = plane.breaker_for("m1")
        b.record_failure()
        b.record_failure()
        b.record_failure()
        plane.note_fallback(100, "m1")
        m.counter("records_out").inc(1000)
        out = io.StringIO()
        cli._top_render_failover(
            "w0", m.struct_snapshot(), out, source="dump.json"
        )
        text = out.getvalue()
        assert "open" in text
        assert "fallback" in text
        assert "fjt-trace" in text

    def test_empty_panel_fallback_line(self):
        import io

        from flink_jpmml_tpu import cli

        out = io.StringIO()
        cli._top_render_failover("w0", {}, out)
        assert "no failover telemetry" in out.getvalue()
