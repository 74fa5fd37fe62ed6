"""Fault-injection harness (runtime/faults.py) + retry/backoff
(utils/retry.py): the ISSUE 8 acceptance faults drilled through the
REAL code paths —

- **broker death** → the kafka reconnect/backoff path recovers and the
  stream resumes with nothing lost;
- **slow fetch** → the delay lands in the real fetch histogram;
- **checkpoint-write failure** → the retry/backoff path saves anyway
  (and an unrecoverable streak raises loudly);
- plus dispatch delay, worker wedge, the env grammar, and the capped
  full-jitter backoff schedule itself.
"""

import os
import time

import numpy as np
import pytest

from flink_jpmml_tpu.obs import recorder as flight
from flink_jpmml_tpu.runtime import faults
from flink_jpmml_tpu.utils.metrics import MetricsRegistry
from flink_jpmml_tpu.utils.retry import Backoff

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.clear()
    yield
    faults.clear()


class TestGrammar:
    def test_parse_spec(self):
        fs = faults.parse_spec(
            "slow_fetch:delay_ms=40:p=0.5,broker_death:after_s=5:for_s=2"
        )
        assert [f.kind for f in fs] == ["slow_fetch", "broker_death"]
        assert fs[0].delay_s == pytest.approx(0.04)
        assert fs[0].p == 0.5
        assert fs[1].after_s == 5.0 and fs[1].for_s == 2.0

    def test_unknown_kind_raises(self):
        with pytest.raises(ValueError, match="unknown fault kind"):
            faults.parse_spec("segfault:delay_ms=1")

    def test_bad_param_raises(self):
        with pytest.raises(ValueError, match="bad fault param"):
            faults.parse_spec("slow_fetch:delay_ms")

    def test_install_from_env(self):
        assert faults.install_from_env("worker_wedge:wedge_s=0.01:n=1")
        assert faults.active()
        faults.clear()
        # garbage is skipped loudly, never fatal; nothing installs
        assert not faults.install_from_env("not_a_fault:x=1")
        assert not faults.active()
        assert not faults.install_from_env("")

    def test_count_and_probability_gates(self):
        f = faults.inject("dispatch_delay", delay_ms=0, n=3)
        for _ in range(10):
            faults.fire("dispatch")
        assert f.fires == 3
        # p=0 never fires regardless of the count budget
        faults.clear()
        f2 = faults.inject("dispatch_delay", delay_ms=0, p=0.0)
        for _ in range(50):
            faults.fire("dispatch")
        assert f2.fires == 0

    def test_seeded_probability_is_deterministic(self):
        def run():
            faults.clear()
            f = faults.inject("dispatch_delay", delay_ms=0, p=0.5, seed=7)
            pattern = []
            for _ in range(32):
                before = f.fires
                faults.fire("dispatch")
                pattern.append(f.fires > before)
            return pattern

        assert run() == run()


class TestBackoff:
    def test_full_jitter_schedule(self):
        # rng pinned at 1.0 exposes the ceiling sequence
        b = Backoff("t", base_s=0.1, cap_s=1.0, max_attempts=10,
                    rng=lambda: 1.0, sleep=lambda s: None)
        delays = [b.next_delay() for _ in range(6)]
        assert delays == pytest.approx([0.1, 0.2, 0.4, 0.8, 1.0, 1.0])
        # jitter draws UNDER the ceiling
        b2 = Backoff("t", base_s=0.1, cap_s=1.0, rng=lambda: 0.25,
                     sleep=lambda s: None)
        assert b2.next_delay() == pytest.approx(0.025)

    def test_reset_rearms_schedule_and_gauge(self):
        m = MetricsRegistry()
        b = Backoff("t", base_s=0.1, cap_s=1.0, metrics=m,
                    rng=lambda: 1.0, sleep=lambda s: None)
        b.next_delay()
        b.next_delay()
        assert m.snapshot()["reconnect_backoff_s"] == pytest.approx(0.2)
        b.reset()
        assert b.attempts == 0
        assert m.snapshot()["reconnect_backoff_s"] == 0.0
        assert b.next_delay() == pytest.approx(0.1)  # schedule restarted

    def test_give_up_event_once_per_streak(self):
        m = MetricsRegistry()
        b = Backoff("drill", base_s=0.001, max_attempts=3, metrics=m,
                    sleep=lambda s: None)
        for _ in range(6):
            b.sleep()
        assert b.exhausted
        give_ups = [
            e for e in flight.events() if e["kind"] == "retry_give_up"
            and e.get("what") == "drill"
        ]
        assert len(give_ups) == 1  # once per streak, not per retry
        assert m.snapshot()["retry_give_ups"] == 1.0

    def test_env_overrides(self, monkeypatch):
        monkeypatch.setenv("FJT_RETRY_BASE_S", "0.2")
        monkeypatch.setenv("FJT_RETRY_CAP_S", "0.5")
        monkeypatch.setenv("FJT_RETRY_MAX", "2")
        b = Backoff("t", base_s=0.01, cap_s=9.0, max_attempts=99)
        assert b.base_s == 0.2 and b.cap_s == 0.5 and b.max_attempts == 2


def _broker_and_source(metrics=None, rows=512):
    from flink_jpmml_tpu.runtime.kafka import (
        KafkaBlockSource, MiniKafkaBroker,
    )

    broker = MiniKafkaBroker(topic="faults")
    data = np.arange(rows * 4, dtype=np.float32).reshape(rows, 4)
    broker.append_rows(data)
    src = KafkaBlockSource(
        broker.host, broker.port, "faults", n_cols=4,
        max_wait_ms=10, reconnect_backoff_s=0.002, metrics=metrics,
        # small fetches: the stream must OUTLIVE the injected fault so
        # recovery has something left to resume
        max_bytes=2048,
    )
    return broker, src, data


class TestKafkaFaultDrills:
    def test_broker_death_recovers_through_backoff(self):
        """ISSUE 8 acceptance fault #1: injected broker death rides the
        real reconnect path — polls fail while the fault is active, the
        backoff streak grows, and when the 'broker' heals the stream
        resumes exactly where it left off (nothing lost, nothing
        duplicated)."""
        m = MetricsRegistry()
        broker, src, data = _broker_and_source(metrics=m)
        try:
            got = src.poll()
            assert got is not None and got[0] == 0
            consumed = got[1].shape[0]
            faults.inject("broker_death", n=4)
            dead_polls = 0
            while faults.stats().get("broker_death", 0) < 4:
                assert src.poll() is None  # the reconnect path, looping
                dead_polls += 1
                assert dead_polls < 50
            # the streak is visible while the broker is down...
            assert m.snapshot()["reconnect_backoff_s"] > 0.0
            reconnects = [
                e for e in flight.events()
                if e["kind"] == "kafka_reconnect"
            ]
            assert len(reconnects) >= 4
            assert reconnects[-1]["attempt"] >= 2  # a growing streak
            # ...and the fault budget exhausted = the broker healed
            healed = None
            for _ in range(50):
                healed = src.poll()
                if healed is not None:
                    break
            assert healed is not None
            assert healed[0] == consumed  # resume AT the cursor
            assert m.snapshot()["reconnect_backoff_s"] == 0.0  # reset
        finally:
            src.close()
            broker.close()

    def test_slow_fetch_lands_in_fetch_histogram(self):
        """ISSUE 8 acceptance fault #2: the injected delay is measured
        by the SAME kafka_fetch_s histogram a real slow broker would
        feed — the telemetry plane sees the fault, not a synthetic."""
        m = MetricsRegistry()
        broker, src, _ = _broker_and_source(metrics=m)
        try:
            faults.inject("slow_fetch", delay_ms=60, n=2)
            polls = 0
            while faults.stats().get("slow_fetch", 0) < 2 and polls < 50:
                src.poll()
                polls += 1
            h = m.histogram("kafka_fetch_s")
            state = h.state()
            assert state["max"] >= 0.06, state
        finally:
            src.close()
            broker.close()


class TestDispatchAndWedge:
    def test_dispatch_delay_injected_at_launch(self):
        from flink_jpmml_tpu.runtime.pipeline import OverlappedDispatcher

        class _Leaf:
            def block_until_ready(self):
                pass

        disp = OverlappedDispatcher(depth=1)
        faults.inject("dispatch_delay", delay_ms=40, n=1)
        t0 = time.monotonic()
        disp.launch(lambda: _Leaf())
        dt = time.monotonic() - t0
        disp.close()
        assert dt >= 0.04

    def test_worker_wedge_stalls_the_score_loop(self):
        """The wedge fires in the real block score loop: a wedged run
        takes visibly longer than a clean one over the same stream but
        still drains completely (the supervisor's wedge-kill plane is
        what would reap a longer one)."""
        from flink_jpmml_tpu.compile import compile_pmml
        from flink_jpmml_tpu.pmml import parse_pmml
        from flink_jpmml_tpu.runtime.block import (
            BlockPipeline, FiniteBlockSource,
        )
        from tests.test_overload import _CONST_XML

        cm = compile_pmml(parse_pmml(_CONST_XML.format(c=1.0)),
                          batch_size=32)
        data = np.zeros((128, 1), np.float32)

        def run():
            sunk = [0]
            pipe = BlockPipeline(
                FiniteBlockSource(data, block_size=32), cm,
                lambda out, n, off: sunk.__setitem__(0, sunk[0] + n),
                in_flight=2, use_native=False,
            )
            t0 = time.monotonic()
            pipe.run_until_exhausted(timeout=60.0)
            return time.monotonic() - t0, sunk[0]

        clean_dt, clean_n = run()
        faults.inject("worker_wedge", wedge_s=0.4, n=1)
        wedged_dt, wedged_n = run()
        assert clean_n == wedged_n == 128  # the stream still drains
        # the wedge sleep sits on the score thread's critical path; the
        # bound is the wedge itself — a clean-vs-wedged comparison
        # would flake whenever the (first, cold) clean run pays more
        # than 0.4 s of compile/scheduling noise
        assert wedged_dt >= 0.35


class TestCheckpointFaultDrill:
    def test_transient_failures_retry_then_succeed(self, tmp_path,
                                                   monkeypatch):
        """ISSUE 8 acceptance fault #3: two injected mid-write failures
        ride the retry/backoff path and the snapshot still lands."""
        monkeypatch.setenv("FJT_RETRY_BASE_S", "0.001")
        from flink_jpmml_tpu.runtime.checkpoint import CheckpointManager

        faults.inject("checkpoint_fail", n=2)
        mgr = CheckpointManager(str(tmp_path))
        mgr.save({"source_offset": 11})
        assert mgr.load_latest() == {"source_offset": 11}
        retries = [
            e for e in flight.events()
            if e["kind"] == "checkpoint_save_retry"
        ]
        assert len(retries) >= 2
        saves = [
            e for e in flight.events() if e["kind"] == "checkpoint_save"
        ]
        assert saves and saves[-1]["retries"] == 2

    def test_persistent_failure_exhausts_and_raises(self, tmp_path,
                                                    monkeypatch):
        monkeypatch.setenv("FJT_RETRY_BASE_S", "0.001")
        monkeypatch.setenv("FJT_RETRY_MAX", "3")
        from flink_jpmml_tpu.runtime.checkpoint import CheckpointManager
        from flink_jpmml_tpu.utils.exceptions import CheckpointException

        faults.inject("checkpoint_fail")  # no budget: never heals
        mgr = CheckpointManager(str(tmp_path))
        with pytest.raises(CheckpointException, match="after 3 retries"):
            mgr.save({"source_offset": 1})
        assert any(
            e["kind"] == "checkpoint_save_failed"
            for e in flight.events()
        )
        assert not list(tmp_path.glob("ckpt-*.json"))


class TestPoisonAndCrashKinds:
    """ISSUE 12: the delivery-correctness chaos primitives."""

    def test_poison_record_offset_targeting(self):
        import numpy as np

        f = faults.inject("poison_record", offset=5)
        with pytest.raises(faults.InjectedPoisonRecord) as ei:
            faults.fire("score_batch", offsets=np.arange(3, 8))
        assert ei.value.offsets == (5,)
        faults.fire("score_batch", offsets=np.arange(10, 20))  # no hit
        assert f.fires == 1
        # an offset-less call at the site never fires a targeted fault
        faults.fire("score_batch")
        assert f.fires == 1

    def test_poison_record_every_targeting(self):
        faults.inject("poison_record", every=4)
        with pytest.raises(faults.InjectedPoisonRecord) as ei:
            faults.fire("score_batch", offsets=[1, 2, 3, 8, 12])
        assert ei.value.offsets == (8, 12)

    def test_poison_record_needs_targeting(self):
        with pytest.raises(ValueError, match="offset= or every="):
            faults.inject("poison_record", p=1.0)

    def test_worker_crash_site_selection(self):
        fs = faults.parse_spec(
            "worker_crash:site=kafka_fetch:n=1,worker_crash:n=1"
        )
        assert [f.site for f in fs] == ["kafka_fetch", "score_loop"]
        with pytest.raises(ValueError, match="unknown fault site"):
            faults.parse_spec("worker_crash:site=bogus")
        with pytest.raises(ValueError, match="only meaningful"):
            faults.parse_spec("slow_fetch:site=dispatch")

    def test_worker_crash_sigkills_subprocess(self):
        # jax-free child: the kill primitive itself is cheap to pin
        import subprocess
        import sys

        proc = subprocess.run(
            [sys.executable, "-c", (
                "import os\n"
                "os.environ['FJT_FAULTS'] = "
                "'worker_crash:site=dispatch:n=1'\n"
                "import sys\n"
                f"sys.path.insert(0, {REPO!r})\n"
                "from flink_jpmml_tpu.runtime import faults\n"
                "faults.fire('dispatch')\n"
                "print('survived')\n"
            )],
            capture_output=True, text=True, timeout=60,
        )
        assert proc.returncode == -9
        assert "survived" not in proc.stdout


_REPLAY_WORKER = r"""
import glob, os, sys
sys.path.insert(0, sys.argv[2])
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np
from flink_jpmml_tpu.compile import compile_pmml
from flink_jpmml_tpu.pmml import parse_pmml_file
from flink_jpmml_tpu.runtime.block import BlockPipeline, FiniteBlockSource
from flink_jpmml_tpu.runtime.checkpoint import CheckpointManager
from flink_jpmml_tpu.utils.config import BatchConfig, RuntimeConfig

tmp = sys.argv[1]
pmml = glob.glob(os.path.join(tmp, "*.pmml"))[0]
cm = compile_pmml(parse_pmml_file(pmml), batch_size=32)
rng = np.random.default_rng(0)
N = 256
data = rng.normal(0, 1, size=(N, 4)).astype(np.float32)
out = open(os.path.join(tmp, "sink.log"), "a", buffering=1)

def sink(o, n, first_off):
    out.write(f"E {first_off} {n}\n")

pipe = BlockPipeline(
    FiniteBlockSource(data, 64), cm, sink,
    RuntimeConfig(
        batch=BatchConfig(size=32, deadline_us=1000),
        checkpoint_interval_s=0.01,
    ),
    checkpoint=CheckpointManager(os.path.join(tmp, "ck")),
    max_dispatch_chunks=1,
)
pipe.restore()
out.write(f"R {pipe.committed_offset}\n")
pipe.run_until_exhausted(timeout=60)
out.write(f"D {pipe.committed_offset}\n")
"""


class TestMidBatchKillReplayBoundary:
    pytestmark = pytest.mark.slow  # two jax subprocesses

    def test_suffix_replays_exactly_once_per_restart(self, tmp_path):
        """ISSUE 12 satellite (process-kill half; the deterministic
        in-process half is in tests/test_runtime.py): SIGKILL landing
        BETWEEN dispatch and offset commit — incarnation 1 dies the
        instant offset 130's batch reaches the score_batch hook, after
        earlier batches committed — and the restart replays the
        uncommitted suffix exactly once, skipping nothing."""
        import os
        import subprocess
        import sys

        import numpy as np

        from flink_jpmml_tpu.assets_gen import gen_gbm

        gen_gbm(str(tmp_path), n_trees=3, depth=3, n_features=4)
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env.pop("FJT_RESTART_STREAK", None)
        # incarnation 1: die mid-batch (after drain+dispatch of the
        # batch holding offset 130, before its commit)
        env1 = dict(env)
        env1["FJT_FAULTS"] = "worker_crash:site=score_batch:offset=130"
        p1 = subprocess.run(
            [sys.executable, "-c", _REPLAY_WORKER,
             str(tmp_path), REPO],
            env=env1, capture_output=True, text=True, timeout=120,
        )
        assert p1.returncode == -9, p1.stderr[-2000:]
        # incarnation 2: clean resume
        env2 = dict(env)
        env2.pop("FJT_FAULTS", None)
        p2 = subprocess.run(
            [sys.executable, "-c", _REPLAY_WORKER,
             str(tmp_path), REPO],
            env=env2, capture_output=True, text=True, timeout=120,
        )
        assert p2.returncode == 0, p2.stderr[-2000:]

        emitted, restores = [], []
        for ln in open(tmp_path / "sink.log"):
            kind, *rest = ln.split()
            if kind == "E":
                emitted.append((int(rest[0]), int(rest[1])))
            elif kind == "R":
                restores.append(int(rest[0]))
        assert restores[0] == 0 and len(restores) == 2
        c = restores[1]  # the kill landed between c's commit and 130
        assert 0 < c <= 130
        covered = np.zeros(256, np.int64)
        for off, n in emitted:
            covered[off: off + n] += 1
        assert (covered >= 1).all(), "a record was skipped"
        # below the restore point: exactly once; the uncommitted
        # suffix: at most once per incarnation (== exactly once per
        # restart); nothing ever thrice
        assert (covered[:c] == 1).all()
        assert (covered <= 2).all()
