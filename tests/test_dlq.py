"""Delivery-correctness plane (runtime/dlq.py): the dead-letter queue,
record-level poison isolation on both hot paths, crash-loop
fingerprinting, the decode-error quarantine, and the fjt-dlq CLI.

The kill-anywhere acceptance drill lives in bench.py
(--recovery-drill) with a smoke-scale tripwire in tools/perf_smoke.py;
this file pins the mechanisms one at a time.
"""

import json
import os
import subprocess
import sys
import textwrap
import time

import numpy as np
import pytest

from flink_jpmml_tpu.runtime import faults
from flink_jpmml_tpu.runtime.dlq import (
    CrashFingerprint,
    DeadLetterQueue,
    PoisonIsolationOverflow,
    dlq_for_checkpoint,
    fingerprint,
    make_envelope,
    payload_bytes,
    serialize_record,
)
from flink_jpmml_tpu.utils.metrics import MetricsRegistry

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean_faults(monkeypatch):
    monkeypatch.delenv("FJT_RESTART_STREAK", raising=False)
    faults.clear()
    yield
    faults.clear()


@pytest.fixture(scope="module")
def small_gbm():
    """One tiny compiled GBM shared by the module (compile once)."""
    import tempfile

    from flink_jpmml_tpu.assets_gen import gen_gbm
    from flink_jpmml_tpu.compile import compile_pmml
    from flink_jpmml_tpu.pmml import parse_pmml_file

    tmp = tempfile.mkdtemp(prefix="fjt-dlq-model-")
    return compile_pmml(
        parse_pmml_file(gen_gbm(tmp, n_trees=3, depth=3, n_features=4)),
        batch_size=32,
    )


class TestDeadLetterQueue:
    def test_roundtrip_and_fingerprint(self, tmp_path):
        q = DeadLetterQueue(str(tmp_path / "dlq"))
        env = q.quarantine(
            b"\x01\x02", offset=7, reason="score",
            error=ValueError("boom"), partition=3,
        )
        got = list(q.scan())
        assert got == [env]
        assert payload_bytes(got[0]) == b"\x01\x02"
        assert got[0]["exception"] == "ValueError: boom"
        assert got[0]["partition"] == 3
        # content-addressed: same bytes → same fingerprint, any offset
        assert got[0]["fingerprint"] == fingerprint(b"\x01\x02")
        assert make_envelope(b"\x01\x02", 99, "decode")["fingerprint"] \
            == got[0]["fingerprint"]

    def test_rotation_reopen_and_bound(self, tmp_path):
        m = MetricsRegistry()
        q = DeadLetterQueue(
            str(tmp_path / "dlq"), max_records=6, segment_records=2,
            metrics=m,
        )
        for i in range(5):
            q.quarantine(b"p%d" % i, offset=i, reason="score")
        # a reopened DLQ continues the segment sequence, loses nothing
        q2 = DeadLetterQueue(
            str(tmp_path / "dlq"), max_records=6, segment_records=2,
            metrics=m,
        )
        q2.quarantine(b"p5", offset=5, reason="decode")
        assert q2.offsets() == [0, 1, 2, 3, 4, 5]
        # past the bound: OLDEST segments drop, counted
        for i in range(6, 10):
            q2.quarantine(b"p%d" % i, offset=i, reason="decode")
        offs = q2.offsets()
        assert len(offs) <= 8 and offs[-1] == 9 and 0 not in offs
        snap = m.struct_snapshot()["counters"]
        assert snap['dlq_records{reason="score"}'] == 5
        assert snap['dlq_records{reason="decode"}'] == 5
        assert snap["dlq_dropped"] >= 2

    def test_corrupt_line_skipped(self, tmp_path):
        q = DeadLetterQueue(str(tmp_path / "dlq"), segment_records=8)
        q.quarantine(b"a", offset=1, reason="score")
        q.quarantine(b"b", offset=2, reason="score")
        seg = [p for p in os.listdir(q.directory)
               if p.startswith("dlq-")][0]
        path = os.path.join(q.directory, seg)
        lines = open(path).read().splitlines()
        lines.insert(1, "{torn garbage")
        open(path, "w").write("\n".join(lines) + "\n")
        assert q.offsets() == [1, 2]  # neighbors survive the damage

    def test_concurrent_puts_lose_nothing(self, tmp_path):
        # the default wiring shares one DLQ between the ingest thread
        # (decode poison) and the score thread (scoring poison): puts
        # racing a segment rotation must not drop envelopes
        import threading

        q = DeadLetterQueue(
            str(tmp_path / "dlq"), segment_records=3, max_records=10_000,
        )

        def writer(base):
            for i in range(100):
                q.quarantine(b"p", offset=base + i, reason="score")

        ts = [
            threading.Thread(target=writer, args=(b,))
            for b in (0, 10_000)
        ]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        offs = q.offsets()
        assert len(offs) == 200
        assert sorted(offs) == sorted(
            list(range(100)) + list(range(10_000, 10_100))
        )

    def test_dlq_for_checkpoint_colocation(self, tmp_path):
        from flink_jpmml_tpu.runtime.checkpoint import CheckpointManager

        ck = CheckpointManager(str(tmp_path / "ck"))
        q = dlq_for_checkpoint(ck)
        assert q.directory == os.path.join(ck.directory, "dlq")
        assert dlq_for_checkpoint(None) is None

    def test_serialize_record_shapes(self):
        assert json.loads(serialize_record({"a": 1})) == {"a": 1}
        # non-JSON payloads still serialize to something inspectable
        assert b"object" in serialize_record(object())


class TestCrashFingerprint:
    def test_restore_counting(self, tmp_path):
        fp = CrashFingerprint(str(tmp_path))
        assert fp.note_restore(5) == 1
        assert fp.note_restore(5) == 2
        assert fp.note_restore(5) == 3
        assert fp.note_restore(9) == 1  # progress resets the loop count

    def test_marker_roundtrip(self, tmp_path):
        fp = CrashFingerprint(str(tmp_path))
        assert fp.read_marker() is None
        fp.write_marker(10, 74, attempts=2)
        assert fp.read_marker() == {"lo": 10, "hi": 74, "attempts": 2}
        fp.clear_marker()
        assert fp.read_marker() is None
        fp.clear_marker()  # idempotent


class TestDispatcherOnError:
    def test_handled_error_is_swallowed_fifo_continues(self):
        from flink_jpmml_tpu.runtime.pipeline import OverlappedDispatcher

        class Boom:
            def block_until_ready(self):
                raise RuntimeError("device says no")

        handled = []
        done = []
        disp = OverlappedDispatcher(
            depth=None,
            complete=lambda out, meta: done.append(meta),
            on_error=lambda out, meta, e: (
                handled.append((meta, str(e))) or True
            ),
        )
        disp.launch(lambda: 1, meta="a")
        disp.launch(lambda: Boom(), meta="b")
        disp.launch(lambda: 3, meta="c")
        disp.flush()  # must NOT raise: b is handled, a/c complete
        assert done == ["a", "c"]
        assert handled == [("b", "device says no")]

    def test_unhandled_error_still_raises(self):
        from flink_jpmml_tpu.runtime.pipeline import OverlappedDispatcher

        class Boom:
            def block_until_ready(self):
                raise RuntimeError("no")

        disp = OverlappedDispatcher(
            depth=None, on_error=lambda out, meta, e: False,
        )
        disp.launch(lambda: Boom(), meta="b")
        with pytest.raises(RuntimeError, match="no"):
            disp.flush()


class TestBlockPathIsolation:
    def _run(self, small_gbm, tmp_path, data, restore=False, **pipe_kw):
        from flink_jpmml_tpu.runtime.block import (
            BlockPipeline, FiniteBlockSource,
        )
        from flink_jpmml_tpu.runtime.checkpoint import CheckpointManager
        from flink_jpmml_tpu.utils.config import BatchConfig, RuntimeConfig

        emitted = []

        def sink(out, n, first_off):
            emitted.append((first_off, n))

        pipe = BlockPipeline(
            FiniteBlockSource(data, 64), small_gbm, sink,
            RuntimeConfig(
                batch=BatchConfig(size=32, deadline_us=1000),
                checkpoint_interval_s=0.05,
            ),
            checkpoint=CheckpointManager(str(tmp_path / "ck")),
            **pipe_kw,
        )
        if restore:
            assert pipe.restore()
        pipe.run_until_exhausted(timeout=60)
        return pipe, emitted

    def test_poison_goes_to_dlq_rest_to_sink(self, small_gbm, tmp_path):
        N = 400
        rng = np.random.default_rng(0)
        data = rng.normal(0, 1, size=(N, 4)).astype(np.float32)
        faults.inject("poison_record", offset=97)
        faults.inject("poison_record", offset=255)
        pipe, emitted = self._run(small_gbm, tmp_path, data)
        covered = np.zeros(N, np.int64)
        for off, n in emitted:
            covered[off: off + n] += 1
        assert sorted(np.flatnonzero(covered == 0).tolist()) == [97, 255]
        assert (covered <= 1).all()
        assert pipe.committed_offset == N  # parked poison still commits
        dlq = DeadLetterQueue(str(tmp_path / "ck" / "dlq"))
        envs = {e["offset"]: e for e in dlq.scan()}
        assert sorted(envs) == [97, 255]
        assert envs[97]["reason"] == "score"
        # the payload is the raw f32 row — redrivable
        assert payload_bytes(envs[97]) == data[97].tobytes()
        snap = pipe.metrics.struct_snapshot()["counters"]
        assert snap['dlq_records{reason="score"}'] == 2
        # suspect gauge returned to 0 after the transient isolation
        assert (
            pipe.metrics.struct_snapshot()["gauges"][
                "poison_suspect_mode"
            ]["value"] == 0.0
        )

    def test_without_dlq_error_is_fatal(self, small_gbm, tmp_path):
        from flink_jpmml_tpu.runtime.block import (
            BlockPipeline, FiniteBlockSource,
        )
        from flink_jpmml_tpu.utils.config import BatchConfig, RuntimeConfig

        data = np.zeros((64, 4), np.float32)
        faults.inject("poison_record", offset=5)
        pipe = BlockPipeline(
            FiniteBlockSource(data, 64), small_gbm,
            lambda *a: None,
            RuntimeConfig(batch=BatchConfig(size=32, deadline_us=1000)),
            # no checkpoint → no DLQ → historical fail-fast behavior
        )
        with pytest.raises(faults.InjectedPoisonRecord):
            pipe.run_until_exhausted(timeout=30)

    def test_quarantine_budget_aborts_isolation(
        self, small_gbm, tmp_path, monkeypatch
    ):
        # every record poisoned: a model-level failure must NOT be
        # converted into mass quarantine — isolation aborts and the
        # original error kills the pipeline honestly
        monkeypatch.setenv("FJT_DLQ_MAX_PER_BATCH", "4")
        data = np.zeros((64, 4), np.float32)
        faults.inject("poison_record", every=1)
        from flink_jpmml_tpu.runtime.block import (
            BlockPipeline, FiniteBlockSource,
        )
        from flink_jpmml_tpu.runtime.checkpoint import CheckpointManager
        from flink_jpmml_tpu.utils.config import BatchConfig, RuntimeConfig

        pipe = BlockPipeline(
            FiniteBlockSource(data, 64), small_gbm, lambda *a: None,
            RuntimeConfig(batch=BatchConfig(size=32, deadline_us=1000)),
            checkpoint=CheckpointManager(str(tmp_path / "ck")),
        )
        with pytest.raises(PoisonIsolationOverflow):
            pipe.run_until_exhausted(timeout=30)
        dlq = DeadLetterQueue(str(tmp_path / "ck" / "dlq"))
        assert dlq.count() <= 4

    def test_replay_counter_on_restore(self, small_gbm, tmp_path):
        # phase 1: commit partway, leave an in-flight high-water mark;
        # phase 2: restore → records below inflight_hi count as replays
        N = 320
        data = np.arange(N * 4, dtype=np.float32).reshape(N, 4)
        pipe, _ = self._run(small_gbm, tmp_path, data)
        assert pipe.committed_offset == N
        state = pipe._ckpt_state()
        assert state["inflight_hi"] == N
        # simulate a torn run: rewind the checkpoint to mid-stream with
        # a wider in-flight range, then restore a fresh pipeline
        from flink_jpmml_tpu.runtime.checkpoint import CheckpointManager

        ck = CheckpointManager(str(tmp_path / "ck"))
        time.sleep(0.002)
        ck.save({"source_offset": 128, "inflight_hi": 256})
        pipe2, emitted2 = self._run(
            small_gbm, tmp_path, data, restore=True
        )
        assert pipe2.committed_offset == N
        snap = pipe2.metrics.struct_snapshot()["counters"]
        assert snap["records_replayed"] == 256 - 128
        assert emitted2[0][0] == 128  # resumed at the commit, not 0


class TestRecordPathIsolation:
    class _ListSource:
        def __init__(self, rows):
            self._rows = rows
            self._i = 0

        def poll(self, max_n):
            out = []
            while self._i < len(self._rows) and len(out) < max_n:
                out.append((self._i + 1, self._rows[self._i]))
                self._i += 1
            return out

        def seek(self, offset):
            self._i = offset

        @property
        def exhausted(self):
            return self._i >= len(self._rows)

    def test_poison_record_isolated_on_engine_path(
        self, small_gbm, tmp_path
    ):
        from flink_jpmml_tpu.runtime.checkpoint import CheckpointManager
        from flink_jpmml_tpu.runtime.engine import Pipeline, StaticScorer
        from flink_jpmml_tpu.runtime.sinks import CollectSink
        from flink_jpmml_tpu.utils.config import BatchConfig, RuntimeConfig

        N = 200
        rng = np.random.default_rng(1)
        rows = [
            rng.normal(0, 1, size=4).astype(np.float32).tolist()
            for _ in range(N)
        ]
        # offset targeting uses the record's TRUE offset on this path
        # too (stamps are resume points = offset+1): offset=K names
        # the same record here as on the block path
        faults.inject("poison_record", offset=56)
        sink = CollectSink()
        pipe = Pipeline(
            self._ListSource(rows), StaticScorer(small_gbm), sink,
            RuntimeConfig(
                batch=BatchConfig(size=32, deadline_us=1000),
                checkpoint_interval_s=0.05,
            ),
            checkpoint=CheckpointManager(str(tmp_path / "ck")),
        )
        pipe.run_until_exhausted(timeout=60)
        assert len(sink.items) == N - 1
        assert pipe.committed_offset == N
        dlq = DeadLetterQueue(str(tmp_path / "ck" / "dlq"))
        envs = list(dlq.scan())
        assert [e["offset"] for e in envs] == [56]
        # the record payload round-trips as JSON
        assert json.loads(payload_bytes(envs[0])) == rows[56]


class TestCrashLoopFingerprint:
    pytestmark = pytest.mark.slow  # multi-incarnation subprocess drill

    _WORKER = textwrap.dedent(r"""
        import glob, os, sys
        sys.path.insert(0, sys.argv[2])
        import jax
        jax.config.update("jax_platforms", "cpu")
        import numpy as np
        from flink_jpmml_tpu.compile import compile_pmml
        from flink_jpmml_tpu.pmml import parse_pmml_file
        from flink_jpmml_tpu.runtime.block import (
            BlockPipeline, FiniteBlockSource,
        )
        from flink_jpmml_tpu.runtime.checkpoint import CheckpointManager
        from flink_jpmml_tpu.utils.config import BatchConfig, RuntimeConfig

        tmp = sys.argv[1]
        pmml = glob.glob(os.path.join(tmp, "*.pmml"))[0]
        cm = compile_pmml(parse_pmml_file(pmml), batch_size=32)
        rng = np.random.default_rng(0)
        N = 200
        data = rng.normal(0, 1, size=(N, 4)).astype(np.float32)
        out = open(os.path.join(tmp, "sink.log"), "a", buffering=1)

        def sink(o, n, first_off):
            out.write(f"{first_off} {n}\n")

        pipe = BlockPipeline(
            FiniteBlockSource(data, 64), cm, sink,
            RuntimeConfig(
                batch=BatchConfig(size=32, deadline_us=1000),
                checkpoint_interval_s=0.02,
            ),
            checkpoint=CheckpointManager(os.path.join(tmp, "ck")),
            max_dispatch_chunks=1,
        )
        pipe.restore()
        pipe.run_until_exhausted(timeout=60)
        print("DONE", pipe.committed_offset, flush=True)
    """)

    def test_process_killing_record_converges_to_dlq(self, tmp_path):
        """A record that SIGKILLs the worker on every dispatch is
        fingerprinted across restarts (count via crashes.json +
        FJT_RESTART_STREAK), bisected under persisted markers, and
        quarantined WITHOUT a final dispatch — in ≤ log2(batch)+
        threshold incarnations, with zero loss elsewhere."""
        from flink_jpmml_tpu.assets_gen import gen_gbm

        gen_gbm(str(tmp_path), n_trees=3, depth=3, n_features=4)
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env["FJT_FAULTS"] = "worker_crash:site=score_batch:offset=117"
        env["FJT_POISON_RESTARTS"] = "1"
        env.pop("FJT_RESTART_STREAK", None)
        deaths = 0
        for attempt in range(14):
            proc = subprocess.run(
                [sys.executable, "-c", self._WORKER,
                 str(tmp_path), REPO],
                env=env, capture_output=True, text=True, timeout=120,
            )
            if proc.returncode == 0:
                break
            assert proc.returncode == -9, proc.stderr[-2000:]
            deaths += 1
        else:
            pytest.fail(f"no convergence after {deaths} deaths")
        assert deaths >= 1  # it DID crash-loop before converging
        dlq = DeadLetterQueue(str(tmp_path / "ck" / "dlq"))
        envs = {e["offset"]: e for e in dlq.scan()}
        assert sorted(envs) == [117]
        assert envs[117]["reason"] == "crash_loop"
        covered = np.zeros(200, np.int64)
        for ln in open(tmp_path / "sink.log"):
            off, n = map(int, ln.split())
            covered[off: off + n] += 1
        assert np.flatnonzero(covered == 0).tolist() == [117]
        # marker cleaned up after convergence
        assert not (tmp_path / "ck" / "suspect-marker.json").exists()


class TestProduceAndCLI:
    def test_produce_roundtrip(self):
        from flink_jpmml_tpu.runtime.kafka import (
            KafkaClient, MiniKafkaBroker,
        )

        broker = MiniKafkaBroker(topic="t")
        try:
            c = KafkaClient(broker.host, broker.port)
            assert c.produce("t", 0, [b"abc", b"def"]) == 0
            assert c.produce("t", 0, [b"ghi"]) == 2
            hw, recs = c.fetch("t", 0, 0)
            assert hw == 3
            assert [v for _, v in recs] == [b"abc", b"def", b"ghi"]
            c.close()
        finally:
            broker.close()

    def test_cli_list_inspect_redrive(self, tmp_path, capsys):
        from flink_jpmml_tpu.cli import dlq_main
        from flink_jpmml_tpu.runtime.kafka import (
            KafkaClient, MiniKafkaBroker,
        )

        ck = tmp_path / "ck"
        q = DeadLetterQueue(str(ck / "dlq"))
        row = np.arange(4, dtype=np.float32)
        q.quarantine(row.tobytes(), offset=137, reason="score",
                     error=ValueError("boom"), partition=0)
        q.quarantine(b"junk", offset=200, reason="decode", partition=0)
        # a duplicate envelope (same bytes, same offset — a replayed
        # quarantine): redrive must dedupe it
        q.quarantine(row.tobytes(), offset=137, reason="score")

        assert dlq_main(["list", str(ck)]) == 0
        out = capsys.readouterr().out
        assert "137" in out and "score" in out and "boom" in out

        assert dlq_main(["inspect", str(ck), "--offset", "137"]) == 0
        out = capsys.readouterr().out
        assert "as f32 row: [0.0, 1.0, 2.0, 3.0]" in out

        broker = MiniKafkaBroker(topic="re")
        try:
            assert dlq_main([
                "redrive", str(ck), "--host", broker.host,
                "--port", str(broker.port), "--topic", "re",
                "--reason", "score",
            ]) == 0
            c = KafkaClient(broker.host, broker.port)
            _, recs = c.fetch("re", 0, 0)
            # deduped: ONE produce despite two score envelopes
            assert [v for _, v in recs] == [row.tobytes()]
            c.close()
        finally:
            broker.close()

    def test_cli_redrive_nothing_matches(self, tmp_path):
        from flink_jpmml_tpu.cli import dlq_main

        q = DeadLetterQueue(str(tmp_path / "dlq"))
        q.quarantine(b"x", offset=1, reason="decode")
        with pytest.raises(SystemExit, match="nothing to redrive"):
            dlq_main([
                "redrive", str(tmp_path), "--host", "h", "--port", "1",
                "--topic", "t", "--reason", "score",
            ])
