"""Kafka wire-protocol path (runtime/kafka.py): protocol bytes (CRC32C,
varints, magic-2 record batches), client↔broker calls, engine
integration, kill/resume exactness, broker-restart reconnect — the real
wire-format counterpart of test_net.py's FJT1 drills."""

import json
import time

import numpy as np
import pytest

from assets.generate import gen_gbm
from flink_jpmml_tpu.compile import compile_pmml
from flink_jpmml_tpu.pmml import parse_pmml_file
from flink_jpmml_tpu.runtime.block import BlockPipeline
from flink_jpmml_tpu.runtime.checkpoint import CheckpointManager
from flink_jpmml_tpu.runtime.engine import Pipeline, StaticScorer
from flink_jpmml_tpu.runtime.kafka import (
    KafkaBlockSource,
    KafkaClient,
    KafkaProtocolError,
    KafkaRecordSource,
    MiniKafkaBroker,
    crc32c,
    decode_record_batches,
    decode_record_batches_rows,
    encode_record_batch,
)
from flink_jpmml_tpu.runtime.sinks import CollectSink
from flink_jpmml_tpu.utils.config import BatchConfig, RuntimeConfig
from flink_jpmml_tpu.utils.metrics import MetricsRegistry as _MReg


class TestProtocolBytes:
    def test_crc32c_known_vectors(self):
        # RFC 3720 / kernel test vectors for Castagnoli
        assert crc32c(b"123456789") == 0xE3069283
        assert crc32c(b"") == 0
        assert crc32c(b"\x00" * 32) == 0x8A9136AA

    def test_record_batch_roundtrip(self):
        values = [f"record-{i}".encode() for i in range(7)]
        raw = encode_record_batch(100, values)
        got = decode_record_batches(raw)
        assert got == [(100 + i, v) for i, v in enumerate(values)]

    def test_multiple_batches_and_partial_tail(self):
        b1 = encode_record_batch(0, [b"a", b"b"])
        b2 = encode_record_batch(2, [b"c"])
        got = decode_record_batches(b1 + b2)
        assert got == [(0, b"a"), (1, b"b"), (2, b"c")]
        # Kafka truncates record sets at max_bytes: a partial trailing
        # batch decodes to the complete prefix, no exception
        got = decode_record_batches(b1 + b2[: len(b2) // 2])
        assert got == [(0, b"a"), (1, b"b")]

    def test_tiny_batch_len_tail_is_partial(self):
        import struct

        b1 = encode_record_batch(0, [b"a", b"b"])
        # a corrupt/truncated trailer whose batch_len (4) fits inside the
        # buffer but is too short to hold the v2 header: must be treated
        # as a partial tail, not indexed into
        tail = struct.pack(">q", 2) + struct.pack(">i", 4) + b"\x00" * 4
        got = decode_record_batches(b1 + tail)
        assert got == [(0, b"a"), (1, b"b")]

    def test_crc_corruption_detected(self):
        raw = bytearray(encode_record_batch(0, [b"payload"]))
        raw[-1] ^= 0xFF
        with pytest.raises(ValueError, match="CRC32C"):
            decode_record_batches(bytes(raw))


class TestNativeRowDecode:
    """decode_record_batches_rows: the C++ fixed-length fast path must be
    byte-identical with the Python walk (and fall back when the tabular
    contract doesn't hold)."""

    def test_rows_match_python_decode(self):
        rng = np.random.default_rng(21)
        rows = rng.normal(size=(700, 6)).astype(np.float32)
        raw = encode_record_batch(40, [rows[i].tobytes() for i in range(700)])
        raw += encode_record_batch(
            740, [rows[i].tobytes() for i in range(100)]
        )
        offs, got = decode_record_batches_rows(raw, 6)
        ref = decode_record_batches(raw)
        assert offs.tolist() == [o for o, _ in ref]
        np.testing.assert_array_equal(got[:700], rows)
        np.testing.assert_array_equal(got[700:], rows[:100])

    def test_native_falls_back_on_variable_lengths(self):
        from flink_jpmml_tpu.runtime import native

        raw = encode_record_batch(0, [b"12345678", b"1234"])
        if native.available():
            assert native.kafka_decode_fixed(raw, 8) is None
        # the general path still serves them (here as a length error at
        # row construction, same as the pre-native behavior)
        with pytest.raises(ValueError):
            decode_record_batches_rows(raw, 2)

    def test_native_encode_byte_exact(self):
        from flink_jpmml_tpu.runtime import native

        if not native.available():
            pytest.skip("native library unavailable")
        rows = np.random.default_rng(31).normal(size=(300, 5)).astype(
            np.float32
        )
        raw8 = rows.view(np.uint8).reshape(300, -1)
        got = native.kafka_encode_fixed(raw8, 777)
        ref = encode_record_batch(
            777, [rows[i].tobytes() for i in range(300)]
        )
        assert got == ref  # the C++ producer path IS the wire format

    def test_partial_tail_and_crc_parity(self):
        rows = np.arange(24, dtype=np.float32).reshape(6, 4)
        b1 = encode_record_batch(0, [rows[i].tobytes() for i in range(6)])
        offs, got = decode_record_batches_rows(b1 + b1[: len(b1) // 2], 4)
        assert offs.tolist() == list(range(6))
        np.testing.assert_array_equal(got, rows)
        bad = bytearray(b1)
        bad[-1] ^= 0xFF
        with pytest.raises(ValueError, match="CRC32C"):
            decode_record_batches_rows(bytes(bad), 4)


def _native_or_skip():
    from flink_jpmml_tpu.runtime import native

    if not native.available():
        pytest.skip("native library unavailable")
    return native


def _batch_with_body_residue(residue: int, n_cols: int = 3):
    """→ (rows, raw): one encoded batch whose CRC-covered body (all but
    its first 21 bytes) is ``residue`` bytes past a multiple of 8."""
    rows = np.random.default_rng(residue).normal(size=(64, n_cols))
    rows = rows.astype(np.float32)
    for n in range(1, 65):
        raw = encode_record_batch(0, [rows[i].tobytes() for i in range(n)])
        if (len(raw) - 21) % 8 == residue:
            return rows[:n], raw
    raise AssertionError(f"no record count gives residue {residue}")


class TestNativeCrc32c:
    """Both CRC32C implementations of the native library — the one the
    Kafka codecs use (the crc32 instruction on an SSE4.2 host) and the
    byte table — equal the Python oracle on every tail length, on both
    sides of a word boundary, at every start alignment."""

    @staticmethod
    def _cases(case):
        rng = np.random.default_rng(38)
        if case == "vector":
            return [(b"123456789", 0xE3069283)]
        if case == "empty":
            return [(b"", 0)]
        if case == "lengths":
            data = rng.integers(0, 256, 4103, dtype=np.uint8).tobytes()
            ns = list(range(73)) + list(range(4093, 4104))
            return [(data[:n], crc32c(data[:n])) for n in ns]
        if case == "offsets":
            # memoryview slices: the pointer handed over really starts
            # 0-7 bytes past the buffer's own alignment
            data = rng.integers(0, 256, 4200, dtype=np.uint8).tobytes()
            view = memoryview(data)
            return [
                (view[k : k + n], crc32c(data[k : k + n]))
                for k in range(8)
                for n in (0, 1, 7, 8, 9, 15, 16, 17, 63, 4097)
            ]
        assert case == "batch_body"  # 512 records of 32 f32: ~70 KB
        rows = rng.normal(size=(512, 32)).astype(np.float32)
        raw = encode_record_batch(0, [r.tobytes() for r in rows])
        assert len(raw) > 70_000
        return [(raw[21:], crc32c(raw[21:]))]

    @pytest.mark.parametrize(
        "case", ["vector", "empty", "lengths", "offsets", "batch_body"]
    )
    @pytest.mark.parametrize("impl", ["crc32c", "crc32c_table"])
    def test_matches_python_oracle(self, impl, case):
        fn = getattr(_native_or_skip(), impl)
        for data, want in self._cases(case):
            assert fn(data) == want, (impl, case, len(data))

    def test_impl_is_named(self):
        native = _native_or_skip()
        assert native.crc32c_impl() in ("sse4.2", "table")

    @pytest.mark.parametrize("back", range(1, 9))
    def test_decode_rejects_a_flip_in_the_tail(self, back):
        """A body of 8k+7 bytes: its last seven are the tail loop's, the
        eighth from the end the last word's."""
        native = _native_or_skip()
        _, raw = _batch_with_body_residue(7)
        assert native.kafka_decode_fixed(raw, 12) is not None
        bad = bytearray(raw)
        bad[-back] ^= 0x01
        with pytest.raises(ValueError, match="CRC32C"):
            native.kafka_decode_fixed(bytes(bad), 12)

    @pytest.mark.parametrize("residue", range(8))
    def test_encode_byte_exact_every_residue(self, residue):
        native = _native_or_skip()
        rows, ref = _batch_with_body_residue(residue)
        got = native.kafka_encode_fixed(rows.view(np.uint8), 0)
        assert got == ref

    def test_block_source_reports_the_impl(self):
        native = _native_or_skip()
        broker = MiniKafkaBroker(topic="c")
        try:
            broker.append_rows(np.ones((4, 2), np.float32))
            m = _MReg()
            src = KafkaBlockSource(
                broker.host, broker.port, "c", n_cols=2, metrics=m,
                max_wait_ms=10,
            )
            try:
                while src.poll() is None:
                    pass
            finally:
                src.close()
            snap = m.struct_snapshot()
            want = float(native.crc32c_impl() == "sse4.2")
            assert snap["gauges"]["kafka_crc32c_hw"]["value"] == want
            assert snap["counters"]["kafka_decode_s"] > 0
        finally:
            broker.close()


class TestProtocolFuzz:
    """Random batches, truncations, and corruptions through both
    decoders: every outcome must be a correct prefix or a typed
    ValueError — never an IndexError/struct.error escape."""

    @pytest.mark.parametrize("seed", range(10))
    def test_roundtrip_truncate_corrupt(self, seed):
        rng = np.random.default_rng(7000 + seed)
        n = int(rng.integers(1, 120))
        fixed = bool(rng.integers(0, 2))
        vlen = int(rng.integers(0, 64))
        values = [
            rng.bytes(vlen if fixed else int(rng.integers(0, 64)))
            for _ in range(n)
        ]
        base = int(rng.integers(0, 10_000))
        raw = encode_record_batch(base, values)

        ref = decode_record_batches(raw)
        assert ref == [(base + i, v) for i, v in enumerate(values)]
        if fixed and vlen > 0:
            from flink_jpmml_tpu.runtime import native

            if native.available():
                # a fixed-length batch MUST take the native fast path —
                # a spurious None here would be a silent fallback bug
                dec = native.kafka_decode_fixed(raw, vlen)
                assert dec is not None
                offs, vals = dec
                assert offs.tolist() == [o for o, _ in ref]
                assert [vals[i].tobytes() for i in range(len(ref))] == values

        # truncations: every strict prefix of the single batch decodes
        # to [] — the decoder must never fabricate records
        for _ in range(6):
            k = int(rng.integers(0, len(raw)))
            assert decode_record_batches(raw[:k]) == []

        # corruptions: a flipped byte is caught typed (CRC/magic/framing)
        # or yields a clean prefix. The v2 CRC deliberately does NOT
        # cover the first 21 header bytes (base_offset/batch_len/epoch),
        # so flips there can decode successfully with shifted offsets —
        # the VALUES must still be intact (they are CRC-covered).
        for _ in range(5):
            j = int(rng.integers(0, len(raw)))
            bad = bytearray(raw)
            bad[j] ^= 0xFF
            try:
                got = decode_record_batches(bytes(bad))
                assert got == [] or [v for _, v in got] == [
                    v for _, v in ref
                ]
            except ValueError:
                pass  # typed rejection is the expected outcome
            if fixed and vlen > 0:
                from flink_jpmml_tpu.runtime import native

                if native.available():
                    try:
                        dec = native.kafka_decode_fixed(bytes(bad), vlen)
                        assert dec is None or len(dec[0]) in (0, n)
                    except ValueError:
                        pass


class TestClientBroker:
    def test_api_versions_metadata_offsets(self):
        broker = MiniKafkaBroker(topic="t")
        try:
            c = KafkaClient(broker.host, broker.port)
            vers = c.api_versions()
            # the broker answers in fixed response shapes; it must only
            # advertise the versions those shapes are valid for
            assert vers[1] == (4, 4)  # Fetch: v4 only
            brokers, parts = c.metadata("t")
            assert parts == {0: 0}
            assert list(brokers.values())[0][1] == broker.port
            assert c.list_offset("t", 0, -2) == 0  # earliest
            broker.append(b"x", b"y")
            assert c.list_offset("t", 0, -1) == 2  # latest
            c.close()
        finally:
            broker.close()

    def test_fetch_from_offset_and_wait(self):
        broker = MiniKafkaBroker()
        try:
            broker.append(*(f"v{i}".encode() for i in range(10)))
            c = KafkaClient(broker.host, broker.port)
            hw, recs = c.fetch(broker.topic, 0, 4)
            assert hw == 10
            assert recs == [(i, f"v{i}".encode()) for i in range(4, 10)]
            # empty fetch respects max_wait and returns no records
            t0 = time.monotonic()
            hw, recs = c.fetch(broker.topic, 0, 10, max_wait_ms=80)
            assert recs == [] and time.monotonic() - t0 >= 0.05
            c.close()
        finally:
            broker.close()

    def test_out_of_range_partition_fails_fast(self):
        # err 3 (UNKNOWN_TOPIC_OR_PARTITION), not an empty err-0 log: a
        # consumer misconfigured with a bad partition id must fail, not
        # poll a phantom partition forever
        broker = MiniKafkaBroker(topic="t")
        try:
            broker.append(b"x")
            c = KafkaClient(broker.host, broker.port)
            with pytest.raises(KafkaProtocolError, match="error 3"):
                c.list_offset("t", 7, -1)
            t0 = time.monotonic()
            with pytest.raises(KafkaProtocolError, match="error 3"):
                c.fetch("t", 7, 0, max_wait_ms=5000)
            # and the error is immediate — no long-poll on a bad index
            assert time.monotonic() - t0 < 2.0
            c.close()
        finally:
            broker.close()

    def test_fetch_respects_max_bytes(self):
        broker = MiniKafkaBroker()
        try:
            broker.append(*(bytes(1000) for _ in range(100)))
            c = KafkaClient(broker.host, broker.port)
            _, recs = c.fetch(broker.topic, 0, 0, max_bytes=10_000)
            assert 0 < len(recs) < 100  # bounded, not the whole log
            # and the stream continues from where it stopped
            _, recs2 = c.fetch(
                broker.topic, 0, recs[-1][0] + 1, max_bytes=10_000
            )
            assert recs2[0][0] == recs[-1][0] + 1
            c.close()
        finally:
            broker.close()


@pytest.mark.slow
class TestEngineIntegration:
    def test_json_records_through_pipeline(self, assets_dir):
        doc = parse_pmml_file(str(assets_dir / "iris_lr.pmml"))
        cm = compile_pmml(doc, batch_size=32)
        rng = np.random.default_rng(1)
        recs = [
            {f: float(v) for f, v in zip(doc.active_fields, row)}
            for row in rng.normal(3, 2, size=(150, 4))
        ]
        broker = MiniKafkaBroker(topic="iris")
        try:
            broker.append(*(json.dumps(r).encode() for r in recs))
            src = KafkaRecordSource(
                broker.host, broker.port, "iris", max_wait_ms=20
            )
            sink = CollectSink()
            pipe = Pipeline(
                src, StaticScorer(cm), sink,
                RuntimeConfig(batch=BatchConfig(size=32, deadline_us=2000)),
            )
            pipe.start()
            deadline = time.monotonic() + 30.0
            while len(sink.items) < 150 and time.monotonic() < deadline:
                time.sleep(0.01)
            pipe.stop()
            pipe.join(timeout=30.0)
            assert len(sink.items) >= 150
            direct = cm.score_records(recs[:5])
            for got, exp in zip(sink.items[:5], direct):
                assert got.score.value == pytest.approx(
                    exp.score.value, rel=1e-6
                )
            src.close()
        finally:
            broker.close()

    def test_block_source_contiguous(self):
        rng = np.random.default_rng(3)
        data = rng.normal(size=(512, 6)).astype(np.float32)
        broker = MiniKafkaBroker(topic="blocks")
        try:
            broker.append_rows(data)
            src = KafkaBlockSource(
                broker.host, broker.port, "blocks",
                n_cols=6, max_wait_ms=20,
            )
            pos = 0
            deadline = time.monotonic() + 15.0
            while pos < 512 and time.monotonic() < deadline:
                polled = src.poll()
                if polled is None:
                    continue
                off, blk = polled
                assert off == pos
                np.testing.assert_array_equal(
                    blk, data[off : off + blk.shape[0]]
                )
                pos += blk.shape[0]
            assert pos == 512
            # seek replays the Kafka log from the requested offset
            src.seek(500)
            off, blk = src.poll()
            assert off == 500 and blk.shape[0] == 12
            src.close()
        finally:
            broker.close()


@pytest.mark.slow
class TestKillResume:
    def test_block_pipeline_resumes_exactly(self, tmp_path):
        doc = parse_pmml_file(
            gen_gbm(str(tmp_path), n_trees=10, depth=3, n_features=5)
        )
        cm = compile_pmml(doc, batch_size=64)
        rng = np.random.default_rng(2)
        N = 3000
        data = rng.normal(0, 1.5, size=(N, 5)).astype(np.float32)
        ckdir = str(tmp_path / "ck")
        cfg = RuntimeConfig(
            batch=BatchConfig(size=64, deadline_us=2000),
            checkpoint_interval_s=0.05,
        )
        seen = []

        def sink(out, n, first_off):
            seen.append((first_off, n))

        broker = MiniKafkaBroker(topic="gbm")
        try:
            broker.append_rows(data)
            src = KafkaBlockSource(
                broker.host, broker.port, "gbm", n_cols=5, max_wait_ms=20
            )
            pipe = BlockPipeline(
                src, cm, sink, cfg, checkpoint=CheckpointManager(ckdir)
            )
            pipe.start()
            deadline = time.monotonic() + 10.0
            while pipe.committed_offset < 500 and time.monotonic() < deadline:
                time.sleep(0.005)
            pipe.stop()
            pipe.join(timeout=30.0)
            committed = pipe.committed_offset
            assert 0 < committed
            src.close()

            src2 = KafkaBlockSource(
                broker.host, broker.port, "gbm", n_cols=5, max_wait_ms=20
            )
            pipe2 = BlockPipeline(
                src2, cm, sink, cfg, checkpoint=CheckpointManager(ckdir)
            )
            assert pipe2.restore()
            assert pipe2.committed_offset == committed
            pipe2.start()
            deadline = time.monotonic() + 30.0
            while pipe2.committed_offset < N and time.monotonic() < deadline:
                time.sleep(0.01)
            pipe2.stop()
            pipe2.join(timeout=30.0)
            src2.close()
        finally:
            broker.close()

        covered = np.zeros(N, np.int32)
        for off, n in seen:
            covered[off : off + n] += 1
        assert (covered == 1).all(), (
            f"gaps={np.flatnonzero(covered == 0)[:5]} "
            f"dups={np.flatnonzero(covered > 1)[:5]}"
        )

    def test_multi_partition_interleave_restores_order(self):
        # producer round-robins rows over 2 partitions; the interleaved
        # consumer reconstructs the original global order exactly
        rng = np.random.default_rng(11)
        data = rng.normal(size=(300, 4)).astype(np.float32)
        broker = MiniKafkaBroker(topic="mp", n_partitions=2)
        try:
            broker.append_rows_round_robin(data)
            src = KafkaBlockSource(
                broker.host, broker.port, "mp", partitions=[0, 1],
                n_cols=4, max_wait_ms=20, interleave="strict",
            )
            pos = 0
            deadline = time.monotonic() + 15.0
            while pos < 300 and time.monotonic() < deadline:
                polled = src.poll()
                if polled is None:
                    continue
                off, blk = polled
                assert off == pos
                np.testing.assert_array_equal(
                    blk, data[off : off + blk.shape[0]]
                )
                pos += blk.shape[0]
            assert pos == 300
            # seek: one scalar offset restores BOTH partition cursors
            src.seek(151)
            off, blk = src.poll()
            assert off == 151
            np.testing.assert_array_equal(blk[0], data[151])
            src.close()
        finally:
            broker.close()

    def test_multi_partition_staggered_producers(self):
        # partitions fill at different rates: the strict interleave must
        # stall at the slowest partition's cursor (never reorder or skip)
        # and drain the backlog once it catches up
        rows = np.arange(400 * 2, dtype=np.float32).reshape(400, 2)
        broker = MiniKafkaBroker(topic="st", n_partitions=2)
        try:
            # partition 0 far ahead of partition 1
            broker.append_rows(rows[0::2], partition=0)
            broker.append_rows(rows[1::2][:3], partition=1)
            src = KafkaBlockSource(
                broker.host, broker.port, "st", partitions=[0, 1],
                n_cols=2, max_wait_ms=20, interleave="strict",
            )
            got = []
            pos = 0
            deadline = time.monotonic() + 10.0
            while pos < 7 and time.monotonic() < deadline:
                p = src.poll()
                if p:
                    got.append(p[1])
                    pos += p[1].shape[0]
            # 3 full strides + the head record of the incomplete one
            # (global 6 lands on partition 0); global 7 needs partition
            # 1's 4th record, which doesn't exist yet
            assert pos == 7
            assert src.poll() is None  # stalled, not reordered
            # catch-up: the rest of partition 1 arrives
            broker.append_rows(rows[1::2][3:], partition=1)
            deadline = time.monotonic() + 15.0
            while pos < 400 and time.monotonic() < deadline:
                p = src.poll()
                if p:
                    got.append(p[1])
                    pos += p[1].shape[0]
            assert pos == 400
            np.testing.assert_array_equal(np.concatenate(got), rows)
            src.close()
        finally:
            broker.close()

    def test_multi_partition_record_source(self):
        broker = MiniKafkaBroker(topic="mpr", n_partitions=3)
        try:
            for i in range(30):
                broker.append(
                    json.dumps({"i": i}).encode(), partition=i % 3
                )
            src = KafkaRecordSource(
                broker.host, broker.port, "mpr", partitions=[0, 1, 2],
                max_wait_ms=20, interleave="strict",
            )
            got = []
            deadline = time.monotonic() + 15.0
            while len(got) < 30 and time.monotonic() < deadline:
                got.extend(src.poll(max_n=7))
            # engine offsets are global-index+1; records in global order
            assert [r["i"] for _, r in got] == list(range(30))
            assert [o for o, _ in got] == list(range(1, 31))
            src.close()
        finally:
            broker.close()

    def test_concurrent_produce_consume_ordered(self):
        # live producer racing the consumer: the segment cache grows
        # under the lock while fetches serve from it — order and
        # completeness must hold (the round-4 broker stores encoded
        # segments, so this is the write/read race that rework created)
        import threading

        rows = np.arange(2000 * 3, dtype=np.float32).reshape(2000, 3)
        broker = MiniKafkaBroker(topic="live")
        try:
            def produce():
                for i in range(0, 2000, 100):
                    broker.append_rows(rows[i : i + 100])
                    time.sleep(0.002)

            t = threading.Thread(target=produce)
            t.start()
            src = KafkaBlockSource(
                broker.host, broker.port, "live", n_cols=3, max_wait_ms=20
            )
            got = []
            pos = 0
            deadline = time.monotonic() + 30.0
            while pos < 2000 and time.monotonic() < deadline:
                polled = src.poll()
                if polled is None:
                    continue
                off, blk = polled
                assert off == pos
                got.append(blk)
                pos += blk.shape[0]
            t.join()
            assert pos == 2000
            np.testing.assert_array_equal(np.concatenate(got), rows)
            src.close()
        finally:
            broker.close()

    def test_source_survives_broker_restart(self):
        data = np.arange(400 * 3, dtype=np.float32).reshape(400, 3)
        broker = MiniKafkaBroker(topic="r")
        port = broker.port
        src = KafkaBlockSource(
            broker.host, port, "r", n_cols=3, max_wait_ms=20
        )
        broker.append_rows(data[:250])
        got = []
        pos = 0
        deadline = time.monotonic() + 30.0
        while pos < 250 and time.monotonic() < deadline:
            polled = src.poll()
            if polled is None:
                time.sleep(0.005)
                continue
            got.append(polled)
            pos += polled[1].shape[0]
        assert pos == 250
        broker.close()  # broker dies
        # outage: polls yield None (reconnect with backoff), never raise
        assert src.poll() is None
        # restart on the same port with the full log (a real broker's
        # log is durable; the mini broker models that by re-serving it)
        broker2 = MiniKafkaBroker(topic="r", port=port)
        try:
            broker2.append_rows(data)
            deadline = time.monotonic() + 30.0
            while pos < 400 and time.monotonic() < deadline:
                polled = src.poll()
                if polled is None:
                    time.sleep(0.005)
                    continue
                off, blk = polled
                assert off == pos  # resumed at exactly the next offset
                got.append(polled)
                pos += blk.shape[0]
            assert pos == 400
            covered = np.zeros(400, np.int32)
            for off, blk in got:
                covered[off : off + blk.shape[0]] += 1
            assert (covered == 1).all()
            src.close()
        finally:
            broker2.close()


@pytest.mark.slow
class TestMultiPartitionResume:
    def test_block_pipeline_resumes_exactly_across_two_partitions(
        self, tmp_path
    ):
        """VERDICT r3 #10: the kill/resume drill over a 2-partition topic —
        the single checkpointed offset must restore both partition cursors
        and replay every record exactly once."""
        doc = parse_pmml_file(
            gen_gbm(str(tmp_path), n_trees=8, depth=3, n_features=5)
        )
        cm = compile_pmml(doc, batch_size=64)
        rng = np.random.default_rng(13)
        N = 2000
        data = rng.normal(0, 1.5, size=(N, 5)).astype(np.float32)
        ckdir = str(tmp_path / "ck")
        cfg = RuntimeConfig(
            batch=BatchConfig(size=64, deadline_us=2000),
            checkpoint_interval_s=0.05,
        )
        seen = []

        def sink(out, n, first_off):
            seen.append((first_off, n))

        def mk_src():
            return KafkaBlockSource(
                broker.host, broker.port, "mp2", partitions=[0, 1],
                n_cols=5, max_wait_ms=20, interleave="strict",
            )

        broker = MiniKafkaBroker(topic="mp2", n_partitions=2)
        try:
            broker.append_rows_round_robin(data)
            src = mk_src()
            pipe = BlockPipeline(
                src, cm, sink, cfg, checkpoint=CheckpointManager(ckdir)
            )
            pipe.start()
            deadline = time.monotonic() + 10.0
            while pipe.committed_offset < 400 and time.monotonic() < deadline:
                time.sleep(0.005)
            pipe.stop()
            pipe.join(timeout=30.0)
            committed = pipe.committed_offset
            assert 0 < committed
            src.close()

            src2 = mk_src()
            pipe2 = BlockPipeline(
                src2, cm, sink, cfg, checkpoint=CheckpointManager(ckdir)
            )
            assert pipe2.restore()
            assert pipe2.committed_offset == committed
            pipe2.start()
            deadline = time.monotonic() + 30.0
            while pipe2.committed_offset < N and time.monotonic() < deadline:
                time.sleep(0.01)
            pipe2.stop()
            pipe2.join(timeout=30.0)
            src2.close()
        finally:
            broker.close()

        covered = np.zeros(N, np.int32)
        for off, n in seen:
            covered[off : off + n] += 1
        assert (covered == 1).all(), (
            f"gaps={np.flatnonzero(covered == 0)[:5]} "
            f"dups={np.flatnonzero(covered > 1)[:5]}"
        )


class TestInterleaveMigration:
    """The strict-resume migration path (docs/migration.md, 'Kafka
    multi-partition interleave and checkpoint migration'): a legacy
    scalar-only checkpoint written by the pre-vector strict bijection
    (a) is REFUSED by a default-constructed (auto) source with a pointer
    to the migration notes, and (b) resumes exactly when the source is
    constructed with interleave='strict' as the notes prescribe.
    Fast-loop on purpose: tier-1 guards the migration contract."""

    def test_legacy_scalar_checkpoint_migration_path(self):
        rng = np.random.default_rng(9)
        data = rng.normal(size=(40, 4)).astype(np.float32)
        broker = MiniKafkaBroker(topic="vec", n_partitions=2)
        try:
            # round-robin producer: global index g lives at partition
            # g % 2, offset g // 2 — the strict bijection's layout
            broker.append_rows(data[0::2], partition=0)
            broker.append_rows(data[1::2], partition=1)

            # (a) the post-default-change constructor (auto) cannot
            # expand a scalar offset; the error routes to the docs
            src_auto = KafkaBlockSource(
                broker.host, broker.port, "vec",
                partitions=[0, 1], n_cols=4, max_wait_ms=20,
            )
            with pytest.raises(
                KafkaProtocolError, match="docs/migration.md"
            ):
                src_auto.seek(10)  # the legacy checkpoint's scalar
            src_auto.close()

            # (b) the documented migration: interleave='strict' resumes
            # the same scalar exactly, records in producer order
            src = KafkaBlockSource(
                broker.host, broker.port, "vec",
                partitions=[0, 1], n_cols=4, max_wait_ms=20,
                interleave="strict",
            )
            src.seek(10)
            got, pos = [], 10
            deadline = time.monotonic() + 10.0
            while len(got) < 30 and time.monotonic() < deadline:
                polled = src.poll()
                if polled is None:
                    time.sleep(0.01)
                    continue
                off, blk = polled
                assert off == pos
                pos += blk.shape[0]
                got.extend(np.asarray(blk))
            src.close()
            np.testing.assert_array_equal(
                np.asarray(got), data[10:40]
            )
        finally:
            broker.close()


@pytest.mark.slow
class TestVectorOffsets:
    """Multi-partition ``interleave="auto"`` (the default): keyed
    producers (no round-robin bijection), compaction gaps, and resume
    from a checkpointed per-partition offset vector (VERDICT r4 #5)."""

    def _keyed_gapped_broker(self, data, n_partitions=3):
        """Keyed producer over ``n_partitions`` + compaction gaps in
        every partition → (broker, surviving row multiset)."""
        broker = MiniKafkaBroker(topic="vec", n_partitions=n_partitions)
        keys = [f"user-{i % 17}" for i in range(data.shape[0])]
        broker.append_rows_keyed(data, keys)
        # compact away a slice of each partition's middle (real gaps)
        survivors = []
        with broker._mu:
            sizes = [len(v) for v in broker._vals]
        for p in range(n_partitions):
            drop = list(range(5, min(25, sizes[p])))
            broker.compact(p, drop)
        with broker._mu:
            for p in range(n_partitions):
                survivors.extend(broker._vals[p])
        return broker, survivors

    def test_keyed_uneven_fill_consumes_everything(self):
        rng = np.random.default_rng(3)
        data = rng.normal(size=(300, 4)).astype(np.float32)
        broker = MiniKafkaBroker(topic="vec", n_partitions=3)
        try:
            broker.append_rows_keyed(
                data, [f"k{i % 11}" for i in range(300)]
            )
            src = KafkaBlockSource(
                broker.host, broker.port, "vec",
                partitions=[0, 1, 2], n_cols=4, max_wait_ms=20,
            )
            rows, pos = [], 0
            deadline = time.monotonic() + 15.0
            while len(rows) < 300 and time.monotonic() < deadline:
                polled = src.poll()
                if polled is None:
                    continue
                off, blk = polled
                assert off == pos  # global indices stay contiguous
                pos += blk.shape[0]
                rows.extend(blk.tobytes(order="C")[i * 16 : (i + 1) * 16]
                            for i in range(blk.shape[0]))
            src.close()
        finally:
            broker.close()
        # every produced row consumed exactly once (content multiset)
        want = sorted(data[i].tobytes() for i in range(300))
        assert sorted(rows) == want

    def test_compaction_gaps_are_data_not_errors(self):
        rng = np.random.default_rng(4)
        data = rng.normal(size=(240, 4)).astype(np.float32)
        broker, survivors = self._keyed_gapped_broker(data)
        try:
            src = KafkaBlockSource(
                broker.host, broker.port, "vec",
                partitions=[0, 1, 2], n_cols=4, max_wait_ms=20,
            )
            rows = []
            deadline = time.monotonic() + 15.0
            while len(rows) < len(survivors) and (
                time.monotonic() < deadline
            ):
                polled = src.poll()
                if polled is None:
                    continue
                _, blk = polled
                rows.extend(
                    blk[i].tobytes() for i in range(blk.shape[0])
                )
            src.close()
        finally:
            broker.close()
        assert sorted(rows) == sorted(survivors)

    def test_vector_state_resume_is_content_exact(self):
        """checkpoint_state/restore_state round trip: rows below the
        resume boundary never refetch; the union of pre-boundary and
        post-restore emissions is EXACTLY the surviving log."""
        rng = np.random.default_rng(5)
        data = rng.normal(size=(240, 4)).astype(np.float32)
        broker, survivors = self._keyed_gapped_broker(data)
        try:
            src = KafkaBlockSource(
                broker.host, broker.port, "vec",
                partitions=[0, 1, 2], n_cols=4, max_wait_ms=20,
            )
            run1 = []  # (global_idx, row bytes)
            while len(run1) < len(survivors) // 2:
                polled = src.poll()
                if polled is None:
                    continue
                off, blk = polled
                run1.extend(
                    (off + i, blk[i].tobytes())
                    for i in range(blk.shape[0])
                )
            committed = len(run1) - 3  # a commit mid-emission
            state = src.checkpoint_state(committed)
            assert state is not None and state["offset"] <= committed
            src.close()

            src2 = KafkaBlockSource(
                broker.host, broker.port, "vec",
                partitions=[0, 1, 2], n_cols=4, max_wait_ms=20,
            )
            resume = src2.restore_state(state)
            assert resume == state["offset"]
            run2 = []
            deadline = time.monotonic() + 15.0
            while time.monotonic() < deadline:
                polled = src2.poll()
                if polled is None:
                    if len(run2) + resume >= len(survivors):
                        break
                    continue
                off, blk = polled
                assert off == resume + len(run2)  # contiguous from k'
                run2.extend(
                    blk[i].tobytes() for i in range(blk.shape[0])
                )
            src2.close()
        finally:
            broker.close()
        kept = [row for g, row in run1 if g < resume]
        assert sorted(kept + run2) == sorted(survivors), (
            len(kept), len(run2), len(survivors), resume,
        )

    def test_source_fails_fast_on_unknown_partition(self):
        # err 3 must propagate THROUGH the source's reconnect shield:
        # the fetch loop normally swallows KafkaProtocolError and
        # retries, which would turn a misconfigured partition list into
        # an infinite silent poll
        from flink_jpmml_tpu.runtime.kafka import KafkaPartitionError

        broker = MiniKafkaBroker(topic="vec", n_partitions=2)
        try:
            broker.append(b"\x00" * 16, partition=0)
            src = KafkaBlockSource(
                broker.host, broker.port, "vec",
                partitions=[0, 7], n_cols=4, max_wait_ms=20,
            )
            with pytest.raises(KafkaPartitionError, match="partition 7"):
                deadline = time.monotonic() + 5.0
                while time.monotonic() < deadline:
                    src.poll()
            src.close()
        finally:
            broker.close()

    def test_auto_mode_rejects_scalar_start_offset(self):
        with pytest.raises(ValueError, match="strict"):
            KafkaBlockSource(
                "127.0.0.1", 1, "t", partitions=[0, 1], n_cols=4,
                start_offset=100,
            )

    def test_vector_checkpoint_refused_by_strict_source(self):
        # auto-era cursor-vector state restored into a strict source
        # must refuse loudly (the bijection would misread the offsets)
        broker = MiniKafkaBroker(topic="vec", n_partitions=2)
        try:
            src = KafkaBlockSource(
                broker.host, broker.port, "vec",
                partitions=[0, 1], n_cols=4, interleave="strict",
            )
            with pytest.raises(KafkaProtocolError, match="strict"):
                src.restore_state(
                    {"offset": 10, "cursors": {"0": 6, "1": 4}}
                )
            src.close()
        finally:
            broker.close()

    def test_strict_mode_rejects_keyed_layout(self):
        # the fast path must fail loudly, not mis-align lanes, when the
        # producer was not round-robin
        rng = np.random.default_rng(6)
        data = rng.normal(size=(90, 4)).astype(np.float32)
        broker = MiniKafkaBroker(topic="vec", n_partitions=3)
        try:
            # partition fill 45/30/15 — no bijection exists
            broker.append_rows(data[:45], partition=0)
            broker.append_rows(data[45:75], partition=1)
            broker.append_rows(data[75:], partition=2)
            src = KafkaBlockSource(
                broker.host, broker.port, "vec",
                partitions=[0, 1, 2], n_cols=4, max_wait_ms=20,
                interleave="strict",
            )
            got = 0
            last_progress = time.monotonic()
            # strict mode serves the bijection prefix — global indices
            # up to the first one whose slot has run dry (partition 2
            # holds 15 records: indices 0..46 are servable, 47 maps to
            # slot 2 offset 15 which never arrives) — then stalls; it
            # must never emit beyond it
            while time.monotonic() - last_progress < 1.0:
                polled = src.poll()
                if polled is None:
                    time.sleep(0.01)
                    continue
                got += polled[1].shape[0]
                last_progress = time.monotonic()
            assert got == 47, got
            src.close()
        finally:
            broker.close()

    def test_pipeline_kill_resume_keyed_gapped(self, tmp_path):
        """The VERDICT drill: kill/resume over a keyed (non-round-robin)
        producer and a gap-containing log — exact offset accounting
        below the restore point, duplicates confined to the replay
        window, final commit == surviving record count."""
        doc = parse_pmml_file(
            gen_gbm(str(tmp_path), n_trees=6, depth=3, n_features=4)
        )
        cm = compile_pmml(doc, batch_size=32)
        rng = np.random.default_rng(7)
        data = rng.normal(0, 1.5, size=(1500, 4)).astype(np.float32)
        broker, survivors = self._keyed_gapped_broker(data)
        total = len(survivors)
        ckdir = str(tmp_path / "ck")
        cfg = RuntimeConfig(
            batch=BatchConfig(size=32, deadline_us=2000),
            checkpoint_interval_s=0.02,
        )
        seen = []

        def sink(out, n, first_off):
            seen.append((first_off, n))

        def mk_src():
            return KafkaBlockSource(
                broker.host, broker.port, "vec",
                partitions=[0, 1, 2], n_cols=4, max_wait_ms=20,
            )

        try:
            src = mk_src()
            pipe = BlockPipeline(
                src, cm, sink, cfg, checkpoint=CheckpointManager(ckdir)
            )
            pipe.start()
            deadline = time.monotonic() + 15.0
            while pipe.committed_offset < total // 3 and (
                time.monotonic() < deadline
            ):
                time.sleep(0.005)
            pipe.stop()  # mid-stream: uncommitted backlog discarded
            pipe.join(timeout=30.0)
            src.close()

            src2 = mk_src()
            pipe2 = BlockPipeline(
                src2, cm, sink, cfg, checkpoint=CheckpointManager(ckdir)
            )
            assert pipe2.restore()
            resume = pipe2.committed_offset
            assert 0 < resume <= total
            pipe2.start()
            deadline = time.monotonic() + 30.0
            while pipe2.committed_offset < total and (
                time.monotonic() < deadline
            ):
                time.sleep(0.01)
            pipe2.stop()
            pipe2.join(timeout=30.0)
            src2.close()
            assert pipe2.committed_offset == total
        finally:
            broker.close()

        covered = np.zeros(total, np.int64)
        for off, n in seen:
            covered[off : off + n] += 1
        assert (covered >= 1).all(), (
            f"gaps={np.flatnonzero(covered == 0)[:5]}"
        )
        assert (covered[:resume] == 1).all(), (
            f"dups below resume at "
            f"{np.flatnonzero(covered[:resume] > 1)[:5]}"
        )


class TestIdleCommit:
    def test_paused_feed_commits_tail_batch(self, tmp_path):
        """A feed that stops mid-stream must not pin the final partial
        batch uncommitted in the in-flight window: committed_offset has
        to reach the high watermark WITHOUT stop() being called."""
        doc = parse_pmml_file(
            gen_gbm(str(tmp_path), n_trees=5, depth=3, n_features=4)
        )
        cm = compile_pmml(doc, batch_size=64)
        N = 200  # 3 full batches of 64 + a 8-record tail
        data = np.random.default_rng(9).normal(size=(N, 4)).astype(np.float32)
        broker = MiniKafkaBroker(topic="pause")
        try:
            broker.append_rows(data)
            src = KafkaBlockSource(
                broker.host, broker.port, "pause", n_cols=4, max_wait_ms=10
            )
            done = []
            pipe = BlockPipeline(
                src, cm, lambda out, n, off: done.append(n),
                RuntimeConfig(batch=BatchConfig(size=64, deadline_us=2000)),
            )
            pipe.start()
            deadline = time.monotonic() + 15.0
            while pipe.committed_offset < N and time.monotonic() < deadline:
                time.sleep(0.01)
            committed = pipe.committed_offset  # BEFORE stop
            pipe.stop()
            pipe.join(timeout=10.0)
            src.close()
            assert committed == N, (
                f"paused feed left offset at {committed} (<{N}); the "
                "in-flight window was not flushed on idle"
            )
            assert sum(done) == N
        finally:
            broker.close()


class TestEventTimeFreshness:
    """The freshness plane's kafka side (ISSUE 7): event time rides the
    record-batch headers, fetch advances per-partition watermarks, and
    the ingest→sink stamp channel books record staleness."""

    def test_record_batch_time_range_header_walk(self):
        from flink_jpmml_tpu.runtime.kafka import record_batch_time_range

        b1 = encode_record_batch(0, [b"a", b"b"], timestamp_ms=5_000)
        b2 = encode_record_batch(2, [b"c"], timestamp_ms=9_000)
        assert record_batch_time_range(b1) == (5.0, 5.0)
        assert record_batch_time_range(b1 + b2) == (5.0, 9.0)
        # timestamp 0 (the native encoder's default) = no event time
        b0 = encode_record_batch(3, [b"d"])
        assert record_batch_time_range(b0) is None
        assert record_batch_time_range(b0 + b2) == (9.0, 9.0)
        # truncated tail: the whole-batch prefix still reads
        assert record_batch_time_range(b1 + b2[: len(b2) // 2]) == (5.0, 5.0)
        assert record_batch_time_range(b"") is None

    def test_timestamped_append_rows_roundtrip(self):
        """A timestamped append_rows takes the Python encoder (the
        native one writes ts 0) and stays byte-decodable with the same
        offsets and payloads."""
        from flink_jpmml_tpu.runtime.kafka import record_batch_time_range

        rng = np.random.default_rng(5)
        data = rng.normal(size=(700, 4)).astype(np.float32)
        broker = MiniKafkaBroker(topic="ts")
        try:
            broker.append_rows(data, timestamp_ms=42_000)
            client = KafkaClient(broker.host, broker.port)
            try:
                hw, raw = client.fetch_raw("ts", 0, 0, max_wait_ms=20)
                assert hw == 700
                recs = decode_record_batches(raw)
                assert recs[0] == (0, data[0].tobytes())
                tr = record_batch_time_range(raw)
                assert tr == (42.0, 42.0)
            finally:
                client.close()
        finally:
            broker.close()

    def test_block_source_advances_watermark_and_books_staleness(self):
        from flink_jpmml_tpu.obs.freshness import freshness_for
        from flink_jpmml_tpu.utils.metrics import MetricsRegistry

        rng = np.random.default_rng(6)
        data = rng.normal(size=(256, 4)).astype(np.float32)
        broker = MiniKafkaBroker(topic="fresh")
        m = MetricsRegistry()
        try:
            now_ms = int(time.time() * 1000)
            broker.append_rows(data, timestamp_ms=now_ms - 3_000)
            src = KafkaBlockSource(
                broker.host, broker.port, "fresh",
                n_cols=4, max_wait_ms=20, metrics=m,
            )
            try:
                pos = 0
                deadline = time.monotonic() + 15.0
                while pos < 256 and time.monotonic() < deadline:
                    polled = src.poll()
                    if polled is None:
                        continue
                    off, blk = polled
                    pos = off + blk.shape[0]
                assert pos == 256
                g = m.struct_snapshot()["gauges"]
                wm_lag = g.get('watermark_lag_s{partition="0"}')
                assert wm_lag is not None
                # the records were stamped ~3 s ago: end-to-end event-
                # time lag reads it (bounded well above by test slop)
                assert 2.5 <= wm_lag["value"] < 60.0
                # the fetch path fed the forecaster: lag + age gauges
                assert 'kafka_lag{partition="0"}' in g
                assert 'kafka_lag_age_s{partition="0"}' in g
                # the sink side consumes the ingest stamps
                fr = freshness_for(m)
                fr.observe_sink(0, 256)
                h = m.histogram("record_staleness_s")
                assert h.count() >= 2
                assert h.quantile(0.5) == pytest.approx(3.0, abs=2.0)
                assert g_val(m, "watermark_ts") == pytest.approx(
                    (now_ms - 3_000) / 1000.0, abs=1.0
                )
            finally:
                src.close()
        finally:
            broker.close()

    def test_fetch_failure_still_sweeps_lag_age(self, monkeypatch):
        """A dead broker must not freeze kafka_lag_age_s at its last
        fresh-looking value: every fetch skips _observe_fetch on the
        reconnect path, so the sweep has to ride that path too or the
        FJT_LAG_STALE_S crossing never fires (review finding, pinned)."""
        from flink_jpmml_tpu.utils.metrics import MetricsRegistry

        rng = np.random.default_rng(13)
        data = rng.normal(size=(32, 4)).astype(np.float32)
        broker = MiniKafkaBroker(topic="dead")
        m = MetricsRegistry()
        src = None
        try:
            broker.append_rows(
                data, timestamp_ms=int(time.time() * 1000)
            )
            src = KafkaBlockSource(
                broker.host, broker.port, "dead",
                n_cols=4, max_wait_ms=20, metrics=m,
                reconnect_backoff_s=0.01,
            )
            pos = 0
            deadline = time.monotonic() + 15.0
            while pos < 32 and time.monotonic() < deadline:
                polled = src.poll()
                if polled is None:
                    continue
                off, blk = polled
                pos = off + blk.shape[0]
            assert pos == 32
            broker.close()
            broker = None
            sweeps = []
            monkeypatch.setattr(
                src._forecaster, "sweep",
                lambda now=None: sweeps.append(1),
            )
            src.poll()  # fetch fails → reconnect → sweep still runs
            assert sweeps
        finally:
            if src is not None:
                src.close()
            if broker is not None:
                broker.close()

    def test_unstamped_log_stays_out_of_the_freshness_plane(self):
        from flink_jpmml_tpu.obs.freshness import freshness_for
        from flink_jpmml_tpu.utils.metrics import MetricsRegistry

        rng = np.random.default_rng(7)
        data = rng.normal(size=(128, 4)).astype(np.float32)
        broker = MiniKafkaBroker(topic="nots")
        m = MetricsRegistry()
        try:
            broker.append_rows(data)  # native path: timestamp 0
            src = KafkaBlockSource(
                broker.host, broker.port, "nots",
                n_cols=4, max_wait_ms=20, metrics=m,
            )
            try:
                pos = 0
                deadline = time.monotonic() + 15.0
                while pos < 128 and time.monotonic() < deadline:
                    polled = src.poll()
                    if polled is None:
                        continue
                    off, blk = polled
                    pos = off + blk.shape[0]
                assert pos == 128
                fr = freshness_for(m)
                assert fr.low_watermark() is None
                fr.observe_sink(0, 128)
                assert m.histogram("record_staleness_s").count() == 0
                g = m.struct_snapshot()["gauges"]
                assert 'watermark_lag_s{partition="0"}' not in g
                # a 1970 watermark would have read as ~56 years of lag
            finally:
                src.close()
        finally:
            broker.close()

    def test_seek_resets_stamps_but_not_watermarks(self):
        from flink_jpmml_tpu.obs.freshness import freshness_for
        from flink_jpmml_tpu.utils.metrics import MetricsRegistry

        rng = np.random.default_rng(8)
        data = rng.normal(size=(64, 4)).astype(np.float32)
        broker = MiniKafkaBroker(topic="seek")
        m = MetricsRegistry()
        try:
            broker.append_rows(
                data, timestamp_ms=int(time.time() * 1000)
            )
            src = KafkaBlockSource(
                broker.host, broker.port, "seek",
                n_cols=4, max_wait_ms=20, metrics=m,
            )
            try:
                deadline = time.monotonic() + 15.0
                pos = 0
                while pos < 64 and time.monotonic() < deadline:
                    polled = src.poll()
                    if polled is None:
                        continue
                    off, blk = polled
                    pos = off + blk.shape[0]
                fr = freshness_for(m)
                wm = fr.low_watermark()
                assert wm is not None
                src.seek(0)  # replay: offset domain restarted
                fr.observe_sink(0, 64)
                # the pre-seek stamps were dropped, not mis-keyed
                assert m.histogram("record_staleness_s").count() == 0
                assert fr.low_watermark() == wm  # time never regresses
            finally:
                src.close()
        finally:
            broker.close()


def g_val(m, name):
    v = m.struct_snapshot()["gauges"].get(name)
    return v["value"] if isinstance(v, dict) else None


class TestEventTimeStrictInterleave:
    def test_strict_interleave_stamps_ingest(self):
        """The strict round-robin path buffers rows across fetches; its
        emitted runs must still carry ingest stamps so the sink books
        staleness (review finding: the plane was dark on
        interleave='strict' topologies, pinned)."""
        from flink_jpmml_tpu.obs.freshness import freshness_for
        from flink_jpmml_tpu.utils.metrics import MetricsRegistry

        rng = np.random.default_rng(9)
        data = rng.normal(size=(300, 4)).astype(np.float32)
        broker = MiniKafkaBroker(topic="mpts", n_partitions=2)
        m = MetricsRegistry()
        try:
            now_ms = int(time.time() * 1000)
            broker.append_rows_round_robin(
                data, timestamp_ms=now_ms - 4_000
            )
            src = KafkaBlockSource(
                broker.host, broker.port, "mpts", partitions=[0, 1],
                n_cols=4, max_wait_ms=20, interleave="strict",
                metrics=m,
            )
            try:
                pos = 0
                deadline = time.monotonic() + 15.0
                while pos < 300 and time.monotonic() < deadline:
                    polled = src.poll()
                    if polled is None:
                        continue
                    off, blk = polled
                    np.testing.assert_array_equal(
                        blk, data[off : off + blk.shape[0]]
                    )
                    pos = off + blk.shape[0]
                assert pos == 300
                g = m.struct_snapshot()["gauges"]
                assert 'watermark_lag_s{partition="0"}' in g
                assert 'watermark_lag_s{partition="1"}' in g
                fr = freshness_for(m)
                fr.observe_sink(0, 300)
                h = m.histogram("record_staleness_s")
                assert h.count() >= 2
                assert h.quantile(0.5) == pytest.approx(4.0, abs=2.0)
                assert g_val(m, "watermark_ts") == pytest.approx(
                    (now_ms - 4_000) / 1000.0, abs=1.0
                )
            finally:
                src.close()
        finally:
            broker.close()

    def test_explicit_none_trange_never_borrows_last_fetch(self):
        """An interleaved run whose consumed slots carried NO event
        times merges to trange=None; the stamp must be a no-op — not
        fall back to the previous (possibly foreign-partition) fetch's
        range and book unstamped rows with borrowed event times
        (review finding, pinned)."""
        from flink_jpmml_tpu.obs.freshness import freshness_for
        from flink_jpmml_tpu.utils.metrics import MetricsRegistry

        rng = np.random.default_rng(11)
        data = rng.normal(size=(64, 4)).astype(np.float32)
        broker = MiniKafkaBroker(topic="mixed")
        m = MetricsRegistry()
        try:
            now_ms = int(time.time() * 1000)
            broker.append_rows(data, timestamp_ms=now_ms - 5_000)
            src = KafkaBlockSource(
                broker.host, broker.port, "mixed",
                n_cols=4, max_wait_ms=20, metrics=m,
            )
            try:
                pos = 0
                deadline = time.monotonic() + 15.0
                while pos < 64 and time.monotonic() < deadline:
                    polled = src.poll()
                    if polled is None:
                        continue
                    off, blk = polled
                    pos = off + blk.shape[0]
                assert pos == 64
                assert src._last_trange is not None  # a stamped fetch
                fr = freshness_for(m)
                fr.observe_sink(0, 64)
                h = m.histogram("record_staleness_s")
                booked = h.count()
                assert booked >= 2
                # an unstamped run: explicit None, NOT the default
                src._stamp_ingest(1_000, 8, trange=None)
                fr.observe_sink(1_000, 8)
                assert h.count() == booked
            finally:
                src.close()
        finally:
            broker.close()


class TestDecodePoisonRouting:
    """ISSUE 12 satellite: decode errors stop being silently filtered —
    counted per partition, raw bytes to the DLQ, record skipped exactly
    once (never refetched forever, never fatal with a DLQ installed)."""

    def _broker_with_poison(self):
        broker = MiniKafkaBroker(topic="p")
        rows = np.arange(20, dtype=np.float32).reshape(5, 4)
        broker.append_rows(rows[:2])
        broker.append(b"short")        # 5 bytes: undecodable
        broker.append_rows(rows[2:4])
        broker.append(b"x" * 20)       # 20 bytes: over-long is ALSO
        # poison — np.frombuffer would silently truncate it into a
        # plausible row (pinned strict in decode_record_batches_rows)
        broker.append_rows(rows[4:])
        return broker, rows

    def test_block_source_skips_counts_and_quarantines(self, tmp_path):
        from flink_jpmml_tpu.runtime.dlq import DeadLetterQueue

        broker, rows = self._broker_with_poison()
        try:
            m = _MReg()
            dlq = DeadLetterQueue(str(tmp_path / "dlq"), metrics=m)
            src = KafkaBlockSource(
                broker.host, broker.port, "p", n_cols=4,
                metrics=m, dlq=dlq, max_wait_ms=10,
            )
            try:
                got = []
                for _ in range(30):
                    p = src.poll()
                    if p is None:
                        if len(got) >= 5:
                            break
                        continue
                    off, blk = p
                    for i in range(blk.shape[0]):
                        got.append((off + i, blk[i].tolist()))
                assert [o for o, _ in got] == [0, 1, 3, 4, 6]
                # rows decode under their TRUE offsets (no shift)
                assert got[2][1] == rows[2].tolist()
                assert sorted(set(dlq.offsets())) == [2, 5]
                assert all(
                    e["reason"] == "decode" for e in dlq.scan()
                )
                snap = m.struct_snapshot()["counters"]
                # ≥2: a gap-truncated refetch may see (and re-count) a
                # trailing poison value once more — the counter is per
                # rejection EVENT; the DLQ offset set stays exact
                assert snap['decode_errors{partition="0"}'] >= 2
            finally:
                src.close()
        finally:
            broker.close()

    def test_block_source_without_dlq_or_metrics_raises(self):
        broker, _ = self._broker_with_poison()
        try:
            src = KafkaBlockSource(
                broker.host, broker.port, "p", n_cols=4, max_wait_ms=10,
            )
            try:
                with pytest.raises(ValueError, match="value length"):
                    for _ in range(10):
                        src.poll()
            finally:
                src.close()
        finally:
            broker.close()

    def test_record_source_skips_bad_json(self, tmp_path):
        import json as _json

        from flink_jpmml_tpu.runtime.dlq import (
            DeadLetterQueue, payload_bytes,
        )

        broker = MiniKafkaBroker(topic="r")
        try:
            broker.append(
                _json.dumps({"a": 1}).encode(),
                b"not json {{",
                _json.dumps({"a": 2}).encode(),
            )
            m = _MReg()
            dlq = DeadLetterQueue(str(tmp_path / "dlq"), metrics=m)
            src = KafkaRecordSource(
                broker.host, broker.port, "r",
                metrics=m, dlq=dlq, max_wait_ms=10,
            )
            try:
                recs = src.poll(10)
                assert [r for _, r in recs] == [{"a": 1}, {"a": 2}]
                envs = list(dlq.scan())
                assert [e["offset"] for e in envs] == [1]
                assert payload_bytes(envs[0]) == b"not json {{"
            finally:
                src.close()
        finally:
            broker.close()

    def test_all_poison_fetch_advances_cursor_once(self, tmp_path):
        # a fetch containing ONLY undecodable values must advance the
        # cursor past them — otherwise the next poll refetches and
        # re-quarantines the same bytes forever
        from flink_jpmml_tpu.runtime.dlq import DeadLetterQueue

        broker = MiniKafkaBroker(topic="ap")
        try:
            broker.append(b"junk1", b"junk2")
            rows = np.arange(8, dtype=np.float32).reshape(2, 4)
            broker.append_rows(rows)
            m = _MReg()
            dlq = DeadLetterQueue(str(tmp_path / "dlq"), metrics=m)
            src = KafkaBlockSource(
                broker.host, broker.port, "ap", n_cols=4,
                metrics=m, dlq=dlq, max_wait_ms=10,
            )
            try:
                got = []
                for _ in range(20):
                    p = src.poll()
                    if p is not None:
                        got.append(p[0])
                        if sum(1 for _ in got) >= 1:
                            break
                assert got and got[0] == 2
                assert sorted(set(dlq.offsets())) == [0, 1]
                # quarantined exactly once each, not per refetch
                assert len(dlq.offsets()) == 2
            finally:
                src.close()
        finally:
            broker.close()

    def test_strict_interleave_still_raises(self, tmp_path):
        # the round-robin bijection cannot drop a lane: decode poison
        # under interleave="strict" stays fatal (use auto mode)
        from flink_jpmml_tpu.runtime.dlq import DeadLetterQueue

        broker = MiniKafkaBroker(topic="s", n_partitions=2)
        try:
            rows = np.arange(16, dtype=np.float32).reshape(4, 4)
            broker.append_rows_round_robin(rows)
            broker.append(b"bad", partition=0)
            broker.append_rows(rows[:1], partition=1)
            m = _MReg()
            dlq = DeadLetterQueue(str(tmp_path / "dlq"), metrics=m)
            src = KafkaBlockSource(
                broker.host, broker.port, "s", n_cols=4,
                partitions=[0, 1], interleave="strict",
                metrics=m, dlq=dlq, max_wait_ms=10,
            )
            try:
                with pytest.raises(ValueError, match="value length"):
                    for _ in range(10):
                        src.poll()
                assert dlq.count() == 0
            finally:
                src.close()
        finally:
            broker.close()
