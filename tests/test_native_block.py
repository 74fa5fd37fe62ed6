"""C++ data plane + block pipeline tests (SURVEY.md §6 'stress tests for the
host-side queue/partitioner')."""

import threading
import time

import numpy as np
import pytest

from flink_jpmml_tpu.compile import compile_pmml
from flink_jpmml_tpu.pmml import parse_pmml_file
from flink_jpmml_tpu.runtime import native
from flink_jpmml_tpu.runtime.block import (
    BlockPipeline,
    CyclingBlockSource,
    FiniteBlockSource,
    _PyRing,
    make_ring,
)

needs_native = pytest.mark.skipif(
    not native.available(), reason=f"native plane unavailable: {native.build_error()}"
)


class TestNativeRing:
    @needs_native
    def test_roundtrip_order_and_offsets(self):
        ring = native.NativeRing(capacity=1024, arity=4, batch_size=256)
        blk = np.arange(32, dtype=np.float32).reshape(8, 4)
        assert ring.push_block(blk, first_offset=100) == 8
        out, offs = ring.drain(deadline_us=1000)
        np.testing.assert_array_equal(out, blk)
        assert offs.tolist() == list(range(100, 108))

    @needs_native
    def test_fill_or_deadline(self):
        ring = native.NativeRing(capacity=1024, arity=2, batch_size=64)
        ring.push_block(np.ones((10, 2), np.float32), 0)
        t0 = time.monotonic()
        out, _ = ring.drain(deadline_us=30_000)
        assert out.shape[0] == 10  # partial batch after deadline
        assert time.monotonic() - t0 < 1.0

    @needs_native
    def test_backpressure_blocks_producer(self):
        ring = native.NativeRing(capacity=8, arity=1, batch_size=8)
        assert ring.push_block(np.ones((8, 1), np.float32), 0) == 8
        # ring full: timed push returns short
        pushed = ring.push_block(np.ones((4, 1), np.float32), 8, timeout_us=50_000)
        assert pushed == 0
        ring.drain(deadline_us=100)
        assert ring.push_block(np.ones((4, 1), np.float32), 8, timeout_us=50_000) == 4

    @needs_native
    def test_threaded_producer_consumer_conserves_records(self):
        ring = native.NativeRing(capacity=4096, arity=3, batch_size=512)
        N, BLK = 100_000, 1000
        total = [0]

        def produce():
            sent = 0
            while sent < N:
                blk = np.full((BLK, 3), sent, np.float32)
                got = 0
                while got < BLK:
                    got += ring.push_block(blk[got:], sent + got, timeout_us=1_000_000)
                sent += BLK
            ring.close()

        t = threading.Thread(target=produce)
        t.start()
        offsets_seen = []
        while True:
            out, offs = ring.drain(deadline_us=2000)
            if out.shape[0] == 0:
                break
            total[0] += out.shape[0]
            offsets_seen.append(offs.copy())
        t.join()
        assert total[0] == N
        all_offs = np.concatenate(offsets_seen)
        assert all_offs.shape[0] == N
        assert np.array_equal(np.sort(all_offs), np.arange(N, dtype=np.uint64))

    def test_python_fallback_same_interface(self):
        ring = _PyRing(capacity=64, arity=2, batch_size=16)
        ring.push_block(np.ones((20, 2), np.float32) * 7, 5)
        out, offs = ring.drain(deadline_us=1000)
        assert out.shape == (16, 2)
        assert offs.tolist() == list(range(5, 21))
        out2, offs2 = ring.drain(deadline_us=1000)
        assert out2.shape[0] == 4
        assert offs2.tolist() == [21, 22, 23, 24]

    def test_make_ring_falls_back(self):
        r = make_ring(16, 2, 8, native=False)
        assert isinstance(r, _PyRing)


def _boundaries():
    """The float32 values at and on either side of every byte-length
    boundary of the key encoding, 2^(8b-1), both signs; 2^63 itself and
    what lies beyond are out of int64's range."""
    out = []
    for b in range(1, 9):
        lim = np.float32(2.0 ** (8 * b - 1))
        out += [np.nextafter(lim, np.float32(0)), lim,
                np.nextafter(lim, np.float32(np.inf))]
    out += [float(v) for v in (126, 127, 128, 129, 32767, 32768, 32769)]
    return np.array(out + [-v for v in out], np.float32)


_HASH_COLUMNS = {
    "zero_and_ones": np.array([0.0, -0.0, 1.0, -1.0, 0.5, -0.5, 1.9, -1.9],
                              np.float32),
    "byte_length_boundaries": _boundaries(),
    # the benchmark's ids: the float32 with bit pattern 0x4B000000 + rank
    "benchmark_ids": (
        np.uint32(0x4B000000) + np.concatenate([
            np.arange(0, 70000), np.arange(2**23 - 9, 2**23 + 9),
            np.linspace(0, 335544319, 50001).astype(np.int64),
            np.arange(335544319 - 9, 335544320),
        ]).astype(np.uint32)).view(np.float32),
    "negatives": -np.concatenate([
        np.arange(1, 3000), 2.0 ** np.arange(0, 63), 3.0 * 2.0 ** np.arange(0, 61),
    ]).astype(np.float32),
    "int64_min": np.array([-(2.0 ** 63)], np.float32),
    # numpy casts these to INT64_MIN on this platform, and so does the plane
    "nan_inf_and_out_of_range": np.array(
        [np.nan, -np.nan, np.inf, -np.inf, 1e30, -1e30, 2.0 ** 63,
         -(2.0 ** 64), 3.4e38], np.float32),
}


@needs_native
class TestStateHash:
    """``fjt_state_hash_f32`` reads the key column where it lies in a raw
    f32 block and hashes it as ``stable_hash_vec`` hashes the column cast
    to int64: the oracle, and what ``hash_keys(extract_keys(X))`` is."""

    @pytest.mark.parametrize("name", sorted(_HASH_COLUMNS))
    def test_strided_f32_column_equals_stable_hash_vec(self, name):
        from flink_jpmml_tpu.parallel.partitioner import stable_hash_vec

        col = _HASH_COLUMNS[name]
        with np.errstate(invalid="ignore"):
            keys = col.astype(np.int64)
        if name in ("int64_min", "nan_inf_and_out_of_range"):
            assert (keys == np.iinfo(np.int64).min).all()
        want = stable_hash_vec(keys)
        X = np.full((col.size, 5), 7.0, np.float32)
        X[:, 2] = col
        assert np.array_equal(native.state_hash_f32(X, 2), want)
        # any strides: every other row of a column-major copy
        F = np.asfortranarray(X)[::2]
        assert np.array_equal(native.state_hash_f32(F, 2), want[::2])
        assert native.state_hash_f32(X[:0], 2).shape == (0,)

    def test_hash_block_is_hash_keys_of_extract_keys(self, monkeypatch):
        from flink_jpmml_tpu.runtime.state import KeyedStateTable, StateSpec

        t = KeyedStateTable(StateSpec(capacity=16, key_col=1))
        X = np.random.default_rng(3).normal(0, 1e6, (500, 3)).astype(
            np.float32)
        want = t.hash_keys(t.extract_keys(X))
        assert np.array_equal(t.hash_block(X), want)
        # not a float32 block: numpy hashes it
        assert np.array_equal(t.hash_block(X.astype(np.float64)), want)
        monkeypatch.setattr(native, "available", lambda: False)
        assert np.array_equal(t.hash_block(X), want)


def _plain_resolve(khash, apply, keys, occ, touch, probe, seq, slots):
    """``fjt_state_resolve`` as a plain walk, a record at a time and
    nothing ahead of the record: → ``(pending, collided)``."""
    cap = keys.shape[0]
    pending, collided = np.zeros(khash.size, bool), 0
    for i, h in enumerate(khash.tolist()):
        if not apply[i]:
            continue
        c, p = h % cap, 0
        while p < probe and occ[c] and keys[c] != h:
            c, p = (c + 1) % cap, p + 1
        if p < probe and occ[c]:
            slots[i] = c
            if touch[c] != seq:
                touch[c] = seq
                collided += p != 0
        else:
            pending[i] = True
    return pending, collided


def _mirror(rng, cap, load):
    """A mirror that linear probing has filled to ``load``, and the
    hashes that live in it."""
    keys, occ = np.zeros(cap, np.uint32), np.zeros(cap, bool)
    hashes = rng.choice(2**32, int(cap * load), replace=False).astype(
        np.uint32)
    for h in hashes.tolist():
        c = h % cap
        while occ[c]:
            c = (c + 1) % cap
        keys[c], occ[c] = h, True
    return keys, occ, rng.integers(1, 50, cap).astype(np.int64), hashes


_RESOLVE_N = (0, 1, 15, 16, 17, 33, 65536)


@needs_native
class TestStateResolve:
    """``fjt_state_resolve`` walks a record's window some records ahead
    of its stamp; the answers are those of a walk at the record itself:
    the records left pending, ``slots``, every stamp and the count, for
    any ``n``, the ones shorter than the pipeline included."""

    @pytest.mark.parametrize("n", _RESOLVE_N)
    @pytest.mark.parametrize("cap,probe,load", [
        (4099, 8, 0.9), (257, 64, 0.98), (5, 9, 0.8)])
    def test_equals_a_plain_walk(self, n, cap, probe, load):
        rng = np.random.default_rng([n, cap])
        keys, occ, touch, hashes = _mirror(rng, cap, load)
        # resident keys (many of them more than once: ``collided`` counts
        # a key once a call), keys the table has not got, records that
        # do not apply
        khash = np.where(
            rng.random(n) < 0.7, hashes[rng.integers(0, hashes.size, n)],
            rng.integers(0, 2**32, n)).astype(np.uint32)
        apply = rng.random(n) < 0.9
        seq = 77
        want_slots = np.full(n, cap, np.int32)
        want_touch = touch.copy()
        want_pending, want_collided = _plain_resolve(
            khash, apply, keys, occ, want_touch, probe, seq, want_slots)
        slots = np.full(n, cap, np.int32)
        keys0, occ0 = keys.copy(), occ.copy()
        todo, collided = native.state_resolve(
            khash, apply, keys, occ, touch, probe, seq, slots)
        assert todo.dtype == np.int64
        assert np.array_equal(todo, np.flatnonzero(want_pending))
        assert np.array_equal(slots, want_slots)
        assert np.array_equal(touch, want_touch)
        assert collided == want_collided
        assert np.array_equal(keys, keys0) and np.array_equal(occ, occ0)
        if n == 65536:
            assert 0 < todo.size < apply.sum() and collided > 0

    def test_a_key_twice_in_a_call_collides_once(self):
        # 19 lives behind 3, a slot past its home; 35 is not resident
        keys = np.zeros(16, np.uint32)
        occ = np.zeros(16, bool)
        keys[3:5], occ[3:5] = [3, 19], True
        touch = np.zeros(16, np.int64)
        khash = np.array([19, 3, 19, 35, 19, 7], np.uint32)
        apply = np.array([1, 1, 1, 1, 0, 1], bool)
        slots = np.full(6, 16, np.int32)
        todo, collided = native.state_resolve(
            khash, apply, keys, occ, touch, 4, 9, slots)
        assert slots.tolist() == [4, 3, 4, 16, 16, 16]
        assert todo.tolist() == [3, 5]
        assert collided == 1
        assert touch[3:5].tolist() == [9, 9] and touch.sum() == 18


class TestBlockPipeline:
    @pytest.fixture()
    def iris_model(self, assets_dir):
        doc = parse_pmml_file(str(assets_dir / "iris_lr.pmml"))
        return compile_pmml(doc, batch_size=64)

    @pytest.mark.parametrize("use_native", [False, True])
    def test_end_to_end_counts_and_validity(self, iris_model, use_native):
        if use_native and not native.available():
            pytest.skip("no native plane")
        rng = np.random.default_rng(0)
        data = rng.normal(3, 2, size=(1000, 4)).astype(np.float32)
        data[17, :] = np.nan  # one dirty record
        seen = {"n": 0, "invalid": 0}

        def sink(out, n, first_off):
            seen["n"] += n
            valid = np.asarray(out.valid)[:n]
            seen["invalid"] += int((~valid).sum())

        pipe = BlockPipeline(
            FiniteBlockSource(data, block_size=100),
            iris_model,
            sink,
            use_native=use_native,
        )
        pipe.run_until_exhausted(timeout=30.0)
        assert seen["n"] == 1000
        assert seen["invalid"] == 1
        assert pipe.native == (use_native and native.available())
        snap = pipe.metrics.snapshot()
        assert snap["records_out"] == 1000

    def test_gbm_block_path_takes_rank_wire(self, tmp_path):
        # the production block path must engage the quantized wire for the
        # north-star GBM (VERDICT r1 #2: it used to ship f32 via predict)
        from assets.generate import gen_gbm

        doc = parse_pmml_file(
            gen_gbm(str(tmp_path), n_trees=20, depth=4, n_features=6)
        )
        cm = compile_pmml(doc, batch_size=128)
        rng = np.random.default_rng(5)
        data = rng.normal(0.0, 1.5, size=(500, 6)).astype(np.float32)
        data[rng.random(size=data.shape) < 0.1] = np.nan
        got = np.full((500,), np.nan, np.float32)

        collected = []

        def sink(out, n, first_off):
            collected.append((out, n, first_off))

        pipe = BlockPipeline(
            FiniteBlockSource(data, block_size=100),
            cm,
            sink,
            use_native=native.available(),
        )
        assert pipe.backend.startswith("rank_wire_")
        pipe.run_until_exhausted(timeout=30.0)
        for out, n, first_off in collected:
            preds = pipe.decode(out, n)
            got[first_off : first_off + n] = [p.score.value for p in preds]
        assert not np.isnan(got).any()
        M = np.isnan(data)
        ref = np.asarray(
            cm.predict(np.nan_to_num(data, nan=0.0), M).value, np.float32
        )[:500]
        np.testing.assert_allclose(got, ref, rtol=1e-4, atol=1e-5)
        snap = pipe.metrics.snapshot()
        assert snap[f"scorer_backend_{pipe.backend}"] == 1
        assert snap["records_out"] == 500

    def test_throughput_smoke_cpu(self, iris_model):
        # not a perf assertion — just that the loop sustains block flow
        rng = np.random.default_rng(1)
        data = rng.normal(3, 2, size=(4096, 4)).astype(np.float32)
        count = [0]

        def sink(out, n, first_off):
            count[0] += n

        pipe = BlockPipeline(
            CyclingBlockSource(data, block_size=512),
            iris_model,
            sink,
            use_native=native.available(),
        )
        pipe.run_for(seconds=0.5)
        assert count[0] > 0
