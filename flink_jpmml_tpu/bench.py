"""Benchmark: 500-tree GBM scoring throughput on one TPU chip.

BASELINE config 2 / north star: "score a 500-tree GBM PMML over a stream at
>= 1M records/sec with no CPU evaluator in the hot path". The reference
(flink-jpmml) walks every tree per record on the CPU inside
JPMML-Evaluator; here scoring is three int8/bf16 einsums on the MXU and the
stream crosses the host↔device link as per-feature threshold *ranks*
(uint8 — the rank wire of compile/qtrees.py, bit-exact with f32 scoring),
so a 32-feature record costs 32 bytes in and 2 bytes (bf16 score) out.

Measured: the full streaming pipeline in steady state —
  host featurize (f32 → rank codes, thread pool, standing in for the C++
  ingest plane) → host→device transfer → jitted ensemble scoring →
  device→host score readback — with a bounded in-flight window exactly
  like the streaming runtime. Compile and warmup excluded. Every score
  batch is materialized on the host before it counts.

Prints exactly ONE JSON line:
  {"metric": ..., "value": N, "unit": ..., "vs_baseline": N, ...}
vs_baseline is the ratio against the 1M rec/s north-star target
(the reference publishes no numbers of its own - BASELINE.md). The line
also carries:
  "device_value"   — pure device-side scoring rate, batch already resident
  "backend"        — which backend actually ran
  "p50_latency_s" / "p99_latency_s" — per-batch pipeline latency
    (dispatch → scores materialized on host) at the THROUGHPUT operating
    point (262k-record dispatches: these are seconds-scale by design)
  "latency_mode"   — the LATENCY operating point: the production
    BlockPipeline at a small batch + ms deadline under paced offered
    load, reporting record-level {p50_ms, p99_ms, rec_s} (arrival →
    scores materialized on host). This is the BASELINE tracked metric's
    honest home; the throughput p50/p99 above is not a latency story.
  "kafka_mode"     — BASELINE config 2 literally: the GBM scored over a
    REAL Kafka wire-protocol stream (in-process broker serving magic-v2
    batches on loopback, C++ record-batch decoder on the consume side,
    production BlockPipeline scoring), reporting {rec_s, log_records}.
    Round 14: ingest is PIPELINED by default — a prefetch/decode
    sidecar (runtime/prefetch.py) overlaps fetch RPC + wire decode with
    scoring, with zero-copy memoryviews socket→decoder; the line embeds
    the sidecar's counters under "prefetch" and the decode-tier
    microbench (tools/decode_bench.py) under "decode_bench".
    --no-prefetch is the serial ablation.
  "interp_rec_s" / "interp_ratio" — a per-record oracle-interpreter
    (pmml/interp.py) baseline on the same model and host, and the measured
    speedup of the compiled path over it: the backend-independent
    quantification of "no CPU evaluator in the hot path". Pinned: fixed
    record count, median of 3 repeats, run BEFORE the throughput windows
    (a teardown-competing tail run wobbled 4x across round-3 captures).
  "windows"        — all pipelined measurement windows' rates. "value"
    is the MEDIAN window (the honest typical); "best_window" carries the
    max separately.
  "overlap_efficiency" / "h2d_stall_ms" — how well host staging hid
    behind device execution in the median window: every mode (hand
    loop, --block-pipeline, latency, kafka) runs through the SAME
    OverlappedDispatcher as the production pipelines
    (runtime/pipeline.py), which accounts the host time spent gated on
    device completion ("stall"); efficiency = 1 − stall/elapsed. The
    latency_mode / kafka_mode dicts carry their own pair.
Process shape: ONE process, the measurement in-line. Any exception in
any phase is a traceback and a non-zero exit. The perf capture needs a
TPU backend: anywhere else it exits non-zero and prints no line under a
``*_per_chip`` metric (a CPU rate is not a chip rate). The correctness
drills (--zoo, --stateful, --rollout-drill, ...) run on whatever backend
JAX resolves — ``JAX_PLATFORMS=cpu`` for a host-only run. The compile
cache is placed by ``JAX_COMPILATION_CACHE_DIR`` or defaults to
``<checkout>/.jax_cache`` (compile/cachedir.py).
"""

import argparse
import collections
import json
import os
import re
import shutil
import subprocess
import sys
import tempfile
import time
from concurrent.futures import ThreadPoolExecutor

from flink_jpmml_tpu.obs import attr as attr_mod
from flink_jpmml_tpu.obs import profiler as prof_mod
from flink_jpmml_tpu.utils.metrics import _nearest_rank
from flink_jpmml_tpu.utils.profiling import overlap_stats, wire_stats

NORTH_STAR_REC_S = 1_000_000.0


def _device_utilization(dev_rate: float, trees: int, depth: int,
                        features: int, f32_wire: bool):
    """→ (device_mfu, device_membw_util, flops_per_record) or Nones.

    Roofline math per docs/performance.md "Where the time goes": the
    path-matrix formulation costs ~2·T·(2^d−1)·2^d FLOPs/record in the
    split-indicator einsum plus 2·T·2^d in the leaf contraction; HBM
    stream traffic per record is F uint8 ranks in + a bf16 score out on
    the rank wire, or 4·F f32 bytes in on --f32-wire (the param tables
    amortize over the chunk). A gather-shaped workload that
    deliberately trades FLOPs toward bandwidth will sit in single-digit
    MFU — the point of the field is that the artifact says so itself.
    Chip peaks and the roofline arithmetic are shared with the LIVE
    gauges (obs/profiler.py); the bench keeps the strict null-on-
    unknown-chip convention.
    """
    import jax

    kind = getattr(jax.devices()[0], "device_kind", "") or ""
    peaks = prof_mod.chip_peaks(kind, strict=True)
    splits = (1 << depth) - 1
    leaves = 1 << depth
    flops_per_record = 2.0 * trees * splits * leaves + 2.0 * trees * leaves
    if peaks is None or dev_rate <= 0:
        return None, None, flops_per_record
    bytes_per_record = (4.0 * features if f32_wire else features) + 2.0
    mfu, membw = prof_mod.roofline(
        dev_rate, flops_per_record, bytes_per_record, peaks
    )
    return round(mfu, 4), round(membw, 4), flops_per_record


def _require_native(pipe) -> None:
    """The measured pipelines run on the C++ data plane or not at all:
    a rate taken on the pure-Python ring is a different system's."""
    if not pipe.native:
        from flink_jpmml_tpu.runtime import native

        raise RuntimeError(
            f"C++ data plane unavailable: {native.build_error()}"
        )


def _calibrate_latency_batch(doc, data_f32, args, use_quantized: bool):
    """Deadline-aware compiled-batch choice for the latency operating
    point (serving/overload.py AdaptiveBatcher, the predict-then-verify
    loop): time full-batch dispatches at a few compiled sizes, fit the
    ``c0 + c1·n`` capacity model, and pick the largest calibrated size
    predicted to fit inside 80% of ``--latency-deadline-us``. Returns
    ``(chosen_size, compiled_model, batcher)`` — the static 4096 this
    replaces posted p99≈90 ms against a 2 ms deadline because nothing
    ever consulted the deadline when sizing the batch."""
    import jax

    from flink_jpmml_tpu.compile import compile_pmml
    from flink_jpmml_tpu.serving.overload import AdaptiveBatcher

    Bl = int(args.latency_batch)
    deadline_s = args.latency_deadline_us / 1e6
    batcher = AdaptiveBatcher(
        deadline_s=deadline_s, target_frac=0.8,
        min_records=64, max_records=Bl,
        model=f"bench-gbm{args.trees}x{args.depth}x{args.features}",
        backend="latency_mode",
    )
    if not use_quantized:
        # the --f32-wire ablation keeps its historical static batch
        return Bl, compile_pmml(doc, batch_size=Bl), batcher
    # three calibrated sizes bound the compile cost (each size is a
    # fresh jit); the chosen size is restricted to a calibrated one so
    # calibration never buys a fourth compile
    sizes = sorted({Bl, max(64, Bl // 4), max(64, Bl // 16)})
    compiled = {}
    for b in sizes:
        cmb = compile_pmml(doc, batch_size=b)
        q = cmb.quantized_scorer()
        if q is None:
            return Bl, compile_pmml(doc, batch_size=Bl), batcher
        wire = q.wire.encode(data_f32[:b])
        jax.block_until_ready(q.predict_wire(wire))  # warm
        reps = []
        for _ in range(3):
            t0 = time.perf_counter()
            jax.block_until_ready(q.predict_wire(wire))
            reps.append(time.perf_counter() - t0)
        batcher.observe(b, sorted(reps)[len(reps) // 2])
        compiled[b] = cmb
    chosen = batcher.propose(sizes)
    batcher.flush()  # the fitted model persists beside kernel_costs.json
    return chosen, compiled[chosen], batcher


def _measure_latency_mode(doc, data_f32, args, use_quantized: bool):
    """The LATENCY operating point (BASELINE's tracked metric): the
    production BlockPipeline compiled at a DEADLINE-CHOSEN batch size
    (see :func:`_calibrate_latency_batch`) with a millisecond
    fill-or-deadline, under paced offered load below capacity.
    Record-level latency = block arrival (source poll stamp) → that
    block's scores materialized on the host; blocks are equal-size, so
    block percentiles == record percentiles.

    Offered load self-paces: a short UNPACED pre-run measures THIS
    pipeline's capacity on THIS backend, and the measured run offers
    80% of it (capped by --latency-offered) — the ROADMAP item 5
    operating point ("p99 ≤ deadline at 80% of capacity"). A fixed
    offered rate above capacity measures queue depth, not latency — the
    r4 artifact did exactly that on the CPU fallback, and the r5 TPU
    capture showed the same failure at 100k offered vs ~81k capacity
    (p50 452 ms of backlog against a 2 ms deadline). The line carries
    ``capacity_rec_s`` and ``achieved_frac`` so a capture where
    achieved < 0.95 x offered is self-evidently queueing, plus
    ``p99_vs_deadline_ratio`` so the deadline verdict is one field.

    Only called from the measurement child (jax already imported)."""
    import jax
    import numpy as np

    from flink_jpmml_tpu.runtime.block import BlockPipeline, BlockSource
    from flink_jpmml_tpu.utils.config import BatchConfig, RuntimeConfig

    Bl, cm, batcher = _calibrate_latency_batch(
        doc, data_f32, args, use_quantized
    )
    # granularity of arrival stamps (and of the percentiles); must not
    # exceed the data pool or the offset domain (steps of `block`) would
    # diverge from the record-count domain the sink matches against
    block = min(256, Bl, int(data_f32.shape[0]))
    # arrival stamps in offset order (ingest thread appends, score-loop
    # sink pops — deque ops are atomic under the GIL). Ordered matching
    # rather than stride-keyed lookup: the fill-or-deadline drain may
    # close a batch mid-block, so sink offsets need not stay
    # block-aligned; a block counts as done when its LAST record has
    # materialized.
    arrivals = collections.deque()  # (offset, t_arrival)
    lats = []

    class _PacedSource(BlockSource):
        """Cycles the dataset in small blocks at a paced offered rate
        (``offered_rec_s=None`` = unpaced: the capacity pre-run),
        stamping each block's arrival time."""

        exhausted = False

        def __init__(self, offered_rec_s):
            self._pos = 0
            self._off = 0
            self._interval = (
                block / float(offered_rec_s) if offered_rec_s else 0.0
            )
            self._next = None

        def poll(self):
            now = time.monotonic()
            if self._next is None:
                self._next = now
            if now < self._next:
                return None  # pipeline ingest re-polls after a short sleep
            n = data_f32.shape[0]
            if self._pos + block <= n:
                blk = data_f32[self._pos : self._pos + block]
                self._pos += block
            else:
                self._pos = block
                blk = data_f32[:block]
            off = self._off
            self._off += block
            arrivals.append((off, time.monotonic()))
            # pace against the schedule (no drift), but a stall must not
            # turn into a catch-up burst that measures queueing, not the
            # pipeline
            self._next = max(
                self._next + self._interval, now - 5 * self._interval
            )
            return off, blk

        def seek(self, offset: int) -> None:
            pass

    def sink(out, n, first_off):
        # force the D2H round trip: latency counts *materialized* scores
        np.asarray(
            out.value if hasattr(out, "value")
            else out[0] if isinstance(out, tuple) else out
        )
        t = time.monotonic()
        end = first_off + n
        while arrivals and arrivals[0][0] + block <= end:
            _, t_arr = arrivals.popleft()
            lats.append(t - t_arr)

    def _run(offered_rec_s, seconds):
        """One pipeline run → (rec_s, sorted latencies, backend,
        overlap stats). The pipeline's score loop IS the overlapped
        dispatcher (runtime/pipeline.py) — in_flight=1 holds it at the
        synchronous latency operating point, and its stall accounting
        rides out in the artifact so the two operating modes are
        directly comparable."""
        arrivals.clear()
        lats.clear()
        pipe = BlockPipeline(
            _PacedSource(offered_rec_s), cm, sink,
            RuntimeConfig(batch=BatchConfig(
                size=Bl, deadline_us=int(args.latency_deadline_us)
            )),
            in_flight=1,  # latency point: no completion window to hide in
            use_quantized=use_quantized,
        )
        _require_native(pipe)
        drift_fields = _drift_attach(pipe.metrics, cm)
        t0 = time.monotonic()
        pipe.run_for(seconds=seconds)
        elapsed = time.monotonic() - t0
        return (
            len(lats) * block / elapsed, sorted(lats), pipe.backend,
            {
                **overlap_stats(pipe.metrics, elapsed),
                **wire_stats(pipe.metrics, len(lats) * block),
                # per-stage latency attribution (obs/attr.py): where
                # this operating point's wall time went
                "attribution": attr_mod.summary(pipe.metrics),
                # the mode's exposition snapshot (scrape-format struct)
                "varz": pipe.metrics.struct_snapshot(),
                # data-health (obs/drift.py), present iff baselined
                "drift": (
                    drift_fields() if drift_fields is not None else None
                ),
            },
        )

    # warm the compile + first transfer outside the measured runs
    q = cm.quantized_scorer() if use_quantized else None
    if q is not None:
        jax.block_until_ready(q.predict_wire(q.wire.encode(data_f32[:Bl])))
    else:
        cm.warmup()
    seconds = min(4.0, max(2.0, args.seconds))
    # capacity pre-run: unpaced, short — what THIS pipeline sustains on
    # THIS backend; the measured run offers 80% of it (the ROADMAP
    # item 5 operating point) so the captured percentiles are latency,
    # not queue depth
    capacity, _, _, _ = _run(None, min(1.5, seconds))
    if capacity <= 0:
        return None
    offered = min(float(args.latency_offered), 0.8 * capacity)
    rate, s, backend, ostats = _run(offered, seconds)
    if not s:
        return None
    achieved_frac = rate / offered if offered else 0.0
    if achieved_frac < 0.95:
        # still saturated (capacity estimate was optimistic): one retry
        # at half again keeps the artifact a latency measurement. Adopt
        # the retry ONLY as a unit — a retry that yielded no samples
        # (e.g. a mid-run wedge) must not mix its rate/offered into the
        # first run's percentiles
        offered2 = offered * 0.5
        rate2, s2, backend2, ostats2 = _run(offered2, seconds)
        if s2:
            rate, s, backend, offered = rate2, s2, backend2, offered2
            ostats = ostats2
            achieved_frac = rate / offered if offered else 0.0
    p99_ms = round(1000 * s[min(len(s) - 1, int(0.99 * len(s)))], 3)
    deadline_ms = args.latency_deadline_us / 1000.0
    return {
        "p50_ms": round(1000 * s[len(s) // 2], 3),
        "p99_ms": p99_ms,
        # nearest-rank (ceil(q·n)-1, utils.metrics): int(q·n) over-
        # indexes — at exactly 1000 samples it returns the MAX. p50/p99
        # keep their historical convention (comparable across rounds);
        # p999 is new this round and starts unbiased
        "p999_ms": round(1000 * s[_nearest_rank(0.999, len(s))], 3),
        "rec_s": round(rate, 1),
        "offered_rec_s": round(offered, 1),
        "capacity_rec_s": round(capacity, 1),
        "achieved_frac": round(achieved_frac, 3),
        # the batch the AdaptiveBatcher CHOSE for this window (the
        # --latency-batch knob is the ceiling, echoed separately): the
        # deadline verdict rides p99_vs_deadline_ratio, ≤ 1.0 = met
        "batch": Bl,
        "batch_requested": int(args.latency_batch),
        "p99_vs_deadline_ratio": (
            round(p99_ms / deadline_ms, 3) if deadline_ms > 0 else None
        ),
        "capacity_model": batcher.state(),
        "deadline_us": int(args.latency_deadline_us),
        "backend": backend,
        "overlap_efficiency": ostats["overlap_efficiency"],
        "h2d_stall_ms": ostats["h2d_stall_ms"],
        "encode_ms": ostats.get("encode_ms"),
        "h2d_bytes_per_record": ostats.get("h2d_bytes_per_record"),
        "attribution": ostats.get("attribution"),
        "varz": ostats.get("varz"),
        "drift": ostats.get("drift"),
    }


def _probe_zero_copy_fetch() -> bool:
    """Does ``fetch_raw`` hand back a view into the response payload
    (zero-copy) rather than a bytes copy? Probed through the REAL
    path — one fetch against an ephemeral loopback broker — so any
    regression anywhere in client→reader→record-set extraction flips
    the artifact field."""
    from flink_jpmml_tpu.runtime.kafka import KafkaClient, MiniKafkaBroker

    broker = MiniKafkaBroker(topic="probe")
    try:
        broker.append(b"\x00\x00\x00\x00")
        client = KafkaClient(broker.host, broker.port)
        try:
            _, record_set = client.fetch_raw(
                "probe", 0, 0, max_wait_ms=50
            )
        finally:
            client.close()
        return isinstance(record_set, memoryview) and len(record_set) > 0
    finally:
        broker.close()


def run_decode_bench(
    records: int = 40_000, n_cols: int = 28, py_records: int = 4_000
) -> dict:
    """Decode-tier microbench: python-walk vs vectorized-numpy vs
    native C++ record-batch decode over one synthetic fixed-width
    record set (the tabular wire contract), parity-checked before
    timing. → the JSON row ``tools/decode_bench.py`` prints and the
    bench artifact embeds as ``kafka_mode.decode_bench``. The python
    walk is timed on a subset (``py_records``) — it is two decades
    slower and exists as the parity oracle, not a contender."""
    import numpy as np

    from flink_jpmml_tpu.runtime import native
    from flink_jpmml_tpu.runtime.kafka import (
        decode_record_batches_rows,
        decode_record_batches_rows_py,
        decode_record_batches_rows_vec,
        encode_record_batch,
    )

    rng = np.random.default_rng(7)
    rows = rng.normal(size=(records, n_cols)).astype(np.float32)

    def record_set(arr):
        parts = []
        for i in range(0, arr.shape[0], 512):
            chunk = arr[i : i + 512]
            parts.append(encode_record_batch(
                i, [chunk[j].tobytes() for j in range(chunk.shape[0])]
            ))
        return b"".join(parts)

    buf = record_set(rows)
    py_n = min(py_records, records)
    buf_py = record_set(rows[:py_n])

    # parity before stopwatch: every tier that will be timed must be
    # byte-identical to the oracle on the subset (incl. the native
    # decoder when present — a stale .so must not post a fast number)
    o_py, r_py = decode_record_batches_rows_py(buf_py, n_cols)
    o_vec, r_vec = decode_record_batches_rows_vec(buf_py, n_cols)
    parity = bool(
        (o_py == o_vec).all() and r_py.tobytes() == r_vec.tobytes()
    )
    if native.available():
        o_nat, r_nat = decode_record_batches_rows(buf_py, n_cols)
        parity = parity and bool(
            (o_py == o_nat).all() and r_py.tobytes() == r_nat.tobytes()
        )

    def rate(fn, b, n, repeats):
        t0 = time.perf_counter()
        for _ in range(repeats):
            fn(b, n_cols)
        return n * repeats / (time.perf_counter() - t0)

    line = {
        "records": records,
        "n_cols": n_cols,
        "parity": parity,
        # fetch_raw hands the decoder a memoryview of the response
        # payload (no socket→decode copy) — probed, not asserted, so a
        # regression to bytes-copying in the reader tier actually
        # flips the field in artifacts
        "zero_copy_fetch": _probe_zero_copy_fetch(),
        "python_rec_s": round(
            rate(decode_record_batches_rows_py, buf_py, py_n, 1), 1
        ),
        "vectorized_rec_s": round(
            rate(decode_record_batches_rows_vec, buf, records, 3), 1
        ),
    }
    if native.available():
        line["native_rec_s"] = round(
            rate(decode_record_batches_rows, buf, records, 3), 1
        )
    else:
        line["native_rec_s"] = None
    line["vectorized_speedup"] = round(
        line["vectorized_rec_s"] / max(line["python_rec_s"], 1e-9), 1
    )
    return line


def _measure_kafka_mode(cm, data_f32, args, use_quantized: bool):
    """BASELINE config 2, literally: the GBM scored over a REAL Kafka
    wire-protocol stream — an in-process broker serving magic-v2 record
    batches on loopback, the C++ record-batch decoder
    (fjt_kafka_decode_fixed) on the consume side, the production
    BlockPipeline scoring. The log cycles (seek-on-wrap) so the steady
    state outlasts the appended records. ``cm`` is the already-compiled
    chunk-batch model (no second compile on the device budget).

    Only called from the measurement child (jax already imported)."""
    import jax
    import numpy as np

    from flink_jpmml_tpu.runtime.block import BlockPipeline
    from flink_jpmml_tpu.runtime.kafka import (
        KafkaBlockSource, MiniKafkaBroker,
    )
    from flink_jpmml_tpu.utils.config import BatchConfig, RuntimeConfig
    from flink_jpmml_tpu.utils.metrics import MetricsRegistry

    C = int(cm.batch_size)
    broker = MiniKafkaBroker(topic="bench")
    try:
        broker.append_rows(data_f32)  # one-time encode, like a producer
        hw = broker.high_watermark

        class _CyclingKafka(KafkaBlockSource):
            """Wraps the cursor back to 0 at the high watermark so a
            finite log sustains a steady-state measurement."""

            def poll(self):
                if self._next >= hw:
                    self.seek(0)
                return super().poll()

        # one registry shared by the source (wire-decode accounting) and
        # the pipeline (encode/h2d + overlap accounting): the kafka_mode
        # line then says where both host threads' time goes
        km = MetricsRegistry()
        src = _CyclingKafka(
            broker.host, broker.port, "bench",
            n_cols=data_f32.shape[1], max_wait_ms=20, metrics=km,
        )
        count = [0]

        def sink(out, n, first_off):
            np.asarray(
                out.value if hasattr(out, "value")
                else out[0] if isinstance(out, tuple) else out
            )
            count[0] += n

        pipe = BlockPipeline(
            src, cm, sink,
            RuntimeConfig(batch=BatchConfig(
                size=C, deadline_us=5000,
                # the ring must hold several batches or the drain
                # serializes on the ingest thread at large chunks
                queue_capacity=max(65536, 4 * C),
            )),
            metrics=km,
            use_quantized=use_quantized,
            # pipelined ingest (runtime/prefetch.py): fetch+decode on a
            # sidecar thread, decoded blocks across a bounded handoff
            # queue — the round-14 default; --no-prefetch is the serial
            # ablation this line's rec_s used to measure
            prefetch=not args.no_prefetch,
        )
        _require_native(pipe)
        drift_fields = _drift_attach(km, cm)
        q = cm.quantized_scorer() if use_quantized else None
        if q is not None:
            jax.block_until_ready(
                q.predict_wire(q.wire.encode(data_f32[:C]))
            )
        else:
            cm.warmup()
        t0 = time.perf_counter()
        pipe.run_for(seconds=min(5.0, max(2.0, args.seconds)))
        dt = time.perf_counter() - t0
        src.close()
        ostats = overlap_stats(pipe.metrics, dt)
        line = {
            "rec_s": round(count[0] / dt, 1),
            "source": "kafka-wire",
            "log_records": hw,
            "backend": pipe.backend,
            "overlap_efficiency": ostats["overlap_efficiency"],
            "h2d_stall_ms": ostats["h2d_stall_ms"],
        }
        # pipelined-ingest accounting (runtime/prefetch.py): queue
        # depth high-water proves the sidecar actually ran ahead;
        # stall vs block says which side of the handoff bounds rec_s
        # (stall = ingest-bound, block = score-bound — the healthy one)
        snap = km.struct_snapshot()
        if not args.no_prefetch:
            from flink_jpmml_tpu.runtime import prefetch as prefetch_mod

            cs, gs = snap["counters"], snap["gauges"]
            line["prefetch"] = {
                "enabled": True,
                "depth": prefetch_mod.env_depth(),
                "batches": int(cs.get("prefetch_batches", 0)),
                "records": int(cs.get("prefetch_records", 0)),
                "depth_max": gs.get("prefetch_depth", {}).get("max", 0.0),
                "occupancy_max": gs.get(
                    "prefetch_occupancy", {}
                ).get("max", 0.0),
                "stall_ms": round(
                    1000 * cs.get("prefetch_stall_s", 0.0), 1
                ),
                "block_ms": round(
                    1000 * cs.get("prefetch_block_s", 0.0), 1
                ),
            }
        else:
            line["prefetch"] = {"enabled": False}
        # the decode-tier microbench (tools/decode_bench.py), embedded
        # so every artifact carries the python/vectorized/native ladder
        # measured on THIS host
        line["decode_bench"] = run_decode_bench(
            records=20_000, n_cols=data_f32.shape[1], py_records=2_000
        )
        # encode placement + consumer decode accounting (encode_ms ≈ 0
        # when the autotuner fused the bucketize onto the device)
        line.update(wire_stats(pipe.metrics, count[0]))
        varz = km.struct_snapshot()
        # per-partition consumer lag (kafka_lag{partition="p"} gauges,
        # runtime/kafka.py): hw minus the cursor at the LAST fetch —
        # the cycling consumer seeks back to 0 at the high watermark,
        # so this oscillates over [0, log_records) rather than sitting
        # at 0; the field pins the gauge's plumbing end to end
        lag = {}
        for name, g in varz.get("gauges", {}).items():
            m = re.match(r'^kafka_lag\{partition="(\d+)"\}$', name)
            if m:
                lag[m.group(1)] = g["value"]
        if lag:
            line["kafka_lag"] = lag
        # the production-shaped path's stage decomposition: the ranked
        # answer to "where does the 545k-vs-1.09M kafka gap live" —
        # fetch/decode (consumer thread) next to encode/h2d/queue_wait/
        # readback/sink (score thread), one shared registry
        line["attribution"] = attr_mod.summary(km)
        line["varz"] = varz
        if drift_fields is not None:
            line["drift"] = drift_fields()
        return line
    finally:
        broker.close()


def run_rollout_drill(
    records: int = 20_000,
    fraction: float = 0.2,
    batch: int = 256,
    trees: int = 10,
    depth: int = 3,
    features: int = 4,
) -> dict:
    """``--rollout-drill``: correctness drill for the rollout control
    plane (rollout/), through the REAL DynamicScorer hot path on a real
    (small) GBM — also the perf-smoke tripwire's engine.

    Asserts the two properties a canary design most easily loses:

    - **split ratio** — the deterministic per-key hash split hands the
      candidate ``fraction`` of unpinned traffic within ±1% (absolute),
      measured from the ``rollout_candidate_records`` counter against
      the emitted predictions (which must also prove the candidate
      actually served: its outputs are bit-identical here, so the
      counter is the arbiter);
    - **zero shadow leakage** — a shadow-stage candidate scores mirrored
      traffic (``rollout_shadow_compared`` > 0, candidate latency
      observed) yet the emitted stream stays exactly one prediction per
      record and the candidate-records counter stays flat.

    Raises ``AssertionError`` on violation; → the drill's JSON line."""
    import numpy as np

    from flink_jpmml_tpu.assets_gen import gen_gbm
    from flink_jpmml_tpu.models.control import AddMessage, RolloutMessage
    from flink_jpmml_tpu.models.core import ModelId
    from flink_jpmml_tpu.runtime.sources import ControlSource
    from flink_jpmml_tpu.serving.scorer import DynamicScorer

    t0 = time.monotonic()
    tmp = tempfile.mkdtemp(prefix="fjt-rollout-drill-")
    pmml_v1 = gen_gbm(tmp, n_trees=trees, depth=depth, n_features=features)
    # the candidate is a byte-identical COPY: a healthy rollout (zero
    # disagreement), so any split-ratio error is pure routing
    pmml_v2 = os.path.join(tmp, "gbm_v2.pmml")
    pmml_v3 = os.path.join(tmp, "gbm_v3.pmml")
    with open(pmml_v1, "rb") as f:
        doc_bytes = f.read()
    for p in (pmml_v2, pmml_v3):
        with open(p, "wb") as f:
            f.write(doc_bytes)

    ctrl = ControlSource()
    sc = DynamicScorer(control=ctrl, batch_size=batch, auto_rollout=False)
    ctrl.push(AddMessage("drill", 1, pmml_v1, timestamp=time.time()))
    sc._drain_control()

    rng = np.random.default_rng(7)
    fields = [f"f{j}" for j in range(features)]
    data = rng.normal(0.0, 1.5, size=(records, features)).astype(np.float32)

    def event(i):
        rec = dict(zip(fields, data[i].tolist()))
        rec["_key"] = f"k{i}"
        return ("drill", rec)

    def run_phase():
        emitted = 0
        for off in range(0, records, batch):
            out = sc.finish(
                sc.submit([event(i) for i in range(off, off + batch)
                           if i < records])
            )
            emitted += len(out)
            assert all(not p.is_empty for p, _ in out), (
                "drill produced empty lanes"
            )
        return emitted

    def wait_warm(mid, timeout_s=120.0):
        deadline = time.monotonic() + timeout_s
        while sc.registry.model_if_warm(mid) is None:
            err = sc.registry.warm_error(mid)
            assert err is None, f"candidate warm failed: {err!r}"
            assert time.monotonic() < deadline, f"{mid} never warmed"
            time.sleep(0.02)

    wait_warm(ModelId("drill", 1))

    def counter(name_suffix):
        # read-side: snapshot lookup, not .counter() — the drill must
        # not register rollout series the scorer didn't emit
        return sc.metrics.struct_snapshot()["counters"].get(
            f'rollout_{name_suffix}{{model="drill"}}', 0.0
        )

    # -- canary phase ------------------------------------------------------
    ctrl.push(RolloutMessage(
        "drill", 2, "canary", time.time(), path=pmml_v2, fraction=fraction,
    ))
    sc._drain_control()
    wait_warm(ModelId("drill", 2))
    emitted = run_phase()
    assert emitted == records, (
        f"canary phase leaked/lost: emitted {emitted} != {records}"
    )
    cand = counter("candidate_records")
    share = cand / records
    assert abs(share - fraction) <= 0.01, (
        f"canary split {share:.4f} off target {fraction} by > 1% abs"
    )
    ctrl.push(RolloutMessage("drill", 2, "full", time.time()))

    # -- shadow phase ------------------------------------------------------
    ctrl.push(RolloutMessage(
        "drill", 3, "shadow", time.time(), path=pmml_v3,
    ))
    sc._drain_control()
    wait_warm(ModelId("drill", 3))
    cand_before = counter("candidate_records")
    compared_before = counter("shadow_compared")
    emitted = run_phase()
    assert emitted == records, (
        f"shadow phase leaked/lost: emitted {emitted} != {records}"
    )
    assert counter("candidate_records") == cand_before, (
        "shadow-stage candidate took live traffic"
    )
    shadow_compared = counter("shadow_compared") - compared_before
    assert shadow_compared > 0, "shadow stage mirrored nothing"
    assert counter("shadow_disagree") == 0, (
        "byte-identical candidate disagreed with the incumbent"
    )
    ctrl.push(RolloutMessage("drill", 3, "rollback", time.time()))
    sc._drain_control()

    # success path only: a FAILED drill's assertion leaves the generated
    # models on disk for inspection
    shutil.rmtree(tmp, ignore_errors=True)
    return {
        "metric": "rollout_drill",
        "ok": True,
        "records_per_phase": records,
        "canary_fraction": fraction,
        "canary_share": round(share, 5),
        "shadow_compared": int(shadow_compared),
        "shadow_disagree": 0,
        "sink_leakage": 0,
        "elapsed_s": round(time.monotonic() - t0, 3),
    }


def _drift_attach(metrics, model_obj):
    """Arm the drift plane (obs/drift.py) on a bench mode's registry
    when a stored baseline exists for the served model — env-
    independent, so every BENCH round on a baselined model carries the
    data-health family in its embedded varz (sketches + drift gauges;
    the registry scrape hook ticks the monitor inside the very
    ``struct_snapshot`` each mode embeds). → a zero-arg closure
    producing the compact per-model artifact fields, or None when no
    baseline is stored (the plane stays dark and the mode's struct is
    byte-identical to a pre-drift round's)."""
    from flink_jpmml_tpu.obs import drift as drift_mod

    label = drift_mod.model_label(model_obj)
    if not label or drift_mod.BaselineStore().load(label) is None:
        return None
    drift_mod.install(metrics)
    return lambda: drift_mod.artifact_fields(metrics)


def run_drift_drill(
    records_per_phase: int = 12_000,
    batch: int = 256,
    trees: int = 10,
    depth: int = 3,
    features: int = 6,
    perturb_feature: int = 1,
    control_feature: int = 0,
    shift: float = 4.0,
    psi_alarm: float = 0.25,
    min_n: int = 500,
    seed: int = 11,
) -> dict:
    """``--drift-drill``: seeded acceptance drill for the data-drift
    plane (obs/drift.py) — also the perf-smoke tripwire's engine.

    Geometry: TWO simulated workers (two registries sharing one
    compiled scorer — exactly how N processes share a model) score
    alternating batches through the REAL ``dispatch_quantized`` path
    with the drift plane armed at interval 0. Phase 1 profiles the
    reference distribution and snapshots it as the baseline (through
    the on-disk :class:`BaselineStore`, exercising save/load). Phase 2
    perturbs ONE feature's generator (a ``shift``·σ mean shift) and
    keeps scoring while a fleet :class:`DriftMonitor` windows the
    MERGED worker structs.

    Asserts the three properties the acceptance criteria pin:

    - **right feature, in the window** — the fleet monitor raises
      ``drift_alarm`` for the perturbed feature before the phase ends;
    - **quiet control** — the unperturbed control feature (and every
      other feature) never alarms;
    - **merge exactness** — the fleet-merged sketch's quantiles equal
      the quantiles of merging the per-worker sketch STATES directly
      (the DrJAX merge-exactly discipline, bitwise).

    Raises ``AssertionError`` on violation; → the drill's JSON line."""
    import jax
    import numpy as np

    from flink_jpmml_tpu.assets_gen import gen_gbm
    from flink_jpmml_tpu.compile import compile_pmml
    from flink_jpmml_tpu.obs import drift as drift_mod
    from flink_jpmml_tpu.pmml import parse_pmml_file
    from flink_jpmml_tpu.runtime.pipeline import dispatch_quantized
    from flink_jpmml_tpu.utils.metrics import (
        MetricsRegistry, QuantileSketch, merge_structs,
    )

    t0 = time.monotonic()
    tmp = tempfile.mkdtemp(prefix="fjt-drift-drill-")
    doc = parse_pmml_file(gen_gbm(
        tmp, n_trees=trees, depth=depth, n_features=features, seed=seed,
    ))
    cm = compile_pmml(doc, batch_size=batch)
    q = cm.quantized_scorer()
    assert q is not None, "drift drill GBM must be rank-wire eligible"
    label = q.model_hash
    fields = list(q.wire.fields)
    f_perturb = fields[perturb_feature]
    f_control = fields[control_feature]

    store = drift_mod.BaselineStore(os.path.join(tmp, "baselines"))
    regs = [MetricsRegistry(), MetricsRegistry()]
    planes = [
        # interval 0 (every batch) + budget off: the drill wants
        # deterministic coverage, not production amortization
        drift_mod.install(r, interval_s=0.0, budget_frac=0, store=store)
        for r in regs
    ]
    for p in planes:
        # worker monitors idle at drill speed; the FLEET monitor below
        # is the asserted surface
        p.monitor.min_n = min_n

    def fleet_struct() -> dict:
        return merge_structs([r.struct_snapshot() for r in regs])

    fleet_gauges = MetricsRegistry()
    monitor = drift_mod.DriftMonitor(
        struct_fn=fleet_struct,
        store=store,
        psi_alarm=psi_alarm,
        psi_clear=psi_alarm / 2.0,
        min_n=min_n,
        window_s=300.0,
        dwell_s=0.0,
        interval_s=0.0,
        gauge_metrics=fleet_gauges,
    )

    rng = np.random.default_rng(seed)
    means = np.arange(features, dtype=np.float32) * 0.5

    def gen_batch(perturbed: bool) -> np.ndarray:
        X = (rng.normal(0.0, 1.0, size=(batch, features))
             .astype(np.float32) + means[None, :])
        X[rng.random(size=X.shape) < 0.02] = np.nan  # missing lane
        if perturbed:
            X[:, perturb_feature] += shift
        return X

    def score_phase(perturbed: bool, tick):
        """Alternate batches across the two workers through the real
        dispatch path; → the batch index of the first perturbed-feature
        alarm (None outside phase 2)."""
        alarm_at = None
        n_batches = max(1, records_per_phase // batch)
        for b in range(n_batches):
            reg = regs[b % len(regs)]
            X = gen_batch(perturbed)
            out = dispatch_quantized(q, X, metrics=reg)
            jax.block_until_ready(out)
            # sink-side prediction sketching, as the pipelines do it
            drift_mod.plane_for(reg).record_predictions(q, out, batch)
            if tick:
                for tr in monitor.tick():
                    if (
                        alarm_at is None
                        and tr["transition"] == "alarm"
                        and tr["feature"] == f_perturb
                    ):
                        alarm_at = b
        return alarm_at

    # warm outside any measurement
    jax.block_until_ready(dispatch_quantized(
        q, gen_batch(False), metrics=MetricsRegistry()
    ))

    # -- phase 1: reference distribution + baseline snapshot ---------------
    score_phase(False, tick=False)
    fleet = fleet_struct()
    payloads = drift_mod.snapshot_from_struct(fleet)
    assert label in payloads and len(payloads[label]["features"]) == (
        features
    ), f"baseline incomplete: {list(payloads)}"
    store.save(label, payloads[label])
    loaded = store.load(label)
    assert loaded is not None, "baseline save/load roundtrip failed"
    monitor.set_baseline(label, loaded)

    # -- merge exactness: fleet merge == direct per-worker state merge -----
    states = [r.struct_snapshot().get("sketches") or {} for r in regs]
    checked = 0
    for name in sorted(set().union(*states)):
        per_worker = [s[name] for s in states if name in s]
        direct = QuantileSketch.from_state(per_worker[0])
        for st in per_worker[1:]:
            direct.merge(QuantileSketch.from_state(st))
        merged = QuantileSketch.from_state(fleet["sketches"][name])
        for qq in (0.01, 0.1, 0.25, 0.5, 0.75, 0.9, 0.99):
            mv, dv = merged.quantile(qq), direct.quantile(qq)
            assert mv == dv, (
                f"fleet merge inexact for {name} q={qq}: {mv} != {dv}"
            )
        checked += 1
    assert checked >= features + 1, checked  # features + predictions

    # -- phase 2: perturb one feature, watch the fleet monitor -------------
    alarm_batch = score_phase(True, tick=True)
    alarmed = {
        (a["model"], a["feature"]) for a in monitor.alarms()
    }
    assert (label, f_perturb) in alarmed, (
        f"perturbed feature {f_perturb} never alarmed "
        f"(alarmed={alarmed}, scores={monitor.scores()})"
    )
    feature_alarms = {f for (_, f) in alarmed if f is not None}
    assert feature_alarms == {f_perturb}, (
        f"alarm bled onto unperturbed features: {feature_alarms}"
    )
    scores = {
        feat: s for (lbl, feat), s in monitor.scores().items()
        if lbl == label
    }
    psi_control = scores.get(f_control)
    assert psi_control is not None and psi_control < psi_alarm, (
        f"control feature {f_control} drifted: psi={psi_control}"
    )

    # success path only: a FAILED drill's assertion leaves the tempdir
    # (model + baselines) on disk for inspection
    shutil.rmtree(tmp, ignore_errors=True)
    return {
        "metric": "drift_drill",
        "ok": True,
        "model": label,
        "records_per_phase": records_per_phase,
        "perturbed_feature": f_perturb,
        "control_feature": f_control,
        "alarm_batch": alarm_batch,
        "psi_perturbed": round(scores[f_perturb], 4),
        "psi_control": round(psi_control, 4),
        "merge_exact": True,
        "sketches_checked": checked,
        "drift": drift_mod.artifact_fields(fleet_gauges),
        "varz": fleet_struct(),
        "elapsed_s": round(time.monotonic() - t0, 3),
    }


def _parse_load_shape(spec: str) -> float:
    """``--load-shape`` → burst factor (0.0 = steady). Accepted:
    ``steady``, ``burst:2x``, ``burst:2`` (any float factor > 1)."""
    s = (spec or "steady").strip().lower()
    if s in ("", "steady"):
        return 0.0
    if s.startswith("burst:"):
        raw = s[len("burst:"):].rstrip("x")
        try:
            f = float(raw)
        except ValueError:
            raise SystemExit(f"bad --load-shape {spec!r}")
        if f <= 1.0:
            raise SystemExit(
                f"--load-shape burst factor must be > 1, got {spec!r}"
            )
        return f
    raise SystemExit(
        f"bad --load-shape {spec!r} (want steady | burst:<factor>x)"
    )


def run_burst_drill(
    base_rate: float = 8_000.0,
    burst_factor: float = 2.0,
    steady_s: float = 2.0,
    burst_s: float = 3.5,
    drain_timeout_s: float = 25.0,
    batch: int = 512,
    trees: int = 10,
    depth: int = 3,
    features: int = 4,
    capacity_frac: float = 0.7,
    scrape: bool = False,
) -> dict:
    """``--load-shape burst:2x``: the kafka burst-recovery drill
    (ROADMAP item 3's "per-partition lag gauges proving drain under 2×
    bursts"), also the perf-smoke freshness tripwire's engine.

    A paced producer appends timestamped rows to a real
    ``MiniKafkaBroker`` at ``base_rate``, bursts to ``base_rate ×
    burst_factor`` for ``burst_s``, then returns to base while the
    backlog drains. The consumer is the production ``BlockPipeline``
    over a ``KafkaBlockSource`` whose sink is *deadline-paced* to a
    capacity BETWEEN base and burst (``capacity_frac × burst``) — so
    lag provably builds under the burst and provably drains after,
    independent of host speed (the pacer absorbs scheduling spikes by
    catch-up instead of accumulating them).

    Asserted (→ ``ok`` + per-check fields):

    - the event-time ``watermark_lag_s`` peaks under the burst and
      returns below ``recover_threshold`` (2× the steady baseline)
      within ``drain_timeout_s`` of the burst ending;
    - ``pressure`` reaches ≥ 0.5 under the burst and decays below it
      after recovery;
    - ``lag_drain_eta_s`` reports a FINITE positive ETA at some point
      during the drain (and the burst itself drives the divergence
      signal).

    ``scrape=True`` additionally serves the live registry over a real
    ``ObsServer`` and captures a ``/metrics`` page mid-drain (the
    perf-smoke acceptance surface). → the drill's JSON line, with the
    registry's ``varz`` struct embedded like every bench mode."""
    import threading

    import numpy as np

    from flink_jpmml_tpu.assets_gen import gen_gbm
    from flink_jpmml_tpu.compile import compile_pmml
    from flink_jpmml_tpu.pmml import parse_pmml_file
    from flink_jpmml_tpu.runtime.block import BlockPipeline
    from flink_jpmml_tpu.runtime.kafka import (
        KafkaBlockSource, MiniKafkaBroker,
    )
    from flink_jpmml_tpu.utils.config import BatchConfig, RuntimeConfig
    from flink_jpmml_tpu.utils.metrics import MetricsRegistry

    t0 = time.monotonic()
    burst_rate = base_rate * burst_factor
    cap_target = capacity_frac * burst_rate
    assert base_rate < cap_target < burst_rate, (
        "drill geometry requires base < capacity < burst "
        f"({base_rate} / {cap_target} / {burst_rate})"
    )
    # short forecaster window so drain-ETA estimates turn over within
    # the drill's seconds-scale phases (restored on exit)
    prev_win = os.environ.get("FJT_LAG_WINDOW_S")
    os.environ["FJT_LAG_WINDOW_S"] = "2.0"
    broker = srv = None
    pipe = src = prod = None
    tmp = None
    stop_producer = threading.Event()
    try:
        tmp = tempfile.mkdtemp(prefix="fjt-burst-")
        doc = parse_pmml_file(
            gen_gbm(tmp, n_trees=trees, depth=depth, n_features=features)
        )
        cm = compile_pmml(doc, batch_size=batch)
        rng = np.random.default_rng(11)
        pool = rng.normal(0.0, 1.5, size=(4096, features)).astype(
            np.float32
        )

        broker = MiniKafkaBroker(topic="burst")
        km = MetricsRegistry()
        src = KafkaBlockSource(
            broker.host, broker.port, "burst",
            n_cols=features, max_wait_ms=20, metrics=km,
            # fetch.max.bytes analogue, ~one batch per fetch RPC: an
            # unbounded fetch would teleport the whole broker backlog
            # into one blocked ring push and the lag signals the drill
            # measures (kafka_lag, fetch-time watermark age) would
            # never see it
            max_bytes=24 * 1024,
        )

        scored = [0]
        next_free = [0.0]

        def sink(out, n, first_off):
            np.asarray(
                out.value if hasattr(out, "value")
                else out[0] if isinstance(out, tuple) else out
            )
            scored[0] += n
            # deadline pacer: the schedule advances n/cap per batch and
            # sleeps only when AHEAD of it, so transient host-scheduling
            # spikes are absorbed by catch-up instead of eroding the
            # drill's capacity floor. The credit is deliberately SHORT
            # (50 ms): a starved steady phase must not bank enough
            # schedule slack to swallow the burst surplus unthrottled
            t = time.monotonic()
            next_free[0] = max(next_free[0], t - 0.05) + n / cap_target
            wait = next_free[0] - time.monotonic()
            if wait > 0:
                time.sleep(wait)

        pipe = BlockPipeline(
            src, cm, sink,
            RuntimeConfig(batch=BatchConfig(
                size=batch, deadline_us=5000,
                # a small ring so producer backlog is VISIBLE as ring
                # occupancy (the pressure score's producer-side input)
                queue_capacity=2 * batch,
            )),
            metrics=km,
            # tight-buffer topology, deliberately: a deep in-flight
            # window + multi-chunk aggregation would swallow the whole
            # burst into host memory and the BROKER-side lag the drill
            # exists to exercise (kafka_lag, fetch-time watermark lag)
            # would never build — backpressure must reach the source.
            # The prefetch sidecar is one more such buffer (its handoff
            # queue absorbs several fetches of burst surplus at this
            # smoke scale), so the drill runs serial ingest: it
            # measures the LAG PLANE, not ingest throughput
            in_flight=1,
            max_dispatch_chunks=1,
            prefetch=False,
        )
        q = cm.quantized_scorer()
        if q is not None:
            import jax

            jax.block_until_ready(
                q.predict_wire(q.wire.encode(pool[:batch]))
            )
        else:
            cm.warmup()

        produced = [0]
        rate_now = [base_rate]

        def produce():
            CHUNK = 256
            nxt = time.monotonic()
            pos = 0
            while not stop_producer.is_set():
                nxt = max(nxt, time.monotonic() - 0.5) + CHUNK / rate_now[0]
                wait = nxt - time.monotonic()
                if wait > 0:
                    time.sleep(wait)
                    if stop_producer.is_set():
                        return
                start = (pos * CHUNK) % (pool.shape[0] - CHUNK)
                broker.append_rows(
                    pool[start : start + CHUNK],
                    timestamp_ms=int(time.time() * 1000),
                )
                produced[0] += CHUNK
                pos += 1

        samples = []

        def sample(tag: str) -> dict:
            g = km.struct_snapshot()["gauges"]

            def gv(name):
                v = g.get(name)
                return v.get("value") if isinstance(v, dict) else None

            s = {
                "t": round(time.monotonic() - t0, 3),
                "tag": tag,
                "wm_lag": gv('watermark_lag_s{partition="0"}'),
                "pressure": gv("pressure"),
                "eta": gv("lag_drain_eta_s"),
                "diverging": gv("lag_diverging"),
                "kafka_lag": gv('kafka_lag{partition="0"}'),
            }
            samples.append(s)
            return s

        def run_phase(seconds: float, tag: str) -> None:
            end = time.monotonic() + seconds
            while time.monotonic() < end:
                sample(tag)
                time.sleep(0.1)

        if scrape:
            from flink_jpmml_tpu.obs.server import ObsServer

            srv = ObsServer.for_registry(km)
        prod = threading.Thread(target=produce, daemon=True)
        pipe.start()
        prod.start()

        run_phase(steady_s, "steady")
        base_lags = [
            s["wm_lag"] for s in samples[-8:] if s["wm_lag"] is not None
        ]
        baseline = (
            sorted(base_lags)[len(base_lags) // 2] if base_lags else 0.2
        )
        recover_threshold = max(2.0 * baseline, 0.4)

        rate_now[0] = burst_rate
        run_phase(burst_s, "burst")
        rate_now[0] = base_rate
        t_drain0 = time.monotonic()
        recovery_s = None
        metrics_text = None
        while time.monotonic() - t_drain0 < drain_timeout_s:
            s = sample("drain")
            if (
                scrape and metrics_text is None
                and time.monotonic() - t_drain0 > 0.3
            ):
                import urllib.request

                with urllib.request.urlopen(
                    srv.url + "/metrics", timeout=10
                ) as r:
                    metrics_text = r.read().decode()
            if (
                s["wm_lag"] is not None
                and s["wm_lag"] <= recover_threshold
                and (s["kafka_lag"] or 0) <= batch
            ):
                recovery_s = round(time.monotonic() - t_drain0, 3)
                break
            time.sleep(0.1)
        if scrape and metrics_text is None:
            # an instant recovery never reached the mid-drain capture
            import urllib.request

            with urllib.request.urlopen(
                srv.url + "/metrics", timeout=10
            ) as r:
                metrics_text = r.read().decode()
        run_phase(2.5, "post")  # settle: pressure must decay too

        stop_producer.set()
        prod.join(timeout=5.0)
        pipe.stop()
        pipe.join(timeout=15.0)

        burst_drain = [
            s for s in samples if s["tag"] in ("burst", "drain")
        ]
        peak_wm = max(
            (s["wm_lag"] for s in burst_drain
             if s["wm_lag"] is not None),
            default=0.0,
        )
        peak_pressure = max(
            (s["pressure"] for s in burst_drain
             if s["pressure"] is not None),
            default=0.0,
        )
        post = sorted(
            s["pressure"] for s in samples[-6:]
            if s["pressure"] is not None
        )
        post_pressure = post[len(post) // 2] if post else 0.0
        finite_eta = [
            s["eta"] for s in samples if s["tag"] == "drain"
            and s["eta"] and s["eta"] > 0 and not s["diverging"]
            and (s["kafka_lag"] or 0) > 0
        ]
        checks = {
            "recovered": recovery_s is not None,
            "lag_built": peak_wm > 1.5 * recover_threshold,
            "pressure_peaked": peak_pressure >= 0.5,
            "pressure_decayed": post_pressure < 0.5,
            "eta_finite_during_drain": bool(finite_eta),
        }
        return {
            "metric": "burst_drill",
            "ok": all(checks.values()),
            "checks": checks,
            "load_shape": f"burst:{burst_factor:g}x",
            "base_rate": base_rate,
            "burst_rate": burst_rate,
            "capacity_target": cap_target,
            "baseline_wm_lag_s": round(baseline, 3),
            "recover_threshold_s": round(recover_threshold, 3),
            "peak_wm_lag_s": round(peak_wm, 3),
            "recovery_s": recovery_s,
            "peak_pressure": round(peak_pressure, 3),
            "post_pressure": round(post_pressure, 3),
            "drain_eta_s": (
                round(sorted(finite_eta)[len(finite_eta) // 2], 3)
                if finite_eta else None
            ),
            "records_produced": produced[0],
            "records_scored": scored[0],
            "elapsed_s": round(time.monotonic() - t0, 3),
            # the per-phase timeseries (one row per ~0.1 s): a failed
            # CI drill is debuggable from the artifact alone — when and
            # why lag/pressure misbehaved, not just that a check is
            # false
            "samples": samples,
            "metrics_scrape": metrics_text,
            # the scrape-format struct, like every bench mode: the
            # freshness gauges/staleness histogram land in the artifact
            "varz": km.struct_snapshot(),
        }
    finally:
        stop_producer.set()
        if prev_win is None:
            os.environ.pop("FJT_LAG_WINDOW_S", None)
        else:
            os.environ["FJT_LAG_WINDOW_S"] = prev_win
        if pipe is not None and pipe._threads:
            try:  # also covers the raised-mid-drill path
                pipe.stop()
                pipe.join(timeout=10.0)
            except Exception:
                pass
        for closer in (
            (lambda: src.close()) if src is not None else None,
            (lambda: broker.close()) if broker is not None else None,
            (lambda: srv.close()) if srv is not None else None,
        ):
            if closer is not None:
                try:
                    closer()
                except Exception:
                    pass
        if tmp is not None:  # the generated model: every CI run leaks
            shutil.rmtree(tmp, ignore_errors=True)  # a dir otherwise


def run_overload_drill(
    deadline_ms: float = None,
    batch: int = 128,
    block: int = 64,
    trees: int = 10,
    depth: int = 3,
    features: int = 4,
    base_frac: float = 0.8,
    surge_frac: float = 1.5,
    phase_s: float = 2.5,
    surge_s: float = 2.5,
    drain_timeout_s: float = 12.0,
) -> dict:
    """``--overload-drill``: the overload-resilience acceptance drill
    (ROADMAP item 5), through the production BlockPipeline with the
    full reflex arc attached — AdaptiveBatcher (deadline-capped
    dispatch aggregation, capacity model fit live), AdmissionController
    (pressure-driven hysteresis shedding), PressureMonitor + SLOTracker
    feeding them.

    Phases, against THIS host's measured capacity:

    1. **capacity** — unpaced pre-run (admission off) measures capacity
       and fits the batcher's ``c0 + c1·n`` model; the deadline (when
       not given) self-calibrates to 5× the predicted single-batch
       dispatch latency, floored at 100 ms so CI scheduling noise can't
       fake a breach.
    2. **base (80%)** — paced at ``base_frac × capacity``: asserts
       **p99 ≤ deadline** (one retry absorbs a shared-CI spike).
    3. **surge (150%)** — paced at ``surge_frac × capacity``: asserts
       **bounded p99** (≤ 10× max(deadline, base p99) — degradation by
       decision, not by unbounded queueing) and a **non-zero explicit
       ``shed_records`` counter** (the admission controller engaged).
    4. **recovery** — back at 80% after a bounded drain wait: asserts
       p99 returns **< 1.05× the base phase's p99**.

    Shed batches ride the FIFO window as no-op entries — offsets
    commit, the sink never sees them (the drill's arrival-matching
    discards their stamps, so shed records never pollute the latency
    percentiles either). Raises AssertionError on violation; → the
    drill's JSON line with the per-0.1 s telemetry timeline embedded."""
    import jax
    import numpy as np

    from flink_jpmml_tpu.assets_gen import gen_gbm
    from flink_jpmml_tpu.compile import compile_pmml
    from flink_jpmml_tpu.obs.slo import SLOTracker
    from flink_jpmml_tpu.pmml import parse_pmml_file
    from flink_jpmml_tpu.runtime.block import BlockPipeline, BlockSource
    from flink_jpmml_tpu.serving import overload as overload_mod
    from flink_jpmml_tpu.serving.overload import (
        AdaptiveBatcher, AdmissionController,
    )
    from flink_jpmml_tpu.utils.config import BatchConfig, RuntimeConfig
    from flink_jpmml_tpu.utils.metrics import MetricsRegistry

    t0 = time.monotonic()
    tmp = tempfile.mkdtemp(prefix="fjt-overload-")
    pipe = None
    try:
        doc = parse_pmml_file(
            gen_gbm(tmp, n_trees=trees, depth=depth, n_features=features)
        )
        cm = compile_pmml(doc, batch_size=batch)
        rng = np.random.default_rng(13)
        pool = rng.normal(0.0, 1.5, size=(4096, features)).astype(
            np.float32
        )
        km = MetricsRegistry()
        batcher = AdaptiveBatcher(
            metrics=km,
            min_records=batch, max_records=8 * batch,
            model=f"overload-gbm{trees}x{depth}x{features}",
            backend="drill",
            path=os.path.join(tmp, "capacity_model.json"),
        )
        # no deadline during phase 1, EXPLICITLY: deadline_s=None in
        # the constructor falls back to FJT_SLO_TARGET_MS, and an
        # operator's exported 2 ms knob would cap aggregation while
        # capacity is being MEASURED — depressing the number every
        # later operating point is derived from
        batcher.deadline_s = None
        # thresholds matched to the drill's ring geometry: the
        # occupancy gauge reads POST-drain (its 1.0 means "ingest
        # blocked"), so with dispatches of up to 4 aggregated batches
        # out of a 16-batch ring a saturated post-drain reading is
        # ~0.75+ — the production defaults (0.85/0.55) sit above what
        # this topology can express
        admission = AdmissionController(
            km, lanes=("block",), interval_s=0.1, dwell_s=0.4,
            on_threshold=0.7, off_threshold=0.35,
        )
        admission.enabled = False  # capacity phase measures, not sheds

        arrivals = collections.deque()  # (offset, t_arrival)
        cur_lats = [None]  # per-phase collection target (None = drop)
        rate_now = [None]  # None = unpaced

        class _PacedSource(BlockSource):
            exhausted = False

            def __init__(self):
                self._pos = 0
                self._off = 0
                self._next = None

            def poll(self):
                now = time.monotonic()
                rate = rate_now[0]
                if rate is not None:
                    if self._next is None:
                        self._next = now
                    if now < self._next:
                        return None
                n = pool.shape[0]
                if self._pos + block <= n:
                    blk = pool[self._pos:self._pos + block]
                    self._pos += block
                else:
                    self._pos = block
                    blk = pool[:block]
                off = self._off
                self._off += block
                arrivals.append((off, time.monotonic()))
                if rate is not None:
                    interval = block / rate
                    # no catch-up bursts past ~5 intervals of stall
                    self._next = max(
                        self._next + interval, now - 5 * interval
                    )
                return off, blk

            def seek(self, offset: int) -> None:
                pass

        scored = [0]

        def sink(out, n, first_off):
            np.asarray(
                out.value if hasattr(out, "value")
                else out[0] if isinstance(out, tuple) else out
            )
            scored[0] += n
            t = time.monotonic()
            # arrivals below first_off were SHED (their batches never
            # sank): discard without a latency sample — shed records
            # must not pollute the percentiles in either direction
            while arrivals and arrivals[0][0] < first_off:
                arrivals.popleft()
            end = first_off + n
            lats = cur_lats[0]
            while arrivals and arrivals[0][0] + block <= end:
                _, t_arr = arrivals.popleft()
                if lats is not None:
                    lats.append(t - t_arr)

        pipe = BlockPipeline(
            _PacedSource(), cm, sink,
            RuntimeConfig(batch=BatchConfig(
                size=batch, deadline_us=2000,
                # bounded ring: backlog is VISIBLE as ring occupancy
                # (the pressure input the admission controller sheds
                # on), deep enough that a post-drain reading under
                # saturation sits clearly above the on-threshold
                queue_capacity=16 * batch,
            )),
            metrics=km,
            in_flight=1,  # the latency operating point
            max_dispatch_chunks=8,
            batcher=batcher,
            admission=admission,
        )
        q = cm.quantized_scorer()
        if q is not None:
            # warm EVERY aggregation shape (one scan program per K):
            # a mid-capacity-phase compile would both depress the
            # measured capacity and poison the batcher's latency
            # observations with compile time
            for k in (1, 2, 4, 8):
                jax.block_until_ready(
                    q.predict_wire(q.wire.encode(pool[:k * batch]))
                )
        else:
            cm.warmup()

        samples = []

        def sample(tag: str) -> dict:
            g = km.struct_snapshot()["gauges"]

            def gv(name):
                v = g.get(name)
                return v.get("value") if isinstance(v, dict) else None

            s = {
                "t": round(time.monotonic() - t0, 3),
                "tag": tag,
                "pressure": gv("pressure"),
                "shed_level": gv("shed_level"),
                "ring": gv("ring_occupancy"),
                "adaptive_batch": gv("adaptive_batch"),
            }
            samples.append(s)
            return s

        def run_phase(seconds: float, tag: str, lats=None):
            cur_lats[0] = lats
            end = time.monotonic() + seconds
            while time.monotonic() < end:
                sample(tag)
                time.sleep(0.1)
            cur_lats[0] = None

        def p99(lats):
            s = sorted(lats)
            return s[_nearest_rank(0.99, len(s))] if s else None

        pipe.start()
        # -- phase 1: capacity + calibration -------------------------------
        run_phase(0.7, "capacity-ramp")  # thread spin-up settles first
        s0 = scored[0]
        t_cap = time.monotonic()
        run_phase(max(1.0, 0.5 * phase_s), "capacity")
        capacity = (scored[0] - s0) / (time.monotonic() - t_cap)
        assert capacity > 0, "capacity phase scored nothing"
        pred = batcher.predicted_latency(batch)
        if deadline_ms is None:
            # 5× the predicted single-batch dispatch, floored at 100 ms:
            # the floor keeps a loaded shared host's scheduling stalls
            # (tens of ms) from faking a deadline breach — the drill's
            # verdicts are about the CONTROL LOOP (shed before breach,
            # bounded degradation, recovery), and unbounded queueing at
            # 150% offered load overshoots any floor by seconds
            deadline_s = min(max(5.0 * (pred or 0.01), 0.1), 2.0)
        else:
            deadline_s = deadline_ms / 1e3
        batcher.deadline_s = deadline_s  # the cap arms from here on
        # deadline SLO tracking + the slo_deadline_ms gauge the
        # fjt-top --overload panel reads, ticked from the completion path
        pipe._slo = SLOTracker(
            km, source="batch_latency_s", deadline_s=deadline_s,
            windows=((5.0, 10.0),),
        )
        admission.enabled = True

        def paced_phase(frac, seconds, tag):
            rate_now[0] = frac * capacity
            lats = []
            run_phase(seconds, tag, lats)
            return lats

        def wait_drained(tag):
            """Settle at base rate until the backlog of the previous
            phase is gone and the shed gate is open — measured phases
            start from steady state, not from the prior phase's ring."""
            rate_now[0] = base_frac * capacity
            t_drain = time.monotonic()
            while time.monotonic() - t_drain < drain_timeout_s:
                sample(tag)
                if len(pipe._ring) < block and not admission.shedding:
                    break
                time.sleep(0.1)

        # -- phase 2: 80% of capacity — p99 ≤ deadline ----------------------
        wait_drained("settle")  # the unpaced capacity phase left a
        # saturated ring (and possibly a raised shed level) behind
        lats_base = paced_phase(base_frac, phase_s, "base")
        for retry in (1, 2):  # shared-host load spikes get two retries
            if p99(lats_base) is not None and p99(lats_base) <= deadline_s:
                break
            lats_base = paced_phase(
                base_frac, phase_s, f"base-retry{retry}"
            )
        p99_base = p99(lats_base)
        assert p99_base is not None, "base phase sank nothing"
        assert p99_base <= deadline_s, (
            f"p99 {1e3 * p99_base:.1f}ms > deadline "
            f"{1e3 * deadline_s:.1f}ms at {base_frac:.0%} capacity"
        )

        # -- phase 3: 150% — bounded p99 + explicit shed --------------------
        shed_before = sum(admission.counts()["shed"].values())
        lats_surge = paced_phase(surge_frac, surge_s, "surge")
        shed_records = sum(admission.counts()["shed"].values()) - shed_before
        p99_surge = p99(lats_surge)
        surge_bound = 10.0 * max(deadline_s, p99_base)
        assert shed_records > 0, (
            "150% offered load shed nothing — the admission controller "
            "never engaged"
        )
        # an empty lats_surge means the single lane shed the WHOLE
        # window — 100% explicit drop is still degradation by decision
        # (the multi-lane production config keeps high-priority traffic
        # flowing instead); what must never happen is served records
        # with unbounded queueing latency
        surge_all_shed = not lats_surge
        if not surge_all_shed:
            assert p99_surge <= surge_bound, (
                f"surge p99 {1e3 * p99_surge:.1f}ms not bounded by "
                f"{1e3 * surge_bound:.1f}ms — degradation by queueing, "
                "not by decision"
            )

        # -- phase 4: recovery at 80% after a bounded drain -----------------
        wait_drained("drain")
        lats_rec = paced_phase(base_frac, phase_s, "recovery")
        # <1.05x the steady-state baseline, with a 10 ms absolute noise
        # allowance: at a multi-ms CPU baseline the ratio alone is a
        # sub-ms tolerance — below shared-host scheduler noise — while
        # FAILED recovery (residual backlog) overshoots by the ring's
        # whole residence time, far past either term
        allowed = max(1.05 * p99_base, p99_base + 0.010)
        for retry in (1, 2):
            if p99(lats_rec) is not None and p99(lats_rec) < allowed:
                break
            lats_rec = paced_phase(
                base_frac, phase_s, f"recovery-retry{retry}"
            )
        p99_rec = p99(lats_rec)
        rec_disp = (
            f"{1e3 * p99_rec:.1f}ms" if p99_rec is not None else "none"
        )
        assert p99_rec is not None and p99_rec < allowed, (
            f"post-surge p99 {rec_disp} did not recover below "
            f"1.05x baseline ({1e3 * allowed:.1f}ms)"
        )

        pipe.stop()
        pipe.join(timeout=15.0)
        counts = admission.counts()
        struct = km.struct_snapshot()
        return {
            "metric": "overload_drill",
            "ok": True,
            "checks": {
                "p99_within_deadline_at_80pct": True,
                "shed_engaged_at_150pct": True,
                "p99_bounded_under_surge": True,
                "recovered_below_1p05x": True,
            },
            "capacity_rec_s": round(capacity, 1),
            "deadline_ms": round(1e3 * deadline_s, 3),
            "p99_base_ms": round(1e3 * p99_base, 3),
            "p99_surge_ms": (
                round(1e3 * p99_surge, 3) if p99_surge is not None
                else None
            ),
            "surge_all_shed": surge_all_shed,
            "p99_recovery_ms": round(1e3 * p99_rec, 3),
            "recovery_ratio": round(p99_rec / p99_base, 3),
            "shed_records": int(shed_records),
            "admitted_records": int(counts["admitted"]),
            "adaptive_max_records": batcher.max_records(),
            "capacity_model": batcher.state(),
            "overload": overload_mod.summary(struct),
            "records_scored": scored[0],
            "elapsed_s": round(time.monotonic() - t0, 3),
            "samples": samples,
            "varz": struct,
        }
    finally:
        if pipe is not None and pipe._threads:
            try:
                pipe.stop()
                pipe.join(timeout=10.0)
            except Exception:
                pass
        shutil.rmtree(tmp, ignore_errors=True)


_HISTORY_WORKER = r'''
import os, sys, time
sys.path.insert(0, sys.argv[2])
hist_dir = sys.argv[1]
from flink_jpmml_tpu.utils.metrics import MetricsRegistry
from flink_jpmml_tpu.obs import history
from flink_jpmml_tpu.serving.overload import (
    AdaptiveBatcher, AdmissionController,
)

m = MetricsRegistry()
# teach the capacity model a ~10k rec/s fit through the production
# observe() -> refit path (c1 = 1e-4 s/record -> capacity_rec_s = 10k),
# so the recorder's headroom telemetry reads the same gauge a serving
# worker would publish
batcher = AdaptiveBatcher(
    metrics=m, model="hist-drill", backend="cpu",
    path=os.path.join(hist_dir, "capacity_model.json"),
)
for _rep in range(6):
    for n in (64, 128, 256, 512):
        batcher.observe(n, 0.002 + 1e-4 * n)
admission = AdmissionController(
    m, lanes=("valid",), interval_s=0.02, dwell_s=0.05,
    on_threshold=0.6, off_threshold=0.3,
)
rec = history.install(
    m, directory=hist_dir, src="w0", interval_s=0.1,
    resolutions=(0.1, 1.0), start_thread=False,
)
c_in = m.counter("records_in")
c_out = m.counter("records_out")
g_p = m.gauge("pressure")
h_lat = m.histogram("batch_latency_s")
# synthetic members of the catalogued tenant_records{model="*"} family
# (names prebuilt: the serving plane owns the literal emission site)
tenants = ["seg%02d" % i for i in range(int(sys.argv[3]))]
tnames = ['tenant_records{model="%s"}' % t for t in tenants]
tcs = [m.counter(n) for n in tnames]
weights = [1.0 / (i + 1) for i in range(len(tenants))]
wsum = sum(weights)
capacity = 10000.0
print("READY", flush=True)
t0 = time.time()
while True:  # runs until the parent SIGKILLs it mid-incident
    now = time.time()
    el = now - t0
    # the incident: offered load ramps 25% -> 160% of fitted capacity
    # over ~1.1 s and holds there until the kill
    offered = capacity * min(0.25 + 1.2 * el, 1.6)
    n = max(1, int(offered * 0.02))
    c_in.inc(n)
    g_p.set(min(1.0, 0.625 * offered / capacity))
    admission.maybe_tick()
    if admission.admit("valid", n):
        c_out.inc(n)
        for w, tc in zip(weights, tcs):
            k = int(n * w / wsum)
            if k:
                tc.inc(k)
    h_lat.observe(0.002 + 1e-4 * n)
    rec.maybe_capture(now)
    time.sleep(0.02)
'''


def run_history_drill(
    tenants: int = 30,
    max_series: int = 8,
    zoo_scale: int = 1000,
    timeout_s: float = 60.0,
) -> dict:
    """``--history-drill``: the incident-replay acceptance drill. A
    child process arms the telemetry history plane (0.1 s frames
    cascading to 1 s, ``FJT_METRICS_MAX_SERIES`` governing its
    per-tenant families) and drives a real overload incident — the
    production AdmissionController shedding on a rising pressure gauge,
    the AdaptiveBatcher's fitted ``capacity_rec_s`` feeding per-frame
    headroom. The parent waits until the incident is in full swing
    (shed counters recorded, headroom collapsed), then **SIGKILLs the
    child mid-append** and reconstructs the whole story from the
    durable frames ALONE:

    - pressure rise, a non-zero shed counter trail, and the headroom
      collapse are all read back from disk across the process death;
    - the governed per-tenant table stays within the series bound in
      every frame, with an exact-sum ``_other`` fold;
    - the cascaded 1 s frames equal direct downsamples of the 0.1 s
      frames BITWISE (canonical JSON equality), and the fleet merge is
      invariant under adversarial input orderings — on this very run's
      frames, not synthetic ones;
    - ``fjt-replay`` renders the timeline and the zoo/overload panels
      from the directory;
    - separately, a ``zoo_scale``-tenant registry is governed through
      the same path a ``/metrics`` scrape and a heartbeat frame use,
      asserting the series bound with fleet totals exact.

    Raises AssertionError on violation; → the drill's JSON line."""
    import contextlib
    import io
    import random
    import signal

    from flink_jpmml_tpu import cli
    from flink_jpmml_tpu.obs import history
    from flink_jpmml_tpu.utils.metrics import (
        MetricsRegistry, govern_struct,
    )

    t0 = time.monotonic()
    tmp = tempfile.mkdtemp(prefix="fjt-history-")
    hist = os.path.join(tmp, "history")
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = None
    try:
        env = dict(os.environ)
        env["FJT_METRICS_MAX_SERIES"] = str(max_series)
        env.pop("FJT_HISTORY_DIR", None)  # the child gets an explicit dir
        env.pop("FJT_HISTORY_RES", None)
        env.pop("FJT_HISTORY_INTERVAL_S", None)
        proc = subprocess.Popen(
            [sys.executable, "-c", _HISTORY_WORKER, hist, repo,
             str(tenants)],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
        )

        def _gv(frame, name):
            g = (frame.get("gauges") or {}).get(name)
            if not isinstance(g, dict):
                return None
            return history.combined_last(name, g.get("last"))

        def _shed_total(frames):
            tot = 0.0
            for f in frames:
                for n, v in (f.get("counters") or {}).items():
                    if n.split("{", 1)[0] == "shed_records":
                        tot += history.wire_float(v)
            return tot

        # wait for the incident to be fully on disk: shed counters
        # recorded AND headroom collapsed in some frame
        deadline = time.monotonic() + timeout_s
        frames = []
        while time.monotonic() < deadline:
            if proc.poll() is not None:
                err = proc.stderr.read().decode(errors="replace")
                raise AssertionError(
                    f"history worker died rc={proc.returncode}: "
                    f"{err[-2000:]}"
                )
            frames = history.read_frames(hist, res=0.1)
            if (
                _shed_total(frames) > 0
                and any(
                    (h := _gv(f, "headroom_frac")) is not None
                    and h < 0.1
                    for f in frames
                )
                and any(
                    (p := _gv(f, "pressure")) is not None and p > 0.9
                    for f in frames
                )
            ):
                break
            time.sleep(0.1)
        else:
            raise AssertionError(
                f"incident never fully recorded within {timeout_s}s "
                f"({len(frames)} frames, shed={_shed_total(frames)})"
            )
        # mid-incident, mid-append-cadence: the torn-tail case
        os.kill(proc.pid, signal.SIGKILL)
        proc.wait(timeout=10.0)

        # -- everything below reads the durable frames ALONE ---------------
        fine = history.read_frames(hist, res=0.1)
        assert len(fine) >= 5, f"only {len(fine)} fine frames survived"

        # pressure rise + headroom collapse, reconstructed from disk
        p_first = _gv(fine[0], "pressure")
        p_peak = max(
            (p for f in fine if (p := _gv(f, "pressure")) is not None),
            default=None,
        )
        assert p_first is not None and p_peak is not None
        assert p_first < 0.35 and p_peak > 0.9, (
            f"pressure rise not reconstructed: first {p_first} "
            f"peak {p_peak}"
        )
        heads = [
            h for f in fine
            if (h := _gv(f, "headroom_frac")) is not None
        ]
        assert heads and heads[0] > 0.3 and min(heads) < 0.1, (
            f"headroom collapse not reconstructed: {heads[:3]}... "
            f"min {min(heads) if heads else None}"
        )
        shed_records = _shed_total(fine)
        assert shed_records > 0, "no shed counters in the durable frames"

        # the governed per-tenant table: bounded in EVERY frame, with
        # the exact-sum _other fold present once folding began
        tseries_max = 0
        saw_other = False
        for f in fine:
            tnames = [
                n for n in (f.get("counters") or {})
                if n.split("{", 1)[0] == "tenant_records"
            ]
            tseries_max = max(tseries_max, len(tnames))
            saw_other = saw_other or any(
                '="_other"' in n for n in tnames
            )
        assert 0 < tseries_max <= max_series, (
            f"tenant series bound violated: {tseries_max} > {max_series}"
        )
        assert saw_other, "governor never folded a _other series"

        # bitwise commutation ON THIS RUN: cascaded 1 s frames vs
        # direct downsamples of the fine frames, slot by slot
        coarse = history.read_frames(hist, res=1.0)
        direct = {
            int(f["t0"] // 1.0): f
            for f in history.downsample(fine, 1.0)
        }
        matched = 0
        for f in coarse:
            d = direct.get(int(f["t0"] // 1.0))
            assert d is not None, f"cascaded slot {f['t0']} not in direct"
            assert history.canonical(f) == history.canonical(d), (
                f"cascade != direct downsample at t0={f['t0']}"
            )
            matched += 1
        assert matched >= 1, "no complete coarse slot survived the kill"

        # merge invariance under adversarial orderings, same frames
        shuffled = list(fine)
        random.Random(11).shuffle(shuffled)
        assert history.canonical(
            history.merge_frames(fine)
        ) == history.canonical(history.merge_frames(shuffled)), (
            "merge not order-invariant on the drill's own frames"
        )

        # fjt-replay renders the incident from the directory
        buf_zoo, buf_over = io.StringIO(), io.StringIO()
        with contextlib.redirect_stdout(buf_zoo):
            rc = cli.replay_main([hist, "--step", "1", "--panel", "zoo"])
        assert rc == 0, f"fjt-replay --panel zoo rc={rc}"
        out_zoo = buf_zoo.getvalue()
        assert "seg00" in out_zoo and "_other" in out_zoo, (
            f"replayed zoo table missing top tenant / _other:\n{out_zoo}"
        )
        with contextlib.redirect_stdout(buf_over):
            rc = cli.replay_main(
                [hist, "--step", "1", "--panel", "overload"]
            )
        assert rc == 0, f"fjt-replay --panel overload rc={rc}"
        assert "shed" in buf_over.getvalue(), (
            "replayed overload panel missing shed counters"
        )

        # zoo-scale governor: 1000 tenants through the same fold the
        # /metrics page and the heartbeat frame apply — bounded series,
        # fleet totals EXACT
        zm = MetricsRegistry()
        for i in range(zoo_scale):
            zname = 'tenant_records{model="z%04d"}' % i
            zm.counter(zname).inc(i + 1)
        governed = govern_struct(
            zm.struct_snapshot(), max_series=max_series
        )
        znames = [
            n for n in governed["counters"]
            if n.split("{", 1)[0] == "tenant_records"
        ]
        assert len(znames) == max_series, (
            f"zoo-scale page not bounded: {len(znames)} series"
        )
        ztotal = sum(governed["counters"][n] for n in znames)
        assert ztotal == zoo_scale * (zoo_scale + 1) / 2, (
            f"governed fleet total inexact: {ztotal}"
        )

        return {
            "metric": "history_drill",
            "ok": True,
            "checks": {
                "survives_sigkill_mid_append": True,
                "pressure_rise_reconstructed": True,
                "headroom_collapse_reconstructed": True,
                "shed_trail_reconstructed": True,
                "tenant_table_governed": True,
                "cascade_bitwise_equals_direct": True,
                "merge_order_invariant": True,
                "replay_renders_panels": True,
                "zoo_scale_totals_exact": True,
            },
            "fine_frames": len(fine),
            "coarse_frames_matched": matched,
            "shed_records": int(shed_records),
            "pressure_first": round(p_first, 4),
            "pressure_peak": round(p_peak, 4),
            "headroom_first": round(heads[0], 4),
            "headroom_min": round(min(heads), 4),
            "tenant_series_max": tseries_max,
            "max_series": max_series,
            "zoo_scale": zoo_scale,
            "elapsed_s": round(time.monotonic() - t0, 3),
        }
    finally:
        if proc is not None and proc.poll() is None:
            try:
                proc.kill()
                proc.wait(timeout=5.0)
            except Exception:
                pass
        shutil.rmtree(tmp, ignore_errors=True)


_DEVFAULT_WORKER = r'''
import os, sys, time
# per-incarnation fault seed BEFORE the package imports (env faults arm
# at import); each incarnation re-arms its own device-fault counts, so
# a restart mid-outage resumes INTO an outage — the hard case
os.environ["FJT_FAULTS"] = os.environ.get("FJT_FAULTS", "").replace(
    "PIDSEED", str(os.getpid())
)
sys.path.insert(0, sys.argv[8])
import jax
import numpy as np
from flink_jpmml_tpu.compile import compile_pmml
from flink_jpmml_tpu.pmml import parse_pmml_file
from flink_jpmml_tpu.runtime.block import BlockPipeline
from flink_jpmml_tpu.runtime.checkpoint import CheckpointManager
from flink_jpmml_tpu.runtime.dlq import DeadLetterQueue
from flink_jpmml_tpu.runtime.kafka import KafkaBlockSource
from flink_jpmml_tpu.runtime.supervisor import reporter_from_env
from flink_jpmml_tpu.serving.overload import AdaptiveBatcher
from flink_jpmml_tpu.utils.config import BatchConfig, RuntimeConfig
from flink_jpmml_tpu.utils.metrics import MetricsRegistry

host, port, topic, pmml, ckdir, outfile, total = sys.argv[1:8]
total = int(total)
m = MetricsRegistry()
rep = reporter_from_env(metrics=m)
dlq = DeadLetterQueue(os.path.join(ckdir, "dlq"), metrics=m)
src = KafkaBlockSource(
    host, int(port), topic, n_cols=6, max_wait_ms=20, metrics=m, dlq=dlq,
)
cm = compile_pmml(parse_pmml_file(pmml), batch_size=64)
batcher = AdaptiveBatcher(metrics=m, model="drill", backend="cpu")
out = open(outfile, "a", buffering=1)
wm = m.gauge("watermark_ts")

def sink(o, n, first_off):
    out.write("E %d %d %d %.3f\n" % (os.getpid(), first_off, n, wm.get()))

pipe = BlockPipeline(
    src, cm, sink,
    RuntimeConfig(
        batch=BatchConfig(size=64, deadline_us=2000, queue_capacity=4096),
        checkpoint_interval_s=0.05,
    ),
    metrics=m,
    checkpoint=CheckpointManager(ckdir),
    dlq=dlq,
    batcher=batcher,
    max_dispatch_chunks=4,
)
pipe.restore()
out.write("R %d %d\n" % (os.getpid(), pipe.committed_offset))
pipe.start()

def telemetry():
    snap = m.struct_snapshot()
    c = snap.get("counters", {})
    g = snap.get("gauges", {})
    fstate = max(
        [float(v.get("value", 0.0)) for k, v in g.items()
         if k.startswith("failover_state")] or [0.0]
    )
    out.write("F %d %.0f %.0f %.0f %.1f\n" % (
        os.getpid(),
        c.get("fallback_records", 0), c.get("redispatch_records", 0),
        c.get("oom_shrinks", 0), fstate,
    ))

last_t = 0.0
while pipe.committed_offset < total and pipe._error is None:
    time.sleep(0.02)
    if time.monotonic() - last_t >= 0.1:
        last_t = time.monotonic()
        telemetry()
pipe.stop()
pipe.join(timeout=30.0)
telemetry()
p99 = m.histogram("batch_latency_s").quantile(0.99)
out.write("P %d %.3f\n" % (os.getpid(), -1.0 if p99 is None else p99 * 1e3))
out.write("D %d %d\n" % (os.getpid(), pipe.committed_offset))
src.close()
out.close()
'''


def run_zoo_bench(
    registered: int = 1000,
    hot: int = 100,
    records_per_hot: int = 1024,
    batch: int = 256,
    docs: int = 10,
    per_round: int = 256,
) -> dict:
    """``--zoo``: the multi-tenant packed-scoring capture + acceptance
    drill, through the REAL DynamicScorer hot path.

    Geometry: ``registered`` tiny GBMs served (cycling ``docs`` distinct
    documents, so the process-level reader cache amortises the
    compiles exactly as a real zoo does), ``hot`` of them receiving
    interleaved traffic. Three scorers run the same event stream:

    - **baseline** — ONE tenant, the classic single-model hand loop
      (the per-chip capture's shape): the throughput yardstick;
    - **solo oracle** — the hot tenants with the zoo manager OFF (every
      per-model group dispatches alone): the byte-parity oracle;
    - **zoo** — the same tenants with ``zoo=True``: pack-eligible
      groups ride ONE launch per planned pack.

    Asserts the acceptance criteria the packed path must hold:

    - **byte parity / zero leakage** — every (tenant, record) prediction
      from the packed run equals the solo oracle's exactly;
    - **aggregate throughput** — the packed multi-tenant run sustains
      >= 75% of the single-model hand loop's records/s;
    - **planes still keyed per tenant, same run** — a canary rollout on
      one tenant books its candidate counter; the drift plane sketches
      predictions for >= 2 distinct served documents; an injected
      device fault mid-pack redispatches and parity still holds.

    Raises ``AssertionError`` on violation; → the capture's JSON line."""
    import numpy as np

    from flink_jpmml_tpu.assets_gen import gen_gbm
    from flink_jpmml_tpu.models.control import AddMessage, RolloutMessage
    from flink_jpmml_tpu.models.core import ModelId
    from flink_jpmml_tpu.obs import drift as drift_mod
    from flink_jpmml_tpu.runtime import faults
    from flink_jpmml_tpu.runtime.sources import ControlSource
    from flink_jpmml_tpu.serving.scorer import DynamicScorer

    t0 = time.monotonic()
    tmp = tempfile.mkdtemp(prefix="fjt-zoo-bench-")
    features = 4
    doc_paths = [
        gen_gbm(tmp, n_trees=6 + d, depth=3, n_features=features,
                seed=100 + d, name=f"zoo{d}")
        for d in range(docs)
    ]
    fields = [f"f{j}" for j in range(features)]
    names = [f"t{i:04d}" for i in range(registered)]
    # the hot set must SPAN the document mix (a strided pick of
    # registered//hot collides with the docs cycle and serves one
    # document 100 times — no heterogeneity, nothing for the pack
    # search or the drift plane to discriminate); the prefix cycles
    # all ``docs`` shapes evenly and which 100 of the 1,000 are hot is
    # immaterial to the registry
    hot_names = names[:hot]

    rng = np.random.default_rng(23)
    data = rng.normal(0.0, 1.5, size=(
        hot * records_per_hot, features)).astype(np.float32)
    data[rng.random(size=data.shape) < 0.01] = np.nan  # missing lanes

    def event(name, i):
        rec = dict(zip(fields, data[i % len(data)].tolist()))
        rec["_key"] = f"k{i}"
        return (name, rec)

    rounds = max(1, records_per_hot // per_round)
    round_batches = []  # each: one interleaved multi-tenant submit list
    cursor = 0
    for _ in range(rounds):
        ev = []
        for name in hot_names:
            ev.extend(event(name, cursor + j) for j in range(per_round))
            cursor += per_round
        round_batches.append(ev)
    total = sum(len(ev) for ev in round_batches)

    def wait_warm(sc, mids, timeout_s=600.0):
        deadline = time.monotonic() + timeout_s
        for mid in mids:
            while sc.registry.model_if_warm(mid) is None:
                err = sc.registry.warm_error(mid)
                assert err is None, f"{mid.key()} warm failed: {err!r}"
                assert time.monotonic() < deadline, (
                    f"{mid.key()} never warmed"
                )
                time.sleep(0.01)

    def sig(p):
        # byte-level identity signature: empties collapse equal, a live
        # score compares on its exact float (decode is deterministic)
        if p.is_empty:
            return None
        t = p.target
        return (p.score.value, None if t is None else repr(t))

    def run_stream(sc, batches):
        sigs = []
        for ev in batches:
            out = sc.finish(sc.submit(ev))
            sigs.extend(sig(p) for p, _ in out)
        return sigs

    # -- build all three scorers, then time them symmetrically -------------
    ctrl_b = ControlSource()
    sc_b = DynamicScorer(control=ctrl_b, batch_size=batch,
                         auto_rollout=False)
    # the yardstick serves the MEDIAN document of the fleet mix: the
    # fleet's tree counts span docs[0]..docs[-1], and comparing the
    # heterogeneous packed run against its cheapest member would fold
    # the fleet's extra per-record compute into the "packing tax"
    ctrl_b.push(AddMessage("base", 1, doc_paths[docs // 2],
                           timestamp=time.time()))
    sc_b._drain_control()

    ctrl_s = ControlSource()
    sc_s = DynamicScorer(control=ctrl_s, batch_size=batch,
                         auto_rollout=False)
    for name in hot_names:
        d = names.index(name) % docs
        ctrl_s.push(AddMessage(name, 1, doc_paths[d],
                               timestamp=time.time()))
    sc_s._drain_control()

    ctrl_z = ControlSource()
    sc_z = DynamicScorer(control=ctrl_z, batch_size=batch,
                         auto_rollout=False, zoo=True)
    for i, name in enumerate(names):
        ctrl_z.push(AddMessage(name, 1, doc_paths[i % docs],
                               timestamp=time.time()))
    sc_z._drain_control()

    # steady-state capture: wait out EVERY registration's background
    # warm (the reader cache makes the cold 900 cheap), or the timed
    # runs pay compile contention a steady-state server never sees
    wait_warm(sc_b, [ModelId("base", 1)])
    wait_warm(sc_s, [ModelId(n, 1) for n in hot_names])
    wait_warm(sc_z, [ModelId(n, 1) for n in names])

    # big-registry serving hygiene, applied BEFORE EACH timed phase
    # alike: the compiled documents (and each earlier phase's retained
    # results) are immortal for the rest of the capture, and cyclic-GC
    # gen-2 pauses otherwise scale with whatever the heap has
    # accumulated by the time a phase runs (~40% of the 1,000-model
    # hot loop; the LAST phase would pay the most, skewing the ratio)
    # — freezing the surviving graph out of collector traversal is
    # standard large-heap server practice
    import gc

    def settle():
        gc.collect()
        gc.freeze()

    # -- baseline: single-model hand loop ----------------------------------
    base_batches = [
        [event("base", i + j) for j in range(batch)]
        for i in range(0, total, batch)
    ]
    run_stream(sc_b, base_batches[:4])  # warm the loop itself
    settle()
    tb = time.monotonic()
    run_stream(sc_b, base_batches)
    base_rps = total / (time.monotonic() - tb)

    # -- solo oracle: hot tenants, zoo OFF ---------------------------------
    run_stream(sc_s, round_batches[:1])
    settle()
    ts = time.monotonic()
    solo_sigs = run_stream(sc_s, round_batches)
    solo_rps = total / (time.monotonic() - ts)

    # -- zoo: every tenant registered, hot ones packed ---------------------
    run_stream(sc_z, round_batches[:1])  # plan + pack warm outside timing
    settle()
    tz = time.monotonic()
    zoo_sigs = run_stream(sc_z, round_batches)
    zoo_rps = total / (time.monotonic() - tz)

    counters = sc_z.metrics.struct_snapshot()["counters"]
    pack_dispatches = counters.get("pack_dispatches", 0)
    assert pack_dispatches > 0, "zoo run never packed a dispatch"

    # the timed replay covers every (tenant, record) pair exactly once
    assert len(zoo_sigs) == total == len(solo_sigs), (
        f"zoo stream lost records: {len(zoo_sigs)} vs {total}"
    )
    mismatches = sum(1 for a, b in zip(zoo_sigs, solo_sigs) if a != b)
    assert mismatches == 0, (
        f"packed-vs-solo parity broke on {mismatches}/{total} records "
        "(cross-tenant leakage or reduction-order drift)"
    )

    ratio = zoo_rps / base_rps
    assert ratio >= 0.75, (
        f"aggregate packed throughput {zoo_rps:,.0f} rec/s fell below "
        f"75% of the single-model hand loop ({base_rps:,.0f} rec/s)"
    )

    # -- rollout plane, keyed per tenant, same run -------------------------
    rt = hot_names[0]
    cand = os.path.join(tmp, "cand.pmml")
    with open(doc_paths[names.index(rt) % docs], "rb") as f:
        body = f.read()
    with open(cand, "wb") as f:
        f.write(body)
    ctrl_z.push(RolloutMessage(rt, 2, "canary", time.time(), path=cand,
                               fraction=0.3))
    sc_z._drain_control()
    wait_warm(sc_z, [ModelId(rt, 2)])
    run_stream(sc_z, [[event(rt, i) for i in range(batch * 4)]])
    counters = sc_z.metrics.struct_snapshot()["counters"]
    cand_records = counters.get(
        f'rollout_candidate_records{{model="{rt}"}}', 0
    )
    assert cand_records > 0, "per-tenant canary served no records"
    ctrl_z.push(RolloutMessage(rt, 2, "rollback", time.time()))
    sc_z._drain_control()

    # -- drift plane, per served document, same run ------------------------
    drift_mod.install(sc_z.metrics, interval_s=0.0, budget_frac=0)
    run_stream(sc_z, round_batches[:1])
    sketches = sc_z.metrics.struct_snapshot().get("sketches") or {}
    drift_labels = {
        m.group(1)
        for m in (drift_mod._PRED_SKETCH.match(k) for k in sketches)
        if m
    }
    assert len(drift_labels) >= 2, (
        f"drift plane sketched {len(drift_labels)} served documents"
    )

    # -- failover: device fault mid-pack, parity preserved -----------------
    before = sc_z.metrics.struct_snapshot()["counters"].get(
        "redispatch_records", 0
    )
    faults.inject("device_error", site="device_readback", n=1)
    try:
        fault_sigs = run_stream(sc_z, round_batches[:1])
    finally:
        faults.clear()
    after = sc_z.metrics.struct_snapshot()["counters"].get(
        "redispatch_records", 0
    )
    assert after > before, "injected pack fault never redispatched"
    n0 = len(round_batches[0])
    assert fault_sigs == solo_sigs[:n0], (
        "per-tenant parity broke under a mid-pack device fault"
    )

    zsnap = sc_z._zoo.snapshot()
    gauges = sc_z.metrics.struct_snapshot().get("gauges") or {}
    shutil.rmtree(tmp, ignore_errors=True)
    return {
        "metric": "zoo_bench",
        "ok": True,
        "registered": registered,
        "hot": hot,
        "distinct_documents": docs,
        "records": total,
        "baseline_rps": round(base_rps, 1),
        "solo_multi_rps": round(solo_rps, 1),
        "zoo_rps": round(zoo_rps, 1),
        "zoo_vs_baseline": round(ratio, 4),
        "parity_mismatches": 0,
        "leakage": 0,
        "pack_dispatches": int(pack_dispatches),
        "pack_occupancy": gauges.get("pack_occupancy"),
        "pack_pad_waste": gauges.get("pack_pad_waste"),
        "resident_packs": zsnap["resident_packs"],
        "resident_bytes": zsnap["resident_bytes"],
        "warm_pool_hits": int(counters.get("warm_pool_hits", 0)),
        "zoo_evictions": int(counters.get("zoo_evictions", 0)),
        "rollout_candidate_records": int(cand_records),
        "drift_documents": len(drift_labels),
        "fault_redispatched": int(after - before),
        "elapsed_s": round(time.monotonic() - t0, 3),
    }


_STATEFUL_WORKER = r'''
import os, sys, time
sys.path.insert(0, sys.argv[10])
import jax
import numpy as np
from flink_jpmml_tpu.compile import compile_pmml
from flink_jpmml_tpu.pmml import parse_pmml_file
from flink_jpmml_tpu.runtime.block import BlockPipeline, FiniteBlockSource
from flink_jpmml_tpu.runtime.checkpoint import CheckpointManager
from flink_jpmml_tpu.runtime import state as state_mod
from flink_jpmml_tpu.utils.config import BatchConfig, RuntimeConfig

pmml, ckdir, outpath, seed, records, keys, capacity, B, feats = sys.argv[1:10]
seed, records, keys = int(seed), int(records), int(keys)
capacity, B, feats = int(capacity), int(B), int(feats)
# every incarnation regenerates the IDENTICAL stream from the seed: the
# kill-phase parity claim is about the STATE plane, not the source
rng = np.random.default_rng(seed)
data = rng.normal(0.0, 1.0, size=(records, feats)).astype(np.float32)
data[:, 0] = ((rng.zipf(1.3, size=records) - 1) % keys).astype(np.float32)
cm = compile_pmml(parse_pmml_file(pmml), batch_size=B)
pipe = BlockPipeline(
    # block == dispatch batch and a fill deadline far past any
    # scheduler hiccup: every dispatch is one aligned B-sized block, so
    # a restore at a committed (block-aligned) offset replays the exact
    # batch boundaries of the single-life run — the byte-parity
    # precondition (scatter-add order inside a batch is fixed; across a
    # DIFFERENT split it would be float-reassociated)
    FiniteBlockSource(data, block_size=B), cm,
    lambda out, n, first_off: None,
    RuntimeConfig(
        batch=BatchConfig(size=B, deadline_us=5_000_000),
        checkpoint_interval_s=0.05,
    ),
    checkpoint=CheckpointManager(ckdir),
    state=state_mod.StateSpec(capacity=capacity, key_col=0),
)
pipe.restore()
pipe.start()
while pipe.committed_offset < records and pipe._error is None:
    time.sleep(0.02)
pipe.stop()
pipe.join(timeout=30.0)
if pipe._error is not None:
    raise SystemExit(f"stateful worker pipeline error: {pipe._error!r}")
tbl = pipe._state
jax.block_until_ready(tbl.values)
tmp_out = outpath + ".tmp"
np.savez(tmp_out, values=np.asarray(tbl.values),
         applied_hi=np.int64(tbl.applied_hi))
os.replace(tmp_out + ".npz", outpath)  # np.savez appends .npz
'''


def _stateful_kill_parity(
    tmp: str,
    pmml: str,
    records: int,
    keys: int,
    capacity: int,
    batch: int,
    kills: int,
    seed: int,
    features: int,
    timeout_s: float = 240.0,
) -> dict:
    """The ``--stateful`` capture's SIGKILL phase: the same keyed
    stream scored twice through the production BlockPipeline with the
    state table + checkpoints armed — once uninterrupted (the
    single-life reference), once SIGKILLed mid-stream ``kills`` times
    with each incarnation restoring from the latest checkpoint (offsets
    + npz state sidecar). The two final tables must match BYTE-exactly:
    restore rehydrates the full mirror (values, keys, touch, epoch,
    ``applied_hi``), replayed offsets below ``skip_until`` bypass to
    the scratch row, and block==batch alignment keeps every replayed
    scatter-add in its original batch. Workers are JAX_PLATFORMS=cpu
    subprocesses (a chip belongs to one process; the parent may hold
    it)."""
    import signal

    import numpy as np

    from flink_jpmml_tpu.runtime.checkpoint import CheckpointManager

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

    def run_life(tag: str, kill_targets: list) -> tuple:
        """→ (final npz path, incarnations). Spawns the worker, SIGKILLs
        it once committed progress passes each target, then lets the
        final incarnation drain."""
        ckdir = os.path.join(tmp, f"ck-{tag}")
        outpath = os.path.join(tmp, f"state-{tag}.npz")
        argv = [
            sys.executable, "-c", _STATEFUL_WORKER,
            pmml, ckdir, outpath, str(seed), str(records),
            str(keys), str(capacity), str(batch), str(features), repo,
        ]
        env = dict(os.environ)
        env.update({
            "JAX_PLATFORMS": "cpu",
            "FJT_AUTOTUNE_CACHE": os.path.join(tmp, "autotune"),
        })

        def committed() -> int:
            try:
                st = CheckpointManager(ckdir).load_latest()
                return int(st["source_offset"]) if st else 0
            except Exception:
                return 0

        incarnations = 0
        pending = list(kill_targets)
        deadline = time.monotonic() + timeout_s
        while True:
            assert time.monotonic() < deadline, (
                f"stateful kill phase ({tag}) did not drain within "
                f"{timeout_s}s (committed {committed()}/{records})"
            )
            proc = subprocess.Popen(
                argv, env=env, stdout=subprocess.DEVNULL,
                stderr=subprocess.PIPE, text=True,
            )
            incarnations += 1
            if pending:
                target = pending[0]
                while (
                    proc.poll() is None
                    and committed() < target
                    and time.monotonic() < deadline
                ):
                    time.sleep(0.02)
                if proc.poll() is None:
                    os.kill(proc.pid, signal.SIGKILL)
                    proc.wait(timeout=10)
                    pending.pop(0)
                    continue
                # the worker finished before the target: no more kills
                pending.clear()
            try:
                proc.wait(timeout=max(deadline - time.monotonic(), 5.0))
            except subprocess.TimeoutExpired:
                proc.kill()
                raise AssertionError(
                    f"stateful worker ({tag}) wedged past the deadline"
                )
            assert proc.returncode == 0, (
                f"stateful worker ({tag}) rc={proc.returncode}: "
                f"{(proc.stderr.read() or '')[-800:]}"
            )
            assert os.path.exists(outpath), (
                f"stateful worker ({tag}) exited 0 without its table dump"
            )
            return outpath, incarnations

    ref_path, _ = run_life("ref", [])
    targets = [
        int(records * (i + 1) / (kills + 1)) for i in range(kills)
    ]
    kill_path, incarnations = run_life("kill", targets)

    ref = np.load(ref_path)
    killed = np.load(kill_path)
    assert int(ref["applied_hi"]) == int(killed["applied_hi"]) == records
    mismatch = int(
        (ref["values"].tobytes() != killed["values"].tobytes())
    )
    assert mismatch == 0, (
        "kill->restore state diverged from the single-life table "
        f"(shapes {ref['values'].shape} vs {killed['values'].shape})"
    )
    return {
        "records": int(records),
        "kills": int(kills),
        "incarnations": int(incarnations),
        "parity_mismatch_bytes": 0,
    }


def run_stateful_bench(
    keys: int = 10_000_000,
    records: int = 10_485_760,
    capacity: int = 1 << 21,
    batch: int = 8192,
    kill_records: int = 49_152,
    kill_keys: int = 16_384,
    kill_capacity: int = 32_768,
    kill_batch: int = 1024,
    kills: int = 2,
    trees: int = 20,
    depth: int = 4,
    features: int = 8,
    seed: int = 29,
) -> dict:
    """``--stateful``: the keyed-state capture + acceptance drill
    (ISSUE 19) — per-key session state fused into the scoring dispatch.

    Geometry: one GBM compiled at ``batch``; two key mixes stream
    ``records`` each through the REAL ``dispatch_quantized`` state
    stage against a ``capacity``-slot device-resident table:

    - **sweep** — keys walk a multiplicative permutation of the full
      ``keys`` domain (>= 10M distinct by default), every record a
      fresh key once the domain exceeds the table: the insert/evict
      worst case, occupancy pinned at the ceiling;
    - **zipf** — a=1.1 skew over the same domain: the session-locality
      case the fused lookup exists for (hit-ratio reported).

    A stateless hand loop over the same model is the overhead
    denominator. The SIGKILL phase (:func:`_stateful_kill_parity`)
    re-runs a smaller keyed stream through the production BlockPipeline
    with checkpoints, kills it mid-stream, and asserts the restored
    replay's final table is BYTE-identical to an uninterrupted life.

    Raises ``AssertionError`` on violation; → the capture's JSON
    line."""
    import jax
    import numpy as np

    from flink_jpmml_tpu.assets_gen import gen_gbm
    from flink_jpmml_tpu.compile import compile_pmml
    from flink_jpmml_tpu.pmml import parse_pmml_file
    from flink_jpmml_tpu.runtime import state as state_mod
    from flink_jpmml_tpu.runtime.pipeline import dispatch_quantized
    from flink_jpmml_tpu.utils.metrics import MetricsRegistry

    t0 = time.monotonic()
    assert records % batch == 0, "--stateful-records must divide --stateful-batch"
    tmp = tempfile.mkdtemp(prefix="fjt-stateful-")
    try:
        pmml = gen_gbm(
            tmp, n_trees=trees, depth=depth, n_features=features,
            seed=seed,
        )
        cm = compile_pmml(parse_pmml_file(pmml), batch_size=batch)
        q = cm.quantized_scorer()
        assert q is not None, "stateful bench GBM must be rank-wire eligible"
        backend = jax.default_backend()

        rng = np.random.default_rng(seed)
        # feature pool cycled by view: the timed loop must measure the
        # dispatch, not 10M rows of host-side normal() generation
        pool_n = 64 * batch
        pool = rng.normal(0.0, 1.0, size=(pool_n, features)).astype(
            np.float32
        )
        n_batches = records // batch
        # 0x9E3779B1 (prime): offset -> key is a permutation of the
        # domain whenever gcd(p, keys) == 1, so the sweep touches
        # min(records, keys) DISTINCT keys — the >= 10M-key claim is by
        # construction, not by sampling luck
        _PERM = 2654435761
        zipf_keys = ((rng.zipf(1.1, size=records) - 1) % keys).astype(
            np.int64
        )

        def sweep_keys(off: int) -> np.ndarray:
            return (np.arange(off, off + batch, dtype=np.int64)
                    * _PERM) % keys

        def run_mix(key_fn, table) -> float:
            last = None
            t_mix = time.monotonic()
            for b in range(n_batches):
                off = b * batch
                X = pool[(off % pool_n):(off % pool_n) + batch]
                kw = {}
                if table is not None:
                    kw = {
                        "state": table,
                        "state_keys": key_fn(off),
                        "offsets": np.arange(off, off + batch,
                                             dtype=np.int64),
                        # steady-state path: the [rows, 8] buffer
                        # donates and updates in place — without it
                        # every dispatch copies the whole table
                        # (capacity x 32 B), and at 2M slots that copy
                        # IS the bench
                        "donate": True,
                    }
                last = dispatch_quantized(q, X, **kw)
                # bounded in-flight: let the device run ahead one batch
                if b % 2:
                    jax.block_until_ready(last)
            jax.block_until_ready(last)
            return records / (time.monotonic() - t_mix)

        spec = state_mod.StateSpec(capacity=capacity, key_col=0)
        # warm every entry (stateless + state) outside the timed loops
        warm = state_mod.KeyedStateTable(spec)
        jax.block_until_ready(dispatch_quantized(
            q, pool[:batch], state=warm,
            state_keys=sweep_keys(0),
            offsets=np.arange(batch, dtype=np.int64),
            donate=True,
        ))
        jax.block_until_ready(dispatch_quantized(q, pool[:batch]))
        del warm

        stateless_rec_s = run_mix(None, None)

        reg_sweep = MetricsRegistry()
        sweep_rec_s = run_mix(
            sweep_keys, state_mod.KeyedStateTable(spec, metrics=reg_sweep)
        )
        reg_zipf = MetricsRegistry()
        zipf_rec_s = run_mix(
            lambda off: zipf_keys[off:off + batch],
            state_mod.KeyedStateTable(spec, metrics=reg_zipf),
        )

        def plane(reg) -> tuple:
            snap = reg.struct_snapshot()
            cs, gs = snap["counters"], snap.get("gauges") or {}
            return cs, {k: v.get("value") for k, v in gs.items()}

        cs_sweep, gs_sweep = plane(reg_sweep)
        cs_zipf, gs_zipf = plane(reg_zipf)
        assert int(cs_sweep.get("state_records", 0)) == records
        assert int(cs_zipf.get("state_records", 0)) == records
        # the sweep saturates the table: a permutation domain >> slots
        # must pin occupancy at the ceiling and keep evicting
        if min(records, keys) > 2 * capacity:
            assert gs_sweep.get("state_occupancy_frac", 0) > 0.95, gs_sweep
            assert cs_sweep.get("state_evictions", 0) > 0, cs_sweep

        kill = _stateful_kill_parity(
            tmp, pmml, records=kill_records, keys=kill_keys,
            capacity=kill_capacity, batch=kill_batch, kills=kills,
            seed=seed + 1, features=features,
        )

        n_dev = max(1, jax.local_device_count())
        line = {
            "metric": "stateful_bench",
            "ok": True,
            "unit": "records/s/chip",
            "backend": backend,
            "key_domain": int(keys),
            "distinct_keys_swept": int(min(records, keys)),
            "records_per_mix": int(records),
            "capacity": int(capacity),
            "batch": int(batch),
            "trees": int(trees),
            # the table lives on ONE device; per-chip == absolute here
            "value": round(zipf_rec_s / n_dev, 1),
            "zipf_rec_s": round(zipf_rec_s, 1),
            "sweep_rec_s": round(sweep_rec_s, 1),
            "stateless_rec_s": round(stateless_rec_s, 1),
            "state_overhead_frac": round(
                max(0.0, 1.0 - zipf_rec_s / stateless_rec_s), 4
            ),
            "vs_target": round(zipf_rec_s / 500_000.0, 4),
            "occupancy_frac": gs_sweep.get("state_occupancy_frac"),
            "resident_keys": gs_sweep.get("state_resident_keys"),
            "zipf_hit_ratio": gs_zipf.get("state_hit_ratio"),
            "sweep_evictions": int(cs_sweep.get("state_evictions", 0)),
            "sweep_inserts": int(cs_sweep.get("state_inserts", 0)),
            "zipf_collisions": int(cs_zipf.get("state_collisions", 0)),
            "kill_records": kill["records"],
            "kill_incarnations": kill["incarnations"],
            "parity_mismatch_bytes": kill["parity_mismatch_bytes"],
            "elapsed_s": round(time.monotonic() - t0, 3),
        }
        return line
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def run_device_fault_drill(
    records: int = 24_000,
    seed: int = 11,
    timeout_s: float = 240.0,
    max_restarts: int = 20,
    kill_during_fallback: bool = True,
    device_error_fires: int = 14,
    oom_fires: int = 3,
    throttle_ms: float = 1.0,
) -> dict:
    """``--device-fault-drill``: the device-fault resilience acceptance
    drill (ISSUE 15 / ROADMAP item 1's fault half). A supervised worker
    scores a real Kafka stream (production BlockPipeline, checkpoints +
    DLQ + failover plane) while injected DEVICE faults land at the real
    launch/readback sites:

    - ``device_oom`` (n=``oom_fires``) forces the batch-size bisection
      and the AdaptiveBatcher cap feedback;
    - ``device_error`` (n=``device_error_fires``, persistent past the
      retry budget) trips the circuit breaker onto the host fallback
      tier, then heals — the breaker must re-close via green probes
      with NO operator action;
    - with ``kill_during_fallback`` the parent SIGKILLs the worker the
      moment it observes the circuit OPEN (fallback serving) — the
      kill-during-fallback member of the recovery-drill family; the
      restarted incarnation re-enters an outage (fault counts re-arm
      per process) and must converge again.

    Verified end to end: zero record loss; duplication bounded by the
    replay windows the restarts admit; the DLQ stays EMPTY (a sick
    device never quarantines clean records); non-zero fallback-tier
    records during the outage; ≥1 OOM shrink with a standing adaptive
    cap; non-zero redispatched records; the final incarnation ends
    with every circuit CLOSED (``failover_state`` 0); watermarks
    monotone within each incarnation; p99 bounded."""
    import signal

    import numpy as np

    from flink_jpmml_tpu.assets_gen import gen_gbm
    from flink_jpmml_tpu.runtime.dlq import DeadLetterQueue
    from flink_jpmml_tpu.runtime.kafka import MiniKafkaBroker
    from flink_jpmml_tpu.runtime.supervisor import (
        RestartPolicy, Supervisor, WorkerSpec,
    )

    t0 = time.monotonic()
    rng = np.random.default_rng(seed)
    tmp = tempfile.mkdtemp(prefix="fjt-devfault-")
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    broker = None
    sup = None
    ok = False
    try:
        pmml = gen_gbm(tmp, n_trees=6, depth=3, n_features=6)
        broker = MiniKafkaBroker(topic="devfault")
        data = rng.normal(0, 1.2, size=(records, 6)).astype(np.float32)
        ts0 = int(time.time() * 1000) - records
        # pre-produce one ring's worth; the REST is paced from the
        # supervision loop below — on a CPU host the fallback tier runs
        # at device speed, and an eagerly-produced stream would drain
        # entirely inside one open-circuit window, leaving no traffic
        # for the half-open probes that must re-close the breaker
        produced = min(4096, records)
        broker.append_rows(data[:produced], timestamp_ms=ts0 + produced)

        fault_spec = [
            # persistent-past-retries device errors → circuit breaker
            f"device_error:site=device_readback:n={device_error_fires}",
            # an OOM streak deep enough that the bisection must split
            f"device_oom:site=device_dispatch:n={oom_fires}",
        ]
        if throttle_ms > 0:
            fault_spec.append(f"dispatch_delay:delay_ms={throttle_ms}")
        ckdir = os.path.join(tmp, "ck")
        outfile = os.path.join(tmp, "emissions.log")
        open(outfile, "w").close()
        worker_env = {
            "FJT_FAULTS": ",".join(fault_spec),
            "FJT_RESTART_BASE_S": "0.02",
            "FJT_RESTART_CAP_S": "0.2",
            "FJT_RETRY_BASE_S": "0.01",
            # fast breaker geometry so the open→half-open→closed
            # lifecycle completes several times inside one drill
            "FJT_FAILOVER_COOLDOWN_S": "0.3",
            "FJT_FAILOVER_GREENS": "2",
            "FJT_AUTOTUNE_CACHE": os.path.join(tmp, "autotune"),
            "JAX_PLATFORMS": "cpu",
        }
        argv = [
            sys.executable, "-c", _DEVFAULT_WORKER,
            broker.host, str(broker.port), "devfault", pmml,
            ckdir, outfile, str(records), repo,
        ]
        give_ups = []
        sup = Supervisor(
            [WorkerSpec("scorer", argv, env=worker_env)],
            policy=RestartPolicy(
                max_restarts=max_restarts, backoff_s=0.02,
                max_backoff_s=0.2,
            ),
            heartbeat_timeout_s=None,
            on_give_up=give_ups.append,
        )

        def tail_f_lines():
            rows = []
            try:
                for ln in open(outfile, "r", encoding="utf-8"):
                    p = ln.split()
                    if p and p[0] == "F":
                        rows.append((
                            int(p[1]), float(p[2]), float(p[3]),
                            float(p[4]), float(p[5]),
                        ))
            except OSError:
                pass
            return rows

        sup.start()
        deadline = time.monotonic() + timeout_s
        kills_done = 0
        pace_chunk = max(records // 100, 64)
        while time.monotonic() < deadline:
            st = sup.status()["scorer"]
            if st["finished"] or st["gave_up"]:
                break
            if produced < records:
                hi = min(produced + pace_chunk, records)
                broker.append_rows(
                    data[produced:hi], timestamp_ms=ts0 + hi
                )
                produced = hi
            if kill_during_fallback and kills_done == 0:
                rows = tail_f_lines()
                if rows and rows[-1][4] >= 2.0:
                    # the circuit is OPEN — the worker is serving on
                    # the fallback tier RIGHT NOW: kill it there
                    pid = st["pid"]
                    if pid is not None and st["alive"]:
                        try:
                            os.kill(pid, signal.SIGKILL)
                            kills_done += 1
                        except OSError:
                            pass
            time.sleep(0.05)
        st = sup.status()["scorer"]
        restarts = int(st["restarts"])
        assert not give_ups and not st["gave_up"], (
            f"give-up fired after {restarts} restarts (status {st})"
        )
        assert st["finished"], (
            f"drill did not drain within {timeout_s}s (status {st})"
        )
        sup.stop()
        sup = None

        # ---- verification --------------------------------------------
        emitted = []
        f_rows = []
        p99_by_pid = {}
        for ln in open(outfile, "r", encoding="utf-8"):
            p = ln.split()
            if not p:
                continue
            if p[0] == "E":
                emitted.append((
                    int(p[1]), int(p[2]), int(p[3]), float(p[4]),
                ))
            elif p[0] == "F":
                f_rows.append((
                    int(p[1]), float(p[2]), float(p[3]), float(p[4]),
                    float(p[5]),
                ))
            elif p[0] == "P":
                p99_by_pid[int(p[1])] = float(p[2])
        covered = np.zeros(records, np.int64)
        for _, off, n, _wm in emitted:
            covered[off: off + n] += 1
        lost = np.flatnonzero(covered == 0)
        assert lost.size == 0, (
            f"record loss at offsets {lost[:10].tolist()}"
        )
        replay_window = 4096 + 4 * 64 * 2
        excess = int(np.clip(covered - 1, 0, None).sum())
        n_incarnations = restarts + 1
        assert excess <= n_incarnations * replay_window, (
            f"duplicate excess {excess} exceeds "
            f"{n_incarnations} x {replay_window}"
        )
        # a sick device must never quarantine clean records
        dlq_offsets = sorted(
            set(DeadLetterQueue(os.path.join(ckdir, "dlq")).offsets())
        )
        assert dlq_offsets == [], (
            f"device faults quarantined clean records: {dlq_offsets}"
        )
        # per-incarnation counter maxima (counters reset per process)
        by_pid: dict = {}
        for pid, fb, rd, oo, stv in f_rows:
            prev = by_pid.get(pid, (0.0, 0.0, 0.0, 0.0))
            by_pid[pid] = (
                max(prev[0], fb), max(prev[1], rd), max(prev[2], oo),
                stv,  # last state seen for this pid
            )
        fallback_total = sum(v[0] for v in by_pid.values())
        redispatch_total = sum(v[1] for v in by_pid.values())
        oom_total = sum(v[2] for v in by_pid.values())
        assert fallback_total > 0, (
            "no fallback-tier records served during the outage"
        )
        assert oom_total >= 1, "no OOM batch shrink recorded"
        assert redispatch_total > 0, "no redispatched records"
        assert f_rows, "no failover telemetry lines"
        final_state = f_rows[-1][4]
        assert final_state == 0.0, (
            f"circuit did not re-close (final failover_state "
            f"{final_state})"
        )
        saw_open = any(r[4] >= 2.0 for r in f_rows)
        assert saw_open, "circuit never opened — the outage was a no-op"
        if kill_during_fallback:
            assert kills_done == 1, (
                f"kill-during-fallback never landed (kills {kills_done})"
            )
        # watermarks monotone within each incarnation
        wm_by_pid: dict = {}
        for pid, _off, _n, wm in emitted:
            if wm <= 0:
                continue
            prev = wm_by_pid.get(pid)
            assert prev is None or wm >= prev - 1e-9, (
                f"watermark regressed within pid {pid}: {prev} -> {wm}"
            )
            wm_by_pid[pid] = wm
        # p99 bounded: degraded batches (ladder backoffs + host-tier
        # scoring) are booked honestly, and must still stay bounded
        final_p99 = max(p99_by_pid.values()) if p99_by_pid else None
        assert final_p99 is not None and 0 < final_p99 <= 5_000.0, (
            f"p99 unbounded or unmeasured: {final_p99} ms"
        )

        ok = True
        return {
            "metric": "device_fault_drill",
            "ok": True,
            "records": int(records),
            "restarts": restarts,
            "kill_during_fallback": bool(kills_done),
            "fallback_records": fallback_total,
            "redispatch_records": redispatch_total,
            "oom_shrinks": oom_total,
            "circuit_reclosed": final_state == 0.0,
            "duplicate_excess": excess,
            "max_dup": int(covered.max()),
            "dlq_empty": True,
            "p99_ms": final_p99,
            "elapsed_s": round(time.monotonic() - t0, 3),
        }
    finally:
        if sup is not None:
            sup.stop()
        if broker is not None:
            broker.close()
        if ok:
            shutil.rmtree(tmp, ignore_errors=True)
        else:
            print(f"[device-fault-drill] artifacts kept at {tmp}",
                  file=sys.stderr)


def _mesh_devices(min_devices: int = 4) -> int:
    """The devices the mesh modes run on → their count: the real chips
    of a multi-chip TPU host, or — only when the environment asks for
    the CPU (``JAX_PLATFORMS=cpu``) — a simulated 8-device host. The virtual-device flag must land before the first backend
    init (the same trick tests/conftest.py uses), so both mesh
    entrypoints call this before anything imports jax."""
    if os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu":
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=8"
            ).strip()
    import jax

    n = jax.device_count()
    assert n >= min_devices, (
        f"mesh mode needs >= {min_devices} devices, found {n} "
        f"{jax.default_backend()} device(s): run on a multi-chip host, "
        "or set JAX_PLATFORMS=cpu for the simulated 8-device mesh"
    )
    return n


def run_mesh_bench(
    records: int = 40_000,
    seed: int = 7,
    batch: int = 512,
    timeout_s: float = 300.0,
) -> dict:
    """``--mesh``: the per-chip scaling curve for the MULTICHIP
    artifact. One production BlockPipeline per data-axis width w ∈
    {1, 2, 4, 8} (capped at the device count) scores the SAME GBM over
    a real Kafka stream with w partitions — each chip owns its
    partitions via the rendezvous ChipAssignment (parallel/assignment)
    and the batch splits across the data axis through
    ShardedModel.shard_map dispatch. The line carries:

    - ``curve``       — per-width {rec_per_s, per_chip_rec_per_s,
      scaling_vs_1chip, per-chip record counts, partition ownership}
    - ``fleet``       — the width runs' metrics structs merged under
      the fleet rules (per-chip counters SUM, mesh_data_width MIN,
      mesh_chip_state worst-of): the supervisor's merged view stays
      exact at any mesh width.

    On a CPU host every "chip" is the same silicon, so the curve is a
    geometry capture (flat-to-falling), not a speedup claim — the
    capture-gated v5e-8 run is where near-linear shows up (same
    protocol as the PR 11/14 MULTICHIP rounds)."""
    import threading

    import numpy as np

    n_dev = _mesh_devices(4)
    from flink_jpmml_tpu.assets_gen import gen_gbm
    from flink_jpmml_tpu.compile import compile_pmml
    from flink_jpmml_tpu.obs import mesh as mesh_obs
    from flink_jpmml_tpu.parallel.mesh import make_mesh
    from flink_jpmml_tpu.pmml import parse_pmml_file
    from flink_jpmml_tpu.runtime.block import BlockPipeline
    from flink_jpmml_tpu.runtime.kafka import (
        KafkaBlockSource, MiniKafkaBroker,
    )
    from flink_jpmml_tpu.utils.config import (
        BatchConfig, MeshConfig, RuntimeConfig,
    )
    from flink_jpmml_tpu.utils.metrics import (
        MetricsRegistry, merge_structs,
    )

    t0 = time.monotonic()
    rng = np.random.default_rng(seed)
    tmp = tempfile.mkdtemp(prefix="fjt-meshbench-")
    widths = [w for w in (1, 2, 4, 8) if w <= n_dev]
    curve = []
    snaps = []
    try:
        pmml = gen_gbm(tmp, n_trees=6, depth=3, n_features=6)
        doc = parse_pmml_file(pmml)
        cm = compile_pmml(doc, batch_size=batch)
        data = rng.normal(0, 1.2, size=(records, 6)).astype(np.float32)

        for w in widths:
            # scaling-curve geometry: width w deliberately uses a
            # SUBSET mesh (the remaining chips idle) — that is the
            # point of the curve, not a throughput bug
            mesh = (
                make_mesh(MeshConfig(data=w, model=1),
                          allow_subset=True)
                if w > 1 else None
            )
            m = MetricsRegistry()
            # 2 partitions per chip (w > 1): rendezvous ownership
            # spreads far better over-partitioned, exactly like a real
            # Kafka topic sized above its consumer count
            n_parts = 2 * w if w > 1 else 1
            broker = MiniKafkaBroker(topic="mesh", n_partitions=n_parts)
            broker.append_rows_round_robin(data)
            src = KafkaBlockSource(
                broker.host, broker.port, "mesh",
                partitions=list(range(n_parts)), n_cols=6,
                max_wait_ms=20, metrics=m,
            )
            rows = []
            lock = threading.Lock()

            def sink(o, n, first_off, rows=rows, lock=lock):
                with lock:
                    rows.append((time.monotonic(), n))

            pipe = BlockPipeline(
                src, cm, sink,
                RuntimeConfig(batch=BatchConfig(
                    size=batch, deadline_us=5000, queue_capacity=8192,
                )),
                metrics=m, max_dispatch_chunks=4, mesh=mesh,
            )
            pipe.start()
            deadline = time.monotonic() + timeout_s
            while time.monotonic() < deadline:
                with lock:
                    total = sum(n for _, n in rows)
                if total >= records or pipe._error is not None:
                    break
                time.sleep(0.02)
            pipe.stop()
            pipe.join(timeout=30.0)
            src.close()
            broker.close()
            assert pipe._error is None, (
                f"width {w} pipeline died: {pipe._error!r}"
            )
            assert len(rows) >= 2, f"width {w} drained {len(rows)} batches"
            # rate over steady state: the first sunk batch absorbs the
            # shard_map compile + window fill, so timing starts there
            warm_t = rows[0][0]
            steady = sum(n for t, n in rows[1:])
            elapsed = max(rows[-1][0] - warm_t, 1e-9)
            rate = steady / elapsed
            snap = m.struct_snapshot()
            snaps.append(snap)
            msum = mesh_obs.summary(snap)
            model = pipe._bound.model
            owner = {}
            if getattr(model, "assignment", None) is not None:
                owner = {
                    str(c): list(model.assignment.partitions_for(c))
                    for c in model.assignment.chips
                }
            curve.append({
                "data_width": w,
                "rec_per_s": round(rate, 1),
                "per_chip_rec_per_s": round(rate / w, 1),
                "in_flight": pipe._in_flight_max,
                "chip_records": (
                    {c: round(v["records"], 1)
                     for c, v in msum["chips"].items()}
                    if msum else {}
                ),
                "chip_partitions": owner,
            })
        base = curve[0]["rec_per_s"] or 1.0
        for entry in curve:
            entry["scaling_vs_1chip"] = round(
                entry["rec_per_s"] / (base * entry["data_width"]), 3
            )
        fleet = merge_structs(snaps)
        fg, fc = fleet.get("gauges", {}), fleet.get("counters", {})
        fleet_line = {
            "workers": len(snaps),
            "mesh_chip_records": {
                k.split('"')[1]: round(float(v), 1)
                for k, v in fc.items()
                if k.startswith("mesh_chip_records{")
            },
            # MIN-merged: the most-degraded worker's surviving width
            "mesh_data_width": (
                fg.get("mesh_data_width", {}) or {}
            ).get("value"),
            "records_out": float(fc.get("records_out", 0.0)),
        }
        import jax

        return {
            "metric": "mesh_scaling",
            "ok": True,
            "backend": jax.default_backend(),
            "devices": n_dev,
            "batch": batch,
            "records_per_width": int(records),
            "curve": curve,
            "fleet": fleet_line,
            "elapsed_s": round(time.monotonic() - t0, 3),
        }
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def run_mesh_fault_drill(
    records: int = 24_000,
    seed: int = 11,
    batch: int = 512,
    timeout_s: float = 300.0,
) -> dict:
    """``--device-fault-drill --mesh``: chip loss ON the mesh hot path.
    A mesh-sharded BlockPipeline (data=4) scores a Kafka stream; at
    half-stream an injected ``chip_loss`` lands at the real readback
    site. The KIND_LOST rung (runtime/block.py) must rebuild over the
    surviving chips IN PLACE (``ShardedModel.without_devices`` — no
    process restart, no supervisor) and keep serving degraded:

    - zero record loss and zero duplication (no restart ⇒ no replay);
    - the DLQ stays EMPTY (a dead chip never quarantines records);
    - exactly one mesh rebuild, surviving width N−1, dead chip flagged
      ``mesh_chip_state`` = lost;
    - steady-state degraded throughput ≥ (N−1)/N of the pre-loss rate
      (the rebuild stall itself is reported separately, not smeared
      into the steady-state rate)."""
    import threading

    import numpy as np

    n_dev = _mesh_devices(4)
    from flink_jpmml_tpu.assets_gen import gen_gbm
    from flink_jpmml_tpu.compile import compile_pmml
    from flink_jpmml_tpu.obs import mesh as mesh_obs
    from flink_jpmml_tpu.parallel.mesh import make_mesh
    from flink_jpmml_tpu.pmml import parse_pmml_file
    from flink_jpmml_tpu.runtime import faults
    from flink_jpmml_tpu.runtime.block import BlockPipeline
    from flink_jpmml_tpu.runtime.dlq import DeadLetterQueue
    from flink_jpmml_tpu.runtime.kafka import (
        KafkaBlockSource, MiniKafkaBroker,
    )
    from flink_jpmml_tpu.utils.config import (
        BatchConfig, MeshConfig, RuntimeConfig,
    )
    from flink_jpmml_tpu.utils.metrics import MetricsRegistry

    t0 = time.monotonic()
    rng = np.random.default_rng(seed)
    tmp = tempfile.mkdtemp(prefix="fjt-meshfault-")
    data_w = 4
    model_w = 2 if n_dev >= 8 else 1
    half = (records // 2 // batch) * batch
    broker = None
    src = None
    pipe = None
    ok = False
    try:
        pmml = gen_gbm(tmp, n_trees=6, depth=3, n_features=6)
        cm = compile_pmml(parse_pmml_file(pmml), batch_size=batch)
        mesh = make_mesh(MeshConfig(data=data_w, model=model_w))
        data = rng.normal(0, 1.2, size=(records, 6)).astype(np.float32)

        m = MetricsRegistry()
        dlq = DeadLetterQueue(os.path.join(tmp, "dlq"), metrics=m)
        broker = MiniKafkaBroker(topic="meshfault")
        broker.append_rows(data[:half])
        src = KafkaBlockSource(
            broker.host, broker.port, "meshfault", n_cols=6,
            max_wait_ms=20, metrics=m, dlq=dlq,
        )
        rows = []
        lock = threading.Lock()

        def sink(o, n, first_off):
            with lock:
                rows.append((time.monotonic(), first_off, n))

        pipe = BlockPipeline(
            src, cm, sink,
            RuntimeConfig(batch=BatchConfig(
                size=batch, deadline_us=5000, queue_capacity=8192,
            )),
            metrics=m, max_dispatch_chunks=4, dlq=dlq, mesh=mesh,
        )

        def total():
            with lock:
                return sum(n for _, _, n in rows)

        def wait_total(target, deadline):
            while time.monotonic() < deadline:
                if total() >= target or pipe._error is not None:
                    return
                time.sleep(0.02)

        pipe.start()
        wait_total(half, time.monotonic() + timeout_s)
        assert pipe._error is None, f"pre-loss error: {pipe._error!r}"
        assert total() >= half, "pre-loss phase never drained"
        t_kill = time.monotonic()
        # the chip dies at the REAL readback site of the next dispatch
        faults.inject("chip_loss", n=1)
        broker.append_rows(data[half:])
        wait_total(records, time.monotonic() + timeout_s)
        pipe.stop()
        pipe.join(timeout=30.0)
        assert pipe._error is None, f"post-loss error: {pipe._error!r}"

        # ---- verification -------------------------------------------
        with lock:
            emitted = list(rows)
        covered = np.zeros(records, np.int64)
        for _, off, n in emitted:
            covered[off: off + n] += 1
        lost_offs = np.flatnonzero(covered == 0)
        assert lost_offs.size == 0, (
            f"record loss at offsets {lost_offs[:10].tolist()}"
        )
        assert int(covered.max()) == 1, (
            f"duplication without a restart (max {int(covered.max())})"
        )
        assert sorted(set(dlq.offsets())) == [], (
            "chip loss quarantined clean records"
        )
        assert faults.stats().get("chip_loss", 0) == 1, (
            "the injected chip loss never fired"
        )
        snap = m.struct_snapshot()
        c, g = snap["counters"], snap["gauges"]
        assert c.get("mesh_rebuilds", 0) >= 1, "no mesh rebuild ran"
        width = (g.get("mesh_data_width", {}) or {}).get("value")
        assert width == float(data_w - 1), (
            f"surviving width {width}, expected {data_w - 1}"
        )
        msum = mesh_obs.summary(snap)
        assert msum is not None
        lost_chips = [
            chip for chip, v in msum["chips"].items()
            if v["state"] == "lost"
        ]
        assert len(lost_chips) == 1, (
            f"expected exactly one lost chip, saw {lost_chips}"
        )
        # throughput: steady-state degraded rate vs pre-loss rate. The
        # first post-loss emission carries the rebuild (re-jit on the
        # degraded mesh) — that stall is reported, not averaged in.
        pre = [(t, n) for t, _, n in emitted if t <= t_kill]
        post = [(t, n) for t, _, n in emitted if t > t_kill]
        assert len(pre) >= 3 and len(post) >= 3, (
            f"too few batches to rate ({len(pre)} pre / {len(post)} post)"
        )
        pre_rate = (
            sum(n for _, n in pre[1:])
            / max(pre[-1][0] - pre[0][0], 1e-9)
        )
        rebuild_stall_s = post[0][0] - t_kill
        post_rate = (
            sum(n for _, n in post[2:])
            / max(post[-1][0] - post[1][0], 1e-9)
        )
        floor = (data_w - 1) / data_w
        assert post_rate >= floor * pre_rate, (
            f"degraded rate {post_rate:.0f} rec/s under the "
            f"{floor:.2f}x floor of pre-loss {pre_rate:.0f} rec/s"
        )
        ok = True
        return {
            "metric": "mesh_device_fault_drill",
            "ok": True,
            "devices": n_dev,
            "mesh": {"data": data_w, "model": model_w},
            "records": int(records),
            "records_lost": 0,
            "duplicates": 0,
            "dlq_empty": True,
            "mesh_rebuilds": int(c.get("mesh_rebuilds", 0)),
            "surviving_width": int(width),
            "lost_chips": lost_chips,
            "pre_rate_rec_s": round(pre_rate, 1),
            "post_rate_rec_s": round(post_rate, 1),
            "degraded_ratio": round(post_rate / max(pre_rate, 1e-9), 3),
            "rebuild_stall_s": round(rebuild_stall_s, 3),
            "elapsed_s": round(time.monotonic() - t0, 3),
        }
    finally:
        faults.clear()
        if pipe is not None:
            pipe.stop()
            pipe.join(timeout=10.0)
        if src is not None:
            src.close()
        if broker is not None:
            broker.close()
        if ok:
            shutil.rmtree(tmp, ignore_errors=True)
        else:
            print(f"[mesh-fault-drill] artifacts kept at {tmp}",
                  file=sys.stderr)


_RECOVERY_WORKER = r'''
import os, sys, time
# per-incarnation fault seed BEFORE the package imports (env faults arm
# at import): the seeded p-gates draw a fresh pattern per incarnation,
# so a site-targeted crash can't deterministically re-fire at the same
# call forever
os.environ["FJT_FAULTS"] = os.environ.get("FJT_FAULTS", "").replace(
    "PIDSEED", str(os.getpid())
)
sys.path.insert(0, sys.argv[8])
import jax
import numpy as np
from flink_jpmml_tpu.compile import compile_pmml
from flink_jpmml_tpu.pmml import parse_pmml_file
from flink_jpmml_tpu.runtime.block import BlockPipeline
from flink_jpmml_tpu.runtime.checkpoint import CheckpointManager
from flink_jpmml_tpu.runtime.dlq import DeadLetterQueue
from flink_jpmml_tpu.runtime.kafka import KafkaBlockSource
from flink_jpmml_tpu.runtime.supervisor import reporter_from_env
from flink_jpmml_tpu.utils.config import BatchConfig, RuntimeConfig
from flink_jpmml_tpu.utils.metrics import MetricsRegistry

host, port, topic, pmml, ckdir, outfile, total = sys.argv[1:8]
total = int(total)
m = MetricsRegistry()
rep = reporter_from_env(metrics=m)
dlq = DeadLetterQueue(os.path.join(ckdir, "dlq"), metrics=m)
src = KafkaBlockSource(
    host, int(port), topic, n_cols=6, max_wait_ms=20, metrics=m, dlq=dlq,
)
cm = compile_pmml(parse_pmml_file(pmml), batch_size=64)
out = open(outfile, "a", buffering=1)
wm = m.gauge("watermark_ts")

def sink(o, n, first_off):
    out.write("E %d %d %d %.3f\n" % (os.getpid(), first_off, n, wm.get()))

pipe = BlockPipeline(
    src, cm, sink,
    RuntimeConfig(
        batch=BatchConfig(size=64, deadline_us=2000, queue_capacity=4096),
        checkpoint_interval_s=0.05,
    ),
    metrics=m,
    checkpoint=CheckpointManager(ckdir),
    dlq=dlq,
    max_dispatch_chunks=4,
)
pipe.restore()
out.write("R %d %d\n" % (os.getpid(), pipe.committed_offset))
pipe.start()
while pipe.committed_offset < total and pipe._error is None:
    time.sleep(0.02)
pipe.stop()
pipe.join(timeout=30.0)
out.write("D %d %d\n" % (os.getpid(), pipe.committed_offset))
src.close()
out.close()
'''


def run_recovery_drill(
    records: int = 24_000,
    kills: int = 2,
    poison: int = 2,
    hard_poison: bool = True,
    decode_poison_n: int = 2,
    seed: int = 7,
    timeout_s: float = 300.0,
    max_restarts: int = 60,
    throttle_ms: float = 0.0,
    kill_dwell: tuple = (0.2, 0.7),
) -> dict:
    """``--recovery-drill``: the kill-anywhere delivery-correctness
    acceptance drill. A supervised worker scores a real Kafka stream
    (in-process broker, production BlockPipeline, checkpoints + DLQ)
    while chaos lands from every direction:

    - the PARENT SIGKILLs it at randomized mid-stream instants;
    - ``FJT_FAULTS`` ``worker_crash`` kinds SIGKILL from inside at the
      real sites (mid-fetch / mid-dispatch / mid-checkpoint), seeded
      per incarnation; ``slow_fetch`` rides along;
    - ``poison_record`` faults make chosen offsets raise in scoring
      (the catchable-poison path → suspect-mode bisection);
    - one optional HARD poison offset SIGKILLs the process whenever its
      batch is dispatched (the crash-loop path → fingerprint + marker
      convergence, supervisor streak cooperation);
    - wrong-length producer records exercise the decode-poison path.

    Verified end to end: zero record loss; duplication bounded by the
    replay windows the restarts admit; every retained checkpoint
    parseable; watermarks monotone within each incarnation; the
    injected poison offsets land in the DLQ EXACTLY (and never in the
    sink); no ``on_give_up`` fired; ``fjt-dlq redrive`` round-trips
    a quarantined record back through the live pipeline; and the
    poison record's causal journey (obs/trace.py) reconstructs from
    durable fragments alone — dispatch hops across the SIGKILL
    incarnation boundary, suspect-mode bisection, the terminal DLQ
    quarantine, and (post-redrive) the traceparent-linked re-ingest —
    embedded in the artifact as ``journeys``/``trace``."""
    import signal

    import numpy as np

    from flink_jpmml_tpu import cli as cli_mod
    from flink_jpmml_tpu.assets_gen import gen_gbm
    from flink_jpmml_tpu.runtime.dlq import DeadLetterQueue
    from flink_jpmml_tpu.runtime.kafka import MiniKafkaBroker
    from flink_jpmml_tpu.runtime.supervisor import (
        RestartPolicy, Supervisor, WorkerSpec,
    )

    t0 = time.monotonic()
    rng = np.random.default_rng(seed)
    tmp = tempfile.mkdtemp(prefix="fjt-recovery-")
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    broker = None
    sup = None
    ok = False
    try:
        pmml = gen_gbm(tmp, n_trees=6, depth=3, n_features=6)
        broker = MiniKafkaBroker(topic="recovery")
        data = rng.normal(0, 1.2, size=(records, 6)).astype(np.float32)

        # -- produce, stamped with a synthetic-but-ordered event-time
        #    axis, interleaving wrong-length decode-poison values
        decode_offsets = []
        chunk = 512
        ts0 = int(time.time() * 1000) - records
        decode_positions = set(
            int(p) for p in np.linspace(
                records // 4, records // 2, max(decode_poison_n, 0),
            )
        )
        produced = 0
        while produced < records:
            hi = min(produced + chunk, records)
            broker.append_rows(
                data[produced:hi], timestamp_ms=ts0 + hi,
            )
            produced = hi
            for p in sorted(decode_positions):
                if produced - chunk <= p < produced:
                    decode_offsets.append(
                        broker.append(b"\xde\xad\xbe\xef-poison")
                    )
        total_off = records + len(decode_offsets)

        # -- poison targeting (offsets in the BROKER's domain — the
        #    decode poisons above shifted everything after them)
        def log_off(row_idx: int) -> int:
            return row_idx + sum(
                1 for d in decode_offsets if d <= row_idx
            )

        score_poison = sorted(
            log_off(int(i)) for i in np.linspace(
                records // 6, 5 * records // 8, max(poison, 0),
            )
        )
        hard_off = (
            log_off(int(3 * records // 4)) if hard_poison else None
        )
        fault_spec = [
            f"poison_record:offset={o}" for o in score_poison
        ]
        if hard_off is not None:
            fault_spec.append(
                f"worker_crash:site=score_batch:offset={hard_off}"
            )
        fault_spec += [
            "worker_crash:site=kafka_fetch:p=0.003:n=1"
            ":after_s=0.5:for_s=1.5:seed=PIDSEED",
            "worker_crash:site=dispatch:p=0.003:n=1"
            ":after_s=0.5:for_s=1.5:seed=PIDSEED",
            "worker_crash:site=checkpoint_write:p=0.02:n=1"
            ":after_s=0.5:for_s=1.5:seed=PIDSEED",
            "slow_fetch:delay_ms=3:p=0.02:seed=PIDSEED",
        ]
        if throttle_ms > 0:
            # stretch a smoke-scale stream so the parent's kill cannot
            # race a sub-second drain (the full drill's hard poison
            # provides that runway by construction)
            fault_spec.append(f"dispatch_delay:delay_ms={throttle_ms}")
        ckdir = os.path.join(tmp, "ck")
        outfile = os.path.join(tmp, "emissions.log")
        open(outfile, "w").close()
        jdir = os.path.join(tmp, "journeys")
        worker_env = {
            "FJT_FAULTS": ",".join(fault_spec),
            "FJT_POISON_RESTARTS": "2",
            "FJT_RESTART_BASE_S": "0.02",
            "FJT_RESTART_CAP_S": "0.2",
            "FJT_RETRY_BASE_S": "0.01",
            "FJT_AUTOTUNE_CACHE": os.path.join(tmp, "autotune"),
            # record-journey tracing (obs/trace.py): an armed fault
            # plan flips the store to write-through, so every
            # incarnation's dispatch hops are durable BEFORE its kill —
            # the drill verifies the poison record's journey
            # reconstructs from these fragments alone
            "FJT_JOURNEY_DIR": jdir,
            "JAX_PLATFORMS": "cpu",
        }
        argv = [
            sys.executable, "-c", _RECOVERY_WORKER,
            broker.host, str(broker.port), "recovery", pmml,
            ckdir, outfile, str(total_off), repo,
        ]
        give_ups = []
        sup = Supervisor(
            [WorkerSpec("scorer", argv, env=worker_env)],
            policy=RestartPolicy(
                max_restarts=max_restarts, backoff_s=0.02,
                max_backoff_s=0.2,
            ),
            heartbeat_timeout_s=None,  # exit detection is the drill's
            # only death signal; wedges aren't injected here
            on_give_up=give_ups.append,
        )

        def committed() -> int:
            try:
                from flink_jpmml_tpu.runtime.checkpoint import (
                    CheckpointManager,
                )
                st = CheckpointManager(ckdir).load_latest()
                return int(st["source_offset"]) if st else 0
            except Exception:
                return 0

        sup.start()
        deadline = time.monotonic() + timeout_s
        kills_done = 0
        last_kill_committed = -1
        while time.monotonic() < deadline:
            st = sup.status()["scorer"]
            if st["finished"] or st["gave_up"]:
                break
            c = committed()
            if (
                kills_done < kills
                and st["alive"]
                and c > last_kill_committed
                and c > 0
            ):
                # kill-anywhere: a randomized dwell then SIGKILL, but
                # only after fresh progress since the last kill — the
                # in-worker crash faults own the no-progress regimes
                time.sleep(float(rng.uniform(*kill_dwell)))
                pid = sup.status()["scorer"]["pid"]
                if pid is not None:
                    try:
                        os.kill(pid, signal.SIGKILL)
                        kills_done += 1
                        last_kill_committed = c
                    except OSError:
                        pass
            time.sleep(0.05)
        st = sup.status()["scorer"]
        restarts = int(st["restarts"])
        assert not give_ups and not st["gave_up"], (
            f"give-up fired after {restarts} restarts — the poison "
            f"plane failed to convert the crash loop (status {st})"
        )
        assert st["finished"], (
            f"drill did not drain within {timeout_s}s "
            f"(committed {committed()}/{total_off}, status {st})"
        )
        sup.stop()
        sup = None

        # ---- verification --------------------------------------------
        expected_quarantine = sorted(
            set(score_poison)
            | set(decode_offsets)
            | ({hard_off} if hard_off is not None else set())
        )
        # every retained checkpoint parses (the atomic-writer contract
        # under SIGKILL-anywhere)
        import glob as _glob
        snaps = sorted(_glob.glob(os.path.join(ckdir, "ckpt-*.json")))
        assert snaps, "no checkpoint survived the drill"
        for p in snaps:
            with open(p, "r", encoding="utf-8") as f:
                payload = json.load(f)
            assert "state" in payload, f"torn checkpoint {p}"

        emitted = []   # (pid, first_off, n, wm)
        restores = []  # (pid, committed-at-restore)
        for ln in open(outfile, "r", encoding="utf-8"):
            parts = ln.split()
            if not parts:
                continue
            if parts[0] == "E":
                emitted.append((
                    int(parts[1]), int(parts[2]), int(parts[3]),
                    float(parts[4]),
                ))
            elif parts[0] == "R":
                restores.append((int(parts[1]), int(parts[2])))
        covered = np.zeros(total_off, np.int64)
        for _, off, n, _wm in emitted:
            covered[off: off + n] += 1
        qset = np.zeros(total_off, bool)
        qset[expected_quarantine] = True
        lost = np.flatnonzero((covered == 0) & ~qset)
        assert lost.size == 0, (
            f"record loss at offsets {lost[:10].tolist()}"
        )
        leaked = np.flatnonzero((covered > 0) & qset)
        assert leaked.size == 0, (
            f"quarantined offsets reached the sink: "
            f"{leaked[:10].tolist()}"
        )
        # duplication bounded by the replay windows the restarts admit:
        # each incarnation can replay at most records-since-last-commit
        # = the ring capacity + the in-flight window
        replay_window = 4096 + 4 * 64 * 2
        excess = int(np.clip(covered - 1, 0, None).sum())
        n_incarnations = restarts + 1
        assert excess <= n_incarnations * replay_window, (
            f"duplicate excess {excess} exceeds "
            f"{n_incarnations} x {replay_window}"
        )
        # watermarks monotone within each incarnation
        by_pid: dict = {}
        for pid, _off, _n, wm in emitted:
            if wm <= 0:
                continue
            prev = by_pid.get(pid)
            assert prev is None or wm >= prev - 1e-9, (
                f"watermark regressed within pid {pid}: {prev} -> {wm}"
            )
            by_pid[pid] = wm
        # the DLQ holds the injected poison EXACTLY (dedup by offset:
        # replays may quarantine the same record more than once)
        dlq = DeadLetterQueue(os.path.join(ckdir, "dlq"))
        dlq_envs = list(dlq.scan())
        dlq_offsets = sorted(set(
            int(e["offset"]) for e in dlq_envs
        ))
        assert dlq_offsets == expected_quarantine, (
            f"DLQ {dlq_offsets} != expected {expected_quarantine}"
        )
        reasons = {
            int(e["offset"]): e["reason"] for e in dlq_envs
        }
        for o in decode_offsets:
            assert reasons[o] == "decode", reasons
        if hard_off is not None:
            assert reasons[hard_off] == "crash_loop", reasons

        # ---- kill-anywhere journey continuity (obs/trace.py) ---------
        # the poison record's full journey must reconstruct from the
        # durable fragments alone: ingest + the dispatch that died
        # (incarnation boundary = pid change), suspect-mode bisection
        # hops, and the terminal DLQ quarantine — fjt-trace's own
        # merge/select logic does the reconstruction
        from flink_jpmml_tpu.obs import trace as trace_lib  # noqa: F401

        trace_target = (
            hard_off if hard_off is not None
            else (score_poison[0] if score_poison else None)
        )
        trace_info = None
        sel: list = []
        if trace_target is not None:
            jrows = cli_mod._trace_rows_from_dir(tmp)
            sel = cli_mod._trace_select(jrows, offset=trace_target)
            kinds = {r.get("kind") for r in sel}
            pids = sorted({
                int(r["pid"]) for r in sel
                if isinstance(r.get("pid"), int)
            })
            assert {"dlq", "dlq_envelope"} & kinds, (
                f"poison journey at {trace_target} has no terminal "
                f"DLQ hop (kinds {sorted(k for k in kinds if k)})"
            )
            assert {"dispatch", "suspect_dispatch"} & kinds, (
                f"poison journey at {trace_target} has no dispatch "
                f"hop (kinds {sorted(k for k in kinds if k)})"
            )
            if hard_off is not None:
                # the crash-loop path: the marker-twin bisection hops
                # and at least two incarnations must be visible
                assert "suspect_dispatch" in kinds, sorted(kinds)
                assert len(pids) >= 2, (
                    f"no incarnation boundary in the journey "
                    f"(pids {pids})"
                )
            trace_info = {
                "offset": int(trace_target),
                "kinds": sorted(k for k in kinds if k),
                "pids": pids,
                "rows": len(sel),
            }

        # ---- redrive round-trip through the LIVE pipeline ------------
        redrive_off = score_poison[0] if score_poison else None
        redrive_ok = None
        if redrive_off is not None:
            cli_mod.dlq_main([
                "redrive", ckdir,
                "--host", broker.host, "--port", str(broker.port),
                "--topic", "recovery", "--offset", str(redrive_off),
            ])
            clean_env = dict(os.environ)
            clean_env.update(worker_env)
            clean_env.pop("FJT_FAULTS", None)  # corrected pipeline
            argv2 = list(argv)
            # the worker's `total` argument is second-to-last (repo
            # path trails it): drain through the redriven record
            assert argv2[-2] == str(total_off)
            argv2[-2] = str(total_off + 1)
            proc = subprocess.run(
                argv2, env=clean_env, capture_output=True, text=True,
                timeout=120,
            )
            assert proc.returncode == 0, (
                f"redrive consumer failed rc={proc.returncode}: "
                f"{proc.stderr[-800:]}"
            )
            tail = [
                (int(p[2]), int(p[3]))
                for p in (
                    ln.split() for ln in open(outfile, encoding="utf-8")
                )
                if p and p[0] == "E"
            ]
            redrive_ok = any(
                off <= total_off < off + n for off, n in tail
            )
            assert redrive_ok, (
                "redriven record never reached the sink"
            )
            # journey continuity through the redrive: the envelope's
            # trace context rode the traceparent header back into the
            # topic, so the redriven record's ingest hop is a CHILD of
            # the original journey (same trace id, envelope span as
            # parent) — pinned end-to-end through the live pipeline
            env_tid = next(
                (
                    e.get("trace_id") for e in dlq_envs
                    if int(e["offset"]) == redrive_off
                    and e.get("trace_id")
                ),
                None,
            )
            assert env_tid is not None, "envelope lost its trace context"
            jrows2 = cli_mod._trace_rows_from_dir(jdir)
            redriven = [
                r for r in jrows2
                if r.get("redriven") and r.get("trace_id") == env_tid
            ]
            assert redriven, (
                "redriven record's ingest hop does not link the "
                f"original journey {env_tid}"
            )

        ok = True
        return {
            "metric": "recovery_drill",
            "ok": True,
            "records": int(records),
            "log_records": int(total_off),
            "parent_kills": int(kills_done),
            "restarts": int(restarts),
            "incarnations": len(restores),
            "quarantined": expected_quarantine,
            "dlq_reasons": {
                str(k): v for k, v in sorted(reasons.items())
            },
            "duplicate_excess": excess,
            "max_dup": int(covered.max()),
            "checkpoints_verified": len(snaps),
            "redrive_ok": redrive_ok,
            # the poison journey, reconstructed + embedded so
            # `fjt-trace BENCH_*.json --grep offset=K` replays the
            # timeline from the artifact alone
            "trace": trace_info,
            "journeys": (
                sel[:512] if trace_info is not None else []
            ),
            "elapsed_s": round(time.monotonic() - t0, 3),
        }
    finally:
        if sup is not None:
            sup.stop()
        if broker is not None:
            broker.close()
        if ok:  # a failed drill leaves its logs/DLQ for inspection
            shutil.rmtree(tmp, ignore_errors=True)
        else:
            print(f"[recovery-drill] artifacts kept at {tmp}",
                  file=sys.stderr)


def _latency_headline(line: dict, trees: int, backend: str) -> dict:
    """--latency: re-headline the artifact on the latency operating
    point (p50 record latency, ms); the throughput number rides along."""
    lm = line.get("latency_mode")
    if not lm:
        return line  # latency capture unavailable: keep the line honest
    return {
        "metric": f"gbm{trees}_record_latency_p50_ms",
        "value": lm["p50_ms"],
        "unit": "ms",
        "vs_baseline": None,  # BASELINE tracks but fixes no number
        "backend": backend,
        "latency_mode": lm,
        "throughput_rec_s": line.get("value"),
    }


def build_arg_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--trees", type=int, default=500)
    ap.add_argument("--depth", type=int, default=6)
    ap.add_argument("--features", type=int, default=32)
    ap.add_argument("--batch", type=int, default=262144,
                    help="records per dispatch (scored in --chunk chunks)")
    ap.add_argument("--chunk", type=int, default=16384)
    ap.add_argument("--window", type=int, default=3,
                    help="batches in flight before blocking on readback")
    ap.add_argument("--seconds", type=float, default=4.0)
    ap.add_argument("--f32-wire", action="store_true",
                    help="ship raw f32 features instead of the rank wire")
    ap.add_argument("--skip-interp", action="store_true",
                    help="skip the per-record interpreter baseline")
    ap.add_argument("--skip-latency", action="store_true",
                    help="skip the latency-mode operating point")
    ap.add_argument("--skip-kafka", action="store_true",
                    help="skip the Kafka wire-protocol operating point")
    ap.add_argument("--no-prefetch", action="store_true",
                    help="ablation: run kafka mode WITHOUT the "
                         "pipelined-ingest sidecar (runtime/prefetch.py)"
                         " — fetch+decode back on the ingest thread, "
                         "the pre-round-14 serial operating point")
    ap.add_argument("--no-autotune", action="store_true",
                    help="skip the warmup autotune sweep (ablation: the "
                         "hand-picked defaults + host encode)")
    ap.add_argument("--kernel-search", action="store_true",
                    help="force a FRESH learned kernel search during "
                         "warmup (ignore the autotune cache) so the "
                         "artifact carries the full predict-then-verify "
                         "ranking for this run")
    ap.add_argument("--no-kernel-search", action="store_true",
                    help="ablation: disable the learned-cost-model "
                         "layout search (the built ref layout only — "
                         "sets FJT_KERNEL_SEARCH_DISABLE=1)")
    ap.add_argument("--latency", action="store_true",
                    help="make the latency operating point the headline "
                         "metric (p50 record latency in ms)")
    ap.add_argument("--latency-batch", type=int, default=4096)
    ap.add_argument("--latency-deadline-us", type=int, default=2000)
    ap.add_argument("--latency-offered", type=float, default=100_000.0,
                    help="paced offered load (rec/s) for the latency mode")
    ap.add_argument("--load-shape", default="steady",
                    help="steady (default) or burst:<factor>x — the "
                         "latter appends the kafka burst-recovery "
                         "drill (watermark catch-up, drain ETA, "
                         "pressure decay) to the artifact as "
                         "burst_drill")
    ap.add_argument("--block-pipeline", action="store_true",
                    help="measure through the production BlockPipeline "
                         "(ring + rank wire) instead of the hand loop — "
                         "the engine-vs-bench parity check")
    ap.add_argument("--rollout-drill", action="store_true",
                    help="run the rollout control-plane correctness "
                         "drill (canary split ratio ±1%%, zero shadow "
                         "sink leakage) instead of the perf capture")
    ap.add_argument("--overload-drill", action="store_true",
                    help="run the overload-resilience drill instead of "
                         "the perf capture: p99 ≤ deadline at 80%% of "
                         "measured capacity, bounded p99 + explicit "
                         "shed_records at 150%% offered load, recovery "
                         "to <1.05x baseline after the surge")
    ap.add_argument("--overload-deadline-ms", type=float, default=None,
                    help="overload-drill deadline (default: "
                         "self-calibrated from the measured capacity "
                         "model)")
    ap.add_argument("--rollout-records", type=int, default=20_000,
                    help="records per rollout-drill phase")
    ap.add_argument("--rollout-fraction", type=float, default=0.2,
                    help="canary traffic share the drill asserts")
    ap.add_argument("--history-drill", action="store_true",
                    help="run the incident-replay acceptance drill "
                         "instead of the perf capture: a child process "
                         "records governed telemetry history through a "
                         "real overload incident, the parent SIGKILLs "
                         "it mid-append and reconstructs the incident "
                         "(pressure rise, shed trail, headroom "
                         "collapse, governed tenant table) from the "
                         "durable frames alone, with the downsample/"
                         "merge commutation asserted bitwise on the "
                         "same run's frames")
    ap.add_argument("--history-tenants", type=int, default=30,
                    help="synthetic tenants the history drill's child "
                         "books per-tenant counters for")
    ap.add_argument("--history-max-series", type=int, default=8,
                    help="FJT_METRICS_MAX_SERIES bound the history "
                         "drill governs under")
    ap.add_argument("--drift-drill", action="store_true",
                    help="run the data-drift acceptance drill instead "
                         "of the perf capture: perturb one feature's "
                         "generator mid-run, assert the drift alarm "
                         "lands on that feature within the window, the "
                         "control feature stays quiet, and the fleet-"
                         "merged sketch quantiles equal the per-worker "
                         "state merge exactly")
    ap.add_argument("--drift-records", type=int, default=12_000,
                    help="records per drift-drill phase")
    ap.add_argument("--recovery-drill", action="store_true",
                    help="run the kill-anywhere delivery-correctness "
                         "drill instead of the perf capture: SIGKILLs "
                         "(parent + in-worker fault sites) + poison "
                         "records against a supervised Kafka pipeline; "
                         "asserts zero loss, bounded duplication, "
                         "parseable checkpoints, monotone watermarks, "
                         "poison offsets exactly in the DLQ, and an "
                         "fjt-dlq redrive round-trip")
    ap.add_argument("--recovery-records", type=int, default=24_000,
                    help="records the recovery drill streams")
    ap.add_argument("--recovery-kills", type=int, default=2,
                    help="parent-driven SIGKILLs during the drill")
    ap.add_argument("--no-hard-poison", action="store_true",
                    help="skip the crash-loop (process-killing) poison "
                         "record — the drill's slowest phase")
    ap.add_argument("--device-fault-drill", action="store_true",
                    help="run the device-fault resilience drill "
                         "instead of the perf capture: injected "
                         "device_oom / device_error faults at the real "
                         "launch/readback sites against a supervised "
                         "Kafka pipeline, a SIGKILL while the circuit "
                         "is open; asserts zero loss, an EMPTY DLQ, "
                         "non-zero fallback-tier records, OOM batch "
                         "shrink, circuit re-close, monotone "
                         "watermarks, bounded p99")
    ap.add_argument("--device-fault-records", type=int, default=24_000,
                    help="records the device-fault drill streams")
    ap.add_argument("--no-fallback-kill", action="store_true",
                    help="skip the SIGKILL-during-fallback phase of "
                         "the device-fault drill")
    ap.add_argument("--mesh", action="store_true",
                    help="multichip mode: alone, run the per-chip "
                         "scaling-curve bench (one mesh-sharded "
                         "BlockPipeline per data-axis width over a "
                         "partitioned Kafka stream, fleet-merged "
                         "metrics) for the MULTICHIP artifact; "
                         "combined with --device-fault-drill, run the "
                         "on-mesh chip-loss drill (in-place "
                         "without_devices rebuild, zero loss, empty "
                         "DLQ, >=(N-1)/N degraded throughput). Both "
                         "use the host's real chips, or a simulated "
                         "8-device host under JAX_PLATFORMS=cpu")
    ap.add_argument("--mesh-records", type=int, default=40_000,
                    help="records per width the mesh bench streams")
    ap.add_argument("--zoo", action="store_true",
                    help="multi-tenant packed-scoring capture: "
                         "--zoo-registered tiny GBMs served, "
                         "--zoo-hot of them scored interleaved; "
                         "asserts packed-vs-solo byte parity, zero "
                         "leakage, aggregate throughput >= 75%% of the "
                         "single-model hand loop, and the rollout/"
                         "drift/failover planes keyed per tenant on "
                         "the same run")
    ap.add_argument("--zoo-registered", type=int, default=1000,
                    help="served model count for --zoo")
    ap.add_argument("--zoo-hot", type=int, default=100,
                    help="tenants receiving traffic in --zoo")
    ap.add_argument("--zoo-records", type=int, default=1024,
                    help="records per hot tenant in --zoo")
    ap.add_argument("--stateful", action="store_true",
                    help="keyed-state capture + acceptance drill: two "
                         "key mixes (full-domain permutation sweep + "
                         "zipf skew) stream --stateful-records each "
                         "through the fused per-key state stage "
                         "against a --stateful-capacity device table, "
                         "reporting rec/s/chip, occupancy, hit ratio "
                         "and the overhead vs a stateless loop; then "
                         "a SIGKILLed BlockPipeline run with "
                         "checkpoints must restore and finish with a "
                         "state table BYTE-identical to an "
                         "uninterrupted life")
    ap.add_argument("--stateful-keys", type=int, default=10_000_000,
                    help="distinct-key domain for --stateful")
    ap.add_argument("--stateful-records", type=int, default=10_485_760,
                    help="records per key mix in --stateful (must be "
                         "a multiple of --stateful-batch)")
    ap.add_argument("--stateful-capacity", type=int, default=1 << 21,
                    help="state-table slots for --stateful")
    ap.add_argument("--stateful-batch", type=int, default=8192,
                    help="dispatch batch for --stateful")
    ap.add_argument("--stateful-kills", type=int, default=2,
                    help="mid-stream SIGKILLs in the --stateful "
                         "kill->restore parity phase")
    return ap


def main() -> None:
    args = build_arg_parser().parse_args()
    burst_factor = _parse_load_shape(args.load_shape)  # validate early

    if args.zoo:
        # multi-tenant capture + acceptance drill (tiny GBMs compile
        # anywhere; the reader cache makes the 1,000-model registration
        # cheap)
        try:
            line = run_zoo_bench(
                registered=args.zoo_registered,
                hot=args.zoo_hot,
                records_per_hot=args.zoo_records,
            )
        except AssertionError as e:
            print(json.dumps({
                "metric": "zoo_bench", "ok": False, "error": str(e),
            }))
            sys.exit(1)
        print(json.dumps(line))
        return

    if args.stateful:
        # keyed-state capture (the state table and the dispatch loop
        # run on whatever backend resolved; the SIGKILL phase runs its
        # workers as JAX_PLATFORMS=cpu subprocesses)
        try:
            line = run_stateful_bench(
                keys=args.stateful_keys,
                records=args.stateful_records,
                capacity=args.stateful_capacity,
                batch=args.stateful_batch,
                kills=args.stateful_kills,
            )
        except AssertionError as e:
            print(json.dumps({
                "metric": "stateful_bench", "ok": False, "error": str(e),
            }))
            sys.exit(1)
        print(json.dumps(line))
        return

    if args.rollout_drill:
        # correctness drill, not a perf capture (a tiny GBM compiles
        # anywhere)
        try:
            line = run_rollout_drill(
                records=args.rollout_records,
                fraction=args.rollout_fraction,
            )
        except AssertionError as e:
            print(json.dumps({
                "metric": "rollout_drill", "ok": False, "error": str(e),
            }))
            sys.exit(1)
        print(json.dumps(line))
        return

    if args.overload_drill:
        # resilience drill, not a perf capture: capacity is measured
        # relative to THIS host, so the drill's geometry holds on a CPU
        # runner and a TPU alike
        try:
            line = run_overload_drill(
                deadline_ms=args.overload_deadline_ms,
            )
        except AssertionError as e:
            print(json.dumps({
                "metric": "overload_drill", "ok": False, "error": str(e),
            }))
            sys.exit(1)
        print(json.dumps(line))
        return

    if args.history_drill:
        # observability drill, not a perf capture: the child is a
        # jax-free synthetic-load process
        try:
            line = run_history_drill(
                tenants=args.history_tenants,
                max_series=args.history_max_series,
            )
        except AssertionError as e:
            print(json.dumps({
                "metric": "history_drill", "ok": False, "error": str(e),
            }))
            sys.exit(1)
        print(json.dumps(line))
        return

    if args.recovery_drill:
        # delivery-correctness drill, not a perf capture: the workers
        # are JAX_PLATFORMS=cpu subprocesses (a chip belongs to one
        # process; a restart storm of workers cannot share it)
        try:
            line = run_recovery_drill(
                records=args.recovery_records,
                kills=args.recovery_kills,
                hard_poison=not args.no_hard_poison,
            )
        except AssertionError as e:
            print(json.dumps({
                "metric": "recovery_drill", "ok": False, "error": str(e),
            }))
            sys.exit(1)
        print(json.dumps(line))
        return

    if args.device_fault_drill and args.mesh:
        # chip loss ON the mesh hot path: in-process (the loss is
        # survivable — the KIND_LOST rung rebuilds in place, so no
        # supervisor choreography is needed)
        try:
            line = run_mesh_fault_drill(
                records=args.device_fault_records,
            )
        except AssertionError as e:
            print(json.dumps({
                "metric": "mesh_device_fault_drill", "ok": False,
                "error": str(e),
            }))
            sys.exit(1)
        print(json.dumps(line))
        return

    if args.mesh:
        # per-chip scaling capture for the MULTICHIP artifact: the
        # host's real chips, or the simulated 8-device mesh under
        # JAX_PLATFORMS=cpu
        try:
            line = run_mesh_bench(records=args.mesh_records)
        except AssertionError as e:
            print(json.dumps({
                "metric": "mesh_scaling", "ok": False, "error": str(e),
            }))
            sys.exit(1)
        print(json.dumps(line))
        return

    if args.device_fault_drill:
        # device-fault resilience drill, not a perf capture: the
        # worker is a JAX_PLATFORMS=cpu subprocess (the faults are
        # injected, and a chip belongs to one process)
        try:
            line = run_device_fault_drill(
                records=args.device_fault_records,
                kill_during_fallback=not args.no_fallback_kill,
            )
        except AssertionError as e:
            print(json.dumps({
                "metric": "device_fault_drill", "ok": False,
                "error": str(e),
            }))
            sys.exit(1)
        print(json.dumps(line))
        return

    if args.drift_drill:
        # data-health drill, not a perf capture (a tiny GBM compiles
        # anywhere, both "workers" are registries in this process)
        try:
            line = run_drift_drill(records_per_phase=args.drift_records)
        except AssertionError as e:
            print(json.dumps({
                "metric": "drift_drill", "ok": False, "error": str(e),
            }))
            sys.exit(1)
        print(json.dumps(line))
        return

    metric = f"gbm{args.trees}_records_per_sec_per_chip"
    t_start = time.time()

    def stage(msg: str) -> None:
        print(f"[bench +{time.time() - t_start:6.1f}s] {msg}",
              file=sys.stderr, flush=True)

    stage("importing jax")

    import jax

    if args.no_autotune:
        # a true ablation: the compile-time cache consult must not apply
        # a config an earlier run swept (autotune.lookup honours this)
        os.environ["FJT_AUTOTUNE_DISABLE"] = "1"
    if args.no_kernel_search:
        # layout-search ablation: the warmup sweep times the built
        # default only (compile/autotune.py honours it)
        os.environ["FJT_KERNEL_SEARCH_DISABLE"] = "1"

    import jax.numpy as jnp
    import numpy as np

    backend = jax.default_backend()
    stage(f"backend resolved: {backend}")
    if backend != "tpu":
        # a rate from any other backend is not a chip rate: no line
        # under a *_per_chip metric, no shrunken "diagnostic" workload
        sys.exit(
            f"bench: the perf capture needs a TPU backend; JAX resolved "
            f"{backend!r} (JAX_PLATFORMS="
            f"{os.environ.get('JAX_PLATFORMS')!r})"
        )

    def quantiles(lats):
        if not lats:
            return None, None, None
        s = sorted(lats)
        # p50/p99 keep the historical convention (comparable across
        # BENCH rounds); the new p999 uses unbiased nearest-rank
        return (
            round(s[len(s) // 2], 6),
            round(s[min(len(s) - 1, int(0.99 * len(s)))], 6),
            round(s[_nearest_rank(0.999, len(s))], 6),
        )

    def interp_baseline(doc, X, n_records=100, repeats=3):
        """Pinned per-record oracle-interpreter rate (rec/s) on the same
        model: what a reference-style CPU evaluator costs, measured not
        assumed. Fixed record count, MEDIAN of repeats, and the caller
        runs it BEFORE the throughput windows — the round-3 tail-run
        version (deadline-bounded, after the windows, competing with
        encode-pool teardown) wobbled 4x across captures of the same
        model on the same host."""
        from flink_jpmml_tpu.pmml.interp import evaluate

        fields = doc.active_fields
        recs = [dict(zip(fields, row.tolist())) for row in X[:n_records]]
        evaluate(doc, recs[0])  # first-call setup out of the timing
        rates = []
        for _ in range(repeats):
            t0 = time.perf_counter()
            for rec in recs:
                evaluate(doc, rec)
            rates.append(len(recs) / (time.perf_counter() - t0))
        rates.sort()
        return rates[len(rates) // 2]

    # keep the dispatch/chunk contract valid for any flag combination
    args.batch = max(args.chunk, (args.batch // args.chunk) * args.chunk)

    from flink_jpmml_tpu.assets_gen import gen_gbm
    from flink_jpmml_tpu.compile import compile_pmml
    from flink_jpmml_tpu.pmml import parse_pmml_file

    # the model is generated fresh into a directory this run owns: a
    # file found by name would score whatever generator wrote it
    with tempfile.TemporaryDirectory(prefix="fjt-bench-") as model_dir:
        doc = parse_pmml_file(gen_gbm(
            model_dir,
            n_trees=args.trees,
            depth=args.depth,
            n_features=args.features,
        ))
    stage("model generated + parsed")

    B, C, F = args.batch, args.chunk, args.features
    K = B // C  # batch was normalized to a multiple of chunk above

    rng = np.random.default_rng(0)
    pool_f32 = [
        rng.normal(0.0, 1.5, size=(B, F)).astype(np.float32) for _ in range(4)
    ]

    # pinned oracle baseline FIRST: quiet host, nothing competing
    interp_rate = None
    if not args.skip_interp:
        stage("interp baseline (pinned, pre-windows)")
        interp_rate = interp_baseline(doc, pool_f32[0])
        stage(f"interp baseline: {interp_rate:,.1f} rec/s")

    cm = compile_pmml(doc, batch_size=C)
    stage("lowered (host)")

    # bench-warmup autotune: sweep fused-vs-host encode and the kernel
    # layouts on THIS backend, or apply the winner cached under
    # <checkout>/.fjt_cache. Runs before any measured window — it is
    # warmup.
    q_tuned = None if args.f32_wire else cm.quantized_scorer()
    tuned = None
    if q_tuned is not None and not args.no_autotune:
        from flink_jpmml_tpu.compile import autotune

        stage("autotune: cache consult / learned kernel search")
        tuned = autotune.ensure_tuned(
            q_tuned, pool_f32[0][:C], repeats=2,
            # --kernel-search: force a fresh predict-then-verify pass
            # so the artifact embeds THIS run's candidate ranking
            use_cache=not args.kernel_search,
        )
        stage(
            f"autotune: encode={tuned.encode} layout={tuned.layout} "
            f"source={tuned.source}"
        )

    def autotune_fields(line: dict) -> dict:
        line["autotune"] = tuned.as_dict() if tuned is not None else None
        # the predict-then-verify summary stands alone too: candidates
        # ranked vs timed, chosen variant, prediction residual — the
        # --kernel-search / --no-kernel-search story in one field
        line["kernel_search"] = tuned.search if tuned is not None else None
        line["encode_mode"] = (
            "f32" if args.f32_wire
            else (q_tuned.encode_mode if q_tuned is not None else None)
        )
        return line

    if args.block_pipeline:
        # the production path: f32 blocks → C++ ring → bucketizer →
        # quantized scoring → sink. Same model, same chunk size; reported
        # under the same metric so the two numbers are directly comparable.
        from flink_jpmml_tpu.runtime.block import (
            BlockPipeline, CyclingBlockSource,
        )
        from flink_jpmml_tpu.utils.config import BatchConfig, RuntimeConfig

        count = [0]

        def bsink(out, n, first_off):
            # force the D2H round trip so the rate counts *completed*
            # work, same as the hand loop — not async dispatches
            np.asarray(out.value if hasattr(out, "value") else
                       out[0] if isinstance(out, tuple) else out)
            count[0] += n

        pipe = BlockPipeline(
            CyclingBlockSource(np.concatenate(pool_f32), block_size=C),
            cm,
            bsink,
            RuntimeConfig(batch=BatchConfig(
                size=C, deadline_us=5000,
                # the ring must hold several batches or the drain
                # serializes on the ingest thread at large chunks
                queue_capacity=max(65536, 4 * C),
            )),
            use_quantized=not args.f32_wire,
        )
        _require_native(pipe)
        # data-health rides the artifact when a baseline is stored for
        # this model: features profile inside dispatch_quantized,
        # predictions at the sink, monitor ticks on the varz snapshot
        drift_fields = _drift_attach(pipe.metrics, cm)
        q = None if args.f32_wire else cm.quantized_scorer()
        if q is not None:
            jax.block_until_ready(
                q.predict_wire(q.wire.encode(pool_f32[0][:C]))
            )
        else:
            cm.warmup()
        t0 = time.perf_counter()
        pipe.run_for(seconds=args.seconds)
        dt = time.perf_counter() - t0
        rate = count[0] / dt
        # histogram-backed quantiles (runtime/block.py records batch
        # latency into the mergeable fixed-bucket histogram now): the
        # same sketch a fleet scrape merges, so the bench's p999 and a
        # production /metrics p999 are the same estimator
        blat = pipe.metrics.histogram("batch_latency_s")
        p50, p99, p999 = (
            blat.quantile(0.5), blat.quantile(0.99), blat.quantile(0.999)
        )

        ostats = overlap_stats(pipe.metrics, dt)
        line = {
            "metric": metric,
            "value": round(rate, 1),
            "unit": "records/s/chip",
            "vs_baseline": round(rate / NORTH_STAR_REC_S, 3),
            "device_value": None,  # keys uniform with the hand-loop line
            "backend": f"{backend}/{pipe.backend}",
            "p50_latency_s": round(p50, 6) if p50 is not None else None,
            "p99_latency_s": round(p99, 6) if p99 is not None else None,
            "p999_latency_s": round(p999, 6) if p999 is not None else None,
            "windows": [round(rate, 1)],  # keys uniform with the hand loop
            "best_window": round(rate, 1),
            "overlap_efficiency": ostats["overlap_efficiency"],
            "h2d_stall_ms": ostats["h2d_stall_ms"],
            "inflight_depth_max": ostats["inflight_depth_max"],
            "donation_hits": ostats["donation_hits"],
        }
        line.update(wire_stats(pipe.metrics, count[0]))
        line["attribution"] = attr_mod.summary(pipe.metrics)
        # the scrape format's first consumer: the same typed struct the
        # /metrics endpoint renders, embedded per operating mode so a
        # BENCH_*.json diff and a Prometheus scrape tell one story
        line["varz"] = pipe.metrics.struct_snapshot()
        if drift_fields is not None:
            line["drift"] = drift_fields()
        autotune_fields(line)
        if interp_rate is not None:
            line["interp_rec_s"] = round(interp_rate, 1)
            line["interp_ratio"] = round(rate / interp_rate, 1)
        if not args.skip_latency:
            stage("latency mode: compile + paced run")
            line["latency_mode"] = _measure_latency_mode(
                doc, pool_f32[0], args, use_quantized=not args.f32_wire
            )
            stage("latency mode done")
        if not args.skip_kafka:
            stage("kafka mode: broker + wire consume + score")
            line["kafka_mode"] = _measure_kafka_mode(
                cm, pool_f32[0], args, use_quantized=not args.f32_wire
            )
            stage("kafka mode done")
        if burst_factor:
            stage(f"burst drill: {burst_factor:g}x load shape")
            line["burst_drill"] = run_burst_drill(
                burst_factor=burst_factor
            )
            stage("burst drill done")
        if args.latency:
            line = _latency_headline(line, args.trees, line["backend"])
        print(json.dumps(line))
        return

    from flink_jpmml_tpu.utils.metrics import Counter

    # host featurize seconds, accumulated from the 2-worker encode pool
    # (the same lock-protected Counter dispatch_quantized feeds for the
    # other modes; windows account deltas against it)
    enc_counter = Counter()

    def _timed_encode(encode_impl):
        def encode(X):
            t0 = time.perf_counter()
            out = encode_impl(X)
            enc_counter.inc(time.perf_counter() - t0)
            return out
        return encode

    if args.f32_wire:
        inner = getattr(cm._jit_fn, "__wrapped__", cm._jit_fn)
        params = cm.params

        @jax.jit
        def run(p, X):
            def body(c, x):
                out = inner(p, x, jnp.isnan(x))
                return c, out.value.astype(jnp.bfloat16)
            _, vals = jax.lax.scan(body, 0, X.reshape(K, C, F))
            return vals.reshape(-1)

        encode = _timed_encode(lambda X: X)
    else:
        q = cm.quantized_scorer()
        assert q is not None, "bench GBM must be rank-wire eligible"
        params = q.params
        fused = q.encode_mode == "fused" and q.supports_fused
        # fused: raw f32 ships and the threshold-rank bucketize is
        # traced INTO the scan program (one dispatch covers
        # encode+pad+score); host: the C++ bucketizer runs in the
        # encode pool and uint8 codes ship
        qfn = (
            q._fused_inner if fused
            else getattr(q._jit_fn, "__wrapped__", q._jit_fn)
        )

        @jax.jit
        def run(p, Xq):
            def body(c, xq):
                return c, qfn(p, xq).astype(jnp.bfloat16)
            # -1: a packed-wire layout stages W bytes/record, not F
            _, vals = jax.lax.scan(body, 0, Xq.reshape(K, C, -1))
            return vals.reshape(-1)

        if fused:
            enc_impl = lambda X: X  # noqa: E731 — raw f32 ships as-is
        elif q._wire_pack is not None:
            # the kernel search adopted a packed-wire layout: the jit
            # entry expects packed bytes, so the hand loop (which
            # bypasses pad_wire) must pack too
            enc_impl = lambda X: q._wire_pack.pack(q.wire.encode(X))  # noqa: E731
        else:
            enc_impl = q.wire.encode
        encode = _timed_encode(enc_impl)

    # ---- pipeline: featurize (threads) → h2d → score → d2h readback ----
    # the window runs through the SAME OverlappedDispatcher as the
    # production pipelines (runtime/pipeline.py): encoded batches stage
    # via jax.device_put, dispatch async, and the host blocks only on
    # the oldest dispatch when the depth-K window is full — so the bench
    # measures the real overlap machinery and its stall accounting feeds
    # the overlap_efficiency / h2d_stall_ms artifact fields
    from flink_jpmml_tpu.runtime.pipeline import OverlappedDispatcher
    from flink_jpmml_tpu.utils.metrics import MetricsRegistry

    enc_pool = ThreadPoolExecutor(max_workers=2)

    # warm: compile + first transfers (excluded from the measurement)
    stage("warmup: first compile + transfers")
    payload0 = encode(pool_f32[0])
    h2d_per_rec = payload0.nbytes / B  # what one record costs on the wire
    warm = np.asarray(run(params, jax.device_put(payload0)))
    stage("warm done; measuring")
    assert warm.shape == (B,) and np.isfinite(
        warm.astype(np.float32)
    ).all(), "warmup produced non-finite scores"

    def measure_window(seconds: float):
        """One steady-state pipelined window → (rate, latencies,
        overlap stats)."""
        PRE = args.window + 2  # encoded batches staged ahead
        encoded = collections.deque(
            enc_pool.submit(encode, pool_f32[i % len(pool_f32)])
            for i in range(PRE)
        )
        done_records = [0]
        lats = []
        enc0 = enc_counter.get()  # per-window host-encode accounting
        # dispatch-issued stamps in FIFO order: latency = dispatch
        # complete → scores materialized, same quantity as every prior
        # round's artifact (NOT including the host-side staging call)
        t_dispatched = collections.deque()
        wm = MetricsRegistry()

        def complete(out, _meta):
            scores = np.asarray(out)  # D2H copy (prefetched at launch)
            lats.append(time.perf_counter() - t_dispatched.popleft())
            done_records[0] += scores.shape[0]

        disp = OverlappedDispatcher(
            depth=args.window, metrics=wm, complete=complete
        )

        def dispatch(Xq):
            out = run(params, jax.device_put(Xq))
            t_dispatched.append(time.perf_counter())
            return out

        i = 0
        t0 = time.perf_counter()
        deadline = t0 + seconds
        while time.perf_counter() < deadline:
            Xq = encoded.popleft().result()
            encoded.append(
                enc_pool.submit(
                    encode, pool_f32[(i + PRE) % len(pool_f32)]
                )
            )
            disp.launch(lambda Xq=Xq: dispatch(Xq))
            i += 1
        disp.close()  # drain the window: every dispatch counts or none
        elapsed = time.perf_counter() - t0
        rate_w = done_records[0] / elapsed
        # settle the staged-ahead encode futures OUTSIDE the timed
        # window: leftovers would otherwise clog the shared pool and
        # depress the next window's start (and linger past shutdown)
        for f in encoded:
            f.cancel() or f.result()
        ostats_w = overlap_stats(wm, elapsed)
        ostats_w["encode_ms"] = round(
            1000.0 * (enc_counter.get() - enc0), 3
        )
        # per-stage attribution + the window's scrape struct: the hand
        # loop's queue_wait/readback columns come from the shared
        # dispatcher; encode/h2d ride the artifact's existing fields
        ostats_w["attribution"] = attr_mod.summary(wm)
        ostats_w["varz"] = wm.struct_snapshot()
        return rate_w, lats, ostats_w

    # three windows: "value" is the MEDIAN (a best-of policy shipped a
    # max the repeats didn't reproduce); the max rides "best_window",
    # every window rides "windows".
    windows = [measure_window(args.seconds) for _ in range(3)]
    by_rate = sorted(windows, key=lambda t: t[0])
    rate, lats, ostats = by_rate[len(by_rate) // 2]
    best_rate = by_rate[-1][0]
    enc_pool.shutdown(wait=False)
    p50, p99, p999 = quantiles(lats)
    stage(
        "pipelined windows: "
        + ", ".join(f"{r:,.0f}" for r, _, _ in windows)
        + " rec/s"
    )

    # pure device-side rate: batch already resident, no host link in the
    # loop — separates chip capability from the host side of the path.
    # Completion-counted with a 2-deep in-flight window: an unthrottled
    # dispatch loop would queue minutes of executions on a slow backend
    # and then hang in the final block_until_ready (the round-3 bench
    # timeout on both TPU and CPU was exactly that).
    Xq_dev = jax.device_put(encode(pool_f32[0]))
    jax.block_until_ready(run(params, Xq_dev))
    reps = 0
    pending = collections.deque()
    t1 = time.perf_counter()
    dev_deadline = t1 + min(3.0, args.seconds)
    while True:
        dispatching = time.perf_counter() < dev_deadline
        if not dispatching and not pending:
            break
        if dispatching:
            pending.append(run(params, Xq_dev))
        while len(pending) > (2 if dispatching else 0):
            jax.block_until_ready(pending.popleft())
            reps += 1
    dev_rate = reps * B / (time.perf_counter() - t1)
    stage(f"device-resident measurement done: {dev_rate:,.0f} rec/s")

    # the fused path also streams raw f32 to the device; one predicate
    # feeds both the artifact roofline and the kernel cost ledger so
    # their bytes_per_record can never diverge
    f32ish = args.f32_wire or (
        q_tuned is not None and q_tuned.encode_mode == "fused"
    )
    mfu, membw_util, flops_rec = _device_utilization(
        dev_rate, args.trees, args.depth, args.features, f32ish,
    )
    # feed the bench's high-quality device measurement into the kernel
    # cost ledger (obs/profiler.py, persisted next to the autotune
    # cache): the predict-then-verify cost model's best training rows
    # come from here, where the measurement is device-resident and
    # multi-second, not a single sampled bracket
    if dev_rate > 0:
        prof_mod.KernelCostLedger(flush_interval_s=0.0).update(
            model=(
                q_tuned.model_hash if q_tuned is not None
                else f"gbm{args.trees}x{args.depth}x{args.features}"
            ),
            backend=f"bench:{backend}",
            device_s=reps * B / dev_rate,
            records=reps * B,
            flops_per_record=flops_rec,
            bytes_per_record=(
                q_tuned.staged_bytes_per_record + 2.0
                if q_tuned is not None and not args.f32_wire
                else (4.0 * args.features if f32ish
                      else float(args.features)) + 2.0
            ),
            # the adopted variant's provenance makes this a training
            # row for the learned cost model (compile/costmodel.py):
            # device-resident, multi-second — its best data
            variant=getattr(q_tuned, "_cost_variant", None),
            features=getattr(q_tuned, "_cost_feat", None),
            # the SERVING variant's prediction (nulled by autotune when
            # a cached variant degraded to defaults) — tuned.predicted
            # records cache provenance, which may describe a kernel
            # that is not running
            predicted=getattr(q_tuned, "_pred_s_per_record", None),
        )
    # data-health for the hand loop: the scan path bypasses
    # dispatch_quantized, so when a baseline is stored the drift
    # profile records the pool slices (the exact stream being scored)
    # and the warm scores into a sidecar registry, whose families merge
    # into the embedded varz — every mode's artifact then carries the
    # drift varz family when a baseline is present
    drift_line = None
    if q_tuned is not None:
        from flink_jpmml_tpu.obs import drift as drift_mod
        from flink_jpmml_tpu.utils.metrics import merge_structs

        if drift_mod.BaselineStore().load(q_tuned.model_hash) is not None:
            dm = MetricsRegistry()
            dplane = drift_mod.install(dm, interval_s=0.0)
            for Xf in pool_f32:
                dplane.record_features(q_tuned, Xf)
            dplane.record_predictions(q_tuned, warm, B)
            drift_line = drift_mod.artifact_fields(dm)
            ostats["varz"] = merge_structs(
                [ostats.get("varz") or {}, dm.struct_snapshot()]
            )

    line = {
        "metric": metric,
        "value": round(rate, 1),
        "unit": "records/s/chip",
        "vs_baseline": round(rate / NORTH_STAR_REC_S, 3),
        "device_value": round(dev_rate, 1),
        "backend": backend,
        "p50_latency_s": p50,
        "p99_latency_s": p99,
        "p999_latency_s": p999,
        "windows": [round(r, 1) for r, _, _ in windows],
        "best_window": round(best_rate, 1),
        # overlap accounting for the MEDIAN window (the headline rate):
        # how well host staging hid behind device execution, and the
        # total host time gated on the device
        "overlap_efficiency": ostats["overlap_efficiency"],
        "h2d_stall_ms": ostats["h2d_stall_ms"],
        "inflight_depth_max": ostats["inflight_depth_max"],
        # encode placement accounting for the MEDIAN window: host
        # featurize time (≈0 when the autotuner fused the encode onto
        # the device) and staged bytes per record on the wire
        "encode_ms": ostats.get("encode_ms"),
        "h2d_bytes_per_record": round(h2d_per_rec, 2),
        # honest roofline: achieved device FLOP/s and HBM bytes/s vs the
        # chip's peaks (null off-TPU / unknown chip); low MFU is the
        # DESIGN for this gather-shaped workload — the rank wire trades
        # FLOPs toward bandwidth (docs/performance.md)
        "device_mfu": mfu,
        "device_membw_util": membw_util,
        "flops_per_record": flops_rec,
        # stage attribution + scrape struct of the MEDIAN window: the
        # same stage_seconds family a production /metrics scrape serves
        "attribution": ostats.get("attribution"),
        "varz": ostats.get("varz"),
    }
    if drift_line is not None:
        line["drift"] = drift_line
    autotune_fields(line)
    if interp_rate is not None:
        line["interp_rec_s"] = round(interp_rate, 1)
        line["interp_ratio"] = round(rate / interp_rate, 1)
    if not args.skip_latency:
        stage("latency mode: compile + paced run")
        line["latency_mode"] = _measure_latency_mode(
            doc, pool_f32[0], args, use_quantized=not args.f32_wire
        )
        stage("latency mode done")
    if not args.skip_kafka:
        stage("kafka mode: broker + wire consume + score")
        line["kafka_mode"] = _measure_kafka_mode(
            cm, pool_f32[0], args, use_quantized=not args.f32_wire
        )
        stage("kafka mode done")
    if burst_factor:
        stage(f"burst drill: {burst_factor:g}x load shape")
        line["burst_drill"] = run_burst_drill(burst_factor=burst_factor)
        stage("burst drill done")
    if args.latency:
        line = _latency_headline(line, args.trees, backend)
    print(json.dumps(line))


if __name__ == "__main__":
    main()
