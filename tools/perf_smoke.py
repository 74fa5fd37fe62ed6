#!/usr/bin/env python
"""Perf-smoke check for the overlapped dispatch pipeline (CI tier-1).

Runs a tiny GBM stream through the production BlockPipeline — and a raw
:class:`OverlappedDispatcher` window — under ``JAX_PLATFORMS=cpu``, and
fails loudly on exactly the regressions new concurrency code breeds:

- **ordering**: sink deliveries must arrive in contiguous offset order
  (the dispatcher's FIFO contract feeding the commit protocol);
- **loss/duplication**: every source record reaches the sink once;
- **shutdown hangs**: the whole check runs under a hard watchdog that
  dumps all thread stacks and force-exits non-zero if the pipeline
  wedges instead of draining;
- **fused-encode divergence**: the on-device featurize stage
  (compile/qtrees.py fused path) must stay byte-identical to the host
  bucketizer, through the production pipeline too;
- **autotune-cache fragility**: a corrupt on-disk autotune cache must
  read as empty (silent re-tune) — never crash a compile or a sweep;
- **kernel-search budget rot**: the learned predict-then-verify search
  (compile/costmodel.py + autotune) must time at most top-K of the
  layout×tile candidate space, land its timings in the kernel cost
  ledger as feature rows a replayed fit predicts within a sane band,
  treat a stale search-space tag as no cache entry, and keep the
  ``--no-kernel-search`` ablation flag wired;
- **scrape-surface rot**: a live pipeline's ``/metrics`` endpoint
  (obs/server.py) must serve parseable Prometheus text whose
  ``fjt_records_out`` is non-zero and whose histogram ``_count``
  matches its ``+Inf`` bucket — the fleet dashboard's ground truth —
  and, since the attribution plane landed, non-zero per-stage
  ``fjt_stage_seconds`` histograms, a live ``fjt_device_ns_per_record`` gauge,
  and at least one Prometheus exemplar whose trace id resolves to a
  ``latency_exemplar`` flight-recorder event;
- **observability overhead**: the stage ledger + sampled device
  profiler must cost ≤2% of hand-loop dispatch throughput — measured
  as per-launch attribution ops against per-launch dispatch time (the
  tripwire for anyone adding per-batch work to the obs plane);
- **rollout-plane drift**: the canary hash split must hand the
  candidate its configured fraction ±1% with zero shadow-traffic sink
  leakage (the ``bench.py --rollout-drill`` engine at smoke scale);
- **freshness-plane rot**: the ``bench.py --load-shape burst:2x``
  burst-recovery drill at smoke scale — event-time ``watermark_lag_s``
  must build under a 2× burst and recover within a bounded drain
  window with a finite ``lag_drain_eta_s`` en route, the composite
  ``pressure`` score must reach ≥0.5 under the burst and decay after,
  and a live mid-drain ``/metrics`` scrape must expose non-zero
  ``record_staleness_s`` buckets, ``pressure`` in [0,1], and
  per-partition ``watermark_lag_s`` (the acceptance surface ROADMAP
  item 5's adaptive-batching controller will read);
- **overload-plane rot**: the ``bench.py --overload-drill`` engine at
  smoke scale — p99 ≤ deadline at 80% of measured capacity, bounded
  p99 plus a NON-ZERO explicit ``shed_records`` counter at 150%
  offered load, and post-surge recovery to <1.05× the steady-state
  p99 (ROADMAP item 5's acceptance drill, tier-1-guarded);
- **drift-plane rot**: the ``bench.py --drift-drill`` engine at smoke
  scale — the perturbed feature's ``drift_alarm`` fires while the
  control feature stays quiet and the fleet-merged sketch quantiles
  equal the per-worker state merge exactly — plus a live ``/metrics``
  scrape of a baselined production pipeline asserting non-zero
  ``fjt_drift_score`` gauges and feature-profile counters, and the
  ≤2%-of-dispatch overhead bound on the sampled profile path (the
  unsampled gate is µs-scale, and the accumulated-overhead budget
  keeps the sampled work under 2% of wall clock by construction);
- **journey-trace rot**: the record-journey plane (``obs/trace.py``) —
  the unarmed per-dispatch gate must stay ≤2µs, the accumulated-
  overhead budget must hold when armed (a zero-budget store sheds its
  own bookkeeping, never the pipeline's throughput), and a live
  ``/trace`` scrape must retrieve ≥1 complete journey whose sink hop's
  trace id matches a ``latency_exemplar`` flight event (the
  fjt-top → fjt-trace pivot's ground truth);
- **device-fault-plane rot**: the recovery ladder (``runtime/
  devfault.py`` + ``serving/failover.py``) at smoke scale — an
  injected persistent ``device_error`` streak must trip the circuit
  breaker onto the host fallback tier (a live ``/metrics`` scrape
  mid-outage shows ``fjt_failover_state`` open and non-zero
  ``fjt_fallback_records``), the breaker must re-close on green
  probes, redispatch must land records, the stream must drain with
  zero loss, and the unarmed device fault-hook sites must stay ≤2µs;
- **fault-hook overhead**: with ``FJT_FAULTS`` unset, the injection
  hooks on the fetch/dispatch/checkpoint/score paths
  (``runtime/faults.py fire()``) must be a genuine no-op — sub-µs per
  call and no installed plan — so the harness costs nothing when it
  isn't drilling.

Seconds-cheap by design (tier-1 guards it — tests/test_perf_smoke.py);
exit 0 = healthy, 1 = assertion failure, 2 = watchdog fired.
"""

import faulthandler
import os
import pathlib
import shutil
import sys
import tempfile
import threading

os.environ.setdefault("JAX_PLATFORMS", "cpu")
# runnable from anywhere: the repo root (one level up) on the path
sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))

WATCHDOG_S = float(os.environ.get("FJT_SMOKE_WATCHDOG_S", 150.0))

# hermetic autotune cache: the smoke must neither inherit a developer's
# .fjt_cache entries (a cached "fused" config would change which
# path check_block_pipeline exercises) nor pollute them
os.environ["FJT_AUTOTUNE_CACHE"] = os.path.join(
    tempfile.mkdtemp(prefix="fjt-smoke-at-"), "autotune.json"
)


def _watchdog():
    """Force-exit with stacks when the pipeline wedges: a hang is the
    failure mode this smoke exists to catch, so it must terminate."""
    faulthandler.dump_traceback(file=sys.stderr)
    print(
        f"perf-smoke: WATCHDOG after {WATCHDOG_S:.0f}s — "
        "pipeline shutdown hang",
        file=sys.stderr,
        flush=True,
    )
    os._exit(2)


def check_dispatcher_ordering() -> None:
    """Raw window FIFO under adversarial completion timing: leaves that
    become ready out of order must still complete in launch order."""
    import time

    from flink_jpmml_tpu.runtime.pipeline import OverlappedDispatcher

    class _Leaf:
        def __init__(self, i):
            self.i = i
            # later launches get SHORTER waits: readiness order is the
            # reverse of launch order, the worst case for FIFO delivery
            self.delay = max(0.0, (8 - i) * 0.002)

        def block_until_ready(self):
            time.sleep(self.delay)

    seen = []
    disp = OverlappedDispatcher(
        depth=3, complete=lambda out, meta: seen.append(meta)
    )
    for i in range(32):
        disp.launch(lambda i=i: _Leaf(i), meta=i)
    disp.close()
    assert seen == list(range(32)), f"dispatcher order broke: {seen[:10]}..."
    assert len(disp) == 0, "close() left work in flight"


def check_block_pipeline() -> None:
    """Tiny GBM through the production overlapped block pipeline:
    exhaustive drain, in-order contiguous sink offsets, no loss."""
    import numpy as np

    from assets.generate import gen_gbm
    from flink_jpmml_tpu.compile import compile_pmml
    from flink_jpmml_tpu.pmml import parse_pmml_file
    from flink_jpmml_tpu.runtime.block import BlockPipeline, FiniteBlockSource

    with tempfile.TemporaryDirectory() as tmp:
        doc = parse_pmml_file(
            gen_gbm(tmp, n_trees=10, depth=3, n_features=4)
        )
    cm = compile_pmml(doc, batch_size=64)
    rng = np.random.default_rng(0)
    data = rng.normal(0.0, 1.0, size=(1000, 4)).astype(np.float32)

    deliveries = []

    def sink(out, n, first_off):
        np.asarray(out if not hasattr(out, "value") else out.value)
        deliveries.append((first_off, n))

    pipe = BlockPipeline(
        FiniteBlockSource(data, block_size=100),
        cm,
        sink,
        in_flight=3,
        use_native=False,
    )
    pipe.run_until_exhausted(timeout=60.0)

    total = sum(n for _, n in deliveries)
    assert total == 1000, f"lost/duplicated records: {total} != 1000"
    cursor = 0
    for first_off, n in deliveries:
        assert first_off == cursor, (
            f"out-of-order sink delivery at offset {first_off}, "
            f"expected {cursor}"
        )
        cursor += n
    assert pipe.committed_offset == 1000, pipe.committed_offset
    snap = pipe.metrics.snapshot()
    assert snap["records_out"] == 1000, snap["records_out"]
    assert snap["dispatches"] >= 1


def check_kafka_pipeline() -> None:
    """Pipelined-ingest tripwire (ISSUE 14): the Kafka wire path with
    the prefetch/decode sidecar armed end to end — in-order no-loss
    delivery through a real (loopback) broker, a non-zero
    ``prefetch_depth`` high-water proving the sidecar actually ran
    ahead, decode-tier byte parity (python walk vs vectorized numpy),
    and the ``--no-prefetch`` ablation (serial ingest) still passing
    the same ordering contract."""
    import time

    import numpy as np

    from assets.generate import gen_gbm
    from flink_jpmml_tpu.compile import compile_pmml
    from flink_jpmml_tpu.pmml import parse_pmml_file
    from flink_jpmml_tpu.runtime.block import BlockPipeline
    from flink_jpmml_tpu.runtime.kafka import (
        KafkaBlockSource,
        MiniKafkaBroker,
        decode_record_batches_rows_py,
        decode_record_batches_rows_vec,
        encode_record_batch,
    )
    from flink_jpmml_tpu.runtime.prefetch import PrefetchedBlockSource
    from flink_jpmml_tpu.utils.metrics import MetricsRegistry

    with tempfile.TemporaryDirectory() as tmp:
        doc = parse_pmml_file(
            gen_gbm(tmp, n_trees=10, depth=3, n_features=4)
        )
    cm = compile_pmml(doc, batch_size=64)
    rng = np.random.default_rng(3)
    data = rng.normal(size=(6000, 4)).astype(np.float32)
    data[17, 2] = np.nan  # missing-value lane rides the wire too

    # decode-tier parity: canonical layout AND the header-carrying
    # fallback must be byte-identical to the python oracle
    vals = [data[i].tobytes() for i in range(256)]
    for hdrs in (None, [[("traceparent", b"00-ab-cd-01")]] + [None] * 255):
        buf = encode_record_batch(7, vals, timestamp_ms=5, headers=hdrs)
        o1, r1 = decode_record_batches_rows_py(buf, 4)
        o2, r2 = decode_record_batches_rows_vec(buf, 4)
        assert (o1 == o2).all() and r1.tobytes() == r2.tobytes(), (
            "vectorized decode diverged from the python oracle"
        )

    def run(prefetch: bool) -> dict:
        broker = MiniKafkaBroker(topic="smoke")
        src = None
        try:
            broker.append_rows(data)
            km = MetricsRegistry()
            src = KafkaBlockSource(
                broker.host, broker.port, "smoke",
                n_cols=4, max_wait_ms=20, metrics=km,
            )
            deliveries = []

            def sink(out, n, first_off):
                deliveries.append((first_off, n))

            pipe = BlockPipeline(
                src, cm, sink, metrics=km, in_flight=2,
                prefetch=prefetch,
            )
            if prefetch:
                assert isinstance(pipe._source, PrefetchedBlockSource)
            else:
                assert pipe._source is src, "ablation still wrapped"
            pipe.start()
            deadline = time.monotonic() + 60.0
            while (
                sum(n for _, n in deliveries) < 6000
                and time.monotonic() < deadline
            ):
                time.sleep(0.02)
            pipe.stop()
            pipe.join(timeout=30.0)
            total = sum(n for _, n in deliveries)
            assert total == 6000, f"lost records: {total} != 6000"
            cursor = 0
            for first_off, n in deliveries:
                assert first_off == cursor, (
                    f"out-of-order delivery at {first_off} != {cursor} "
                    f"(prefetch={prefetch})"
                )
                cursor += n
            return km.struct_snapshot()
        finally:
            if src is not None:
                src.close()
            broker.close()

    snap = run(True)
    assert snap["gauges"]["prefetch_depth"]["max"] > 0, (
        "prefetch sidecar never queued a batch ahead"
    )
    assert snap["counters"].get("prefetch_batches", 0) >= 1, (
        snap["counters"]
    )
    snap2 = run(False)
    assert "prefetch_batches" not in snap2["counters"], (
        "--no-prefetch ablation still ran the sidecar"
    )


def check_fused_pipeline_parity() -> None:
    """Fused on-device encode through the production BlockPipeline:
    byte-identical codes vs the host bucketizer, and identical decoded
    scores for the whole stream (no loss, no divergence)."""
    import numpy as np

    from assets.generate import gen_gbm
    from flink_jpmml_tpu.compile import compile_pmml
    from flink_jpmml_tpu.pmml import parse_pmml_file
    from flink_jpmml_tpu.runtime.block import BlockPipeline, FiniteBlockSource

    with tempfile.TemporaryDirectory() as tmp:
        doc = parse_pmml_file(
            gen_gbm(tmp, n_trees=10, depth=3, n_features=4)
        )
    cm = compile_pmml(doc, batch_size=64)
    q = cm.quantized_scorer()
    assert q is not None and q.supports_fused, "fused path unavailable"
    rng = np.random.default_rng(1)
    data = rng.normal(0.0, 1.5, size=(1000, 4)).astype(np.float32)
    data[rng.random(size=data.shape) < 0.2] = np.nan

    # 1) encode-stage byte parity
    host_codes = q.wire.encode(data)
    dev_codes = np.asarray(q.encode_device(data))
    assert dev_codes.dtype == host_codes.dtype
    assert np.array_equal(dev_codes, host_codes), "fused encode diverged"

    # 2) whole-stream parity through the production pipeline: host-mode
    # run vs fused-mode run over the same stream — identical dispatch
    # shapes, so byte-identical codes must mean BIT-identical scores
    def run_pipeline(mode):
        q.encode_mode = mode
        got = np.full((1000,), np.nan, np.float32)

        def sink(out, n, first_off):
            vals = np.asarray(
                out if not hasattr(out, "value") else out.value, np.float32
            )[:n]
            got[first_off : first_off + n] = vals

        pipe = BlockPipeline(
            FiniteBlockSource(data, block_size=100),
            cm,
            sink,
            in_flight=2,
            use_native=False,
        )
        pipe.run_until_exhausted(timeout=60.0)
        assert np.isfinite(got).all(), f"{mode} pipeline lost records"
        return got, pipe.metrics.snapshot()

    ref, snap_host = run_pipeline("host")
    got, snap_fused = run_pipeline("fused")
    # the two runs may pick different drain/aggregation boundaries (the
    # fill-or-deadline ring is timing-dependent), so scores compare at
    # f32 noise tolerance; the CODES above are the bit-exactness check
    assert np.allclose(got, ref, rtol=1e-5, atol=1e-6), (
        "fused pipeline scores diverged from the host-encode oracle"
    )
    # fused ships raw f32 (4 bytes/feature) vs the uint8 wire (1): the
    # staged-bytes accounting must reflect it (ratio has slack because
    # per-run padding differs with drain boundaries)
    ratio = snap_fused["h2d_bytes"] / max(snap_host["h2d_bytes"], 1)
    assert 3.5 < ratio < 4.6, (
        f"fused h2d accounting wrong (bytes ratio {ratio:.2f}, expected ~4)"
    )


def check_autotune_cache_roundtrip() -> None:
    """Sweep → persist → cache-consult round trip, plus the corrupt-file
    contract: garbage on disk means silent re-tune, not a crash."""
    import json

    import numpy as np

    from assets.generate import gen_gbm
    from flink_jpmml_tpu.compile import autotune
    from flink_jpmml_tpu.compile.qtrees import build_quantized_scorer
    from flink_jpmml_tpu.pmml import parse_pmml_file

    with tempfile.TemporaryDirectory() as tmp:
        doc = parse_pmml_file(
            gen_gbm(tmp, n_trees=10, depth=3, n_features=4)
        )
    rng = np.random.default_rng(2)
    X = rng.normal(0.0, 1.5, size=(64, 4)).astype(np.float32)
    prev_cache = os.environ.get("FJT_AUTOTUNE_CACHE")
    with tempfile.TemporaryDirectory() as tmp:
        os.environ["FJT_AUTOTUNE_CACHE"] = os.path.join(tmp, "at.json")
        try:
            q = build_quantized_scorer(doc, batch_size=64)
            cfg = autotune.ensure_tuned(q, X, repeats=1)
            assert cfg.source == "sweep"
            with open(autotune.cache_path()) as f:
                assert json.load(f)["entries"], "sweep did not persist"
            q2 = build_quantized_scorer(doc, batch_size=64)
            assert q2.tuned is not None and q2.tuned.source == "cache", (
                "fresh compile did not consult the cache"
            )
            # corrupt the file: everything must keep working silently
            with open(autotune.cache_path(), "w") as f:
                f.write("\x00garbage{{{")
            q3 = build_quantized_scorer(doc, batch_size=64)  # no crash
            assert q3.tuned is None
            cfg3 = autotune.ensure_tuned(q3, X, repeats=1)
            assert cfg3.source == "sweep", "corrupt cache did not re-tune"
            with open(autotune.cache_path()) as f:
                assert json.load(f)["entries"], "re-tune did not rewrite"
        finally:
            if prev_cache is None:
                os.environ.pop("FJT_AUTOTUNE_CACHE", None)
            else:
                os.environ["FJT_AUTOTUNE_CACHE"] = prev_cache


def check_kernel_search() -> None:
    """Learned kernel search tripwire (ISSUE 11): the predict-then-
    verify search must complete within its candidate budget (top-K
    timed, NOT the full layout×tile space), feed the kernel cost
    ledger rows a ledger-replay fit predicts within a sane band, honor
    the stale-space-tag invalidation, and keep the
    ``--no-kernel-search`` bench ablation flag wired."""
    import json
    import math

    import numpy as np

    from assets.generate import gen_gbm
    from flink_jpmml_tpu.compile import autotune, costmodel, layouts
    from flink_jpmml_tpu.compile.qtrees import build_quantized_scorer
    from flink_jpmml_tpu.obs import profiler
    from flink_jpmml_tpu.pmml import parse_pmml_file

    with tempfile.TemporaryDirectory() as tmp:
        doc = parse_pmml_file(
            gen_gbm(tmp, n_trees=12, depth=3, n_features=4)
        )
    rng = np.random.default_rng(6)
    X = rng.normal(0.0, 1.5, size=(64, 4)).astype(np.float32)
    prev_cache = os.environ.get("FJT_AUTOTUNE_CACHE")
    with tempfile.TemporaryDirectory() as tmp:
        os.environ["FJT_AUTOTUNE_CACHE"] = os.path.join(tmp, "at.json")
        try:
            q = build_quantized_scorer(
                doc, batch_size=64, backend="pallas", pallas_interpret=True
            )
            cfg = autotune.ensure_tuned(q, X, repeats=1, top_k=3)
            s = cfg.search
            assert s is not None and s["space"] == layouts.SPACE_TAG
            # the budget: top-K timed, not the full space
            assert s["timed"] <= s["top_k"] == 3, s
            assert s["candidates_total"] > s["top_k"], s
            assert cfg.layout in (
                "ref", "bfs", "mega", "mega_bfs"
            ), cfg.layout
            # every timed candidate became a ledger training row with
            # features, and a replayed fit predicts each row within a
            # sane band (interpret-mode timings are noisy; the band
            # checks sanity, not precision)
            rows = costmodel.training_rows(
                profiler.cost_ledger_path()
            )
            assert len(rows) >= s["timed"] > 0, (len(rows), s)
            model = costmodel.fit_from_ledger(
                path=profiler.cost_ledger_path(), min_rows=1
            )
            assert model is not None, "ledger replay produced no fit"
            for feats, y in rows:
                pred = model.predict(feats)
                assert pred is not None and pred > 0
                assert abs(math.log(pred / y)) < math.log(16.0), (
                    f"ledger-replay prediction {pred} vs observed {y} "
                    "outside the 16x sanity band"
                )
            # stale space tag ⇒ silent re-search (the cached pre-layout
            # winner must never pin a new binary)
            key = autotune.backend_key(q)
            path = autotune.cache_path()
            data = json.load(open(path))
            entry = data["entries"][f"{q.model_hash}|{key}"]
            entry["space"] = "space-v0:obsolete"
            path.write_text(json.dumps(data))
            assert autotune.lookup(q.model_hash, key) is None, (
                "an obsolete-space cache entry was honoured"
            )
            # the --no-kernel-search ablation gate: the built default
            # alone, no layout candidates
            os.environ["FJT_KERNEL_SEARCH_DISABLE"] = "1"
            try:
                q2 = build_quantized_scorer(
                    doc, batch_size=64, backend="pallas",
                    pallas_interpret=True,
                )
                cfg2 = autotune.sweep(q2, X, repeats=1, top_k=3)
                assert cfg2.search["mode"] == "legacy", cfg2.search
                assert cfg2.layout == "ref"
            finally:
                os.environ.pop("FJT_KERNEL_SEARCH_DISABLE", None)
        finally:
            if prev_cache is None:
                os.environ.pop("FJT_AUTOTUNE_CACHE", None)
            else:
                os.environ["FJT_AUTOTUNE_CACHE"] = prev_cache
    # the bench flag itself stays wired (parse-level, no measurement)
    from flink_jpmml_tpu.bench import build_arg_parser

    ns = build_arg_parser().parse_args(["--no-kernel-search"])
    assert ns.no_kernel_search and not ns.kernel_search
    ns = build_arg_parser().parse_args(["--kernel-search"])
    assert ns.kernel_search


def check_obs_scrape() -> None:
    """Live-pipeline /metrics tripwire: run a small stream with an
    ObsServer attached to its registry, scrape over real HTTP, and
    assert the scrape is a truthful Prometheus rendering — non-zero
    ``fjt_records_out``, histogram ``_count`` == ``+Inf`` bucket,
    non-zero per-stage ``fjt_stage_seconds`` attribution, a live
    ``fjt_device_ns_per_record`` gauge (the sampled profiler fired; the
    roofline gauges need a chip whose peaks are known), and ≥1
    exemplar resolving to a ``latency_exemplar`` flight event."""
    import re
    import urllib.request

    import numpy as np

    from assets.generate import gen_gbm
    from flink_jpmml_tpu.compile import compile_pmml
    from flink_jpmml_tpu.obs import recorder as flight
    from flink_jpmml_tpu.obs.server import ObsServer
    from flink_jpmml_tpu.pmml import parse_pmml_file
    from flink_jpmml_tpu.runtime.block import BlockPipeline, FiniteBlockSource

    with tempfile.TemporaryDirectory() as tmp:
        doc = parse_pmml_file(
            gen_gbm(tmp, n_trees=10, depth=3, n_features=4)
        )
    cm = compile_pmml(doc, batch_size=64)
    rng = np.random.default_rng(3)
    data = rng.normal(0.0, 1.0, size=(1000, 4)).astype(np.float32)

    def sink(out, n, first_off):
        np.asarray(out if not hasattr(out, "value") else out.value)

    pipe = BlockPipeline(
        FiniteBlockSource(data, block_size=100), cm, sink,
        in_flight=2, use_native=False,
    )
    srv = ObsServer.for_registry(pipe.metrics)
    try:
        pipe.run_until_exhausted(timeout=60.0)
        # a plain scrape serves classic 0.0.4 — which must stay free of
        # exemplar suffixes (a stock text parser rejects a page with
        # them); the OpenMetrics-negotiated scrape carries them
        with urllib.request.urlopen(srv.url + "/metrics", timeout=10) as r:
            assert r.status == 200
            assert "trace_id" not in r.read().decode(), (
                "exemplars leaked into a classic 0.0.4 scrape"
            )
        req = urllib.request.Request(
            srv.url + "/metrics",
            headers={"Accept": "application/openmetrics-text"},
        )
        with urllib.request.urlopen(req, timeout=10) as r:
            assert r.status == 200
            assert "openmetrics-text" in r.headers.get("Content-Type", "")
            text = r.read().decode()
        assert text.endswith("# EOF\n"), "OpenMetrics page missing # EOF"
        metrics = {}
        for line in text.splitlines():
            if line.startswith("#") or not line.strip():
                continue
            # exemplar suffixes (` # {trace_id="..."} v ts`) are not
            # part of the sample value
            line = line.split(" # ", 1)[0]
            name, value = line.rsplit(" ", 1)
            metrics[name] = float(value)
        assert metrics.get("fjt_records_out") == 1000, (
            f"scraped fjt_records_out={metrics.get('fjt_records_out')}"
            " != 1000"
        )
        assert metrics.get("fjt_dispatches", 0) >= 1
        inf_bucket = metrics.get('fjt_batch_latency_s_bucket{le="+Inf"}')
        assert inf_bucket is not None and inf_bucket >= 1, (
            "batch latency histogram missing from the scrape"
        )
        assert metrics.get("fjt_batch_latency_s_count") == inf_bucket, (
            "histogram _count != +Inf bucket — non-cumulative render"
        )
        # the attribution plane: per-stage histograms with samples
        stage_counts = {
            name: v for name, v in metrics.items()
            if name.startswith("fjt_stage_seconds_count")
        }
        assert stage_counts and any(v > 0 for v in stage_counts.values()), (
            f"no stage_seconds attribution in the scrape: {stage_counts}"
        )
        for stage in ("encode", "sink"):
            key = f'fjt_stage_seconds_count{{stage="{stage}"}}'
            assert metrics.get(key, 0) > 0, f"{key} missing/zero"
        # the sampled device profiler must have fired at least once
        # during a real pipeline run
        assert metrics.get("fjt_device_samples", 0) >= 1, (
            "device profiler never sampled"
        )
        assert metrics.get("fjt_device_ns_per_record", 0) > 0, (
            "live fjt_device_ns_per_record gauge missing/zero"
        )
        # ≥1 exemplar on the wire, resolvable to its flight event
        tids = re.findall(r'# \{trace_id="([^"]+)"\}', text)
        assert tids, "no Prometheus exemplars in the scrape"
        flight_tids = {
            e.get("trace_id") for e in flight.events()
            if e.get("kind") == "latency_exemplar"
        }
        assert set(tids) & flight_tids, (
            "scraped exemplar trace ids don't resolve to "
            "latency_exemplar flight events"
        )
        with urllib.request.urlopen(srv.url + "/healthz", timeout=10) as r:
            assert r.status == 200
    finally:
        srv.close()


def check_attribution_overhead() -> None:
    """Observability-overhead tripwire: the per-launch attribution work
    (stage ledger observes + the profiler's sampling predicate) must
    cost ≤2% of dispatch-loop throughput; the 'off' arm is the
    identical dispatcher with its ledger/profiler stripped (the
    pre-attribution hot path).

    Estimator: this runs on shared CI machines whose load bursts swing
    a short window's throughput several-fold, so ANY on-vs-off
    differential (medians, paired windows — both tried) flakes. The
    throughput delta equals per_launch_attr_cost / per_launch_time, so
    measure the two factors directly instead, each as ONE long
    continuous timing (bursts average out within a measurement and
    cancel between two back-to-back ones): the real attributed
    dispatch loop for the denominator, and a tight loop over exactly
    the ops a steady-state launch adds — one ``queue_wait``
    ledger-observe, the sampling predicate, and the per-launch
    ``dispatch_profile`` build — for the numerator."""
    import time

    import numpy as np

    from flink_jpmml_tpu.obs import attr, profiler
    from flink_jpmml_tpu.runtime.pipeline import OverlappedDispatcher
    from flink_jpmml_tpu.utils.metrics import MetricsRegistry

    a = np.random.default_rng(4).normal(size=(128, 128)).astype(np.float32)

    class _Leaf:
        __slots__ = ()

        def block_until_ready(self):
            pass

    _leaf = _Leaf()

    def dispatch():
        # ~1 ms of real numpy work per launch — the scale of a real
        # full-batch dispatch, so the per-launch attribution cost (a
        # few µs) is judged against a production-shaped denominator
        for _ in range(24):
            np.dot(a, a)
        return _leaf

    m_on = MetricsRegistry()
    prof = profiler.DeviceProfiler(m_on, interval_s=0.25)
    ledger = attr.ledger_for(m_on)
    prof_payload = {"records": 64, "flops_per_record": 1280.0,
                    "bytes_per_record": 6.0, "model": "smoke",
                    "backend": "fake"}

    disp = OverlappedDispatcher(depth=2, metrics=m_on, profiler=prof)
    assert disp._ledger is ledger
    for _ in range(20):  # warm allocator + code paths
        disp.launch(dispatch, profile=prof_payload)
    launches = 400
    t0 = time.perf_counter()
    for _ in range(launches):
        disp.launch(dispatch, profile=prof_payload)
    per_launch = (time.perf_counter() - t0) / launches
    disp.close()

    # a representative scorer stand-in so dispatch_profile walks its
    # real getattr/cache path (params shape scan caches on first call)
    class _FakeWire:
        fields = ["a", "b", "c", "d"]
        bytes_per_record = 8.0

    class _FakeScorer:
        params = {"split": np.zeros((10, 8, 8), dtype=np.float32)}
        wire = _FakeWire()
        backend = "fake"
        encode_mode = "host"
        model_hash = "smoke"

    fake_q = _FakeScorer()
    n = 50_000
    t0 = time.perf_counter()
    for _ in range(n):
        ledger.observe("queue_wait", 3e-4)
        prof.should_sample()
        # every real launch site builds this per launch too
        # (block.py / scorer.py pass it as profile=)
        attr.dispatch_profile(fake_q, 64)
    per_attr = (time.perf_counter() - t0) / n

    ratio = per_attr / per_launch
    assert ratio <= 0.02, (
        f"attribution overhead {100 * ratio:.2f}% > 2% "
        f"({per_attr * 1e6:.2f}µs attr ops vs "
        f"{per_launch * 1e6:.0f}µs per launch)"
    )
    # the on-arm must actually have attributed something, or the
    # comparison proves nothing
    snap = m_on.struct_snapshot()
    assert any(
        k.startswith("stage_seconds") for k in snap["histograms"]
    ), "on-arm recorded no stage attribution"
    assert snap["counters"].get("device_samples", 0) >= 1, (
        "on-arm profiler never sampled"
    )


def check_rollout_drill() -> None:
    """Rollout control-plane tripwire: the bench drill's engine at smoke
    scale — canary split ratio ±1% absolute, zero shadow sink leakage,
    zero disagreement on a byte-identical candidate. (The end-to-end
    guardrail promote/rollback drills live in tests/test_rollout.py;
    this guards the routing arithmetic every one of them rests on.)"""
    from flink_jpmml_tpu.bench import run_rollout_drill

    line = run_rollout_drill(records=4096, fraction=0.2, batch=256)
    assert line["ok"], line
    assert line["shadow_compared"] > 0, line


def check_freshness_burst_drill() -> None:
    """Burst-recovery tripwire: the ``--load-shape burst:2x`` drill at
    smoke scale, with the live mid-drain ``/metrics`` scrape asserted
    against the freshness plane's acceptance surface. The drill's own
    geometry (sink deadline-paced between base and burst rate) keeps it
    host-speed-independent; shrunk phases keep it seconds-cheap."""
    import re

    from flink_jpmml_tpu.bench import run_burst_drill

    line = run_burst_drill(
        base_rate=8_000.0,
        burst_factor=2.0,
        steady_s=1.5,
        burst_s=2.5,
        drain_timeout_s=15.0,
        scrape=True,
    )
    assert line["ok"], {k: line[k] for k in ("checks", "recovery_s",
                                             "peak_wm_lag_s",
                                             "peak_pressure")}
    checks = line["checks"]
    assert checks["recovered"] and checks["lag_built"], checks
    assert checks["pressure_peaked"] and checks["pressure_decayed"], checks
    assert checks["eta_finite_during_drain"], checks
    assert line["recovery_s"] is not None and line["recovery_s"] <= 15.0
    assert line["records_scored"] > 0

    # the live scrape captured mid-drain: the fleet dashboard's view of
    # the same drill must carry the freshness families with real values
    text = line["metrics_scrape"]
    assert text, "burst drill captured no /metrics page"
    samples = {}
    for ln in text.splitlines():
        if ln.startswith("#") or not ln.strip():
            continue
        name, value = ln.split(" # ", 1)[0].rsplit(" ", 1)
        samples[name] = float(value)
    inf = samples.get('fjt_record_staleness_s_bucket{le="+Inf"}')
    assert inf is not None and inf > 0, (
        "no record_staleness_s observations in the live scrape"
    )
    assert samples.get("fjt_record_staleness_s_count") == inf
    p = samples.get("fjt_pressure")
    assert p is not None and 0.0 <= p <= 1.0, f"fjt_pressure={p}"
    wm_keys = [
        k for k in samples
        if re.match(r'fjt_watermark_lag_s\{partition="[^"]+"\}', k)
    ]
    assert wm_keys, "no per-partition fjt_watermark_lag_s in the scrape"
    assert all(samples[k] >= 0 for k in wm_keys)
    assert "fjt_lag_drain_eta_s" in samples
    assert samples.get("fjt_watermark_ts", 0) > 1e9  # a real event time

    # the artifact's embedded varz struct carries the same families
    # (the bench-artifact contract fjt-top --freshness renders)
    varz = line["varz"]
    assert varz["histograms"]["record_staleness_s"]["n"] > 0
    assert "pressure" in varz["gauges"]
    assert 'watermark_lag_s{partition="0"}' in varz["gauges"]


def check_overload_drill() -> None:
    """Overload tripwire: the ``--overload-drill`` engine at smoke
    scale. Asserts the three ROADMAP item 5 acceptance properties —
    deadline met at 80% capacity, bounded-p99 + explicit shed at 150%,
    clean recovery — against THIS host's measured capacity (the drill
    self-calibrates, so it is as meaningful on a CI CPU as on a TPU)."""
    from flink_jpmml_tpu.bench import run_overload_drill

    line = run_overload_drill(phase_s=2.0, surge_s=2.5,
                              drain_timeout_s=10.0)
    assert line["ok"], line["checks"]
    assert all(line["checks"].values()), line["checks"]
    assert line["shed_records"] > 0, line["shed_records"]
    assert line["p99_base_ms"] <= line["deadline_ms"], (
        line["p99_base_ms"], line["deadline_ms"],
    )
    # recovery is the drill's own check (1.05x with a small absolute
    # floor for sub-ms baselines); don't re-derive a stricter one here
    # the artifact's struct carries the overload families the
    # fjt-top --overload panel renders
    varz = line["varz"]
    assert "shed_level" in varz["gauges"]
    assert 'shed_records{lane="block"}' in varz["counters"]
    assert varz["counters"]["admitted_records"] > 0


def check_drift_plane() -> None:
    """Data-drift-plane tripwire: (1) the bench drill at smoke scale —
    right feature alarms, control stays quiet, fleet merge exact; (2) a
    baselined production BlockPipeline whose live /metrics scrape
    carries real drift telemetry; (3) the dispatch-path overhead bound:
    the unsampled per-dispatch gate vs a production-shaped ~1 ms launch
    (the attribution-tripwire estimator), and the sampled path held
    ≤2% of wall clock by the plane's accumulated-overhead budget."""
    import time
    import urllib.request

    import numpy as np

    from assets.generate import gen_gbm
    from flink_jpmml_tpu.bench import run_drift_drill
    from flink_jpmml_tpu.compile import compile_pmml
    from flink_jpmml_tpu.obs import drift
    from flink_jpmml_tpu.obs.server import ObsServer
    from flink_jpmml_tpu.pmml import parse_pmml_file
    from flink_jpmml_tpu.runtime.block import BlockPipeline, FiniteBlockSource
    from flink_jpmml_tpu.utils.metrics import MetricsRegistry

    # 1) the drill engine at smoke scale
    line = run_drift_drill(records_per_phase=4096, batch=256)
    assert line["ok"] and line["merge_exact"], line
    model = line["model"]
    assert line["perturbed_feature"] in (
        line["drift"][model]["alarmed_features"]
    ), line["drift"]
    assert line["psi_control"] < 0.25, line["psi_control"]
    # the drill's artifact carries the drift varz family
    assert line["varz"]["sketches"], "drill varz carries no sketches"

    # 2) live pipeline scrape: baseline → shifted stream → /metrics
    with tempfile.TemporaryDirectory() as tmp:
        doc = parse_pmml_file(
            gen_gbm(tmp, n_trees=10, depth=3, n_features=4)
        )
        cm = compile_pmml(doc, batch_size=64)
        rng = np.random.default_rng(5)
        base = rng.normal(0.0, 1.0, size=(1000, 4)).astype(np.float32)
        shifted = base.copy()
        shifted[:, 1] += 4.0
        metrics = MetricsRegistry()
        store = drift.BaselineStore(os.path.join(tmp, "bl"))
        plane = drift.install(
            metrics, interval_s=0.0, budget_frac=0, store=store
        )
        mon = plane.monitor
        mon.min_n = 200
        mon.dwell_s = 0.0
        mon._interval = 0.0

        def sink(out, n, first_off):
            np.asarray(out if not hasattr(out, "value") else out.value)

        def run_stream(data):
            pipe = BlockPipeline(
                FiniteBlockSource(data, block_size=100), cm, sink,
                in_flight=2, use_native=False, metrics=metrics,
            )
            pipe.run_until_exhausted(timeout=60.0)

        run_stream(base)
        saved = drift.snapshot_registry(metrics, store=store)
        assert saved, "pipeline recorded no drift profiles to baseline"
        run_stream(shifted)
        srv = ObsServer.for_registry(metrics)
        try:
            with urllib.request.urlopen(
                srv.url + "/metrics", timeout=10
            ) as r:
                assert r.status == 200
                text = r.read().decode()
        finally:
            srv.close()
        samples = {}
        for ln in text.splitlines():
            if ln.startswith("#") or not ln.strip():
                continue
            name, value = ln.split(" # ", 1)[0].rsplit(" ", 1)
            samples[name] = float(value)
        score_keys = [
            k for k in samples if k.startswith("fjt_drift_score{")
        ]
        assert score_keys, "no fjt_drift_score gauges in the live scrape"
        assert any(samples[k] > 0 for k in score_keys), (
            "every scraped fjt_drift_score is zero after a 4-sigma "
            f"shift: { {k: samples[k] for k in score_keys} }"
        )
        rec_keys = [
            k for k in samples
            if k.startswith("fjt_drift_feature_records{")
        ]
        assert rec_keys and all(samples[k] > 0 for k in rec_keys), (
            "feature-profile counters missing from the dispatch path"
        )
        assert any(
            k.startswith("fjt_feature_missing_rate{") for k in samples
        ), "no missing-rate gauges in the scrape"

        # 3) overhead bound on the dispatch path
        q = cm.quantized_scorer()
        assert q is not None
        X = base[:256]
        a = rng.normal(size=(128, 128)).astype(np.float32)
        launches = 200
        t0 = time.perf_counter()
        for _ in range(launches):
            for _ in range(24):  # ~1 ms of real work per launch
                np.dot(a, a)
        per_launch = (time.perf_counter() - t0) / launches
        # (a) the steady-state per-dispatch cost is the unsampled gate
        m2 = MetricsRegistry()
        gate_plane = drift.install(m2, interval_s=3600.0)
        gate_plane.record_features(q, X)  # the one sample; rest gate
        n = 50_000
        t0 = time.perf_counter()
        for _ in range(n):
            gate_plane.record_features(q, X)
        per_gate = (time.perf_counter() - t0) / n
        ratio = per_gate / per_launch
        assert ratio <= 0.02, (
            f"drift gate costs {100 * ratio:.2f}% of a launch "
            f"({per_gate * 1e6:.2f}µs vs {per_launch * 1e6:.0f}µs)"
        )
        # (b) the sampled path: an interval-0 plane hammered for half a
        # second must stay within its 2% accumulated-overhead budget.
        # Best-of-3: the 0.5s window is short enough that one scheduler
        # hiccup inside a sampled pass can inflate the fraction past
        # the slack on a loaded box — the contract is that the budget
        # is HOLDABLE, so any quiet window satisfies it
        frac = None
        for _ in range(3):
            m3 = MetricsRegistry()
            busy_plane = drift.install(
                m3, interval_s=0.0, budget_frac=0.02
            )
            t0 = time.perf_counter()
            while time.perf_counter() - t0 < 0.5:
                busy_plane.record_features(q, X)
            assert busy_plane.stats()["sampled"] >= 2, busy_plane.stats()
            attempt = busy_plane.overhead_fraction()
            frac = attempt if frac is None else min(frac, attempt)
            if frac <= 0.03:
                break
        assert frac <= 0.03, (
            f"sampled drift profiling consumed {100 * frac:.1f}% of "
            "wall clock — the overhead budget is not holding"
        )


def check_journey_trace() -> None:
    """Record-journey-tracing tripwire (obs/trace.py): (1) the
    unarmed hot-path gate — ``store_for`` with ``FJT_JOURNEY_DIR``
    unset — must cost ≤2µs per dispatch (a dict miss + one env
    lookup); (2) armed, the accumulated-overhead budget must hold (a
    zero-budget store drops every non-terminal hop); (3) a live
    pipeline's ``/trace`` scrape must retrieve ≥1 COMPLETE journey
    (dispatch + sink hops) whose sink hop's trace id matches a
    ``latency_exemplar`` flight event's — the fjt-top → fjt-trace
    pivot's ground truth."""
    import json
    import time
    import urllib.request

    import numpy as np

    from assets.generate import gen_gbm
    from flink_jpmml_tpu.compile import compile_pmml
    from flink_jpmml_tpu.obs import recorder as flight
    from flink_jpmml_tpu.obs import trace as trace_mod
    from flink_jpmml_tpu.obs.server import ObsServer
    from flink_jpmml_tpu.pmml import parse_pmml_file
    from flink_jpmml_tpu.runtime.block import BlockPipeline, FiniteBlockSource
    from flink_jpmml_tpu.utils.metrics import MetricsRegistry

    # 1) the unsampled gate: env unset, nothing armed
    assert not os.environ.get("FJT_JOURNEY_DIR"), (
        "FJT_JOURNEY_DIR leaked into the smoke env"
    )
    m_gate = MetricsRegistry()
    assert trace_mod.store_for(m_gate) is None
    # best of five: a shared host's scheduling doubles a single loop's
    # reading; the gate's own cost is the floor
    n = 40_000
    per_call = float("inf")
    for _ in range(5):
        t0 = time.perf_counter()
        for _ in range(n):
            trace_mod.store_for(m_gate)
        per_call = min(per_call, (time.perf_counter() - t0) / n)
    assert per_call <= 2e-6, (
        f"unarmed journey gate costs {per_call * 1e6:.2f}µs/dispatch > 2µs"
    )

    with tempfile.TemporaryDirectory() as tmp:
        # 2) the budget: a zero-budget store must shed its own work
        m_budget = MetricsRegistry()
        store = trace_mod.install(
            m_budget, os.path.join(tmp, "b"), budget_frac=0.0, head_n=0
        )
        for i in range(2000):
            ctx = trace_mod.context_for(i * 64)
            store.hop("dispatch", ctx, i * 64, 64)
            store.finish(ctx, i * 64, 64, latency_s=0.001)
        snap = m_budget.struct_snapshot()["counters"]
        dropped = sum(
            v for k, v in snap.items() if k.startswith("journeys_dropped")
        )
        assert dropped > 0 and snap.get("journeys_sampled", 0) == 0, (
            f"zero-budget store persisted work: {snap}"
        )

        # 3) live pipeline + /trace scrape + exemplar linkage
        doc = parse_pmml_file(
            gen_gbm(tmp, n_trees=10, depth=3, n_features=4)
        )
        cm = compile_pmml(doc, batch_size=64)
        rng = np.random.default_rng(7)
        data = rng.normal(0.0, 1.0, size=(1000, 4)).astype(np.float32)
        metrics = MetricsRegistry()
        trace_mod.install(metrics, os.path.join(tmp, "journeys"))

        def sink(out, n_, first_off):
            np.asarray(out if not hasattr(out, "value") else out.value)

        pipe = BlockPipeline(
            FiniteBlockSource(data, block_size=100), cm, sink,
            in_flight=2, use_native=False, metrics=metrics,
        )
        srv = ObsServer.for_registry(metrics)
        try:
            pipe.run_until_exhausted(timeout=60.0)
            with urllib.request.urlopen(
                srv.url + "/trace", timeout=10
            ) as r:
                assert r.status == 200
                payload = json.loads(r.read().decode())
        finally:
            srv.close()
        rows = payload["journeys"]
        assert rows, "live /trace scrape returned no journey rows"
        by_id = {}
        for row in rows:
            by_id.setdefault(row.get("trace_id"), set()).add(row["kind"])
        complete = {
            tid for tid, kinds in by_id.items()
            if {"dispatch", "sink"} <= kinds
        }
        assert complete, f"no complete journeys in the scrape: {by_id}"
        exemplar_tids = {
            e.get("trace_id") for e in flight.events()
            if e.get("kind") == "latency_exemplar"
        }
        assert complete & exemplar_tids, (
            "no scraped journey's sink hop matches a latency_exemplar "
            f"trace id (journeys {sorted(complete)[:4]}, exemplars "
            f"{sorted(t for t in exemplar_tids if t)[:4]})"
        )
        snap = metrics.struct_snapshot()["counters"]
        assert snap.get("journeys_sampled", 0) >= 1, snap


def check_recovery_drill() -> None:
    """Delivery-correctness tripwire: the ``--recovery-drill`` engine
    at smoke scale — one parent SIGKILL + poison records + decode
    poison against a supervised Kafka pipeline. Asserts the kill →
    restart → invariants chain: zero loss, bounded duplication,
    parseable checkpoints, poison offsets exactly in the DLQ, and the
    ``fjt-dlq redrive`` round-trip. The crash-loop (hard-poison)
    convergence needs ~log2(batch) restarts, so it runs only in the
    full ``bench.py --recovery-drill``, not here."""
    from flink_jpmml_tpu.bench import run_recovery_drill

    line = run_recovery_drill(
        records=4_000, kills=1, poison=1, hard_poison=False,
        decode_poison_n=1, timeout_s=120.0, max_restarts=20,
        throttle_ms=25.0, kill_dwell=(0.05, 0.25),
    )
    assert line["ok"], line
    assert line["parent_kills"] >= 1, line
    assert line["restarts"] >= 1, line
    assert line["redrive_ok"], line
    assert len(line["quarantined"]) == 2, line  # 1 score + 1 decode
    assert line["max_dup"] <= line["restarts"] + 1, line


def check_device_fault() -> None:
    """Device-fault resilience tripwire (runtime/devfault.py +
    serving/failover.py): unarmed hook-site overhead ≤2µs; then a
    smoke-scale outage — a persistent injected ``device_error`` streak
    trips the circuit onto the host fallback tier while a live
    ``/metrics`` scrape observes it (``fjt_failover_state`` open,
    non-zero ``fjt_fallback_records``), the breaker re-closes on green
    probes, redispatch lands records, and the paced stream drains with
    zero loss and in-order sinks."""
    import re
    import time
    import urllib.request

    import numpy as np

    from assets.generate import gen_gbm
    from flink_jpmml_tpu.compile import compile_pmml
    from flink_jpmml_tpu.obs.server import ObsServer
    from flink_jpmml_tpu.pmml import parse_pmml_file
    from flink_jpmml_tpu.runtime import faults
    from flink_jpmml_tpu.runtime.block import BlockPipeline, BlockSource

    # -- unarmed overhead: the new device hook sites ride the same
    #    no-op contract as every other fault site
    assert not faults.active(), "faults armed — no-op check invalid"
    n = 200_000
    t0 = time.perf_counter()
    for _ in range(n):
        faults.fire("device_readback")
        faults.fire("device_dispatch")
    per_call = (time.perf_counter() - t0) / (2 * n)
    assert per_call <= 2e-6, (
        f"inactive device fault hook costs {per_call * 1e6:.2f}µs/call"
    )

    class PacedSource(BlockSource):
        """One block per interval: on a CPU host the fallback tier
        runs at device speed, and an instantly-available stream would
        drain inside one open-circuit window — pacing leaves traffic
        for the half-open probes that must re-close the breaker."""

        def __init__(self, data, block, interval_s):
            self._data = data
            self._block = block
            self._interval = interval_s
            self._pos = 0
            self._next_t = 0.0

        def poll(self):
            if self._pos >= self._data.shape[0]:
                return None
            now = time.monotonic()
            if now < self._next_t:
                return None
            self._next_t = now + self._interval
            blk = self._data[self._pos: self._pos + self._block]
            off = self._pos
            self._pos += blk.shape[0]
            return off, blk

        @property
        def exhausted(self):
            return self._pos >= self._data.shape[0]

    with tempfile.TemporaryDirectory() as tmp:
        doc = parse_pmml_file(
            gen_gbm(tmp, n_trees=8, depth=3, n_features=5)
        )
    cm = compile_pmml(doc, batch_size=64)
    rng = np.random.default_rng(17)
    N = 12_288
    data = rng.normal(0.0, 1.0, size=(N, 5)).astype(np.float32)
    emitted = []

    def sink(out, n_rec, first_off):
        emitted.append((first_off, n_rec))

    env_saved = {
        k: os.environ.get(k)
        for k in ("FJT_FAILOVER", "FJT_FAILOVER_COOLDOWN_S",
                  "FJT_FAILOVER_GREENS", "FJT_RETRY_BASE_S")
    }
    os.environ["FJT_FAILOVER"] = "1"  # arm without a DLQ: env opt-in
    os.environ["FJT_FAILOVER_COOLDOWN_S"] = "0.2"
    os.environ["FJT_FAILOVER_GREENS"] = "1"
    os.environ["FJT_RETRY_BASE_S"] = "0.005"
    srv = None
    pipe = None
    try:
        # 7 fires: batch 1 (1 + 2 retries) opens the circuit; probe 1
        # burns 3 more and re-opens; probe 2's initial readback burns
        # the last, its first RETRY succeeds (redispatch_records), and
        # the next green completion closes the circuit
        faults.inject("device_error", site="device_readback", n=7)
        pipe = BlockPipeline(
            PacedSource(data, 64, 0.004), cm, sink,
            in_flight=2, use_native=False, max_dispatch_chunks=1,
        )
        srv = ObsServer.for_registry(pipe.metrics)
        pipe.start()
        saw_open = False
        saw_fallback = 0.0
        deadline = time.monotonic() + 60.0
        while time.monotonic() < deadline:
            if pipe._error is not None:
                raise pipe._error
            try:
                with urllib.request.urlopen(
                    srv.url + "/metrics", timeout=5
                ) as r:
                    page = r.read().decode()
            except OSError:
                page = ""
            m = re.search(
                r'fjt_failover_state\{model="static"\} ([0-9.]+)', page
            )
            if m and float(m.group(1)) >= 2.0:
                saw_open = True
                fb = re.search(r"fjt_fallback_records ([0-9.e+]+)", page)
                if fb:
                    saw_fallback = max(saw_fallback, float(fb.group(1)))
            if pipe._source.exhausted and not len(pipe._ring):
                break
            time.sleep(0.02)
        pipe._drain_all = True
        pipe.stop()
        pipe.join(timeout=30.0)
        assert saw_open, (
            "live scrape never observed fjt_failover_state open"
        )
        assert saw_fallback > 0, (
            "live scrape never observed non-zero fjt_fallback_records "
            "during the outage"
        )
        snap = pipe.metrics.struct_snapshot()
        g = snap.get("gauges", {})
        state = g.get('failover_state{model="static"}', {}).get("value")
        assert state == 0.0, (
            f"circuit did not re-close (failover_state {state})"
        )
        c = snap.get("counters", {})
        assert c.get("fallback_records", 0) > 0
        assert c.get("redispatch_records", 0) > 0, (
            "no redispatched records — the transient ladder never won"
        )
        assert c.get('device_fault_total{kind="device_error"}', 0) >= 7
        covered = np.zeros(N, np.int64)
        for off, n_rec in emitted:
            covered[off: off + n_rec] += 1
        assert (covered == 1).all(), (
            f"loss/dup under device faults: "
            f"lost={int((covered == 0).sum())} "
            f"dup={int((covered > 1).sum())}"
        )
        offs = [o for o, _ in emitted]
        assert offs == sorted(offs), "sink order violated under faults"
    finally:
        faults.clear()
        if pipe is not None:
            try:
                pipe.stop()
                pipe.join(timeout=10.0)
            except Exception:
                pass
        if srv is not None:
            srv.close()
        for k, v in env_saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


def check_fault_hooks_noop() -> None:
    """Fault harness zero-overhead contract: with FJT_FAULTS unset,
    fire() must be a global load + None check (≤ 2 µs even on a loaded
    CI machine — measured ~0.3 µs), and injection must be fully
    reversible (clear() restores the no-op path)."""
    import time

    from flink_jpmml_tpu.runtime import faults

    assert not faults.active(), (
        "faults installed with FJT_FAULTS unset — the no-op path is "
        "not the default"
    )
    n = 200_000
    t0 = time.perf_counter()
    for _ in range(n):
        faults.fire("kafka_fetch")
    per_call = (time.perf_counter() - t0) / n
    assert per_call <= 2e-6, (
        f"inactive fault hook costs {per_call * 1e6:.2f}µs/call > 2µs"
    )
    # injection engages the real paths... and clear() fully disarms
    f = faults.inject("slow_fetch", delay_ms=1, n=1)
    faults.fire("kafka_fetch")
    assert f.fires == 1 and faults.stats() == {"slow_fetch": 1}
    faults.clear()
    assert not faults.active()
    faults.fire("kafka_fetch")  # no plan: must be inert again
    assert f.fires == 1


def check_mesh_gate_noop() -> None:
    """Single-chip mesh-gate zero-overhead contract (PR 16): with no
    mesh configured, the multichip promotion adds exactly two
    operations to the dispatch hot path — a getattr-with-default on
    ``batch_divisor`` (the pad-target rounding in ``_score_f32``) and
    a ``_mesh_obs is None`` test in the completion path. Both together
    must cost ≤ 2 µs/dispatch (measured ~0.2 µs), and the telemetry /
    window plumbing must stay fully disengaged for single-chip
    models."""
    import time

    from flink_jpmml_tpu.obs import mesh as mesh_obs
    from flink_jpmml_tpu.parallel.assignment import mesh_in_flight
    from flink_jpmml_tpu.utils.metrics import MetricsRegistry

    class _SingleChipModel:  # a CompiledModel has no mesh attrs
        batch_size = 512

    model = _SingleChipModel()
    obs = None
    n = 200_000
    t0 = time.perf_counter()
    for _ in range(n):
        target = 512
        target += (-target) % getattr(model, "batch_divisor", 1)
        if obs is not None:
            raise AssertionError("unreachable")
    per_call = (time.perf_counter() - t0) / n
    assert per_call <= 2e-6, (
        f"single-chip mesh gate costs {per_call * 1e6:.2f}µs/dispatch "
        "> 2µs"
    )
    # disengagement: no telemetry for single-chip models, and the
    # mesh-aware window leaves the single-chip depth untouched
    assert mesh_obs.telemetry_for(MetricsRegistry(), model) is None
    assert mesh_in_flight(None, 2) == 2


def check_zoo_pack() -> None:
    """Multi-tenant packed-scoring tripwire: byte parity packed-vs-solo
    (zero cross-tenant leakage), LRU eviction + warm-pool re-admit
    identity under a 1-byte FJT_ZOO_BYTES cap, and a lenient
    pack-vs-solo wall-clock ratio. (The 1,000-model acceptance capture
    is ``bench.py --zoo``; this guards the pack path's correctness on
    every smoke run.)"""
    import time

    import numpy as np

    from flink_jpmml_tpu.assets_gen import gen_gbm
    from flink_jpmml_tpu.models.control import AddMessage
    from flink_jpmml_tpu.models.core import ModelId
    from flink_jpmml_tpu.runtime.sources import ControlSource
    from flink_jpmml_tpu.serving.scorer import DynamicScorer

    tmp = tempfile.mkdtemp(prefix="fjt-smoke-zoo-")
    tenants, features, rows = 6, 4, 64
    docs = [
        gen_gbm(tmp, n_trees=4 + i, depth=3, n_features=features,
                seed=50 + i, name=f"z{i}")
        for i in range(tenants)
    ]
    fields = [f"f{j}" for j in range(features)]
    rng = np.random.default_rng(5)
    data = rng.normal(0.0, 1.0, size=(
        tenants * rows * 8, features)).astype(np.float32)
    data[rng.random(size=data.shape) < 0.02] = np.nan  # missing lanes

    def build(zoo):
        ctrl = ControlSource()
        sc = DynamicScorer(control=ctrl, batch_size=256,
                           auto_rollout=False, zoo=zoo)
        for i in range(tenants):
            ctrl.push(AddMessage(f"z{i}", 1, docs[i],
                                 timestamp=time.time()))
        sc._drain_control()
        deadline = time.monotonic() + 120.0
        for i in range(tenants):
            mid = ModelId(f"z{i}", 1)
            while sc.registry.model_if_warm(mid) is None:
                assert sc.registry.warm_error(mid) is None, mid.key()
                assert time.monotonic() < deadline, (
                    f"{mid.key()} never warmed"
                )
                time.sleep(0.01)
        return sc

    def batch(round_i):
        ev = []
        for i in range(tenants):
            base = (round_i * tenants + i) * rows
            for j in range(rows):
                rec = dict(zip(
                    fields, data[(base + j) % len(data)].tolist()
                ))
                rec["_key"] = f"k{base + j}"
                ev.append((f"z{i}", rec))
        return ev

    def run(sc, rounds):
        out = []
        for r in rounds:
            for p, _ in sc.finish(sc.submit(batch(r))):
                out.append(None if p.is_empty else p.score.value)
        return out

    sc_solo = build(None)

    # tight caps: width-2 packs, a byte cap that can hold exactly one —
    # every group admit evicts the previous pack, round 2 re-admits
    # from the warm pool; parity across both rounds pins the
    # eviction/re-admit identity
    env_keys = ("FJT_PACK_MAX", "FJT_ZOO_BYTES", "FJT_AUTOTUNE_DISABLE")
    saved = {k: os.environ.get(k) for k in env_keys}
    os.environ.update({"FJT_PACK_MAX": "2", "FJT_ZOO_BYTES": "1",
                       "FJT_AUTOTUNE_DISABLE": "1"})
    try:
        sc_zoo = build(True)
        want = run(sc_solo, [0, 1])
        got = run(sc_zoo, [0, 1])
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
    assert got == want, (
        "packed-vs-solo parity broke (cross-tenant leakage or "
        "reduction-order drift)"
    )
    c = sc_zoo.metrics.struct_snapshot()["counters"]
    assert c.get("pack_dispatches", 0) > 0, "zoo never packed a dispatch"
    assert c.get("zoo_evictions", 0) > 0, (
        "1-byte FJT_ZOO_BYTES cap never evicted a pack"
    )
    assert c.get("warm_pool_hits", 0) > 0, (
        "round 2 rebuilt its packs instead of re-admitting from the "
        "warm pool"
    )

    # lenient wall-clock tripwire under default caps (one wide pack,
    # no thrash): packed dispatch must not be pathologically slower
    # than solo — the real >=75% throughput gate lives in bench --zoo
    sc_fast = build(True)
    run(sc_fast, [0])  # plan + pack compile outside timing
    t0 = time.perf_counter()
    run(sc_fast, [1, 2, 3])
    dt_zoo = time.perf_counter() - t0
    t0 = time.perf_counter()
    run(sc_solo, [1, 2, 3])
    dt_solo = time.perf_counter() - t0
    assert dt_zoo <= 3.0 * dt_solo + 0.25, (
        f"packed path took {dt_zoo:.3f}s vs solo {dt_solo:.3f}s "
        "(> 3x tripwire)"
    )
    shutil.rmtree(tmp, ignore_errors=True)


def check_history() -> None:
    """Telemetry-history tripwire (obs/history.py): the unarmed
    ``history_for`` gate costs ≤2µs/call (the journey-store contract);
    an armed recorder keeps its accumulated bookkeeping under the 2%
    budget while capturing for real; and a live pipeline's ``/history``
    frames RECONCILE over HTTP — the summed counter deltas equal the
    registry's cumulative totals exactly."""
    import json
    import time
    import urllib.request

    import numpy as np

    from assets.generate import gen_gbm
    from flink_jpmml_tpu.compile import compile_pmml
    from flink_jpmml_tpu.obs import history
    from flink_jpmml_tpu.obs.server import ObsServer
    from flink_jpmml_tpu.pmml import parse_pmml_file
    from flink_jpmml_tpu.runtime.block import (
        BlockPipeline, FiniteBlockSource,
    )
    from flink_jpmml_tpu.utils.metrics import MetricsRegistry

    env_saved = {
        k: os.environ.get(k)
        for k in ("FJT_HISTORY_DIR", "FJT_HISTORY_RES",
                  "FJT_HISTORY_INTERVAL_S", "FJT_METRICS_MAX_SERIES")
    }
    for k in env_saved:
        os.environ.pop(k, None)
    srv = None
    try:
        # -- unarmed: a dict miss + one env lookup, nothing records
        m_idle = MetricsRegistry()
        n = 200_000
        t0 = time.perf_counter()
        for _ in range(n):
            history.history_for(m_idle)
        per_call = (time.perf_counter() - t0) / n
        assert per_call <= 2e-6, (
            f"unarmed history gate costs {per_call * 1e6:.2f}µs/call"
        )

        # -- armed: real captures against the accumulated-overhead
        #    budget, paced at the production default cadence
        with tempfile.TemporaryDirectory() as tmp:
            m_armed = MetricsRegistry()
            c = m_armed.counter("records_out")
            g = m_armed.gauge("pressure")
            rec = history.HistoryRecorder(
                m_armed, tmp, src="smoke", interval_s=0.05,
                resolutions=(0.05, 1.0), start_thread=False,
            )
            t_end = time.monotonic() + 1.0
            while time.monotonic() < t_end:
                c.inc(100)
                g.set(0.5)
                rec.maybe_capture()
                time.sleep(0.005)
            frac = rec.overhead_fraction()
            rec.close()
            assert frac <= 0.02, (
                f"armed history overhead {100 * frac:.2f}% > 2% budget"
            )

        # -- live scrape: /history frames reconcile with the registry's
        #    cumulative counters across a real pipeline run
        with tempfile.TemporaryDirectory() as tmp:
            doc = parse_pmml_file(
                gen_gbm(tmp, n_trees=10, depth=3, n_features=4)
            )
        cm = compile_pmml(doc, batch_size=64)
        rng = np.random.default_rng(5)
        data = rng.normal(0.0, 1.0, size=(1000, 4)).astype(np.float32)

        def sink(out, n_rec, first_off):
            np.asarray(out if not hasattr(out, "value") else out.value)

        hdir = tempfile.mkdtemp(prefix="fjt-smoke-history-")
        try:
            pipe = BlockPipeline(
                FiniteBlockSource(data, block_size=100), cm, sink,
                in_flight=2, use_native=False,
            )
            rec = history.install(
                pipe.metrics, directory=hdir, src="smoke",
                interval_s=0.05, start_thread=False,
            )
            # the baseline capture happens BEFORE any traffic, so the
            # frame deltas cover the whole run
            rec.maybe_capture()
            srv = ObsServer.for_registry(pipe.metrics)
            pipe.run_until_exhausted(timeout=60.0)
            time.sleep(0.06)  # past the interval gate
            rec.maybe_capture()
            rec.flush()
            with urllib.request.urlopen(
                srv.url + "/history?source=smoke", timeout=10
            ) as r:
                assert r.status == 200
                payload = json.loads(r.read().decode())
            frames = payload.get("frames") or []
            assert frames, "live /history served no frames"
            total = 0.0
            for f in frames:
                v = (f.get("counters") or {}).get("records_out")
                if v is not None:
                    total += history.wire_float(v)
            cum = pipe.metrics.struct_snapshot()["counters"][
                "records_out"
            ]
            assert total == cum == 1000, (
                f"/history deltas ({total}) don't reconcile with the "
                f"registry cumulative ({cum})"
            )
        finally:
            shutil.rmtree(hdir, ignore_errors=True)
    finally:
        if srv is not None:
            srv.close()
        for k, v in env_saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


def check_stateful() -> None:
    """Keyed-state tripwire (runtime/state.py + compile/statekernel.py):
    the unarmed per-dispatch additions (the ``state is None`` branch +
    ``split_output`` on a stateless output) must stay ≤2µs; an armed
    dispatch — host slot routing + the fused gather/scatter state
    stage — must stay within a small constant factor of the stateless
    dispatch at smoke scale (a per-record host loop would be 100×); a
    mid-run snapshot restored into a fresh table and replayed from
    offset 0 must converge to the single-life table BYTE-exactly (the
    exactly-once replay guard); and a live stateful pipeline's
    ``/metrics`` scrape must show non-zero ``fjt_state_resident_keys``."""
    import time
    import urllib.request

    import numpy as np

    from assets.generate import gen_gbm
    from flink_jpmml_tpu.compile import compile_pmml
    from flink_jpmml_tpu.obs.server import ObsServer
    from flink_jpmml_tpu.pmml import parse_pmml_file
    from flink_jpmml_tpu.runtime import state as state_mod
    from flink_jpmml_tpu.runtime.block import (
        BlockPipeline, FiniteBlockSource,
    )
    from flink_jpmml_tpu.runtime.pipeline import dispatch_quantized

    import jax

    # -- unarmed gate: the stateless hot path's only new per-dispatch
    #    work is `state is None` branches plus split_output on the raw
    #    output object
    out_stateless = np.zeros(64, np.float32)
    n = 200_000
    t0 = time.perf_counter()
    for _ in range(n):
        state_mod.split_output(out_stateless)
    per_call = (time.perf_counter() - t0) / n
    assert per_call <= 2e-6, (
        f"unarmed state gate costs {per_call * 1e6:.2f}µs/dispatch"
    )

    with tempfile.TemporaryDirectory() as tmp:
        doc = parse_pmml_file(
            gen_gbm(tmp, n_trees=10, depth=3, n_features=4)
        )
    cm = compile_pmml(doc, batch_size=256)
    q = cm.quantized_scorer()
    B, rounds = 256, 40
    rng = np.random.default_rng(11)
    X = rng.normal(0.0, 1.0, size=(rounds * B, 4)).astype(np.float32)
    X[:, 0] = rng.integers(0, 5000, size=rounds * B).astype(np.float32)

    def run(table):
        last = None
        t_run0 = time.perf_counter()
        for i in range(rounds):
            xb = X[i * B:(i + 1) * B]
            if table is None:
                last = dispatch_quantized(q, xb)
            else:
                last = dispatch_quantized(
                    q, xb, state=table,
                    offsets=np.arange(i * B, (i + 1) * B),
                )
        jax.block_until_ready(last)
        return time.perf_counter() - t_run0

    spec = state_mod.StateSpec(capacity=8192, key_col=0)
    # warm both entries (compiles are not the overhead under test)
    run(None)
    run(state_mod.KeyedStateTable(spec))
    t_plain = run(None)
    t_armed = run(state_mod.KeyedStateTable(spec))
    assert t_armed <= 5.0 * t_plain + 0.25, (
        f"armed state overhead unbounded: {t_armed:.3f}s armed vs "
        f"{t_plain:.3f}s stateless over {rounds} dispatches"
    )

    # -- kill→restore parity at smoke scale: snapshot mid-run (the
    #    checkpoint a killed incarnation leaves), restore into a fresh
    #    table, replay the WHOLE stream from offset 0 — the replayed
    #    prefix bypasses (exactly-once), the suffix re-applies, and the
    #    final buffer equals the single-life table bitwise
    ref = state_mod.KeyedStateTable(spec)
    payload = None
    for i in range(rounds):
        out = dispatch_quantized(
            q, X[i * B:(i + 1) * B], state=ref,
            offsets=np.arange(i * B, (i + 1) * B),
        )
        if i == rounds // 2 - 1:
            jax.block_until_ready(ref.values)
            payload = ref.to_payload()
    jax.block_until_ready(out)
    ref_vals = np.asarray(ref.values).copy()
    rep = state_mod.KeyedStateTable(spec)
    assert rep.from_payload(payload), "state payload restore failed"
    assert rep.skip_until == (rounds // 2) * B
    for i in range(rounds):
        out = dispatch_quantized(
            q, X[i * B:(i + 1) * B], state=rep,
            offsets=np.arange(i * B, (i + 1) * B),
        )
    jax.block_until_ready(out)
    assert np.array_equal(ref_vals, np.asarray(rep.values)), (
        "kill→restore replay diverged from the single-life state table"
    )

    # -- live scrape: a stateful pipeline's /metrics shows the family
    srv = None
    try:
        data = rng.normal(0.0, 1.0, size=(2048, 4)).astype(np.float32)
        data[:, 0] = rng.integers(0, 500, size=2048).astype(np.float32)
        seen = []

        def sink(out, n_rec, first_off):
            seen.append(n_rec)

        pipe = BlockPipeline(
            FiniteBlockSource(data, block_size=256), cm, sink,
            in_flight=2, use_native=False,
            state=state_mod.StateSpec(capacity=4096, key_col=0),
        )
        srv = ObsServer.for_registry(pipe.metrics)
        pipe.run_until_exhausted(timeout=60.0)
        assert sum(seen) == 2048, f"stateful pipeline lost records: {seen}"
        with urllib.request.urlopen(srv.url + "/metrics", timeout=10) as r:
            assert r.status == 200
            text = r.read().decode()
        resident = None
        for line in text.splitlines():
            if line.startswith("fjt_state_resident_keys"):
                resident = float(line.split()[-1])
        assert resident is not None and resident > 0, (
            f"/metrics shows no live state_resident_keys: {resident}"
        )
    finally:
        if srv is not None:
            srv.close()


def main() -> int:
    timer = threading.Timer(WATCHDOG_S, _watchdog)
    timer.daemon = True
    timer.start()
    check_dispatcher_ordering()
    print("perf-smoke: dispatcher ordering OK", flush=True)
    check_block_pipeline()
    print("perf-smoke: block pipeline drain/ordering OK", flush=True)
    check_kafka_pipeline()
    print("perf-smoke: kafka pipeline OK", flush=True)
    check_fused_pipeline_parity()
    print("perf-smoke: fused encode parity OK", flush=True)
    check_autotune_cache_roundtrip()
    print("perf-smoke: autotune cache roundtrip OK", flush=True)
    check_kernel_search()
    print("perf-smoke: kernel search OK", flush=True)
    check_obs_scrape()
    print("perf-smoke: obs /metrics scrape OK", flush=True)
    check_attribution_overhead()
    print("perf-smoke: attribution overhead OK", flush=True)
    check_rollout_drill()
    print("perf-smoke: rollout drill OK", flush=True)
    check_freshness_burst_drill()
    print("perf-smoke: freshness burst drill OK", flush=True)
    check_overload_drill()
    print("perf-smoke: overload drill OK", flush=True)
    check_drift_plane()
    print("perf-smoke: drift plane OK", flush=True)
    check_journey_trace()
    print("perf-smoke: journey trace OK", flush=True)
    check_recovery_drill()
    print("perf-smoke: recovery drill OK", flush=True)
    check_device_fault()
    print("perf-smoke: device fault plane OK", flush=True)
    check_fault_hooks_noop()
    print("perf-smoke: fault hooks no-op OK", flush=True)
    check_mesh_gate_noop()
    print("perf-smoke: mesh gate no-op OK", flush=True)
    check_zoo_pack()
    print("perf-smoke: zoo pack OK", flush=True)
    check_history()
    print("perf-smoke: history OK", flush=True)
    check_stateful()
    print("perf-smoke: keyed state OK", flush=True)
    timer.cancel()
    return 0


if __name__ == "__main__":
    sys.exit(main())
