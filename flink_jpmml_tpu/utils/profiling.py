"""Tracing / profiling hooks (SURVEY.md §6 row "Tracing / profiling").

The reference exposes Flink's web-UI metrics and backpressure monitors; the
TPU-native equivalents here are:

- :class:`StageTimer` — lightweight wall-clock accounting per pipeline
  stage of the record path (featurize / h2d+dispatch / readback /
  sink), feeding the metrics registry so ``snapshot()`` shows where
  stream time goes; with ``FJT_TRACE_DIR`` set it additionally emits
  host-side chrome://tracing spans (obs/spans.py);
- :func:`overlap_stats` / :func:`wire_stats` — the bench's overlap and
  encode-placement accounting.

The block path's stages are :meth:`obs.attr.StageLedger.span`, which
also puts each on the clock of a ``jax.profiler.start_trace`` session.
"""

from __future__ import annotations

import contextlib
import time
from typing import Dict, Iterator, Optional

from flink_jpmml_tpu.obs import spans
from flink_jpmml_tpu.utils.metrics import MetricsRegistry


def overlap_stats(
    metrics: MetricsRegistry, elapsed_s: float
) -> Dict[str, float]:
    """Overlap accounting for a run through the
    :class:`~flink_jpmml_tpu.runtime.pipeline.OverlappedDispatcher`.

    ``h2d_stall_ms`` is the total host time spent blocked on device
    completion (the dispatcher's ``h2d_stall_s`` counter);
    ``overlap_efficiency`` is the fraction of the run's wall clock the
    host was NOT so blocked — 1.0 means host staging fully hid behind
    device execution.  The bench emits both per operating mode.
    """
    stall = metrics.counter("h2d_stall_s").get()
    eff = 1.0
    if elapsed_s > 0:
        eff = max(0.0, min(1.0, 1.0 - stall / elapsed_s))
    return {
        "overlap_efficiency": round(eff, 4),
        "h2d_stall_ms": round(1000.0 * stall, 3),
        "inflight_depth_max": metrics.gauge("inflight_depth").max,
        "donation_hits": metrics.counter("donation_hits").get(),
    }


def wire_stats(metrics: MetricsRegistry, records: float) -> Dict[str, object]:
    """Encode-placement accounting for a run through
    :func:`~flink_jpmml_tpu.runtime.pipeline.dispatch_quantized`.

    ``encode_ms`` is the total host featurize+align time spent on the
    dispatch path (≈0 when the autotuner picked the fused on-device
    encode); ``h2d_bytes_per_record`` is staged host→device bytes per
    record (F on the uint8 rank wire, 4·F on the fused f32 wire);
    ``decode_ms`` rides along when a Kafka source accounted its wire
    decode (``kafka_decode_s``). The bench emits these per operating
    mode next to the overlap stats."""
    enc = metrics.counter("encode_s").get()
    dec = metrics.counter("kafka_decode_s").get()
    h2d = metrics.counter("h2d_bytes").get()
    out: Dict[str, object] = {
        "encode_ms": round(1000.0 * enc, 3),
        "h2d_bytes_per_record": (
            round(h2d / records, 2) if records else None
        ),
    }
    if dec:
        out["decode_ms"] = round(1000.0 * dec, 3)
    return out


class StageTimer:
    """Per-stage wall-clock accounting into a :class:`MetricsRegistry`.

    Each ``stage(name)`` context adds its elapsed seconds to the counter
    ``stage_<name>_s``; the registry snapshot then shows the share of
    pipeline time per stage.
    """

    def __init__(self, metrics: Optional[MetricsRegistry] = None):
        self.metrics = metrics or MetricsRegistry()

    @contextlib.contextmanager
    def stage(self, name: str) -> Iterator[None]:
        t0 = time.perf_counter()
        t0_span = time.monotonic()  # span clock: shared across emitters
        try:
            yield
        finally:
            dt = time.perf_counter() - t0
            self.metrics.counter(f"stage_{name}_s").inc(dt)
            spans.emit(name, t0_span, dt)
