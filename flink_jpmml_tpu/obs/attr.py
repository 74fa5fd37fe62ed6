"""Latency attribution: the per-batch stage ledger.

BENCH_r05 served 1.085M rec/s with the chip 94% idle and the kafka path
at half the hand loop — and the PR 3 observability plane could say how
long batches took but not WHERE the time went. This module is the
missing decomposition: every scored batch's wall time splits into the
pipeline stages

    fetch → decode → drain → encode → route → h2d → queue_wait →
    device → readback → sink → commit

each recorded into a ``stage_seconds{stage="..."}`` histogram in the
caller's :class:`~flink_jpmml_tpu.utils.metrics.MetricsRegistry`. The
histograms are the SAME mergeable fixed-bucket sketches every other
fleet metric uses, so per-stage attribution aggregates across workers
exactly like the PR 3 quantiles: heartbeats piggyback them, the
supervisor's ``/metrics`` merges them, and ``fjt-top`` renders the
fleet-wide ranked list of which stage to attack next.

**One span per stage** (:meth:`StageLedger.span`): a site wraps its
work once and the interval lands, under ONE name, in three places —
the stage histogram; the chrome-trace span file when ``FJT_TRACE_DIR``
is set (obs/spans.py); and, as ``fjt.<stage>``, a
``jax.profiler.TraceAnnotation`` on the thread that did the work, so
whenever a profiler session runs (``jax.profiler.start_trace`` — no
knob of this package arms it) the program's stages lie on
``/host:CPU`` of the same xplane and the same clock as the device's
``XLA Ops``. The span's keyword arguments ride the chrome span's
``args`` and the annotation's stats: ``first_off``/``n`` identify the
dispatch (the key the journey plane and the sink use), ``bytes`` sizes
an ``h2d``, ``records`` a ``decode``.

Stage semantics (who observes what):

- ``fetch``     — source fetch RPC (kafka consumer, per fetch; on the
                  prefetch sidecar when pipelined ingest is armed);
- ``decode``    — wire → f32 block decode (kafka consumer thread /
                  prefetch sidecar);
- ``prefetch_wait`` — the ring-feeding thread waiting on an EMPTY
                  prefetch handoff queue (runtime/prefetch.py): the
                  residual ingest cost once fetch+decode moved
                  off-thread — if this ranks high, the sidecar is the
                  bottleneck, not the hot path;
- ``drain``     — the score thread taking a batch off the ring:
                  ``ring.drain`` (its fill-or-deadline wait included)
                  and the multi-chunk aggregation's copies;
- ``encode``    — host featurize+align on the dispatch path
                  (``dispatch_quantized``; ≈0 when the encode is fused
                  on-device);
- ``route``     — keyed state's host routing on a state-armed
                  dispatch: key hashing, ``maybe_renorm``,
                  ``assign_slots``, the pad rows;
- ``claim``     — inside ``route``, and booked there too (a span is
                  a whole interval): the numpy claim rounds of
                  ``KeyedStateTable.route`` for the records its native
                  pass left — fresh keys, full probe windows, the
                  evictions. Nothing is booked where no record is left
                  (a stream of resident keys), so a sum over the
                  score thread's stages leaves ``claim`` out;
- ``shard``     — the keyed shuffle of a state table over a mesh
                  (runtime/shuffle.py): owner and rank of each held
                  record, the cut where a chip's bucket fills
                  (``cut``), the bucketed operands, the tail moved on,
                  and in ``dispatch_quantized`` the codes put in bucket
                  order (two intervals a dispatch);
- ``h2d``       — host-side staging + async dispatch issue (on the
                  trace its two halves are the child annotations
                  ``fjt.h2d.put`` and ``fjt.h2d.launch``: a long
                  ``h2d`` reads as a copy or as a call that blocked);
- ``queue_wait``— a ready batch waiting for an in-flight window slot
                  (``OverlappedDispatcher.launch`` on a full window);
- ``device``    — SAMPLED pure device execution time (the profiler's
                  block-until-ready delta pair, obs/profiler.py — a
                  sampled distribution, not every batch);
- ``readback``  — host blocked fetching results (``finish_oldest`` /
                  ``wait``);
- ``unshard``   — a mesh dispatch's scores fetched and put back in
                  offset order before the sink (``ShardPlan.unshard``;
                  the D2H copy a one-chip pipeline's sink pays is here);
- ``sink``      — sink delivery (block pipelines' ``_complete``);
- ``commit``    — the checkpoint tick after delivery
                  (``CheckpointManager.maybe_save``);
- ``prof_sample`` — the sampled device profiler's own bubble: its
                  drain of the in-flight window and its bracket wait
                  on the new dispatch (``OverlappedDispatcher.launch``
                  once per ``FJT_PROF_SAMPLE`` interval), two
                  intervals a sample.

**Exemplars**: an observation landing at (or above) the highest bucket
a stage has ever filled gets a trace id attached — recorded as a
``latency_exemplar`` flight-recorder event (with the active span file,
if tracing) and exported on the ``_bucket`` line of
OpenMetrics-negotiated ``/metrics`` scrapes (classic 0.0.4 scrapes
stay suffix-free: that format does not admit exemplars) — so a p99
scrape links directly to the postmortem context of the batch that
caused it.

**Stall events**: with a deadline configured (``FJT_SLO_TARGET_MS``), a
``queue_wait`` observation beyond ``FJT_SLO_STALL_FRAC`` (default 0.5)
of it records a ``stage_stall`` flight event (rate-limited: the flight
ring is for rare events).

Steady-state cost with nothing special happening: one dict lookup, one
``bisect``, one locked histogram increment per stage per batch, plus
the span's two clock reads and an unarmed ``TraceAnnotation`` (about a
microsecond with no profiler session) — the perf-smoke
observability-overhead tripwire holds the total under 2% of hand-loop
throughput.
"""

from __future__ import annotations

import os
import threading
import time
import weakref
from typing import Dict, Optional

from flink_jpmml_tpu.obs import recorder as flight
from flink_jpmml_tpu.obs import spans
from flink_jpmml_tpu.obs import trace as trace_mod
from flink_jpmml_tpu.utils.metrics import Histogram, MetricsRegistry

STAGES = (
    "fetch", "decode", "prefetch_wait", "drain", "encode", "route", "claim",
    "shard", "h2d", "queue_wait", "device", "readback", "unshard", "sink",
    "commit", "prof_sample",
)

# which thread each stage is observed on — rendered as the fjt-top
# stage table's thread column so an operator reading a pipelined-ingest
# profile knows which stages burn SIDECAR time (overlapped with
# scoring; runtime/prefetch.py moves fetch/decode there) vs hot-path
# time. "ingest" = the source-facing thread: the prefetch sidecar when
# one is armed, the pipeline's own ingest thread otherwise.
STAGE_THREADS = {
    "fetch": "ingest",
    "decode": "ingest",
    "prefetch_wait": "ring-feed",  # hot path waiting on the handoff
    "drain": "score",
    "encode": "score",
    "route": "score",
    "claim": "score",
    "shard": "score",
    "h2d": "score",
    "queue_wait": "score",
    "device": "device",
    "readback": "score",
    "unshard": "score",
    "sink": "score",
    "commit": "score",
    "prof_sample": "score",
}

# stages whose spans lie inside another stage's on the same thread:
# their time is the outer stage's too, so a sum over stages leaves them
# out (``summary``'s shares are of the un-nested total)
NESTED_IN = {"claim": "route"}

# the name a stage's span carries on the profiler's clock
ANNOTATION_PREFIX = "fjt."

_STALL_MS_ENV = "FJT_SLO_TARGET_MS"
_STALL_FRAC_ENV = "FJT_SLO_STALL_FRAC"
_EXEMPLAR_MIN_PERIOD_S = 1.0  # repeat top-bucket exemplars at most 1/s
# a steady stream landing in the SAME top bucket re-checks the clock
# only every this-many hits: the common hot-path outcome (top bucket,
# not due) costs an int compare instead of a time.monotonic() call
_EXEMPLAR_CHECK_EVERY = 32
_STALL_MIN_PERIOD_S = 1.0

_tid_lock = threading.Lock()
_tid_seq = 0


def new_trace_id() -> str:
    """Process-unique trace id: pid + monotone sequence (hex). Short
    enough to ride every exemplar, unique enough to grep a flight dump
    and a span file for."""
    global _tid_seq
    with _tid_lock:
        _tid_seq += 1
        seq = _tid_seq
    return f"{os.getpid():x}-{seq:x}"


_TRACE_ANNOTATION = None


def trace_only(name: str, **args):
    """A ``jax.profiler.TraceAnnotation`` named ``fjt.<name>``: an
    interval on the profiler's clock and nowhere else (no histogram, no
    chrome span) — the children of a stage (``h2d.put``/``h2d.launch``)
    and the carrier inside every :class:`StageSpan`. jax is imported at
    the first span, not with this module: fjt-top and the other
    struct-reading tools stay jax-free."""
    global _TRACE_ANNOTATION
    if _TRACE_ANNOTATION is None:
        from jax.profiler import TraceAnnotation

        _TRACE_ANNOTATION = TraceAnnotation
    return _TRACE_ANNOTATION(ANNOTATION_PREFIX + name, **args)


class StageSpan:
    """One interval of one stage: see :meth:`StageLedger.span`. After
    the span ended, ``t0`` (``time.monotonic``) and ``seconds`` say
    when and how long, for a caller that feeds a counter of its own
    from the same interval."""

    __slots__ = ("_ledger", "stage", "args", "t0", "seconds", "_ann")

    def __init__(self, ledger: "StageLedger", stage: str, args: dict):
        self._ledger = ledger
        self.stage = stage
        self.args = args
        self.t0 = 0.0
        self.seconds = 0.0
        self._ann = None

    def __enter__(self) -> "StageSpan":
        # the annotation opens first and closes last: it encloses the
        # booked interval by a microsecond, never the other way round
        self._ann = trace_only(self.stage, **self.args)
        self._ann.__enter__()
        self.t0 = time.monotonic()
        return self

    def note(self, **args) -> None:
        """Add what the site learned only while it worked (a drain's
        ``n``) to the open span's arguments."""
        self.args.update(args)
        self._ann.set_metadata(**args)

    def end(self) -> float:
        """Close the span → its seconds."""
        self.seconds = dt = time.monotonic() - self.t0
        self._ann.__exit__(None, None, None)
        if self._ledger.booked:
            self._ledger.observe(self.stage, dt)
        spans.emit(self.stage, self.t0, dt, **self.args)
        return dt

    def __exit__(self, exc_type, exc, tb) -> bool:
        # time spent is booked even when the work raised: the thread
        # WAS in this stage for that long either way
        self.end()
        return False


def stage_metric_name(stage: str) -> str:
    """The registry-name convention for the per-stage family (the obs
    server renders the suffix as a real Prometheus label, like
    ``kafka_lag{partition="..."}``)."""
    return f'stage_seconds{{stage="{stage}"}}'


class StageLedger:
    """Per-batch stage attribution into one :class:`MetricsRegistry`.

    One ledger per registry (see :func:`ledger_for`); all methods are
    thread-safe — ingest threads observe ``fetch``/``decode`` while the
    score thread observes the dispatch-side stages.
    """

    def __init__(self, metrics: Optional[MetricsRegistry]):
        # weak: the _LEDGERS cache is keyed weakly on the registry, and
        # a strong back-reference from the cached VALUE would keep the
        # key alive forever (the documented WeakKeyDictionary caveat) —
        # every ephemeral bench/test registry would leak
        self._metrics_ref = (
            weakref.ref(metrics) if metrics is not None else lambda: None
        )
        # False only for UNBOOKED: spans without a registry to book in
        self.booked = metrics is not None
        self._hists: Dict[str, Histogram] = {}
        self._mu = threading.Lock()
        # per-stage exemplar state: [max bucket idx, last capture t,
        # same-bucket hits since the last clock check]
        self._ex_state: Dict[str, list] = {}
        self._last_stall = 0.0
        # deadline config is read once per ledger: the hot path must not
        # hit os.environ per batch
        try:
            ms = float(os.environ.get(_STALL_MS_ENV) or 0.0)
        except ValueError:
            ms = 0.0
        try:
            frac = float(os.environ.get(_STALL_FRAC_ENV) or 0.5)
        except ValueError:
            frac = 0.5
        self._stall_threshold_s = (ms / 1000.0) * frac if ms > 0 else None

    def _hist(self, stage: str) -> Histogram:
        h = self._hists.get(stage)
        if h is None:
            reg = self._metrics_ref()
            if reg is None:  # registry died under a live caller:
                return Histogram()  # absorb the observe, don't cache
            # literal f-string so tools/metrics_lint.py sees the site
            h = reg.histogram(f'stage_seconds{{stage="{stage}"}}')
            self._hists[stage] = h
        return h

    def span(self, stage: str, **args) -> StageSpan:
        """Context manager over one interval of ``stage`` on the
        calling thread. On exit the interval is booked into
        ``stage_seconds{stage=...}`` exactly as :meth:`observe` books a
        duration, emitted as the chrome span ``<stage>`` when
        ``FJT_TRACE_DIR`` is set, and — held over the whole interval —
        it is the ``jax.profiler.TraceAnnotation`` ``fjt.<stage>``, so
        a running profiler session sees it beside the device's ops.
        ``args`` ride the chrome span and the annotation."""
        return StageSpan(self, stage, args)

    def begin(self, stage: str, **args) -> StageSpan:
        """:meth:`span` for a site that cannot wrap its work: → the
        started span; the site calls its ``end()`` on the same
        thread."""
        return StageSpan(self, stage, args).__enter__()

    def observe(self, stage: str, seconds: float) -> None:
        """Record one batch's time in ``stage`` for a caller that has
        only a duration (the sampled ``device`` stage); captures an exemplar
        when the observation lands in the stage's top-ever bucket and
        a ``stage_stall`` flight event when a ``queue_wait`` crosses
        the configured deadline fraction."""
        h = self._hists.get(stage)
        if h is None:
            h = self._hist(stage)
        idx = h.bucket_index(seconds)
        exemplar = None
        # journey linkage (obs/trace.py): with a record-journey context
        # active on this thread, the exemplar id IS the journey's trace
        # id — the fjt-top exemplar row pivots straight to fjt-trace —
        # and capturing one marks the journey interesting, which is
        # exactly the "top-latency journeys survive tail-sampling"
        # policy (the exemplar path already decides what the tail is)
        jctx = trace_mod.current()
        with self._mu:
            st = self._ex_state.get(stage)
            # st = [max bucket idx seen, last capture t, hits since check]
            if st is None:
                st = self._ex_state[stage] = [-1, 0.0, 0]
            if idx > st[0]:
                st[0] = idx
                st[1] = time.monotonic()
                st[2] = 0
                exemplar = (
                    jctx.trace_id if jctx is not None else new_trace_id()
                )
            elif idx == st[0]:
                # the steady-state outcome for a stage whose tail sits
                # in one bucket: an int compare, no clock read
                st[2] += 1
                if st[2] >= _EXEMPLAR_CHECK_EVERY:
                    st[2] = 0
                    now = time.monotonic()
                    if now - st[1] >= _EXEMPLAR_MIN_PERIOD_S:
                        st[1] = now
                        exemplar = (
                            jctx.trace_id if jctx is not None
                            else new_trace_id()
                        )
        if exemplar is not None and jctx is not None:
            jstore = trace_mod.store_for(self._metrics_ref())
            if jstore is not None:
                jstore.mark(jctx.trace_id, "exemplar")
        if exemplar is not None:
            w = spans.writer()
            flight.record(
                "latency_exemplar",
                trace_id=exemplar,
                stage=stage,
                seconds=round(seconds, 6),
                span_file=(w.path if w is not None else None),
            )
            spans.emit(
                stage + "_exemplar",
                time.monotonic() - seconds,
                seconds,
                trace_id=exemplar,
            )
        h.observe(seconds, exemplar=exemplar)
        if (
            stage == "queue_wait"
            and self._stall_threshold_s is not None
            and seconds > self._stall_threshold_s
        ):
            now = time.monotonic()  # rare path: past the deadline frac
            with self._mu:
                stall_due = now - self._last_stall >= _STALL_MIN_PERIOD_S
                if stall_due:
                    self._last_stall = now
            if stall_due:
                flight.record(
                    "stage_stall",
                    stage=stage,
                    seconds=round(seconds, 6),
                    threshold_s=round(self._stall_threshold_s, 6),
                )


# one ledger per registry, resolved once per dispatch path (cf. the
# _WIRE_COUNTERS pattern in runtime/pipeline.py); weak keys let
# ephemeral bench registries die normally
_LEDGERS: "weakref.WeakKeyDictionary" = weakref.WeakKeyDictionary()
_LEDGERS_MU = threading.Lock()


# spans for a caller that has no registry (a bare dispatch_quantized, a
# source built without metrics): on the chrome trace and the profiler's
# clock like any other, booked nowhere
UNBOOKED = StageLedger(None)


def ledger_for(metrics: Optional[MetricsRegistry]) -> Optional[StageLedger]:
    if metrics is None:
        return None
    led = _LEDGERS.get(metrics)
    if led is None:
        with _LEDGERS_MU:
            led = _LEDGERS.get(metrics)
            if led is None:
                led = _LEDGERS[metrics] = StageLedger(metrics)
    return led


# ---------------------------------------------------------------------------
# Dispatch profiles: what a launch site tells the device profiler
# ---------------------------------------------------------------------------


def _scorer_flops_per_record(q) -> Optional[float]:
    """Analytic FLOPs/record of a quantized tree-ensemble scorer — the
    same path-matrix roofline bench.py uses (2·T·S·L split-indicator
    einsum + 2·T·L leaf contraction), derived from the packed param
    shapes so it holds for any (trees, depth). Cached on the scorer."""
    cached = getattr(q, "_attr_flops", False)
    if cached is not False:
        return cached
    flops = None
    try:
        for v in q.params.values():
            shape = tuple(getattr(v, "shape", ()) or ())
            if len(shape) == 3:
                t, s, l = (float(x) for x in shape)
                flops = 2.0 * t * s * l + 2.0 * t * l
                break
    except Exception:
        flops = None
    try:
        q._attr_flops = flops
    except Exception:
        pass
    return flops


def dispatch_profile(scorer_or_bound, n: int) -> dict:
    """Per-launch metadata for the sampled device profiler: record
    count, the analytic FLOP/byte cost model (None fields when unknown
    — e.g. the f32 fallback path), and a model key for the kernel cost
    ledger. Accepts a ``QuantizedScorer``, a ``BoundScorer`` (its ``q``
    is used when present), or any model object."""
    q = getattr(scorer_or_bound, "q", None) or scorer_or_bound
    flops = None
    if getattr(q, "params", None) is not None:
        flops = _scorer_flops_per_record(q)
    # HBM stream bytes per record: the staged wire bytes in + a bf16
    # score out (the bench roofline's convention). The scorer's own
    # layout-aware property covers fused f32 AND the packed rank wire;
    # the wire fallback handles foreign scorer objects
    bpr = None
    wire = getattr(q, "wire", None)
    if wire is not None:
        try:
            staged = getattr(q, "staged_bytes_per_record", None)
            if staged is not None:
                bpr = float(staged) + 2.0
            else:
                bpr = float(wire.bytes_per_record) + 2.0
        except Exception:
            bpr = None
    model_key = (
        getattr(scorer_or_bound, "key", None)
        or getattr(q, "model_hash", None)
        or None
    )
    return {
        "records": int(n),
        "flops_per_record": flops,
        "bytes_per_record": bpr,
        "model": model_key,
        # the autotune cache key half: the drift-band re-search trigger
        # clears by model_hash, while ``model`` above may be the
        # serving registry name (BoundScorer.key)
        "model_hash": getattr(q, "model_hash", None),
        "backend": getattr(q, "backend", None),
        # kernel-search provenance: which catalogue variant is serving,
        # its feature vector (ledger training row), and the prediction
        # for the variant ACTUALLY running (the live drift band
        # verifies it; autotune nulls it when a cached variant
        # degraded to defaults) — all cached scorer attributes, so the
        # per-launch cost stays a handful of getattrs (the
        # attribution-overhead tripwire)
        "layout": getattr(q, "layout", None),
        "variant": getattr(q, "_cost_variant", None),
        "features": getattr(q, "_cost_feat", None),
        "predicted_s_per_record": getattr(q, "_pred_s_per_record", None),
    }


# ---------------------------------------------------------------------------
# Attribution summaries (bench artifacts / fjt-top)
# ---------------------------------------------------------------------------


def summary(struct_or_registry) -> Optional[dict]:
    """Per-stage attribution summary from a metrics struct (or a live
    registry): ``{stage: {n, total_ms, p50_ms, p99_ms, share}}`` with
    ``share`` = this stage's total over all stages' total. None when no
    stage was ever observed (the field stays honest in artifacts)."""
    if isinstance(struct_or_registry, MetricsRegistry):
        struct = struct_or_registry.struct_snapshot()
    else:
        struct = struct_or_registry or {}
    hists = struct.get("histograms") or {}
    out: dict = {}
    total = 0.0
    for stage in STAGES:
        state = hists.get(stage_metric_name(stage))
        if not isinstance(state, dict):
            continue
        try:
            h = Histogram.from_state(state)
        except (KeyError, IndexError, TypeError, ValueError):
            continue
        if h.count() == 0:
            continue
        s = h.sum()
        if stage not in NESTED_IN:
            total += s
        out[stage] = {
            "n": h.count(),
            "total_ms": round(1000.0 * s, 3),
            "p50_ms": round(1000.0 * (h.quantile(0.5) or 0.0), 3),
            "p99_ms": round(1000.0 * (h.quantile(0.99) or 0.0), 3),
        }
    if not out:
        return None
    for stage, row in out.items():
        row["share"] = round((row["total_ms"] / 1000.0) / total, 4) if total else 0.0
    return out


# ---------------------------------------------------------------------------
# Snapshot staleness (fjt-top --watch honesty, fjt-replay frame ages)
# ---------------------------------------------------------------------------


def snapshot_age_s(struct, now: Optional[float] = None) -> Optional[float]:
    """Age of a metrics struct from its OWN capture timestamp (the
    ``ts`` every ``struct_snapshot`` self-reports; a merged struct
    carries its stalest member's). None for pre-``ts`` structs (old
    BENCH artifacts, version-skewed workers) — unknown age, not zero:
    a watch loop re-rendering a wedged source must say 'stale', never
    imply freshness it can't prove."""
    if not isinstance(struct, dict):
        return None
    try:
        ts = float(struct["ts"])
    except (KeyError, TypeError, ValueError):
        return None
    return max(0.0, (time.time() if now is None else now) - ts)


def staleness_tag(
    struct,
    threshold_s: float = 10.0,
    now: Optional[float] = None,
) -> str:
    """Render suffix for a panel title: empty while fresh, a loud
    ``[STALE <age>]`` past ``threshold_s`` — identical numbers from a
    dead source must not keep looking live."""
    age = snapshot_age_s(struct, now=now)
    if age is None or age <= threshold_s:
        return ""
    return f"  [STALE {age:.0f}s]"
