"""Block pipeline: the high-throughput vector path (≥1M rec/s).

The record-object :class:`~flink_jpmml_tpu.runtime.engine.Pipeline` is
flexible but pays Python-object costs per record — fine for thousands of
records/sec, fatal for millions. On this path records are contiguous
float32 *blocks* end to end:

    BlockSource.poll() → [n, F] numpy block
      → C++ ring (native.NativeRing; Python fallback)  ← backpressure
      → fill-or-deadline drain into a reused batch buffer
      → pad → jitted scoring (async dispatch, in-flight window)
      → sink(outputs)

No Python object per record exists anywhere; the only per-batch host work
is one memcpy into the ring and one out. This is the "no CPU evaluator in
the hot path" half of the BASELINE north star made concrete on the host
side.
"""

from __future__ import annotations

import os
import threading
import time
import warnings
from typing import Callable, List, Optional, Tuple

import numpy as np

from flink_jpmml_tpu.compile import prepare
from flink_jpmml_tpu.compile.compiler import CompiledModel
from flink_jpmml_tpu.obs import attr as attr_mod
from flink_jpmml_tpu.obs import drift as drift_mod
from flink_jpmml_tpu.obs import freshness as fresh_mod
from flink_jpmml_tpu.obs import pressure as pressure_mod
from flink_jpmml_tpu.obs import recorder as flight
from flink_jpmml_tpu.obs import trace as trace_mod
from flink_jpmml_tpu.runtime import devfault
from flink_jpmml_tpu.runtime import faults
from flink_jpmml_tpu.runtime import prefetch as prefetch_mod
from flink_jpmml_tpu.runtime import state as state_mod
from flink_jpmml_tpu.runtime.checkpoint import CheckpointPolicy
from flink_jpmml_tpu.runtime.dlq import (
    REASON_CRASH_LOOP,
    REASON_SCORE,
    CrashFingerprint,
    PoisonIsolationOverflow,
    dlq_for_checkpoint,
    env_count,
)
from flink_jpmml_tpu.runtime.pipeline import (
    OverlappedDispatcher,
    _block_ready,
    _prefetch_host,  # noqa: F401  (re-export: engine.py imports it here)
    dispatch_quantized,
    filter_donate_warning,
)
from flink_jpmml_tpu.utils.config import RuntimeConfig
from flink_jpmml_tpu.utils.exceptions import (
    FlinkJpmmlTpuError,
    InputValidationException,
)
from flink_jpmml_tpu.utils.metrics import MetricsRegistry


class BlockSource:
    """poll() → (first_offset, block [n,F]) or None when drained/starved."""

    def poll(self) -> Optional[Tuple[int, np.ndarray]]:
        raise NotImplementedError

    def seek(self, offset: int) -> None:
        """Resume hook: next poll starts at this record offset."""
        raise NotImplementedError(
            f"{type(self).__name__} does not support offset seek/resume"
        )

    @property
    def exhausted(self) -> bool:
        return False


class CyclingBlockSource(BlockSource):
    """Cycles over a fixed dataset in blocks forever (bench/load-gen)."""

    def __init__(self, data: np.ndarray, block_size: int):
        self._data = np.ascontiguousarray(data, np.float32)
        self._block = block_size
        self._pos = 0
        self._offset = 0

    def poll(self):
        n = self._data.shape[0]
        if self._pos + self._block <= n:
            blk = self._data[self._pos : self._pos + self._block]
            self._pos += self._block
        else:
            a = self._data[self._pos :]
            b = self._data[: self._block - a.shape[0]]
            blk = np.concatenate([a, b], axis=0)
            self._pos = self._block - a.shape[0]
        off = self._offset
        self._offset += blk.shape[0]
        return off, blk

    def seek(self, offset: int) -> None:
        self._offset = offset
        self._pos = offset % self._data.shape[0]


class FiniteBlockSource(BlockSource):
    def __init__(self, data: np.ndarray, block_size: int):
        self._data = np.ascontiguousarray(data, np.float32)
        self._block = block_size
        self._pos = 0

    def poll(self):
        if self._pos >= self._data.shape[0]:
            return None
        blk = self._data[self._pos : self._pos + self._block]
        off = self._pos
        self._pos += blk.shape[0]
        return off, blk

    def seek(self, offset: int) -> None:
        self._pos = offset

    @property
    def exhausted(self) -> bool:
        return self._pos >= self._data.shape[0]


class _PyRing:
    """Pure-Python fallback with the NativeRing interface (chunk list +
    condition variables; same fill-or-deadline semantics, more GIL)."""

    def __init__(self, capacity: int, arity: int, batch_size: int):
        self._cap = capacity
        self._arity = arity
        self._chunks: List[Tuple[int, np.ndarray]] = []
        self._count = 0
        self._lock = threading.Lock()
        self._not_empty = threading.Condition(self._lock)
        self._not_full = threading.Condition(self._lock)
        self._closed = False
        self._batch = np.zeros((batch_size, arity), np.float32)
        self._offsets = np.zeros((batch_size,), np.uint64)

    def push_block(self, block, first_offset, timeout_us=-1) -> int:
        block = np.ascontiguousarray(block, np.float32)
        pushed = 0
        deadline = (
            None if timeout_us < 0 else time.monotonic() + timeout_us / 1e6
        )
        with self._not_full:
            while pushed < block.shape[0]:
                while self._count >= self._cap and not self._closed:
                    remaining = (
                        None if deadline is None else deadline - time.monotonic()
                    )
                    if remaining is not None and remaining <= 0:
                        return pushed
                    self._not_full.wait(remaining if remaining else 0.1)
                if self._closed:
                    return pushed
                room = self._cap - self._count
                take = min(room, block.shape[0] - pushed)
                self._chunks.append(
                    (first_offset + pushed, block[pushed : pushed + take])
                )
                self._count += take
                pushed += take
                self._not_empty.notify()
        return pushed

    def _wait_first(self, idle_timeout_us: int) -> bool:
        """With the lock held: blocks until a record is in (bounded by
        ``idle_timeout_us`` when >= 0) → False where none came: closed
        and empty, or the idle bound expired (an empty return on an
        open ring lets the consumer run control-plane work)."""
        idle_deadline = (
            None
            if idle_timeout_us < 0
            else time.monotonic() + idle_timeout_us / 1e6
        )
        while self._count == 0:
            if self._closed:
                return False
            if idle_deadline is None:
                self._not_empty.wait(0.1)
            else:
                remaining = idle_deadline - time.monotonic()
                if remaining <= 0:
                    return False
                self._not_empty.wait(min(remaining, 0.1))
        return True

    def drain(self, deadline_us: int, idle_timeout_us: int = -1):
        with self._not_empty:
            if not self._wait_first(idle_timeout_us):
                return self._batch[:0], self._offsets[:0]
            deadline = time.monotonic() + deadline_us / 1e6
            drained = 0
            max_n = self._batch.shape[0]
            while drained < max_n:
                while self._chunks and drained < max_n:
                    off, chunk = self._chunks[0]
                    take = min(chunk.shape[0], max_n - drained)
                    self._batch[drained : drained + take] = chunk[:take]
                    self._offsets[drained : drained + take] = np.arange(
                        off, off + take, dtype=np.uint64
                    )
                    if take == chunk.shape[0]:
                        self._chunks.pop(0)
                    else:
                        self._chunks[0] = (off + take, chunk[take:])
                    self._count -= take
                    drained += take
                    self._not_full.notify_all()
                if drained >= max_n or self._closed:
                    break
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    break
                self._not_empty.wait(remaining)
            return self._batch[:drained], self._offsets[:drained]

    def wait(self, deadline_us: int, idle_timeout_us: int = -1) -> int:
        """The waiting half of :meth:`drain` (``fjt_ring_wait``): the
        first record, then a batch, the deadline or close; takes
        nothing → the microseconds left of the deadline, -1 where no
        record came."""
        with self._not_empty:
            if not self._wait_first(idle_timeout_us):
                return -1
            deadline = time.monotonic() + deadline_us / 1e6
            want = min(self._batch.shape[0], self._cap)
            while self._count < want and not self._closed:
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    break
                self._not_empty.wait(remaining)
            return max(0, int((deadline - time.monotonic()) * 1e6))

    def close(self):
        with self._lock:
            self._closed = True
            self._not_empty.notify_all()
            self._not_full.notify_all()

    @property
    def closed(self):
        with self._lock:
            return self._closed

    def __len__(self):
        with self._lock:
            return self._count


def make_ring(capacity: int, arity: int, batch_size: int, native: bool = True):
    """NativeRing when asked for and the C++ plane builds; _PyRing
    otherwise — said once per process, with the build error, when the
    caller asked for the native ring and did not get it."""
    if native:
        from flink_jpmml_tpu.runtime import native as native_mod

        if native_mod.available():
            return native_mod.NativeRing(capacity, arity, batch_size)
        warnings.warn(
            "C++ data plane unavailable; the pipeline runs on the "
            f"pure-Python ring: {native_mod.build_error()}",
            RuntimeWarning,
            stacklevel=3,
        )
    return _PyRing(capacity, arity, batch_size)


class BoundScorer:
    """One servable compiled model bound for block scoring: its (maybe)
    rank-wire scorer, the ``rank_wire_*``/``f32`` backend tag, and the
    decode callable (carrying ``model_key``) handed to dynamic sinks.
    Shared by the static and dynamic pipelines so the probe/backend/
    decode logic cannot diverge between them."""

    def __init__(self, key: str, model, use_quantized: bool, metrics=None):
        self.key = key
        self.model = model
        probe = getattr(model, "quantized_scorer", None)
        self.q = probe() if (use_quantized and probe is not None) else None
        self.backend = (
            f"rank_wire_{self.q.backend}" if self.q is not None else "f32"
        )
        if metrics is not None and self.backend == "rank_wire_xla":
            # the XLA twin's form, once a scorer is adopted: the depth
            # of its select tree, 0 where it contracts its path matrix
            metrics.gauge("qtrees_twin_depth").set(float(self.q.twin_depth))

        def decode(out, n):
            if self.q is not None:
                return self.q.decode(out, n)
            return self.model.decode(out, n)

        decode.model_key = key
        # the drift plane's content-addressed label: matches the
        # feature-profile label dispatch_quantized records under, so a
        # model's feature and prediction series share one baseline
        decode.model_hash = (
            self.q.model_hash if self.q is not None else None
        )
        self.decode = decode


class BlockPipelineBase:
    """Shared machinery of the static and dynamic block pipelines:
    ingest→ring, lifecycle (start/stop/join/run_*), the ``_drain_all``
    stop protocol, and the score loop skeleton. Subclass hooks:

    - ``_acquire(finish_one)`` → per-batch scoring handle (or None to
      abandon the loop — the dynamic pipeline's bounded registry-gap
      give-up); called with a drained batch pending, between batches.
    - ``_dispatch(handle, X, n)`` → ``(raw_out, decode_or_None)``, the
      async device dispatch.
    - ``_emit(out, n, first_off, decode)`` → deliver to the sink.
    - ``_on_idle()`` — called when the ring drain returns empty on an
      open ring; reachable only when ``_IDLE_WAIT_US >= 0`` bounds the
      drain's wait for a first record (the dynamic pipeline sets it so
      Add/Del messages apply promptly on an idle stream).
    """

    _THREAD_TAG = "blk"
    _IDLE_WAIT_US = -1  # block indefinitely for the first record

    def __init__(
        self,
        source: BlockSource,
        sink: Callable,
        arity: int,
        batch_size: int,
        config: Optional[RuntimeConfig],
        metrics: Optional[MetricsRegistry],
        use_native: bool,
        in_flight: int,
        checkpoint,
        max_dispatch_chunks: int = 8,
        donate: Optional[bool] = None,
        slo=None,
        batcher=None,
        admission=None,
        shed_lane: str = "block",
        dlq=None,
        prefetch: Optional[bool] = None,
        failover=None,
        tenant: Optional[str] = None,
        state=None,
    ):
        # per-tenant delivery label (serving/zoo.py plane): see
        # engine.Pipeline — records_out stays the total, the labelled
        # counter adds the tenant axis. Mutable via set_tenant so the
        # dynamic block pipeline re-labels on a served-model swap.
        self._tenant = tenant
        self._source = source
        self._sink = sink
        # optional obs/slo.SLOTracker: ticked from the completion path
        # (between batches, on the score thread — the RolloutController
        # piggyback pattern), so burn-rate state stays live without a
        # thread of its own
        self._slo = slo
        # overload plane (serving/overload.py), both optional:
        # - batcher: AdaptiveBatcher — caps opportunistic multi-chunk
        #   aggregation at the size predicted to fit the deadline, fed
        #   from every completed dispatch (deadline-aware batching with
        #   no recompile);
        # - admission: AdmissionController — drained batches it refuses
        #   ride the FIFO window as no-op entries (offsets commit in
        #   order, the SINK NEVER SEES a shed record) under
        #   ``shed_lane``; its controller ticks piggyback on the
        #   completion path like the SLO tracker's.
        self._batcher = batcher
        self._admission = admission
        self._shed_lane = shed_lane
        if admission is not None and shed_lane not in admission.lanes:
            # unknown lanes are never shed (the safe per-record
            # default), which here would mean a controller that climbs
            # levels and reports shedding while refusing NOTHING —
            # silent no-op protection is the wrong default for a
            # whole-pipeline wire, so fail loudly at construction
            raise InputValidationException(
                f"shed_lane {shed_lane!r} is not one of the admission "
                f"controller's lanes {admission.lanes!r} — this "
                "pipeline could never shed"
            )
        self._arity = arity
        self._batch_size = batch_size
        # >1 enables opportunistic multi-chunk dispatch on a backed-up
        # ring (see _aggregate_full_batches); 1 = one batch per dispatch
        self._max_dispatch_chunks = max(1, max_dispatch_chunks)
        self._config = config or RuntimeConfig()
        self.metrics = metrics or MetricsRegistry()
        # pipelined ingest (runtime/prefetch.py): sources that mark
        # themselves prefetchable (the Kafka sources — real network
        # fetch + wire decode) get a sidecar thread running their poll
        # loop, so this pipeline's ingest thread only moves decoded
        # blocks into the ring. prefetch=None is auto; the wrapper
        # proxies seek/checkpoint hooks, so restore() is unchanged.
        self._source = prefetch_mod.maybe_wrap_block(
            self._source, metrics=self.metrics, enable=prefetch
        )
        self._ring = make_ring(
            self._config.batch.queue_capacity,
            arity,
            batch_size,
            native=use_native,
        )
        # what a drain that found no record hands back
        self._nothing = (
            np.zeros((0, arity), np.float32), np.zeros(0, np.uint64)
        )
        self._in_flight_max = max(1, in_flight)
        # buffer donation on the rank-wire dispatch: None = auto (on
        # when the backend isn't CPU — XLA:CPU ignores donation with a
        # warning per compile, so tests stay quiet by default)
        self._donate = donate
        self._donation_hits = self.metrics.counter("donation_hits")
        # drained-but-undispatched batches carried across loop
        # iterations (aggregation stops at an offset discontinuity —
        # a cycling source's wrap — and a chunk cannot be re-queued;
        # the poison plane additionally splits mid-batch gaps, which
        # can queue a second carry, hence a deque)
        self._carry_drain: "List[Tuple[np.ndarray, np.ndarray]]" = []
        # see engine.Pipeline: True only for run_until_exhausted's full
        # drain; plain stop() discards the uncommitted ring backlog so it
        # returns promptly under a flooding source
        self._drain_all = False
        self._stop = threading.Event()
        self._threads: List[threading.Thread] = []
        self._error: Optional[BaseException] = None
        self.committed_offset = 0
        self._ckpt = CheckpointPolicy(
            checkpoint, self._config.checkpoint_interval_s,
            metrics=self.metrics,
        )
        # -- delivery-correctness plane (runtime/dlq.py) ------------------
        # The DLQ defaults to living BESIDE the checkpoints: record-level
        # error isolation only makes sense when the quarantine survives
        # the restarts it exists to prevent. dlq=None with no checkpoint
        # keeps the historical behavior exactly (a scoring error kills
        # the worker).
        self._dlq = dlq if dlq is not None else dlq_for_checkpoint(
            checkpoint, metrics=self.metrics
        )
        ckpt_dir = getattr(checkpoint, "directory", None)
        self._ckpt_dir = ckpt_dir
        self._fingerprint = (
            CrashFingerprint(ckpt_dir)
            if (ckpt_dir is not None and self._dlq is not None) else None
        )
        # -- keyed per-record state (runtime/state.py) --------------------
        # state=StateSpec arms the fused state stage (the table joins
        # THIS pipeline's registry so state_* metrics scrape/merge like
        # every other family); a prebuilt KeyedStateTable passes
        # through (caller chose the registry). Unarmed pipelines pay
        # one None check per dispatch.
        if isinstance(state, state_mod.StateSpec):
            state = state_mod.KeyedStateTable(state, metrics=self.metrics)
        self._state = state
        # >0 while a recovery/isolation path is dispatching: those
        # re-dispatches (and bisection probes, which score records
        # MORE THAN ONCE) must never mutate the table — the PR 8/12
        # never-delivered contract extended to state
        self._state_bypass = 0
        # the batch offsets of the dispatch currently being launched
        # (stashed by _dispatch_checked for the state stage; the score
        # loop is single-threaded by the ring contract)
        self._cur_offsets = None
        # the current dispatch's shard plan, stashed the same way, and
        # the keyed shuffle of a state table over a mesh
        # (runtime/shuffle.py; the subclass arms it)
        self._cur_plan = None
        self._shuffle = None
        # -- device-fault resilience (runtime/devfault.py +
        #    serving/failover.py) ------------------------------------------
        # The recovery ladder (redispatch → OOM batch bisection →
        # circuit breaker → fallback tier) arms by default wherever the
        # staging batches are ALREADY retained past the async dispatch
        # (a DLQ is wired — the production shape), or explicitly via
        # failover=<plane> / FJT_FAILOVER=1. A bare bench loop with no
        # durable state pays neither the retention copy nor the plane.
        # failover=False disables outright (historical fail-fast).
        if failover is False:
            self._failover = None
        elif failover is not None:
            self._failover = failover
        elif self._dlq is not None or os.environ.get("FJT_FAILOVER"):
            from flink_jpmml_tpu.serving import failover as failover_mod

            self._failover = failover_mod.plane_for(self.metrics)
        else:
            self._failover = None
        # retain the drained batch (private copy) past the async
        # dispatch: poison isolation AND device-fault recovery both
        # re-dispatch from this host-retained staging copy
        self._retain_batches = (
            self._dlq is not None or self._failover is not None
        )
        # highest offset ever handed to a dispatch (+n): checkpointed as
        # inflight_hi so a restart knows the at-least-once replay region
        self._dispatched_hi = 0
        # replay accounting + crash-loop suspect mode, armed by restore()
        self._replay_until = 0
        self._suspect_until: Optional[int] = None
        self._death_marker: Optional[dict] = None
        # 1 while scoring in suspect mode (fleet merge: worst-of — one
        # worker bisecting poison flags the fleet)
        self._suspect_gauge = self.metrics.gauge("poison_suspect_mode")
        # per-chip mesh telemetry (obs/mesh.MeshTelemetry), attached by
        # the subclass when the bound model is mesh-sharded; None keeps
        # the single-chip hot path at one attribute test per batch
        self._mesh_obs = None

    @property
    def native(self) -> bool:
        return not isinstance(self._ring, _PyRing)

    def _ckpt_state(self) -> dict:
        state = {
            "source_offset": self.committed_offset,
            # the in-flight offset range's upper bound: on restore,
            # [source_offset, inflight_hi) is exactly the at-least-once
            # replay region — what records_replayed counts and what a
            # crash-loop fingerprint resumes in suspect mode
            "inflight_hi": max(self._dispatched_hi, self.committed_offset),
        }
        # sources whose resume needs more than the scalar offset (e.g.
        # multi-partition Kafka's per-partition cursor vector) embed it
        # via the checkpoint_state/restore_state hooks
        snap = getattr(self._source, "checkpoint_state", None)
        if snap is not None:
            extra = snap(self.committed_offset)
            if extra is not None:
                state["source_state"] = extra
        if self._state is not None:
            # the keyed state table rides the checkpoint: an npz
            # sidecar beside the snapshots (same atomic-writer
            # discipline) referenced by name, or an inline payload for
            # small dirless tables. Saved at the SAME instant as the
            # offsets (this method runs when the policy fires, on the
            # score thread), so offsets and state agree; the table's
            # own applied_hi makes replayed records below it bypass
            # after restore (exactly-once state).
            ref = (
                self._state.save_sidecar(self._ckpt_dir)
                if self._ckpt_dir is not None else None
            )
            if ref is not None:
                state["state_sidecar"] = ref
            else:
                try:
                    state["state"] = self._state.to_payload()
                except Exception:
                    # a large table with no checkpoint directory:
                    # state is not durable — restart loses it (the
                    # runbook's sizing note), offsets stay correct
                    pass
        return state

    def restore(self) -> bool:
        """Resume from the latest checkpoint: seek the source to the last
        committed record offset (commit happens after sink, C7). A
        source-state payload (per-partition offset vector) takes
        precedence — its effective resume offset may sit one emission
        boundary below the scalar commit (at-least-once replay)."""
        state = self._ckpt.restore_latest()
        if state is None:
            # no snapshot yet — but the crash-loop fingerprint must
            # still count this restore: a poison record in the FIRST
            # uncommitted window crash-loops at offset 0 before any
            # checkpoint ever lands
            self._init_poison_state({})
            return False
        off = int(state.get("source_offset", 0))
        sstate = state.get("source_state")
        rst = getattr(self._source, "restore_state", None)
        if sstate is not None and rst is not None:
            off = int(rst(sstate))
        else:
            self._source.seek(off)
        self.committed_offset = off
        self._init_poison_state(state)
        self._restore_extra(state)
        return True

    def _init_poison_state(self, state: dict) -> None:
        """Crash-loop fingerprinting at restore: count consecutive
        restores stuck at the same committed offset (``crashes.json``
        beside the checkpoints) and read the supervisor's
        ``FJT_RESTART_STREAK`` hint — EITHER crossing
        ``FJT_POISON_RESTARTS`` flips the checkpoint's in-flight range
        into suspect mode, converting a crash loop into a DLQ entry
        instead of an ``on_give_up`` outage."""
        self._replay_until = max(
            int(state.get("inflight_hi", 0)), self.committed_offset
        )
        if self._fingerprint is None:
            return
        committed = self.committed_offset
        count = self._fingerprint.note_restore(committed)
        streak = env_count("FJT_RESTART_STREAK", 0)
        self._death_marker = self._fingerprint.read_marker()
        if (
            self._death_marker is not None
            and self._death_marker["hi"] <= committed
        ):
            # marker from a range that later committed: stale
            self._death_marker = None
            self._fingerprint.clear_marker()
        jstore = trace_mod.store_for(self.metrics)
        if jstore is not None:
            # the incarnation boundary, durable: fjt-trace renders the
            # pid change + the committed offset this restore resumed at
            jstore.hop(
                "restore", trace_mod.context_for(committed),
                first_off=committed, durable=True,
                restarts=max(count - 1, streak),
            )
        threshold = env_count("FJT_POISON_RESTARTS", 3)
        if max(count - 1, streak) >= threshold:
            # count-1: the FIRST restore at an offset is a normal
            # restart, not yet a loop
            hi = self._replay_until
            if hi <= committed:
                hi = committed + self._batch_size
            self._suspect_until = hi
            self._suspect_gauge.set(1.0)
            if jstore is not None:
                # suspect mode flips the journey store to write-through:
                # every hop of the bisection protocol must be on disk
                # BEFORE a process-killing record strikes again — the
                # marker protocol's observability twin
                jstore.write_through = True
                jstore.hop(
                    "suspect_mode", trace_mod.context_for(committed),
                    first_off=committed, n=hi - committed, durable=True,
                    restarts=max(count - 1, streak),
                )
            flight.record(
                "poison_suspect_mode", lo=committed, hi=hi,
                restarts=max(count - 1, streak),
                marker=self._death_marker,
            )

    def _restore_extra(self, state: dict) -> None:
        if self._state is None:
            return
        ref = state.get("state_sidecar")
        if ref and self._ckpt_dir is not None:
            self._state.restore_sidecar(self._ckpt_dir, ref)
        elif state.get("state"):
            self._state.from_payload(state["state"])

    def start(self):
        t1 = threading.Thread(
            target=self._ingest,
            name=f"fjt-{self._THREAD_TAG}-ingest",
            daemon=True,
        )
        t2 = threading.Thread(
            target=self._score,
            name=f"fjt-{self._THREAD_TAG}-score",
            daemon=True,
        )
        self._threads = [t1, t2]
        t1.start()
        t2.start()
        return self

    def stop(self) -> None:
        self._stop.set()
        stop_sidecar = getattr(self._source, "stop_prefetch", None)
        if stop_sidecar is not None:
            # park the prefetch sidecar too: without this it would keep
            # fetching into the (bounded) handoff queue until the
            # process exits — harmless but dishonest in lag gauges
            stop_sidecar()
        self._ring.close()

    def join(self, timeout: Optional[float] = None) -> None:
        for t in self._threads:
            t.join(timeout)
        if self._error is not None:
            raise self._error

    def run_for(self, seconds: float) -> None:
        self.start()
        time.sleep(seconds)
        self.stop()
        self.join(timeout=30.0)

    def run_until_exhausted(self, timeout: float = 60.0) -> None:
        """Deterministic drain: join the ingest thread (exits once the
        source is exhausted and fully pushed), then close the ring — the
        score loop drains the ring's remainder plus its in-flight window
        before exiting. No sleep-based settle windows."""
        self.start()
        deadline = time.monotonic() + timeout
        ingest = self._threads[0]
        while ingest.is_alive() and self._error is None:
            remaining = deadline - time.monotonic()
            if remaining <= 0:
                break
            ingest.join(timeout=min(remaining, 0.05))
        self._drain_all = True
        self.stop()
        self.join(timeout=max(30.0, deadline - time.monotonic()))

    # -- subclass hooks ----------------------------------------------------

    def _acquire(self, finish_one):
        raise NotImplementedError

    def _dispatch(self, handle, X, n):
        raise NotImplementedError

    def _emit(self, out, n, first_off, decode) -> None:
        self._sink(out, n, first_off)

    def _on_idle(self) -> None:
        pass

    def _aggregate_full_batches(self, X, offsets, bs: int):
        """Opportunistic multi-chunk dispatch: when the ring is backed
        up (the first drain came back FULL), immediately drain further
        already-full batches and ship them as ONE dispatch. Each device
        dispatch pays a fixed launch cost, so K chunks per dispatch
        amortize it K-fold exactly like the scan in the hand-written
        bench loop (whether that pays on a local chip is ROADMAP D4's
        question); a lightly-loaded stream
        never aggregates (the ring holds at most one full batch), so
        the latency operating point is untouched.

        K is rounded DOWN to a power of two ≤ ``max_dispatch_chunks``:
        the Pallas scorer compiles one scan program per distinct K, and
        a drifting backlog yielding K=3,5,6,7… would pay a mid-stream
        compile for each — power-of-two K bounds that to log2(max)
        programs. Only provably-FULL extra batches are drained (a
        partial cannot be pushed back and would force a padded
        dispatch — measured 418k → 74k rec/s on the Kafka stream when
        partials rode along). Drained views alias the ring's reuse
        buffer, hence the copies."""
        avail = 1 + len(self._ring) // bs  # full batches on hand NOW
        k_cap = self._max_dispatch_chunks
        if self._batcher is not None:
            # deadline-aware aggregation cap: a backed-up ring wants the
            # biggest dispatch, the deadline wants the smallest — the
            # capacity model's max_records() is where they meet (None =
            # no deadline/no fit yet: keep the static cap)
            mr = self._batcher.max_records()
            if mr is not None:
                k_cap = min(k_cap, max(1, mr // bs))
        k_target = 1
        while k_target * 2 <= avail and k_target * 2 <= k_cap:
            k_target *= 2
        if k_target == 1:
            return X, offsets, bs
        parts = [np.array(X, copy=True)]
        # carry the REAL drained offset arrays, never a fabricated
        # np.arange: a cycling source's wrap-to-0 can land INSIDE the
        # first drained batch (the ring stitches chunks from both sides
        # of the wrap), and synthesized-contiguous offsets would mislabel
        # every record after the wrap
        off_parts = [np.array(offsets, copy=True)]
        total = bs
        while total < bs * k_target and len(self._ring) >= bs:
            X2, off2 = self._ring.drain(0, 0)
            n2 = X2.shape[0]
            if n2 == 0:
                break
            if n2 < bs or int(off2[0]) != int(off_parts[-1][-1]) + 1:
                # offset discontinuity: cycling sources legitimately
                # wrap back to 0 (steady-state benches), and aggregating
                # across the gap would break the one-dispatch ==
                # contiguous-commit-range invariant — carry the drained
                # chunk to the NEXT loop iteration as its own dispatch
                self._carry_drain.append(
                    (np.array(X2, copy=True), np.array(off2, copy=True))
                )
                break
            parts.append(np.array(X2, copy=True))
            off_parts.append(np.array(off2, copy=True))
            total += n2
        if len(parts) == 1:
            # MUST return the copies, not the drained views: X/offsets
            # alias the ring's reuse buffer, and a discontinuous extra
            # drain above just overwrote it in place — returning the
            # aliased views would ship the carried chunk's data twice
            # and lose this batch entirely
            return parts[0], off_parts[0], bs
        X = np.concatenate(parts, axis=0)
        offsets = np.concatenate(off_parts)
        return X, offsets, total

    def _wait_drain(self, ledger, deadline_us: int, idle_us: int):
        """``ring.drain(deadline_us, idle_us)`` in its two halves: the
        wait (for the first record, then for a batch, the deadline or
        close), booked as ``drain_wait``, and the copies, which wait for
        nothing where the ring holds a batch or the deadline passed. The
        batch is the one the whole drain would have given."""
        with ledger.span("drain_wait"):
            left = self._ring.wait(deadline_us, idle_us)
        if left < 0:  # closed and empty, or the idle bound expired
            return self._nothing
        return self._ring.drain(left, 0)

    def _drain_into_shuffle(
        self, sh, ledger, deadline_us: int, idle_us: int
    ) -> int:
        """The drain of a pipeline whose state table lies over a mesh:
        blocks go from the ring into the keyed shuffle
        (runtime/shuffle.py), which holds them until a dispatch takes
        them. The first drain waits (fill or deadline) only where the
        shuffle holds nothing; after it, provably full batches follow
        while the shuffle has room for one. → records drained."""
        bs, got, first = self._batch_size, 0, True
        while True:
            if first and not sh.pending:
                X, offsets = self._wait_drain(ledger, deadline_us, idle_us)
            elif sh.room >= bs and (first or len(self._ring) >= bs):
                X, offsets = self._ring.drain(0, 0)
            else:
                break
            first = False
            sh.feed(X, offsets)
            got += X.shape[0]
            if X.shape[0] < bs:
                break
        return got

    def _resolve_donate(self) -> bool:
        """Donation default: on unless the backend is CPU. Resolved
        once, lazily — backend identity needs jax initialized.

        The wire batch (uint8/uint16 [B, F]) can never output-alias the
        f32 score outputs, so XLA flags every donated compile with a
        "donated buffers were not usable" warning; the donation still
        releases the staging buffer to the device allocator at dispatch
        (bounding steady-state input allocations to the window depth)
        rather than holding it to fetch time, so it is kept — and the
        known-inert warning is silenced once, only when a pipeline
        actually donates, and only for the rank-wire uint dtypes
        (pipeline.filter_donate_warning — the fused f32 shape gets the
        same treatment there): an application's own f32 donation
        warnings (where failed aliasing IS actionable) stay visible."""
        if self._donate is None:
            from flink_jpmml_tpu.compile import common

            self._donate = not common.backend_is_cpu()
        if self._donate:
            filter_donate_warning(r"uint(8|16)\[")
        return self._donate

    def _dispatch_bound(self, bound: "BoundScorer", X, n):
        """Shared async dispatch through a :class:`BoundScorer` — the
        rank wire when eligible, the f32 path otherwise. The rank-wire
        hop runs through :func:`runtime.pipeline.dispatch_quantized`:
        host encode (the bucketizer folds NaN→missing — no separate
        host-side NaN pass, no f32 mask plane) or the fused on-device
        encode stage, per the scorer's autotuned ``encode_mode``.

        Rank-wire dispatches stage the batch onto the device explicitly
        (``jax.device_put``, async) and donate the staging buffer to
        the jitted call: the buffer is released to the device allocator
        at dispatch instead of being pinned until fetch, so with the
        depth-2 in-flight window steady-state input allocations stay
        bounded at two staging buffers. ``donation_hits`` counts
        dispatches whose staging buffer was actually consumed
        (invalidated) by the call — 0 on backends that ignore
        donation. ``encode_s``/``h2d_bytes`` accounting lands in this
        pipeline's metrics registry."""
        if bound.q is not None:
            # keyed state arms here — and ONLY here: recovery ladders
            # and bisection probes raise _state_bypass, so re-scored
            # records can never fold into the table twice
            st = (
                self._state
                if self._state is not None and not self._state_bypass
                else None
            )
            return dispatch_quantized(
                bound.q, X,
                donate=self._resolve_donate(),
                metrics=self.metrics,
                donation_hits=self._donation_hits,
                state=st,
                # the state stage's decay clock + replay guard, and the
                # dispatch's first_off on its encode/route/h2d spans
                offsets=self._cur_offsets,
                plan=self._cur_plan if st is not None else None,
            )
        if self._state is not None and not self._state_bypass:
            raise InputValidationException(
                "stateful scoring requires the rank-wire scorer "
                "(f32 fallback dispatch cannot carry the state stage)"
            )
        return self._score_f32(bound.model, X, n)

    def _score_f32(self, model, X, n):
        """Shared f32 fallback dispatch: NaN cells are the missing
        convention on this path; one isnan pass builds the mask (any()
        on bools is cheap), not a scan-then-rescan."""
        B = model.batch_size
        # a mesh-sharded model's data axis must divide the dispatch: a
        # degraded-mesh rebuild can leave a divisor that no longer
        # divides B (or an aggregated multiple of B), so the pad target
        # rounds up to the divisor — single-chip models (divisor 1)
        # keep the exact historical pad-to-B geometry
        target = max(B, n)
        target += (-target) % getattr(model, "batch_divisor", 1)
        Mb = np.isnan(X)
        if Mb.any():
            Xb = np.where(Mb, 0.0, X).astype(np.float32)
        else:
            Xb, Mb = X, _ZEROS_M.get(n, self._arity)
        if n < target:
            Xb, Mb, _ = prepare.pad_batch(Xb, Mb, target)
        if Xb is X:
            # a full, NaN-free batch reaches here still aliasing the
            # ring's reuse buffer; jax's CPU backend can zero-copy that
            # numpy array into the async dispatch, so the next drain
            # would overwrite an in-flight batch — ship a private copy
            # (cf. pipeline.dispatch_quantized's fused branch)
            Xb = np.array(Xb, copy=True)
        return model.predict(Xb, Mb)  # async dispatch

    # -- poison isolation (runtime/dlq.py) ---------------------------------

    def _dispatch_checked(self, handle, X, n, offsets, plan=None):
        """The one dispatch entry carrying the batch's offsets past the
        fault harness: ``poison_record`` / offset-targeted
        ``worker_crash`` faults match against exactly the range being
        scored, so bisection isolates an injected poison the same way
        it isolates a real one. ``plan`` is the dispatch's shard plan
        where the keyed shuffle made one."""
        faults.fire("score_batch", offsets=offsets)
        self._cur_offsets = offsets  # read by _dispatch_bound
        self._cur_plan = plan
        return self._dispatch(handle, X, n)

    def _on_dispatch_error(self, out, meta, error) -> bool:
        """OverlappedDispatcher error hook, with device-fault triage
        FIRST (runtime/devfault.py): a sick device runs the recovery
        ladder (redispatch → OOM bisection → fallback tier) and record
        poison enters suspect mode — the PR 12 bisection must never
        quarantine clean records for a device fault. → False (re-raise)
        when the entry carries no retained batch (shed no-ops) or the
        matching plane isn't wired."""
        if meta is None or len(meta) < 7:
            return False
        n, first_off, t_start, shed, handle, X, offsets = meta[:7]
        if shed or X is None or offsets is None:
            return False
        ctx = meta[7] if len(meta) > 7 else None
        if self._state is not None and not self._state_bypass:
            # the failed dispatch donated (and thereby poisoned) the
            # state buffer and may have chained later in-flight batches
            # on it: restore the last snapshot before ANY recovery
            # re-dispatch. Bounded, counted loss (state_rollbacks);
            # the recovery paths below score statelessly.
            self._state.rollback()
        kind = devfault.classify(error)
        if kind is not None:
            if self._failover is None:
                return False  # historical fail-fast: die, restart
            self._device_recover(handle, X, offsets, error, kind, ctx=ctx)
            return True
        if self._dlq is None:
            return False
        self._suspect_scan(handle, X, offsets, error=error, ctx=ctx)
        return True

    # -- device-fault recovery ladder (runtime/devfault.py) ----------------

    def _redispatch_sync(self, handle, X, n, offsets):
        """One synchronous re-dispatch of a host-retained staging copy
        through the REAL dispatch path (fault hook sites included, so
        an injected persistent fault keeps failing here exactly like a
        real one) → (out, decode), device-synchronized."""
        faults.fire("device_dispatch")
        self._state_bypass += 1  # recovery re-scores: never re-fold state
        try:
            out, decode = self._dispatch_checked(handle, X, n, offsets)
        finally:
            self._state_bypass -= 1
        faults.fire("device_readback")
        _block_ready(out)
        return out, decode

    def set_tenant(self, tenant) -> None:
        """Re-label delivered records (the dynamic block pipeline calls
        this on a served-model swap so tenant_records follows the key
        actually serving)."""
        self._tenant = tenant

    def _book_tenant(self, n: int) -> None:
        if self._tenant is not None:
            self.metrics.counter(
                f'tenant_records{{model="{self._tenant}"}}'
            ).inc(n)

    def _emit_recovered(self, out, decode, offsets, lo, hi,
                        ctx=None, t0=None) -> None:
        """Deliver + commit one recovered run (redispatch, OOM
        sub-batch, or fallback-tier score): sink in offset order,
        freshness stamps consumed, offsets committed — idempotent with
        the sink contract because the failed dispatch never reached
        ``_complete`` (zero loss, no duplication beyond restart
        replay)."""
        n_run = hi - lo
        first = int(offsets[lo])
        self._emit(out, n_run, first, decode)
        self.metrics.counter("records_out").inc(n_run)
        self._book_tenant(n_run)
        freshness = fresh_mod.freshness_for(self.metrics)
        if freshness is not None:
            freshness.observe_sink(first, n_run)
        jstore = trace_mod.store_for(self.metrics)
        if jstore is not None:
            c = ctx if ctx is not None else trace_mod.context_for(first)
            jstore.hop(
                "sink", c.child(), first, n_run, durable=True,
                recovered=True,
            )
        if t0 is not None:
            # fallback/recovered batches are real deliveries: their
            # latency belongs in the histogram the SLO plane watches —
            # a degraded tier must not flatter p99
            self.metrics.histogram("batch_latency_s").observe(
                time.monotonic() - t0
            )
        self.committed_offset = int(offsets[hi - 1]) + 1
        self._ckpt.maybe_save(self._ckpt_state)

    def _device_recover(self, handle, X, offsets, error, kind,
                        ctx=None) -> None:
        """The recovery ladder for one device-classified dispatch
        failure: (1) transient errors re-dispatch the retained batch
        under the shared full-jitter backoff; (2) OOM bisects the
        BATCH SIZE (never the records) and feeds the proven cap back
        into the AdaptiveBatcher; (3) exhausted retries fall through
        to the fallback tier (the circuit breaker keeps later batches
        off the device entirely); (4) chip loss escalates to the
        supervisor (restart with FJT_RESTART_STREAK context)."""
        from flink_jpmml_tpu.utils.retry import Backoff

        plane = self._failover
        n = int(X.shape[0])
        first = int(offsets[0])
        key = getattr(handle, "key", None) or "default"
        plane.note_fault(kind, key, first_off=first, n=n, error=error)
        if kind == devfault.KIND_LOST:
            self._lost_recover(handle, X, offsets, error, ctx=ctx)
            return
        breaker = plane.breaker_for(key)
        breaker.record_failure(kind)
        if kind == devfault.KIND_OOM:
            self._oom_recover(handle, X, offsets, error, ctx=ctx)
            return
        bo = Backoff(
            "device", base_s=0.02, cap_s=0.5,
            max_attempts=plane.retries,
        )
        while not bo.exhausted:
            bo.sleep()
            try:
                out, decode = self._redispatch_sync(
                    handle, X, n, offsets
                )
            except Exception as e2:
                k2 = devfault.classify(e2)
                if k2 is None:
                    # the device fault cleared and a RECORD error
                    # surfaced underneath: that is poison's jurisdiction
                    if self._dlq is not None:
                        self._suspect_scan(
                            handle, X, offsets, error=e2, ctx=ctx
                        )
                        return
                    raise
                plane.note_fault(k2, key, first_off=first, n=n, error=e2)
                if k2 == devfault.KIND_LOST:
                    self._lost_recover(handle, X, offsets, e2, ctx=ctx)
                    return
                breaker.record_failure(k2)
                if k2 == devfault.KIND_OOM:
                    self._oom_recover(handle, X, offsets, e2, ctx=ctx)
                    return
                error = e2
                continue
            breaker.record_success()
            plane.redispatch_records.inc(n)
            flight.record(
                "device_redispatch", model=key, first=first, n=n,
                attempts=bo.attempts,
            )
            self._emit_recovered(out, decode, offsets, 0, n, ctx=ctx)
            return
        # retries exhausted: degraded-mode serving beats a crash loop
        if plane.tier.supports(handle):
            self._serve_fallback(handle, X, offsets, jctx=ctx)
            return
        raise error

    def _lost_recover(self, handle, X, offsets, error, ctx=None) -> None:
        """The KIND_LOST rung of the ladder, mesh-aware: a sharded
        model rebuilds over the surviving chips in place
        (``ShardedModel.without_devices`` — dispatcher state and the
        partition/key assignment carry through) and the retained batch
        redispatches synchronously on the degraded mesh: zero loss,
        (N−1)/N capacity, no process restart. A single-chip model (or
        an unsurvivable mesh) keeps the historical contract — escalate
        to the supervisor via the raise."""
        plane = self._failover
        n = int(X.shape[0])
        first = int(offsets[0])
        key = getattr(handle, "key", None) or "default"
        rebuilt = self._mesh_rebuild(handle, error)
        if rebuilt is None:
            flight.record(
                "device_lost_escalate", model=key, first=first, n=n,
                error=repr(error),
            )
            raise error
        try:
            out, decode = self._redispatch_sync(handle, X, n, offsets)
        except Exception as e2:
            k2 = devfault.classify(e2)
            if k2 is None:
                # the chip loss cleared and a RECORD error surfaced
                # underneath: poison's jurisdiction
                if self._dlq is not None:
                    self._suspect_scan(
                        handle, X, offsets, error=e2, ctx=ctx
                    )
                    return
                raise
            # the degraded mesh is live but THIS dispatch failed again:
            # re-enter the ladder from the top (another KIND_LOST
            # shrinks once more — bounded, without_devices raises once
            # no full data row survives)
            self._device_recover(handle, X, offsets, e2, k2, ctx=ctx)
            return
        plane.redispatch_records.inc(n)
        flight.record(
            "mesh_rebuild_redispatch", model=key, first=first, n=n,
            data=rebuilt.batch_divisor,
        )
        self._emit_recovered(out, decode, offsets, 0, n, ctx=ctx)

    def _mesh_rebuild(self, handle, error):
        """Chip loss on a mesh-sharded model: rebuild over the
        survivors and adopt the rebuilt model into the live scoring
        handle → the rebuilt :class:`ShardedModel`, or None when there
        is no mesh to shrink (single-chip model, one data row left) or
        no survivable rebuild."""
        model = getattr(handle, "model", None)
        if not hasattr(model, "without_devices"):
            return None
        lost = self._lost_devices(model, error)
        if not lost:
            return None
        try:
            rebuilt = model.without_devices(lost)
        except FlinkJpmmlTpuError:
            return None  # unsurvivable: escalate like a single chip
        self._adopt_rebuilt(handle, rebuilt)
        self.metrics.counter("mesh_rebuilds").inc()
        self.metrics.gauge("mesh_lost_devices").set(float(len(lost)))
        if self._mesh_obs is not None:
            self._mesh_obs.note_rebuild(rebuilt, lost)
        flight.record(
            "mesh_rebuild",
            lost=[str(getattr(d, "id", d)) for d in lost],
            data=rebuilt.batch_divisor,
        )
        return rebuilt

    def _lost_devices(self, model, error) -> list:
        """Which device(s) died. The runtime rarely names the chip in
        the raised error (XLA's loss surfaces as a bare UNAVAILABLE),
        so: an explicit ``error.devices``/``error.device`` attribute
        wins; otherwise the LAST data row of the mesh is retired —
        retiring any one full row restores (N−1)/N capacity with the
        model axis intact, and last-row is the choice every process
        derives identically with no coordination (row identity — the
        first device of each surviving row — is what the carried
        ChipAssignment's rendezvous weights key on, so survivor rows
        keep their partitions and keys)."""
        dev = getattr(error, "devices", None)
        if dev is None:
            dev = getattr(error, "device", None)
        if dev is not None:
            if isinstance(dev, (list, tuple, set, frozenset)):
                return list(dev)
            return [dev]
        mesh = getattr(model, "mesh", None)
        if mesh is None:
            return []
        from flink_jpmml_tpu.parallel.mesh import DATA_AXIS

        rows = mesh.devices.reshape(mesh.shape[DATA_AXIS], -1)
        if rows.shape[0] <= 1:
            return []  # one data row left: nothing to shrink onto
        return list(rows[-1])

    def _adopt_rebuilt(self, handle, rebuilt) -> None:
        """Swap the rebuilt model into the live scoring handle (the
        BoundScorer's decode closure follows ``handle.model``, so the
        sink path needs no rebind)."""
        handle.model = rebuilt
        if getattr(handle, "q", None) is not None:
            # the rank-wire scorer spans the mesh it was built on
            handle.q = rebuilt.quantized_scorer()
        if self._state is not None:
            # chip loss moves state WITH its keys: slot = hash %
            # capacity is mesh-independent, so re-placing the value
            # buffer over the survivors preserves every key's state
            mesh = getattr(rebuilt, "mesh", None)
            if mesh is not None:
                self._state.migrate(mesh)

    def _oom_recover(self, handle, X, offsets, error, ctx=None) -> None:
        """Device-OOM ladder step: bisect the BATCH SIZE until runs
        fit, deliver each run in offset order, and feed the largest
        proven size into the AdaptiveBatcher as the standing dispatch
        cap. Records are never quarantined — an allocator refusal says
        nothing about the data."""
        plane = self._failover
        n = int(X.shape[0])
        key = getattr(handle, "key", None) or "default"
        state = {"max_ok": 0}

        def attempt(lo: int, hi: int) -> None:
            size = hi - lo
            try:
                out, decode = self._redispatch_sync(
                    handle, X[lo:hi], size, offsets[lo:hi]
                )
            except Exception as e2:
                k2 = devfault.classify(e2)
                if k2 is None:
                    if self._dlq is not None:
                        self._suspect_scan(
                            handle, X[lo:hi], offsets[lo:hi],
                            error=e2, ctx=ctx,
                        )
                        return
                    raise
                plane.note_fault(
                    k2, key, first_off=int(offsets[lo]), n=size,
                    error=e2,
                )
                if k2 == devfault.KIND_LOST:
                    self._lost_recover(
                        handle, X[lo:hi], offsets[lo:hi], e2, ctx=ctx
                    )
                    return
                plane.breaker_for(key).record_failure(k2)
                if size == 1:
                    # one record alone exceeds the device: the host
                    # tier serves it (or the worker escalates) — a
                    # sick device never quarantines a clean record
                    if plane.tier.supports(handle):
                        self._serve_fallback(
                            handle, X[lo:hi], offsets[lo:hi], jctx=ctx
                        )
                        return
                    raise e2
                mid = (lo + hi) // 2
                attempt(lo, mid)
                attempt(mid, hi)
                return
            state["max_ok"] = max(state["max_ok"], size)
            plane.redispatch_records.inc(size)
            self._emit_recovered(
                out, decode, offsets, lo, hi, ctx=ctx
            )

        attempt(0, n)
        plane.oom_shrinks.inc()
        cap = state["max_ok"] or None
        if cap and self._batcher is not None:
            cap = self._batcher.note_oom_cap(cap)
        flight.record(
            "oom_batch_shrink", model=key, from_records=n,
            to_records=cap,
        )
        plane.record_success(key)

    def _fallback_dispatch(self, handle, X, n):
        """Host-tier scoring hook → (out, decode) in the subclass's
        sink shape (the static path's sink takes no decode)."""
        return self._failover.tier.score_bound(handle, X), None

    def _fallback_checked(self, handle, X, n, offsets):
        """The fallback tier's ``_dispatch_checked`` twin: still a
        real scoring site, so record-targeted faults (and real record
        poison) strike it exactly like the device path."""
        faults.fire("score_batch", offsets=offsets)
        return self._fallback_dispatch(handle, X, n)

    def _serve_fallback(self, handle, X, offsets, jctx=None) -> None:
        """Score one batch on the host fallback tier — the pipeline
        keeps serving degraded instead of crash-looping while the
        circuit is open (or the ladder exhausted its retries). Record
        poison that surfaces HERE isolates on the tier that hit it
        (the suspect scan's sub-dispatches route through the fallback
        twin) — an open circuit must not exempt poison from the DLQ
        contract, nor isolation re-dispatch to the sick device."""
        plane = self._failover
        n = int(X.shape[0])
        first = int(offsets[0])
        key = getattr(handle, "key", None) or "default"
        freshness = fresh_mod.freshness_for(self.metrics)
        if freshness is not None:
            # the fallback tier IS the dispatch stage while degraded
            freshness.propagate_low_watermark("dispatch", first, n)
        t0 = time.monotonic()
        try:
            out, decode = self._fallback_checked(handle, X, n, offsets)
        except Exception as e:
            if devfault.classify(e) is not None or self._dlq is None:
                raise
            self._suspect_scan(
                handle, X, offsets, error=e, ctx=jctx,
                dispatch=self._fallback_checked,
            )
            return
        plane.note_fallback(n, key)
        self._emit_recovered(
            out, decode, offsets, 0, n, ctx=jctx, t0=t0
        )

    def _suspect_scan(
        self, handle, X, offsets, error, persist: bool = False,
        ctx=None, dispatch=None,
    ) -> None:
        """Bisection ("suspect mode") over one failed batch: dispatch
        halves synchronously until the offending record(s) are single —
        those go to the DLQ (never the sink); every clean run proceeds
        to the sink in offset order. The whole range then commits, so a
        restart never replays the quarantined record back to life.

        ``persist=True`` (crash-loop fingerprint mode) additionally
        writes the suspect MARKER before every sub-dispatch: a record
        that kills the process outright narrows the marker by one
        bisection level per incarnation, and a single-record marker is
        quarantined WITHOUT being dispatched at all.

        More than ``FJT_DLQ_MAX_PER_BATCH`` quarantines in one batch
        aborts isolation (:class:`PoisonIsolationOverflow`): that is a
        model-level failure, not poison.

        ``dispatch`` overrides the sub-dispatch primitive (default:
        the device path's ``_dispatch_checked``) — the fallback tier
        passes its host-tier twin so poison that surfaces while the
        circuit is OPEN isolates on the tier that hit it, never by
        re-dispatching to the sick device."""
        dispatch = dispatch if dispatch is not None else (
            self._dispatch_checked
        )
        if self._state is not None:
            # bisection probes score records MORE THAN ONCE (and DLQ'd
            # records must never land at all): every sub-dispatch of
            # the scan runs with the state stage disarmed
            inner_dispatch = dispatch

            def dispatch(h, Xs, ns, os_, _inner=inner_dispatch):
                self._state_bypass += 1
                try:
                    return _inner(h, Xs, ns, os_)
                finally:
                    self._state_bypass -= 1
        n = int(X.shape[0])
        if n == 0:
            return
        freshness = fresh_mod.freshness_for(self.metrics)
        records_out = self.metrics.counter("records_out")
        cap = env_count("FJT_DLQ_MAX_PER_BATCH", 32)
        state = {"q": 0}
        # journey trail (obs/trace.py): isolation is exactly the story
        # fjt-trace exists to tell, so every bisection hop is durable
        jstore = trace_mod.store_for(self.metrics)
        if ctx is None and jstore is not None:
            ctx = trace_mod.context_for(int(offsets[0]))
        if jstore is not None:
            jstore.hop(
                "suspect_scan", ctx, int(offsets[0]), n, durable=True,
                persist=persist,
                error=None if error is None else repr(error),
            )
        flight.record(
            "poison_isolation",
            first=int(offsets[0]), n=n, persist=persist,
            error=None if error is None else repr(error),
            trace_id=None if ctx is None else ctx.trace_id,
        )
        self._suspect_gauge.set(1.0)

        def quarantine(i: int, exc, reason=REASON_SCORE, attempts=1):
            if state["q"] >= cap:
                raise PoisonIsolationOverflow(
                    state["q"], exc if exc is not None else error
                )
            state["q"] += 1
            off = int(offsets[i])
            # the terminal hop + the envelope's trace context: the ids
            # the DLQ carries are what fjt-dlq redrive stamps into the
            # traceparent header, linking the redriven journey segment
            rctx = trace_mod.TraceContext(
                trace_mod.trace_id_for(off),
                parent_id=None if ctx is None else ctx.span_id,
            )
            if jstore is not None:
                jstore.terminal(
                    "dlq", rctx, offset=off, reason=reason,
                    attempts=attempts,
                )
            self._dlq.quarantine(
                X[i].tobytes(), offset=off, reason=reason, error=exc,
                attempts=attempts, model=getattr(handle, "key", None),
                trace_id=rctx.trace_id, span_id=rctx.span_id,
            )
            if freshness is not None:
                # a quarantined record was DROPPED, not delivered: its
                # ingest stamp must not advance the sink watermark or
                # the staleness books (the PR 8 shed contract)
                freshness.discard_stamps(off, 1)

        def emit_run(out, decode, lo: int, hi: int):
            n_run = hi - lo
            first = int(offsets[lo])
            self._emit(out, n_run, first, decode)
            records_out.inc(n_run)
            self._book_tenant(n_run)
            if jstore is not None:
                jstore.hop(
                    "sink", ctx.child(), first, n_run, durable=True,
                    isolated=True,
                )
            if freshness is not None:
                freshness.observe_sink(first, n_run)

        def scan(lo: int, hi: int):
            if hi <= lo:
                return
            n_sub = hi - lo
            off_lo, off_hi = int(offsets[lo]), int(offsets[hi - 1]) + 1
            dm = self._death_marker if persist else None
            if dm is not None and off_lo <= dm["lo"] and dm["hi"] <= off_hi:
                # a previous incarnation DIED dispatching dm's range
                if dm["hi"] - dm["lo"] == 1:
                    hit = np.nonzero(
                        offsets[lo:hi] == np.uint64(dm["lo"])
                    )[0]
                    if hit.size:
                        i = lo + int(hit[0])
                        scan(lo, i)
                        quarantine(
                            i, None, reason=REASON_CRASH_LOOP,
                            attempts=dm.get("attempts", 1),
                        )
                        self._death_marker = None
                        self._fingerprint.clear_marker()
                        scan(i + 1, hi)
                        return
                elif n_sub > 1:
                    # never re-dispatch a span that already killed a
                    # process whole: split first (one narrowing per
                    # death bounds convergence at log2(batch) restarts)
                    mid = (lo + hi) // 2
                    scan(lo, mid)
                    scan(mid, hi)
                    return
            if persist and self._fingerprint is not None:
                attempts = 1
                if (
                    dm is not None
                    and dm["lo"] == off_lo and dm["hi"] == off_hi
                ):
                    attempts = dm.get("attempts", 1) + 1
                self._fingerprint.write_marker(off_lo, off_hi, attempts)
                if jstore is not None:
                    # the marker's journey twin, written BEFORE the
                    # sub-dispatch: if this range kills the process the
                    # hop survives — "the dispatch that died" stays
                    # visible across the incarnation boundary
                    jstore.hop(
                        "suspect_dispatch", ctx.child(),
                        off_lo, off_hi - off_lo, durable=True,
                        attempts=attempts,
                    )
            try:
                out, decode = dispatch(
                    handle, X[lo:hi], n_sub, offsets[lo:hi]
                )
                _block_ready(out)
            except PoisonIsolationOverflow:
                raise
            except Exception as e:
                if devfault.classify(e) is not None:
                    # a SICK DEVICE mid-bisection is not record
                    # poison: quarantining clean records for it is the
                    # one thing this scan must never do — escalate
                    # (already-emitted runs replay on restore, the
                    # at-least-once contract)
                    raise
                if n_sub == 1:
                    quarantine(lo, e)
                    return
                mid = (lo + hi) // 2
                scan(lo, mid)
                scan(mid, hi)
                return
            emit_run(out, decode, lo, hi)

        try:
            scan(0, n)
        finally:
            self._suspect_gauge.set(
                1.0 if self._suspect_until is not None else 0.0
            )
        if persist and self._fingerprint is not None:
            self._fingerprint.clear_marker()
            self._death_marker = None
        # the WHOLE range commits — quarantined offsets included, so a
        # restart cannot replay a parked poison record back to life
        self.committed_offset = int(offsets[-1]) + 1
        if state["q"]:
            flight.record(
                "poison_isolated", quarantined=state["q"],
                first=int(offsets[0]), n=n,
            )
        self._ckpt.maybe_save(self._ckpt_state)

    def _exit_suspect_mode(self) -> None:
        flight.record(
            "poison_suspect_exit", committed=self.committed_offset
        )
        self._suspect_until = None
        self._death_marker = None
        if self._fingerprint is not None:
            self._fingerprint.clear_marker()
        self._suspect_gauge.set(0.0)
        jstore = trace_mod.store_for(self.metrics)
        if jstore is not None:
            jstore.hop(
                "suspect_exit",
                trace_mod.context_for(self.committed_offset),
                first_off=self.committed_offset, durable=True,
            )
            # back to tail-sampled buffering — unless a fault drill (or
            # FJT_JOURNEY_SYNC) armed write-through for the process
            jstore.write_through = bool(
                faults.active() or os.environ.get("FJT_JOURNEY_SYNC")
            )

    # -- internals ---------------------------------------------------------

    def _ingest(self) -> None:
        records_in = self.metrics.counter("records_in")
        try:
            while not self._stop.is_set():
                polled = self._source.poll()
                if polled is None:
                    if self._source.exhausted:
                        return
                    time.sleep(0.0005)
                    continue
                off, block = polled
                pushed = 0
                while pushed < block.shape[0] and not self._stop.is_set():
                    pushed += self._ring.push_block(
                        block[pushed:], off + pushed, timeout_us=100_000
                    )
                records_in.inc(block.shape[0])
        except BaseException as e:
            self._error = e
            self._stop.set()

    def _score(self) -> None:
        batch_cfg = self._config.batch
        records_out = self.metrics.counter("records_out")
        # what each completed dispatch brings back for the sink: the
        # scorer's whole output (a classifier's triple, a regression's
        # vector), not the state's derived rows
        readback_bytes = self.metrics.counter("readback_bytes")
        batches = self.metrics.counter("batches")
        fill = self.metrics.counter("batch_fill_records")
        # fixed-bucket histogram, not a reservoir: N workers' bucket
        # counts ADD, so the supervisor's fleet /metrics view can merge
        # per-worker latency distributions exactly (utils/metrics.py)
        lat = self.metrics.histogram("batch_latency_s")

        ledger = attr_mod.ledger_for(self.metrics)
        # the freshness plane (event-time watermarks + staleness) and
        # the composite backpressure score: both per-registry singletons
        # shared with the source (which stamps event times at fetch)
        # and ticked from this loop — the SLOTracker piggyback pattern,
        # no thread of their own
        freshness = fresh_mod.freshness_for(self.metrics)
        monitor = pressure_mod.pressure_for(self.metrics)
        # the data-drift plane (obs/drift.py): None unless
        # FJT_DRIFT_SAMPLE is set or a bench mode armed it — predictions
        # are sketched at the sink, features already rode
        # dispatch_quantized; its monitor ticks from these record calls
        dplane = drift_mod.plane_for(self.metrics)
        # record-journey tracing (obs/trace.py): None unless
        # FJT_JOURNEY_DIR armed it — one env check at loop start, and
        # with it None every per-batch site below is a None test
        jstore = trace_mod.store_for(self.metrics)
        ring_occ = self.metrics.gauge("ring_occupancy")
        ring_cap = float(max(self._config.batch.queue_capacity, 1))

        replayed = self.metrics.counter("records_replayed")

        def _complete(pair, meta):
            """FIFO completion off the dispatcher: sink, then commit —
            offsets only advance past records that reached the sink.
            A SHED entry (admission refusal, a no-op through the same
            FIFO window) commits its offsets and consumes its freshness
            stamps without ever touching the sink — the drop is
            explicit, bounded, and replay-consistent."""
            n, first_off, t_start, shed = meta[:4]
            jctx = meta[7] if len(meta) > 7 else None
            if first_off < self._replay_until:
                # at-least-once replay accounting: records below the
                # previous incarnation's in-flight high-water mark are
                # re-deliveries, not new progress
                replayed.inc(min(n, self._replay_until - first_off))
            if shed:
                self.committed_offset = first_off + n
                if freshness is not None:
                    freshness.discard_stamps(first_off, n)
                with ledger.span("commit", first_off=first_off, n=n):
                    self._ckpt.maybe_save(self._ckpt_state)
                if monitor is not None:
                    monitor.maybe_tick()
                return
            out, decode = pair
            derived = None
            if self._state is not None:
                # a state-armed dispatch returns (score_out, derived):
                # the sink sees exactly the stateless output shape,
                # and the derived session features feed the drift
                # plane under the model's "#state" label (state
                # corruption surfaces as feature drift)
                out, derived = state_mod.split_output(out)
            readback_bytes.inc(sum(
                int(getattr(a, "nbytes", 0))
                for a in (out if isinstance(out, tuple) else (out,))
            ))
            plan = meta[8] if len(meta) > 8 else None
            if plan is not None:
                # a mesh dispatch comes back sorted by owning chip:
                # back into offset order, on the host, before the sink
                with ledger.span("unshard", first_off=first_off, n=n):
                    out = plan.unshard(out)
                    if dplane is not None and derived is not None:
                        derived = plan.unshard(derived)
            # the completing batch's OWN context wraps the sink: its
            # span (and any exemplar the sink stage captures) must
            # carry THIS journey's ids, not whichever batch the score
            # loop happens to be launching right now
            with trace_mod.use(jctx):
                with ledger.span("sink", first_off=first_off, n=n) as sp:
                    self._emit(out, n, first_off, decode)
            t_done = sp.t0 + sp.seconds
            # the delivery's telemetry, one interval: the sink's
            # sketches, latency and tenant books, the per-chip tally,
            # the breaker, the batcher's fit, the watermark, and the
            # SLO and pressure ticks (before the checkpoint tick, which
            # reads none of them)
            tel = ledger.begin("telemetry", first_off=first_off, n=n)
            if dplane is not None:
                # score-distribution sketch at the sink (sampled): shed
                # batches never reach here, so a shed record can no
                # more skew the prediction baseline than the watermark
                dplane.record_predictions(
                    getattr(decode, "model_hash", None)
                    or getattr(decode, "model_key", None),
                    out, n,
                )
                if derived is not None:
                    state_mod.record_derived(
                        dplane, self._state,
                        getattr(
                            getattr(meta[4], "q", None)
                            if len(meta) > 4 else None,
                            "model_hash", None,
                        ),
                        derived, n,
                    )
            if jstore is not None and jctx is not None:
                # the sink hop closes the journey: tail-sampling keeps
                # it only if it is interesting (exemplar-marked, head
                # sample, terminal elsewhere)
                jstore.finish(
                    jctx, first_off, n, latency_s=t_done - t_start,
                )
            lat.observe(t_done - t_start)
            records_out.inc(n)
            self._book_tenant(n)
            if self._mesh_obs is not None:
                # per-chip accounting (obs/mesh.py): one call per BATCH
                # — a data-parallel dispatch spans every chip equally,
                # so the split is arithmetic, not a per-record loop
                if plan is not None:
                    self._mesh_obs.note_folded(plan.counts, len(disp))
                else:
                    self._mesh_obs.note_batch(n, len(disp))
            if self._failover is not None:
                # green completion: clears strike streaks / counts a
                # half-open probe (a dict miss while no breaker exists)
                self._failover.record_success(
                    getattr(meta[4], "key", None) if len(meta) > 4
                    else None
                )
            if self._batcher is not None:
                # the capacity model's verify half: every completed
                # dispatch is a (size, latency) observation
                self._batcher.observe(n, t_done - t_start)
            self.committed_offset = first_off + n
            if freshness is not None:
                # consume the source's ingest stamps for this offset
                # range: record_staleness_s books + the sink-stage
                # watermark (watermark_ts) advance here, after delivery
                freshness.observe_sink(first_off, n)
            if self._slo is not None:
                self._slo.maybe_tick()
            if monitor is not None:
                monitor.maybe_tick()
            tel.end()
            with ledger.span("commit", first_off=first_off, n=n):
                self._ckpt.maybe_save(self._ckpt_state)

        # the overlapped in-flight window: batch N executes on device
        # while batch N+1 is drained, encoded, and staged here — the
        # window only ever blocks on its own oldest dispatch, so the
        # ring's fill-or-deadline semantics are untouched. in_flight=1
        # keeps its historical meaning (finish every batch before the
        # next drain — the latency operating point) via depth 0.
        disp = OverlappedDispatcher(
            depth=self._in_flight_max if self._in_flight_max > 1 else 0,
            metrics=self.metrics,
            complete=_complete,
            # record-level poison isolation: a scoring exception runs
            # the suspect-mode bisection instead of killing the worker
            # (only when a DLQ is wired — without one the historical
            # fail-fast behavior is unchanged)
            on_error=self._on_dispatch_error,
        )

        # the iteration that builds and launches a dispatch, from its
        # drain to the launch's return, on the profiler's clock alone
        # (no stage: it holds stages), so a chip waiting on the host
        # building the next dispatch is named for that work; closed at
        # the next loop head, whichever way the iteration ended
        building = None
        try:
            while True:
                if building is not None:
                    building.__exit__(None, None, None)
                    building = None
                if self._stop.is_set() and not self._drain_all:
                    break  # stop(): skip the uncommitted backlog
                # worker-wedge injection point (runtime/faults.py): a
                # global load + None check when no faults are configured
                faults.fire("score_loop")
                # with work in flight the first-record wait must be
                # bounded: an indefinitely-blocked drain on a paused
                # feed would pin completed batches uncommitted (and
                # their offsets unsaved) until new data arrives
                idle_us = (
                    min(batch_cfg.deadline_us, 20_000)
                    if len(disp) and self._IDLE_WAIT_US < 0
                    else self._IDLE_WAIT_US
                )
                if monitor is not None:
                    # pre-drain occupancy peak-hold: the saturation
                    # signal a post-drain gauge read undersamples when
                    # one aggregated drain empties half the ring
                    with ledger.span("telemetry"):
                        monitor.note_ring(
                            min(len(self._ring) / ring_cap, 1.0)
                        )
                building = attr_mod.trace_only("dispatch")
                building.__enter__()
                # drain ends before the batch's offsets are known: its
                # span carries n alone
                sh, plan = self._shuffle, None
                with ledger.span("drain") as sp:
                    if sh is not None:
                        n = self._drain_into_shuffle(
                            sh, ledger, batch_cfg.deadline_us, idle_us
                        )
                    elif self._carry_drain:
                        X, offsets = self._carry_drain.pop(0)
                    else:
                        X, offsets = self._wait_drain(
                            ledger, batch_cfg.deadline_us, idle_us
                        )
                    if sh is None:
                        n = X.shape[0]
                    # ring fill fraction AFTER the drain: the
                    # producer-side saturation input to the pressure
                    # score (1.0 = the ingest thread is blocked pushing)
                    ring_occ.set(min(len(self._ring) / ring_cap, 1.0))
                    if (
                        sh is None
                        and n == self._batch_size  # drain limit = model batch
                        and self._max_dispatch_chunks > 1
                    ):
                        X, offsets, n = self._aggregate_full_batches(
                            X, offsets, self._batch_size
                        )
                    sp.note(n=n)
                if sh is not None:
                    # route what was drained, and take the longest
                    # prefix of what is held that the buckets can take
                    X, offsets, n, plan = sh.take(ledger)
                if n == 0:
                    if self._ring.closed:
                        break
                    # idle stream: the in-flight window would otherwise
                    # hold completed batches uncommitted until NEW data
                    # arrives — unbounded tail latency (and a stuck
                    # committed_offset) on a paused feed. Flush it.
                    disp.flush()
                    self._on_idle()
                    continue
                if self._dlq is not None and n > 1:
                    # the delivery-correctness plane needs exact
                    # (first_off, n) sink labeling and commits, but a
                    # decode-quarantined record leaves an offset GAP
                    # that the ring can stitch into one drained batch
                    # (run tail + next run): split at the first break
                    # and carry the remainder as its own dispatch
                    brk = np.nonzero(
                        np.diff(offsets.astype(np.int64)) != 1
                    )[0]
                    if brk.size:
                        cut = int(brk[0]) + 1
                        self._carry_drain.insert(0, (
                            np.array(X[cut:], copy=True),
                            np.array(offsets[cut:], copy=True),
                        ))
                        X, offsets = X[:cut], offsets[:cut]
                        n = cut
                building.set_metadata(first_off=int(offsets[0]), n=n)
                if self._admission is not None:
                    self._admission.maybe_tick()
                    if not self._admission.admit(self._shed_lane, n):
                        # explicit load shed: the batch rides the FIFO
                        # window as a no-op entry, so its offsets still
                        # commit strictly in launch order behind the
                        # in-flight dispatches — the sink never sees it
                        # and a restore replays nothing extra; the
                        # entry is UNACCOUNTED (no device work — it
                        # must not dilute the dispatch counters the
                        # pressure score divides by)
                        if sh is not None:
                            sh.abandon(plan)  # routed, never folded
                        if jstore is not None and n:
                            # the shed decision IS the journey's point:
                            # terminal hop, always kept
                            jstore.terminal(
                                "shed",
                                trace_mod.context_for(int(offsets[0])),
                                int(offsets[0]), n,
                                lane=self._shed_lane,
                            )
                        disp.launch(
                            lambda: None,
                            meta=(
                                n, int(offsets[0]) if n else 0,
                                time.monotonic(), True, None, None, None,
                            ),
                            accounted=False,
                            ident={
                                "first_off": int(offsets[0]) if n else 0,
                                "n": n,
                            },
                        )
                        continue
                handle = self._acquire(disp.finish_oldest)
                if handle is None:
                    # abandoned (dynamic give-up): drop un-fetched work;
                    # records replay from the committed offset on restore
                    disp.abandon()
                    return
                if self._retain_batches:
                    # isolation AND device-fault recovery need the RAW
                    # batch retained past the async dispatch (the
                    # drained views alias the ring's reuse buffer): one
                    # private copy per batch, paid only when a DLQ or
                    # the failover plane is wired
                    X = np.array(X, copy=True)
                    offsets = np.array(offsets, copy=True)
                first_off = int(offsets[0]) if n else 0
                self._dispatched_hi = max(self._dispatched_hi, first_off + n)
                if (
                    self._suspect_until is not None
                    and first_off < self._suspect_until
                ):
                    # crash-loop fingerprint: this range killed previous
                    # incarnations — score it synchronously under
                    # persisted suspect markers so a process-killing
                    # record converges to a DLQ entry across restarts.
                    # Flush first: the marker protocol and the FIFO
                    # commit contract both need nothing else in flight.
                    disp.flush()
                    if sh is not None:
                        sh.abandon(plan)  # the scan scores statelessly
                    self._suspect_scan(
                        handle, X, offsets, error=None, persist=True,
                        ctx=(
                            trace_mod.context_for(first_off)
                            if jstore is not None else None
                        ),
                    )
                    if self.committed_offset >= self._suspect_until:
                        self._exit_suspect_mode()
                    batches.inc()
                    fill.inc(n)
                    continue
                if (
                    self._failover is not None
                    and self._failover.should_fallback(
                        getattr(handle, "key", None), handle
                    )
                ):
                    # circuit OPEN for this model: the window must
                    # drain first (FIFO commit order), then this batch
                    # serves synchronously on the host fallback tier —
                    # degraded, not down
                    disp.flush()
                    if sh is not None:
                        sh.abandon(plan)  # the host tier folds nothing
                    self._serve_fallback(
                        handle, X, offsets,
                        jctx=(
                            trace_mod.context_for(first_off)
                            if jstore is not None else None
                        ),
                    )
                    batches.inc()
                    fill.inc(n)
                    continue
                if freshness is not None:
                    # stage-boundary watermark propagation: the batch
                    # crossing ring→device advances the dispatch-stage
                    # watermark with ITS OWN ingest-stamp event times
                    # (exported as watermark_stage_ts{stage="dispatch"},
                    # fleet MIN) — under backpressure the ring holds old
                    # records, and the fetch-time watermark would lie;
                    # monotone by construction, so a replayed or
                    # out-of-order chunk can never regress it
                    freshness.propagate_low_watermark(
                        "dispatch", int(offsets[0]) if n else None, n
                    )
                t_start = time.monotonic()
                # the batch's journey context: trace id derived purely
                # from first_off (deterministic across incarnations and
                # — later — chips), one dispatch hop per BATCH so the
                # fan-out to per-record journeys costs nothing per
                # record; active around the launch so the featurize/
                # h2d/readback spans and any exemplar carry its ids
                jctx = (
                    trace_mod.context_for(first_off)
                    if jstore is not None else None
                )
                if jstore is not None:
                    jstore.hop(
                        "dispatch", jctx, first_off, n,
                        model=getattr(handle, "key", None),
                    )
                    if (
                        self._state is not None
                        and not self._state_bypass
                    ):
                        # the state read/update rides THIS dispatch:
                        # one hop per batch so fjt-trace shows the
                        # session-state hop in the journey
                        jstore.hop(
                            "state", jctx, first_off, n,
                            resident=self._state.resident,
                        )
                try:
                    with trace_mod.use(jctx):
                        disp.launch(
                            lambda h=handle, X=X, n=n, o=offsets, p=plan: (
                                self._dispatch_checked(h, X, n, o, plan=p)
                            ),
                            meta=(
                                n, first_off, t_start, False,
                                handle,
                                X if self._retain_batches else None,
                                offsets if self._retain_batches else None,
                                jctx, plan,
                            ),
                            ident={"first_off": first_off, "n": n},
                            # opts this launch into the sampled
                            # device-timing pool (rate-limited;
                            # obs/profiler.py) — the live MFU/membw
                            # gauges and the kernel cost ledger; skipped
                            # entirely when profiling is off
                            profile=(
                                attr_mod.dispatch_profile(handle, n)
                                if disp.profiling else None
                            ),
                        )
                except PoisonIsolationOverflow:
                    raise  # isolation already abandoned: die honestly
                except Exception as e:
                    # the dispatch itself raised (host featurize, an
                    # injected poison, a device fault at launch time):
                    # device-fault triage FIRST — errors from OLDER
                    # window entries were already handled (or
                    # re-raised) inside launch's trim via on_error, so
                    # this exception belongs to THIS batch
                    kind = devfault.classify(e)
                    if (
                        self._state is not None
                        and not self._state_bypass
                        and (kind is not None and self._failover
                             is not None
                             or kind is None and self._dlq is not None)
                    ):
                        # a recoverable launch failure may have half-
                        # applied this batch to the table (host mirror
                        # mutated, device update never dispatched):
                        # restore the snapshot before recovery
                        self._state.rollback()
                    if kind is not None and self._failover is not None:
                        # older in-flight batches must commit BEFORE
                        # this one's synchronous recovery commits its
                        # range (FIFO contract)
                        disp.flush()
                        self._device_recover(
                            handle, X, offsets, e, kind, ctx=jctx
                        )
                    elif kind is not None or self._dlq is None:
                        raise
                    else:
                        disp.flush()
                        self._suspect_scan(
                            handle, X, offsets, error=e, ctx=jctx
                        )
                batches.inc()
                fill.inc(n)
            if building is not None:  # the iteration that found the end
                building.__exit__(None, None, None)
                building = None
            disp.close()  # drain the window: every dispatched batch sinks
            self._ckpt.save_now(self._ckpt_state)  # clean drain → exact resume
        except BaseException as e:
            self._error = e
            self._stop.set()
        finally:
            if building is not None:
                building.__exit__(None, None, None)


class BlockPipeline(BlockPipelineBase):
    """source → ring → padded batches → async scoring → sink.

    ``sink(out, n: int, first_offset: int)`` receives raw device outputs
    (decode is the caller's choice — fetching to host costs a D2H transfer
    per batch; use :meth:`decode` to turn one into ``Prediction``s). When
    the model is rank-wire eligible (``use_quantized``, the default) the
    scoring hop is the quantized path of compile/qtrees.py: the drained f32
    block is encoded to threshold ranks by the multithreaded C++ bucketizer
    and ``out`` is the QuantizedScorer output; otherwise ``out`` is a
    :class:`ModelOutput` from the f32 path. ``backend`` says which engaged
    and is also recorded in metrics as ``scorer_backend_*``.
    """

    def __init__(
        self,
        source: BlockSource,
        model: CompiledModel,
        sink: Callable,
        config: Optional[RuntimeConfig] = None,
        metrics: Optional[MetricsRegistry] = None,
        use_native: bool = True,
        in_flight: int = 2,
        use_quantized: bool = True,
        checkpoint=None,
        max_dispatch_chunks: int = 8,
        donate: Optional[bool] = None,
        slo=None,
        batcher=None,
        admission=None,
        shed_lane: str = "block",
        dlq=None,
        prefetch: Optional[bool] = None,
        failover=None,
        mesh=None,
        state=None,
    ):
        if model.batch_size is None:
            raise InputValidationException(
                "BlockPipeline needs a fixed-batch compiled model "
                "(compile_pmml(batch_size=...))"
            )
        if mesh is not None and not hasattr(model, "without_devices"):
            # promote the compiled model onto the mesh (ROADMAP item
            # 1): batch sharded over the data axis, wide params TP-
            # sharded over the model axis — the scoring contract and
            # the sink shape are unchanged (ShardedModel proxies the
            # CompiledModel surface). An already-sharded model passes
            # through untouched.
            from flink_jpmml_tpu.parallel.mesh import DATA_AXIS
            from flink_jpmml_tpu.parallel.sharding import mesh_sharded

            n_data = int(mesh.shape.get(DATA_AXIS, 1))
            if model.batch_size % max(n_data, 1) != 0:
                raise InputValidationException(
                    f"batch_size {model.batch_size} must divide by the "
                    f"mesh data-axis size {n_data}"
                )
            model = mesh_sharded(model, mesh)
        if hasattr(model, "in_flight_depth"):
            # mesh-aware in-flight window: deep enough to cover the
            # data rows (parallel/assignment.mesh_in_flight), recorded
            # as carried dispatch state so a degraded-mesh rebuild
            # keeps the window geometry without re-derivation. A
            # state-armed pipeline keeps the depth it was given: its
            # dispatches chain on the one donated table, one at a time
            # on every chip, so a deeper window buys no overlap
            if state is None:
                in_flight = model.in_flight_depth(in_flight)
            model.with_dispatch_state(in_flight=in_flight)
            if getattr(model, "assignment", None) is None:
                from flink_jpmml_tpu.parallel.assignment import (
                    assignment_for,
                )

                model.assignment = assignment_for(
                    model.mesh, getattr(source, "partitions", ()) or ()
                )
        if (
            isinstance(state, state_mod.StateSpec)
            and getattr(model, "mesh", None) is not None
        ):
            # born on the chips: zeros allocated shard by shard, no
            # table-sized host array on the way (runtime/state.py)
            metrics = metrics if metrics is not None else MetricsRegistry()
            state = state_mod.KeyedStateTable(
                state, metrics=metrics, mesh=model.mesh
            )
        super().__init__(
            source=source,
            sink=sink,
            arity=model.field_space.arity,
            batch_size=model.batch_size,
            config=config,
            metrics=metrics,
            use_native=use_native,
            in_flight=in_flight,
            checkpoint=checkpoint,
            max_dispatch_chunks=max_dispatch_chunks,
            donate=donate,
            slo=slo,
            batcher=batcher,
            admission=admission,
            shed_lane=shed_lane,
            dlq=dlq,
            prefetch=prefetch,
            failover=failover,
            state=state,
        )
        self._bound = BoundScorer("static", model, use_quantized,
                                  self.metrics)
        self.backend = self._bound.backend
        self.metrics.counter(f"scorer_backend_{self.backend}").inc()
        if self._state is not None:
            if self._bound.q is None:
                raise InputValidationException(
                    "stateful scoring requires the rank-wire scorer: "
                    "this model is not quantized-eligible (or "
                    "use_quantized=False)"
                )
            model_mesh = getattr(model, "mesh", None)
            if model_mesh is not None:
                # the table over the mesh data axis alongside the model
                # it rides with (nothing to do for one built with
                # ``mesh=``: it was born there)
                self._state.shard(model_mesh)
            if self._state.n_shards > 1:
                # the keyed shuffle: a chip's bucket is a power of two
                # of the model's chunks, all buckets of a dispatch
                # together at most max_dispatch_chunks of them
                from flink_jpmml_tpu.runtime.shuffle import KeyShuffle

                most = max(1, max_dispatch_chunks // self._state.n_shards)
                self._shuffle = KeyShuffle(
                    self._state, model.field_space.arity, model.batch_size,
                    [1 << i for i in range(most.bit_length())],
                    max(1, max_dispatch_chunks) * model.batch_size,
                    self.metrics,
                )
        if hasattr(model, "batch_divisor"):
            from flink_jpmml_tpu.obs import mesh as mesh_obs

            self._mesh_obs = mesh_obs.telemetry_for(self.metrics, model)

    @property
    def bucket_chunks(self) -> tuple:
        """The chunk counts a chip's bucket of a keyed mesh dispatch
        may have (runtime/shuffle.py), ascending: with the model's
        batch size, every device shape the state-armed program runs
        at. Empty where the state table lies on one chip."""
        return () if self._shuffle is None else self._shuffle.sizes

    def decode(self, out, n: int):
        """Sink-received raw output → ``Prediction`` list (host-side).
        A state-armed pipeline's sink still receives the stateless
        output shape (the pipeline unwraps the derived features before
        the sink), but decode also tolerates a raw fused pair."""
        out, _ = state_mod.split_output(out)
        return self._bound.decode(out, n)

    def _acquire(self, finish_one):
        return self._bound  # one static model: nothing to resolve

    def _dispatch(self, bound, X, n):
        return self._dispatch_bound(bound, X, n), None


class _ZerosMCache:
    """Reused all-False missing masks (avoid reallocating 256KB per batch)."""

    def __init__(self):
        self._cache = {}

    def get(self, b: int, f: int) -> np.ndarray:
        key = (b, f)
        m = self._cache.get(key)
        if m is None:
            m = np.zeros((b, f), bool)
            self._cache[key] = m
        return m


_ZEROS_M = _ZerosMCache()
