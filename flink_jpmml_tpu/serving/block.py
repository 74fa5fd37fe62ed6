"""Dynamic model serving on the block path (C6 × the ≥1M rec/s plane).

The reference's flagship v0.6 capability — swap served models from a
control stream while traffic flows (SURVEY.md §1 C6, §4.3) — composed
with its *data plane*. Round 2 shipped the two separately: Add/Del +
double-buffered swap lived only on the record-object engine
(thousands/sec), while the production :class:`~flink_jpmml_tpu.runtime
.block.BlockPipeline` took exactly one static model. This class is the
composition, built on the shared
:class:`~flink_jpmml_tpu.runtime.block.BlockPipelineBase` loop:

    BlockSource → ring → drained f32 batches
                     ↘ control stream (Add/Del) → ModelRegistry
    batch × current-model → quantized/f32 scoring (async dispatch)
                          → sink(out, n, first_offset, decode)

Swap protocol (double-buffered, non-draining):

- An ``AddMessage`` starts a *background* parse+compile+jit via the
  registry's bounded warm pool; the score loop keeps dispatching against
  the current scorer the whole time — no batch ever waits on a compile.
- Between batches (never mid-batch) the loop adopts the newest
  *warm-and-ready* served version whose arity matches the stream.
  Readiness is judged by the registry's **compiled-model instance**, not
  by (name, version) alone — a Del + re-Add of the same id with a new
  document produces a new instance and therefore a fresh adoption, never
  a stale cache hit. In-flight batches dispatched under the previous
  version are NOT drained or cancelled: they ride the same FIFO window,
  get sunk in order, and their offsets commit after sink exactly like
  static-path batches — offsets stay contiguous across the swap.
- A ``DelMessage`` of the serving version drops it; the loop falls back
  to the newest remaining warm version. With nothing servable the loop
  *holds* the drained batch (ring backpressure upstream) rather than
  dropping records; on shutdown the hold is bounded
  (``drain_hold_timeout_s``) and abandoned records simply replay from
  the committed offset on restore (at-least-once, C7).

The sink gains a 4th argument vs the static path: ``decode``, a callable
(out, n) → [Prediction] bound to the exact model that scored the batch
(with ``decode.model_key`` naming it) — after a swap, an in-flight
batch's raw output must be decoded by the model that produced it, not
whichever is current at sink time.
"""

from __future__ import annotations

import time
from typing import Callable, Optional

from flink_jpmml_tpu.models.control import ServingMessage
from flink_jpmml_tpu.models.core import ModelId
from flink_jpmml_tpu.runtime.block import (
    BlockPipelineBase,
    BlockSource,
    BoundScorer,
)
from flink_jpmml_tpu.runtime.sources import ControlSource
from flink_jpmml_tpu.serving.registry import ModelRegistry
from flink_jpmml_tpu.utils.config import CompileConfig, RuntimeConfig
from flink_jpmml_tpu.utils.exceptions import InputValidationException
from flink_jpmml_tpu.utils.metrics import MetricsRegistry


class DynamicBlockPipeline(BlockPipelineBase):
    """Block-speed scoring with control-stream model serving.

    ``sink(out, n, first_offset, decode)`` — see module docstring.
    ``name`` pins which served model name this stream scores (versions of
    it swap in and out); the newest warm version wins, reference
    "latest-wins" routing (SURVEY.md §4.3).
    """

    _THREAD_TAG = "dblk"
    # bounded wait for the first record: an idle stream still applies
    # Add/Del and kicks background warms every ~20ms (see _on_idle)
    _IDLE_WAIT_US = 20_000

    def __init__(
        self,
        source: BlockSource,
        control: ControlSource,
        sink: Callable,
        name: str,
        arity: int,
        batch_size: int,
        config: Optional[RuntimeConfig] = None,
        metrics: Optional[MetricsRegistry] = None,
        compile_config: Optional[CompileConfig] = None,
        use_native: bool = True,
        in_flight: int = 2,
        use_quantized: bool = True,
        checkpoint=None,
        hold_poll_s: float = 0.005,
        drain_hold_timeout_s: float = 5.0,
        mesh=None,
        max_dispatch_chunks: int = 8,
        donate: Optional[bool] = None,
        slo=None,
        batcher=None,
        admission=None,
        shed_lane: str = "block",
        dlq=None,
        failover=None,
    ):
        if batch_size <= 0:
            raise InputValidationException(
                f"batch_size must be positive: {batch_size}"
            )
        if mesh is not None:
            n_data = mesh.shape.get("data", 1)
            if batch_size % max(n_data, 1) != 0:
                raise InputValidationException(
                    f"batch_size {batch_size} must divide by the mesh "
                    f"data-axis size {n_data} (sharded dispatch pads to "
                    "the batch, which must split evenly across devices)"
                )
            # mesh-aware in-flight window: deep enough to cover the
            # data rows (parallel/assignment.mesh_in_flight); the
            # single-chip depth is untouched when data=1
            from flink_jpmml_tpu.parallel.assignment import mesh_in_flight

            in_flight = mesh_in_flight(mesh, in_flight)
        super().__init__(
            source=source,
            sink=sink,
            arity=arity,
            batch_size=batch_size,
            config=config,
            metrics=metrics,
            use_native=use_native,
            in_flight=in_flight,
            checkpoint=checkpoint,
            max_dispatch_chunks=max_dispatch_chunks,
            donate=donate,
            # deadline SLO burn-rate tracking (obs/slo.py) rides the
            # completion path here exactly as on the static pipeline,
            # and so does the overload plane (serving/overload.py):
            # deadline-capped aggregation + admission shedding
            slo=slo,
            batcher=batcher,
            admission=admission,
            shed_lane=shed_lane,
            # record-level poison isolation (runtime/dlq.py) works on
            # the dynamic path exactly as on the static one: the
            # suspect scan re-dispatches through the CURRENT BoundScorer
            # and quarantined envelopes carry its model key
            dlq=dlq,
            # device-fault recovery (runtime/devfault.py) works per
            # served model: the circuit breaker keys on the bound
            # scorer's model key, so one sick model's failover does
            # not gate its siblings
            failover=failover,
        )
        self._control = control
        self._name = name
        self._use_quantized = use_quantized
        self._hold_poll_s = hold_poll_s
        self._drain_hold_timeout_s = drain_hold_timeout_s
        self.registry = ModelRegistry(
            batch_size=batch_size, compile_config=compile_config, mesh=mesh
        )
        self._current: Optional[BoundScorer] = None
        self._rejected: set = set()  # arity-mismatched served ids
        self.swaps = self.metrics.counter("model_swaps")

    @property
    def serving_key(self) -> Optional[str]:
        cur = self._current
        return cur.key if cur is not None else None

    @property
    def backend(self) -> Optional[str]:
        cur = self._current
        return cur.backend if cur is not None else None

    # -- checkpoint (C7: source offset + served metadata, like the
    #    reference's checkpointed operator state) --------------------------

    def _ckpt_state(self) -> dict:
        # the base state (source offset + inflight_hi for the replay
        # region + optional source cursor vector) plus the served-model
        # registry — the reference's checkpointed operator state
        state = super()._ckpt_state()
        state["registry"] = self.registry.state()
        return state

    def _restore_extra(self, state: dict) -> None:
        super()._restore_extra(state)  # keyed state table, if armed
        self.registry.restore(state.get("registry", {}))

    # -- model resolution --------------------------------------------------

    def _poll_control(self) -> None:
        """Drain pending Add/Del messages; adopt the newest warm, arity-
        matching compiled model when it differs from the current one.
        Runs between batches only — a batch is never re-routed
        mid-dispatch."""
        changed = False
        while True:
            msgs = self._control.poll(64)
            if not msgs:
                break
            for _, msg in msgs:
                if isinstance(msg, ServingMessage):
                    changed |= self.registry.apply(msg)
        if changed:
            # a registry change may supersede any quarantine (a corrected
            # document can be re-Added under the same name+version)
            self._rejected.clear()
        cur = self._current
        # current version un-served (Del): drop it even with nothing warm
        if cur is not None:
            mid = ModelId.from_key(cur.key)
            if self.registry.resolve(mid.name, mid.version) is None:
                self._current = None
                cur = None
        # the newest warm-and-compiled served version of our name wins;
        # warmness is judged per *compiled instance*, so a re-Add with a
        # different document (new instance after its background warm) is
        # adopted even though the (name, version) key looks unchanged.
        # An active rollout's candidate is NOT adoptable: the block path
        # serves whole dense batches to one model, so the candidate
        # becomes visible here only at promotion to full (shadow/canary
        # splitting is the record-path DynamicScorer's job) — a
        # guardrail rollback therefore never had block traffic to undo.
        ro = self.registry.rollout(self._name)
        cand_version = ro.candidate_version if ro is not None else None
        best_mid = None
        best_model = None
        for mid in sorted(
            (m for m in self.registry.served if m.name == self._name),
            key=lambda m: m.version,
            reverse=True,
        ):
            if mid in self._rejected or mid.version == cand_version:
                continue
            model = self.registry.model_if_warm(mid)  # kicks warm if cold
            if model is None:
                continue
            if model.field_space.arity != self._arity:
                # served document doesn't fit this stream's record shape:
                # quarantine the id (until the registry changes again)
                self._rejected.add(mid)
                self.metrics.counter("arity_rejected_models").inc()
                continue
            best_mid, best_model = mid, model
            break
        if best_model is None:
            return
        if cur is not None and cur.model is best_model:
            return  # already serving exactly this compiled instance
        # a fresh BoundScorer per adoption — no cache: the quantized
        # probe is memoized on the CompiledModel so this is cheap, and
        # nothing pins superseded models (in-flight batches hold their
        # own decode references until sunk; the registry owns the rest)
        bound = BoundScorer(best_mid.key(), best_model, self._use_quantized,
                            self.metrics)
        if hasattr(best_model, "with_dispatch_state"):
            # sharded serving: record the window geometry + partition
            # ownership on the adopted model so a degraded-mesh rebuild
            # carries both (ShardedModel.without_devices), and arm the
            # per-chip telemetry for the adopted mesh
            best_model.with_dispatch_state(in_flight=self._in_flight_max)
            if getattr(best_model, "assignment", None) is None:
                from flink_jpmml_tpu.parallel.assignment import (
                    assignment_for,
                )

                best_model.assignment = assignment_for(
                    best_model.mesh,
                    getattr(self._source, "partitions", None) or (),
                )
            from flink_jpmml_tpu.obs import mesh as mesh_obs

            self._mesh_obs = mesh_obs.telemetry_for(
                self.metrics, best_model
            )
        self._current = bound
        self.set_tenant(best_mid.key())
        self.swaps.inc()
        self.metrics.counter(f"scorer_backend_{bound.backend}").inc()

    # -- BlockPipelineBase hooks ------------------------------------------

    def _on_idle(self) -> None:
        self._poll_control()  # idle ring: still apply Add/Del promptly

    def _acquire(self, finish_one):
        self._poll_control()
        hold_start = time.monotonic()
        while self._current is None:
            # hold the batch (never drop it) until something is servable;
            # in-flight keeps draining meanwhile
            if self._stop.is_set() or self._ring.closed:
                if not self._drain_all:
                    return None
                # draining shutdown: bounded wait, then give up — the
                # held batch replays from the committed offset on restore
                if (
                    time.monotonic() - hold_start
                    > self._drain_hold_timeout_s
                ):
                    return None
            finish_one()  # already-dispatched batches keep reaching the
            # sink while we hold
            time.sleep(self._hold_poll_s)
            self._poll_control()
        return self._current

    def _dispatch(self, bound, X, n):
        return self._dispatch_bound(bound, X, n), bound.decode

    def _adopt_rebuilt(self, handle, rebuilt) -> None:
        # degraded-mesh rebuild (runtime/block.py KIND_LOST rung): the
        # registry's compiled instance must follow, or the next
        # latest-wins re-adoption would swap the dead mesh back in
        super()._adopt_rebuilt(handle, rebuilt)
        self.registry.adopt_rebuilt(handle.key, rebuilt)

    def _fallback_dispatch(self, bound, X, n):
        # host-tier output decodes through the SAME bound decode (the
        # tier re-runs the identical XLA program on CPU), so a swap
        # mid-outage keeps per-batch decode correctness
        return self._failover.tier.score_bound(bound, X), bound.decode

    def _emit(self, out, n, first_off, decode) -> None:
        self._sink(out, n, first_off, decode)
