"""Fallback-tier circuit breaking: keep serving when the chip is sick.

``runtime/devfault.py`` tells a device fault apart from record poison;
this module is what the hot paths DO about a persistent one. Three
pieces, composed per pipeline as a :class:`FailoverPlane`:

:class:`CircuitBreaker`
    One per (model, backend) key, the classic closed → open →
    half-open machine. ``record_failure`` counts consecutive device
    faults; at ``FJT_FAILOVER_THRESHOLD`` the circuit OPENS and the
    pipeline stops dispatching that model to the device — batches
    serve on the fallback tier instead of crash-looping. After
    ``FJT_FAILOVER_COOLDOWN_S`` the circuit goes HALF-OPEN: dispatches
    flow to the device again as *probes*, any failure re-opens, and
    ``FJT_FAILOVER_GREENS`` consecutive green probes CLOSE it —
    automatic promotion back, no operator action. State is exported as
    ``failover_state{model=...}`` (0 closed / 1 half-open / 2 open,
    fleet merge: worst-of) and every transition is a flight event.

:class:`FallbackTier`
    The degraded-mode scorer: the same XLA program the device runs,
    compiled for and executed on the HOST (CPU) backend — the
    host/interpret path the autotune sweep already builds against. The
    rank-wire path re-dispatches the identical jitted program with a
    CPU-resident params copy, so outputs stay byte-compatible with the
    sink's ``decode``; f32 models run their functional ``_jit_fn`` the
    same way (a :class:`~flink_jpmml_tpu.parallel.sharding.ShardedModel`
    falls back to its single-host ``base``). A Pallas-backed scorer has
    no host twin (the kernel bakes TPU tiling) and reports itself
    unsupported — the ladder escalates to the supervisor instead, which
    is the honest degraded mode for that backend.

:class:`FailoverPlane`
    Per-registry bundle (``plane_for``): breakers keyed by model,
    the shared tier, and the recovery-ladder accounting —
    ``device_fault_total{kind}``, ``redispatch_records``,
    ``fallback_records``, ``oom_shrinks`` (all fleet merge: sum).

The plane arms automatically on pipelines that already retain their
staging batches (a DLQ is wired — production shape), or explicitly via
``FJT_FAILOVER=1`` / the ``failover=`` constructor knob; a bare bench
loop pays nothing. The ladder itself lives in the hot paths
(``runtime/block.py`` ``_device_recover``, ``runtime/engine.py``
``_recover_device``); this module owns the state machines they share.
"""

from __future__ import annotations

import re
import threading
import time
import weakref
from typing import Callable, Dict, Optional

import numpy as np

from flink_jpmml_tpu.obs import recorder as flight
from flink_jpmml_tpu.utils.exceptions import FlinkJpmmlTpuError
from flink_jpmml_tpu.utils.metrics import MetricsRegistry
from flink_jpmml_tpu.utils.retry import env_float, env_int

_THRESHOLD_ENV = "FJT_FAILOVER_THRESHOLD"
_COOLDOWN_ENV = "FJT_FAILOVER_COOLDOWN_S"
_GREENS_ENV = "FJT_FAILOVER_GREENS"
_RETRIES_ENV = "FJT_DEVICE_RETRIES"

STATE_CLOSED = 0.0
STATE_HALF_OPEN = 1.0
STATE_OPEN = 2.0

_STATE_NAMES = {
    STATE_CLOSED: "closed",
    STATE_HALF_OPEN: "half-open",
    STATE_OPEN: "open",
}

_FALLBACK_EVENT_MIN_PERIOD_S = 1.0


class FallbackUnavailable(FlinkJpmmlTpuError):
    """This scorer has no host fallback twin (Pallas kernel, no CPU
    device): the ladder escalates instead of serving degraded."""


class CircuitBreaker:
    """closed → open → half-open per served model; see module doc."""

    def __init__(
        self,
        metrics: Optional[MetricsRegistry] = None,
        key: str = "default",
        fail_threshold: Optional[int] = None,
        cooldown_s: Optional[float] = None,
        probe_greens: Optional[int] = None,
        clock: Callable[[], float] = time.monotonic,
    ):
        self.key = key
        self.fail_threshold = (
            fail_threshold if fail_threshold is not None
            else env_int(_THRESHOLD_ENV, 3)
        )
        self.cooldown_s = (
            cooldown_s if cooldown_s is not None
            else env_float(_COOLDOWN_ENV, 2.0)
        )
        self.probe_greens = (
            probe_greens if probe_greens is not None
            else env_int(_GREENS_ENV, 3)
        )
        self._clock = clock
        self._mu = threading.Lock()
        self._state = STATE_CLOSED
        self._strikes = 0  # consecutive device faults while closed
        self._greens = 0  # consecutive green probes while half-open
        self._opened_at = 0.0
        self._gauge = (
            metrics.gauge(f'failover_state{{model="{key}"}}')
            if metrics is not None else None
        )

    @property
    def state(self) -> float:
        return self._state

    def _set_state(self, state: float) -> None:
        self._state = state
        if self._gauge is not None:
            self._gauge.set(state)

    def allow_dispatch(self) -> bool:
        """Hot-path verdict: may this model dispatch to the device?
        CLOSED and HALF-OPEN → yes (half-open dispatches are probes);
        OPEN → no until the cooldown elapses, at which point the
        circuit flips to HALF-OPEN and the answer becomes yes."""
        if self._state == STATE_CLOSED:
            return True
        with self._mu:
            if self._state == STATE_OPEN:
                if self._clock() - self._opened_at < self.cooldown_s:
                    return False
                self._set_state(STATE_HALF_OPEN)
                self._greens = 0
                flight.record(
                    "failover_half_open", model=self.key,
                    cooldown_s=self.cooldown_s,
                )
            return True

    def record_failure(self, kind: str = "device_error") -> None:
        """One device fault attributed to this model. Opens the
        circuit past the threshold; any half-open probe failure
        re-opens immediately (the cooldown clock restarts)."""
        with self._mu:
            if self._state == STATE_CLOSED:
                self._strikes += 1
                if self._strikes < self.fail_threshold:
                    return
            self._strikes = 0
            self._greens = 0
            reopened = self._state == STATE_HALF_OPEN
            self._set_state(STATE_OPEN)
            self._opened_at = self._clock()
        flight.record(
            "failover_open", model=self.key, fault=kind,
            probe_failed=reopened,
        )

    def record_success(self) -> None:
        """One clean device completion. Closed: clears the strike
        streak. Half-open: counts a green probe — at ``probe_greens``
        the circuit CLOSES (automatic promotion back)."""
        if self._state == STATE_CLOSED and self._strikes == 0:
            return  # steady-state fast path: no lock
        closed_now = False
        with self._mu:
            if self._state == STATE_CLOSED:
                self._strikes = 0
                return
            if self._state == STATE_HALF_OPEN:
                self._greens += 1
                if self._greens >= self.probe_greens:
                    self._set_state(STATE_CLOSED)
                    self._strikes = 0
                    closed_now = True
        if closed_now:
            flight.record(
                "failover_close", model=self.key,
                greens=self.probe_greens,
            )


class FallbackTier:
    """Host-backend scoring twin for degraded-mode serving."""

    @staticmethod
    def _cpu_device():
        import jax

        try:
            return jax.devices("cpu")[0]
        except RuntimeError:
            return None

    def supports(self, bound) -> bool:
        """Can this BoundScorer-shaped handle serve on the host tier?
        Rank-wire XLA and f32 models yes; Pallas kernels no (their
        grid is baked for the device)."""
        if self._cpu_device() is None:
            return False
        q = getattr(bound, "q", None)
        if q is not None:
            return q.backend == "xla"
        model = getattr(bound, "model", None)
        model = getattr(model, "base", model)  # ShardedModel → base
        return getattr(model, "_jit_fn", None) is not None

    @staticmethod
    def _params_cpu(obj, params, cpu):
        """CPU-resident params copy cached ON the scorer itself — its
        lifetime is the model's lifetime (an id()-keyed side table
        would hand a NEW model allocated at a retired model's address
        the wrong params, and pin retired trees forever)."""
        cached = getattr(obj, "_fjt_cpu_params", None)
        if cached is not None:
            return cached
        import jax

        placed = jax.device_put(params, cpu)
        try:
            object.__setattr__(obj, "_fjt_cpu_params", placed)
        except (AttributeError, TypeError):
            pass  # slotted/frozen scorer: recompute per call —
            # correctness over the cache
        return placed

    def score_bound(self, bound, X):
        """Score one raw f32 batch on the host tier → raw output in
        the SAME wire form the device path produces (the sink's
        ``decode`` cannot tell the tiers apart). Synchronous — the
        degraded tier trades latency for availability, and blocking
        here keeps the ring's backpressure honest."""
        import jax

        cpu = self._cpu_device()
        if cpu is None:
            raise FallbackUnavailable("no CPU device for the host tier")
        X = np.ascontiguousarray(X, np.float32)
        q = getattr(bound, "q", None)
        if q is not None:
            if q.backend != "xla":
                raise FallbackUnavailable(
                    f"{q.backend} kernel has no host twin (tiling is "
                    "baked for the device) — escalate instead"
                )
            # the byte-parity host encode + the SAME jitted program,
            # executed on the CPU backend with a CPU params copy: the
            # output decodes identically to a device dispatch
            payload, K = q.pad_wire(q.wire.encode(X, None))
            params = self._params_cpu(q, q.params, cpu)
            with jax.default_device(cpu):
                out = q._entry(K, False)(params, payload)
            return jax.block_until_ready(out)
        model = getattr(bound, "model", None)
        model = getattr(model, "base", model)
        fn = getattr(model, "_jit_fn", None)
        if fn is None:
            raise FallbackUnavailable(
                f"{type(model).__name__} exposes no functional jit "
                "entry for the host tier"
            )
        # f32 path: NaN is the missing convention (cf. block._score_f32)
        M = np.isnan(X)
        if M.any():
            X = np.where(M, 0.0, X).astype(np.float32)
        bs = getattr(model, "batch_size", None)
        if bs is not None and X.shape[0] != bs:
            from flink_jpmml_tpu.compile import prepare

            X, M, _ = prepare.pad_batch(X, M, bs)
        params = self._params_cpu(model, model.params, cpu)
        with jax.default_device(cpu):
            out = fn(params, X, M)
        return jax.block_until_ready(out)


class FailoverPlane:
    """Per-registry bundle: breakers by model key + the fallback tier
    + the recovery ladder's accounting. See module docstring."""

    def __init__(
        self,
        metrics: MetricsRegistry,
        tier: Optional[FallbackTier] = None,
        retries: Optional[int] = None,
        clock: Callable[[], float] = time.monotonic,
        **breaker_kw,
    ):
        self.metrics = metrics
        self.tier = tier if tier is not None else FallbackTier()
        # redispatch attempts per failed batch before the ladder falls
        # through to the fallback tier
        self.retries = (
            retries if retries is not None else env_int(_RETRIES_ENV, 2)
        )
        self._clock = clock
        self._breaker_kw = breaker_kw
        self._mu = threading.Lock()
        self._breakers: Dict[str, CircuitBreaker] = {}
        self.fallback_records = metrics.counter("fallback_records")
        self.redispatch_records = metrics.counter("redispatch_records")
        self.oom_shrinks = metrics.counter("oom_shrinks")
        self._last_fallback_event = 0.0

    # -- breakers ----------------------------------------------------------

    def breaker_for(self, key: Optional[str]) -> CircuitBreaker:
        key = key or "default"
        b = self._breakers.get(key)
        if b is None:
            with self._mu:
                b = self._breakers.get(key)
                if b is None:
                    b = CircuitBreaker(
                        self.metrics, key=key, clock=self._clock,
                        **self._breaker_kw,
                    )
                    self._breakers[key] = b
        return b

    def breakers(self) -> Dict[str, CircuitBreaker]:
        with self._mu:
            return dict(self._breakers)

    def record_success(self, key: Optional[str]) -> None:
        """Steady-state per-completion feed: a dict miss (no breaker
        ever created — no fault ever seen) is the whole cost."""
        b = self._breakers.get(key or "default")
        if b is not None:
            b.record_success()

    def should_fallback(self, key: Optional[str], bound) -> bool:
        """True when this model's circuit is OPEN (cooldown not yet
        elapsed) AND the fallback tier can actually serve the handle —
        an unsupported handle keeps dispatching (each failure
        re-ladders) rather than silently dropping to nothing."""
        b = self._breakers.get(key or "default")
        if b is None or b.allow_dispatch():
            return False
        return self.tier.supports(bound)

    # -- accounting --------------------------------------------------------

    def note_fault(self, kind: str, key=None, first_off=None, n=None,
                   error=None) -> None:
        from flink_jpmml_tpu.runtime import devfault

        devfault.note(
            self.metrics, kind, model=key, first_off=first_off, n=n,
            error=error,
        )

    def note_fallback(self, n: int, key=None) -> None:
        self.fallback_records.inc(n)
        now = self._clock()
        due = False
        with self._mu:
            if (
                now - self._last_fallback_event
                >= _FALLBACK_EVENT_MIN_PERIOD_S
            ):
                self._last_fallback_event = now
                due = True
        if due:  # rate-limited: an outage serves MANY fallback batches
            flight.record("fallback_serving", model=key, records=n)


# -- per-registry singletons (the obs/attr.py discipline) --------------------

_PLANES: "weakref.WeakKeyDictionary" = weakref.WeakKeyDictionary()
_planes_mu = threading.Lock()


def plane_for(metrics: Optional[MetricsRegistry]) -> Optional[FailoverPlane]:
    """The registry's failover plane, created on first use (every
    pipeline sharing a registry shares one set of breakers — a sick
    device is sick for all of them). None for a None registry."""
    if metrics is None:
        return None
    plane = _PLANES.get(metrics)
    if plane is None:
        with _planes_mu:
            plane = _PLANES.get(metrics)
            if plane is None:
                plane = FailoverPlane(metrics)
                _PLANES[metrics] = plane
    return plane


# -- operator summary (fjt-top --failover) -----------------------------------


def state_name(value: float) -> str:
    return _STATE_NAMES.get(float(value), f"?{value}")


def summary(struct: dict) -> Optional[dict]:
    """Failover-plane summary from a metrics struct (``fjt-top
    --failover``, bench artifacts): circuit state per model, fallback
    share of delivered records, redispatch/OOM-shrink counts, the
    device-fault kind totals, and the checkpoint-suspension flag.
    None when the struct carries no failover telemetry at all."""
    gauges = struct.get("gauges") or {}
    counters = struct.get("counters") or {}

    def g(name):
        v = gauges.get(name)
        return v.get("value") if isinstance(v, dict) else None

    states: Dict[str, float] = {}
    for name, v in gauges.items():
        m = re.match(r'^failover_state\{model="([^"]+)"\}$', name)
        if m and isinstance(v, dict):
            states[m.group(1)] = float(v.get("value") or 0.0)
    faults_by_kind: Dict[str, float] = {}
    for name, v in counters.items():
        m = re.match(r'^device_fault_total\{kind="([^"]+)"\}$', name)
        if m:
            faults_by_kind[m.group(1)] = v
    out: dict = {}
    if states:
        out["states"] = {
            k: state_name(s) for k, s in sorted(states.items())
        }
    if faults_by_kind:
        out["device_faults"] = faults_by_kind
    for name in ("fallback_records", "redispatch_records", "oom_shrinks"):
        v = counters.get(name)
        if v:
            out[name] = v
    records_out = counters.get("records_out")
    fb = counters.get("fallback_records")
    if fb and records_out:
        out["fallback_share"] = round(float(fb) / float(records_out), 4)
    suspended = g("checkpoint_suspended")
    if suspended:
        out["checkpoint_suspended"] = suspended
    lost = g("mesh_lost_devices")
    if lost:
        out["mesh_lost_devices"] = lost
    return out or None
