"""Device-fault classification: a sick chip is not a poison record.

PR 12 taught the hot paths to survive *record* poison — a scoring
exception bisects the batch and quarantines the offender. But the
``on_error`` hook saw every exception the same way, and a device OOM,
an XLA runtime error, or a lost chip mid-dispatch would have sent
perfectly clean records to the dead-letter queue (or killed the worker
outright and burned a restart+replay cycle for a fault that a simple
re-dispatch heals). This module is the triage step both hot paths run
FIRST on any dispatch/readback-time exception:

=================  ====================================================
kind               meaning / recovery ladder entry
=================  ====================================================
``device_oom``     the device allocator refused the batch — bisect the
                   *batch size* (never the records) and feed the
                   shrunken cap into the AdaptiveBatcher
                   (serving/overload.py)
``device_error``   a transient XLA internal/runtime failure — re-
                   dispatch the in-flight batch from its host-retained
                   staging copy under the shared full-jitter backoff;
                   persistent streaks trip the circuit breaker
                   (serving/failover.py) onto the host fallback tier
``chip_loss``      the device is gone — escalate to the supervisor
                   (restart with ``FJT_RESTART_STREAK`` context) and,
                   on a mesh, to degraded-mesh mode
                   (parallel/sharding.degraded_mesh)
``None``           not a device fault: record poison, routing bugs,
                   featurize errors — the PR 12 isolation path owns it
=================  ====================================================

Classification is type-gated: only the runtime's own injected device
faults (runtime/faults.py) and the XLA runtime error type
(``jax.errors.JaxRuntimeError``) classify at all — an application
``ValueError`` can never be mistaken for a sick device, and an injected
poison record (a ``ValueError`` subclass) stays poison. Within the XLA type the *kind* comes from the
status-message markers XLA actually emits (``RESOURCE_EXHAUSTED`` /
"out of memory" → OOM; device-lost/halted markers → chip loss;
everything else → transient device error), so the injected faults and
the real errors exercise one classifier.
"""

from __future__ import annotations

import threading
import time
from typing import Optional

from jax.errors import JaxRuntimeError

from flink_jpmml_tpu.obs import recorder as flight

KIND_OOM = "device_oom"
KIND_ERROR = "device_error"
KIND_LOST = "chip_loss"
KINDS = (KIND_OOM, KIND_ERROR, KIND_LOST)

# status markers in XLA runtime error messages (lowercased substring
# match); LOST checks first — a dead chip's message can mention memory
_OOM_MARKERS = ("resource_exhausted", "out of memory", "oom")
_LOST_MARKERS = (
    "device lost", "device_lost", "data_loss", "halted",
    "device unavailable", "failed to connect",
)

def _kind_from_message(msg: str) -> str:
    m = msg.lower()
    for marker in _LOST_MARKERS:
        if marker in m:
            return KIND_LOST
    for marker in _OOM_MARKERS:
        if marker in m:
            return KIND_OOM
    return KIND_ERROR


def classify(exc: BaseException) -> Optional[str]:
    """→ the device-fault kind of ``exc``, or None when it is NOT a
    device fault (record poison, application errors). The one triage
    call both hot paths make before the PR 12 isolation path may run —
    clean records must never be quarantined for a sick device."""
    from flink_jpmml_tpu.runtime import faults

    if isinstance(exc, faults.InjectedChipLoss):
        return KIND_LOST
    if isinstance(exc, faults.InjectedDeviceOOM):
        return KIND_OOM
    if isinstance(exc, faults.InjectedDeviceError):
        return KIND_ERROR
    if isinstance(exc, JaxRuntimeError):
        return _kind_from_message(str(exc))
    return None


# -- shared fault accounting -------------------------------------------------

_EVENT_MIN_PERIOD_S = 1.0
_note_mu = threading.Lock()
# rate limiter PER KIND: a chatty device_error stream must not
# suppress the first (possibly only) device_oom/chip_loss event —
# each fault kind keeps its own flight-event cadence
_last_event: dict = {}


def note(metrics, kind: str, model=None, first_off=None, n=None,
         error=None) -> None:
    """Book one observed device fault: the ``device_fault_total{kind}``
    counter (fleet merge: sum — true fault volume) plus a rate-limited
    ``device_fault`` flight event carrying the active journey's trace
    id when one is set (the fjt-trace pivot). Shared by the block
    path's failover plane, the record engine, and the dynamic scorer so
    the fault kinds cannot drift between them."""
    if metrics is not None:
        metrics.counter(f'device_fault_total{{kind="{kind}"}}').inc()
    now = time.monotonic()
    due = False
    with _note_mu:
        if now - _last_event.get(kind, 0.0) >= _EVENT_MIN_PERIOD_S:
            _last_event[kind] = now
            due = True
    if due:
        from flink_jpmml_tpu.obs import trace as trace_mod

        ctx = trace_mod.current()
        flight.record(
            "device_fault", fault=kind, model=model, first=first_off,
            n=n, error=None if error is None else repr(error),
            trace_id=None if ctx is None else ctx.trace_id,
        )
