"""Overlapped host→device dispatch: the depth-K in-flight window.

The round-5 bench showed the chip idle most of the time (5.8% MFU,
near-zero HBM utilization) because the streaming hot path staged,
dispatched, and blocked on every batch serially.  This module is the
fix's shared core: a bounded **in-flight window** through which every
scoring path (the block pipelines, the dynamic scorer, the bench) runs
its async device dispatches, so that while batch N executes on the
device, batch N+1 is drained from the ring, wire-encoded on the host,
and `jax.device_put` to a fresh staging buffer.  Results are fetched
only when the window is full (or on flush) — never per-batch on the
critical path.

Semantics:

- **FIFO.**  Completions happen strictly in launch order; the pipelines
  rely on this for in-order sink delivery and contiguous offset commits.
- **Bounded.**  At most ``depth`` dispatches are in flight after
  ``launch`` returns; launching into a full window blocks on the oldest
  dispatch (that wait is the *stall* — time the host spent gated on
  device completion — accounted in ``h2d_stall_s``).  With ``depth=2``
  (the default everywhere) staging is double-buffered: the entry being
  executed and the entry being staged each pin one device input buffer,
  and buffer donation (see :meth:`QuantizedScorer.predict_padded`)
  releases the executed entry's staging buffer to the device allocator
  at dispatch — steady-state input allocations stay bounded at the
  window depth instead of accumulating to fetch time.
- **Composable with ring deadlines.**  The dispatcher itself never
  waits for *work to arrive* — only for work it already launched — so
  the fill-or-deadline semantics of ``_PyRing``/``NativeRing``/
  ``BoundedQueue`` drains are untouched: an idle stream still hits its
  idle bound upstream, and the caller flushes the window explicitly.
- **Errors surface where the host blocks.**  An exception raised while
  dispatching propagates out of ``launch``; one raised by the device
  (or the fetch) propagates out of whichever call first waits on that
  entry (``launch`` on a full window, ``finish_oldest``, ``wait``,
  ``flush``, ``close``).  After an error the window keeps its remaining
  entries so a supervisor can still drain or abandon them.
- **Clean shutdown.**  ``close()`` flushes by default; ``abandon()``
  drops un-fetched work (the block pipelines' give-up path — records
  replay from the committed offset on restore, C7 at-least-once).

Metrics (into the shared :class:`MetricsRegistry`):

- ``h2d_stall_s``   — total host time blocked waiting on device work;
- ``dispatches``    — launches through the window;
- ``donation_hits`` — steady-state dispatches whose staged input buffer
  was donated to (consumed by) the jitted call, incremented by the
  callers that stage (see ``BlockPipelineBase._dispatch_bound``);
- ``inflight_depth`` gauge — current and high-water in-flight depth.

``profiling.overlap_stats`` turns these into the bench's
``overlap_efficiency`` / ``h2d_stall_ms`` artifact fields.
"""

from __future__ import annotations

import time
import weakref
from collections import deque
from typing import Any, Callable, Optional

import jax
import numpy as np

from flink_jpmml_tpu.obs import attr as attr_mod
from flink_jpmml_tpu.obs import drift as drift_mod
from flink_jpmml_tpu.obs import profiler as prof_mod
from flink_jpmml_tpu.obs import recorder as flight
from flink_jpmml_tpu.runtime import faults
from flink_jpmml_tpu.utils.exceptions import FlinkJpmmlTpuError
from flink_jpmml_tpu.utils.metrics import MetricsRegistry


def _prefetch_host(out) -> None:
    """Queue the D2H copies for a dispatched batch NOW, so the sink's
    later ``np.asarray`` finds the data already on the host.  Without
    this the copy is first issued inside the sink's blocking fetch, so
    every batch pays the device→host round trip serially."""
    for leaf in jax.tree_util.tree_leaves(out):
        fn = getattr(leaf, "copy_to_host_async", None)
        if fn is not None:  # numpy fallback leaves are host-resident
            fn()


def _block_ready(out) -> None:
    """Wait for every device leaf of ``out`` (host leaves pass through).

    Uses the leaves' own ``block_until_ready`` so test doubles and
    numpy fallbacks compose; device-side errors raise here."""
    for leaf in jax.tree_util.tree_leaves(out):
        fn = getattr(leaf, "block_until_ready", None)
        if fn is not None:
            fn()


def _is_ready(out) -> bool:
    """Non-blocking probe: would :func:`_block_ready` return instantly?

    Leaves without an ``is_ready`` (numpy, test doubles) count as
    ready — only a device leaf that reports itself in flight makes the
    whole value not-ready."""
    for leaf in jax.tree_util.tree_leaves(out):
        fn = getattr(leaf, "is_ready", None)
        if fn is not None and not fn():
            return False
    return True


class DispatcherClosed(FlinkJpmmlTpuError):
    """launch() after close(): the window is shut down."""


# shape regexes whose inert donation warning is already silenced (see
# filter_donate_warning)
_DONATE_WARN_FILTERED: set = set()


def filter_donate_warning(shape_re: str) -> None:
    """One-shot, NARROW silencing of XLA's "donated buffers were not
    usable" warning for a wire batch shape that can never output-alias
    its scores (the uint8/uint16 rank wire, or the fused path's raw
    f32 [B, F] batch): the donation still frees the staging buffer to
    the device allocator at dispatch, so the warning is inert — but
    only for these shapes; an application's own actionable donation
    warnings stay visible. Shared by the block pipelines' uint-wire
    filter and the fused dispatch path (one mechanism, one message
    shape to keep in sync with XLA)."""
    if shape_re in _DONATE_WARN_FILTERED:
        return
    import warnings

    warnings.filterwarnings(
        "ignore",
        message=(
            r"Some donated buffers were not usable: ShapedArray\("
            + shape_re
        ),
    )
    _DONATE_WARN_FILTERED.add(shape_re)
    # once per shape, so a postmortem can see which donation warnings
    # this process decided were inert (and when)
    flight.record("donation_warning_filtered", shape_re=shape_re)


# per-registry (encode_s, h2d_bytes) counter pairs: resolving through
# the registry lock on every per-batch dispatch is avoidable hot-path
# work; weak keys let ephemeral bench registries die normally
_WIRE_COUNTERS: "weakref.WeakKeyDictionary" = weakref.WeakKeyDictionary()


def _wire_counters(metrics: MetricsRegistry):
    pair = _WIRE_COUNTERS.get(metrics)
    if pair is None:
        pair = (metrics.counter("encode_s"), metrics.counter("h2d_bytes"))
        _WIRE_COUNTERS[metrics] = pair
    return pair


def dispatch_quantized(
    q,
    X,
    M=None,
    *,
    donate: bool = False,
    metrics: Optional[MetricsRegistry] = None,
    donation_hits=None,
    state=None,
    state_keys=None,
    offsets=None,
    plan=None,
):
    """Featurize + stage + async-dispatch one raw f32 batch through a
    :class:`~flink_jpmml_tpu.compile.qtrees.QuantizedScorer` — the ONE
    place the autotuned encode-placement decision (``q.encode_mode``)
    is enacted, shared by the block pipelines, the dynamic scorer, and
    every bench mode:

    - ``"host"`` (default, the byte-parity oracle): the C++ bucketizer
      rank-encodes on the host and the uint8/uint16 codes ship;
    - ``"fused"``: the raw f32 batch ships and the threshold-rank
      bucketize runs on-device as an XLA pre-stage traced into the
      scoring jit — one dispatch covers encode+pad+score.

    ``M`` is an optional explicit missing mask (the dynamic scorer's
    record path); the fused stage understands only the NaN convention,
    so the mask folds in as NaN before staging.

    Two counters land in ``metrics`` (→ the bench's ``encode_ms`` /
    ``h2d_bytes_per_record`` fields via ``profiling.wire_stats``):
    ``encode_s`` — host featurize+align time on the dispatch path (≈0
    when fused); ``h2d_bytes`` — bytes staged per dispatch (F uint
    codes per record on the host path, 4·F f32 on the fused path).

    ``donate=True`` stages via an explicit ``jax.device_put`` and
    donates the staging buffer to the jitted call (released to the
    device allocator at dispatch, not pinned until fetch);
    ``donation_hits`` counts dispatches whose buffer was actually
    consumed.

    ``state`` arms the keyed state stage (runtime/state.py): the batch
    additionally gathers/updates the table's device buffer inside the
    SAME dispatch and the return becomes ``(out, derived[B, 8])``
    (callers unwrap via ``state.is_state_output``). ``state_keys`` are
    precomputed uint32 key hashes (default: hash the table's key
    column of ``X``); ``offsets`` are the records' ring offsets —
    the decay clock and the exactly-once replay guard, and, state or
    none, the ``first_off`` this dispatch's ``encode``/``route``/``h2d``
    spans carry (obs/attr.py). Unarmed cost is one ``is None`` check.

    A table over a mesh (``state.n_shards > 1``) is folded by the mesh
    form of the program: the records go out sorted by owning chip
    (runtime/shuffle.py; span ``shard``). A pipeline routes and cuts
    its dispatch beforehand and hands the ``plan``
    (``KeyShuffle.take``): scores and derived rows then come back in
    bucket order, for ``plan.unshard`` at readback. Without one, all of
    ``X`` is routed and bucketed here, in as many chunks a chip as the
    fullest needs, and what is returned is in offset order, ``n``
    long."""
    if state is not None and q.mesh is not state.mesh:
        # a caller that holds the one-chip scorer (a pipeline binds the
        # twin that spans the table's mesh when it is built)
        q = q.on_mesh(state.mesh)
    enc, h2d = (
        _wire_counters(metrics) if metrics is not None else (None, None)
    )
    # per-batch stage attribution (obs/attr.py): one span a stage, and
    # the same registry's stage_seconds{stage=...} histograms merge
    # fleet-wide like every other metric
    ledger = attr_mod.ledger_for(metrics) or attr_mod.UNBOOKED
    offs = np.asarray(offsets, np.int64) if offsets is not None else None
    # the dispatch's identity on its encode/route/h2d spans: the
    # (first_off, n) key the journey plane and the sink already use
    ident = {"n": len(X)}
    if offs is not None and offs.size:
        ident["first_off"] = int(offs[0])
    # data-drift profiling (obs/drift.py) on the RAW batch, before any
    # encode touches it: None + one env lookup when FJT_DRIFT_SAMPLE is
    # unset (the pinned zero-records contract); rate-limited + overhead-
    # budgeted when armed. Outside the encode span below so encode_s /
    # the encode stage stay honest.
    dplane = drift_mod.plane_for(metrics)
    if dplane is not None:
        dplane.record_features(q, X, M)
    fused = getattr(q, "encode_mode", "host") == "fused" and q.supports_fused
    # a planned dispatch's rows are laid out by the plan (bucket order,
    # pad rows and all): no alignment padding before it
    planned = plan is not None
    # encode covers featurize+align
    with ledger.span(
        "encode", fused=fused, layout=getattr(q, "layout", "ref"), **ident
    ) as sp:
        if fused:
            owned = False  # does X already sit in a buffer only we hold?
            if M is not None and np.asarray(M).any():
                X = np.where(M, np.nan, np.asarray(X, np.float32))
                owned = True
            payload, K = (
                (np.ascontiguousarray(X, np.float32), 1) if planned
                else q.pad_f32(X)
            )
            if payload is X and not owned and not planned:
                # an unpadded f32-contiguous batch passes through
                # pad_f32 unchanged, and the caller's array may alias a
                # REUSED ring drain buffer — which jax's CPU backend can
                # zero-copy alias straight into the async dispatch,
                # letting the next drain overwrite an in-flight batch.
                # The host path never hits this (wire.encode always
                # allocates); the fused path must ship a private copy.
                # (One memcpy per batch — the same cost the ring drain
                # itself pays.)
                payload = np.array(payload, copy=True)
            predict = q.predict_fused_padded
        else:
            # layout-aware staging: pad_wire routes the codes through
            # the scorer's adopted wire packing (compile/layouts.py
            # WirePack) when the kernel search chose one, so the staged
            # payload, h2d_bytes, and the donation accounting all see
            # the packed wire without any per-call-site knowledge
            Xq = q.wire.encode(X, M)
            payload, K = (
                (q.pack_wire(Xq), 1) if planned else q.pad_wire(Xq)
            )
            predict = q.predict_padded
    if enc is not None:
        enc.inc(sp.seconds)
    st_args = None
    sharded = unplanned = False
    if state is not None:
        sharded, unplanned = state.n_shards > 1, plan is None
        if unplanned:
            # keyed state routing (host-side slot assignment; the state
            # gather/update itself is traced into the dispatch below) —
            # one native pass per batch for the keys that are resident,
            # numpy rounds for the rest; no per-record Python
            with ledger.span("route", **ident):
                khash = (
                    np.asarray(state_keys, np.uint32)
                    if state_keys is not None
                    else state.hash_block(X)
                )
                state.maybe_renorm(ident.get("first_off", state.applied_hi))
                slots, reset, rel, w = state.assign_slots(khash, offs)
                pad = payload.shape[0] - ident["n"]
                if pad > 0 and not sharded:
                    # alignment rows ride the scratch slot with zero
                    # weight — by construction they cannot touch any
                    # key's state
                    slots = np.concatenate(
                        [slots, np.full(pad, state.scratch, np.int32)]
                    )
                    reset = np.concatenate([reset, np.zeros(pad, bool)])
                    rel = np.concatenate([rel, np.zeros(pad, np.float32)])
                    w = np.concatenate([w, np.zeros(pad, np.float32)])
                st_args = (slots, rel, w, reset)
        if sharded:
            # the table lies over a mesh: records, codes and routing
            # operands go out sorted by owning chip (runtime/shuffle.py)
            with ledger.span("shard", cut=bool(plan and plan.cut), **ident):
                if unplanned:
                    from flink_jpmml_tpu.runtime import shuffle

                    plan = shuffle.make_plan(
                        state, slots, reset, rel, w, q.batch_size or 256,
                        first_off=ident.get("first_off", 0),
                    )
                elif plan.applied_hi is not None:
                    state.mark_applied(plan.applied_hi)
                # the codes in bucket order; K counts a chip's chunks
                payload = plan.place(payload)
                K = plan.chunks if q.backend == "pallas" else 1
                st_args = (plan.slots, plan.rel, plan.w, plan.reset)
        predict_state = (
            q.predict_fused_padded_state if fused
            else q.predict_padded_state
        )
    if h2d is not None:
        h2d.inc(payload.nbytes)
    # h2d: the host-side staging + async dispatch issue, and nothing
    # else; its two halves are children on the profiler's clock only
    staged = None
    with ledger.span("h2d", bytes=payload.nbytes, **ident):
        if donate:
            if fused:
                filter_donate_warning(rf"float32\[\d+,{payload.shape[1]}\]")
            if st_args is not None:
                # the state buffer donates alongside the batch: its
                # update is in-place on device (one [rows, 8] buffer in
                # steady state)
                filter_donate_warning(r"float32\[\d+,8\]")
                if not fused:
                    # the uint wire payload rides the same donated call
                    # and can never output-alias its scores — the same
                    # inert warning the block pipelines' uint-wire
                    # filter suppresses
                    filter_donate_warning(
                        rf"uint(?:8|16)\[\d+,{payload.shape[1]}\]"
                    )
            with attr_mod.trace_only("h2d.put"):
                # async H2D staging copy (a mesh: each chip its rows)
                payload = staged = q.stage(payload)
        kw = {"donate": True} if donate else {}
        with attr_mod.trace_only("h2d.launch"):
            if st_args is None:
                out = predict(payload, K, **kw)  # async dispatch
            else:
                out, derived, S2 = predict_state(
                    payload, K, state, *st_args, **kw
                )
                state.commit(S2)
                if sharded and unplanned:
                    # nobody downstream holds the plan: offset order here
                    out, derived = jax.tree_util.tree_map(
                        lambda a: a[plan.dest], (out, derived)
                    )
                out = (out, derived)
    if staged is not None and donation_hits is not None:
        deleted = getattr(staged, "is_deleted", None)
        if deleted is not None and deleted():
            donation_hits.inc()
    return out


class _InFlight:
    """One launched dispatch: its (lazy) result + caller metadata.

    ``done`` means the entry left the window; ``error`` carries the
    fetch failure when it left poisoned — a later ``wait`` re-raises it
    instead of handing back a never-synchronized result."""

    __slots__ = (
        "out", "meta", "t_launch", "done", "error", "accounted", "ident",
    )

    def __init__(self, out: Any, meta: Any, t_launch: float,
                 accounted: bool = True, ident: Optional[dict] = None):
        self.out = out
        self.meta = meta
        # what the wait on this entry carries on its span (the block
        # pipelines pass the dispatch's first_off and n)
        self.ident = ident or {}
        self.t_launch = t_launch
        self.done = False
        self.error: Optional[BaseException] = None
        # False for shed no-op entries (no device work was launched):
        # the device-readback fault hook must not fire for them
        self.accounted = accounted


class OverlappedDispatcher:
    """Bounded FIFO window of in-flight async device dispatches.

    ``complete(out, meta)`` (optional) runs on the launching thread for
    every finished entry, in launch order — the block pipelines hang
    sink delivery + offset commit on it.  ``finish_oldest``/``wait``
    also *return* the finished entries for callers (the dynamic scorer)
    that prefer pull-style completion.
    """

    def __init__(
        self,
        depth: Optional[int] = 2,
        metrics: Optional[MetricsRegistry] = None,
        complete: Optional[Callable[[Any, Any], None]] = None,
        profiler: Optional["prof_mod.DeviceProfiler"] = None,
        on_error: Optional[Callable[[Any, Any, Exception], bool]] = None,
    ):
        # depth = dispatches allowed to REMAIN in flight after launch
        # returns; 0 = synchronous (each launch finishes its own batch —
        # the latency operating point, no completion window to hide in);
        # None = unbounded (launch NEVER blocks — for callers whose own
        # contract forbids blocking in submit, e.g. the dynamic scorer:
        # they still get prefetch, FIFO completion, and stall metrics,
        # and bound the window themselves via finish/wait)
        self._depth = None if depth is None else max(0, int(depth))
        self._window: "deque[_InFlight]" = deque()
        self._complete = complete
        # on_error(out, meta, exc) -> bool: called on the launching
        # thread when fetching an entry raised an *Exception* (never a
        # KeyboardInterrupt/SystemExit). True = handled — the error is
        # swallowed, the complete-callback is skipped, and the caller's
        # loop continues; False/None = re-raise as before. The block
        # pipelines hang record-level poison isolation (suspect-mode
        # bisection → DLQ) on this hook, so one bad record stops
        # killing the worker.
        self._on_error = on_error
        self._closed = False
        self.metrics = metrics or MetricsRegistry()
        self._stall = self.metrics.counter("h2d_stall_s")
        self._dispatches = self.metrics.counter("dispatches")
        # launches that found the window FULL and blocked (depth > 0
        # only: a depth-0 synchronous window finishes every batch by
        # design, which is the latency operating point, not saturation).
        # window_full_launches / dispatches over a tick interval is the
        # "window-full fraction" input to the composite backpressure
        # score (obs/pressure.py).
        self._window_full = self.metrics.counter("window_full_launches")
        self._gauge = self.metrics.gauge("inflight_depth")
        # attribution + sampled device profiling (obs/attr.py,
        # obs/profiler.py): the per-registry singletons, so every path
        # sharing this registry lands in one stage ledger / one set of
        # live roofline gauges
        self._ledger = attr_mod.ledger_for(self.metrics)
        self._profiler = (
            profiler if profiler is not None
            else prof_mod.profiler_for(self.metrics)
        )

    # -- introspection -----------------------------------------------------

    @property
    def profiling(self) -> bool:
        """True when launches should build a dispatch profile — a
        sampled device profiler is attached and not disabled, so call
        sites can skip the per-launch profile build entirely when
        FJT_PROF_SAMPLE is off."""
        p = self._profiler
        return p is not None and p.enabled

    def __len__(self) -> int:
        return len(self._window)

    @property
    def depth(self) -> Optional[int]:
        return self._depth

    @property
    def closed(self) -> bool:
        return self._closed

    # -- core --------------------------------------------------------------

    def launch(
        self,
        dispatch_fn: Callable[[], Any],
        meta: Any = None,
        profile: Optional[dict] = None,
        accounted: bool = True,
        ident: Optional[dict] = None,
    ) -> _InFlight:
        """Dispatch asynchronously and admit the result to the window.

        ``dispatch_fn()`` must *dispatch* device work and return without
        blocking on it (the JAX async-dispatch contract).  If admitting
        the new entry overflows ``depth``, the oldest entry is finished
        first — the only place a healthy steady state ever blocks; the
        ledger books that wait as ``queue_wait`` (a ready batch waiting
        for a window slot) rather than ``readback``.

        ``profile`` (see :func:`obs.attr.dispatch_profile`) opts this
        launch into the sampled device-timing pool: when the profiler's
        rate limiter fires, the window is drained and the *post-dispatch*
        wait is bracketed with ``block_until_ready`` — dispatch_fn's own
        host time (featurize/staging) is excluded, so the delta is pure
        device execution, feeding the live
        ``device_mfu``/``device_membw_util`` gauges and the kernel cost
        ledger. Unsampled launches pay one predicate check.

        ``ident`` (``{"first_off": ..., "n": ...}``) names this
        dispatch on the span of whoever later waits on it
        (``queue_wait``/``readback``).

        ``accounted=False`` keeps this entry out of the ``dispatches``
        and window-full counters: the admission controller's SHED
        no-ops ride the window only for FIFO offset commits — counting
        them as dispatches would dilute the pressure monitor's
        window-full fraction (real-dispatch denominator) exactly while
        the shed rate is highest, flapping the gate open mid-overload.
        """
        if self._closed:
            raise DispatcherClosed("launch() on a closed dispatcher")
        # device-dispatch delay + launch-time device-fault injection
        # (runtime/faults.py): each a global load + None check when no
        # faults are configured. A device fault raised HERE propagates
        # out of launch to the caller's direct-dispatch handler —
        # classified by runtime/devfault.py, never quarantined as
        # record poison
        faults.fire("dispatch")
        faults.fire("device_dispatch")
        ident = ident or {}
        prof = self._profiler
        sampling = (
            prof is not None
            and profile is not None
            and prof.should_sample()
        )
        if sampling:
            # drain so the bracket times THIS dispatch, not the tail of
            # whatever the device was already running (entries stay in
            # the window: FIFO completion/callbacks are untouched). The
            # sampler's two waits are the prof_sample stage: the bubble
            # it puts between host and device has a name of its own
            with self._ledger.span(
                "prof_sample", wait="drain", **ident
            ) as drained:
                try:
                    for h in self._window:
                        _block_ready(h.out)
                except Exception:
                    # a poisoned in-flight batch: its error belongs to
                    # finish_oldest (right meta, right caller) — this
                    # launch just forfeits its sample
                    sampling = False
        if sampling:
            # dispatch_fn books its own encode/route/h2d, outside both
            # prof_sample intervals: its host work (featurize/staging
            # on the host-encode path) happens BEFORE the device kernel
            # is queued, so folding it into the bracket would book host
            # time as device time — inflating device_ns_per_record,
            # poisoning the kernel cost ledger, and double-booking the
            # interval dispatch_quantized already attributed
            out = dispatch_fn()
            with self._ledger.span(
                "prof_sample", wait="bracket", **ident
            ) as bracket:
                try:
                    _block_ready(out)
                except Exception:
                    sampling = False  # the finish path re-raises with
                    # attribution
            if sampling:
                # overhead = drain + bracket wait; dispatch_fn's own
                # host time is work the caller pays regardless, so it
                # must not eat the sampling budget
                prof.record_sample(
                    bracket.seconds,
                    profile,
                    overhead_s=drained.seconds + bracket.seconds,
                )
        else:
            out = dispatch_fn()
        _prefetch_host(out)
        handle = _InFlight(
            out, meta, time.monotonic(), accounted=accounted, ident=ident
        )
        self._window.append(handle)
        if accounted:
            self._dispatches.inc()
        if (
            accounted
            and self._depth is not None
            and self._depth > 0
            and len(self._window) > self._depth
            # a healthy overlapped pipeline's steady state is a window
            # trimmed to exactly depth, so overshoot alone is not
            # saturation — count only launches whose oldest entry is
            # still in flight, i.e. the trim below will actually block
            and not _is_ready(self._window[0].out)
        ):
            self._window_full.inc()
        while self._depth is not None and len(self._window) > self._depth:
            # depth 0 (the latency operating point) has no window for a
            # ready batch to wait in: this wait is the host blocking on
            # its OWN just-dispatched batch, i.e. readback — booking it
            # as queue_wait would tell the operator "window too shallow"
            # (and fire stage_stall events) on every batch of a normal
            # synchronous pipeline
            self.finish_oldest(
                _stage="queue_wait" if self._depth > 0 else "readback"
            )
        # gauge records post-enforcement depth: the window's steady
        # occupancy, not the transient overshoot inside this call
        self._gauge.set(len(self._window))
        return handle

    def finish_oldest(self, _stage: str = "readback"):
        """Finish (wait + complete-callback) the oldest in-flight entry.

        → ``(out, meta)`` or None when the window is empty.  Safe to
        call from pipeline hooks while a batch is held. ``_stage`` is
        the attribution bucket for the blocking wait — ``launch`` books
        its overflow waits as ``queue_wait`` so one wall-clock interval
        is never attributed to two stages."""
        if not self._window:
            return None
        handle = self._window[0]
        error: Optional[BaseException] = None
        # ONLY the blocking wait is booked, and under the caller's
        # stage — launch's overflow loop passes queue_wait, every other
        # caller is a readback; the complete-callback below books its
        # own time (sink), so one wall-clock interval never lands in
        # two stages. On the trace: how long the host sat on the oldest
        # dispatch, and how deep the window was
        with self._ledger.span(
            _stage, inflight=len(self._window), **handle.ident
        ) as sp:
            try:
                # readback-time device-fault injection: raises inside
                # the same try as the real fetch, so an injected device
                # error takes exactly the real error path (handle.error
                # + on_error classification); shed no-ops
                # (accounted=False) launched no device work and are
                # skipped
                if handle.accounted:
                    faults.fire("device_readback")
                _block_ready(handle.out)
            except BaseException as e:
                handle.error = e  # wait() on this handle re-raises,
                # never returns the unsynchronized result as if it
                # completed
                error = e
        # stall time counts even when the wait raised: the host WAS
        # gated on the device for that long either way
        self._stall.inc(sp.seconds)
        # the entry leaves the window regardless — a poisoned batch
        # must not wedge every later flush
        self._window.popleft()
        handle.done = True
        self._gauge.set(len(self._window))
        if error is not None:
            if (
                self._on_error is not None
                and isinstance(error, Exception)
                and self._on_error(handle.out, handle.meta, error)
            ):
                # handled (e.g. isolated to the DLQ): no complete
                # callback — the handler owns delivery + commit
                return None
            raise error
        if self._complete is not None:
            self._complete(handle.out, handle.meta)
        return handle.out, handle.meta

    def wait(self, handle: _InFlight) -> Any:
        """Finish entries in FIFO order until ``handle`` is done; → its
        (fetched) result.  A handle already finished returns at once; a
        handle whose fetch FAILED re-raises its error on every wait.
        The synchronized-or-raise guarantee holds even for a handle the
        window no longer tracks (e.g. dropped by :meth:`abandon`): it is
        fetched directly rather than handed back unsynchronized."""
        while not handle.done and self._window:
            self.finish_oldest()
        if not handle.done:
            sp = self._ledger.begin("readback", **handle.ident)
            try:
                if handle.accounted:
                    faults.fire("device_readback")
                _block_ready(handle.out)
            except BaseException as e:
                handle.error = e
                raise
            finally:
                self._stall.inc(sp.end())
                handle.done = True
        if handle.error is not None:
            raise handle.error
        return handle.out

    def flush(self) -> None:
        """Finish everything in flight (the drain-on-close protocol)."""
        while self._window:
            self.finish_oldest()

    def abandon(self) -> int:
        """Drop all in-flight entries without fetching; → count dropped.

        The block pipelines' bounded give-up: abandoned batches simply
        replay from the committed offset on restore (at-least-once)."""
        n = len(self._window)
        self._window.clear()
        self._gauge.set(0)
        if n:  # a give-up is exactly what a postmortem wants to see
            flight.record("dispatch_abandon", dropped=n)
        return n

    def close(self, drain: bool = True) -> None:
        """Shut the window down: flush (default) or abandon, then
        refuse further launches.  Idempotent."""
        if drain:
            self.flush()
        else:
            self.abandon()
        self._closed = True
