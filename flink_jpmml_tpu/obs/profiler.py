"""Continuous device profiling: sampled kernel timing → live roofline.

``device_mfu`` and ``device_membw_util`` existed only as one-shot bench
artifact fields — a production pipeline could not say whether the chip
was busy. This module makes them **live**: a rate-limited sampler
measures true device execution time with a block-until-ready delta
pair around a dispatch (drain the in-flight window, stamp, dispatch,
block, stamp), and from the sample stream derives per-registry gauges

- ``device_mfu``          — achieved FLOP/s over the chip's bf16 peak,
- ``device_membw_util``   — achieved HBM stream bytes/s over peak,
- ``flops_per_record``    — the analytic cost model's FLOPs/record,
- ``device_ns_per_record``— smoothed measured device time per record,

plus a ``stage_seconds{stage="device"}`` histogram entry per sample
(the attribution plane's sampled device column). Sampling serializes
the window for the sampled batch, so it is **rate-limited twice**: at
most once per ``FJT_PROF_SAMPLE`` seconds (default 1.0; ``0``/``off``
disables), and never past an accumulated-overhead budget of 1% of wall
clock — the perf-smoke tripwire pins total attribution overhead <2%.

Each sample also lands in the **kernel cost ledger**: per
``(model, backend)`` the observed device-seconds/record next to the
analytic FLOP/byte model — persisted as JSON beside the autotune cache
(``kernel_costs.json``), the training data ROADMAP item 2's
predict-then-verify cost model needs.

Chip peaks are one table keyed by ``device_kind``. An unknown kind (a
CPU test run, a new chip) has no roofline: the ``device_mfu`` /
``device_membw_util`` gauges are not registered at all, unless
``FJT_PROF_PEAKS=flops,bytes`` supplies the chip's real peaks. The
bench artifact reads the table alone (``chip_peaks(strict=True)``).
"""

from __future__ import annotations

import json
import os
import threading
import time
import weakref
from typing import Callable, Dict, Optional, Tuple

from flink_jpmml_tpu.utils.metrics import MetricsRegistry

_SAMPLE_ENV = "FJT_PROF_SAMPLE"
_PEAKS_ENV = "FJT_PROF_PEAKS"
_DEFAULT_INTERVAL_S = 1.0
_OVERHEAD_BUDGET = 0.01  # ≤1% of wall clock spent inside samples
_EWMA_ALPHA = 0.3  # smoothing for the per-record device time
# prediction drift band (PR 8's capacity_reestimated pattern): observed
# device cost outside [pred/band, pred·band] for this many consecutive
# samples means the adopted kernel config's prediction went stale —
# invalidate the cost-model fit and clear the model's autotune entry so
# the next warmup re-searches
_PRED_BAND = 1.75
_PRED_STRIKES = 3

# chip peaks (device_kind substring → (bf16 peak FLOP/s, HBM bytes/s));
# shared with bench.py's roofline fields
CHIP_PEAKS = (
    ("v5 lite", (197e12, 819e9)),  # v5e
    ("v5e", (197e12, 819e9)),
    ("v4", (275e12, 1228e9)),
    ("v5p", (459e12, 2765e9)),
)


def chip_peaks(
    device_kind: str, strict: bool = False
) -> Optional[Tuple[float, float]]:
    """(bf16 peak FLOP/s, HBM bytes/s) for a device kind, or None for
    a kind the table does not know — unless, outside ``strict``,
    ``FJT_PROF_PEAKS`` supplies the peaks."""
    kind = (device_kind or "").lower()
    for sub, peaks in CHIP_PEAKS:
        if sub in kind:
            return peaks
    if strict:
        return None
    raw = os.environ.get(_PEAKS_ENV)
    if raw:
        try:
            f, b = (float(x) for x in raw.split(","))
            if f > 0 and b > 0:
                return (f, b)
        except ValueError:
            pass
    return None


def roofline(
    dev_rate: float,
    flops_per_record: Optional[float],
    bytes_per_record: Optional[float],
    peaks: Optional[Tuple[float, float]],
) -> Tuple[Optional[float], Optional[float]]:
    """→ (mfu, membw_util) for a measured device record rate against a
    chip's peaks; None fields where the cost model or peaks are
    unknown."""
    if peaks is None or dev_rate <= 0:
        return None, None
    flop_peak, membw_peak = peaks
    mfu = (
        dev_rate * flops_per_record / flop_peak
        if flops_per_record else None
    )
    membw = (
        dev_rate * bytes_per_record / membw_peak
        if bytes_per_record else None
    )
    return mfu, membw


def _device_kind() -> str:
    try:
        import jax

        return getattr(jax.devices()[0], "device_kind", "") or ""
    except Exception:
        return ""


# ---------------------------------------------------------------------------
# Kernel cost ledger (persisted next to the autotune cache)
# ---------------------------------------------------------------------------


def cost_ledger_path() -> str:
    """``kernel_costs.json`` in the autotune cache's directory — the
    measured-cost training data lives next to the measured-config
    cache it feeds (compile/costmodel.py)."""
    from flink_jpmml_tpu.compile import autotune

    p = autotune.cache_path()
    return str(p.parent / "kernel_costs.json")


def _read_entries(path: str) -> Dict[str, dict]:
    """Parse one ledger file → entries dict; {} on any problem (the
    corrupt-tolerant contract every cache-dir artifact follows)."""
    try:
        with open(path) as f:
            data = json.load(f)
        entries = data.get("entries")
        if isinstance(entries, dict):
            return {
                k: v for k, v in entries.items() if isinstance(v, dict)
            }
    except (OSError, ValueError, AttributeError):
        pass
    return {}


def read_ledger(path: Optional[str] = None) -> Dict[str, dict]:
    """Merge-on-load entry point for ledger consumers (the cost model's
    training replay, tooling): the on-disk entries as written by ANY
    process — each writer merges entry-wise (newest ``ts`` wins per
    key), so a reader never sees one bench process's view clobbering a
    sibling's."""
    if path is None:
        try:
            path = cost_ledger_path()
        except Exception:
            return {}
    return _read_entries(path)


def _merge_entries(
    disk: Dict[str, dict], mine: Dict[str, dict]
) -> Dict[str, dict]:
    """Entry-wise union: unknown keys survive from either side; for a
    shared key the newer ``ts`` wins (two sibling processes sampling
    the same (model, backend, variant) converge on the freshest EWMA
    instead of last-writer-wins clobbering)."""
    out = dict(disk)
    for k, e in mine.items():
        cur = out.get(k)
        if cur is None or float(e.get("ts") or 0) >= float(
            cur.get("ts") or 0
        ):
            out[k] = e
    return out


def _platform() -> str:
    """The jax platform string, resolved once per process — stamped
    into ledger rows so a cost-model fit can filter CPU-interpret
    timings out of a TPU fit."""
    global _PLATFORM
    if _PLATFORM is None:
        try:
            import jax

            _PLATFORM = jax.default_backend()
        except Exception:
            _PLATFORM = "unknown"
    return _PLATFORM


_PLATFORM: Optional[str] = None


class KernelCostLedger:
    """Observed device cost per (model, backend) vs the analytic model.

    Every profiler sample updates one entry (EWMA of device
    seconds/record, sample count, last batch shape, the analytic
    flops/bytes per record); entries persist through the same
    corrupt-tolerant atomic-replace JSON discipline as the autotune
    cache, rate-limited to one write per ``flush_interval_s``."""

    def __init__(
        self,
        path: Optional[str] = None,
        flush_interval_s: float = 5.0,
        clock: Callable[[], float] = time.monotonic,
    ):
        self._path = path
        self._flush_interval = flush_interval_s
        self._clock = clock
        self._mu = threading.Lock()
        self._entries: Dict[str, dict] = {}
        self._dirty = False
        self._last_flush = 0.0

    def _resolve_path(self) -> Optional[str]:
        if self._path is None:
            try:
                self._path = cost_ledger_path()
            except Exception:
                return None
        return self._path

    def update(
        self,
        model: Optional[str],
        backend: Optional[str],
        device_s: float,
        records: int,
        flops_per_record: Optional[float],
        bytes_per_record: Optional[float],
        variant: Optional[str] = None,
        features: Optional[dict] = None,
        predicted: Optional[float] = None,
    ) -> None:
        """Fold one measured (device_s, records) pair into the entry
        for (model, backend[, variant]).

        ``variant``/``features`` are the kernel-search extension: a
        per-variant row whose feature dict is a training sample for
        the learned cost model (compile/costmodel.py);
        ``predicted`` records the model's prediction at measurement
        time, so the row carries its own residual."""
        if not records or device_s <= 0:
            return
        key = f"{model or 'unknown'}|{backend or 'unknown'}"
        if variant:
            key = f"{key}|{variant}"
        per_rec = device_s / records
        with self._mu:
            e = self._entries.get(key)
            if e is None:
                e = self._entries[key] = {
                    "model": model, "backend": backend,
                    "device_s_per_record": per_rec, "samples": 0,
                }
            else:
                e["device_s_per_record"] = (
                    (1.0 - _EWMA_ALPHA) * e["device_s_per_record"]
                    + _EWMA_ALPHA * per_rec
                )
            e["samples"] += 1
            e["last_batch"] = int(records)
            e["last_device_s"] = round(device_s, 9)
            e["flops_per_record"] = flops_per_record
            e["bytes_per_record"] = bytes_per_record
            e["rec_s"] = round(records / device_s, 1)
            e["platform"] = _platform()
            if variant:
                e["variant"] = variant
            if isinstance(features, dict) and features:
                e["features"] = dict(features)
            if predicted is not None and predicted > 0:
                e["predicted_s_per_record"] = predicted
                e["pred_err"] = round(
                    abs(per_rec - predicted) / predicted, 4
                )
            e["ts"] = time.time()
            self._dirty = True
            now = self._clock()
            due = now - self._last_flush >= self._flush_interval
            if due:
                self._last_flush = now
        if due:
            self.flush()

    def entries(self) -> Dict[str, dict]:
        with self._mu:
            return {k: dict(v) for k, v in self._entries.items()}

    def flush(self) -> None:
        """Merge-write this process's entries into the on-disk ledger.

        Concurrency discipline (two bench processes flushing at once
        used to last-writer-wins clobber each other's entries): the
        whole read→merge→replace runs under an exclusive ``flock`` on
        a sidecar lock file, the merge is entry-wise (newest ``ts``
        wins per key, unknown keys union), and the write itself is the
        PR 8 checkpoint protocol — temp file, fsync, ``os.replace``,
        best-effort directory fsync. Any I/O or parse failure is
        silent — a read-only cache dir must not break serving."""
        path = self._resolve_path()
        if path is None:
            return
        with self._mu:
            if not self._dirty:
                return
            mine = {k: dict(v) for k, v in self._entries.items()}
            self._dirty = False
        lock = None
        try:
            import fcntl

            os.makedirs(os.path.dirname(path), exist_ok=True)
            lock = open(f"{path}.lock", "w")
            fcntl.flock(lock, fcntl.LOCK_EX)
        except (ImportError, OSError):
            # no flock (non-posix / read-only dir): the atomic replace
            # below still guarantees readers never see a torn file
            if lock is not None:
                lock.close()
                lock = None
        from flink_jpmml_tpu.utils.diskio import atomic_write_json

        try:
            merged = _merge_entries(_read_entries(path), mine)
            atomic_write_json(path, {"version": 1, "entries": merged})
        finally:
            if lock is not None:
                try:
                    lock.close()  # closing releases the flock
                except OSError:
                    pass


# ---------------------------------------------------------------------------
# The sampler
# ---------------------------------------------------------------------------


class DeviceProfiler:
    """Rate-limited device-time sampler feeding live roofline gauges.

    The :class:`~flink_jpmml_tpu.runtime.pipeline.OverlappedDispatcher`
    consults :meth:`should_sample` per launch; on a sample it drains
    its window, brackets the dispatch with ``block_until_ready``, and
    hands the delta to :meth:`record_sample` together with the launch
    site's :func:`~flink_jpmml_tpu.obs.attr.dispatch_profile`."""

    def __init__(
        self,
        metrics: MetricsRegistry,
        interval_s: Optional[float] = None,
        overhead_budget: float = _OVERHEAD_BUDGET,
        clock: Callable[[], float] = time.monotonic,
        cost_ledger: Optional[KernelCostLedger] = None,
    ):
        # weak for the same reason as attr.StageLedger: the _PROFILERS
        # cache keys weakly on the registry, so a strong back-ref here
        # would pin every registry for process lifetime
        self._metrics_ref = weakref.ref(metrics)
        if interval_s is None:
            raw = (os.environ.get(_SAMPLE_ENV) or "").strip().lower()
            if raw in ("0", "off", "false", "no"):
                interval_s = 0.0
            else:
                try:
                    interval_s = float(raw) if raw else _DEFAULT_INTERVAL_S
                except ValueError:
                    interval_s = _DEFAULT_INTERVAL_S
        self._interval = max(0.0, float(interval_s))
        self._budget = overhead_budget
        self._clock = clock
        self._mu = threading.Lock()
        self._t0 = clock()
        self._last_sample = 0.0
        self._overhead_s = 0.0
        # EWMA of ns/record keyed per (model, backend): multi-model
        # serving (incumbent + rollout candidate through one
        # dispatcher) must not blend one model's rate with another's
        # flop/byte model — the roofline would report a cross-term
        # true of neither
        self._ns_per_record: Dict[str, float] = {}
        self._peaks = None
        self._peaks_resolved = False
        self.cost_ledger = cost_ledger or KernelCostLedger()
        # predicted-vs-observed tracking per (model, backend): the
        # kernel_pred_error gauge registers lazily (only pipelines
        # serving a search-adopted config carry it) and the strike
        # counters drive the stale-prediction re-search trigger
        self._pred_err_ewma: Dict[str, float] = {}
        self._pred_strikes: Dict[str, int] = {}
        # prediction value that already fired per key: the trigger is
        # one-shot per prediction — a long-lived server with a stale
        # config must not keep wiping the fit/cache a sibling's fresh
        # re-search just wrote; a NEW prediction re-arms the band
        self._pred_fired: Dict[str, float] = {}
        self._g_pred_err = None
        self._samples = metrics.counter("device_samples")
        self._g_flops = metrics.gauge("flops_per_record")
        self._g_nsrec = metrics.gauge("device_ns_per_record")

    @property
    def enabled(self) -> bool:
        return self._interval > 0.0

    def should_sample(self) -> bool:
        """One atomic check-and-claim per launch: True at most once per
        interval AND only while accumulated sampling overhead stays
        under the budget share of wall clock. The claim is optimistic —
        a claimed slot that doesn't call :meth:`record_sample` simply
        wastes one interval, never double-samples."""
        if self._interval <= 0.0:
            return False
        now = self._clock()
        with self._mu:
            if now - self._last_sample < self._interval:
                return False
            elapsed = max(now - self._t0, 1e-9)
            if (
                self._overhead_s > 0.0
                and self._overhead_s / elapsed > self._budget
            ):
                return False
            self._last_sample = now
            return True

    def record_sample(
        self,
        device_s: float,
        profile: Optional[dict],
        overhead_s: Optional[float] = None,
    ) -> None:
        """Fold one measured (device seconds, dispatch profile) pair
        into the gauges, the sampled device-stage histogram, and the
        kernel cost ledger. ``overhead_s`` is the sample's full
        serialization cost (drain + bracket), charged against the
        rate limiter's budget."""
        profile = profile or {}
        records = int(profile.get("records") or 0)
        with self._mu:
            self._overhead_s += (
                overhead_s if overhead_s is not None else device_s
            )
        self._samples.inc()
        if device_s <= 0 or records <= 0:
            return
        per_rec = device_s / records
        key = f"{profile.get('model')}|{profile.get('backend')}"
        with self._mu:
            prev = self._ns_per_record.get(key)
            if prev is None:
                self._ns_per_record[key] = per_rec * 1e9
            else:
                self._ns_per_record[key] = (
                    (1.0 - _EWMA_ALPHA) * prev
                    + _EWMA_ALPHA * per_rec * 1e9
                )
            ns_rec = self._ns_per_record[key]
            if not self._peaks_resolved:
                self._peaks = chip_peaks(_device_kind())
                self._peaks_resolved = True
            peaks = self._peaks
        self._g_nsrec.set(ns_rec)
        # smoothed records/s of pure device time — THIS model's EWMA
        # against THIS model's cost profile, so the roofline is
        # internally consistent even when models alternate samples
        dev_rate = 1e9 / ns_rec
        flops = profile.get("flops_per_record")
        bpr = profile.get("bytes_per_record")
        mfu, membw = roofline(dev_rate, flops, bpr, peaks)
        if flops is not None:
            self._g_flops.set(float(flops))
        # registered on first use: a chip with no known peaks carries
        # no roofline gauges at all (a 0.0 would read as a measurement)
        reg = self._metrics_ref()
        if reg is not None:
            if mfu is not None:
                reg.gauge("device_mfu").set(round(mfu, 6))
            if membw is not None:
                reg.gauge("device_membw_util").set(round(membw, 6))
        # the sampled device column of the attribution plane
        from flink_jpmml_tpu.obs import attr

        led = attr.ledger_for(self._metrics_ref())
        if led is not None:
            led.observe("device", device_s)
        self._verify_prediction(profile, per_rec)
        self.cost_ledger.update(
            profile.get("model"), profile.get("backend"),
            device_s, records, flops, bpr,
            variant=profile.get("variant"),
            features=profile.get("features"),
            predicted=profile.get("predicted_s_per_record"),
        )

    def _verify_prediction(self, profile: dict, per_rec: float) -> None:
        """Predict-then-verify, live: compare the sampled device cost
        against the adopted kernel config's prediction. Updates the
        ``kernel_pred_error`` gauge (relative |obs−pred| EWMA) and, on
        sustained out-of-band drift, invalidates the cost-model fit
        and clears this model's autotune entry — the next warmup
        re-searches instead of trusting the stale prediction."""
        pred = profile.get("predicted_s_per_record")
        try:
            pred = float(pred) if pred else 0.0
        except (TypeError, ValueError):
            return
        if pred <= 0 or per_rec <= 0:
            return
        key = f"{profile.get('model')}|{profile.get('backend')}"
        err = abs(per_rec - pred) / pred
        stale = False
        with self._mu:
            prev = self._pred_err_ewma.get(key)
            ewma = (
                err if prev is None
                else (1.0 - _EWMA_ALPHA) * prev + _EWMA_ALPHA * err
            )
            self._pred_err_ewma[key] = ewma
            already_fired = self._pred_fired.get(key) == pred
            if already_fired:
                pass  # this prediction is already invalidated; only a
                # re-search (new prediction value) re-arms the trigger
            elif pred / _PRED_BAND <= per_rec <= pred * _PRED_BAND:
                self._pred_strikes[key] = max(
                    0, self._pred_strikes.get(key, 0) - 1
                )
                self._pred_fired.pop(key, None)
            else:
                strikes = self._pred_strikes.get(key, 0) + 1
                stale = strikes >= _PRED_STRIKES
                self._pred_strikes[key] = 0 if stale else strikes
                if stale:
                    self._pred_fired[key] = pred
            if self._g_pred_err is None:
                reg = self._metrics_ref()
                if reg is not None:
                    self._g_pred_err = reg.gauge("kernel_pred_error")
        if self._g_pred_err is not None:
            self._g_pred_err.set(round(ewma, 4))
        if not stale:
            return
        from flink_jpmml_tpu.obs import recorder as flight

        flight.record(
            "kernel_search_stale",
            model=profile.get("model"),
            backend=profile.get("backend"),
            predicted_s_per_record=pred,
            observed_s_per_record=round(per_rec, 12),
        )
        try:
            from flink_jpmml_tpu.compile import autotune, costmodel

            costmodel.mark_stale(f"drift band: {key}")
            # the cache keys on model_hash; profile["model"] may be
            # the serving registry name (BoundScorer.key) and would
            # clear nothing
            model = profile.get("model_hash") or profile.get("model")
            if model:
                autotune.clear(str(model))
        except Exception:
            pass  # re-search is best-effort; serving never breaks


# one profiler per registry (cf. attr.ledger_for); a shared process-wide
# cost ledger so every pipeline's samples land in one file
_COST_LEDGER = KernelCostLedger()
_PROFILERS: "weakref.WeakKeyDictionary" = weakref.WeakKeyDictionary()
_PROFILERS_MU = threading.Lock()


def profiler_for(
    metrics: Optional[MetricsRegistry],
    interval_s: Optional[float] = None,
) -> Optional[DeviceProfiler]:
    """The registry's one profiler. ``interval_s`` is the sampling
    interval of a profiler THIS call creates (a deployment's own
    setting, stated before its pipeline is built: a sample drains the
    in-flight window, 0.3–0.4 s on four chips); None takes the
    environment's. A profiler that exists keeps its interval."""
    if metrics is None:
        return None
    prof = _PROFILERS.get(metrics)
    if prof is None:
        with _PROFILERS_MU:
            prof = _PROFILERS.get(metrics)
            if prof is None:
                prof = _PROFILERS[metrics] = DeviceProfiler(
                    metrics, interval_s=interval_s,
                    cost_ledger=_COST_LEDGER,
                )
    return prof
