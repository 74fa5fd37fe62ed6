"""Learned-cost-model kernel search + on-disk config cache.

The warmup sweep measures encode placement and the layout catalogue
(compile/layouts.py: breadth-first SoA split order, uint8/uint16 wire
packing, the multi-tree megakernel) per (model, backend), the way "A
Learned Performance Model for TPUs" (PAPERS.md) says to — *predict then
verify*. (The Pallas tile axis ``block_b x gt`` left the space in PR 21:
on the v5e Mosaic refuses a 1-D score block under 1024 rows, and ``gt=8``
regroups the f32 tree sum, which breaks byte parity with the default.)

1. **Predict.** A ridge cost model (compile/costmodel.py) fit on the
   accumulated kernel cost ledger (``kernel_costs.json`` — every
   profiler sample and every prior sweep's timings are training rows)
   ranks the FULL candidate space by predicted device-s/record.
2. **Verify.** Only the top-K (``FJT_SEARCH_TOPK``, default 5) are
   re-packed, compiled, and timed on the device; the measured winner
   is adopted. Every timing lands back in the ledger with its feature
   vector, so the next search's fit is better than this one's.
3. **Re-search on drift.** The live profiler (obs/profiler.py)
   compares sampled device cost against the adopted config's
   prediction; sustained drift outside the band (PR 8's
   ``capacity_reestimated`` pattern) invalidates the fit
   (``costmodel.mark_stale``) and clears this model's cache entry, so
   the next warmup re-searches instead of trusting a stale prediction.

With no usable fit yet (a cold ledger) the search *bootstraps*: it
times the built default first, then the catalogue in order — still
capped at K — and fits the first model from those measurements.

The winning :class:`TunedConfig` is cached per
``(model_hash, backend_key)`` in ``$FJT_AUTOTUNE_CACHE`` (default
``<checkout>/.fjt_cache/autotune.json``, compile/cachedir.py) consulted
by ``build_quantized_scorer`` on every compile. Every stored entry is
stamped with the search-space schema tag (``layouts.SPACE_TAG``): an
entry written against an older space reads as *no entry* — silent
re-search, the same corrupt-tolerant contract as ever (a pre-layout
winner can never pin a new binary to an obsolete kernel config).
``FJT_KERNEL_SEARCH_DISABLE=1`` (the bench's ``--no-kernel-search``
ablation) restricts the space to the built default;
``FJT_AUTOTUNE_DISABLE=1`` (``--no-autotune``) disables all of it.
"""

from __future__ import annotations

import contextlib
import dataclasses
import json
import math
import os
import pathlib
import time
from dataclasses import dataclass
from typing import Dict, List, Optional

import numpy as np

from flink_jpmml_tpu.compile import cachedir, layouts

_CACHE_ENV = "FJT_AUTOTUNE_CACHE"
_CACHE_VERSION = 1
_SEARCH_DISABLE_ENV = "FJT_KERNEL_SEARCH_DISABLE"
_TOPK_ENV = "FJT_SEARCH_TOPK"
_DEFAULT_TOPK = 5


@dataclass
class TunedConfig:
    """One measured winner: encode placement + kernel variant.

    ``layout`` is the compile/layouts.py catalogue id; ``rates``
    keeps the per-candidate rec/s the search observed;
    ``predicted_s_per_record`` is the cost model's prediction for the
    adopted variant (the live profiler verifies it — drift re-opens
    the search); ``search`` summarizes the predict-then-verify pass
    for the bench artifact; ``space`` stamps the search-space schema
    (a mismatched tag reads as no entry); ``source`` says where the
    config came from ("default" | "sweep" | "cache")."""

    encode: str = "host"  # "host" | "fused"
    layout: str = "ref"
    space: str = layouts.SPACE_TAG
    rec_s: Optional[float] = None
    predicted_s_per_record: Optional[float] = None
    rates: Dict[str, float] = dataclasses.field(default_factory=dict)
    search: Optional[dict] = None
    source: str = "default"

    def as_dict(self) -> dict:
        return {
            "encode": self.encode,
            "layout": self.layout,
            "space": self.space,
            "rec_s": self.rec_s,
            "predicted_s_per_record": self.predicted_s_per_record,
            "rates": dict(self.rates),
            "search": self.search,
            "source": self.source,
        }

    @classmethod
    def from_dict(cls, d: dict) -> "TunedConfig":
        enc = d.get("encode")
        layout = d.get("layout")
        return cls(
            encode=enc if enc in ("host", "fused") else "host",
            layout=layout if isinstance(layout, str) and layout else "ref",
            # absent tag = a pre-layout entry: must NOT default to the
            # current tag or stale winners would survive the schema bump
            space=str(d.get("space") or ""),
            rec_s=float(d["rec_s"]) if d.get("rec_s") else None,
            predicted_s_per_record=(
                float(d["predicted_s_per_record"])
                if d.get("predicted_s_per_record")
                else None
            ),
            rates={
                str(k): float(v)
                for k, v in (d.get("rates") or {}).items()
                if isinstance(v, (int, float))
            },
            search=d.get("search") if isinstance(d.get("search"), dict)
            else None,
            source=str(d.get("source") or "cache"),
        )


# ---------------------------------------------------------------------------
# On-disk cache
# ---------------------------------------------------------------------------


def cache_path() -> pathlib.Path:
    p = os.environ.get(_CACHE_ENV)
    if p:
        return pathlib.Path(p)
    return cachedir.state_dir() / "autotune.json"


@contextlib.contextmanager
def _cache_lock():
    """Exclusive flock over the cache's sidecar lock file (the kernel
    cost ledger's discipline): ``store``/``clear`` are read-modify-
    write, and ``clear`` is a live trigger now (the profiler's drift
    band fires it) — unsynchronized writers would last-writer-wins
    resurrect a cleared stale entry or drop a sibling's freshly
    measured winner. No flock available (non-posix, read-only dir) ⇒
    proceed unlocked; the atomic replace still keeps readers safe."""
    lock = None
    try:
        import fcntl

        path = cache_path()
        path.parent.mkdir(parents=True, exist_ok=True)
        lock = open(f"{path}.lock", "w")
        fcntl.flock(lock, fcntl.LOCK_EX)
    except (ImportError, OSError):
        if lock is not None:
            lock.close()
        lock = None
    try:
        yield
    finally:
        if lock is not None:
            try:
                lock.close()  # closing releases the flock
            except OSError:
                pass


def _load_cache() -> dict:
    """→ the entries dict; {} on ANY problem (missing, corrupt,
    unreadable, wrong schema) — the silent-re-tune contract."""
    try:
        with open(cache_path()) as f:
            data = json.load(f)
        entries = data.get("entries")
        if isinstance(entries, dict):
            return entries
    except (OSError, ValueError, AttributeError):
        pass
    return {}


def lookup(model_hash: str, backend_key: str) -> Optional[TunedConfig]:
    # FJT_AUTOTUNE_DISABLE=1 forces the hand-picked defaults + host
    # encode everywhere (the bench's --no-autotune ablation sets it:
    # without this gate, build_quantized_scorer would still apply a
    # config an EARLIER run cached, silently un-ablating the baseline)
    if os.environ.get("FJT_AUTOTUNE_DISABLE"):
        return None
    if not model_hash:
        return None
    raw = _load_cache().get(f"{model_hash}|{backend_key}")
    if not isinstance(raw, dict):
        return None
    try:
        cfg = TunedConfig.from_dict(raw)
    except (TypeError, ValueError):
        return None
    if cfg.space != layouts.SPACE_TAG:
        # cached against an older search space: a pre-layout winner
        # must not pin this binary to an obsolete kernel config —
        # reads as no entry (silent re-search)
        return None
    cfg.source = "cache"
    return cfg


def store(model_hash: str, backend_key: str, cfg: TunedConfig) -> None:
    """Read-modify-write with an atomic replace; failures are silent
    (a read-only home dir must not break a sweep)."""
    if not model_hash:
        return
    from flink_jpmml_tpu.utils.diskio import atomic_write_json

    with _cache_lock():
        entries = _load_cache()
        entry = cfg.as_dict()
        entry["ts"] = time.time()
        entries[f"{model_hash}|{backend_key}"] = entry
        atomic_write_json(
            str(cache_path()),
            {"version": _CACHE_VERSION, "entries": entries},
        )


def clear(model_hash: Optional[str] = None) -> None:
    """Drop the whole cache file (or, with ``model_hash``, just that
    model's entries). Test/tooling helper AND the live re-search
    trigger (the profiler's drift band clears a model whose adopted
    prediction went stale). Scoped rewrites go through the same
    tmp-file + atomic replace as :func:`store` — a truncating
    in-place write would let a concurrent reader (or a crash) see a
    half-written file and, by the silent-corruption contract, lose
    EVERY model's entries instead of only this one's."""
    path = cache_path()
    if model_hash is None:
        try:
            os.unlink(path)
        except OSError:
            pass
        return
    from flink_jpmml_tpu.utils.diskio import atomic_write_json

    with _cache_lock():
        entries = {
            k: v for k, v in _load_cache().items()
            if not k.startswith(f"{model_hash}|")
        }
        atomic_write_json(
            str(path), {"version": _CACHE_VERSION, "entries": entries}
        )


def backend_key(scorer) -> str:
    """Cache key half that pins WHERE the measurement holds: platform +
    device kind + which scorer backend compiled. A config measured on a
    v5e does not transfer to CPU interpret mode."""
    return f"{platform_key()}:{scorer.backend}"


# ---------------------------------------------------------------------------
# Cross-model pack plans (the zoo's layout decision, keyed per model SET)
# ---------------------------------------------------------------------------


@dataclass
class PackPlan:
    """One adopted packing partition for a model set.

    ``groups`` are lists of model hashes sharing a packed buffer
    (singleton = solo). Cached per ``(model-set hash, platform)`` —
    the SET hash, not any member's hash: adding or removing a tenant
    changes the set hash, so the stale winner simply misses and the
    partition re-searches (satellite: stale-winner invalidation,
    pinned by tests/test_zoo.py)."""

    groups: List[List[str]]
    set_hash: str
    pred_s_per_record: Optional[float] = None
    waste: float = 0.0
    space: str = layouts.PACK_SPACE_TAG
    source: str = "search"

    def as_dict(self) -> dict:
        return {
            "kind": "pack_plan",
            "groups": [list(g) for g in self.groups],
            "set_hash": self.set_hash,
            "pred_s_per_record": self.pred_s_per_record,
            "waste": self.waste,
            "space": self.space,
            "source": self.source,
        }

    @classmethod
    def from_dict(cls, d: dict) -> Optional["PackPlan"]:
        try:
            groups = [
                [str(h) for h in g] for g in d["groups"]
            ]
            return cls(
                groups=groups,
                set_hash=str(d.get("set_hash") or ""),
                pred_s_per_record=(
                    float(d["pred_s_per_record"])
                    if d.get("pred_s_per_record") is not None
                    else None
                ),
                waste=float(d.get("waste") or 0.0),
                # absent tag must NOT default to the current one (the
                # TunedConfig rule): a pre-packspace entry re-searches
                space=str(d.get("space") or ""),
                source=str(d.get("source") or "cache"),
            )
        except (KeyError, TypeError, ValueError):
            return None


def platform_key() -> str:
    """Pack-plan cache key half: platform + device kind. No scorer
    backend dimension — packs are XLA-only by eligibility."""
    import jax

    plat = jax.default_backend()
    kind = getattr(jax.devices()[0], "device_kind", "") or ""
    return f"{plat}:{kind.replace(' ', '_')}"


def _pack_key(set_hash: str, plat: str) -> str:
    return f"packset:{set_hash}|{plat}"


def lookup_pack_plan(
    set_hash: str, plat: Optional[str] = None
) -> Optional[PackPlan]:
    if os.environ.get("FJT_AUTOTUNE_DISABLE"):
        return None
    if not set_hash:
        return None
    raw = _load_cache().get(_pack_key(set_hash, plat or platform_key()))
    if not isinstance(raw, dict):
        return None
    plan = PackPlan.from_dict(raw)
    if plan is None or plan.space != layouts.PACK_SPACE_TAG:
        return None
    plan.source = "cache"
    return plan


def store_pack_plan(plan: PackPlan, plat: Optional[str] = None) -> None:
    """Same read-modify-write + atomic-replace discipline as
    :func:`store`; silent on failure."""
    if not plan.set_hash or os.environ.get("FJT_AUTOTUNE_DISABLE"):
        return
    from flink_jpmml_tpu.utils.diskio import atomic_write_json

    with _cache_lock():
        entries = _load_cache()
        entry = plan.as_dict()
        entry["ts"] = time.time()
        entries[_pack_key(plan.set_hash, plat or platform_key())] = entry
        atomic_write_json(
            str(cache_path()),
            {"version": _CACHE_VERSION, "entries": entries},
        )


def ensure_pack_plan(
    metas: Dict[str, dict], plat: Optional[str] = None
) -> PackPlan:
    """The zoo's layout decision: adopted pack partition for a model
    set, cache-else-search-else-store.

    ``metas`` maps model_hash → packed-shape summary
    (``QuantizedScorer._meta``). The search enumerates
    ``layouts.pack_partitions`` and prices each with
    ``costmodel.pack_partition_cost`` (predicted device-s/record
    inflated by padded waste — the two ranking axes the issue names);
    the argmin is adopted and persisted under the model-SET hash. A
    cached plan whose member union no longer matches the live set
    (possible only through a hash collision or a corrupt file) reads
    as no entry."""
    from flink_jpmml_tpu.compile import costmodel, packs
    from flink_jpmml_tpu.obs import recorder as flight

    plat = plat or platform_key()
    set_hash = packs.model_set_hash(list(metas))
    cached = lookup_pack_plan(set_hash, plat)
    if cached is not None:
        members = {h for g in cached.groups for h in g}
        if members == set(metas):
            return cached
    model = costmodel.current_model()
    best = None
    best_cost = math.inf
    best_waste = 0.0
    n_cands = 0
    for part in layouts.pack_partitions(metas):
        n_cands += 1
        cost, waste = costmodel.pack_partition_cost(metas, part, model)
        if cost < best_cost:
            best, best_cost, best_waste = part, cost, waste
    if best is None:  # empty set: degenerate, nothing to pack
        return PackPlan(groups=[], set_hash=set_hash, source="empty")
    plan = PackPlan(
        groups=[list(g) for g in best],
        set_hash=set_hash,
        pred_s_per_record=(
            best_cost if math.isfinite(best_cost) else None
        ),
        waste=best_waste,
        source="search",
    )
    store_pack_plan(plan, plat)
    flight.record(
        "pack_plan_adopted",
        set_hash=set_hash,
        models=len(metas),
        groups=len(plan.groups),
        candidates=n_cands,
        waste=round(best_waste, 4),
        pred_s_per_record=plan.pred_s_per_record,
    )
    return plan


# ---------------------------------------------------------------------------
# Candidate space
# ---------------------------------------------------------------------------


def search_top_k(top_k: Optional[int] = None) -> int:
    if top_k is not None:
        return max(1, int(top_k))
    try:
        return max(1, int(os.environ.get(_TOPK_ENV) or _DEFAULT_TOPK))
    except ValueError:
        return _DEFAULT_TOPK


def candidate_space(scorer, legacy: bool = False) -> List[str]:
    """Every kernel variant the search may rank for this scorer: the
    ids of the backend's layout catalogue. The built default (``ref``)
    is always candidate 0. ``legacy`` restricts to it alone (the
    ``--no-kernel-search`` ablation)."""
    if legacy:
        return ["ref"]
    if scorer.backend == "pallas" and scorer._pallas_rebuild is not None:
        names = layouts.pallas_layouts()
    elif scorer.backend != "pallas" and scorer._xla_rebuild is not None:
        names = layouts.xla_layouts(scorer.wire)
    else:
        names = ()
    return ["ref"] + [layout for layout in names if layout != "ref"]


def _cand_features(scorer, layout: str) -> Dict[str, float]:
    from flink_jpmml_tpu.compile import costmodel

    wire_bytes = float(scorer.wire.bytes_per_record)
    if "wirepack" in (layouts.flags(layout) or ()):
        wp = layouts.plan_wire_pack(scorer.wire)
        if wp is not None:
            wire_bytes = float(wp.bytes_per_record)
    return costmodel.variant_features(
        costmodel.scorer_meta(scorer), scorer.backend, layout,
        wire_bytes=wire_bytes,
    )


def _describe_serving_variant(scorer) -> None:
    """Point the scorer's feature vector / variant id channels
    (obs/attr.py dispatch_profile → kernel cost ledger + live drift
    band) at the variant ACTUALLY serving."""
    try:
        scorer._cost_feat = _cand_features(scorer, scorer.layout)
        scorer._cost_variant = layouts.variant_id(
            scorer.backend, scorer.layout
        )
    except Exception:
        scorer._cost_feat = None


# ---------------------------------------------------------------------------
# Apply / search / sweep
# ---------------------------------------------------------------------------


def apply(scorer, cfg: TunedConfig) -> None:
    """Apply a config to a scorer: rebuild the kernel when the cached
    layout differs from the built default, then set the encode mode
    (gated on the scorer actually supporting the fused stage — a stale
    "fused" entry degrades to host, never crashes).

    A scorer is tuned at most once per lifetime, so the rebuild hooks
    are RELEASED afterwards — their closures pin the host-side packing
    tables (~11MB for the flagship GBM) that would otherwise sit next
    to the device-resident copies for as long as the model is served."""
    layout = cfg.layout or "ref"
    applied = layout == "ref"
    if not applied:
        built = scorer.build_variant(layout)
        if built is not None:
            scorer.adopt_variant(built, layout)
            applied = True
    scorer._pallas_rebuild = None
    scorer._xla_rebuild = None
    scorer.encode_mode = (
        "fused" if cfg.encode == "fused" and scorer.supports_fused else "host"
    )
    # a cached variant this build degraded to defaults must not ship
    # its prediction: the drift band would invalidate a perfectly good
    # fit against a kernel that is not running
    _describe_serving_variant(scorer)
    scorer._pred_s_per_record = (
        cfg.predicted_s_per_record if applied else None
    )
    scorer.tuned = cfg


def _time_best(fn, repeats: int) -> float:
    """Best-of wall time of ``fn()`` (which must block on its own
    result). One unmeasured warm call first — candidate compiles must
    not count as candidate cost."""
    fn()
    best = math.inf
    for _ in range(max(1, repeats)):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best


def _variant_search(
    scorer,
    X: np.ndarray,
    repeats: int,
    budget_s: float,
    t_start: float,
    rates: Dict[str, float],
    top_k: Optional[int] = None,
):
    """Predict-then-verify over the candidate space → (predicted
    s/record for the adopted layout, search summary).

    Ranks ALL candidates by the ledger-fit cost model when one exists
    (bootstrap order otherwise), times at most K on device, adopts the
    measured winner, and feeds every timing back into the ledger as a
    (features → device-s/record) training row."""
    import jax

    from flink_jpmml_tpu.compile import costmodel
    from flink_jpmml_tpu.obs import profiler as prof_mod

    legacy = bool(os.environ.get(_SEARCH_DISABLE_ENV))
    cands = candidate_space(scorer, legacy=legacy)
    K = search_top_k(top_k)
    names = {c: layouts.variant_id(scorer.backend, c) for c in cands}
    feats = {names[c]: _cand_features(scorer, c) for c in cands}
    platform = backend_key(scorer).split(":", 1)[0]
    model = None if legacy else costmodel.current_model(platform=platform)
    predictions: Dict[str, float] = {}
    if model is not None:
        ranked = model.rank(feats)
        predictions = {
            n: round(p, 12) for n, p in ranked if math.isfinite(p)
        }
        order = [next(c for c in cands if names[c] == n)
                 for n, _ in ranked]
        # the built default is ALWAYS verified, mispredicted or not:
        # without it a bad fit could rank the incumbent outside top-K
        # and the search would adopt-and-persist a variant slower than
        # the default it replaced (never having measured the default)
        order = [cands[0]] + [c for c in order if c != cands[0]]
        mode = "learned"
    else:
        order = cands  # cold ledger: the default, then catalogue order
        mode = "legacy" if legacy else "bootstrap"

    bs = X.shape[0]
    meta = costmodel.scorer_meta(scorer)
    flops_rec = (
        2.0 * meta["trees"] * meta["splits"] * meta["leaves"]
        + 2.0 * meta["trees"] * meta["leaves"]
        if meta else None
    )
    ledger = prof_mod.KernelCostLedger(flush_interval_s=math.inf)
    best_rate, best_cand, best_built = -1.0, cands[0], None
    timed = 0
    for c in order:
        if timed >= K:
            break
        if time.perf_counter() - t_start > budget_s and timed:
            break
        name = names[c]
        if c == "ref":
            built, params, fn, wp = (
                None, scorer.params, scorer._jit_fn, scorer._wire_pack,
            )
        else:
            built = scorer.build_variant(c)
            if built is None:
                continue  # nothing to pack
            params, fn, wp = (
                built["params"], built["jit_fn"], built["wire_pack"],
            )
        payload = wp.pack(X) if wp is not None else X
        # stage a FRESH buffer per call: with donate_batches=True the
        # jitted entry donates (deletes) its batch argument, so a
        # reused staged buffer would crash the second rep on any
        # backend that honours donation (uniform per-call staging
        # keeps the candidate ranking fair)
        dt = _time_best(
            lambda fn=fn, params=params, payload=payload: (
                jax.block_until_ready(fn(params, jax.device_put(payload)))
            ),
            repeats,
        )
        timed += 1
        rates[name] = round(bs / dt, 1)
        ledger.update(
            scorer.model_hash, scorer.backend, dt, bs,
            flops_rec,
            payload.nbytes / bs + 2.0,  # staged wire in + bf16 out
            variant=name, features=feats[name],
            predicted=predictions.get(name),
        )
        if bs / dt > best_rate:
            best_rate, best_cand, best_built = bs / dt, c, built
    if best_built is not None:
        scorer.adopt_variant(best_built, best_cand)
    ledger.flush()
    # refit from the ledger (now including this search's rows) and
    # persist, so the NEXT search predicts from these measurements
    refit = costmodel.fit_from_ledger(platform=platform)
    best_name = names[best_cand]
    # predicted-vs-measured residual over the verified candidates: the
    # honest "is the model any good yet" number in the artifact
    resid = None
    checked = [
        (predictions[n], 1.0 / rates[n])
        for n in rates
        if n in predictions and rates.get(n)
    ]
    if checked:
        ratios = [
            abs(math.log(max(p, 1e-18) / max(obs, 1e-18)))
            for p, obs in checked
        ]
        resid = round(sum(ratios) / len(ratios), 4)
    search_info = {
        "space": layouts.SPACE_TAG,
        "mode": mode,
        "candidates_total": len(cands),
        "timed": timed,
        "top_k": K,
        "chosen": best_name,
        "predicted": predictions or None,
        "pred_abs_log_err": resid,
        "model": (refit or model).stats if (refit or model) else None,
    }
    return predictions.get(best_name), search_info


def sweep(
    scorer,
    X_sample: np.ndarray,
    repeats: int = 2,
    budget_s: float = 30.0,
    top_k: Optional[int] = None,
) -> TunedConfig:
    """Search the kernel-variant space and measure encode placement on
    THIS backend; adopt the winner.

    ``X_sample`` is a raw f32 feature batch; it is tiled/trimmed to
    exactly one compile batch so every candidate times the same
    dispatch shape. Returns the applied :class:`TunedConfig`
    (``source="sweep"``) with per-candidate rates in ``rates`` and the
    predict-then-verify summary in ``search``."""
    import jax

    t_start = time.perf_counter()
    X = np.ascontiguousarray(np.asarray(X_sample, np.float32))
    bs = scorer.batch_size or X.shape[0]
    if X.shape[0] != bs:
        reps = -(-bs // X.shape[0])
        X = np.ascontiguousarray(np.tile(X, (reps, 1))[:bs])
    rates: Dict[str, float] = {}
    predicted = None
    search_info = None

    # -- kernel-variant search (layouts, host-encoded input) --------------
    has_variants = (
        scorer.backend == "pallas" and scorer._pallas_rebuild is not None
    ) or (scorer.backend != "pallas" and scorer._xla_rebuild is not None)
    if has_variants:
        # raw (unpacked) rank codes at exactly one compile batch; each
        # candidate packs them itself when its layout calls for it
        Xq = scorer.wire.encode(X)
        predicted, search_info = _variant_search(
            scorer, Xq, repeats, budget_s, t_start, rates, top_k
        )
    # tuned once: release the rebuild closures so they stop pinning
    # the host-side packing tables (see apply())
    scorer._pallas_rebuild = None
    scorer._xla_rebuild = None

    # -- encode placement sweep (end to end from raw f32 on host) ---------
    def _host():
        Xq, Kc = scorer.pad_wire(scorer.wire.encode(X))
        jax.block_until_ready(
            scorer.predict_padded(jax.device_put(Xq), Kc)
        )

    rates["encode_host"] = round(bs / _time_best(_host, repeats), 1)
    encode = "host"
    if scorer.supports_fused:
        def _fused():
            Xp, Kc = scorer.pad_f32(X)
            jax.block_until_ready(
                scorer.predict_fused_padded(jax.device_put(Xp), Kc)
            )

        rates["encode_fused"] = round(bs / _time_best(_fused, repeats), 1)
        if rates["encode_fused"] > rates["encode_host"]:
            encode = "fused"

    cfg = TunedConfig(
        encode=encode,
        layout=scorer.layout,
        rec_s=rates.get(f"encode_{encode}"),
        predicted_s_per_record=predicted,
        rates=rates,
        search=search_info,
        source="sweep",
    )
    scorer.encode_mode = (
        "fused" if encode == "fused" and scorer.supports_fused else "host"
    )
    _describe_serving_variant(scorer)
    # the chosen candidate IS the serving variant here (the search
    # adopted it), so its prediction is the one the live band verifies
    scorer._pred_s_per_record = predicted
    scorer.tuned = cfg
    return cfg


def ensure_tuned(
    scorer,
    X_sample: np.ndarray,
    repeats: int = 2,
    use_cache: bool = True,
    budget_s: float = 30.0,
    top_k: Optional[int] = None,
) -> TunedConfig:
    """The warmup entry point: cache hit → apply it; miss → search and
    persist the winner. Always returns the config now in force."""
    from flink_jpmml_tpu.obs import recorder as flight

    key = backend_key(scorer)
    if use_cache:
        cfg = lookup(scorer.model_hash, key)
        if cfg is not None:
            apply(scorer, cfg)
            flight.record(
                "autotune_decision", source="cache", backend=key,
                model_hash=scorer.model_hash, encode=cfg.encode,
                layout=cfg.layout,
            )
            return cfg
    cfg = sweep(
        scorer, X_sample, repeats=repeats, budget_s=budget_s, top_k=top_k
    )
    store(scorer.model_hash, key, cfg)
    flight.record(
        "autotune_decision", source="sweep", backend=key,
        model_hash=scorer.model_hash, encode=cfg.encode,
        layout=cfg.layout,
        rec_s=cfg.rec_s,
        timed=(cfg.search or {}).get("timed"),
        candidates=(cfg.search or {}).get("candidates_total"),
    )
    return cfg
