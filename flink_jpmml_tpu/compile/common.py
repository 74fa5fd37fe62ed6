"""Shared lowering machinery: batch layout, output pytree, lowering context.

Design (SURVEY.md §8 step 2): every model family lowers to a pure function

    (X: f32[B, F], M: bool[B, F]) -> ModelOutput

where ``X`` holds the records' field values *in field-space order* and ``M``
marks missing cells (``True`` = missing; NaNs in ``X`` are also treated as
missing at the entry point). The reference's per-record, exception-based
evaluation (SURVEY.md §4.1 hot loop) becomes batched, branch-free XLA:
per-record failures are lanes where ``valid`` is ``False`` (capability C5).

String-valued categorical fields are *encoded* host-side to float codes (the
index of the value in its DataField's declared value list) by
:mod:`flink_jpmml_tpu.compile.prepare`; predicates over such fields compare
codes. This keeps the device path purely numeric.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field as dc_field
from typing import Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

# All value-carrying dots use full f32 precision: on TPU the *default*
# precision multiplies f32 operands in bf16 passes, which breaks golden
# parity with the (f64) reference semantics. The topology/match einsums in
# trees.py intentionally run in bf16 — their operands are small integers,
# exact in bf16 — and opt out of this.
HIGHEST = jax.lax.Precision.HIGHEST

from flink_jpmml_tpu.pmml import ir
from flink_jpmml_tpu.utils.config import CompileConfig
from flink_jpmml_tpu.utils.exceptions import ModelCompilationException

# Lazily-probed backend kind. Lowering consults this to pick matmul
# dtypes (bf16/int8 on TPU, f32 where there are no such dot kernels). A
# backend that fails to initialise raises here: it is not a CPU.
_BACKEND_IS_CPU: Optional[bool] = None


def backend_is_cpu() -> bool:
    global _BACKEND_IS_CPU
    if _BACKEND_IS_CPU is None:
        _BACKEND_IS_CPU = jax.default_backend() == "cpu"
    return _BACKEND_IS_CPU


class ModelOutput(NamedTuple):
    """Batched model result; structure is static per compiled model.

    ``value``:  f32[B] — regression value / winning-class probability /
                winning cluster index.
    ``valid``:  bool[B] — lane validity (False ⇔ reference's EmptyScore).
    ``probs``:  f32[B, C] or None — per-class probabilities (classification)
                or per-cluster distances (clustering).
    ``label_idx``: i32[B] or None — index into the model's static label list.
    """

    value: jnp.ndarray
    valid: jnp.ndarray
    probs: Optional[jnp.ndarray] = None
    label_idx: Optional[jnp.ndarray] = None


# fn(params, X, M) -> ModelOutput. ``params`` is a pytree of arrays passed
# as *arguments* rather than closed-over constants: XLA doesn't constant-
# fold over megabytes of tree tensors, and the door stays open for
# executable sharing between same-architecture model versions (today each
# document still gets its own jit entry — sharing would key the jitted fn on
# an architecture signature; the ModelReader cache dedupes same-path loads).
ModelFn = Callable[[dict, jnp.ndarray, jnp.ndarray], ModelOutput]


@dataclass
class Lowered:
    """A lowered (but not yet jitted) model: fn + its params + metadata."""

    fn: ModelFn
    params: dict
    labels: Tuple[str, ...] = ()  # class labels (classification/clustering)

    @property
    def is_classification(self) -> bool:
        return bool(self.labels)


@dataclass
class LowerCtx:
    """Compile-time context threaded through the per-family lowerers.

    ``field_index`` maps field name → column in ``X``; modelChain extends it
    with intermediate output fields. ``codecs`` maps a categorical field name
    to its value→code table (only string-typed categorical fields need one;
    numeric fields compare raw values).
    """

    field_index: Dict[str, int]
    codecs: Dict[str, Dict[str, float]] = dc_field(default_factory=dict)
    config: CompileConfig = dc_field(default_factory=CompileConfig)
    # True inside MiningModel segments: entity-surface extras (KNN
    # neighbor-index columns) stay off so ensemble blends see uniform
    # probs shapes; entity outputs are top-level-model features
    nested: bool = False

    @property
    def n_fields(self) -> int:
        return len(self.field_index)

    def column(self, name: str) -> int:
        try:
            return self.field_index[name]
        except KeyError:
            raise ModelCompilationException(
                f"model references field {name!r} which is not in the input "
                f"field space {sorted(self.field_index)}"
            ) from None

    def encode(self, name: str, raw: str) -> float:
        """Encode a PMML literal (predicate/predictor value) for ``name``.

        String-categorical fields go through their codec; everything else
        must parse as a number. Unknown category → NaN (never matches,
        mirroring the oracle's string-inequality result).
        """
        codec = self.codecs.get(name)
        if codec is not None:
            # undeclared category → NaN (never matches); no numeric fallback,
            # which would alias a numeric-looking literal onto a code
            return codec.get(raw, math.nan)
        try:
            return float(raw)
        except ValueError:
            raise ModelCompilationException(
                f"non-numeric literal {raw!r} for non-categorical field {name!r}"
            ) from None

    def with_extra_fields(
        self, names: Tuple[str, ...], codecs: Dict[str, Dict[str, float]]
    ) -> "LowerCtx":
        """Extend the field space (modelChain intermediate outputs)."""
        idx = dict(self.field_index)
        for n in names:
            if n in idx:
                raise ModelCompilationException(
                    f"modelChain output field {n!r} shadows an existing field"
                )
            idx[n] = len(idx)
        merged = dict(self.codecs)
        merged.update(codecs)
        return LowerCtx(field_index=idx, codecs=merged, config=self.config)


def build_codecs(dd: ir.DataDictionary) -> Dict[str, Dict[str, float]]:
    """value→code tables for string-typed categorical fields.

    The code of a category is its index in the DataField's declared value
    list — stable across host and device because both sides derive it from
    the same document.
    """
    codecs: Dict[str, Dict[str, float]] = {}
    for f in dd.fields:
        if f.is_categorical and f.dtype == "string" and f.values:
            codecs[f.name] = {v: float(i) for i, v in enumerate(f.values)}
    return codecs


# ---------------------------------------------------------------------------
# Predicate lowering (used by MiningModel segment predicates; canonical tree
# splits have their own fused path in trees.py)
# ---------------------------------------------------------------------------


class PredOut(NamedTuple):
    is_true: jnp.ndarray  # bool[B]
    unknown: jnp.ndarray  # bool[B]


PredFn = Callable[[jnp.ndarray, jnp.ndarray], PredOut]


def lower_predicate(pred: ir.Predicate, ctx: LowerCtx) -> PredFn:
    """Three-valued predicate semantics, vectorized: (true, unknown)."""
    if isinstance(pred, ir.TruePredicate):
        def t(X, M):
            shape = X.shape[:1]
            return PredOut(jnp.ones(shape, bool), jnp.zeros(shape, bool))
        return t
    if isinstance(pred, ir.FalsePredicate):
        def f(X, M):
            shape = X.shape[:1]
            return PredOut(jnp.zeros(shape, bool), jnp.zeros(shape, bool))
        return f
    if isinstance(pred, ir.SimplePredicate):
        col = ctx.column(pred.field)
        op = pred.operator
        if op in ("isMissing", "isNotMissing"):
            def miss(X, M, _col=col, _neg=(op == "isNotMissing")):
                m = M[:, _col]
                t = ~m if _neg else m
                return PredOut(t, jnp.zeros_like(t))
            return miss
        v = ctx.encode(pred.field, pred.value)
        cmp = {
            "equal": lambda x, t: x == t,
            "notEqual": lambda x, t: x != t,
            "lessThan": lambda x, t: x < t,
            "lessOrEqual": lambda x, t: x <= t,
            "greaterThan": lambda x, t: x > t,
            "greaterOrEqual": lambda x, t: x >= t,
        }[op]
        def simple(X, M, _col=col, _v=v, _cmp=cmp):
            m = M[:, _col]
            t = _cmp(X[:, _col], jnp.float32(_v)) & ~m
            return PredOut(t, m)
        return simple
    if isinstance(pred, ir.SimpleSetPredicate):
        col = ctx.column(pred.field)
        codes = jnp.asarray(
            [ctx.encode(pred.field, s) for s in pred.values], jnp.float32
        )
        neg = pred.boolean_operator == "isNotIn"
        def sset(X, M, _col=col, _codes=codes, _neg=neg):
            m = M[:, _col]
            member = jnp.any(X[:, _col, None] == _codes[None, :], axis=-1)
            t = (~member if _neg else member) & ~m
            return PredOut(t, m)
        return sset
    if isinstance(pred, ir.CompoundPredicate):
        subs = [lower_predicate(p, ctx) for p in pred.predicates]
        op = pred.boolean_operator
        def compound(X, M, _subs=subs, _op=op):
            outs = [s(X, M) for s in _subs]
            ts = jnp.stack([o.is_true for o in outs])
            us = jnp.stack([o.unknown for o in outs])
            if _op == "and":
                any_false = jnp.any(~ts & ~us, axis=0)
                unknown = ~any_false & jnp.any(us, axis=0)
                return PredOut(jnp.all(ts, axis=0), unknown)
            if _op == "or":
                any_true = jnp.any(ts, axis=0)
                unknown = ~any_true & jnp.any(us, axis=0)
                return PredOut(any_true, unknown)
            if _op == "xor":
                unknown = jnp.any(us, axis=0)
                parity = jnp.sum(ts.astype(jnp.int32), axis=0) % 2 == 1
                return PredOut(parity & ~unknown, unknown)
            # surrogate: first sub-predicate whose value is known
            B = ts.shape[1]
            result = jnp.zeros(B, bool)
            decided = jnp.zeros(B, bool)
            for i in range(ts.shape[0]):
                known = ~us[i] & ~decided
                result = jnp.where(known, ts[i], result)
                decided = decided | ~us[i]
            return PredOut(result, ~decided)
        if op not in ("and", "or", "xor", "surrogate"):
            raise ModelCompilationException(f"unsupported CompoundPredicate {op!r}")
        return compound
    raise ModelCompilationException(
        f"unsupported predicate {type(pred).__name__}"
    )


# ---------------------------------------------------------------------------
# Targets rescale
# ---------------------------------------------------------------------------


def apply_targets_value(value, targets: Tuple[ir.Target, ...]):
    """Targets rescale/cast on a bare value vector (shared by the f32 and
    quantized scoring paths so their semantics cannot diverge)."""
    if not targets:
        return value
    t = targets[0]
    v = value * jnp.float32(t.rescale_factor) + jnp.float32(t.rescale_constant)
    if t.cast_integer == "round":
        v = jnp.round(v)
    elif t.cast_integer == "ceiling":
        v = jnp.ceil(v)
    elif t.cast_integer == "floor":
        v = jnp.floor(v)
    return v


def apply_targets(out: ModelOutput, targets: Tuple[ir.Target, ...]) -> ModelOutput:
    if not targets:
        return out
    return out._replace(value=apply_targets_value(out.value, targets))


_TREAT_CODES = {"asIs": 0, "asMissing": 1, "returnInvalid": 2, "asValue": 3}


def extract_invalid_policy(
    dd: "ir.DataDictionary", schema: "ir.MiningSchema", ctx: "LowerCtx"
):
    """DataDictionary validity + ``invalidValueTreatment`` per raw input
    column → policy dict for the jitted sanitize stage, or None when no
    active field can ever be invalid (no declared category table, no
    Intervals — the common case pays nothing).

    Host-side encoding marks an undeclared category as ``+inf``
    (prepare.encode_cell); continuous out-of-Interval values are detected
    on-device. Keys: ``treat`` i32[F] (0 asIs, 1 asMissing,
    2 returnInvalid — the spec default — 3 asValue), ``repl`` f32[F],
    ``has_cat`` bool[F], and when any Intervals exist ``lo``/``hi``
    f32[F, I] with ``lo_open``/``hi_open`` bool[F, I] (±inf padded) and
    ``has_ivl`` bool[F]."""
    F = ctx.n_fields
    has_cat = np.zeros((F,), bool)
    cat_n = np.zeros((F,), np.float32)  # declared categories per column
    intervals: dict = {}
    for f in dd.fields:
        j = ctx.field_index.get(f.name)
        if j is None:
            continue
        if f.is_categorical and f.dtype == "string" and f.values:
            has_cat[j] = True
            cat_n[j] = len(f.values)
        if f.intervals:
            intervals[j] = f.intervals
    if not has_cat.any() and not intervals:
        return None
    treat = np.full((F,), _TREAT_CODES["returnInvalid"], np.int32)
    repl = np.zeros((F,), np.float32)
    for mf in schema.fields:
        j = ctx.field_index.get(mf.name)
        if j is None:
            continue
        code = _TREAT_CODES.get(mf.invalid_value_treatment)
        if code is None:
            raise ModelCompilationException(
                f"unsupported invalidValueTreatment "
                f"{mf.invalid_value_treatment!r} on field {mf.name!r}"
            )
        treat[j] = code
        # the replacement only matters (and is only encodable) for
        # columns that can actually be invalid — a declared category
        # table or Intervals
        if code == _TREAT_CODES["asValue"] and (
            has_cat[j] or j in intervals
        ):
            if mf.invalid_value_replacement is None:
                raise ModelCompilationException(
                    f"invalidValueTreatment='asValue' on {mf.name!r} "
                    "needs invalidValueReplacement"
                )
            repl[j] = ctx.encode(mf.name, mf.invalid_value_replacement)
            if math.isnan(repl[j]):
                # an undeclared category as the replacement would write
                # NaN into X with M=False — silently wrong scores
                raise ModelCompilationException(
                    f"invalidValueReplacement "
                    f"{mf.invalid_value_replacement!r} on {mf.name!r} is "
                    "itself not a declared value"
                )
    policy = {
        "treat": treat, "repl": repl, "has_cat": has_cat, "cat_n": cat_n,
    }
    if intervals:
        I = max(len(v) for v in intervals.values())
        lo = np.full((F, I), -np.inf, np.float32)
        hi = np.full((F, I), np.inf, np.float32)
        lo_open = np.zeros((F, I), bool)
        hi_open = np.zeros((F, I), bool)
        has_ivl = np.zeros((F,), bool)
        for j, ivs in intervals.items():
            has_ivl[j] = True
            # padded slots keep (-inf, inf) closed — they would accept
            # everything, so mask them out instead of letting them match
            for k in range(len(ivs), I):
                lo[j, k] = np.inf  # empty interval: matches nothing
                hi[j, k] = -np.inf
            for k, iv in enumerate(ivs):
                if iv.left is not None:
                    lo[j, k] = iv.left
                    lo_open[j, k] = iv.closure.startswith("open")
                if iv.right is not None:
                    hi[j, k] = iv.right
                    hi_open[j, k] = iv.closure.endswith("Open")
        policy.update(
            lo=lo, hi=hi, lo_open=lo_open, hi_open=hi_open, has_ivl=has_ivl
        )
    else:
        policy["has_ivl"] = None
    return policy


def extract_missing_replacements(
    schema: "ir.MiningSchema", ctx: "LowerCtx"
) -> Tuple[np.ndarray, np.ndarray]:
    """Mining-schema ``missingValueReplacement`` per input column →
    (repl f32[F], has_repl bool[F]). Shared by compiler.compile_pmml and the
    quantized wire (qtrees.py) — one implementation, one semantics."""
    F = ctx.n_fields
    repl = np.zeros((F,), np.float32)
    has_repl = np.zeros((F,), bool)
    for mf in schema.fields:
        if mf.missing_value_replacement is not None and mf.name in ctx.field_index:
            j = ctx.field_index[mf.name]
            has_repl[j] = True
            repl[j] = ctx.encode(mf.name, mf.missing_value_replacement)
    return repl, has_repl
