"""Quantized-wire fast path for numeric tree ensembles (the bench hot path).

The dense path-matrix lowering (trees.py) streams ``f32[B, F]`` feature
batches to the device. For the north-star workload — a 500-tree GBM scored
over a network stream (BASELINE config 2) — the binding resource is
host→device *bytes*, not FLOPs: scoring only ever compares each feature
against the model's own finite set of split thresholds, so a record can be
shipped as per-feature *threshold ranks* instead of raw floats.

This module builds that wire format:

- **Cut tables.** Every comparison split is normalised to a ``x <= cut``
  test (``<`` becomes ``<= nextafter(v, -inf)``; ``>``/``>=`` flip the
  children, which negates the split's path-matrix row and its missing
  default direction). The sorted unique cuts per feature form the table
  ``U[f]``; ``rank(x) = #{c in U[f] : c < x}`` and the split against cut
  ``U[f][i]`` holds iff ``rank(x) <= i``. Integer compares on ranks are
  therefore *bit-exact* with the float compares of the dense path.
- **Wire dtype.** ``uint8`` when every feature has <= 254 cuts (histogram-
  trained GBMs — LightGBM/XGBoost-hist — always satisfy this), else
  ``uint16``. The top code (255/65535) is the missing-value sentinel. A
  32-feature record shrinks from 128+32 bytes (f32 + mask) to 32 bytes.
- **Device kernel.** The same three-einsum structure as trees.py but all
  intermediates are int8 (sign indicators, path accumulator, leaf one-hot),
  which cuts HBM traffic ~4x; leaf values contract in a bf16 hi+lo split
  (exact to ~2^-17 relative) so the MXU stays in fast dtypes without
  giving up float32-level accuracy.
- **The twin's complete form.** A forest of shallow trees
  (``complete_depth``) skips the path contraction: every tree is laid
  out as a complete heap and its leaf picked by a select tree over its
  split compares, the leaf values the same hi+lo pair summed in float32.

- **Kernel layouts (round 11).** The packed tables exist in catalogue
  variants (compile/layouts.py): breadth-first SoA split ordering,
  per-feature uint8/uint16 wire packing (``pad_wire`` packs
  transparently when a ``wirepack`` layout is adopted), and the Pallas
  multi-tree megakernel — every variant byte-identical to this
  reference packing. The learned kernel search (compile/autotune.py +
  compile/costmodel.py) ranks them by predicted device-s/record and
  verifies only the top-K on device.
- **Fused featurization (round 6).** The same bucketize also exists as
  an on-device XLA pre-stage (``_make_encode_stage``: vmapped
  ``searchsorted`` over +inf-padded cut tables, replacement/sentinel
  folding included) traced INTO the scoring jit, so a raw f32 batch can
  ship as-is and one dispatch covers encode+pad+score
  (``QuantizedScorer.predict_fused``). Host vs fused is decided per
  (model, backend) by the measured autotuner (compile/autotune.py);
  the host path stays the default and the byte-parity oracle.

Reference parity: this accelerates the same evaluation the reference runs
per record on the CPU via JPMML-Evaluator (SURVEY.md §4.1 hot loop); the
general f32 path remains the semantic baseline and every model that is not
an all-numeric-comparison tree ensemble simply reports "not eligible".
"""

from __future__ import annotations

import hashlib
from dataclasses import dataclass, field
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from flink_jpmml_tpu.compile import common, prepare
from flink_jpmml_tpu.compile.common import (
    LowerCtx,
    apply_targets_value,
    build_codecs,
    extract_invalid_policy,
    extract_missing_replacements,
)
from flink_jpmml_tpu.compile.trees import (
    _canon_has_halt,
    _canon_leaves,
    _canonicalize_forest,
    pack_ensemble,
)
from flink_jpmml_tpu.models.prediction import Prediction, decode_batch
from flink_jpmml_tpu.pmml import ir
from flink_jpmml_tpu.utils.config import CompileConfig
from flink_jpmml_tpu.utils.exceptions import ModelCompilationException

# opcodes from trees.py: 0 '<', 1 '<=', 2 '>', 3 '>='
_SUPPORTED_OPS = frozenset((0, 1, 2, 3))
# fused-encode cut-table budget: the on-device featurizer carries a
# [F, L] +inf-padded table; a pathological uint16 wire (tens of
# thousands of cuts across many features) would pin tens of MB of HBM
# per served model for a stage the host bucketizer handles fine
_DEVICE_TABLE_BUDGET = 16 * 1024 * 1024
_REGRESSION_METHODS = frozenset(
    ("single", "sum", "average", "weightedAverage", "max", "median")
)
# the int8 path sum of a leaf ranges over ±depth (``count_i8``): past
# 127 it would wrap and mis-select leaves
_MAX_DEPTH = 127
# a tree's dense path matrix ([S, L], a byte a cell on the wire, four
# while packing) grows with the square of its leaves: at most what a
# complete tree of depth 10 makes, 1,023 splits × 1,024 leaves (about a
# MiB), the most the wire took while the f32 path's dense depth gated it
_MAX_TREE_PATH = 1023 * 1024
# the XLA twin keeps its temporaries a dispatch under this share of the
# device's memory: the rest holds what the deployment keeps there (a
# keyed state table and the copy its renorm sweep makes)
_TWIN_MEMORY_SHARE = 16
# the complete form's select tree is unrolled at trace time, a select a
# heap node (``_select_tree``): past depth 8, 255 selects a tree, the
# traced program grows with the heap, and such trees keep the path form
_MAX_HEAP_DEPTH = 8


def complete_depth(depth: int, S: int, L: int) -> int:
    """The depth of the twin's select tree for a forest whose deepest
    tree is ``depth`` deep and whose packed trees hold ``S`` splits and
    ``L`` leaves, or 0 where the twin contracts its path matrix.

    The complete form lays every tree out as a complete heap of
    ``depth`` levels and picks its leaf by ``2**depth - 1`` selects; the
    path form contracts every sign against every leaf, ``S · L``
    multiply-adds a tree. The heap is taken where its padded selects and
    leaves, ``2**(depth + 1)``, are no more than the path matrix's
    cells: a complete depth-6 tree (128 against 63 × 64) takes it, a
    leaf-wise tree of depth 16 or more (2**17 against 254 × 255 at
    most) and a sparse deep one (a 20-leaf tree of depth 10: 2,048
    against 380) keep the path matrix, as does a tree past
    ``_MAX_HEAP_DEPTH``. Read from the forest's shape alone."""
    if depth > _MAX_HEAP_DEPTH:
        return 0
    return depth if 2 ** (depth + 1) <= S * L else 0


def twin_bytes(rows: int, T: int, S: int, L: int, depth: int = 0) -> int:
    """The XLA twin's temporaries for ``rows`` records at once, at
    most, in the form it runs (``depth``: ``complete_depth``).

    The path form (``depth`` 0): a record's ±1 signs ``[T, S]`` and path
    sums ``[T, L]``, a byte each, and two path matrices' worth
    (``[T, S, L]`` a byte a cell) that XLA lays out again. What the
    compiler keeps depends on the forest's shape: described-v5e compiles
    of a 500 × 63 × 64 forest keep 0, 0.261 and 1.045 GB for 2,048, 4,096
    and 16,384 records, against 0.134, 0.264 and 1.044 GB here; of a
    500 × 254 × 255 forest, whose rank codes and signs live inside the
    path contraction's fusion, 0 to 0.6 MB at any of them, against
    0.586, 1.107 and 4.23 GB here.

    The complete form: a record's compares ``[2**depth - 1, T]``, a byte
    each, and its per-tree values, four, beside the heap's tables (split
    fields, cuts and default directions, 6 bytes a split; leaf values, 4
    a leaf). Described-v5e compiles of the covtype forest (21,000 trees
    of depth 6) keep 0.246, 0.599 and 1.307 GB for 256, 512 and 1,024
    records, against 0.374, 0.734 and 1.454 GB here: under a sixteenth
    of a v5e's memory that is steps of 512 records, where the path form
    took 256; a LightGBM forest of 500 × 254 × 255 keeps the path form
    and its 2,048."""
    if depth:
        n = 2 ** depth - 1
        return rows * T * (n + 4) + T * (6 * n + 4 * (n + 1))
    return rows * T * (S + L) + 2 * T * S * L


def _rank_codes(Xq: jax.Array, feat: jax.Array, dt) -> jax.Array:
    """Every split's rank code ``Xq[:, feat]`` (``[B, T, S]``, in the
    wire's dtype) as a contraction of the records with the one-hot of
    each split's field in ``dt``: exact, since a code is one product
    with 1 among products with 0, and ``dt`` holds every code of the
    wire (bfloat16 to 256, float32 to 2**24). On the chip the compiler
    builds the codes where the path contraction reads them (for
    LightGBM's 254 splits a tree, inside that contraction's own fusion:
    never written to memory), where a gather wrote them records minor
    and a reshape and a copy moved them again into the contraction's
    layout. Narrowed to the wire's dtype, what a shape leaves in memory
    is no more than the gather's (``twin_bytes`` holds for both)."""
    onehot = jax.nn.one_hot(feat, Xq.shape[1], dtype=dt)
    return jnp.einsum(
        "bf,tsf->bts", Xq.astype(dt), onehot,
        preferred_element_type=jnp.float32,
        precision=(jax.lax.Precision.HIGHEST if dt == jnp.float32
                   else None),
    ).astype(Xq.dtype)


def _rank_codes_i8(Xq: jax.Array, feat: jax.Array) -> jax.Array:
    """The rank codes ``Xq[:, feat]`` (``[B, T]``) of a uint8 wire on the
    chip's int8 units: each code moved into int8 (``x - 128``),
    contracted with the int8 one-hot of each split's field in int32, and
    moved back. Exact, as ``_rank_codes``; the complete form builds a
    plane of them a heap split, where the int8 contraction read 365 ms a
    65,536-record dispatch of the covtype forest against 404 in
    bfloat16 (TPU v5 lite, steps of 512 records)."""
    onehot = jax.nn.one_hot(feat, Xq.shape[1], dtype=jnp.int8)
    shifted = (Xq.astype(jnp.int32) - 128).astype(jnp.int8)
    return (jnp.einsum("bf,tf->bt", shifted, onehot,
                       preferred_element_type=jnp.int32)
            + 128).astype(Xq.dtype)


def complete_heap(P: np.ndarray, depth: int):
    """Every tree of a packed forest laid out as a complete heap of
    ``depth`` levels → ``(node, owner)``.

    ``P`` is the packed path matrix ``[T, S, L]`` in the wire's normal
    form (+1: the leaf lies where the split's ``go`` holds, -1: where it
    fails, 0: off the leaf's path), its splits in the preorder
    ``pack_ensemble`` gives them, so a split's level on a leaf's path is
    the count of that path's splits up to it. The heap is level-major;
    within a level a node is numbered by the turns on its path, the
    root's the lowest bit (1 where ``go`` fails). The children of node
    ``k`` of level ``d`` are then ``k`` and ``k + 2**d`` of level
    ``d + 1``: a level's selects read the two halves of the level below.

    ``node[t, s]`` is split ``s``'s index in its heap, ``2**d - 1 + k``
    (-1 for a padded slot); ``owner[t, j]`` is the packed leaf that heap
    leaf ``j`` carries. A leaf above ``depth`` carries every heap leaf
    of the subtree below it, whatever the padded splits there decide,
    so each record reaches the leaf its path matrix matches. Vectorised
    over chunks of 2,048 trees, a chunk's int32 planes tens of MB."""
    chunk = 2048
    T, S, L = P.shape
    node = np.full((T, S), -1, np.int32)
    owner = np.full((T, 1 << depth), -1, np.int32)
    for t0 in range(0, T, chunk):
        Pc = P[t0:t0 + chunk]
        on = Pc != 0
        pos = np.cumsum(on, axis=1, dtype=np.int32) - 1  # level on a path
        code = np.where(Pc < 0, np.left_shift(1, np.maximum(pos, 0)),
                        0).sum(axis=1, dtype=np.int32)  # [t, L] turns
        first = on.argmax(axis=2)  # [t, S]: a leaf below each split
        lvl = np.maximum(np.take_along_axis(
            pos, first[:, :, None], axis=2)[..., 0], 0)
        turns = np.take_along_axis(code, first, axis=1) & ((1 << lvl) - 1)
        node[t0:t0 + chunk] = np.where(
            on.any(axis=2), (1 << lvl) - 1 + turns, -1)
        cnt = on.sum(axis=1)  # [t, L]: a leaf's depth, 0 for a pad
        for c in range(1, depth + 1):
            tt, ll = np.nonzero(cnt == c)
            jj = code[tt, ll][:, None] + (
                np.arange(1 << (depth - c)) << c)[None, :]
            owner[t0 + tt[:, None], jj] = ll[:, None]
    return node, owner


def _select_tree(go: Sequence[jax.Array], table: jax.Array) -> jax.Array:
    """The complete form's walk: ``go``, one ``[B, T]`` plane a heap
    split, level-major (``complete_heap``), and ``table``
    ``[2**D, *P, T]``, each heap leaf's payload → ``[B, *P, T]``, every
    tree's payload at the leaf its record reaches. From the deepest
    level up, node ``k`` of level ``d`` is ``where(go, v[k], v[k +
    2**d])``: ``2**D - 1`` selects a tree, each on whole planes of the
    result's shape, unrolled node by node so that the compiler keeps the
    walk in one fusion (selects over whole levels, ``[B, 2**d, T]``
    each, were a fusion a level on a described v5e, each writing its
    level out)."""
    lead = (1,) * (table.ndim - 2)
    D = table.shape[0].bit_length() - 1
    level = [table[j] for j in range(1 << D)]
    for d in reversed(range(D)):
        w = 1 << d
        level = [
            jnp.where(go[w - 1 + k].reshape(go[w - 1 + k].shape[:1] + lead
                                             + go[w - 1 + k].shape[1:]),
                      level[k], level[k + w])
            for k in range(w)
        ]
    return level[0]


def twin_rows(n: int, T: int, S: int, L: int,
              budget: Optional[int], depth: int = 0) -> int:
    """Records the XLA twin scores in one step of its scan over a batch
    of ``n``: ``n`` itself where the whole batch's temporaries
    (``twin_bytes`` of the form that runs) fit ``budget`` bytes or no
    budget is known, else the largest power of two whose temporaries
    do."""
    if not budget or twin_bytes(n, T, S, L, depth) <= budget:
        return n
    rows = 1
    while twin_bytes(2 * rows, T, S, L, depth) <= budget:
        rows *= 2
    return rows


def _device_budget() -> Optional[int]:
    """A ``_TWIN_MEMORY_SHARE``-th of the first device's memory, or None
    where the backend does not say how much it has (the CPU)."""
    stats = jax.devices()[0].memory_stats() or {}
    limit = stats.get("bytes_limit")
    return int(limit) // _TWIN_MEMORY_SHARE if limit else None


@dataclass(frozen=True)
class QuantizedWire:
    """Host-side featurizer: f32 records → threshold-rank codes.

    ``cuts[j]`` is the sorted cut table of input column ``j`` (possibly
    empty); ``dtype`` is ``np.uint8`` or ``np.uint16``; ``sentinel`` marks
    missing values. ``repl``/``has_repl`` fold the model's top-level
    mining-schema ``missingValueReplacement`` into encoding so the device
    kernel never needs a mask plane.
    """

    fields: Tuple[str, ...]
    cuts: Tuple[np.ndarray, ...]
    dtype: type
    sentinel: int
    repl: np.ndarray  # f32[F]
    has_repl: np.ndarray  # bool[F]

    @property
    def bytes_per_record(self) -> int:
        return len(self.fields) * np.dtype(self.dtype).itemsize

    def _flat_tables(self):
        """(cuts_flat f32, offsets i32[F+1]) for the ragged bucketizer."""
        cached = getattr(self, "_flat_cache", None)
        if cached is None:
            offs = np.zeros((len(self.cuts) + 1,), np.int32)
            for j, c in enumerate(self.cuts):
                offs[j + 1] = offs[j] + len(c)
            flat = (
                np.concatenate(self.cuts).astype(np.float32)
                if offs[-1]
                else np.empty((0,), np.float32)
            )
            cached = (flat, offs)
            object.__setattr__(self, "_flat_cache", cached)
        return cached

    def _pow2_tables(self):
        """(+inf-padded [F, L] f32 table, L) for the lockstep bucketizer,
        or None when the padding blowup says the ragged path wins.

        L = next power of two ≥ the longest per-feature cut table; ranks
        are unchanged by +inf pads (a pad is never < any finite x). The
        lockstep kernel makes EVERY feature pay L-depth rounds and
        L-width memory, so it only pays off when cut counts are roughly
        balanced (GBM exports are); one 4096-cut feature among tiny ones
        would make every probe slower AND blow the padded table out of
        L2 — those models take the ragged kernel."""
        cached = getattr(self, "_pow2_cache", None)
        if cached is None:
            m = max((len(c) for c in self.cuts), default=0)
            total = sum(len(c) for c in self.cuts)
            L = 1
            while L < max(m, 1):
                L <<= 1
            n_f = max(len(self.cuts), 1)
            blowup = (n_f * L) / max(total, 1)
            if blowup > 4.0 and L > 64:
                cached = (None, 0)  # skewed: ragged path
            else:
                padded = np.full((n_f, L), np.inf, np.float32)
                for j, c in enumerate(self.cuts):
                    padded[j, : len(c)] = c
                cached = (np.ascontiguousarray(padded), L)
            object.__setattr__(self, "_pow2_cache", cached)
        return cached

    def encode(
        self, X: np.ndarray, M: Optional[np.ndarray] = None
    ) -> np.ndarray:
        """f32[B, F] (+ optional missing mask) → rank codes [B, F].

        NaNs count as missing. Missing cells take the mining-schema
        replacement value when one is declared, else the sentinel. Uses the
        multithreaded C++ bucketizer (native/fjt_native.cpp) when built;
        numpy searchsorted otherwise (identical semantics).
        """
        from flink_jpmml_tpu.runtime import native

        padded, L = self._pow2_tables()
        if padded is not None:
            out = native.bucketize_pow2(
                X, padded, L,
                self.repl, self.has_repl.astype(np.uint8), self.dtype,
                mask=M,
            )
        else:  # skewed cut tables: ragged kernel (see _pow2_tables)
            flat, offs = self._flat_tables()
            out = native.bucketize(
                X, flat, offs,
                self.repl, self.has_repl.astype(np.uint8), self.dtype,
                mask=M,
            )
        if out is not None:
            return out
        X = np.asarray(X, np.float32)
        miss = np.isnan(X)
        if M is not None:
            miss = miss | M
        if self.has_repl.any():
            use = miss & self.has_repl[None, :]
            X = np.where(use, self.repl[None, :], X)
            miss = miss & ~self.has_repl[None, :]
        out = np.empty(X.shape, self.dtype)
        for j, cuts in enumerate(self.cuts):
            # rank = #{c < x}  (side='left' over the sorted cut table)
            out[:, j] = np.searchsorted(cuts, X[:, j], side="left")
        out[miss] = self.sentinel
        return out

    def encode_records(self, space: prepare.FieldSpace, records) -> np.ndarray:
        X, M = prepare.from_records(space, records)
        return self.encode(X, M)

    def device_tables(self) -> Optional[Dict[str, np.ndarray]]:
        """Operands of the fused on-device encode stage, or None when the
        padded table blows the budget (such models stay host-encoded).

        ``enc_cuts`` is the [F, L] +inf-padded cut table (L the next
        power of two ≥ the longest per-feature table). Unlike
        :meth:`_pow2_tables` there is no skew heuristic: the device
        searchsorted is lockstep by construction and +inf pads never
        change a rank (a pad is never < any finite x), so padding is
        free of rank error regardless of skew."""
        cached = getattr(self, "_dev_cache", None)
        if cached is None:
            m = max((len(c) for c in self.cuts), default=0)
            L = 1
            while L < max(m, 1):
                L <<= 1
            F = max(len(self.cuts), 1)
            if F * L * 4 > _DEVICE_TABLE_BUDGET:
                cached = (None,)
            else:
                padded = np.full((F, L), np.inf, np.float32)
                for j, c in enumerate(self.cuts):
                    padded[j, : len(c)] = c
                cached = ({
                    "enc_cuts": np.ascontiguousarray(padded),
                    "enc_repl": self.repl.astype(np.float32),
                    "enc_has_repl": self.has_repl.astype(bool),
                },)
            object.__setattr__(self, "_dev_cache", cached)
        return cached[0]


def _make_encode_stage(sentinel: int, out_dtype, any_repl: bool):
    """Build the on-device featurize stage: f32[B, F] → rank codes
    [B, F] in the wire dtype, byte-identical to
    :meth:`QuantizedWire.encode` (tested in tests/test_fused_encode.py).

    NaN cells take the mining-schema replacement where one is declared,
    else the missing sentinel; ``rank = #{cut < x}`` comes from a
    vmapped ``searchsorted`` over the +inf-padded per-feature tables —
    bit-exact with the host bucketizer's ragged/lockstep searches. The
    stage is meant to be traced INTO the scoring jit (one dispatch for
    encode+pad+score: the fused path of ISSUE 2)."""

    def encode_stage(pp, X):
        X = X.astype(jnp.float32)
        miss = jnp.isnan(X)
        if any_repl:
            use = miss & pp["enc_has_repl"][None, :]
            X = jnp.where(use, pp["enc_repl"][None, :], X)
            miss = miss & ~pp["enc_has_repl"][None, :]
        ranks = jax.vmap(
            lambda c, x: jnp.searchsorted(c, x, side="left"),
            in_axes=(0, 1),
            out_axes=1,
        )(pp["enc_cuts"], X)
        return jnp.where(miss, sentinel, ranks).astype(out_dtype)

    return encode_stage


@dataclass
class QuantizedScorer:
    """Jitted rank-wire scorer for one tree-ensemble model.

    ``predict_wire(Xq)`` runs the device kernel on an encoded batch and
    returns f32 values (the full aggregate incl. Targets rescale);
    ``score(X, M)`` is the convenience f32 entry (encode + predict).
    """

    wire: QuantizedWire
    params: Dict[str, jnp.ndarray]
    field_space: prepare.FieldSpace
    batch_size: Optional[int]
    n_trees: int
    _jit_fn: object
    backend: str = "xla"  # "xla" | "pallas"
    labels: Tuple[str, ...] = ()  # classification class list; () = regression
    # scan-wrapped multi-chunk dispatchers, keyed by (K, donate) with
    # K = n // batch_size (built lazily; one trace per distinct key —
    # callers bound the K set; fused twins share the dict under
    # ("fused", K, donate) keys)
    _multi_fns: dict = field(default_factory=dict)
    # donate_argnums twin of _jit_fn (built lazily on first donated call)
    _donate_fn: object = None
    # fused featurize+score path: which encode the runtime dispatch
    # helpers take — "host" (wire.encode + uint codes on the wire) or
    # "fused" (raw f32 to the device, encode traced into the scoring
    # jit). Decided per (model, backend) by compile/autotune.py; "host"
    # is the default and the byte-parity oracle.
    encode_mode: str = "host"
    # stable identity for the on-disk autotune cache (wire tables +
    # packed shapes; see build_quantized_scorer)
    model_hash: str = ""
    tuned: object = None  # applied TunedConfig (autotune provenance)
    # un-jitted fused program (encode stage + kernel in one trace) and
    # the bare encode stage (the parity-test surface); None when the
    # model's cut tables blow the device-table budget
    _fused_inner: object = None
    _encode_stage: object = None
    # autotune hook: rebuild the pallas backend under a catalogue
    # layout → a built-variant dict or None when ineligible; None on
    # the XLA backend. Released by compile/autotune.py once a config
    # is applied — the closure pins the host-side packing tables,
    # which a long-lived served model must not carry next to its
    # device-resident copies.
    _pallas_rebuild: object = None
    # XLA twin of the rebuild hook: _xla_rebuild(layout) → built
    # variant dict (BFS split order / wire packing) or None; released
    # with the same discipline (it pins the host numpy param tables)
    _xla_rebuild: object = None
    # which catalogue layout (compile/layouts.py) is currently built
    layout: str = "ref"
    # active wire packing plan (layouts.WirePack) — pad_wire packs the
    # rank codes through it before padding/staging; None = raw codes
    _wire_pack: object = None
    # packed-shape summary for the learned cost model's features
    # (compile/costmodel.py): trees/splits/leaves/fields/batch/dtype
    _meta: dict = field(default_factory=dict)
    # the adopted variant's feature dict + canonical id (set by
    # autotune): ride the dispatch profile into the kernel cost ledger
    _cost_feat: object = None
    _cost_variant: object = None
    # the cost model's prediction for the variant ACTUALLY serving —
    # distinct from tuned.predicted_s_per_record, which records cache
    # provenance: a cached variant that degrades to the built defaults
    # must not ship its prediction into the live drift band
    _pred_s_per_record: object = None
    # cross-model packing hook (compile/packs.py): the un-jitted kernel
    # body + wire facts a PackedScorer needs to re-run this model as
    # one subgraph of a multi-tenant program. A small closure (no param
    # tables pinned — the pack reads the live ``params``); None on the
    # Pallas backend, whose program bakes its own grid.
    _pack_info: object = None
    # the mesh this scorer spans (``on_mesh``; None: one device), and
    # the twins built from this scorer, one a mesh
    mesh: object = None
    _mesh_twins: dict = field(default_factory=dict)
    # a binary logit chain (``_match_chain``): the program returns the
    # probability of ``labels[event_class]`` alone, a ``[B]`` vector the
    # state folds and a sink reads like a regression score; the other
    # class's probability and the label are made from it at decode. A
    # softmax chain returns the classification triple, as a vote does
    event_class: Optional[int] = None
    # the XLA twin's temporaries a dispatch may take (``twin_rows``);
    # None where the device does not say how much memory it has
    twin_budget: Optional[int] = None
    # the depth of the XLA twin's select tree (``complete_depth``); 0
    # where it contracts its path matrix, and on the Pallas kernel
    twin_depth: int = 0

    def twin_rows(self, n: int) -> Optional[int]:
        """Records one step of the XLA twin's scan takes in a batch of
        ``n`` (``n`` itself: no scan); None on the Pallas kernel."""
        if self.backend != "xla":
            return None
        m = self._meta
        return twin_rows(n, int(m["trees"]), int(m["splits"]),
                         int(m["leaves"]), self.twin_budget,
                         self.twin_depth)

    # -- a scorer over a mesh -----------------------------------------------

    def on_mesh(self, mesh):
        """This scorer over ``mesh``: forest parameters replicated, the
        wire batch sharded on the data axis, each chip running the
        unchanged kernel on its own rows under ``shard_map``. Scores
        are this scorer's, bit for bit (a record's score does not
        depend on its batch). One twin a mesh, built once."""
        if mesh is None or mesh == self.mesh:
            return self
        if mesh in self._mesh_twins:
            return self._mesh_twins[mesh]
        import dataclasses

        from jax.sharding import NamedSharding, PartitionSpec as P

        twin = dataclasses.replace(
            self,
            params=jax.device_put(self.params, NamedSharding(mesh, P())),
            mesh=mesh, _multi_fns={}, _donate_fn=None, _mesh_twins={},
        )
        self._mesh_twins[mesh] = twin
        return twin

    @property
    def data_width(self) -> int:
        """Chips a batch is spread over (1 without a mesh)."""
        if self.mesh is None:
            return 1
        from flink_jpmml_tpu.parallel.mesh import DATA_AXIS

        return int(self.mesh.shape.get(DATA_AXIS, 1))

    def shardings(self):
        """→ (replicated, sharded on the leading axis over the data
        axis) on this scorer's mesh."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        from flink_jpmml_tpu.parallel.mesh import DATA_AXIS

        return (NamedSharding(self.mesh, P()),
                NamedSharding(self.mesh, P(DATA_AXIS)))

    def spmd(self, fn, n_sharded: int = 1):
        """``fn(params, *operands)`` as each chip of the mesh runs it:
        params whole, every other operand and every output its own
        rows. Without a mesh, ``fn`` itself."""
        if self.mesh is None:
            return fn
        from jax.sharding import PartitionSpec as P

        from flink_jpmml_tpu.parallel.mesh import DATA_AXIS

        data = P(DATA_AXIS)
        return jax.shard_map(
            fn, mesh=self.mesh, in_specs=(P(),) + (data,) * n_sharded,
            out_specs=data, check_vma=False,
        )

    def stage(self, payload):
        """The aligned batch onto the device(s), asynchronously: each
        chip of a mesh receives its own rows."""
        if self.mesh is None:
            return jax.device_put(payload)
        return jax.device_put(payload, self.shardings()[1])

    @property
    def is_classification(self) -> bool:
        return bool(self.labels)

    @property
    def supports_fused(self) -> bool:
        return self._fused_inner is not None

    @property
    def staged_bytes_per_record(self) -> float:
        """Bytes one record costs on the wire under the CURRENT layout
        and encode mode — the honest bytes/record for the roofline and
        the kernel cost ledger (wire packing shrinks it; fused encode
        ships raw f32)."""
        if self.encode_mode == "fused" and self.supports_fused:
            return 4.0 * len(self.wire.fields)
        if self._wire_pack is not None:
            return float(self._wire_pack.bytes_per_record)
        return float(self.wire.bytes_per_record)

    def pack_wire(self, Xq):
        """The adopted wire packing alone (identity without one): for a
        caller that lays the rows out itself (a keyed mesh dispatch:
        runtime/shuffle.py ``ShardPlan.place``)."""
        return Xq if self._wire_pack is None else self._wire_pack.pack(Xq)

    def pad_wire(self, Xq):
        """Host-side batch alignment → ``(Xq_padded, K)``.

        The ONE place batch-size alignment happens: any batch whose length
        differs from the compile ``batch_size`` is zero-padded up to a
        multiple of it — one padded call on the XLA path (``K == 1``,
        bounded retrace per distinct multiple), fixed-grid batch-size
        chunks on Pallas (``K > 1`` — the kernel bakes
        ``out_shape=(batch_size,)``). Callers pass the encoded batch
        as-is and trim via ``decode(out, n)``.  Split out of
        :meth:`predict_wire` so the overlapped pipeline can stage the
        aligned batch onto the device (``jax.device_put``) *before*
        dispatch — see :meth:`predict_padded`.

        Under a ``wirepack`` layout the rank codes pack here (before
        padding — zero pad rows are packed zero bytes either way), so
        every caller's staged payload and bytes accounting see the
        packed wire without code changes."""
        Xq = self.pack_wire(Xq)
        n = Xq.shape[0]
        bs = self.batch_size
        if self.mesh is not None:
            # every chip takes the same whole number of chunks (K
            # counts a chip's), in arrival order
            bs = (bs or 1) * self.data_width
        if bs is None or n == bs:
            return Xq, 1
        pad = (-n) % bs
        if pad:
            Xq = np.concatenate(
                [Xq, np.zeros((pad, Xq.shape[1]), Xq.dtype)], axis=0
            )
        if self.backend == "pallas":
            # one scan-wrapped dispatch for all K chunks: a python
            # loop of per-chunk calls pays the dispatch cost K times
            # (the block pipeline's multi-chunk dispatches exist to
            # amortize it)
            return Xq, Xq.shape[0] // bs
        return Xq, 1

    def predict_padded(self, Xq, K: int, donate: bool = False):
        """Async-dispatch an already-aligned (and possibly already
        device-resident) batch from :meth:`pad_wire`.

        ``donate=True`` routes through a ``donate_argnums=(1,)`` twin of
        the jitted entry point: a device-staged input buffer is consumed
        by the call — released to the device allocator at dispatch
        rather than pinned until fetch, so the overlapped pipeline's
        steady-state input allocations stay bounded at its window depth
        (the uint8 wire cannot output-alias the f32 scores; donation
        frees, it does not alias).  Callers that donate must not reuse
        ``Xq`` afterwards."""
        return self._entry(K, donate)(self.params, Xq)

    def predict_wire(self, Xq, donate: bool = False):
        """→ f32 values [B] (regression) or (values, probs, label_idx).

        Convenience compose of :meth:`pad_wire` + :meth:`predict_padded`
        (alignment + async dispatch in one call)."""
        Xq, K = self.pad_wire(Xq)
        return self.predict_padded(Xq, K, donate=donate)

    def _entry(self, K: int, donate: bool):
        """The jitted entry point for K chunks, optionally donating its
        batch argument.  Donating twins are separate compiles of the
        same program (built lazily — callers that never donate never
        pay them)."""
        if K == 1 and self.mesh is None:
            if not donate:
                return self._jit_fn
            if self._donate_fn is None:
                inner = getattr(self._jit_fn, "__wrapped__", self._jit_fn)
                self._donate_fn = jax.jit(inner, donate_argnums=(1,))
            return self._donate_fn
        return self._multi_fn(K, donate)

    def _scan_over(self, inner, K: int):
        """Scan ``inner`` over K fixed-size chunks of the leading axis
        (Pallas bakes its batch grid, so bigger batches iterate) —
        shared by the host-encoded and fused dispatch entries."""
        bs = self.batch_size

        def scan_fn(p, Xq):
            def body(c, xq):
                return c, inner(p, xq)

            _, outs = jax.lax.scan(
                body, 0, Xq.reshape(K, bs, Xq.shape[1])
            )
            if isinstance(outs, tuple):  # classification triple
                return tuple(
                    o.reshape((K * bs,) + o.shape[2:]) for o in outs
                )
            return outs.reshape(-1)

        return scan_fn

    def _multi_fn(self, K: int, donate: bool = False):
        """Jitted scan over K fixed-size chunks. Built once per distinct
        (K, donate); callers bound the K set (the block pipeline
        aggregates to powers of two)."""
        if K == 1 and self.mesh is None:
            return self._entry(1, donate)  # already compiled; no wrapper
        key = (K, donate)
        fn = self._multi_fns.get(key)
        if fn is None:
            inner = getattr(self._jit_fn, "__wrapped__", self._jit_fn)
            fn = jax.jit(
                self.spmd(inner if K == 1 else self._scan_over(inner, K)),
                donate_argnums=(1,) if donate else (),
            )
            self._multi_fns[key] = fn
        return fn

    # -- fused featurize+score entries ------------------------------------

    def pad_f32(self, X):
        """:meth:`pad_wire`'s f32 twin for the fused path: zero-row pad
        up to a multiple of the compile batch (trimmed by
        ``decode(out, n)``), chunk count for the Pallas fixed grid."""
        X = np.ascontiguousarray(X, np.float32)
        n = X.shape[0]
        bs = self.batch_size
        if self.mesh is not None:
            bs = (bs or 1) * self.data_width  # as pad_wire
        if bs is None or n == bs:
            return X, 1
        pad = (-n) % bs
        if pad:
            X = np.concatenate(
                [X, np.zeros((pad, X.shape[1]), np.float32)], axis=0
            )
        if self.backend == "pallas":
            return X, X.shape[0] // bs
        return X, 1

    def _fused_entry(self, K: int, donate: bool):
        if self._fused_inner is None:
            raise ModelCompilationException(
                "fused encode unavailable for this model (device cut "
                "tables over budget); use the host-encode path"
            )
        key = ("fused", K, donate)
        fn = self._multi_fns.get(key)
        if fn is None:
            inner = (
                self._fused_inner
                if K == 1
                else self._scan_over(self._fused_inner, K)
            )
            fn = jax.jit(
                self.spmd(inner), donate_argnums=(1,) if donate else ()
            )
            self._multi_fns[key] = fn
        return fn

    def predict_fused_padded(self, X, K: int, donate: bool = False):
        """Fused twin of :meth:`predict_padded`: ``X`` is an aligned
        (possibly device-staged) RAW f32 batch; one dispatch covers
        encode+score. Donation semantics match predict_padded (the f32
        batch cannot output-alias the scores either; donating frees the
        staging buffer at dispatch)."""
        return self._fused_entry(K, donate)(self.params, X)

    def predict_fused(self, X, donate: bool = False):
        """Fused convenience entry: align (:meth:`pad_f32`) + dispatch.
        NaN cells are the missing convention on this path — callers
        with an explicit mask fold it in as NaN first."""
        X, K = self.pad_f32(X)
        return self.predict_fused_padded(X, K, donate=donate)

    # -- state-armed entries (compile/statekernel.py) ----------------------

    def predict_padded_state(self, Xq, K: int, table, slots, rel, w,
                             reset, donate: bool = False):
        """State-armed twin of :meth:`predict_padded`: one dispatch
        scores the aligned wire batch AND folds it through the keyed
        state table → ``(out, derived[B, 8], S')``. ``donate=True``
        donates both the staged batch and the state buffer (the update
        is in-place on device); the caller commits ``S'`` back to the
        table. Slot/decay operands come from
        ``KeyedStateTable.assign_slots`` (host routing)."""
        from flink_jpmml_tpu.compile import statekernel

        fn = statekernel.entry_for(
            self, "wire", K, donate, table.spec.decay, table.local_scratch
        )
        return fn(self.params, Xq, table.values, slots, rel, w, reset)

    def predict_fused_padded_state(self, X, K: int, table, slots, rel,
                                   w, reset, donate: bool = False):
        """Fused-encode twin of :meth:`predict_padded_state` (raw f32
        in, encode+score+state in one dispatch)."""
        from flink_jpmml_tpu.compile import statekernel

        fn = statekernel.entry_for(
            self, "fused", K, donate, table.spec.decay, table.local_scratch
        )
        return fn(self.params, X, table.values, slots, rel, w, reset)

    def encode_device(self, X):
        """Run ONLY the on-device encode stage (jitted) → rank codes.
        The byte-parity oracle surface: tests assert this equals
        ``wire.encode`` exactly, code for code."""
        if self._encode_stage is None:
            raise ModelCompilationException(
                "fused encode unavailable for this model"
            )
        key = ("enc",)
        fn = self._multi_fns.get(key)
        if fn is None:
            fn = jax.jit(self._encode_stage)
            self._multi_fns[key] = fn
        return fn(self.params, jnp.asarray(X, jnp.float32))

    def adopt_backend(self, params, jit_fn, fused_inner) -> None:
        """Autotune apply hook: swap in a re-packed kernel. Clears every
        lazily-built compile cache keyed off the old program."""
        self.params = params
        self._jit_fn = jit_fn
        self._fused_inner = fused_inner
        self._multi_fns.clear()
        self._mesh_twins.clear()
        self._donate_fn = None

    def build_variant(self, layout: str = "ref"):
        """Kernel-search hook: build (without adopting) the catalogue
        variant ``layout`` → a built dict for :meth:`adopt_variant`, or
        None when this scorer can't honour it (a layout this backend
        does not know, nothing to pack, hooks already released) — a
        stale cached candidate degrades to the built defaults. Whatever
        a build raises is a defect and propagates."""
        rebuild = (
            self._pallas_rebuild if self.backend == "pallas"
            else self._xla_rebuild
        )
        return rebuild(layout) if rebuild is not None else None

    def adopt_variant(self, built: dict, layout: str = "ref") -> None:
        """Swap in a variant from :meth:`build_variant`: kernel program
        + params + (possibly) a wire packing plan, atomically enough
        that pad_wire and the jit entry always agree on the wire
        format."""
        self.adopt_backend(
            built["params"], built["jit_fn"], built["fused_inner"]
        )
        self._wire_pack = built.get("wire_pack")
        self.layout = layout

    def score(self, X, M=None) -> List[Prediction]:
        n = np.asarray(X).shape[0]
        out = self.predict_wire(self.wire.encode(X, M))
        return self.decode(out, n)

    def decode(self, out, n: int) -> List[Prediction]:
        if not self.is_classification:
            values = np.asarray(out, np.float32)[:n]
            return decode_batch(values.tolist(), [True] * n, None, None)
        if self.event_class is not None:
            # the f32 path's binary logit: [p, 1 - p] in table order,
            # the label their argmax, the value the label's probability
            p = np.asarray(out, np.float32)[:n]
            P = np.empty((p.shape[0], 2), np.float32)
            P[:, self.event_class] = p
            P[:, 1 - self.event_class] = np.float32(1.0) - p
            idx = P.argmax(axis=1)
            value = P[np.arange(P.shape[0]), idx]
        else:
            value, probs, lab = out
            value = np.asarray(value, np.float32)[:n]
            P = np.asarray(probs, np.float32)[:n]
            idx = np.asarray(lab)[:n]
        lbls = [self.labels[i] for i in idx]
        pmaps = [dict(zip(self.labels, row.tolist())) for row in P]
        return decode_batch(value.tolist(), [True] * n, lbls, pmaps)


def _split_bf16(v: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """f32 → (hi, lo) bf16 pair with hi + lo ≈ v to ~2^-17 relative."""
    hi = v.astype(jnp.bfloat16)
    lo = (v - hi.astype(np.float32)).astype(jnp.bfloat16)
    return np.asarray(hi), np.asarray(lo)


class _Chain(NamedTuple):
    """A classification chain the rank wire takes (``_match_chain``):
    ``models[k]`` sums trees into one field ``m_k``, and ``links[k]`` is
    ``(a_k, b_k)`` of the table ``b_k + a_k·m_k`` over it. ``norm`` is
    ``"logit"`` (one sum; the event class's table first, the other
    empty) or ``"softmax"`` (a sum and a table a class)."""

    models: Tuple[ir.ModelIR, ...]
    links: Tuple[Tuple[float, float], ...]
    labels: Tuple[str, ...]
    norm: str


def _match_chain(doc: ir.PmmlDocument) -> Optional[_Chain]:
    """doc → its ``_Chain`` when the model is a classifier chain as JPMML
    writes a GBM classifier: a classification ``modelChain`` of
    ``<True/>`` segments, each but the last a regression model with one
    ``predictedValue`` output field ``m_k``, the last a classification
    ``RegressionModel`` whose tables are each ``b_k + a_k·m_k`` over the
    k-th field alone. Two forms:

    - binary logit: one sum and two tables, the second with no term;
      ``p = 1 / (1 + exp(-(b + a·m)))`` for the first table's category
      and ``1 - p`` for the second (the f32 path's two-table logit);
    - softmax: C ≥ 2 sums and C tables, ``p = softmax(b + a∘m)`` over
      the tables' categories (JPMML-XGBoost's multi-class model).

    None for any other document: another link, a table over another or
    a second field, a predicate that is not ``<True/>``, or a count of
    sums or tables that the form does not have."""
    model = doc.model
    if not (
        isinstance(model, ir.MiningModelIR)
        and model.function_name == "classification"
        and model.segmentation is not None
        and model.segmentation.multiple_model_method == "modelChain"
    ):
        return None
    segs = model.segmentation.segments
    if len(segs) < 2 or not all(
        isinstance(s.predicate, ir.TruePredicate) for s in segs
    ):
        return None
    *sums, last = segs
    lr = last.model
    if not (
        isinstance(lr, ir.RegressionModelIR)
        and lr.function_name == "classification"
    ):
        return None
    fields = []
    for s in sums:
        outs = s.output_fields
        if (
            len(outs) != 1
            or outs[0].feature != "predictedValue"
            or s.model.function_name != "regression"
        ):
            return None
        fields.append(outs[0].name)
    norm = lr.normalization_method
    if norm == "logit" and len(sums) == 1 and len(lr.tables) == 2:
        tables, rest = lr.tables[:1], lr.tables[1:]
    elif norm == "softmax" and len(sums) >= 2 and len(lr.tables) == len(sums):
        tables, rest = lr.tables, ()
    else:
        return None
    if len(set(fields)) != len(fields) or any(
        t.numeric_predictors or t.categorical_predictors for t in rest
    ):
        return None
    links = []
    for table, name in zip(tables, fields):
        terms = table.numeric_predictors
        if (
            len(terms) != 1
            or terms[0].name != name
            or terms[0].exponent != 1.0
            or table.categorical_predictors
        ):
            return None
        links.append((terms[0].coefficient, table.intercept))
    labels = tuple(
        t.target_category or str(i) for i, t in enumerate(lr.tables)
    )
    return _Chain(tuple(s.model for s in sums), tuple(links), labels, norm)


def _linear_coef(method: str, weights: Sequence[float]) -> Optional[np.ndarray]:
    """Each tree's coefficient in a linear aggregate of its ensemble
    (folded into its leaf values); None for ``max`` / ``median``."""
    w = np.asarray(weights, np.float32)
    if method in ("single", "sum"):
        return np.ones(w.shape, np.float32)
    if method == "average":
        return np.full(w.shape, 1.0 / w.size, np.float32)
    if method == "weightedAverage":
        return (w / w.sum()).astype(np.float32)
    return None


def _match_ensemble(
    model: ir.ModelIR,
) -> Optional[Tuple[List[ir.TreeModelIR], List[float], str]]:
    """model → (trees, weights, method) when it is a tree ensemble the
    fast path can take (regression aggregates, or classification single /
    majority votes); None otherwise."""
    if isinstance(model, ir.TreeModelIR):
        return [model], [1.0], "single"
    if not isinstance(model, ir.MiningModelIR):
        return None
    seg = model.segmentation
    if seg is None:
        return None
    method = seg.multiple_model_method
    if model.function_name == "regression":
        if method not in _REGRESSION_METHODS:
            return None
    elif method not in ("majorityVote", "weightedMajorityVote"):
        return None
    trees: List[ir.TreeModelIR] = []
    weights: List[float] = []
    for s in seg.segments:
        if not isinstance(s.predicate, ir.TruePredicate):
            return None
        if not isinstance(s.model, ir.TreeModelIR):
            return None
        if s.model.function_name != model.function_name:
            return None
        trees.append(s.model)
        weights.append(s.weight)
    if not trees:
        return None
    return trees, weights, method


def build_quantized_scorer(
    doc: ir.PmmlDocument,
    batch_size: Optional[int] = None,
    config: Optional[CompileConfig] = None,
    backend: str = "auto",
    pallas_interpret: bool = False,
) -> Optional[QuantizedScorer]:
    """Try to build the rank-wire fast path for ``doc``.

    Returns None when the model shape is outside the fast path's contract
    (non-regression, non-tree segments, set/equality splits, missing-value
    strategies that null predictions, trees deeper than the int8 path
    sum holds, or trees whose dense path matrix passes a complete depth-10
    tree's). A classifier chain over linear regression ensembles
    (``_match_chain``: a binary logit, or a softmax over equal groups of
    trees, one a class) is taken, its link applied in the same program.
    Raises only on malformed documents.

    ``backend``: "auto" picks the Pallas VMEM-resident kernel
    (qtrees_pallas.py) on TPU when eligible (uint8 wire, fixed batch, and
    a linear regression aggregate or a majority-vote classification
    forest), the XLA einsum path otherwise; "xla"/"pallas" force one.
    ``pallas_interpret`` runs the kernel in interpreter mode (CPU tests).
    """
    config = config or CompileConfig()
    if doc.transformations.derived_fields:
        # derived-field preprocessing isn't folded into the rank wire
        return None
    if doc.output_fields:
        # top-level <Output> post-processing happens in CompiledModel
        # .decode; the wire's decode path doesn't carry it
        return None
    chain = _match_chain(doc)
    if chain is not None and doc.targets:
        return None
    # a binary chain's (a, b); a softmax chain's [C, 2] table of them
    link = softmax = None
    chain_labels: Tuple[str, ...] = ()
    if chain is not None:
        chain_labels = chain.labels
        if chain.norm == "logit":
            link = chain.links[0]
        else:
            softmax = np.asarray(chain.links, np.float32)
    parts = [
        _match_ensemble(m)
        for m in (chain.models if chain is not None else (doc.model,))
    ]
    if any(m is None for m in parts):
        return None
    if len({len(m[0]) for m in parts}) != 1:
        # a softmax chain's classes are equal groups of trees, in order
        return None
    trees = [t for m in parts for t in m[0]]
    weights = [w for m in parts for w in m[1]]
    method = parts[0][2]

    fields = doc.active_fields
    ctx = LowerCtx(
        field_index={f: i for i, f in enumerate(fields)},
        codecs=build_codecs(doc.data_dictionary),
        config=config,
    )
    # the rank wire bypasses compiler.full_fn's sanitize stage: any doc
    # whose fields can be *invalid* (declared category tables, Intervals)
    # must stay on the f32 path for invalidValueTreatment semantics
    if (
        extract_invalid_policy(doc.data_dictionary, doc.model.mining_schema, ctx)
        is not None
    ):
        return None
    try:
        canons, classification, depth = _canonicalize_forest(trees, ctx)
    except ModelCompilationException:
        return None
    # The rank wire's one depth bound is its int8 path sum's. The packed
    # path matrix is [T, S, L] at any depth: leaves and splits size it,
    # not depth. ``max_dense_depth`` is the f32 path's choice between its
    # dense and iterative lowerings (trees.py), and the leaf-wise trees
    # GBM libraries grow are deeper than its 10.
    if depth > _MAX_DEPTH:
        return None
    # Leaves and splits bound the size instead, before anything dense is
    # packed: a tree's path matrix at most ``_MAX_TREE_PATH`` cells, and
    # where the device says its memory, one record's twin temporaries
    # within the twin's budget (its scan's step is at least a record), in
    # the form the twin takes for this shape (``complete_depth``)
    leaves = max(_canon_leaves(c) for c in canons)
    S, L = max(leaves - 1, 1), max(leaves, 2)
    heap_depth = complete_depth(depth, S, L)
    twin_budget = _device_budget()
    if S * L > _MAX_TREE_PATH or (
        twin_budget
        and twin_bytes(1, len(canons), S, L, heap_depth) > twin_budget
    ):
        return None
    # a chain's link reads linear sums of its trees
    if chain is not None and (classification or any(
        _linear_coef(m, w) is None for _, w, m in parts
    )):
        return None
    if classification and method not in (
        "single", "majorityVote", "weightedMajorityVote"
    ):
        return None
    # halting missing-value semantics (lastPrediction / returnLastPrediction)
    # need the iterative f32 backend; pack_ensemble would raise on them
    if any(_canon_has_halt(c) for c in canons):
        return None
    try:
        packed = pack_ensemble(canons, classification)
    except ModelCompilationException:
        return None
    p = packed.params
    if "set_codes" in p or p["mnull"].any():
        return None
    T, S, L = packed.n_trees, packed.n_splits, packed.n_leaves
    ops = packed.opcodes
    # real split slots lie on >=1 leaf path; padded slots have all-zero rows
    real = np.abs(p["P"]).sum(axis=2) > 0  # [T, S]
    if not set(np.unique(ops[real]).tolist()) <= _SUPPORTED_OPS:
        return None
    # a codec (string-categorical) field under an order comparison would
    # compare category codes — semantically fragile; leave to the f32 path
    if ctx.codecs:
        codec_cols = {ctx.field_index[f] for f in ctx.codecs if f in ctx.field_index}
        if any(int(c) in codec_cols for c in np.unique(p["feat"][real])):
            return None

    thresh = p["thresh"]
    feat = p["feat"]
    # normalise every real split to "go_left iff rank <= cut_index"
    #   '<'  v  → cut nextafter(v,-inf)            '>'  v → cut v, flip
    #   '<=' v  → cut v                            '>=' v → cut nextafter, flip
    cut_val = np.where(
        (ops == 0) | (ops == 3),
        np.nextafter(thresh, -np.inf, dtype=np.float32),
        thresh,
    )
    flip = (ops == 2) | (ops == 3)

    F = len(fields)
    cuts: List[np.ndarray] = [np.empty((0,), np.float32) for _ in range(F)]
    for j in range(F):
        sel = real & (feat == j)
        if sel.any():
            cuts[j] = np.unique(cut_val[sel].astype(np.float32))
    max_cuts = max((len(c) for c in cuts), default=0)
    if max_cuts <= 254:
        dtype, sentinel = np.uint8, 255
    elif max_cuts <= 65534:
        dtype, sentinel = np.uint16, 65535
    else:
        return None

    # threshold index per split: position of its cut in its feature's table
    qthr = np.zeros((T, S), dtype)
    for j in range(F):
        sel = real & (feat == j)
        if sel.any():
            qthr[sel] = np.searchsorted(cuts[j], cut_val[sel]).astype(dtype)

    dleft = (p["dleft"] > 0.5) ^ flip
    P = p["P"].copy()
    P[flip] = -P[flip]

    # fold per-tree aggregate coefficients into leaf values where the
    # aggregate is linear, so one fused einsum produces the final value
    w = np.asarray(weights, np.float32)
    fused_linear = False
    if not classification:
        vals = p["leaf_values"].astype(np.float32)  # [T, L]
        coefs = [_linear_coef(m, wt) for _, wt, m in parts]
        # max / median need the per-tree plane
        fused_linear = all(c is not None for c in coefs)
        coef = (
            np.concatenate(coefs) if fused_linear
            else np.ones((T,), np.float32)
        )
        vhi, vlo = _split_bf16(vals * coef[:, None])
    else:
        labels = packed.labels
        C = len(labels)
        leaf_label = np.round(p["leaf_label"]).astype(np.int64)  # [T, L]
        if method == "single":
            # per-leaf class distributions + the leaf's own label
            probs_tbl = p["leaf_probs"].astype(np.float32)  # [T, L, C]
        else:
            # each tree votes its leaf's label one-hot, weighted
            w_eff = (
                w if method == "weightedMajorityVote"
                else np.ones((T,), np.float32)
            )
            probs_tbl = np.zeros((T, L, C), np.float32)
            tt, ll = np.meshgrid(
                np.arange(T), np.arange(L), indexing="ij"
            )
            probs_tbl[tt, ll, leaf_label] = 1.0
            probs_tbl *= w_eff[:, None, None]
            probs_tbl /= w_eff.sum()
        phi, plo = _split_bf16(probs_tbl)
        lab_f = leaf_label.astype(np.float32)

    targets = doc.targets
    out_labels = chain_labels or (packed.labels if classification else ())
    event_class = 0 if link is not None else None
    repl, has_repl = extract_missing_replacements(doc.model.mining_schema, ctx)

    wire = QuantizedWire(
        fields=fields,
        cuts=tuple(cuts),
        dtype=dtype,
        sentinel=sentinel,
        repl=repl,
        has_repl=has_repl,
    )

    params: Dict[str, np.ndarray] = {
        "feat": feat.astype(np.int32),
        "qthr": qthr,
        "dleft": dleft,
        "P_i8": P.astype(np.int8),
        "count_i8": p["count"].astype(np.int8),
    }
    if not classification:
        params["vhi"] = vhi
        params["vlo"] = vlo
        if not fused_linear:
            params["vals_f32"] = vals
    else:
        params["phi"] = phi
        params["plo"] = plo
        params["lab"] = lab_f

    # stable identity for the on-disk autotune cache: the wire tables +
    # packed shapes pin the compiled program (weights don't change the
    # layout choice, but folding the threshold tables in makes the key
    # collision-proof across same-shape models)
    hasher = hashlib.sha256()
    hasher.update(
        f"{T}:{S}:{L}:{F}:{batch_size}:{np.dtype(dtype).name}:"
        f"{int(classification)}:{method}".encode()
    )
    if link is not None:
        hasher.update(f"logit:{link}".encode())
    if softmax is not None:
        hasher.update(b"softmax:" + softmax.tobytes())
    for c in cuts:
        hasher.update(c.tobytes())
    hasher.update(qthr.tobytes())
    hasher.update(np.asarray(dleft, np.uint8).tobytes())
    model_hash = hasher.hexdigest()[:16]

    # packed-shape summary: the learned cost model's model-shape
    # features (compile/costmodel.py variant_features)
    scorer_meta = {
        "trees": float(T), "splits": float(S), "leaves": float(L),
        "fields": float(F), "batch": float(batch_size or 0),
        "dtype_rank": float(np.dtype(dtype).itemsize),
        "classification": 1.0 if classification else 0.0,
    }

    # fused featurize+score pre-stage (tentpole of ISSUE 2): the same
    # threshold-rank bucketize as wire.encode, but as XLA ops traced
    # into the scoring jit — raw f32 batches go straight to the device
    # and one dispatch covers encode+pad+score. The host path stays the
    # default and the byte-parity oracle.
    enc_tables = wire.device_tables()
    encode_stage = (
        _make_encode_stage(sentinel, dtype, bool(has_repl.any()))
        if enc_tables is not None
        else None
    )

    on_cpu = common.backend_is_cpu()
    sent = dtype(sentinel)
    # the dtype the rank codes are built in (``_rank_codes``): bfloat16
    # holds every uint8 code; the CPU has no bfloat16 dot kernel
    code_dt = (
        jnp.bfloat16 if dtype is np.uint8 and not on_cpu else jnp.float32
    )

    # Order-stable reductions for pack-eligible (small) models. XLA's
    # gemv lowering for the final tree-sum contraction is context
    # dependent: compiled inside a multi-model packed program
    # (compile/packs.py) the same einsum can round differently by 1 ULP
    # on some rows, breaking the pack's byte-parity contract. The leaf
    # axis is a one-hot SELECTION (exact in any order), so contracting
    # to a per-tree plane and finishing with a plain axis reduce — whose
    # sequential lowering is module-independent — pins the float order.
    # Gated by size so the flagship big-model solo path keeps the fused
    # single-contraction form.
    from flink_jpmml_tpu.compile import packs as _packs

    stable_small = (
        sum(int(v.nbytes) for v in params.values())
        <= _packs.member_bytes_cap()
    )

    # the twin's stages are named scopes inside the state program's
    # ``fjt.forest`` (metadata on the traced ops; the program is the same
    # without): the rank codes and their compare (``ranks``), the pick of
    # each tree's leaf (``paths``: the path contraction and leaf match,
    # or the select tree of the complete form), the leaf values and class
    # sums (``leaves``) and a softmax chain's link. On the chip the path
    # form's first two are one fusion, which the trace books under
    # ``fjt.forest.paths``
    def _hit(pp, Xq):
        """[B,T,L] leaf one-hot (f32 on CPU — no int8/bf16 dot kernels
        there — bf16 on TPU)."""
        with jax.named_scope("fjt.forest.ranks"):
            xv = _rank_codes(Xq, pp["feat"], code_dt)  # [B, T, S]
            miss = xv == sent
            go = jnp.where(miss, pp["dleft"], xv <= pp["qthr"])
            if on_cpu:
                sign = jnp.where(go, 1.0, -1.0).astype(jnp.float32)
            else:
                sign = jnp.where(go, jnp.int8(1), jnp.int8(-1))
        with jax.named_scope("fjt.forest.paths"):
            if on_cpu:
                acc = jnp.einsum(
                    "bts,tsl->btl", sign, pp["P_i8"].astype(jnp.float32),
                    preferred_element_type=jnp.float32,
                )
                return (
                    acc == pp["count_i8"].astype(jnp.float32)[None]
                ).astype(jnp.float32)
            acc = jnp.einsum(
                "bts,tsl->btl", sign, pp["P_i8"],
                preferred_element_type=jnp.int32,
            ).astype(jnp.int8)
            return (acc == pp["count_i8"][None]).astype(jnp.bfloat16)

    def _walk(pp, Xq):
        """The complete form: ``[B, *P, T]``, every tree's leaf payload
        (``heap_vals``: ``[2**D, *P, T]``, float32 on every backend), by
        the rank codes of each heap split, a ``[B, T]`` plane a split
        with the trees minor (one contraction a split: one of all the
        splits at once, ``[B, 2**D - 1, T]``, was copied out again plane
        by plane on a described v5e), their compare, and the select
        tree."""
        with jax.named_scope("fjt.forest.ranks"):
            go = []
            for n in range(pp["heap_feat"].shape[0]):
                if code_dt == jnp.bfloat16:
                    xv = _rank_codes_i8(Xq, pp["heap_feat"][n])  # [B, T]
                else:
                    xv = _rank_codes(
                        Xq, pp["heap_feat"][n:n + 1], code_dt)[:, 0]
                go.append(jnp.where(xv == sent, pp["heap_dleft"][n],
                                    xv <= pp["heap_qthr"][n]))
        with jax.named_scope("fjt.forest.paths"):
            return _select_tree(go, pp["heap_vals"])

    def _link(value):
        """A chain's logit as the f32 path computes it (regression.py):
        ``y = b + m·a``, then ``1 / (1 + exp(-y))``; the value itself
        where there is no chain."""
        if link is None:
            return value
        a, b = link
        y = jnp.float32(b) + value * jnp.float32(a)
        return 1.0 / (1.0 + jnp.exp(-y))

    def _pair_einsum(spec, hit, hi, lo):
        """hi+lo bf16 split contraction, f32-accurate."""
        if on_cpu:
            h = hi.astype(jnp.float32) + lo.astype(jnp.float32)
            return jnp.einsum(spec, hit, h)
        return jnp.einsum(
            spec, hit, hi, preferred_element_type=jnp.float32
        ) + jnp.einsum(spec, hit, lo, preferred_element_type=jnp.float32)

    def _softmax_link(m):
        """A softmax chain's link as the f32 path computes it
        (regression.py): ``y_k = b_k + m_k·a_k``, the softmax with the
        largest ``y`` subtracted, the label its argmax → the triple
        (the label's probability, ``[B, C]`` probabilities, label)."""
        with jax.named_scope("fjt.forest.link"):
            y = jnp.asarray(softmax[:, 1]) + m * jnp.asarray(softmax[:, 0])
            e = jnp.exp(y - jnp.max(y, axis=1, keepdims=True))
            probs = e / jnp.sum(e, axis=1, keepdims=True)
            lab = jnp.argmax(probs, axis=1).astype(jnp.int32)
            value = jnp.take_along_axis(probs, lab[:, None], axis=1)[:, 0]
            return value, probs, lab

    def _regression_value(value):
        value = _link(apply_targets_value(value, targets))
        return value.astype(jnp.float32)

    def _vote_triple(probs, lab):
        value = jnp.take_along_axis(
            probs, lab[:, None], axis=1)[:, 0]
        value = apply_targets_value(value, targets)
        return (value.astype(jnp.float32),
                probs.astype(jnp.float32), lab)

    # the complete form sums each tree's own value with a plain axis
    # reduce of the per-tree plane: the float order ``stable_small``
    # pins for the path form, at every size
    if softmax is not None:
        C = softmax.shape[0]

        def score_rows(pp, Xq):
            """Class k's sum over its own group of trees (the k-th
            ``T / C`` of the forest): no leaf is read for another
            class, then the link."""
            if heap_depth:
                per = _walk(pp, Xq)
                with jax.named_scope("fjt.forest.leaves"):
                    m = per.reshape(per.shape[0], C, -1).sum(axis=2)
                return _softmax_link(m)
            hit = _hit(pp, Xq)
            with jax.named_scope("fjt.forest.leaves"):
                B = hit.shape[0]
                if stable_small:
                    per = _pair_einsum("btl,tl->bt", hit, pp["vhi"], pp["vlo"])
                    m = per.reshape(B, C, -1).sum(axis=2)
                else:
                    m = _pair_einsum(
                        "bktl,ktl->bk", hit.reshape(B, C, -1, L),
                        pp["vhi"].reshape(C, -1, L),
                        pp["vlo"].reshape(C, -1, L),
                    )
            return _softmax_link(m)
    elif not classification:
        def _aggregate(per_tree):
            if method == "max":
                return jnp.max(per_tree, axis=1)
            return jnp.median(per_tree, axis=1)

        def score_rows(pp, Xq):
            if heap_depth:
                per = _walk(pp, Xq)
                with jax.named_scope("fjt.forest.leaves"):
                    return _regression_value(
                        per.sum(axis=1) if fused_linear else _aggregate(per))
            hit = _hit(pp, Xq)
            with jax.named_scope("fjt.forest.leaves"):
                if fused_linear:
                    if stable_small:
                        per = _pair_einsum(
                            "btl,tl->bt", hit, pp["vhi"], pp["vlo"]
                        )
                        value = per.sum(axis=1)
                    else:
                        value = _pair_einsum(
                            "btl,tl->b", hit, pp["vhi"], pp["vlo"]
                        )
                else:
                    per_tree = jnp.einsum(
                        "btl,tl->bt", hit.astype(jnp.float32),
                        pp["vals_f32"], precision=jax.lax.Precision.HIGHEST,
                    )
                    value = _aggregate(per_tree)
                return _regression_value(value)
    else:
        def score_rows(pp, Xq):
            if heap_depth:
                # each leaf's class shares, then (a single tree) its label
                per = _walk(pp, Xq)  # [B, C (+1), T]
                with jax.named_scope("fjt.forest.leaves"):
                    probs = per[:, :C].sum(axis=2)
                    lab = (
                        jnp.round(per[:, C, 0]).astype(jnp.int32)
                        if method == "single"
                        else jnp.argmax(probs, axis=1).astype(jnp.int32)
                    )
                    return _vote_triple(probs, lab)
            hit = _hit(pp, Xq)
            with jax.named_scope("fjt.forest.leaves"):
                if stable_small:
                    per = _pair_einsum(
                        "btl,tlc->btc", hit, pp["phi"], pp["plo"]
                    )
                    probs = per.sum(axis=1)
                else:
                    probs = _pair_einsum(
                        "btl,tlc->bc", hit, pp["phi"], pp["plo"]
                    )
                if method == "single":
                    # the label is the leaf's score attribute, not argmax
                    lab = jnp.round(
                        jnp.einsum(
                            "btl,tl->b", hit.astype(jnp.float32), pp["lab"],
                            precision=jax.lax.Precision.HIGHEST,
                        )
                    ).astype(jnp.int32)
                else:
                    lab = jnp.argmax(probs, axis=1).astype(jnp.int32)
                return _vote_triple(probs, lab)

    def qfn(pp, Xq):
        """The XLA twin over a batch: ``score_rows`` at once, or a scan
        of it over steps of ``twin_rows`` records where the batch's
        temporaries would pass ``twin_budget`` (a pad to whole steps is
        cut off again). Every record is scored from its own row alone:
        the scan's scores are the unscanned program's, bit for bit
        (tests/test_qtrees_chain.py holds it)."""
        n = Xq.shape[0]
        rows = twin_rows(n, T, S, L, twin_budget, heap_depth)
        if rows >= n:
            return score_rows(pp, Xq)
        steps = -(-n // rows)
        Xs = jnp.pad(Xq, ((0, steps * rows - n), (0, 0)))
        outs = jax.lax.map(
            lambda x: score_rows(pp, x),
            Xs.reshape(steps, rows, Xq.shape[1]),
        )
        return jax.tree_util.tree_map(
            lambda o: o.reshape((steps * rows,) + o.shape[2:])[:n], outs
        )

    # Pallas VMEM-resident kernel: uint8 wire + fixed batch, with either a
    # linear regression aggregate (the GBM hot path) or a classification
    # vote forest (majorityVote — per-leaf class rows contract in-kernel)
    want_pallas = backend in ("auto", "pallas")
    pallas_env = (
        dtype is np.uint8
        and batch_size is not None
        and (not on_cpu or pallas_interpret)
    )
    # round-3 on-device classification parity failure, root-caused: the
    # kernel contracted a single reconstructed f32 vote table with a
    # default-precision dot, which the MXU truncates to bf16 — silently
    # dropping the lo residuals (interpret mode on CPU does exact f32
    # math, so only hardware disagreed). The kernel now contracts the
    # SAME bf16 hi/lo split pair as the XLA path (_pair_einsum), so the
    # vote kernel is back in auto selection.
    pallas_cls = classification and method in (
        "majorityVote", "weightedMajorityVote"
    )
    if want_pallas and pallas_env and (
        (not classification and fused_linear) or pallas_cls
    ):
        from flink_jpmml_tpu.compile import qtrees_pallas

        if softmax is not None:
            # the vote kernel's [T, L, C] table: each tree's leaves in
            # its class's column, the twin's bf16 pair, zeros elsewhere
            tree_class = np.repeat(np.arange(C), T // C)
            vals_tbl = np.zeros((T, L, C), vhi.dtype)
            vals_lo = np.zeros((T, L, C), vlo.dtype)
            vals_tbl[np.arange(T), :, tree_class] = vhi
            vals_lo[np.arange(T), :, tree_class] = vlo
        elif classification:
            # the bf16 hi/lo split pair — identical operands to the XLA
            # path, so labels match exactly and shares to f32 rounding
            vals_tbl, vals_lo = phi, plo
        else:
            # scalar leaf sums stay a single f32 table: the kernel
            # combines them with an elementwise VPU multiply (exact in
            # f32), not an MXU dot
            vals_tbl = vhi.astype(np.float32) + vlo.astype(np.float32)
            vals_lo = None

        def _build_pallas(layout: str = "ref"):
            """Pack + build the kernel under a catalogue layout → a
            built-variant dict, or None for a layout id this backend
            does not know or shapes outside the kernel's contract
            (qtrees_pallas.build_pallas_fn). The ``ref`` layout builds
            the scorer; the kernel search (compile/autotune.py)
            re-invokes this per candidate and adopts the winner
            (:meth:`QuantizedScorer.adopt_variant`)."""
            from flink_jpmml_tpu.compile import layouts as layouts_mod

            fl = layouts_mod.flags(layout)
            if fl is None or not fl <= {"bfs", "mega"}:
                return None  # unknown / XLA-only layout id
            feat_in = params["feat"].astype(np.int64)
            qthr_in, dleft_in, P_in = qthr, np.asarray(dleft), params["P_i8"]
            if "bfs" in fl:
                perm = layouts_mod.bfs_split_order(P_in)
                soa = layouts_mod.apply_split_order(
                    perm, feat_in, qthr_in, dleft_in, P_in
                )
                feat_in, qthr_in = soa["feat"], soa["qthr"]
                dleft_in, P_in = soa["dleft"], soa["P"]
            groups = qtrees_pallas.pack_groups(
                feat=feat_in,
                qthr=qthr_in,
                dleft=dleft_in,
                P=P_in,
                count=params["count_i8"],
                vals=vals_tbl,
                n_fields=F,
                vals_lo=vals_lo,
            )
            raw = qtrees_pallas.build_pallas_fn(
                groups, batch_size, F, sentinel,
                interpret=pallas_interpret,
                fuse_groups="mega" in fl,
            )
            if raw is None:
                return None
            if softmax is not None:
                def pqfn(gp, Xq):
                    return _softmax_link(raw(gp, Xq))  # [B, C] sums
            elif classification:
                def pqfn(gp, Xq):
                    probs = raw(gp, Xq)  # [B, C] vote shares
                    lab = jnp.argmax(probs, axis=1).astype(jnp.int32)
                    value = jnp.take_along_axis(
                        probs, lab[:, None], axis=1
                    )[:, 0]
                    value = apply_targets_value(value, targets)
                    return (
                        value.astype(jnp.float32),
                        probs.astype(jnp.float32),
                        lab,
                    )
            else:
                def pqfn(gp, Xq):
                    return _link(
                        apply_targets_value(raw(gp, Xq), targets)
                    ).astype(jnp.float32)

            fused_inner = None
            if encode_stage is not None:
                # the enc tables ride in the same params dict (added
                # AFTER build_pallas_fn's VMEM budget check: they are
                # XLA-stage operands, not kernel residents)
                groups.update(enc_tables)

                def fused_inner(gp, X):
                    return pqfn(gp, encode_stage(gp, X))

            jit_fn = jax.jit(
                pqfn,
                donate_argnums=(1,) if config.donate_batches else (),
            )
            return {
                "params": jax.device_put(groups),
                "jit_fn": jit_fn,
                "fused_inner": fused_inner,
                "wire_pack": None,  # pallas is uint8-wire only
            }

        # None = outside the kernel's contract: the XLA rank-wire path
        # below serves the model (``backend == "xla"``). A build Mosaic
        # refuses raises at first dispatch, uncaught.
        built = _build_pallas()
        if built is not None:
            scorer = QuantizedScorer(
                wire=wire,
                params=built["params"],
                field_space=prepare.FieldSpace(fields=fields, codecs=ctx.codecs),
                batch_size=batch_size,
                n_trees=T,
                _jit_fn=built["jit_fn"],
                backend="pallas",
                labels=out_labels,
                event_class=event_class,
                model_hash=model_hash,
                _fused_inner=built["fused_inner"],
                _encode_stage=encode_stage,
                _pallas_rebuild=_build_pallas,
                _meta=scorer_meta,
            )
            _consult_autotune(scorer)
            return scorer
    if backend == "pallas":
        return None  # forced pallas but not eligible

    if heap_depth:
        # the complete form's tables (``complete_heap``), each with the
        # trees minor: the path matrix and its counts stay on the host
        node, owner = complete_heap(P, heap_depth)
        N = (1 << heap_depth) - 1
        tt, ss = np.nonzero(node >= 0)
        nn = node[tt, ss]
        heap = {
            "heap_feat": np.zeros((N, T), np.int32),
            "heap_qthr": np.zeros((N, T), dtype),
            "heap_dleft": np.zeros((N, T), bool),
        }
        for key, src in (("heap_feat", feat), ("heap_qthr", qthr),
                         ("heap_dleft", dleft)):
            heap[key][nn, tt] = src[tt, ss]
        if classification:
            # [T, L, C] shares, and a single tree's label beside them
            tbl = phi.astype(np.float32) + plo.astype(np.float32)
            if method == "single":
                tbl = np.concatenate([tbl, lab_f[:, :, None]], axis=2)
        elif fused_linear:
            tbl = vhi.astype(np.float32) + vlo.astype(np.float32)
        else:
            tbl = vals
        got = np.take_along_axis(
            tbl, owner.reshape(owner.shape + (1,) * (tbl.ndim - 2)), axis=1)
        heap["heap_vals"] = np.ascontiguousarray(np.moveaxis(got, 0, -1))
        params = heap

    jit_fn = jax.jit(qfn, donate_argnums=(1,) if config.donate_batches else ())
    codecs = ctx.codecs

    fused_inner = None
    if encode_stage is not None:
        params.update(enc_tables)

        def fused_inner(pp, X):
            return qfn(pp, encode_stage(pp, X))

    def _build_xla_variant(layout: str = "ref"):
        """XLA twin of the pallas rebuild hook: re-derive the jitted
        program under a catalogue layout (BFS split order and/or the
        packed rank wire) → built-variant dict, or None when the
        layout is unknown here / has nothing to pack. ``qfn`` itself
        is layout-agnostic (it reads the param tables), so a variant
        is new params + a new jit entry, never new math."""
        from flink_jpmml_tpu.compile import layouts as layouts_mod

        fl = layouts_mod.flags(layout)
        if fl is None or not fl or not fl <= {"bfs", "wirepack"}:
            return None
        if heap_depth and "bfs" in fl:
            return None  # the heap is already breadth-first
        p2 = dict(params)
        if "bfs" in fl:
            perm = layouts_mod.bfs_split_order(params["P_i8"])
            soa = layouts_mod.apply_split_order(
                perm, params["feat"], params["qthr"],
                np.asarray(params["dleft"]), params["P_i8"],
            )
            p2["feat"] = soa["feat"].astype(np.int32)
            p2["qthr"], p2["dleft"] = soa["qthr"], soa["dleft"]
            p2["P_i8"] = soa["P"].astype(np.int8)
        inner = qfn
        wp = None
        if "wirepack" in fl:
            wp = layouts_mod.plan_wire_pack(wire)
            if wp is None:
                return None
            unpack = wp.unpack_stage()

            def inner(pp, Xpk, _unpack=unpack):
                return qfn(pp, _unpack(Xpk))

        v_jit = jax.jit(
            inner, donate_argnums=(1,) if config.donate_batches else ()
        )
        v_fused = None
        if encode_stage is not None:
            # fused encode ships raw f32 — it bypasses any wire pack,
            # so the fused twin always feeds qfn unpacked rank codes
            def v_fused(pp, X):
                return qfn(pp, encode_stage(pp, X))

        return {
            "params": jax.device_put(p2),
            "jit_fn": v_jit,
            "fused_inner": v_fused,
            "wire_pack": wp,
        }

    scorer = QuantizedScorer(
        wire=wire,
        params=jax.device_put(params),
        field_space=prepare.FieldSpace(fields=fields, codecs=codecs),
        batch_size=batch_size,
        n_trees=T,
        _jit_fn=jit_fn,
        backend="xla",
        labels=out_labels,
        event_class=event_class,
        twin_budget=twin_budget,
        twin_depth=heap_depth,
        model_hash=model_hash,
        _fused_inner=fused_inner,
        _encode_stage=encode_stage,
        _xla_rebuild=_build_xla_variant,
        _meta=scorer_meta,
        # cross-model packing hook (compile/packs.py): qfn is layout-
        # agnostic (it reads whatever param tables are live), so a
        # pack stays byte-identical across bfs re-adoption; wirepack
        # members are screened out at pack time (pack_eligible), and a
        # chain, whose output is not its trees' sum, is not packed
        _pack_info=None if chain is not None else {
            "qfn": qfn,
            "fields": F,
            "dtype": dtype,
            "sentinel": sentinel,
            "classification": classification,
        },
    )
    _consult_autotune(scorer)
    return scorer


def _consult_autotune(scorer: QuantizedScorer) -> None:
    """Apply a previously-measured config from the on-disk autotune
    cache (compile/autotune.py) to a freshly-built scorer.

    A cache problem must not break model compilation — the built
    defaults always work: ``lookup`` reads a corrupt, unreadable or
    stale-schema file as no entry, and ``apply`` degrades a config the
    current build can't honour to the defaults. What else raises here
    is a defect and propagates."""
    from flink_jpmml_tpu.compile import autotune

    cfg = autotune.lookup(scorer.model_hash, autotune.backend_key(scorer))
    if cfg is not None:
        autotune.apply(scorer, cfg)
