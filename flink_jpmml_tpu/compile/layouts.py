"""Kernel layout catalogue for the quantized tree fast path.

BENCH_r05 pinned the ceiling at kernel *structure*: 5.8% MFU with
`device_membw_util` ≈ 0 means the chip is idle between tiny gathers,
not starved by the stream. This module is the menu of alternative
memory layouts the learned kernel search (compile/costmodel.py +
compile/autotune.py) ranks and verifies — every variant is
**byte-identical** to the reference packing by construction, so the
search can adopt whichever wins without a parity risk:

- ``bfs`` — breadth-first SoA split ordering. The packed split tables
  (``feat``/``qthr``/``dleft``/``P``) keep their SoA form but the S
  axis is permuted per tree into descending-reach order (the root
  split — touched by every record — first, then depth-1 splits, …).
  The path-matrix contraction sums over S, so any per-tree permutation
  applied consistently to all four tables is bit-exact; what changes
  is locality: the hot top-of-tree rows become a contiguous prefix.
- ``wirepack`` — per-feature uint8/uint16 threshold-rank packing of
  the wire. The rank wire already bounds cut cardinality per feature;
  a single >254-cut feature currently forces the WHOLE record to
  uint16, doubling bytes/record for every column. :class:`WirePack`
  ships each feature in the fewest bytes its own cut table needs
  (uint8 columns inline, uint16 columns as little-endian byte pairs)
  and a tiny XLA unpack stage traced into the scoring jit restores
  exact ranks — fewer bytes/record, higher arithmetic intensity.
- ``mega`` — the Pallas multi-tree megakernel
  (qtrees_pallas.build_pallas_fn(fuse_groups=True)): all
  ``pack_groups`` tree groups fuse into ONE grid step whose in-kernel
  ``fori_loop`` accumulates group partials in registers, instead of a
  grid axis that revisits the output block once per group.

Combined ids (``bfs_wirepack``, ``mega_bfs``) compose the flags. The
catalogue also exports :func:`bfs_order`, the breadth-first node
renumbering gtrees.py applies to its general-scan node tables (the hop
loop's early gathers then touch a contiguous low-index prefix).

``SPACE_TAG`` versions the whole search space: the autotune cache
stamps it into every stored config, so a winner cached before a layout
(or a future axis) existed can never pin a new binary to an obsolete
kernel config — a stale tag reads as no entry (silent re-search, the
existing corrupt-cache contract).
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

# bump whenever the candidate space changes shape (new layout, new
# search axis, changed packing semantics): stale cached winners must
# re-search, not pin the old space's best onto the new binary
SPACE_TAG = "space-v3:layouts-default-tiles"

_XLA_LAYOUTS = ("ref", "bfs", "wirepack", "bfs_wirepack")
_PALLAS_LAYOUTS = ("ref", "bfs", "mega", "mega_bfs")

_FLAGS = {
    "ref": frozenset(),
    "bfs": frozenset(("bfs",)),
    "wirepack": frozenset(("wirepack",)),
    "bfs_wirepack": frozenset(("bfs", "wirepack")),
    "mega": frozenset(("mega",)),
    "mega_bfs": frozenset(("bfs", "mega")),
}


def flags(layout: Optional[str]) -> Optional[frozenset]:
    """Layout id → its feature-flag set; None for an unknown id (a
    cache entry from a different build — callers treat it as
    ineligible, never raise)."""
    return _FLAGS.get(layout or "ref")


def pallas_layouts() -> Tuple[str, ...]:
    return _PALLAS_LAYOUTS


def xla_layouts(wire) -> Tuple[str, ...]:
    """XLA-backend layout ids eligible for this wire (wirepack variants
    only when the wire actually has mixed-width columns to pack)."""
    if plan_wire_pack(wire) is None:
        return ("ref", "bfs")
    return _XLA_LAYOUTS


# ---------------------------------------------------------------------------
# Breadth-first SoA split ordering
# ---------------------------------------------------------------------------


def bfs_split_order(P: np.ndarray) -> np.ndarray:
    """→ per-tree split permutation ``perm[T, S]`` in breadth-first
    order, derived from the path matrix alone.

    A split's *reach* — how many leaf paths run through it, i.e. its
    count of non-zero rows in ``P[t, s, :]`` — halves per level in a
    binary tree, so a stable descending-reach sort IS level order:
    root first, then depth-1, … with padded all-zero slots (reach 0)
    sinking to the tail. Stability keeps sibling order deterministic."""
    reach = (np.asarray(P) != 0).sum(axis=2)  # [T, S]
    # stable sort on negated reach: ties keep original slot order
    return np.argsort(-reach, axis=1, kind="stable").astype(np.int64)


def apply_split_order(
    perm: np.ndarray,
    feat: np.ndarray,
    qthr: np.ndarray,
    dleft: np.ndarray,
    P: np.ndarray,
) -> Dict[str, np.ndarray]:
    """Permute the four per-split SoA tables consistently along S.

    The split-indicator contraction reduces over S, so the scores are
    bit-identical for ANY consistent permutation (integer accumulators
    on the device path; small-integer f32 sums — exact — on CPU)."""
    return {
        "feat": np.ascontiguousarray(np.take_along_axis(feat, perm, axis=1)),
        "qthr": np.ascontiguousarray(np.take_along_axis(qthr, perm, axis=1)),
        "dleft": np.ascontiguousarray(
            np.take_along_axis(dleft, perm, axis=1)
        ),
        "P": np.ascontiguousarray(
            np.take_along_axis(P, perm[:, :, None], axis=1)
        ),
    }


def bfs_order(children: Sequence[Sequence[int]]) -> List[int]:
    """Breadth-first visit order over a node table (``children[i]`` =
    child indices of node ``i``; node 0 is the root). Every node is
    reachable from the root by construction in the callers; the root
    keeps index 0 so evaluators that start at 0 are untouched."""
    order: List[int] = []
    seen = [False] * len(children)
    queue = [0]
    seen[0] = True
    while queue:
        nxt: List[int] = []
        for i in queue:
            order.append(i)
            for c in children[i]:
                if not seen[c]:
                    seen[c] = True
                    nxt.append(c)
        queue = nxt
    # defensive: unreachable rows (impossible from the flatteners, but
    # a renumbering must be a permutation regardless) go to the tail
    order.extend(i for i, s in enumerate(seen) if not s)
    return order


# ---------------------------------------------------------------------------
# uint8/uint16 threshold-rank wire packing
# ---------------------------------------------------------------------------


class WirePack:
    """Per-feature rank packing plan for a uint16 wire.

    Columns whose cut table fits uint8 ship one byte (with 255 as the
    packed missing marker, widened back to the uint16 sentinel on
    device); the rest ship two little-endian bytes. ``pack`` is the
    host side; ``unpack_stage`` returns the XLA stage traced into the
    scoring jit; ``unpack_host`` is the numpy oracle the byte-parity
    tests pin the stage against."""

    def __init__(self, widths: np.ndarray, sentinel: int):
        self.widths = np.asarray(widths, np.int64)  # [F] ∈ {1, 2}
        self.sentinel = int(sentinel)
        offs = np.zeros((len(self.widths) + 1,), np.int64)
        np.cumsum(self.widths, out=offs[1:])
        self.offsets = offs[:-1]
        self.width = int(offs[-1])  # packed bytes per record
        # gather plans for the unpack stage: lo byte per feature, hi
        # byte (multiplied by 0 for uint8 columns so the gather stays
        # in bounds without a second codepath)
        self._lo_idx = self.offsets.astype(np.int32)
        hi = np.where(self.widths == 2, self.offsets + 1, self.offsets)
        self._hi_idx = hi.astype(np.int32)
        self._hi_mult = np.where(self.widths == 2, 256, 0).astype(np.int32)
        self._u8_col = (self.widths == 1)

    @property
    def bytes_per_record(self) -> int:
        return self.width

    def pack(self, codes: np.ndarray) -> np.ndarray:
        """uint16 rank codes [B, F] → packed uint8 [B, W]."""
        codes = np.asarray(codes)
        B = codes.shape[0]
        out = np.empty((B, self.width), np.uint8)
        for j, (w, off) in enumerate(zip(self.widths, self.offsets)):
            v = codes[:, j].astype(np.uint32)
            if w == 1:
                # ranks ≤ 254 by plan; only the sentinel exceeds uint8
                out[:, off] = np.where(
                    v == self.sentinel, 255, v
                ).astype(np.uint8)
            else:
                out[:, off] = (v & 0xFF).astype(np.uint8)
                out[:, off + 1] = (v >> 8).astype(np.uint8)
        return out

    def unpack_host(self, packed: np.ndarray) -> np.ndarray:
        """Numpy oracle of :meth:`unpack_stage` → int32 ranks [B, F]."""
        packed = np.asarray(packed, np.uint8)
        lo = packed[:, self._lo_idx].astype(np.int32)
        hi = packed[:, self._hi_idx].astype(np.int32) * self._hi_mult
        r = lo + hi
        return np.where(self._u8_col[None, :] & (r == 255), self.sentinel, r)

    def unpack_stage(self):
        """→ jitted-traceable fn(packed uint8 [B, W]) → int32 ranks
        [B, F], bit-exact with :meth:`unpack_host`. Static index plans
        close over the stage so no device tables are needed."""
        import jax.numpy as jnp

        lo_idx = self._lo_idx
        hi_idx = self._hi_idx
        hi_mult = self._hi_mult
        u8_col = self._u8_col
        sentinel = self.sentinel

        def unpack(packed):
            lo = packed[:, lo_idx].astype(jnp.int32)
            hi = packed[:, hi_idx].astype(jnp.int32) * hi_mult
            r = lo + hi
            return jnp.where(u8_col[None, :] & (r == 255), sentinel, r)

        return unpack


def plan_wire_pack(wire) -> Optional[WirePack]:
    """→ the packing plan for a :class:`~flink_jpmml_tpu.compile
    .qtrees.QuantizedWire`, or None when packing cannot help: a uint8
    wire is already minimal, and a uint16 wire where every feature
    needs two bytes has nothing to shrink."""
    if np.dtype(wire.dtype).itemsize == 1:
        return None
    widths = np.asarray(
        [1 if len(c) <= 254 else 2 for c in wire.cuts], np.int64
    )
    if not (widths == 1).any():
        return None
    return WirePack(widths, wire.sentinel)


# ---------------------------------------------------------------------------
# Candidate-space description (shared by autotune + costmodel)
# ---------------------------------------------------------------------------


# ---------------------------------------------------------------------------
# Cross-model packing partitions (the multi-tenant zoo layout axis)
# ---------------------------------------------------------------------------

# versions the PACK candidate space independently of the per-model
# variant space: adding a partition family must invalidate adopted pack
# plans without forcing every per-model winner to re-search (SPACE_TAG
# stays put)
PACK_SPACE_TAG = "packspace-v1"

# candidate pack widths for the bucketed-greedy family; each is capped
# by packs.pack_max() at enumeration time
_PACK_WIDTHS = (4, 8, 16)


def pack_partitions(
    metas: Dict[str, Dict[str, float]]
) -> List[Tuple[Tuple[str, ...], ...]]:
    """Enumerate candidate packing partitions of a model set.

    ``metas`` maps model_hash → packed-shape summary
    (``QuantizedScorer._meta``). A partition is a tuple of groups, each
    group a tuple of model hashes sharing one packed buffer (singleton
    group = solo dispatch). The family is deliberately small — this is
    a ranked search, not exhaustive set partitioning (Bell numbers):

    - **solo** — every model alone (the packing-off baseline; always
      candidate 0 so an empty cost model still has a safe winner),
    - **bucketed-greedy(k)** for k in 4/8/16 — models sorted by
      (wire dtype rank, classification, field count, hash) so lookalike
      shapes land in the same bucket (minimal padded waste), chunked
      into groups of ≤ k,
    - **single-bucket** — one pack per ``packs.pack_max()`` chunk over
      the whole sorted set (maximal launch amortization, maximal
      padding).

    Deterministic: same meta set → same candidate list, so the adopted
    plan is stable under re-search."""
    from flink_jpmml_tpu.compile import packs

    hashes = sorted(metas)
    if not hashes:
        return []
    solo = tuple((h,) for h in hashes)
    if len(hashes) == 1:
        return [solo]

    def shape_key(h):
        # param shape (trees × leaves) ranks BEFORE the wire shape: the
        # packed kernel pads every member to the group max on both axes,
        # and the T·L contraction — not the input buffer — dominates the
        # padded compute, so compute-identical models must neighbour
        m = metas[h] or {}
        return (
            float(m.get("dtype_rank", 1.0)),
            float(m.get("classification", 0.0)),
            float(m.get("trees", 0.0)),
            float(m.get("leaves", 0.0)),
            float(m.get("splits", 0.0)),
            float(m.get("fields", 0.0)),
            h,
        )

    ordered = sorted(hashes, key=shape_key)
    cap = packs.pack_max()
    cands: List[Tuple[Tuple[str, ...], ...]] = [solo]
    seen = {solo}
    for k in tuple(w for w in _PACK_WIDTHS if w <= cap) + (cap,):
        part = tuple(
            tuple(ordered[i: i + k]) for i in range(0, len(ordered), k)
        )
        if part not in seen:
            seen.add(part)
            cands.append(part)
    return cands


def pack_pad_waste(
    metas: Dict[str, Dict[str, float]],
    partition: Sequence[Sequence[str]],
) -> float:
    """Fraction of the partition's padded work that is padding (one of
    the two ranking axes; the batch dimension divides out so this is
    batch-free). Counts BOTH padded axes: the input buffer
    (fields × dtype) and the param contraction (trees × leaves) — the
    latter is where an over-mixed pack actually burns device time."""
    used = 0.0
    total = 0.0
    for group in partition:
        ms = [metas.get(h) or {} for h in group]
        rank = max(float(m.get("dtype_rank", 1.0)) for m in ms)
        f_max = max(float(m.get("fields", 0.0)) for m in ms)
        t_max = max(float(m.get("trees", 0.0)) for m in ms)
        l_max = max(float(m.get("leaves", 0.0)) for m in ms)
        total += len(ms) * (f_max * rank + t_max * l_max)
        used += sum(
            float(m.get("fields", 0.0))
            * float(m.get("dtype_rank", 1.0))
            + float(m.get("trees", 0.0)) * float(m.get("leaves", 0.0))
            for m in ms
        )
    return 1.0 - used / total if total > 0 else 0.0


def variant_id(backend: str, layout: str) -> str:
    """Canonical ledger/rates key for one search candidate."""
    kernel = "pallas" if backend == "pallas" else "xla"
    return f"{kernel}_{layout or 'ref'}"
