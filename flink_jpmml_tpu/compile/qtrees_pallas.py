"""Pallas TPU kernel for the quantized tree-ensemble fast path.

The XLA lowering of qtrees.py materialises its [B, T, S] split indicators
and [B, T, L] leaf accumulators in HBM — ~100KB of traffic per record for
the 500-tree GBM, which makes the op HBM-bound (~1M rec/s/chip). This
kernel keeps every intermediate in VMEM and streams only the rank codes in
and scores out:

- **Tree grouping.** Trees are packed ``GT=4`` per group; each group's path
  matrices form one block-diagonal ``[GT*S, GT*L]`` operand (252x256 for
  depth-6 trees — two full 128x128 MXU tiles on each axis), so the two
  contractions per group are dense MXU matmuls instead of 500 tiny 63x64
  batched ones. The 4x FLOP inflation of the block-diagonal zeros is paid
  back by ~4x better MXU tiling and by not touching HBM.
- **Feature select as matmul.** ``x[b, feat[t,s]]`` gathers are
  TPU-hostile; instead the per-split feature values come from a one-hot
  matmul ``Xq_bf16 @ onehot[F, GT*S]`` (ranks <= 255 and the sentinel are
  exact in bf16, accumulated in f32).
- **Residency.** All group parameters (~11MB for the 500-tree GBM: the
  int8 block-diagonal path matrices, one-hot selectors, thresholds, leaf
  values) live in VMEM for the whole call as full-array inputs; the grid
  is (batch blocks, tree groups) and the kernel indexes the group tensors
  with ``program_id(1)``. The [Bblk] score block's index map ignores the
  group axis, so it stays resident while the inner axis sweeps groups,
  accumulating partials (j==0 initialises).

Per-record HBM traffic: 32B of codes in, 4B of score out, params once per
call — vs ~100KB/rec for the XLA path. Eligibility: a fixed batch that is
a whole number of blocks, group tensors within ``_VMEM_PARAM_BUDGET``
(:func:`build_pallas_fn` returns None otherwise), uint8 wire only
(uint16 ranks up to 65534 are not exactly representable in bf16, so the
one-hot select matmul would corrupt them; carrying the codes as f32 would
halve the MXU rate — such models stay on the XLA int-einsum path), and
either a linear regression aggregate (sum/average/weightedAverage/single,
whose coefficients fold into leaf values → scalar scores) or a
classification *vote* forest (majorityVote/weightedMajorityVote, whose
normalised vote weights fold into per-leaf class rows → [B, C] vote
shares, argmaxed outside the kernel). Everything else stays on XLA.

Correctness is tested in interpret mode on CPU against the XLA quantized
path and the f32 reference (tests/test_qtrees_pallas.py), and compiled on
the chip by ``chip_smoke.py`` at the flagship size.

Round 11 adds the **multi-tree megakernel** variant
(``build_pallas_fn(fuse_groups=True)``, the ``mega`` layout of
compile/layouts.py): the grid keeps only the batch axis and the tree-
group sweep fuses into an in-kernel ``fori_loop`` accumulating partials
in registers — one dispatch, one output write per block, same
accumulation order so scores stay bit-identical (tests/test_layouts.py).
The learned kernel search (compile/autotune.py) decides per model
whether it beats the grid form.
"""

from __future__ import annotations

import functools
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

GT = 4  # trees per block-diagonal group
DEFAULT_BLOCK_B = 1024  # batch rows per grid block
# the group tensors stay resident in VMEM for the whole call. The largest
# forest compiled on a chip so far is the 500-tree depth-6 GBM (~11MB of
# group tensors, v5e, Mosaic's default scoped-VMEM limit); eligibility
# stops just above it until a larger one has been run there.
_VMEM_PARAM_BUDGET = 12 * 1024 * 1024
_SCORE_TILE = 1024  # elements per tile of XLA's 1-D f32 layout on TPU


def pack_groups(
    feat: np.ndarray,     # i[T, S] feature index per split
    qthr: np.ndarray,     # u8[T, S] rank thresholds
    dleft: np.ndarray,    # bool[T, S]
    P: np.ndarray,        # i8[T, S, L]
    count: np.ndarray,    # i8[T, L]
    vals: np.ndarray,     # f32[T, L] scalar leaf values, or bf16[T, L, C]
                          # per-leaf class-row HI table (vote weights
                          # folded in; pass the matching LO residuals via
                          # ``vals_lo``)
    n_fields: int,
    vals_lo: Optional[np.ndarray] = None,  # bf16[T, L, C] LO residuals
    gt: int = GT,
) -> Dict[str, np.ndarray]:
    """Group-pack the per-tree tensors for the kernel (numpy, host-side).

    ``gt`` trees share one group (block-diagonal operand is
    ``[gt*S, gt*L]``): the default 4 makes two full 128x128 MXU tiles
    per axis for depth-6 trees. Another ``gt`` regroups the f32 tree
    sum, so its scores are not byte-identical to the default's.

    Classification tables MUST arrive as the bf16 hi/lo split pair
    (``vals``=hi, ``vals_lo``=lo) — the same operands the XLA path
    contracts. A single reconstructed f32 table is NOT equivalent on
    hardware: a default-precision f32 dot truncates its operands to bf16
    on the MXU, silently dropping the lo residuals (the round-3
    on-device classification parity failure)."""
    if gt <= 0:
        raise ValueError(f"gt must be > 0: {gt}")
    T, S = feat.shape
    L = P.shape[2]
    G = -(-T // gt)
    Tp = G * gt
    Sg, Lg = gt * S, gt * L

    featp = np.zeros((Tp, S), np.int64)
    featp[:T] = feat
    qthrp = np.zeros((Tp, S), np.float32)
    qthrp[:T] = qthr.astype(np.float32)
    dleftp = np.zeros((Tp, S), np.float32)
    dleftp[:T] = dleft.astype(np.float32)
    countp = np.full((Tp, L), -5.0, np.float32)  # padded trees never match
    countp[:T] = count.astype(np.float32)

    def _pad_collapse(tbl, dtype):
        padded = np.zeros((Tp,) + tbl.shape[1:], np.float32)
        padded[:T] = tbl.astype(np.float32)
        # Tp is G*gt contiguous, so collapsing (G, gt, L, …) → (G, Lg, …)
        # keeps each group's leaves in block order
        return padded.reshape((G, Lg) + tbl.shape[2:]).astype(dtype)

    # one-hot feature selector [G, F, Sg] (bf16 operand of the select dot)
    fsel = np.zeros((G, n_fields, Sg), np.float32)
    for t in range(Tp):
        g, o = divmod(t, gt)
        fsel[g, featp[t], o * S + np.arange(S)] = 1.0

    Pg = np.zeros((G, Sg, Lg), np.int8)
    for t in range(T):
        g, o = divmod(t, gt)
        Pg[g, o * S:(o + 1) * S, o * L:(o + 1) * L] = P[t]

    groups = {
        "fsel": fsel.astype(jnp.bfloat16),
        "qthr": qthrp.reshape(G, Sg),
        "dleft": dleftp.reshape(G, Sg),
        "Pg": Pg,
        "count": countp.reshape(G, Lg),
        "vals": _pad_collapse(
            vals, jnp.bfloat16 if vals_lo is not None else np.float32
        ),
    }
    if vals_lo is not None:
        groups["vals_lo"] = _pad_collapse(vals_lo, jnp.bfloat16)
    return groups


def param_bytes(groups: Dict[str, np.ndarray]) -> int:
    return sum(np.asarray(v).nbytes for v in groups.values())


def _leaf_hits(xq_ref, fsel_ref, qthr_ref, dleft_ref, p_ref, count_ref,
               j, sentinel: float):
    """Shared front half: rank codes → [Bblk, Lg] leaf one-hot (f32)."""
    xq = xq_ref[...]                                   # [Bblk, F] bf16
    xv = jnp.dot(
        xq, fsel_ref[j], preferred_element_type=jnp.float32
    )                                                  # [Bblk, Sg] exact ranks
    # predicate math stays in f32 arithmetic (Mosaic lowers bool selects
    # over mixed operands poorly): go = miss ? dleft : (xv <= qthr)
    missf = (xv == sentinel).astype(jnp.float32)
    cmpf = (xv <= qthr_ref[pl.ds(j, 1), :]).astype(jnp.float32)
    gol = missf * dleft_ref[pl.ds(j, 1), :] + (1.0 - missf) * cmpf
    sign = (2.0 * gol - 1.0).astype(jnp.bfloat16)
    acc = jnp.dot(
        sign, p_ref[j].astype(jnp.bfloat16),
        preferred_element_type=jnp.float32,
    )                                                  # [Bblk, Lg]
    return (acc == count_ref[pl.ds(j, 1), :]).astype(jnp.float32)


def _kernel(xq_ref, fsel_ref, qthr_ref, dleft_ref, p_ref, count_ref,
            vals_ref, out_ref, *, sentinel: float):
    j = pl.program_id(1)
    hit = _leaf_hits(
        xq_ref, fsel_ref, qthr_ref, dleft_ref, p_ref, count_ref, j, sentinel
    )
    part = jnp.sum(hit * vals_ref[pl.ds(j, 1), :], axis=1)  # [Bblk] f32

    @pl.when(j == 0)
    def _():
        out_ref[...] = part

    @pl.when(j > 0)
    def _():
        out_ref[...] = out_ref[...] + part


def _kernel_cls(xq_ref, fsel_ref, qthr_ref, dleft_ref, p_ref, count_ref,
                vals_ref, vlo_ref, out_ref, *, sentinel: float):
    """Classification votes: per-leaf class rows contract to [Bblk, C]
    vote-share partials, accumulated over tree groups.

    The class tables are the bf16 hi/lo SPLIT pair, contracted as two
    bf16 dots with f32 accumulation — the same math as the XLA path's
    ``_pair_einsum``. (Round-3 on-device failure: a single reconstructed
    f32 table at default dot precision gets truncated to bf16 by the
    MXU, losing the lo residuals; interpret mode on CPU did exact f32
    math, which is why parity only broke on hardware.)"""
    j = pl.program_id(1)
    hit = _leaf_hits(
        xq_ref, fsel_ref, qthr_ref, dleft_ref, p_ref, count_ref, j, sentinel
    )
    hb = hit.astype(jnp.bfloat16)  # 0/1 one-hot: exact in bf16
    part = jnp.dot(
        hb, vals_ref[j], preferred_element_type=jnp.float32
    ) + jnp.dot(
        hb, vlo_ref[j], preferred_element_type=jnp.float32
    )                                                  # [Bblk, C]

    @pl.when(j == 0)
    def _():
        out_ref[...] = part

    @pl.when(j > 0)
    def _():
        out_ref[...] = out_ref[...] + part


def _kernel_mega(xq_ref, fsel_ref, qthr_ref, dleft_ref, p_ref, count_ref,
                 vals_ref, out_ref, *, sentinel: float, n_groups: int):
    """Megakernel regression variant: ALL tree groups fuse into one
    grid step — an in-kernel ``fori_loop`` accumulates the group
    partials in registers and the [Bblk] output writes once, instead
    of the grid's inner axis revisiting the output block per group.
    Same accumulation order (ascending j, f32 adds of small-integer
    one-hot contractions), so scores are bit-identical to _kernel."""
    def body(j, acc):
        hit = _leaf_hits(
            xq_ref, fsel_ref, qthr_ref, dleft_ref, p_ref, count_ref,
            j, sentinel,
        )
        return acc + jnp.sum(hit * vals_ref[pl.ds(j, 1), :], axis=1)

    out_ref[...] = jax.lax.fori_loop(
        0, n_groups, body, jnp.zeros(out_ref.shape, jnp.float32)
    )


def _kernel_mega_cls(xq_ref, fsel_ref, qthr_ref, dleft_ref, p_ref,
                     count_ref, vals_ref, vlo_ref, out_ref, *,
                     sentinel: float, n_groups: int):
    """Megakernel classification variant: fused group loop over the
    same bf16 hi/lo split-pair dots as _kernel_cls (see there for why
    the split pair is mandatory on hardware)."""
    def body(j, acc):
        hit = _leaf_hits(
            xq_ref, fsel_ref, qthr_ref, dleft_ref, p_ref, count_ref,
            j, sentinel,
        )
        hb = hit.astype(jnp.bfloat16)
        # hi+lo FIRST, then fold into the accumulator — the exact
        # association _kernel_cls uses (out += hi_dot + lo_dot).
        # acc + hi_dot + lo_dot re-associates the f32 adds and drifts
        # 1 ULP from the grid kernel on non-integer vote tables,
        # breaking the catalogue's byte-parity invariant
        part = jnp.dot(
            hb, vals_ref[j], preferred_element_type=jnp.float32
        ) + jnp.dot(
            hb, vlo_ref[j], preferred_element_type=jnp.float32
        )
        return acc + part

    out_ref[...] = jax.lax.fori_loop(
        0, n_groups, body, jnp.zeros(out_ref.shape, jnp.float32)
    )


def build_pallas_fn(
    groups: Dict[str, np.ndarray],
    batch_size: int,
    n_fields: int,
    sentinel: int,
    block_b: int = DEFAULT_BLOCK_B,
    interpret: bool = False,
    fuse_groups: bool = False,
):
    """→ fn(group_params, Xq u8[B, F]) -> f32[B] ensemble sums (scalar
    ``vals``) or f32[B, C] vote shares (class-row ``vals``), or None when
    the shapes are outside this kernel's contract (the caller serves the
    model from the XLA rank-wire path, ``QuantizedScorer.backend ==
    "xla"``): group tensors over ``_VMEM_PARAM_BUDGET``, a batch that is
    not a whole number of blocks, a 1-D score block Mosaic cannot tile.

    ``fuse_groups=True`` builds the multi-tree megakernel (the
    ``mega`` layout of compile/layouts.py): grid ``(batch blocks,)``
    only, with the tree-group sweep fused into an in-kernel loop."""
    G = groups["fsel"].shape[0]
    classification = groups["vals"].ndim == 3
    if param_bytes(groups) > _VMEM_PARAM_BUDGET:
        return None
    while block_b > batch_size:
        block_b //= 2
    if block_b < 8 or batch_size % block_b:
        return None
    # XLA lays a 1-D f32 vector out in 1024-element tiles and Mosaic
    # refuses a score block tiled any other way ("XLA layout
    # {0:T(1024)S(1)} does not match Mosaic layout {0:T(512)S(1)}",
    # v5e), unless the block is the whole vector
    if (
        not classification
        and block_b % _SCORE_TILE
        and block_b != batch_size
    ):
        return None
    nb = batch_size // block_b
    F = n_fields
    # the megakernel's grid has no group axis: index maps take one
    # program id; the grid form keeps its (i, j) maps
    if fuse_groups:
        batch_map, grid = (lambda i: (i, 0)), (nb,)
    else:
        batch_map, grid = (lambda i, j: (i, 0)), (nb, G)

    def _full(shape):
        zeros = (0,) * len(shape)
        if fuse_groups:
            return pl.BlockSpec(shape, lambda i, _z=zeros: _z)
        return pl.BlockSpec(shape, lambda i, j, _z=zeros: _z)

    in_specs = [
        pl.BlockSpec((block_b, F), batch_map),
        _full(groups["fsel"].shape),
        _full(groups["qthr"].shape),
        _full(groups["dleft"].shape),
        _full(groups["Pg"].shape),
        _full(groups["count"].shape),
    ]
    if classification:
        assert "vals_lo" in groups, (
            "classification kernel requires the bf16 hi/lo split tables"
        )
        C = groups["vals"].shape[2]
        kern = (
            functools.partial(
                _kernel_mega_cls, sentinel=float(sentinel), n_groups=G
            )
            if fuse_groups
            else functools.partial(_kernel_cls, sentinel=float(sentinel))
        )
        in_specs.append(_full(groups["vals"].shape))
        in_specs.append(_full(groups["vals_lo"].shape))
        out_specs = pl.BlockSpec((block_b, C), batch_map)
        out_shape = jax.ShapeDtypeStruct((batch_size, C), jnp.float32)
    else:
        kern = (
            functools.partial(
                _kernel_mega, sentinel=float(sentinel), n_groups=G
            )
            if fuse_groups
            else functools.partial(_kernel, sentinel=float(sentinel))
        )
        in_specs.append(_full(groups["vals"].shape))
        out_specs = pl.BlockSpec(
            (block_b,), (lambda i: (i,)) if fuse_groups else
            (lambda i, j: (i,))
        )
        out_shape = jax.ShapeDtypeStruct((batch_size,), jnp.float32)

    call = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
    )

    def fn(gp, Xq):
        xb = Xq.astype(jnp.bfloat16)
        operands = [
            xb, gp["fsel"], gp["qthr"], gp["dleft"], gp["Pg"], gp["count"],
            gp["vals"],
        ]
        if classification:
            operands.append(gp["vals_lo"])
        return call(*operands)

    return fn
