"""Cross-model packing: one device dispatch scoring a batch that spans
N tenants' models (the multi-tenant zoo fast path).

The paper's core idiom is MANY small PMML models served concurrently
from one streaming job — a per-segment zoo. Served solo, a zoo of tiny
tree models serializes into N tiny launches: with a fixed launch
overhead per dispatch, the chip idles between gathers and aggregate
MFU craters. This module
generalizes the per-model group packing (qtrees_pallas.pack_groups
packs TREE groups of one model block-diagonally) one level up: N whole
models ride ONE dispatch.

Design — subgraph packing, not table packing:

- **Shared input buffer.** One staged array ``Xp[N, B, F_max]`` in the
  widest member wire dtype. Slot ``i`` is tenant ``i``'s sub-buffer:
  the host routes each tenant's rank-encoded rows into its slot (the
  tenant-id lane), zero-padding exactly like the solo path's
  ``pad_wire`` does, so a member's slot content is byte-identical to
  what its solo dispatch would have staged. A uint8 member's codes
  widen exactly into a uint16 buffer (codes ≤ 255, and its own
  sentinel value 255 compares unchanged).
- **One program, N member subgraphs.** The jitted packed program
  slices slot ``i``, narrows to the member's own field count, casts
  back to the member's own wire dtype (exact — see above), and runs
  the member's OWN quantized kernel body (``qfn``, attached by
  build_quantized_scorer as ``_pack_info``) against the member's OWN
  live param tables. Every member subgraph therefore executes the
  same ops at the same shapes on the same operands as its solo
  dispatch — de-multiplexed outputs are **byte-identical** to solo by
  construction, not by tolerance (pinned in tests/test_zoo.py). The
  win is launch amortization: one host→device round trip, one
  executable, N models.
- **Zero param duplication.** Member param tables are shared with the
  solo scorer (same device buffers); a pack adds only the staged
  input buffer and one compiled executable.

Which models share a buffer is a LAYOUT decision: compile/layouts.py
enumerates packing partitions, compile/costmodel.py prices them
(padded-waste + predicted device-s/record), and compile/autotune.py
adopts/persists the winner per model-SET hash — see
``autotune.ensure_pack_plan``. The serving-side device-memory manager
(serving/zoo.py) owns pack residency (LRU + warm pool).
"""

from __future__ import annotations

import hashlib
import os
import time
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

# hard cap on members per pack regardless of what a plan says: each
# member is a subgraph in ONE jitted program, so compile time grows
# with pack size — a runaway plan must not compile a 1000-subgraph
# program
_PACK_MAX_ENV = "FJT_PACK_MAX"
_PACK_MAX_DEFAULT = 16
# per-member param-bytes ceiling for pack eligibility: packing exists
# for SMALL models (dispatch-bound); a flagship 500-tree GBM is
# compute-bound and serves better solo
_PACK_MEMBER_BYTES_ENV = "FJT_PACK_MEMBER_BYTES"
_PACK_MEMBER_BYTES_DEFAULT = 8 * 1024 * 1024


def pack_max() -> int:
    try:
        return max(2, int(os.environ.get(_PACK_MAX_ENV)
                          or _PACK_MAX_DEFAULT))
    except ValueError:
        return _PACK_MAX_DEFAULT


def member_bytes_cap() -> int:
    try:
        return int(os.environ.get(_PACK_MEMBER_BYTES_ENV)
                   or _PACK_MEMBER_BYTES_DEFAULT)
    except ValueError:
        return _PACK_MEMBER_BYTES_DEFAULT


def param_bytes(scorer) -> int:
    """Host-visible size of a scorer's param tables (the zoo manager's
    residency accounting unit; device-resident bytes track this).
    Memoized on the scorer — the eligibility pre-filter runs it per
    group per micro-batch, and param tables never change post-compile."""
    cached = getattr(scorer, "_param_bytes", None)
    if cached is not None:
        return cached
    total = 0
    try:
        for v in scorer.params.values():
            nb = getattr(v, "nbytes", None)
            if nb is not None:
                total += int(nb)
    except Exception:
        pass
    try:
        scorer._param_bytes = total
    except Exception:
        pass
    return total


def pack_eligible(scorer) -> bool:
    """Can this scorer ride a cross-model pack?

    Requires the XLA backend with the reference (unpacked) wire: the
    packed program re-runs the member's ``qfn`` body, which reads raw
    rank codes — a ``wirepack`` layout changes the staged wire format
    and a Pallas member bakes its own grid. Fused-encode members still
    qualify (the pack always host-encodes; host is the byte-parity
    oracle the fused path itself is pinned against)."""
    if scorer is None:
        return False
    cap = member_bytes_cap()
    memo = getattr(scorer, "_pack_memo", None)
    if memo is not None and memo[0] == cap:
        return memo[1]
    ok = (
        bool(getattr(scorer, "_pack_info", None))
        and getattr(scorer, "backend", "") == "xla"
        and getattr(scorer, "_wire_pack", None) is None
        and scorer.batch_size is not None
        and param_bytes(scorer) <= cap
    )
    try:
        # keyed on the cap so an FJT_PACK_MEMBER_BYTES change (tests)
        # re-evaluates instead of serving a stale verdict
        scorer._pack_memo = (cap, ok)
    except Exception:
        pass
    return ok


def model_set_hash(hashes: Sequence[str]) -> str:
    """Stable identity of a model MULTISET (tenants may share one
    document): the autotune pack-plan cache key half. Sorted so tenant
    arrival order cannot split the cache; a tenant add/remove changes
    the hash and therefore invalidates the adopted layout."""
    h = hashlib.sha256()
    for mh in sorted(str(x) for x in hashes):
        h.update(mh.encode())
        h.update(b"|")
    return h.hexdigest()[:16]


class PackedScorer:
    """One compiled multi-model program over a fixed member list.

    ``members`` are live :class:`~flink_jpmml_tpu.compile.qtrees
    .QuantizedScorer`s sharing one compile batch size ``B``; ``keys``
    are the tenants' serving labels (metrics only). The packed input
    is ``Xp[N, B, F_max]`` in :attr:`in_dtype`; :meth:`assemble`
    routes per-member encoded rows into their slots and
    :meth:`dispatch` runs the single jitted program. Member ``i``'s
    output element is byte-identical to its solo ``predict_wire`` on
    the same rows (module docstring; pinned in tests/test_zoo.py)."""

    def __init__(self, members: Sequence, keys: Sequence[str]):
        import jax

        if not members:
            raise ValueError("empty pack")
        self.members = list(members)
        self.keys = [str(k) for k in keys]
        sizes = {m.batch_size for m in self.members}
        if len(sizes) != 1 or None in sizes:
            raise ValueError(f"pack members disagree on batch size: {sizes}")
        self.B = int(next(iter(sizes)))
        infos = [m._pack_info for m in self.members]
        if any(not i for i in infos):
            raise ValueError("pack member without _pack_info")
        self.F_max = max(int(i["fields"]) for i in infos)
        self.in_dtype = (
            np.uint16
            if any(i["dtype"] is np.uint16 for i in infos)
            else np.uint8
        )
        self._infos = infos
        self._params = tuple(m.params for m in self.members)
        member_plans = [
            (int(i["fields"]), i["dtype"], i["qfn"]) for i in infos
        ]

        def packed_fn(pps, Xp):
            outs = []
            for i, (f, dt, qfn) in enumerate(member_plans):
                Xi = Xp[i]
                if f < Xp.shape[2]:
                    Xi = Xi[:, :f]
                # exact narrowing: a uint8 member's codes (sentinel
                # included) are ≤ 255 in the widened buffer
                Xi = Xi.astype(dt)
                outs.append(qfn(pps[i], Xi))
            return tuple(outs)

        self._jit_fn = jax.jit(packed_fn)

    @property
    def n_members(self) -> int:
        return len(self.members)

    @property
    def buffer_bytes(self) -> int:
        """Bytes of one staged packed input buffer."""
        return (
            self.n_members * self.B * self.F_max
            * np.dtype(self.in_dtype).itemsize
        )

    @property
    def resident_bytes(self) -> int:
        """Residency accounting for the zoo manager: the staging
        buffer plus the member tables this pack keeps hot. (Member
        params are SHARED with the solo scorers — the pack holds
        references, not copies — but eviction semantics charge the
        pack for keeping them pinned.)"""
        return self.buffer_bytes + sum(
            param_bytes(m) for m in self.members
        )

    def pad_waste(self) -> float:
        """Fraction of the shared input buffer that is padding (the
        layout search's waste axis, re-measured on the built pack)."""
        used = sum(
            self.B * int(i["fields"]) * np.dtype(i["dtype"]).itemsize
            for i in self._infos
        )
        total = self.buffer_bytes
        return 1.0 - used / total if total else 0.0

    def new_buffer(self) -> np.ndarray:
        return np.zeros(
            (self.n_members, self.B, self.F_max), self.in_dtype
        )

    def assemble(
        self,
        rows: Dict[int, np.ndarray],
        out: Optional[np.ndarray] = None,
    ) -> Tuple[np.ndarray, int]:
        """Route per-member encoded rows into their slots.

        ``rows[i]`` is member ``i``'s rank-encoded batch (its OWN wire
        dtype, ≤ B rows); absent members dispatch an all-zero slot
        (scored and discarded — occupancy accounting makes the waste
        visible). → ``(Xp, n_rows_total)``."""
        Xp = out if out is not None else self.new_buffer()
        total = 0
        for i, Xq in rows.items():
            n = Xq.shape[0]
            if n > self.B:
                raise ValueError(
                    f"member {i} rows {n} exceed pack slot {self.B}"
                )
            Xp[i, :n, : Xq.shape[1]] = Xq  # exact widening cast
            total += n
        return Xp, total

    def dispatch(self, Xp: np.ndarray):
        """One launch for all members → tuple of member outputs, each
        exactly what the member's solo ``predict_wire`` returns for
        its slot."""
        return self._jit_fn(self._params, Xp)

    def dispatch_state(self, Xp: np.ndarray, table, slots, rel, w,
                       reset, member: int = 0, donate: bool = False):
        """State-armed launch: every member scores exactly as
        :meth:`dispatch` (byte-identical outputs — the state stage only
        appends ops) and the designated ``member``'s value stream folds
        through the keyed state table → ``(outs, derived, S')``; the
        caller commits ``S'``. See statekernel.packed_entry for the
        shared-table semantics."""
        from flink_jpmml_tpu.compile import statekernel

        fn = statekernel.packed_entry(
            self, donate, table.spec.decay, table.scratch, member
        )
        return fn(self._params, Xp, table.values, slots, rel, w, reset)

    def warmup(self) -> float:
        """Force the XLA compile (the pack's cold-start cost) →
        seconds spent."""
        import jax

        t0 = time.monotonic()
        out = self.dispatch(self.new_buffer())
        jax.block_until_ready(out)
        return time.monotonic() - t0


def build_pack(members: Sequence, keys: Sequence[str]) -> PackedScorer:
    """Validated constructor: every member must be :func:`pack_eligible`
    (callers pre-filter; this is the belt)."""
    for m in members:
        if not pack_eligible(m):
            raise ValueError(
                "pack member not eligible for cross-model packing"
            )
    if len(members) > pack_max():
        raise ValueError(
            f"pack size {len(members)} exceeds FJT_PACK_MAX={pack_max()}"
        )
    return PackedScorer(members, keys)
