"""The event stream as a pure function of (seed, offset): the load
generator (a child process) and the correctness check (the parent)
both derive any stretch of the stream from the seed alone, so nothing
but parameters crosses the process boundary.

The stream is cut into blocks of ``BLOCK`` records; the traffic file's
key mix (``key_mix.kind`` names ``lib/keymix/<kind>.py``) draws block
``b``'s keys from the seed and ``b`` alone, so a block never depends on
how the stream was chunked on the wire. Feature payloads cycle through a
seeded pool (``pool_rows`` rows); the key sequence does not cycle.
"""

from __future__ import annotations

import threading

import numpy as np

from . import byname
from . import keys as keys_mod

BLOCK = 65536
# up to 23 pieces of 8192 in eight encoders' hands span four, and one ahead
_BLOCKS_KEPT = 6


class Stream:
    def __init__(self, seed: int, n_features: int, key_domain: int,
                 key_mix: dict, pool_rows: int):
        self.seed = int(seed)
        self.n_features = int(n_features)
        self.domain = int(key_domain)
        self.mix = dict(key_mix)
        if self.domain > keys_mod.MAX_F32_RANKS:
            raise ValueError(f"key domain {self.domain} exceeds float32 ids")
        rng = np.random.default_rng([self.seed, 0])
        # N(0, 1.5): chip_smoke.py's feature distribution (chip_smoke.py:212)
        self.pool = rng.normal(
            0.0, 1.5, size=(int(pool_rows), self.n_features)
        ).astype(np.float32)
        self._mix_ranks = byname.load(
            "lib/keymix", self.mix["kind"]).ranks
        # block → [lock, ranks]: several threads of the producer ask
        # for the same block; one draws it and the others wait for that
        # block alone
        self._blocks, self._mu = {}, threading.Lock()

    def _block_ranks(self, b: int) -> np.ndarray:
        with self._mu:
            slot = self._blocks.get(b)
            if slot is None:
                slot = self._blocks[b] = [threading.Lock(), None]
                while len(self._blocks) > _BLOCKS_KEPT:
                    del self._blocks[next(iter(self._blocks))]
        with slot[0]:
            if slot[1] is None:
                r = np.asarray(self._mix_ranks(
                    b, self.seed, self.domain, self.mix, BLOCK), np.int64)
                if r.shape != (BLOCK,) or r.min() < 0 or r.max() >= self.domain:
                    raise ValueError(
                        f"key mix {self.mix['kind']!r}: block {b} is not "
                        f"{BLOCK} ranks below {self.domain}")
                slot[1] = r
        return slot[1]

    def ranks(self, lo: int, hi: int) -> np.ndarray:
        """Key ranks of offsets [lo, hi)."""
        parts = []
        pos = lo
        while pos < hi:
            b, i = divmod(pos, BLOCK)
            take = min(hi - pos, BLOCK - i)
            parts.append(self._block_ranks(b)[i:i + take])
            pos += take
        return np.concatenate(parts) if parts else np.empty(0, np.int64)

    def ids(self, lo: int, hi: int) -> np.ndarray:
        """The entity ids the program sees for offsets [lo, hi)."""
        return keys_mod.rank_to_id(self.ranks(lo, hi))

    def rows(self, lo: int, hi: int) -> np.ndarray:
        """float32 [hi-lo, F] feature rows of offsets [lo, hi); the key
        rides column 0."""
        idx = np.arange(lo, hi, dtype=np.int64) % self.pool.shape[0]
        X = self.pool[idx]
        X[:, 0] = keys_mod.rank_to_f32(self.ranks(lo, hi))
        return X
