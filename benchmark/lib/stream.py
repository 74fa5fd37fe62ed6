"""The event stream as a pure function of (seed, offset): the load
generator (a child process) and the correctness check (the parent)
both derive any stretch of the stream from the seed alone, so nothing
but parameters crosses the process boundary.

The stream is cut into blocks of ``BLOCK`` records; block ``b`` draws
its keys from ``default_rng([seed, 1, b])``, so a block never depends on
how the stream was chunked on the wire. Feature payloads cycle through a
seeded pool (``pool_rows`` rows); the key sequence does not cycle.
"""

from __future__ import annotations

import numpy as np

from . import keys as keys_mod

BLOCK = 65536


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
        self._block = (-1, None)

    def _block_ranks(self, b: int) -> np.ndarray:
        if self._block[0] != b:
            kind = self.mix["kind"]
            if kind == "zipf":
                rng = np.random.default_rng([self.seed, 1, b])
                r = keys_mod.zipf_ranks(
                    rng, BLOCK, self.domain, float(self.mix["a"])
                )
            else:
                raise ValueError(f"unknown key mix {kind!r}")
            self._block = (b, r)
        return self._block[1]

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
