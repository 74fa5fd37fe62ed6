"""Path builder ``block_twin``: ``paths/block.py`` as it is, for a
forest the XLA rank-wire twin scores (no Pallas kernel takes it). Its
facts also say how the twin scans each dispatch size the window can
use: the records a step takes (``QuantizedScorer.twin_rows``, from the
forest's shape and the device's memory) and the steps a dispatch.
"""

from __future__ import annotations

from lib import byname

_Block = byname.load("paths", "block").Path


class Path(_Block):
    def facts(self) -> dict:
        out = super().facts()
        for k in self._chunks:
            n = k * self.batch
            rows = self.q.twin_rows(n)
            if rows is not None:
                out[f"twin_rows.{n}"] = rows
                out[f"twin_steps.{n}"] = -(-n // rows)
        return out
