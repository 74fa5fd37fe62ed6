"""The keyed state a stream of (key, score) pairs must leave behind,
with nothing of the program in it: per key, how many records and the
sum of their scores."""

from __future__ import annotations

import numpy as np


class KeyTally:
    """Dict tally of per-key record count and score sum."""

    def __init__(self):
        self.count = {}
        self.total = {}

    def fold(self, keys, values) -> None:
        for k, v in zip(np.asarray(keys).tolist(),
                        np.asarray(values, np.float64).tolist()):
            self.count[k] = self.count.get(k, 0) + 1
            self.total[k] = self.total.get(k, 0.0) + v
