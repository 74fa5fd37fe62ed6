"""Plain reference for a state table that lies over several chips: who
owns a key, and how many records of a stream each chip has to have
folded. Numpy and a rule; no code of the program.

The rule, as the deployment states it: a key's slot is what the table's
own lookup returns (probing from ``hash % capacity`` over the GLOBAL
capacity), and chip ``d`` of ``D`` owns the global slots ``[d·R,
(d+1)·R)`` with ``R = ⌈capacity/D⌉``; a slot's row on its chip is its
place in that range. The owner of a record is the owner of its key's
slot.
"""

from __future__ import annotations

import numpy as np


def owner(slots, capacity: int, n_chips: int):
    """Global slots → ``(chip, row on that chip)``."""
    per_chip = -(-int(capacity) // int(n_chips))
    s = np.asarray(slots, np.int64)
    return s // per_chip, s % per_chip


def tally_by_owner(record_slots, capacity: int, n_chips: int) -> list:
    """Records a chip (one entry each, in chip order) has to have
    folded, given the slot of each record's key: a dict count."""
    counts = {}
    for chip in owner(record_slots, capacity, n_chips)[0].tolist():
        counts[chip] = counts.get(chip, 0) + 1
    return [counts.get(d, 0) for d in range(int(n_chips))]
