"""How a key rank rides the wire (the ranks themselves come from the
traffic file's key mix, ``lib/keymix/<kind>.py``).

``rank_to_f32``: the block path reads the key from a float32 feature
column (``KeyedStateTable.extract_keys``, runtime/state.py:271-274),
and float32 holds only 2**24 consecutive integers. Rank ``r`` becomes
the float32 whose bit pattern is ``0x4B000000 + r``: every float32 at
or above 2**23 is a whole number, so each rank is a distinct integer
id that ``astype(int64)`` recovers exactly (``f32_to_id``).
"""

from __future__ import annotations

import numpy as np

_F32_INT_BASE = 0x4B000000  # bit pattern of 2**23
# ranks whose bit pattern stays below +inf (0x7F800000)
MAX_F32_RANKS = 0x7F800000 - _F32_INT_BASE


def rank_to_f32(ranks: np.ndarray) -> np.ndarray:
    return (np.asarray(ranks, np.int64) + _F32_INT_BASE).astype(
        np.uint32
    ).view(np.float32)


def f32_to_id(col: np.ndarray) -> np.ndarray:
    """The integer id the program reads from a key column."""
    return np.asarray(col, np.float32).astype(np.int64)


def rank_to_id(ranks: np.ndarray) -> np.ndarray:
    """The entity id a rank stands for: the integer value of the float32
    that carries it."""
    return f32_to_id(rank_to_f32(ranks))
