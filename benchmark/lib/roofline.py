"""Operations and bytes the *algorithm* needs for one dispatch of the
state-armed scoring program, from the configuration's shapes alone —
not the kernel's padded MXU count, not the bytes XLA happens to move.

Scoring, per record: one comparison per level of each tree and one add
per tree. Bytes, per dispatch: the forest once (a feature index and a
threshold rank per inner node, a float32 per leaf), and per record the
rank-wire row in (one byte a feature), the score out, and the state
fold: the key's row read, the derived row out, the row written back,
plus the slot, decay stride, weight and reset flag that route it.

The forest kernel alone (``forest_*``) is the same count without the
fold: the same operations, the forest once, and per record the
rank-wire row in and the score out.
"""

from __future__ import annotations


def ops(cfg: dict, n_records: float) -> float:
    m = cfg["model"]
    return n_records * m["n_trees"] * (m["depth"] + 1)


def _forest_bytes(m: dict) -> float:
    inner, leaves = 2 ** m["depth"] - 1, 2 ** m["depth"]
    return m["n_trees"] * (inner * 2 + leaves * 4)


def forest_bytes_moved(cfg: dict, n_records: float) -> float:
    m = cfg["model"]
    return _forest_bytes(m) + n_records * (m["n_features"] + 4)


def bytes_moved(cfg: dict, n_records: float) -> float:
    row = 4 * int(cfg["state"]["width_f32"])
    return forest_bytes_moved(cfg, n_records) + n_records * (
        3 * row + (4 + 4 + 4 + 1))


def _least(n_ops: float, n_bytes: float, peaks: dict):
    t_ops = n_ops / peaks["int8_ops"]
    t_mem = n_bytes / peaks["hbm_bytes_per_s"]
    return (t_ops, "int8_ops") if t_ops >= t_mem else (t_mem, "hbm_bytes_per_s")


def least_seconds(cfg: dict, n_records: float, peaks: dict):
    """→ (seconds, binding roof). The rank wire compares integers, so
    the compute roof is the chip's int8 peak (the higher one: the share
    is never flattered)."""
    return _least(ops(cfg, n_records), bytes_moved(cfg, n_records), peaks)


def forest_least_seconds(cfg: dict, n_records: float, peaks: dict):
    """The forest kernel alone → (seconds, binding roof)."""
    return _least(ops(cfg, n_records), forest_bytes_moved(cfg, n_records),
                  peaks)
