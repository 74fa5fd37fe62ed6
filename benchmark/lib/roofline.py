"""Operations and bytes the *algorithm* needs for one dispatch of the
state-armed scoring program, from the configuration's shapes alone —
not the kernel's padded MXU count, not the bytes XLA happens to move.

The forest's shape is the model kind's to state
(``models/<model_kind>.py``, ``forest_shape(model) -> (trees, inner
nodes, leaves, walk)``: the nodes and leaves summed over the forest,
the walk the comparisons a record makes in one tree), so a forest of
any shape is counted by what it is, whatever implements it.

Scoring, per record: ``walk`` comparisons in each tree and one add per
tree. Bytes, per dispatch: the forest once (a feature index and a
threshold rank per inner node, a float32 per leaf), and per record the
rank-wire row in (one byte a feature), the score out, and the state
fold: the key's row read, the derived row out, the row written back,
plus the slot, decay stride, weight and reset flag that route it.

The forest kernel alone (``forest_*``) is the same count without the
fold: the same operations, the forest once, and per record the
rank-wire row in and the score out.
"""

from __future__ import annotations

from . import byname


def forest_shape(cfg: dict):
    """→ (trees, inner nodes, leaves, walk), as the model kind states
    them for the configuration's ``model``. The counts below take it
    as ``shape`` where a caller has it already: the kind's module is
    loaded once a reading."""
    return byname.load("models", cfg["model_kind"]).forest_shape(cfg["model"])


def ops(cfg: dict, n_records: float, shape=None) -> float:
    n_trees, _, _, walk = shape or forest_shape(cfg)
    return n_records * n_trees * (walk + 1)


def forest_bytes_moved(cfg: dict, n_records: float, shape=None) -> float:
    _, inner, leaves, _ = shape or forest_shape(cfg)
    return inner * 2 + leaves * 4 + n_records * (
        cfg["model"]["n_features"] + 4)


def bytes_moved(cfg: dict, n_records: float, shape=None) -> float:
    row = 4 * int(cfg["state"]["width_f32"])
    return forest_bytes_moved(cfg, n_records, shape) + n_records * (
        3 * row + (4 + 4 + 4 + 1))


def _least(n_ops: float, n_bytes: float, peaks: dict):
    t_ops = n_ops / peaks["int8_ops"]
    t_mem = n_bytes / peaks["hbm_bytes_per_s"]
    return (t_ops, "int8_ops") if t_ops >= t_mem else (t_mem, "hbm_bytes_per_s")


def least_seconds(cfg: dict, n_records: float, peaks: dict):
    """→ (seconds, binding roof). The rank wire compares integers, so
    the compute roof is the chip's int8 peak (the higher one: the share
    is never flattered)."""
    shape = forest_shape(cfg)
    return _least(ops(cfg, n_records, shape),
                  bytes_moved(cfg, n_records, shape), peaks)


def forest_least_seconds(cfg: dict, n_records: float, peaks: dict):
    """The forest kernel alone → (seconds, binding roof)."""
    shape = forest_shape(cfg)
    return _least(ops(cfg, n_records, shape),
                  forest_bytes_moved(cfg, n_records, shape), peaks)
