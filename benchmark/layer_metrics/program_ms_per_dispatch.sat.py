"""Kernels: mean device time of one execution of the scoring program in
the traced stretch (it does not shrink with the batch: the fold's cost
follows the table, not the records)."""
from lib.readers import program_mean_ms as read  # noqa: F401
