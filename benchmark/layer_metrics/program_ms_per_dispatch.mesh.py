"""Kernels: mean device time of one execution of the scoring program
in the traced stretch, over every chip's executions (one a chip a
dispatch: the hot chip's are the long ones, ``chip_busy_spread.mesh``)."""
from lib.readers import program_mean_ms as read  # noqa: F401
