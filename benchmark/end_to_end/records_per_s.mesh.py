"""``records_per_s`` of the mesh cell: the same reader (records
delivered inside the window, each offset once, over the window's
seconds), under a name of its own so that the mesh cell has a bound of
its own. Its runs settle into one of the score thread's regimes and stay
there (PERF.md), a spread the one-chip cells' bound does not hold."""
from lib import byname

read = byname.load("end_to_end", "records_per_s").read
