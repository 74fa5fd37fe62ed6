"""``idle_assembling_frac.sat`` read in the mesh cell, where it moves
``records_per_s.mesh``: the same reader."""
from lib import byname

read = byname.load("layer_metrics", "idle_assembling_frac.sat").read
