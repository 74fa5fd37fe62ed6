"""``route_us_per_krec.sat`` read in the mesh cell, where it moves
``records_per_s.mesh``: the same reader."""
from lib import byname

read = byname.load("layer_metrics", "route_us_per_krec.sat").read
