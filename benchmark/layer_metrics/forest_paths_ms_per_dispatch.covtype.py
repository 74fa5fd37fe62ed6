"""``forest_paths_ms_per_dispatch.lgbm`` read in the covtype cell: the
XLA twin's path contraction and leaf match over its 21,000 trees."""
from lib import byname

read = byname.load("layer_metrics", "forest_paths_ms_per_dispatch.lgbm").read
