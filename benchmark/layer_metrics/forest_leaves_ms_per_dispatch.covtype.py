"""``forest_leaves_ms_per_dispatch.lgbm`` read in the covtype cell: each
class's sum over its own group of trees (the leaf values' contraction);
where the chip fuses it into the path contraction, that scope has it."""
from lib import byname

read = byname.load("layer_metrics", "forest_leaves_ms_per_dispatch.lgbm").read
