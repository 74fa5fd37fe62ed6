"""The benchmark's own tests (``benchmark/tests``), in tier-1: the
harness every cell leans on is guarded by the driver's test run, not
only by a builder who remembers to run it. The cases are the
benchmark's; this file only takes them in."""

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark", "tests"))

from test_harness import *  # noqa: E402,F401,F403
from test_mesh_cell import *  # noqa: E402,F401,F403
from test_churn_cell import *  # noqa: E402,F401,F403
from test_idle_readers import *  # noqa: E402,F401,F403
from test_lgbm_cell import *  # noqa: E402,F401,F403
from test_covtype_cell import *  # noqa: E402,F401,F403
