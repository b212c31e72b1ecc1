"""The benchmark's own tests, in tier-1: the manifest, the readers and
the reductions that decide a PR are held by the run that decides a PR.

These are the test functions (and the fixtures they ask for) of
``chipbench/tests`` that run in one process on the CPU without the
tiny cells' drivers: each keeps its own module's globals, so this file
only has to name them.  ``chipbench/tests/test_*_driver.py`` and
``test_drivers.py`` stay out (PERF.md section 7: five of them pin cell
counts and a gauge that later PRs moved, and only a ``benchmark`` PR may
edit them).
"""

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "chipbench", "tests"))

from test_idle import *  # noqa: E402,F401,F403
from test_manifest import *  # noqa: E402,F401,F403
from test_pipe_readers import *  # noqa: E402,F401,F403
from test_readings import *  # noqa: E402,F401,F403
from test_setup_readers import *  # noqa: E402,F401,F403
from test_spans import *  # noqa: E402,F401,F403
from test_steady import *  # noqa: E402,F401,F403
from test_trace import *  # noqa: E402,F401,F403

# left out: it holds a cell added by a test to exactly the one per-layer
# metric that lists it, and since PR 53 the ten ``setup_*_s`` metrics
# list no cells and so belong to every cell, as ``setup_s`` does (the
# file is the benchmark's: PERF.md section 7)
del test_a_cell_and_a_metric_are_added_by_adding_files  # noqa: F821
