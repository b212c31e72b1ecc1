"""These tests run on the CPU; they are not part of the repository's
tier-1 suite:

    python -m pytest chipbench/tests -q
"""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
