"""Tests of the benchmark harness.  They run on the CPU at tiny sizes,
and compile the cells' programs for a described TPU v5e; none needs a
chip.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q
"""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
