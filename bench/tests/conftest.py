"""Tests of the benchmark's own code, on the CPU at small sizes.

Run from the checkout's root: ``python -m pytest bench/tests``.
"""

import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(os.path.dirname(BENCH), "src"))
sys.path.insert(0, BENCH)
