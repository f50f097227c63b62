"""The benchmark's own tests: ``pytest benchmark/tests`` (not tier-1).
They run on the CPU and never take the chip."""

import os
import sys
from pathlib import Path

os.environ["JAX_PLATFORMS"] = "cpu"
BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH.parent), str(BENCH)]
