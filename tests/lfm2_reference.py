"""The plain reference of ``lfm2_24b_a2b_ep8`` (``benchmark/reference/``)
and the benchmark's comparison, as the tier-1 tests import them."""

import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "benchmark"
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))


from harness import load_module as load  # noqa: E402  (benchmark/ is on the path now)

reference = load(BENCH / "reference" / "lfm2_24b_a2b_ep8.py")
