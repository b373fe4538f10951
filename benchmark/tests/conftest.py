import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent.parent
ROOT = BENCH.parent
for sub in ("lib", "generators", "readers"):
    sys.path.insert(0, str(BENCH / sub))
