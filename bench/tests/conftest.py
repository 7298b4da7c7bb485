"""CPU tests of the benchmark's yardstick. Run from the checkout's root:
``JAX_PLATFORMS=cpu python -m pytest -q bench/tests``."""

import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)
