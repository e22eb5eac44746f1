"""CPU checks of the benchmark: ``python -m pytest bench/tests`` from the
root of the checkout. The program's kernels run interpreted on the CPU."""
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
os.environ.setdefault("JAX_PLATFORMS", "cpu")
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)
