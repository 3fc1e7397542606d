"""The benchmark's own tests, run by hand on the CPU:

    JAX_PLATFORMS=cpu python -m pytest benchmarks/chip/tests

(the repository's ``testpaths`` is ``tests``, so its suite does not collect
them)."""
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[3]
for p in (ROOT / "src", ROOT):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))
