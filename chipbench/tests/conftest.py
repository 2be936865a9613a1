"""The benchmark's own tests run on the CPU: ``python -m pytest
chipbench/tests`` from the repository root (they sit outside the
repository's testpaths)."""
import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))
