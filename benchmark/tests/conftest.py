"""The benchmark's own tests run on the CPU, at small sizes:

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q
"""
import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(1, str(BENCH.parent))
