"""Self-tests of the benchmark, run by hand on the CPU:

    JAX_PLATFORMS=cpu python -m pytest -q perfbench/tests
"""
import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent.parent / "src"))
sys.path.insert(0, str(HERE.parent))
