"""The benchmark's own tests, run on the CPU:

    JAX_PLATFORMS=cpu python -m pytest -q chipbench/tests

(The repository's ``testpaths = ["tests"]`` does not collect them.)
"""
import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
HERE = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parent / "src"))
