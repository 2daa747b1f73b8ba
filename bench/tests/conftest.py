import os
import sys
from pathlib import Path

# the benchmark's tests run on the CPU; only bench/run.py needs a chip
os.environ.setdefault("JAX_PLATFORMS", "cpu")
SRC = str(Path(__file__).resolve().parents[2] / "src")
if SRC not in sys.path:
    sys.path.insert(0, SRC)
