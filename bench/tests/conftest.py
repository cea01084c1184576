import os
import sys
from pathlib import Path

# The benchmark's tests run on the CPU, in interpret mode for Pallas.
os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
