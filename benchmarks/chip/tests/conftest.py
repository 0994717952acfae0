import os
import pathlib
import sys

# the harness's tests run on the CPU; only the benchmark itself needs a chip
os.environ.setdefault("JAX_PLATFORMS", "cpu")

HERE = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(HERE), str(HERE.parents[1] / "src")]
