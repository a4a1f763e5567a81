import os
import pathlib
import sys

# The benchmark's own tests run on the CPU; the benchmark itself refuses it.
os.environ.setdefault("JAX_PLATFORMS", "cpu")

REPO = pathlib.Path(__file__).resolve().parents[2]
for p in (REPO, REPO / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))
