"""The benchmark's own tests, run with ``python -m pytest bench/tests`` on the
CPU. Pallas kernels run in interpret mode and the paged kernels through their
XLA oracle, at small sizes."""
import os
import pathlib
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("REPRO_KERNEL_EXEC", "ref")

ROOT = pathlib.Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)
