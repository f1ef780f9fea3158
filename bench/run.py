"""Run one benchmark cell on the chip this process finds.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Prints the run's numbers on standard error and, as the last line of standard
output, one JSON object with ``correct``, ``attempted``, ``failed``,
``metrics`` and ``device``. Without the TPU chips the cell asks for it exits
non-zero and prints no result. The persistent compilation cache is kept at the
fixed path ``<checkout>/bench/.jax_cache``.
"""
import time

T0 = time.monotonic()

import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / "bench" / ".jax_cache")
sys.path.insert(0, str(ROOT))

from bench.harness import runner  # noqa: E402

if __name__ == "__main__":
    sys.exit(runner.main(t_process=T0))
