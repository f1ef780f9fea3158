"""The control's readings at a cell's own size, on several seeds, in one process.

    python bench/control.py --workload <cell> --seeds 1,2,3 --seconds 10

For each seed, one run of the cell as ``bench/run.py`` makes it, then the
plain reference over the same sample twice: once to read how far the served
tokens' reference logits lie below the best (widest and mean gap), once with
every linear in int4 (the control) to read the same of the token the control
puts first. The control's numbers go through the same comparison with the
cell's limits as the served tokens'; its ``correct`` has to come out false.
The benchmark's own runs never run this. Prints one JSON line per seed.
"""
import time

T0 = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / "bench" / ".jax_cache")
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

from bench.harness import runner, spec  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args()
    cell = spec.load_cell(args.workload)
    runner.start_jax()
    clock = runner.CompileClock()
    for s in args.seeds.split(","):
        t = time.monotonic()
        out = runner.run_cell(cell, int(s), args.seconds, False, t_process=t,
                              control=True, clock=clock)
        print(json.dumps({"seed": int(s), "correct": out["correct"],
                          "control_correct": out["control"]["correct"],
                          "served": out["served"], "control": out["control"],
                          "metrics": out["metrics"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
