"""Benchmark runner: one module per paper table/figure (+ beyond-paper benches).

    PYTHONPATH=src python -m benchmarks.run            # full pass
    PYTHONPATH=src python -m benchmarks.run --quick    # CI-speed subset

Each module runs in its own subprocess: a single long-lived process accumulates
XLA-CPU JIT dylibs across hundreds of compiled graphs and eventually fails with
"Failed to materialize symbols"; process isolation resets the JIT per module.

Prints CSV sections; each line is ``<bench>,<key...>,<value...>``. The mapping to
the paper's tables/figures is in DESIGN.md §7 and benchmarks/README.md; EXPERIMENTS.md
quotes these outputs. ``--json PATH`` additionally writes the machine-readable
``BENCH_*.json`` snapshot (schema in benchmarks/README.md) used for cross-PR
trajectory tracking. A partial run (``--only``) *merges* into an existing
snapshot at PATH — modules not re-run keep their previous entries — and
``total_seconds`` is always recomputed as the sum of the per-module seconds, so
an ``--only`` pass can never shrink the committed baseline to its own runtime
(the staleness the pre-merge writer produced: modules summing to 177.7s under a
``total_seconds`` of 25.0).
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

from repro.launch import compile_cache

MODULES = [
    "table1_alpha", "table2_ppl", "table3_tasks", "fig4_kernels",
    "fig67_threshold", "fig8_alpha_sweep", "grad_compression", "qgemm_bench",
    "serving_bench",
]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--only", default=None, help="comma-separated module subset")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="write a BENCH_*.json snapshot (benchmarks/README.md)")
    args = ap.parse_args()
    mods = args.only.split(",") if args.only else MODULES
    t_all = time.time()
    failures = []
    snapshot = {"schema": 2, "quick": args.quick, "modules": {}}
    if args.json and args.only and os.path.exists(args.json):
        # partial run: merge into the existing snapshot so the modules this run
        # skips keep their entries (and their seconds) instead of vanishing
        try:
            with open(args.json) as fh:
                prev = json.load(fh)
            snapshot["modules"].update(prev.get("modules", {}))
        except (OSError, json.JSONDecodeError) as e:
            print(f"# existing snapshot {args.json} unreadable ({e}); rewriting")
    # children share one persistent compile cache; the parent stays off JAX
    # (compile_cache imports it only in enable()), so a child can hold the chip
    env = {**os.environ, "PYTHONPATH": os.environ.get("PYTHONPATH", "src"),
           compile_cache.ENV: compile_cache.cache_dir()}
    for name in mods:
        t0 = time.time()
        print(f"== {name} ==", flush=True)
        code = (f"from benchmarks.{name} import run\n"
                f"print('\\n'.join(run(quick={args.quick!r})))")
        r = subprocess.run([sys.executable, "-c", code], env=env, text=True,
                           capture_output=True, timeout=3600)
        sys.stdout.write(r.stdout)
        ok = r.returncode == 0
        if not ok:
            failures.append((name, r.stderr.strip().splitlines()[-1][:200]
                             if r.stderr.strip() else "unknown"))
            print(f"{name},ERROR,see stderr", flush=True)
            sys.stderr.write(r.stderr[-2000:])
        dt = time.time() - t0
        snapshot["modules"][name] = {
            "ok": ok, "seconds": round(dt, 1), "quick": args.quick,
            "lines": [ln for ln in r.stdout.splitlines() if ln.strip()],
        }
        print(f"# {name} took {dt:.0f}s", flush=True)
    # total = sum over *recorded* modules (merged entries included), never this
    # invocation's wall clock alone
    snapshot["total_seconds"] = round(
        sum(m.get("seconds", 0.0) for m in snapshot["modules"].values()), 1)
    if args.json:
        os.makedirs(os.path.dirname(args.json) or ".", exist_ok=True)
        with open(args.json, "w") as fh:
            json.dump(snapshot, fh, indent=1)
        print(f"# wrote {args.json}")
    print(f"# this run {time.time() - t_all:.0f}s; "
          f"snapshot modules total {snapshot['total_seconds']:.0f}s")
    if failures:
        print(f"# FAILURES: {failures}")
        sys.exit(1)


if __name__ == "__main__":
    main()
