"""Serving launcher: quantize a model post-training (the paper's deployment) and run
batched greedy decoding through the continuous-batching engine.

    PYTHONPATH=src python -m repro.launch.serve --arch gemma2-9b --smoke \
        --quant fake --n-requests 8 --max-new 16
    PYTHONPATH=src python -m repro.launch.serve --arch deepseek-coder-33b --smoke \
        --quant int8         # prepared integer weights (quantize_tree)
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get
from repro.core import calibration, qlinear as ql
from repro.data import make_train_batches
from repro.launch import compile_cache
from repro.models import model as M
from repro.models.layers import QuantContext
from repro.models.quantize import quantize_tree, quantized_bytes
from repro.serving.config import EngineConfig
from repro.serving.engine import ServeEngine

QUANTS = {
    "fp": ql.FP,
    "fake": ql.W8A8_CROSSQUANT,
    "fake_pt": ql.W8A8_PER_TOKEN,
    "w4a8": ql.W4A8_G128,
    "int8": ql.W8A8_INT8,
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--quant", default="fake", choices=QUANTS)
    ap.add_argument("--n-requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--prompt-lens", default=None, metavar="L1,L2,...",
                    help="mixed-length workload: cycle prompt lengths over requests "
                         "(continuous batcher admits each into its length bucket)")
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--batch-size", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=64)
    ap.add_argument("--eos-id", type=int, default=None,
                    help="EOS token id; default: no EOS (token 0 is the PAD token, "
                         "so it is never an implicit terminator)")
    ap.add_argument("--scheduler", default="continuous",
                    choices=["continuous", "grouped"],
                    help="continuous = slot refill mid-decode (DESIGN.md §3.6); "
                         "grouped = legacy equal-length groups, drained")
    ap.add_argument("--calib-batches", type=int, default=2,
                    help="calibration batches for the int8 static-c path")
    ap.add_argument("--path", default="ref",
                    choices=["ref", "dequant-fp", "fused-int8"],
                    help="integer execution backend (int8 quant, DESIGN.md §3.3)")
    ap.add_argument("--kv-cache", default="fp", choices=["fp", "int8"])
    ap.add_argument("--mesh", default=None, metavar="DATA,MODEL",
                    help="serve TP-sharded on a (data, model) host mesh "
                         "(DESIGN.md §3.7), e.g. --mesh 4,2. Needs data*model "
                         "devices: set XLA_FLAGS=--xla_force_host_platform_"
                         "device_count=N before launching (token-exact vs the "
                         "default single-device path)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    compile_cache.enable()

    cfg = get(args.arch, smoke=args.smoke)
    quant = QUANTS[args.quant]
    key = jax.random.PRNGKey(args.seed)
    params = M.init_params_on_device(key, cfg)
    base_bytes = quantized_bytes(params)

    if args.quant == "int8":
        # Offline PTQ: calibrate column stats eagerly, fold into int8 weights.
        print("calibrating static-c column statistics ...")
        obs = calibration.Observer()
        batch_fn = make_train_batches(cfg.vocab, args.prompt_len, args.batch_size,
                                      seed=args.seed + 1)
        ctx = QuantContext(quant, observer=obs)
        for b in range(args.calib_batches):
            batch = {k: jnp.asarray(v) for k, v in batch_fn(b).items()}
            M.apply(params, batch, cfg, ctx=ctx, mode="train", unroll=True)
        params = quantize_tree(params, quant,
                               tables=calibration.stack_tables(obs.tables()))
        q_bytes = quantized_bytes(params)
        print(f"quantized weights: {base_bytes / 2**20:.1f} MiB -> "
              f"{q_bytes / 2**20:.1f} MiB ({base_bytes / q_bytes:.2f}x smaller)")

    mesh = None
    if args.mesh:
        from repro.launch.mesh import parse_mesh_arg
        mesh = parse_mesh_arg(args.mesh)

    path = None if (args.quant != "int8" or args.path == "ref") else args.path
    config = EngineConfig(batch_size=args.batch_size, max_len=args.max_len,
                          path=path, kv_cache=args.kv_cache,
                          eos_id=args.eos_id, scheduler=args.scheduler)
    engine = ServeEngine(cfg, params, config=config, quant=quant, mesh=mesh)
    if engine.plan is not None:
        print(f"sharded serving: mesh={dict(mesh.shape)} "
              f"plan={engine.plan.describe()}")
    rng = np.random.default_rng(args.seed)
    lens = ([int(x) for x in args.prompt_lens.split(",")] if args.prompt_lens
            else [args.prompt_len])
    prompts = [rng.integers(1, cfg.vocab, size=lens[i % len(lens)]).astype(np.int32)
               for i in range(args.n_requests)]
    engine.submit(prompts, max_new=args.max_new)
    t0 = time.time()
    done = engine.run()
    dt = time.time() - t0
    n_tok = sum(len(r.out) for r in done)
    print(f"served {len(done)} requests, {n_tok} tokens in {dt:.2f}s "
          f"({n_tok / dt:.1f} tok/s) quant={quant.tag()} "
          f"scheduler={args.scheduler} occupancy={engine.occupancy():.2f}")
    for r in done[:4]:
        print(f"  req {r.rid}: prompt[:4]={r.prompt[:4].tolist()} -> out={r.out[:8]}")


if __name__ == "__main__":
    main()
