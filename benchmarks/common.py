"""Shared benchmark infrastructure.

The paper evaluates pretrained 7B–70B checkpoints, which are unavailable offline
(DESIGN.md §5.2). The benchmarks reproduce the paper's *phenomena* on

  1. a small LM trained in-repo on a skewed Markov corpus (real model, real ppl), and
  2. **function-preserving planted outliers**: after training, a chosen fraction of
     channels has its pre-linear activation scaled by ``m`` (norm gain × m) while the
     consuming linear's rows are divided by m — the fp16 model computes the *same
     function*, but its activation matrices now carry the ≥20×-magnitude outlier
     channels of App. A / Dettmers et al. This reproduces the OPT-vs-LLaMA split:
     per-token quantization collapses on the outlier-planted model, CrossQuant holds.

The trained model is cached under results/bench_model/<key>/ so re-runs are fast.
The key hashes ``BENCH_CFG``, the training steps, the seed and the source of every
module that shapes the trained weights, so a cached checkpoint is reused only when
the committed files would rebuild the same model.
"""
from __future__ import annotations

import hashlib
import os
import pathlib
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import CheckpointManager
from repro.configs.base import ModelConfig
from repro.core import qlinear as ql
from repro.data.synthetic import markov_corpus
from repro.models import model as M
from repro.models.layers import QuantContext
from repro.training import optimizer as opt_lib, trainer

CACHE_DIR = os.environ.get("BENCH_CACHE", "results/bench_model")

BENCH_CFG = ModelConfig(
    name="bench-llama", family="dense",
    n_layers=4, d_model=128, n_heads=4, n_kv_heads=2, head_dim=32,
    d_ff=256, vocab=256, act="silu_glu", norm="rmsnorm", tie_embeddings=True,
)

VOCAB, SEQ, BATCH = 256, 64, 16
SKEW = 0.75
SEED = 0             # init and training-data seed of the benchmark LM


def train_batches(step: int, *, seed: int = 0) -> Dict[str, jnp.ndarray]:
    toks = markov_corpus(VOCAB, SEQ, BATCH, seed=seed + 7919 * step, skew=SKEW)
    return {"tokens": jnp.asarray(toks)}


def eval_batches(n: int, *, seed: int = 10_000):
    for i in range(n):
        yield train_batches(0, seed=seed + 31 * i)


def bench_model_key(steps: int) -> str:
    """Cache key of the trained benchmark LM: its config, steps and seed, plus the
    source of the modules its training runs through."""
    h = hashlib.sha256(f"{BENCH_CFG!r}|steps={steps}|seed={SEED}".encode())
    for mod in (M, trainer, opt_lib, markov_corpus, train_batches):
        src = getattr(mod, "__file__", None) or mod.__code__.co_filename
        h.update(pathlib.Path(src).read_bytes())
    return h.hexdigest()[:16]


def get_bench_model(steps: int = 400, force: bool = False):
    """Train (or load the cached) benchmark LM. Returns (cfg, params)."""
    cm = CheckpointManager(os.path.join(CACHE_DIR, bench_model_key(steps)), keep_n=1)
    cfg = BENCH_CFG
    template = M.init_params(jax.random.PRNGKey(SEED), cfg)
    if not force and cm.latest_step() is not None:
        params, _ = cm.restore(template)
        return cfg, params
    opt_cfg = opt_lib.AdamWConfig(lr=3e-3, warmup_steps=20, total_steps=steps)
    step_fn = jax.jit(trainer.make_train_step(cfg, opt_cfg))
    params = template
    opt = opt_lib.init(params)
    for s in range(steps):
        params, opt, metrics = step_fn(params, opt, train_batches(s, seed=SEED))
    cm.save(steps, params, blocking=True)
    print(f"# bench model trained to loss={float(metrics['loss']):.3f}")
    return cfg, params


# --------------------------------------------------------------------------------------
# Function-preserving outlier planting
# --------------------------------------------------------------------------------------

def plant_outliers(params, cfg: ModelConfig, *, frac: float = 0.03,
                   magnitude: float = 40.0, seed: int = 0):
    """Scale ``frac`` of channels by ``magnitude`` in every pre-linear norm gain and
    divide the consuming linear rows by the same factor — function-preserving, but
    the activation matrices now carry App.-A-style outlier channels."""
    rng = np.random.default_rng(seed)
    d = cfg.d_model
    n_out = max(1, int(round(frac * d)))
    ch = rng.choice(d, size=n_out, replace=False)
    mult = np.ones(d, np.float32)
    mult[ch] = magnitude

    def scale_block(block):
        out = jax.tree_util.tree_map(lambda x: x, block)   # shallow-ish copy
        mult_j = jnp.asarray(mult)
        out["norm1"] = {**block["norm1"], "scale": block["norm1"]["scale"] * mult_j}
        out["norm2"] = {**block["norm2"], "scale": block["norm2"]["scale"] * mult_j}
        attn = dict(block["attn"])
        for k in ("wq", "wk", "wv"):
            attn[k] = {"w": block["attn"][k]["w"] / mult_j[:, None]}
        out["attn"] = attn
        mlp = dict(block["mlp"])
        for k in ("up", "gate"):
            if k in mlp:
                mlp[k] = {"w": block["mlp"][k]["w"] / mult_j[:, None]}
        out["mlp"] = mlp
        return out

    new = dict(params)
    new["blocks"] = [jax.vmap(scale_block)(params["blocks"][0])]
    return new


# --------------------------------------------------------------------------------------
# Evaluation
# --------------------------------------------------------------------------------------

def eval_ppl(cfg, params, quant: Optional[ql.QuantConfig] = None, n_batches: int = 8,
             ) -> float:
    ctx = QuantContext(quant or ql.FP)
    total, count = 0.0, 0
    for batch in eval_batches(n_batches):
        loss, m = M.loss_fn(params, batch, cfg, ctx=ctx, remat=False)
        total += float(m["ce"])
        count += 1
    return float(np.exp(total / count))


def eval_acc(cfg, params, quant: Optional[ql.QuantConfig] = None, n_batches: int = 8,
             ) -> float:
    """Top-1 next-token accuracy (the zero-shot-task stand-in; skewed chain ->
    ceiling ≈ SKEW + (1-SKEW)/branching)."""
    ctx = QuantContext(quant or ql.FP)
    hits, total = 0, 0
    for batch in eval_batches(n_batches, seed=20_000):
        logits, _ = M.apply(params, batch, cfg, ctx=ctx, mode="train")
        pred = jnp.argmax(logits[:, :-1], axis=-1)
        want = batch["tokens"][:, 1:]
        hits += int(jnp.sum(pred == want))
        total += int(np.prod(want.shape))
    return hits / total


def mean_kernel_fraction(cfg, params, *, alpha: float = 0.15, bits: int = 8,
                         per_token: bool = False, n_batches: int = 2) -> float:
    """Average activation quantization-kernel fraction across every linear input in
    the model (eager capture via the calibration observer path)."""
    from repro.core import kernel_analysis as KA
    from repro.core import quantizers as Q

    fractions = []

    class KObserver:
        def observe(self, name, x):
            x2 = jnp.asarray(x).reshape(-1, x.shape[-1]).astype(jnp.float32)
            s = (Q.per_token_scale(x2, bits) if per_token
                 else Q.crossquant_scale(x2, bits, alpha))
            fractions.append(float(KA.kernel_fraction(x2, s)))

    ctx = QuantContext(ql.W8A8_CROSSQUANT, observer=KObserver())
    for batch in eval_batches(n_batches):
        M.apply(params, batch, cfg, ctx=ctx, mode="train", unroll=True)
    return float(np.mean(fractions))
