"""Kernel-level roofline micro-benchmark for the Pallas qgemm/act-quantize kernels,
plus an end-to-end fp-vs-fused-int8 serving comparison (DESIGN.md §3.3/§7).

No TPU is attached, so wall-clock numbers are CPU-interpret sanity only; the
*derived* columns are the structural roofline terms for TPU v5e per kernel call:
bytes moved (HBM), int8 MXU ops, arithmetic intensity, and the projected
compute-vs-memory-bound time. GEMM shapes are the hot projections of the assigned
archs at the paper's W8A8 setting.

Reported speedup logic (recorded in §Perf): against a bf16 GEMM of the same shape,
the int8 path moves ~half the weight bytes and runs the MXU at 2x throughput —
projected_bf16 / projected_int8 is the kernel-level headline.

The ``e2e`` section serves the same request batch through the continuous batcher on
the fp path and the fused-int8 path (ServeEngine path="fused-int8"): measured CPU
tokens/sec for both, plus the projected TPU step-time ratio from the model's
decode-GEMM shapes. On CPU the fused path *loses* wall-clock (Pallas interpret
overhead) — the projected column is the deployment-relevant number.

The ``qgemm_sparse`` section times the §3.12 block-sparse kernel against the dense
kernel at varying K-block occupancy (the regress gate pins sparse <= dense on the
skipped-block rows), and ``e2e_sparse`` serves a 2:4-sparsified tree vs the dense
int8 tree plus the deployment-capacity column (extra KV pages per device at fixed
HBM, from ``quantized_bytes(deploy_sparse=True)``).
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.launch.peaks import V5E, peaks

# proj_* columns project one TPU v5e chip (CPU timings are not device numbers)
_CHIP = peaks(V5E)
PEAK_INT8, PEAK_BF16, HBM_BW = _CHIP.int8_ops, _CHIP.bf16_flops, _CHIP.hbm_bw

# (arch tag, M=tokens-per-chip-step, K=d_model, N=output dim of the hot projection)
SHAPES = [
    ("deepseek33b.ffn_up", 4096, 7168, 19200 // 16),
    ("gemma2_9b.ffn_up", 4096, 3584, 14336 // 16),
    ("nemotron15b.ffn_up", 4096, 6144, 24576 // 16),
    ("llama4.expert_up", 5120, 5120, 8192),
    ("starcoder2.qkv", 4096, 4608, 6144 // 16),
]


def derived(M, K, N, w_bits=8):
    bytes_moved = M * K + (K * N) * (w_bits / 8) + M * N * 4 + M * 4 + N * 4
    ops = 2 * M * K * N
    t_compute_int8 = ops / PEAK_INT8
    t_mem = bytes_moved / HBM_BW
    t_int8 = max(t_compute_int8, t_mem)
    bf16_bytes = 2 * (M * K + K * N + M * N)
    t_bf16 = max(ops / PEAK_BF16, bf16_bytes / HBM_BW)
    return bytes_moved, ops, ops / bytes_moved, t_int8, t_bf16


def _serve_tok_s(cfg, params, *, quant, path, kv_cache, n_req, max_new) -> float:
    from repro.serving.config import EngineConfig
    from repro.serving.engine import ServeEngine
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, cfg.vocab, size=8).astype(np.int32)
               for _ in range(n_req)]
    config = EngineConfig(batch_size=min(4, n_req), max_len=32, eos_id=-1,
                          path=path, kv_cache=kv_cache)
    eng = ServeEngine(cfg, params, config=config, quant=quant)
    eng.submit(prompts, max_new=max_new)
    t0 = time.perf_counter()
    done = eng.run()
    dt = time.perf_counter() - t0
    return sum(len(r.out) for r in done) / dt


def sparse(quick: bool = False):
    """Block-sparse int8 GEMM (DESIGN.md §3.12) vs the dense kernel at varying
    K-block occupancy, both through the ops dispatch in interpret mode.

    The occupancy=1.00 row measures pure bookkeeping overhead (the wrapper's
    runtime cond routes it to the dense kernel); the sub-full rows measure the
    win from skipped MXU dots — interpret mode genuinely skips the gated work,
    so the regress gate pins ``sparse <= dense`` wall-clock there. Projected
    TPU columns scale the roofline terms by occupancy (compute and weight
    bytes shrink together; activations and output do not)."""
    from repro.kernels import ops

    M, K, N = (256, 1024, 256) if quick else (256, 2048, 256)
    bk = 256
    key = jax.random.PRNGKey(0)
    qx = jax.random.randint(key, (M, K), -127, 128, jnp.int8)
    qw = jax.random.randint(key, (K, N), -127, 128, jnp.int8)
    a = jnp.ones((M, 1), jnp.float32)
    sw = jnp.ones((N,), jnp.float32)

    def t_us(f):
        f().block_until_ready()
        reps = 3
        t0 = time.perf_counter()
        for _ in range(reps):
            f().block_until_ready()
        return (time.perf_counter() - t0) / reps * 1e6

    lines = ["qgemm_sparse,occupancy,cpu_dense_us,cpu_sparse_us,ratio,"
             "proj_tpu_us_dense,proj_tpu_us_sparse"]
    n_k = K // bk
    bytes_d, ops_d, _, t_dense_tpu, _ = derived(M, K, N)
    for occ_frac in (1.0, 0.5, 0.25):
        keep = jnp.repeat(jnp.arange(n_k) < round(occ_frac * n_k), bk)[:, None]
        mask = keep & jnp.ones((K, N), bool)
        qwm = jnp.where(mask, qw, 0)
        cpu_s = t_us(lambda: ops.qgemm_w8a8_sparse(qx, qwm, a, sw, mask,
                                                   bm=256, bn=256, bk=bk))
        cpu_d = t_us(lambda: ops.qgemm_w8a8(qx, qwm, a, sw, bm=256, bn=256,
                                            bk=bk))
        sp_bytes = M * K + K * N * occ_frac + K * N / 8 + M * N * 4 + M * 4 + N * 4
        t_sp_tpu = max(ops_d * occ_frac / PEAK_INT8, sp_bytes / HBM_BW)
        lines.append(f"qgemm_sparse,{occ_frac:.2f},{cpu_d:.0f},{cpu_s:.0f},"
                     f"{cpu_s / cpu_d:.2f},{t_dense_tpu * 1e6:.1f},"
                     f"{t_sp_tpu * 1e6:.1f}")
    return lines


def e2e_sparse(quick: bool = False):
    """Sparse-vs-dense fused-int8 serving on the smoke model: CPU tok/s for
    both, plus the §3.12 capacity column — the HBM a 2:4 deployment format
    hands back, expressed as extra KV pages per device at fixed HBM."""
    from repro.configs import get
    from repro.core import qlinear as ql
    from repro.models import model as M2
    from repro.models import quantize as MQ

    cfg = get("starcoder2-7b", smoke=True)
    params = M2.init_params(jax.random.PRNGKey(0), cfg)
    qparams = MQ.quantize_tree(params, ql.W8A8_INT8)
    sparams = MQ.sparsify_tree(qparams, MQ.SparsityPlan(nm=(2, 4)))
    n_req, max_new = (2, 4) if quick else (4, 8)
    dense = _serve_tok_s(cfg, qparams, quant=ql.W8A8_INT8, path="fused-int8",
                         kv_cache="int8", n_req=n_req, max_new=max_new)
    sp = _serve_tok_s(cfg, sparams, quant=ql.W8A8_INT8, path="fused-int8",
                      kv_cache="int8", n_req=n_req, max_new=max_new)
    dense_b = MQ.quantized_bytes(qparams)
    deploy_b = MQ.quantized_bytes(sparams, deploy_sparse=True)
    # one int8-KV page: page_size tokens x (k + v) x kv heads x head_dim x
    # n_layers bytes (scales are amortized per page row and negligible here)
    page_b = 8 * 2 * cfg.n_kv_heads * cfg.head_dim * cfg.n_layers
    extra_pages = (dense_b - deploy_b) / page_b
    return [
        "e2e_sparse,arch,cpu_dense_tok_s,cpu_sparse_tok_s,ratio,dense_bytes,"
        "deploy_bytes,extra_pages_per_dev",
        f"e2e_sparse,{cfg.name},{dense:.1f},{sp:.1f},{sp / dense:.2f},"
        f"{dense_b},{deploy_b},{extra_pages:.0f}",
    ]


def e2e(quick: bool = False):
    """End-to-end continuous-batching comparison: fp vs fused-int8 (+ int8 KV)."""
    from repro.configs import get
    from repro.core import qlinear as ql
    from repro.models import model as M2
    from repro.models.quantize import quantize_tree

    cfg = get("starcoder2-7b", smoke=True)
    params = M2.init_params(jax.random.PRNGKey(0), cfg)
    qparams = quantize_tree(params, ql.W8A8_INT8)
    n_req, max_new = (2, 4) if quick else (4, 8)
    fp = _serve_tok_s(cfg, params, quant=ql.FP, path=None, kv_cache="fp",
                      n_req=n_req, max_new=max_new)
    fused = _serve_tok_s(cfg, qparams, quant=ql.W8A8_INT8, path="fused-int8",
                         kv_cache="int8", n_req=n_req, max_new=max_new)
    # Projected TPU ratio from the decode hot GEMMs of this config (structural —
    # the same roofline terms as the qgemm section, summed over the layer's dots).
    d, f = cfg.d_model, cfg.d_ff
    shapes = [(n_req, d, cfg.n_heads * cfg.head_dim),
              (n_req, cfg.n_heads * cfg.head_dim, d),
              (n_req, d, f), (n_req, f, d)]
    t8 = sum(derived(M, K, N)[3] for M, K, N in shapes)
    t16 = sum(derived(M, K, N)[4] for M, K, N in shapes)
    return [
        "e2e,arch,cpu_fp_tok_s,cpu_int8_tok_s,cpu_ratio,proj_tpu_ratio",
        f"e2e,{cfg.name},{fp:.1f},{fused:.1f},{fused / fp:.2f},{t16 / t8:.2f}",
    ]


def run(quick: bool = False):
    lines = ["qgemm,shape,bytes,int8_ops,intensity,proj_tpu_us,proj_bf16_us,speedup,"
             "cpu_ref_us"]
    shapes = SHAPES[:2] if quick else SHAPES
    for tag, M, K, N in shapes:
        b, ops, inten, t8, t16 = derived(M, K, N)
        # CPU sanity timing of the jnp reference int8 GEMM (not a TPU number).
        qx = jnp.ones((min(M, 256), K), jnp.int8)
        qw = jnp.ones((K, min(N, 256)), jnp.int8)
        a = jnp.ones((min(M, 256), 1), jnp.float32)
        sw = jnp.ones((min(N, 256),), jnp.float32)
        from repro.kernels.ref import qgemm_w8a8_ref
        f = jax.jit(qgemm_w8a8_ref)
        f(qx, qw, a, sw).block_until_ready()
        t0 = time.perf_counter()
        reps = 3
        for _ in range(reps):
            f(qx, qw, a, sw).block_until_ready()
        cpu_us = (time.perf_counter() - t0) / reps * 1e6
        lines.append(f"qgemm,{tag},{b:.3g},{ops:.3g},{inten:.0f},"
                     f"{t8 * 1e6:.1f},{t16 * 1e6:.1f},{t16 / t8:.2f},{cpu_us:.0f}")
    lines.extend(sparse(quick))
    lines.extend(e2e(quick))
    lines.extend(e2e_sparse(quick))
    return lines


if __name__ == "__main__":
    print("\n".join(run()))
