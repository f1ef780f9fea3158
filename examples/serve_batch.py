"""Continuous-batched serving of a PTQ-quantized model on a mixed-length workload.

Calibrates CrossQuant's static column statistics on synthetic traffic, folds them
into true-int8 weights (quantize_tree), and serves a *mixed-length* batch of
requests (three prompt lengths, staggered ``max_new``) through the slot-table
continuous batcher (DESIGN.md §3.6): prompts are admitted into free slots via
length-bucketed padded prefill and retired slots refill mid-decode. ``--path``
selects the integer execution backend (DESIGN.md §3.3) and ``--kv-cache int8``
stores decode K/V as int8 codes + per-token scales; ``--compare`` serves the same
workload through the fp baseline and the fused int8 path and reports both
tokens/sec plus slot occupancy. ``--quant-kernel-stats`` replays the served
traffic (prompt + generated tokens) through the model eagerly and reports the
paper's per-layer quantization-kernel proportion (core/kernel_analysis.py) for
per-token quantization vs CrossQuant — the §4.1 statistic, measured on what the
engine actually served rather than a calibration set. For MoE configs
(``--arch granite-moe-3b-a800m`` / ``llama4-scout-17b-a16e``) the report adds
per-expert rows: each expert quantizes its own routed-token block of the
stacked (E, C, d) dispatch buffer, so the kernel proportion is a per-expert
property (padding rows excluded).

``--cache-layout paged`` serves through the paged KV pool with radix prefix
reuse (DESIGN.md §3.8); with ``--shared-prefix N`` every prompt carries an
N-token shared system prompt, so admissions past the first map the cached
prefix pages copy-free and only prefill their suffix (the printed
``prefix_hit_rate`` / ``prefill_saved`` stats).

``--speculate K`` serves speculative (DESIGN.md §3.9): each model step verifies
a K-token draft window proposed by the self-drafting prompt-lookup drafter —
token-exact vs plain decode by greedy acceptance, with accept rate and emitted
tokens/step printed. Pays off on repetitive traffic (templates, code); combine
with ``--cache-layout paged --kv-cache int8`` for the full paged-int8 verify
path.

``--chunked --token-budget N`` serves with chunked prefill + prefill-decode
interleaving (DESIGN.md §3.10): every step packs each generating slot's decode
row first, then fills the leftover budget with prompt chunks through the
ragged flash-prefill kernel — token-exact vs unchunked admission. Combined
with ``--quant-kernel-stats``, the replay additionally reports the per-chunk
CrossQuant kernel proportion (the §4.1 statistic computed over each
token_budget-sized admission slice) and its token-weighted aggregate against
the whole-prompt figure — chunked admission leaves the metric unchanged.

``--sparsity 2:4`` prunes every eligible linear to N:M structured sparsity at
engine build (DESIGN.md §3.12) — scales refit to the survivors, a bit-packed
keep-mask rides the tree, and the fused path serves through the block-sparse
int8 kernel. The report prints pruned-linear count, kept fraction, and the
dense-layout vs N:M-deploy weight bytes.

``--mesh data,model`` serves TP-sharded on a host mesh (DESIGN.md §3.7) — set
``XLA_FLAGS=--xla_force_host_platform_device_count=N`` first.

Engine flags derive from the :class:`EngineConfig` dataclass fields
(``add_config_args``, DESIGN.md §3.11) and ``--config path.json`` loads a JSON
EngineConfig first with explicit flags layered on top; leaving ``--path`` unset
serves on the jnp ref backend.

    PYTHONPATH=src:. python examples/serve_batch.py [--quant int8|fake|fp]
        [--path dequant-fp|fused-int8] [--kv-cache fp|int8] [--compare]
        [--prompt-lens 6,10,14] [--eos-id N] [--quant-kernel-stats]
        [--mesh 4,2] [--speculate 4] [--cache-layout paged]
        [--chunked --token-budget 16] [--sparsity 2:4] [--config engine.json]
"""
import argparse
import dataclasses
import pathlib
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get
from repro.core import calibration, kernel_analysis as KA, qlinear as ql
from repro.data import make_train_batches
from repro.launch import compile_cache
from repro.models import model as M
from repro.models.layers import QuantContext
from repro.models.quantize import quantize_tree, quantized_bytes
from repro.serving.config import EngineConfig, add_config_args, config_from_args
from repro.serving.engine import ServeEngine


def calibrate_and_quantize(cfg, params, quant):
    print("calibrating static-c column stats on 2 batches ...")
    obs = calibration.Observer()
    batch_fn = make_train_batches(cfg.vocab, 16, 4, seed=1)
    for b in range(2):
        batch = {k: jnp.asarray(v) for k, v in batch_fn(b).items()}
        M.apply(params, batch, cfg, ctx=QuantContext(quant, observer=obs),
                mode="train", unroll=True)
    before = quantized_bytes(params)
    qparams = quantize_tree(params, quant,
                            tables=calibration.stack_tables(obs.tables()))
    after = quantized_bytes(qparams)
    print(f"weights {before / 2**20:.1f} MiB -> {after / 2**20:.1f} MiB "
          f"({before / after:.2f}x smaller)")
    return qparams


def mixed_workload(cfg, n_requests, prompt_lens, seed=0, shared_prefix=0):
    """Mixed prompt lengths + staggered max_new: the continuous-batching case.
    ``shared_prefix`` prepends that many identical tokens to every prompt (a
    shared system prompt) — the paged layout's prefix-reuse case."""
    rng = np.random.default_rng(seed)
    shared = rng.integers(1, cfg.vocab, size=shared_prefix).astype(np.int32)
    prompts = [np.concatenate([
        shared, rng.integers(1, cfg.vocab,
                             size=prompt_lens[i % len(prompt_lens)]).astype(np.int32)])
        for i in range(n_requests)]
    max_new = [8 + 4 * (i % 3) for i in range(n_requests)]
    return prompts, max_new


def serve(cfg, params, prompts, max_new, *, config, quant, tag="", mesh=None):
    engine = ServeEngine(cfg, params, config=config, quant=quant, mesh=mesh)
    engine.submit([p.copy() for p in prompts], max_new=list(max_new))
    t0 = time.time()
    done = engine.run()
    dt = time.time() - t0
    total = sum(len(r.out) for r in done)
    st = engine.stats()
    shard = f", tp={engine.plan.tp} tier={engine.plan.tier}" if engine.plan else ""
    paged = ""
    if config.cache_layout == "paged":
        paged = (f", prefix_hit_rate={st.prefix_hit_rate:.2f}, "
                 f"prefill_saved={st.counters['prefix_tokens_reused']}, "
                 f"peak_pages={st.counters['peak_pages_in_use']}"
                 f"/{engine.pool.n_pages}")
    spec = ""
    if config.speculate > 1:
        spec = (f", speculate={config.speculate} "
                f"accept_rate={st.accept_rate:.2f} "
                f"tok/step={st.tokens_per_step:.2f}")
    if config.chunked:
        spec += (f", token_budget={config.token_budget} "
                 f"chunk_steps={st.counters['chunk_steps']} "
                 f"prefill_rows={st.counters['chunk_prefill_rows']}")
    print(f"[{tag or (config.path or 'ref')}] served {len(done)} requests / "
          f"{total} tokens in {dt:.2f}s ({total / dt:.1f} tok/s, "
          f"kv={config.kv_cache}, occupancy={st.occupancy:.2f}, "
          f"refills_mid_decode={st.counters['mid_decode_admissions']}"
          f"{paged}{spec}{shard})")
    return done, total / dt


class _KernelStatsObserver:
    """Observer shim (calibration.Observer protocol): per-layer kernel fractions."""

    def __init__(self, bits: int, alpha: float, chunk: int = 0):
        self.bits, self.alpha, self.chunk = bits, alpha, chunk
        self.stats: dict = {}

    def observe(self, name, x):
        x2 = x.reshape(-1, x.shape[-1]).astype(jnp.float32)
        rec = self.stats.setdefault(name, {"pt": [], "cq": [], "chunks": [],
                                           "experts": {}})
        if x.ndim == 3 and "/moe/" in name:
            # Stacked (E, C, d) expert dispatch buffer (moe_apply serves the
            # observer replay with one global dispatch): row e of the leading
            # axis is expert e's routed tokens, zero rows are capacity padding.
            # The §4.1 proportion is computed per expert over its *routed* rows
            # only — each expert quantizes its own (C, d) activation block, so
            # the kernel statistic is a per-expert property (DESIGN.md §4).
            for e in range(x.shape[0]):
                rows = jnp.asarray(x[e], jnp.float32)
                rows = rows[jnp.any(rows != 0.0, axis=-1)]
                er = rec["experts"].setdefault(e, {"pt": [], "cq": [], "n": 0})
                er["n"] += int(rows.shape[0])
                if rows.shape[0]:
                    er["pt"].append(
                        float(KA.per_token_kernel_fraction(rows, self.bits)))
                    er["cq"].append(
                        float(KA.crossquant_kernel_fraction(rows, self.bits,
                                                            self.alpha)))
            x2 = x2[jnp.any(x2 != 0.0, axis=-1)]   # layer row: routed rows only
            if x2.shape[0] == 0:
                return
        rec["pt"].append(float(KA.per_token_kernel_fraction(x2, self.bits)))
        rec["cq"].append(float(KA.crossquant_kernel_fraction(x2, self.bits,
                                                             self.alpha)))
        if self.chunk:
            # token_budget-sized row slices: the activation rows one chunked
            # admission step quantizes together. CrossQuant's column max c_j
            # is re-derived from only the chunk's rows — the dynamic-c view
            # of chunked admission (static-c serving is chunk-invariant by
            # construction: its c_j comes from calibration, not the chunk).
            for lo in range(0, x2.shape[0], self.chunk):
                part = x2[lo: lo + self.chunk]
                rec["chunks"].append(
                    (part.shape[0],
                     float(KA.crossquant_kernel_fraction(part, self.bits,
                                                         self.alpha))))


def report_kernel_stats(cfg, params, quant, done, chunk: int = 0):
    """Replay the served traffic eagerly and print per-layer kernel proportions.

    The replay runs each request's prompt + generated tokens through the model in
    unroll mode (observers cannot run under scan) on the ref backend — the
    activations feeding every quantized linear are exactly those of the served
    sequences, so the reported proportions are traffic-faithful (paper §4.1).

    With ``chunk`` (the ``--chunked`` serve's token budget), a second table
    slices each layer's activation rows into token_budget-sized chunks — the
    rows one chunked admission step quantizes together — and compares the
    token-weighted aggregate of per-chunk CrossQuant proportions against the
    whole-prompt figure. Causal attention makes the activations themselves
    identical either way, so any gap is purely the dynamic column statistic
    c_j seeing fewer rows per chunk; the aggregate staying at the whole-prompt
    value is the §4.1 metric's invariance under chunked admission.
    """
    bits = getattr(quant, "a_bits", 8) or 8
    alpha = getattr(quant, "alpha", 0.15)
    obs = _KernelStatsObserver(bits, alpha, chunk=chunk)
    ctx = QuantContext(quant, observer=obs)
    for r in done:
        toks = np.concatenate([r.prompt, np.asarray(r.out, np.int32)])
        M.apply(params, {"tokens": jnp.asarray(toks[None])}, cfg, ctx=ctx,
                mode="train", unroll=True)
    print(f"quantization-kernel proportion on served traffic "
          f"(bits={bits}, alpha={alpha}):")
    print(f"  {'layer':<28} {'per-token':>10} {'crossquant':>11} {'shrink':>7}")
    for name, rec in sorted(obs.stats.items()):
        pt = float(np.mean(rec["pt"]))
        cq = float(np.mean(rec["cq"]))
        shrink = (1 - cq / pt) if pt > 0 else 0.0
        print(f"  {name:<28} {pt:>9.2%} {cq:>10.2%} {shrink:>6.1%}")
    moe_layers = {n: r for n, r in obs.stats.items() if r["experts"]}
    if moe_layers:
        print("per-expert crossquant proportion (routed tokens only; the "
              "kernel statistic is per-expert for MoE layers, DESIGN.md §4):")
        print(f"  {'layer[expert]':<28} {'tokens':>6} {'per-token':>10} "
              f"{'crossquant':>11} {'shrink':>7}")
        for name, rec in sorted(moe_layers.items()):
            for e, er in sorted(rec["experts"].items()):
                if not er["pt"]:
                    print(f"  {name + f'[e{e}]':<28} {er['n']:>6d} "
                          f"{'-':>10} {'-':>11} {'-':>7}")
                    continue
                pt = float(np.mean(er["pt"]))
                cq = float(np.mean(er["cq"]))
                shrink = (1 - cq / pt) if pt > 0 else 0.0
                print(f"  {name + f'[e{e}]':<28} {er['n']:>6d} "
                      f"{pt:>9.2%} {cq:>10.2%} {shrink:>6.1%}")
    if chunk:
        print(f"per-chunk crossquant proportion (token_budget={chunk} "
              f"admission slices, dynamic c_j per chunk):")
        print(f"  {'layer':<28} {'chunks':>6} {'per-chunk':>10} "
              f"{'whole':>8} {'|delta|':>8} {'spread':>7}")
        for name, rec in sorted(obs.stats.items()):
            ws = [w for w, _ in rec["chunks"]]
            fs = [f for _, f in rec["chunks"]]
            agg = float(np.average(fs, weights=ws))
            cq = float(np.mean(rec["cq"]))
            print(f"  {name:<28} {len(fs):>6d} {agg:>9.2%} {cq:>7.2%} "
                  f"{abs(agg - cq):>7.4f} {max(fs) - min(fs):>6.2%}")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default=None, metavar="PATH.json",
                    help="load an EngineConfig from JSON; explicit engine "
                         "flags below override its fields")
    add_config_args(ap)
    ap.add_argument("--quant", default="int8", choices=["fp", "fake", "int8"])
    ap.add_argument("--shared-prefix", type=int, default=0, metavar="N",
                    help="prepend N identical tokens to every prompt (shared "
                         "system prompt — exercises paged prefix reuse)")
    ap.add_argument("--compare", action="store_true",
                    help="also serve the fp baseline and report both tok/s")
    ap.add_argument("--arch", default="starcoder2-7b")
    ap.add_argument("--n-requests", type=int, default=6)
    ap.add_argument("--prompt-lens", default="6,10,14", metavar="L1,L2,...",
                    help="prompt lengths cycled over requests (mixed-length "
                         "continuous batching)")
    ap.add_argument("--quant-kernel-stats", action="store_true",
                    help="replay served traffic and report per-layer "
                         "quantization-kernel proportion (paper §4.1)")
    ap.add_argument("--mesh", default=None, metavar="DATA,MODEL[,EXPERT]",
                    help="serve sharded on a (data, model[, expert]) host mesh "
                         "(TP §3.7, expert-parallel MoE §3.13), e.g. "
                         "--mesh 4,2 or --mesh 2,2,2; needs XLA_FLAGS="
                         "--xla_force_host_platform_device_count=<product>")
    args = ap.parse_args()
    compile_cache.enable()

    cfg = get(args.arch, smoke=True)
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    quant = {"fp": ql.FP, "fake": ql.W8A8_CROSSQUANT, "int8": ql.W8A8_INT8}[args.quant]
    mesh = None
    if args.mesh:
        from repro.launch.mesh import parse_mesh_arg
        mesh = parse_mesh_arg(args.mesh)

    base = (EngineConfig.from_json(pathlib.Path(args.config).read_text())
            if args.config else None)
    defaults = dict(batch_size=4, max_len=48)
    if args.quant == "int8":
        defaults["path"] = "fused-int8"
    config = config_from_args(args, base=base, **defaults)
    prompt_lens = [int(x) for x in args.prompt_lens.split(",")]
    prompts, max_new = mixed_workload(cfg, args.n_requests, prompt_lens,
                                      shared_prefix=args.shared_prefix)

    if args.quant != "int8":
        # The int8 KV cache is independent of weight quantization and applies to
        # fp/fake serving too; only the integer backends need a prepared tree.
        if config.path in ("dequant-fp", "fused-int8"):
            print(f"note: path={config.path} needs --quant int8; serving on "
                  f"the ref backend instead")
            config = dataclasses.replace(config, path=None)
        serve_params = params
        done, _ = serve(cfg, params, prompts, max_new, config=config,
                        quant=quant, tag=args.quant, mesh=mesh)
    else:
        qparams = calibrate_and_quantize(cfg, params, quant)
        if config.sparsity != "none":
            # Prune up front with the same default plan the engine would build,
            # so the report below describes exactly the tree being served (the
            # engine's own sparsify_tree pass is idempotent on a masked tree).
            from repro.models import quantize as MQ
            qparams = MQ.sparsify_tree(
                qparams, MQ.SparsityPlan(nm=MQ.parse_nm(config.sparsity)))
            summ = MQ.sparsity_summary(qparams)
            kept = float(np.mean(list(summ.values()))) if summ else 1.0
            dense_b = quantized_bytes(qparams)
            deploy_b = quantized_bytes(qparams, deploy_sparse=True)
            print(f"sparsity {config.sparsity}: {len(summ)} linears pruned, "
                  f"kept fraction {kept:.2f}; weights "
                  f"{dense_b / 2**20:.2f} MiB dense-layout -> "
                  f"{deploy_b / 2**20:.2f} MiB in the N:M deploy format")
        serve_params = qparams
        done, int8_tps = serve(cfg, qparams, prompts, max_new, config=config,
                               quant=quant, mesh=mesh)
        if args.compare:
            fp_config = dataclasses.replace(config, path=None)
            _, fp_tps = serve(cfg, params, prompts, max_new, config=fp_config,
                              quant=ql.FP, tag="fp-baseline", mesh=mesh)
            print(f"end-to-end tokens/sec: fp={fp_tps:.1f} "
                  f"{config.path or 'ref'}={int8_tps:.1f} "
                  f"({int8_tps / fp_tps:.2f}x; "
                  "CPU-interpret numbers — the kernel-level TPU projection is in "
                  "benchmarks/qgemm_bench.py)")
    for r in done[:3]:
        print(f"  req {r.rid}: {r.prompt[:4].tolist()}... -> {r.out[:6]}")
    if args.quant_kernel_stats:
        report_kernel_stats(cfg, serve_params, quant, done,
                            chunk=config.token_budget if config.chunked else 0)


if __name__ == "__main__":
    main()
