"""Smoke run of the fused-int8 paged serving path on a TPU chip.

    python chip_smoke.py              # one chip: the main serving path, end to end
    python chip_smoke.py --chips 4    # four chips: sharded serving and replicas

One chip: granite-moe-3b-a800m at its published widths, random weights from
``--seed``, calibrated and quantized to the int8 tree on the chip, then served
through the normal entry points:

  A. ``AsyncServer(replicas=1)`` with ``EngineConfig(path="fused-int8",
     kv_cache="int8", cache_layout="paged")``: mixed prompt lengths, half of the
     requests sharing a prefix, so cold admission, warm admission and the
     copy-on-write page copy all run;
  B. a ``ServeEngine`` with the same config plus ``chunked=True``, so the ragged
     prefill kernel runs;
  C. prefill logits of the fused-int8 path against the dequant-fp path (XLA,
     no Pallas) on the same prepared tree — what catches a kernel that compiles
     but computes wrong numbers.

It fails when a request does not finish, when the server counts an error, a
restart or a requeue, when the compiled decode, admit or chunked programs hold
no Mosaic kernel (``tpu_custom_call``), or when the logits disagree past
``LOGITS_RTOL``, or when a kernel disagrees with its XLA oracle past
``KERNEL_RTOL``. Four chips: the same model on a (data=1, model=2, expert=2)
mesh against device 0 alone (prefill logits, then served streams with the
decode logits behind them), and ``AsyncServer(replicas=4)`` with each
replica's params and caches on its own device.

The numbers printed are from a smoke run, not a benchmark. The last line of
standard output is one JSON object naming the device; it is printed only when
every check passed. Without a TPU the script exits non-zero and serves nothing.
Everything runs in this one process.
"""

import argparse
import asyncio
import gc
import json
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

ARCH = "granite-moe-3b-a800m"
BATCH, MAX_LEN, MAX_NEW = 8, 512, 32
N_REQUESTS = 12
SHARED_PREFIX = 68          # 8.5 pages of 8: full-page reuse plus a page copy
TOKEN_BUDGET = 256
CALIB_SHAPE = (2, 64)       # calibration batch (rows, tokens)
PREFILL_SHAPE = (8, 128)    # logits-check batch (rows, tokens)
# ||fused - ref|| / ||ref|| over the logits of two correct paths. On random
# weights a quantized model amplifies their rounding differences (int8 code and
# expert-routing flips): fused-int8 against dequant-fp read 0.054 at full width
# on a v5e chip. Logits that share nothing give about 1.4.
LOGITS_RTOL = 0.15
# max |kernel - oracle| / max |oracle| for one kernel call at serving widths:
# the oracle is the same math in XLA at full f32 precision; the kernels take
# bf16 inputs and return bf16 (relative rounding 2^-8).
KERNEL_RTOL = 2e-2


def fail(msg: str) -> None:
    print(f"chip_smoke: FAIL: {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


def say(msg: str) -> None:
    print(msg, flush=True)


class CompileClock:
    """Sums the backend compile seconds JAX reports, per phase."""

    def __init__(self, jax):
        self.total = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.total += duration

    def since(self, mark: float) -> float:
        return self.total - mark


def workload(vocab: int, seed: int):
    """Mixed prompt lengths in [32, 256]; even requests share one prefix."""
    import numpy as np

    rng = np.random.default_rng(seed)
    shared = rng.integers(1, vocab, SHARED_PREFIX)
    lens = [96, 40, 140, 64, 200, 120, 100, 250, 180, 32, 230, 150]
    prompts = []
    for i, n in enumerate(lens[:N_REQUESTS]):
        own = rng.integers(1, vocab, n)
        if i % 2 == 0:
            own[:SHARED_PREFIX] = shared
        prompts.append(own.astype(np.int32))
    return prompts


def build(cfg, seed: int):
    """Random bf16 params on the chip, calibrated and quantized to int8."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import calibration, qlinear as ql
    from repro.models import model as M
    from repro.models.layers import QuantContext
    from repro.models.quantize import quantize_tree, quantized_bytes

    params = M.init_params_on_device(jax.random.PRNGKey(seed), cfg)
    fp_bytes = quantized_bytes(params)
    obs = calibration.Observer()
    rng = np.random.default_rng(seed + 1)
    toks = jnp.asarray(rng.integers(1, cfg.vocab, CALIB_SHAPE), jnp.int32)
    M.apply(params, {"tokens": toks}, cfg,
            ctx=QuantContext(ql.W8A8_INT8, observer=obs), mode="train", unroll=True)
    qparams = quantize_tree(params, ql.W8A8_INT8,
                            tables=calibration.stack_tables(obs.tables()))
    jax.block_until_ready(qparams)
    del params
    gc.collect()
    return qparams, fp_bytes, quantized_bytes(qparams)


def engine_config(**kw):
    from repro.serving.config import EngineConfig

    return EngineConfig(batch_size=BATCH, max_len=MAX_LEN, path="fused-int8",
                        kv_cache="int8", cache_layout="paged",
                        scheduler="continuous", **kw)


async def serve_async(cfg, qparams, config, prompts, *, replicas, inspect=None):
    """Serve every prompt through an AsyncServer; returns (token lists,
    finish reasons, metrics, what ``inspect(server)`` returned before close)."""
    from repro.core import qlinear as ql
    from repro.serving.api import Request
    from repro.serving.server import AsyncServer

    srv = AsyncServer(cfg, qparams, config=config, replicas=replicas,
                      quant=ql.W8A8_INT8, admission_timeout=600.0)
    await srv.start()
    srv.pause()        # queue everything first: admission order is then fixed

    async def one(p):
        toks, reason = [], None
        async for ev in srv.submit(Request(prompt=p.tolist(), max_new=MAX_NEW)):
            if ev.kind == "token":
                toks.append(ev.token)
            elif ev.kind == "finished":
                reason = ev.finish_reason
            else:
                raise RuntimeError(f"request failed: {ev.error}")
        return toks, reason

    tasks = [asyncio.ensure_future(one(p)) for p in prompts]
    while srv.counters["submitted"] < len(prompts):
        await asyncio.sleep(0.01)
    srv.resume()
    done = await asyncio.gather(*tasks)
    extra = inspect(srv) if inspect is not None else None
    metrics = srv.metrics()
    await srv.aclose()
    return [t for t, _ in done], [r for _, r in done], metrics, extra


def server_counters_ok(metrics, n):
    s = metrics["server"]
    bad = {k: s[k] for k in ("errors", "restarts", "requeued", "rejected") if s[k]}
    if bad or s["completed"] != n:
        fail(f"server counters: completed {s['completed']}/{n}, nonzero {bad}")


def check_finished(name, outs, reasons):
    short = [i for i, (o, r) in enumerate(zip(outs, reasons))
             if r is None or len(o) != MAX_NEW]
    if short:
        fail(f"{name}: requests {short} did not finish with {MAX_NEW} tokens")


def mosaic_in(jitted, *args) -> bool:
    """Whether the compiled program of ``jitted(*args)`` calls a Mosaic kernel."""
    return "tpu_custom_call" in jitted.lower(*args).compile().as_text()


def prefill_logits(cfg, qparams, path, tokens, *, mesh=None):
    """Last-position prefill logits of ``path`` on a fresh dense cache, over
    the real vocabulary (padded ids carry -1e9)."""
    import jax
    import jax.numpy as jnp

    from repro.core import qlinear as ql
    from repro.models import model as M
    from repro.serving import engine as E
    from repro.sharding import planner

    step = E.make_prefill_step(cfg, ql.W8A8_INT8, path=path)
    caches = M.init_cache(cfg, tokens.shape[0], tokens.shape[1],
                          dtype=jnp.dtype(cfg.dtype))
    batch = {"tokens": tokens}
    if mesh is None:
        logits, _ = jax.jit(step)(qparams, batch, caches)
        return logits[..., : cfg.vocab]
    plan = planner.make_serve_plan(cfg, mesh)
    param_sh, cache_sh, repl = E.shard_serving_state(qparams, caches, cfg, plan, mesh)
    fn = jax.jit(E._hinted(step, plan, mesh), in_shardings=(param_sh, repl, cache_sh),
                 out_shardings=(repl, cache_sh))
    logits, _ = fn(jax.device_put(qparams, param_sh), batch,
                   jax.device_put(caches, cache_sh))
    return logits[..., : cfg.vocab]


def compare_logits(name, got, ref):
    import numpy as np

    got = np.asarray(got, np.float32)
    ref = np.asarray(ref, np.float32)
    if not (np.isfinite(got).all() and np.isfinite(ref).all()):
        fail(f"{name}: non-finite logits")
    max_abs = float(np.max(np.abs(got - ref)))
    rel = float(np.linalg.norm(got - ref) / np.linalg.norm(ref))
    top1 = float(np.mean(got.argmax(-1) == ref.argmax(-1)))
    say(f"{name}: logits max-abs error {max_abs:.6g}, relative error {rel:.6g} "
        f"(tolerance {LOGITS_RTOL}), top-1 agreement {top1:.3f}")
    if rel > LOGITS_RTOL:
        fail(f"{name}: relative logits error {rel:.6g} > {LOGITS_RTOL}")


def compare_kernel(name, got, ref):
    import numpy as np

    got = np.asarray(got, np.float32)
    ref = np.asarray(ref, np.float32)
    if not np.isfinite(got).all():
        fail(f"{name}: non-finite output")
    max_abs = float(np.max(np.abs(got - ref)))
    rel = max_abs / float(np.max(np.abs(ref)))
    say(f"kernel {name}: max-abs error {max_abs:.6g}, relative {rel:.6g} "
        f"(tolerance {KERNEL_RTOL})")
    if rel > KERNEL_RTOL:
        fail(f"kernel {name}: relative error {rel:.6g} > {KERNEL_RTOL}")


def kernel_checks(cfg, seed):
    """The serving kernels on the chip against their XLA oracles
    (``repro.kernels.ref``): the fused-int8 pair (act_quantize, qgemm_w8a8) at
    the query projection of the logits-check batch, and the attention kernels
    at the model's head widths and the engine's page size, batch and chunked
    budget."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import ops, paged_layout, ref

    rng = np.random.default_rng(seed + 3)
    C = PREFILL_SHAPE[0] * PREFILL_SHAPE[1]
    K, N = cfg.d_model, cfg.n_heads * cfg.head_dim
    chan = np.where(rng.random(K) < 0.02, 20.0, 1.0)           # outlier channels
    x = jnp.asarray(rng.standard_normal((C, K)) * chan, jnp.bfloat16)
    c = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=0)
    for name, alpha in (("static alpha 0.15", None), ("traced alpha 0.3", 0.3)):
        al = 0.15 if alpha is None else alpha
        bcol = c ** (1 - al)
        qx, a = (ops.act_quantize(x, bcol, alpha=al) if alpha is None
                 else ops.act_quantize_dyn(x, bcol, jnp.float32(alpha)))
        want_q, want_a = ref.act_quantize_ref(x, bcol, alpha=al)
        compare_kernel(f"act_quantize row scales ({name})", a, want_a)
        compare_kernel(f"act_quantize dequantized ({name})",
                       qx.astype(jnp.float32) * a * bcol,
                       want_q.astype(jnp.float32) * want_a * bcol)
    # the traced-alpha codes and row scales feed the GEMM, as in serving
    qw = jnp.asarray(rng.integers(-127, 128, (K, N)), jnp.int8)
    sw = jnp.asarray(1e-3 * (1 + rng.random(N)), jnp.float32)
    compare_kernel(f"qgemm_w8a8 ({C}x{K} @ {K}x{N})",
                   ops.qgemm_w8a8(qx, qw, a, sw), ref.qgemm_w8a8_ref(qx, qw, a, sw))
    Hkv, D = cfg.n_kv_heads, cfg.head_dim
    G = cfg.n_heads // Hkv
    ps, maxP = 8, MAX_LEN // 8
    P = BATCH * maxP
    bf16 = lambda x: jnp.asarray(x, jnp.bfloat16)
    kp, vp = (jnp.asarray(rng.integers(-127, 128, (P, ps, Hkv, D)), jnp.int8)
              for _ in range(2))
    ks, vs = (jnp.asarray(0.002 + 0.05 * rng.random((P, ps, Hkv, 1)), jnp.float32)
              for _ in range(2))
    scales = dict(k_scale_pages=ks, v_scale_pages=vs)
    # the kernels take the pools as the engine stores them, the oracles the
    # logical view
    skp, svp, sks, svs = paged_layout.stored(kp, vp, ks, vs)
    stored = dict(k_scale_pages=sks, v_scale_pages=svs)
    # injective page tables; slot b holds kv_len[b] tokens
    kv_len = np.asarray([1, 7, 8, 9, 100, 255, 300, MAX_LEN], np.int32)
    table = np.full((BATCH, maxP), P, np.int32)
    perm = rng.permutation(P)
    for b in range(BATCH):
        n = -(-int(kv_len[b]) // ps)
        table[b, :n] = perm[b * maxP: b * maxP + n]
    table, kvl = jnp.asarray(table), jnp.asarray(kv_len)
    with jax.default_matmul_precision("highest"):
        q = bf16(rng.standard_normal((BATCH, 1, Hkv * G, D)))
        compare_kernel(
            "paged decode (int8 KV)",
            ops.paged_decode_attention(q, skp, svp, table, kvl, **stored),
            ref.paged_decode_attention_ref(q.reshape(BATCH, Hkv, G, D), kp, vp,
                                           table, kvl, **scales)
            .reshape(BATCH, 1, Hkv * G, D))
        W = 4
        qln = jnp.asarray(np.minimum(kv_len, [1, 2, 3, 4, 4, 4, 2, 4]), jnp.int32)
        q = bf16(rng.standard_normal((BATCH, W, Hkv * G, D)))
        got = ops.paged_verify_attention(q, skp, svp, table, kvl, qln, **stored)
        want = jnp.transpose(ref.paged_verify_attention_ref(
            jnp.transpose(q.reshape(BATCH, W, Hkv, G, D), (0, 2, 1, 3, 4)),
            kp, vp, table, kvl, qln, **scales), (0, 2, 1, 3, 4))
        rows = np.arange(W)[None] < np.asarray(qln)[:, None]     # valid rows
        compare_kernel("paged verify (int8 KV, window 4)",
                       np.asarray(got, np.float32)[rows],
                       np.asarray(want.reshape(BATCH, W, -1, D), np.float32)[rows])
        q_len = np.asarray([1, 1, 1, 1, 60, 100, 40, 50], np.int32)
        q_len = np.minimum(q_len, kv_len)
        q_start = np.concatenate([[0], np.cumsum(q_len)[:-1]]).astype(np.int32)
        Nt = TOKEN_BUDGET
        q = bf16(rng.standard_normal((Nt, Hkv * G, D)))
        kn, vn = (bf16(rng.standard_normal((Nt, Hkv, D))) for _ in range(2))
        rest = (table, jnp.asarray(q_start), jnp.asarray(q_len), kvl)
        got = ops.ragged_prefill_attention(q, kn, vn, skp, svp, *rest,
                                           chunk_cap=Nt, **stored)
        want = ref.ragged_prefill_attention_ref(q.reshape(Nt, Hkv, G, D), kn, vn,
                                                kp, vp, *rest, chunk_cap=Nt,
                                                **scales)
        n = int(q_len.sum())
        compare_kernel("ragged prefill (int8 KV, budget 256)",
                       np.asarray(got, np.float32)[:n],
                       np.asarray(want.reshape(Nt, -1, D), np.float32)[:n])


def one_chip(cfg, seed, clock):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import qlinear as ql
    from repro.serving.engine import ServeEngine

    t0, c0 = time.perf_counter(), clock.total
    qparams, fp_bytes, q_bytes = build(cfg, seed)
    say(f"build: params fp {fp_bytes} bytes -> int8 tree {q_bytes} bytes; "
        f"set-up {time.perf_counter() - t0:.3f} s (compile {clock.since(c0):.3f} s)")
    prompts = workload(cfg.vocab, seed)
    n_tok = len(prompts) * MAX_NEW

    # ---- A: AsyncServer, one replica
    def inspect(srv):
        eng = srv.replicas[0].engine
        cur = jnp.asarray(eng._pos + 1, jnp.int32)
        decode = mosaic_in(eng._decode_step, eng.params, jnp.asarray(eng._pending),
                           eng.caches, cur, eng._next_key())
        # the first admission: the six prompts of the 256 bucket, in 8 rows
        rows, bucket = BATCH, 256
        admit_args = (eng.params, jnp.zeros((rows, bucket), jnp.int32),
                      jnp.ones(rows, jnp.int32), jnp.zeros(rows, jnp.int32),
                      jnp.full((rows, eng.maxP), eng.n_pages, jnp.int32),
                      jnp.full(rows, eng.n_pages, jnp.int32), eng.caches,
                      eng._next_key())
        return dict(decode=decode, admit=mosaic_in(eng._admit_cold, *admit_args),
                    counters=dict(eng.counters))

    t0, c0 = time.perf_counter(), clock.total
    outs_a, reasons, metrics, seen = asyncio.run(
        serve_async(cfg, qparams, engine_config(), prompts, replicas=1,
                    inspect=inspect))
    wall = time.perf_counter() - t0
    check_finished("phase A", outs_a, reasons)
    server_counters_ok(metrics, len(prompts))
    ctr = seen["counters"]
    say(f"phase A (AsyncServer, smoke run, not a benchmark): {len(prompts)} requests, "
        f"{n_tok} tokens, wall {wall:.3f} s (compile {clock.since(c0):.3f} s); "
        f"prefix hits {ctr['prefix_hits']}, page copies {ctr['cow_copies']}, "
        f"admissions {ctr['prefill_calls']}")
    say(f"phase A: server counters {json.dumps(metrics['server'])}")
    say(f"phase A: tpu_custom_call in decode {seen['decode']}, admit {seen['admit']}")
    if not (seen["decode"] and seen["admit"]):
        fail("the decode or admit program holds no Mosaic kernel")
    if ctr["prefix_hits"] == 0 or ctr["cow_copies"] == 0:
        fail("warm admission or the page copy did not run")

    # ---- B: ServeEngine, chunked prefill
    t0, c0 = time.perf_counter(), clock.total
    eng = ServeEngine(cfg, qparams, config=engine_config(chunked=True,
                                                         token_budget=TOKEN_BUDGET),
                      quant=ql.W8A8_INT8)
    eng.submit(prompts, max_new=MAX_NEW)
    done = eng.run()
    wall = time.perf_counter() - t0
    check_finished("phase B", [r.out for r in done], [r.finish_reason for r in done])
    B, Nt = eng.B, eng.token_budget
    chunk = mosaic_in(eng._chunk_step, eng.params, jnp.zeros((1, Nt), jnp.int32),
                      jnp.zeros(B, jnp.int32), jnp.ones(B, jnp.int32),
                      jnp.ones(B, jnp.int32), jnp.zeros(Nt, jnp.int32),
                      jnp.full(Nt, B, jnp.int32), eng.caches, eng._next_key())
    say(f"phase B (chunked ServeEngine, smoke run, not a benchmark): {len(done)} "
        f"requests, {sum(len(r.out) for r in done)} tokens, wall {wall:.3f} s "
        f"(compile {clock.since(c0):.3f} s); chunked steps "
        f"{eng.counters['chunk_steps']}; tpu_custom_call in chunked step {chunk}")
    if not chunk or eng.counters["chunk_steps"] == 0:
        fail("the chunked step did not run a Mosaic kernel")
    del eng, done
    gc.collect()

    # ---- C: fused-int8 against dequant-fp logits; kernels against oracles
    t0, c0 = time.perf_counter(), clock.total
    rng = np.random.default_rng(seed + 2)
    toks = jnp.asarray(rng.integers(1, cfg.vocab, PREFILL_SHAPE), jnp.int32)
    fused = prefill_logits(cfg, qparams, "fused-int8", toks)
    with jax.default_matmul_precision("highest"):
        ref = prefill_logits(cfg, qparams, "dequant-fp", toks)
    compare_logits("phase C fused-int8 vs dequant-fp", fused, ref)
    kernel_checks(cfg, seed)
    say(f"phase C: wall {time.perf_counter() - t0:.3f} s "
        f"(compile {clock.since(c0):.3f} s)")


def record_decode_logits(eng, cfg, *, mesh=None):
    """Before each of ``eng``'s decode steps, run a side program on the same
    inputs (caches not donated) that returns the step's logits over the real
    vocabulary, and file each live slot's row under (request id, index of the
    token the step emits). The served stream is untouched. Returns that dict
    and a list that receives the host copies of the caches before and after
    the first decode step."""
    import jax
    import numpy as np

    from repro.core import qlinear as ql
    from repro.models import model as M
    from repro.serving import engine as E

    ctx = E._make_ctx(cfg, ql.W8A8_INT8, "fused-int8")

    def logits(params, tokens, caches, cur_len):
        lg, _ = M.apply(params, {"tokens": tokens[:, None]}, cfg, ctx=ctx,
                        mode="decode", caches=caches, cur_len=cur_len)
        return lg[:, -1, : cfg.vocab]

    if mesh is None:
        side = jax.jit(logits)
    else:
        sh = lambda tree: jax.tree_util.tree_map(lambda x: x.sharding, tree)
        repl = eng._repl_sh
        side = jax.jit(E._hinted(logits, eng.plan, mesh),
                       in_shardings=(sh(eng.params), repl, sh(eng.caches), repl),
                       out_shardings=repl)
    rows, snaps, real = {}, [], eng._decode_step
    # a copy: the step donates its caches, and a CPU array may share them
    host = lambda tree: jax.tree_util.tree_map(lambda x: np.array(x, copy=True), tree)

    def step(params, tokens, caches, cur_len, key):
        lg = np.asarray(side(params, tokens, caches, cur_len))
        for i, r in enumerate(eng._slots):
            if r is not None:
                rows[(r.rid, len(r.out))] = lg[i]
        if snaps:
            return real(params, tokens, caches, cur_len, key)
        snaps.append(host(caches))
        out = real(params, tokens, caches, cur_len, key)
        snaps.append(host(out[1]))
        return out

    eng._decode_step = step
    return rows, snaps


def compare_caches(ref_snaps, snaps):
    """Per cache leaf, the slices of its leading axis (layers; page-table rows)
    that differ between two engines, after admission and after the first
    decode step. For a page pool after the step, the first differing layer's
    differing token rows are also matched against the rows the step wrote."""
    import jax
    import numpy as np

    leaves = jax.tree_util.tree_leaves
    (pre, post), (other_pre, other_post) = ref_snaps, snaps
    for when, a, b in (("after admission", pre, other_pre),
                       ("after the first decode step", post, other_post)):
        diffs = []
        for (path, x), y, x0 in zip(jax.tree_util.tree_flatten_with_path(a)[0],
                                    leaves(b), leaves(pre)):
            bad = [l for l in range(x.shape[0]) if not np.array_equal(x[l], y[l])]
            line = f"{jax.tree_util.keystr(path)} {len(bad)}/{x.shape[0]}"
            if bad and a is post and x.ndim >= 4:        # (layer, page, row, ...)
                rows = lambda u, v: np.any(u[bad[0]] != v[bad[0]],
                                           axis=tuple(range(2, x.ndim - 1)))
                d, w = rows(x, y), rows(x, x0)
                line += (f" (first {bad[0]}: {int(d.sum())} token rows differ, "
                         f"{int((d & w).sum())} of them written by the step)")
            elif bad:
                line += f" (first {bad[0]})"
            diffs.append(line)
        say(f"sharded vs device 0 caches {when}, slices of the leading axis "
            f"(layer; page-table row) that differ: " + "; ".join(diffs))


def bf16_ulp(x: float) -> float:
    """Spacing of bfloat16 values at magnitude ``x`` (8 significant bits)."""
    import math

    return 2.0 ** (math.floor(math.log2(max(abs(x), 1e-30))) - 7)


def compare_streams(ref_run, run):
    """Token streams of sharded serving against device 0 alone, with the decode
    logits behind them. Every first token must agree. A later divergence is
    accepted only where it is rounding: over all steps whose histories still
    agree, the decode logits differ by at most ``LOGITS_RTOL`` (relative), and
    at the step where a stream first differs, device 0's top-2 margin is no
    more than twice that step's largest logit difference, so the measured
    difference alone can swap the top two. Returns the fault, or None."""
    import numpy as np

    (rids, ref_outs, ref_rows), (_, outs, rows) = ref_run, run
    n = len(rids)
    first = sum(a[0] == b[0] for a, b in zip(ref_outs, outs))
    whole = sum(a == b for a, b in zip(ref_outs, outs))
    say(f"sharded vs device 0 tokens: first token equal {first}/{n}, "
        f"whole stream equal {whole}/{n}")
    if first != n:
        return "sharded serving disagrees with device 0 on a first token"
    served = [sum(int(np.argmax(v)) == dict(zip(rids, os))[rid][j]
                  for (rid, j), v in rw.items())
              for rw, os in ((ref_rows, ref_outs), (rows, outs))]
    say(f"decode logits side program: argmax equals the served token on "
        f"{served[0]}/{len(ref_rows)} device 0 rows, {served[1]}/{len(rows)} "
        f"sharded rows")
    rels, n_equal, first_unequal, faults = [], 0, None, []
    for rid, a, b in zip(rids, ref_outs, outs):
        t = next((j for j, (x, y) in enumerate(zip(a, b)) if x != y), len(a))
        for j in range(1, min(t + 1, len(a))):
            l0, l1 = ref_rows[(rid, j)], rows[(rid, j)]
            d = l1 - l0
            rels.append(float(np.linalg.norm(d) / np.linalg.norm(l0)))
            if not d.any():
                n_equal += 1
            elif first_unequal is None or j < first_unequal:
                first_unequal = j
            if j == t:
                top0, top1 = np.argsort(l0)[-2:][::-1], np.argsort(l1)[-2:][::-1]
                margin = float(l0[top0[0]] - l0[top0[1]])
                diff = float(np.max(np.abs(d)))
                ulp = bf16_ulp(float(l0[top0[0]]))
                say(f"request {rid} first differs at token {t}: device 0 top-2 "
                    f"{top0[0]}:{l0[top0[0]]:.6g} {top0[1]}:{l0[top0[1]]:.6g} "
                    f"(margin {margin:.6g} = {margin / ulp:.3g} bf16 ulp), sharded "
                    f"top-2 {top1[0]}:{l1[top1[0]]:.6g} {top1[1]}:{l1[top1[1]]:.6g}; "
                    f"max-abs logits difference {diff:.6g} "
                    f"({diff / ulp:.3g} ulp)")
                if margin > 2 * diff:
                    faults.append(f"request {rid} token {t}: margin {margin:.6g} "
                                  f"> 2 x difference {diff:.6g}")
    say(f"sharded vs device 0 decode logits over {len(rels)} slot-steps with "
        f"equal histories: bitwise equal {n_equal}, first unequal at token "
        f"{first_unequal}, relative error max {max(rels):.6g} "
        f"median {float(np.median(rels)):.6g} (tolerance {LOGITS_RTOL})")
    if max(rels) > LOGITS_RTOL:
        faults.append(f"decode logits relative error {max(rels):.6g} > {LOGITS_RTOL}")
    return "; ".join(faults) or None


def four_chips(cfg, seed, clock):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import qlinear as ql
    from repro.launch.mesh import make_debug_mesh
    from repro.serving.engine import ServeEngine

    devs = jax.devices()
    if len(devs) < 4:
        fail(f"--chips 4 needs four devices, JAX found {len(devs)}")
    t0 = time.perf_counter()
    qparams, fp_bytes, q_bytes = build(cfg, seed)
    say(f"build: params fp {fp_bytes} bytes -> int8 tree {q_bytes} bytes; "
        f"set-up {time.perf_counter() - t0:.3f} s")
    prompts = workload(cfg.vocab, seed)[:BATCH]

    # ---- sharded (data=1, model=2, expert=2) against device 0 alone
    mesh = make_debug_mesh(data=1, model=2, expert=2)
    rng = np.random.default_rng(seed + 2)
    toks = jnp.asarray(rng.integers(1, cfg.vocab, PREFILL_SHAPE), jnp.int32)
    alone = prefill_logits(cfg, qparams, "fused-int8", toks)
    sharded = prefill_logits(cfg, qparams, "fused-int8", toks, mesh=mesh)
    compare_logits("sharded (1,2,2) vs device 0", sharded, alone)

    runs, snaps = {}, {}
    for name, kw in (("device 0", {}), ("sharded", dict(mesh=mesh))):
        eng = ServeEngine(cfg, qparams, config=engine_config(), quant=ql.W8A8_INT8,
                          **kw)
        if kw:
            cur = jnp.asarray(eng._pos + 1, jnp.int32)
            text = eng._decode_step.lower(
                eng.params, jnp.asarray(eng._pending), eng.caches, cur,
                eng._next_key()).compile().as_text()
            n_ag = sum(1 for ln in text.splitlines()
                       if " all-gather(" in ln or " all-gather-start(" in ln)
            say(f"sharded decode program: {n_ag} all-gathers, tpu_custom_call "
                f"{'tpu_custom_call' in text}")
        rows, snaps[name] = record_decode_logits(eng, cfg, mesh=kw.get("mesh"))
        eng.submit(prompts, max_new=MAX_NEW)
        done = eng.run()
        check_finished(name, [r.out for r in done], [r.finish_reason for r in done])
        runs[name] = ([r.rid for r in done], [list(r.out) for r in done], rows)
        del eng, done
        gc.collect()
    stream_fault = compare_streams(runs["device 0"], runs["sharded"])
    compare_caches(snaps["device 0"], snaps["sharded"])

    # ---- four replicas behind the router, one per device
    def placement(srv):
        rows = []
        for i, r in enumerate(srv.replicas):
            p = {d for x in jax.tree_util.tree_leaves(r.engine.params)
                 for d in x.devices()}
            c = {d for x in jax.tree_util.tree_leaves(r.engine.caches)
                 for d in x.devices()}
            rows.append((i, p, c))
        return rows

    outs, reasons, metrics, rows = asyncio.run(
        serve_async(cfg, qparams, engine_config(), workload(cfg.vocab, seed),
                    replicas=4, inspect=placement))
    check_finished("replicas=4", outs, reasons)
    server_counters_ok(metrics, N_REQUESTS)
    for i, p, c in rows:
        say(f"replica {i}: params on {sorted(str(d) for d in p)}, "
            f"caches on {sorted(str(d) for d in c)}")
        if p != {devs[i]} or c != {devs[i]}:
            fail(f"replica {i} is not on device {devs[i]}")
    served = [r["engine"]["prompt_tokens"] if r.get("engine") else None
              for r in metrics["replicas"]]
    say(f"replicas=4: {N_REQUESTS} requests finished; prompt tokens per replica "
        f"{served}")
    if stream_fault:
        fail(stream_fault)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    args = ap.parse_args()
    try:
        from repro.launch import compile_cache
    except ImportError as e:
        fail(f"the repository's src/ is not next to this script ({e})")
    compile_cache.enable()
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        fail(f"no TPU: JAX found only {devs[0].platform} devices; this smoke run "
             "does not fall back to another platform")
    from repro.configs import get

    cfg = get(ARCH)
    say(f"device_kind {devs[0].device_kind}, {len(devs)} device(s), "
        f"{ARCH} at published widths ({cfg.n_layers} layers, d_model {cfg.d_model})")
    clock = CompileClock(jax)
    (four_chips if args.chips == 4 else one_chip)(cfg, args.seed, clock)
    for d in devs[: args.chips]:
        stats = d.memory_stats() or {}
        say(f"{d}: peak_bytes_in_use {stats.get('peak_bytes_in_use')}")
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}), flush=True)


if __name__ == "__main__":
    main()
