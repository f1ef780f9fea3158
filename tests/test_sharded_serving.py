"""TP-sharded serving (DESIGN.md §3.7): token-exact parity vs single-device.

One subprocess with a forced 8-device CPU host platform serves a mixed-length
continuous-batching workload through ``ServeEngine(mesh=...)`` at tp=2 (tier
tp_full for the smoke config) and tp=4 (tier tp_kv_rep: 4 q heads divide, 2 kv
heads degrade to replication) across the full path × KV-cache matrix —
fake / dequant-fp / fused-int8 × fp / int8 — and asserts the emitted tokens are
identical to the single-device engine, per request. A 2:4-sparsified tree
(DESIGN.md §3.12) then serves fused-int8 at tp=2: the packed mask leaves shard
alongside their qw and the sparse tokens must equal single-device sparse. The same matrix then runs
the paged cache layout (DESIGN.md §3.8) at tp=2 on a shared-prefix workload:
paged@tp2 with radix prefix hits must equal dense single-device, token-exact.
One speculative case (DESIGN.md §3.9) then serves speculate=4 draft windows
through the sharded paged fused-int8 path and must equal single-device
non-speculative decode. Expert-parallel MoE serving (DESIGN.md §3.13) then
serves granite-moe / llama4-scout fused-int8 under an ``expert`` mesh axis —
pure ep=2 and composed tp2×ep2 — where the stacked ``(E, ...)`` expert trees
shard over whole experts, the router stays replicated, and the emitted tokens
must equal single-device. The same subprocess pins the row-parallel
int32-accumulator ordering (qlinear ref path bitwise vs single-device: the
cross-shard reduction must happen on integer values before the f32 dequant
multiply — hints.constrain_gemm_acc).

The CI ``sharded-serving`` job runs this file; it also runs under tier-1 by
default (the top-level pytest process stays on the real single CPU device —
only the subprocess forces 8). The tier-1 CI matrix sets
``REPRO_SKIP_SHARDED=1`` to skip it there: the dedicated job already runs it,
and the ~2-minute 8-device subprocess × the python-version matrix buys no extra
coverage.
"""
import os
import subprocess
import sys
import textwrap

import pytest


CODE = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    os.environ["JAX_PLATFORMS"] = "cpu"
    import dataclasses
    import jax, numpy as np
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.configs import get
    from repro.core import qlinear as ql
    from repro.models import model as M
    from repro.models.quantize import quantize_tree
    from repro.serving import engine as E
    from repro.sharding import hints
    from repro.launch.mesh import make_debug_mesh

    cfg = dataclasses.replace(get("starcoder2-7b", smoke=True), dtype="float32")
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    qparams = quantize_tree(params, ql.W8A8_INT8)
    rng = np.random.default_rng(0)
    LENS = [4, 7, 12, 9]
    MAX_NEW = [4, 3, 5, 2]
    prompts = [rng.integers(1, cfg.vocab, size=n).astype(np.int32) for n in LENS]
    COMBOS = [("fake", "fp"), ("fake", "int8"),
              ("dequant-fp", "fp"), ("dequant-fp", "int8"),
              ("fused-int8", "fp"), ("fused-int8", "int8")]

    def serve(mesh, path, kv):
        p, quant = ((params, ql.W8A8_CROSSQUANT) if path == "fake"
                    else (qparams, ql.W8A8_INT8))
        eng = E.ServeEngine(cfg, p, batch_size=2, max_len=32, quant=quant,
                            path=path, kv_cache=kv, mesh=mesh)
        eng.submit([x.copy() for x in prompts], max_new=list(MAX_NEW))
        done = eng.run()
        assert eng.counters["mid_decode_admissions"] > 0   # 4 requests, 2 slots
        return {r.rid: r.out for r in done}

    fails = []
    base = {c: serve(None, *c) for c in COMBOS}
    for tp in (2, 4):
        mesh = make_debug_mesh(8 // tp, tp)
        for c in COMBOS:
            got = serve(mesh, *c)
            ok = got == base[c]
            print(f"tp={tp} path={c[0]} kv={c[1]}: "
                  f"{'OK' if ok else 'MISMATCH ' + repr((got, base[c]))}",
                  flush=True)
            if not ok:
                fails.append((tp, c))

    # N:M structured sparsity (DESIGN.md §3.12) at tp=2: the packed mask leaves
    # shard like their qw (column-parallel masks split d_out; row-parallel masks
    # split the packed axis at byte granularity), and the sparse fused-int8
    # engine must emit exactly the single-device sparse tokens.
    from repro.models import quantize as MQ
    sparams = MQ.sparsify_tree(qparams, MQ.SparsityPlan(nm=(2, 4)))

    def serve_sparse(mesh):
        eng = E.ServeEngine(cfg, sparams, batch_size=2, max_len=32,
                            quant=ql.W8A8_INT8, path="fused-int8",
                            kv_cache="int8", mesh=mesh)
        eng.submit([x.copy() for x in prompts], max_new=list(MAX_NEW))
        return {r.rid: r.out for r in eng.run()}

    sp_base = serve_sparse(None)
    sp_got = serve_sparse(make_debug_mesh(4, 2))
    ok = sp_got == sp_base
    print(f"sparse 2:4 tp=2 fused-int8/int8: "
          f"{'OK' if ok else 'MISMATCH ' + repr((sp_got, sp_base))}", flush=True)
    if not ok:
        fails.append(("sparse-tp2",))

    # Paged layout (DESIGN.md §3.8) at tp=2: the page pool + radix prefix reuse
    # must emit exactly the single-device *dense* tokens on a workload with
    # shared-prefix admissions (warm suffix prefill, page-table-routed decode,
    # pool sharded kv-heads-over-model / pages-over-data).
    sharedp = rng.integers(1, cfg.vocab, size=16).astype(np.int32)
    pprompts = prompts[:2] + [
        np.concatenate([sharedp,
                        rng.integers(1, cfg.vocab, size=4 + i).astype(np.int32)])
        for i in range(2)]
    PMAX_NEW = [4, 3, 5, 4]

    def serve_paged(mesh, path, kv, layout):
        p, quant = ((params, ql.W8A8_CROSSQUANT) if path == "fake"
                    else (qparams, ql.W8A8_INT8))
        eng = E.ServeEngine(cfg, p, batch_size=2, max_len=32, quant=quant,
                            path=path, kv_cache=kv, mesh=mesh,
                            cache_layout=layout, page_size=8)
        eng.submit([x.copy() for x in pprompts], max_new=list(PMAX_NEW))
        done = eng.run()
        return {r.rid: r.out for r in done}, eng

    mesh2 = make_debug_mesh(4, 2)
    for c in COMBOS:
        dense_base, _ = serve_paged(None, *c, "dense")
        got, eng = serve_paged(mesh2, *c, "paged")
        ok = got == dense_base and eng.counters["prefix_hits"] > 0
        print(f"paged tp=2 path={c[0]} kv={c[1]} "
              f"hits={eng.counters['prefix_hits']}: "
              f"{'OK' if ok else 'MISMATCH ' + repr((got, dense_base))}",
              flush=True)
        if not ok:
            fails.append(("paged", c))

    # Speculative decoding (DESIGN.md §3.9) at tp=2 through the paged int8
    # path: speculate=4 draft windows verified by the sharded multi-token
    # kernel must emit exactly the single-device non-speculative tokens. One
    # case — the headline fused-int8 + int8-KV combo; the full speculative
    # matrix runs single-device in tier-1 (tests/test_speculative.py).
    motif = rng.integers(1, cfg.vocab, size=4).astype(np.int32)
    sprompts = [np.tile(motif, 3), pprompts[1], np.tile(motif[:3], 2)]
    # budgets long enough for the greedy streams to settle into the repeated
    # continuations the prompt-lookup drafter can ride — short budgets decode
    # the whole workload before any draft is accepted, and the acceptance
    # assertion below would then vacuously test nothing but overhead
    SMAX_NEW = [16, 12, 20]

    def serve_spec(mesh, speculate):
        eng = E.ServeEngine(cfg, qparams, batch_size=2, max_len=32,
                            quant=ql.W8A8_INT8, path="fused-int8",
                            kv_cache="int8", mesh=mesh, cache_layout="paged",
                            page_size=8, speculate=speculate)
        eng.submit([x.copy() for x in sprompts], max_new=list(SMAX_NEW))
        done = eng.run()
        return {r.rid: r.out for r in done}, eng

    spec_base, _ = serve_spec(None, 1)
    spec_got, eng = serve_spec(mesh2, 4)
    ok = spec_got == spec_base and eng.counters["spec_accepted"] > 0
    print(f"spec tp=2 fused-int8/int8 paged accept={eng.stats().accept_rate:.2f}: "
          f"{'OK' if ok else 'MISMATCH ' + repr((spec_got, spec_base))}",
          flush=True)
    if not ok:
        fails.append(("speculative-tp2",))

    # Chunked mixed-budget scheduling (DESIGN.md §3.10) at tp=2: the packed
    # ragged launch (decode rows + prefill chunks in one forward) under a
    # TP-sharded plan must emit exactly the single-device *unchunked* paged
    # tokens. token_budget=10 forces multi-chunk prompts on this workload.
    def serve_chunked(mesh, **kw):
        eng = E.ServeEngine(cfg, qparams, batch_size=2, max_len=32,
                            quant=ql.W8A8_INT8, path="dequant-fp",
                            kv_cache="fp", mesh=mesh, cache_layout="paged",
                            page_size=8, **kw)
        eng.submit([x.copy() for x in pprompts], max_new=list(PMAX_NEW))
        done = eng.run()
        return {r.rid: r.out for r in done}, eng

    chunk_base, _ = serve_chunked(None)
    chunk_got, eng = serve_chunked(mesh2, chunked=True, token_budget=10)
    ok = chunk_got == chunk_base and eng.counters["chunk_prefill_rows"] > 0
    print(f"chunked tp=2 dequant-fp/fp paged "
          f"chunk_steps={eng.counters['chunk_steps']}: "
          f"{'OK' if ok else 'MISMATCH ' + repr((chunk_got, chunk_base))}",
          flush=True)
    if not ok:
        fails.append(("chunked-tp2",))

    # Expert-parallel MoE serving (DESIGN.md §3.13): a mesh with an "expert"
    # axis shards the stacked (E, ...) int8 expert trees over whole experts
    # (planner moe_mode "expert_axis") with the router replicated, so every
    # expert's int32 GEMM stays shard-local and EP fused-int8 serving is
    # token-exact vs single-device — at pure ep=2 and composed tp=2 x ep=2.
    for moe_name in ("granite-moe-3b-a800m", "llama4-scout-17b-a16e"):
        mcfg = dataclasses.replace(get(moe_name, smoke=True), dtype="float32")
        mparams = M.init_params(jax.random.PRNGKey(1), mcfg)
        mq = quantize_tree(mparams, ql.W8A8_INT8)
        mprompts = [rng.integers(1, mcfg.vocab, size=n).astype(np.int32)
                    for n in LENS]

        def serve_moe(mesh):
            eng = E.ServeEngine(mcfg, mq, batch_size=2, max_len=32,
                                quant=ql.W8A8_INT8, path="fused-int8",
                                kv_cache="int8", mesh=mesh)
            if mesh is not None:
                assert eng.plan.moe_mode == "expert_axis", eng.plan
                assert eng.plan.ep == 2
            eng.submit([x.copy() for x in mprompts], max_new=list(MAX_NEW))
            done = eng.run()
            assert eng.counters["mid_decode_admissions"] > 0
            return {r.rid: r.out for r in done}

        moe_base = serve_moe(None)
        for tag, mesh in (("ep2", make_debug_mesh(4, 1, 2)),
                          ("tp2xep2", make_debug_mesh(2, 2, 2))):
            got = serve_moe(mesh)
            ok = got == moe_base
            print(f"moe {moe_name} {tag} fused-int8/int8: "
                  f"{'OK' if ok else 'MISMATCH ' + repr((got, moe_base))}",
                  flush=True)
            if not ok:
                fails.append(("moe", moe_name, tag))

    # row-parallel int32-accumulator ordering (ref backend, bitwise)
    mesh = make_debug_mesh(4, 2)
    node = jax.tree_util.tree_map(lambda a: a[0], qparams["blocks"][0])["mlp"]["down"]
    x = jnp.asarray(rng.standard_normal((16, node["qw"].shape[0])), jnp.float32)
    repl = NamedSharding(mesh, P())
    sh = {"qw": NamedSharding(mesh, P("model", None)), "sw": repl,
          "bcol": NamedSharding(mesh, P("model")), "qalpha": repl}

    def row_parallel(p, x):
        with hints.sharding_hints(dp_axes=("data",), tp_axis="model", mesh=mesh):
            return ql.apply(p, x, ql.W8A8_INT8, int_exec="ref")

    y_sharded = jax.jit(row_parallel, in_shardings=(sh, repl),
                        out_shardings=repl)(jax.device_put(node, sh), x)
    y_single = jax.jit(
        lambda p, x: ql.apply(p, x, ql.W8A8_INT8, int_exec="ref"))(node, x)
    bitwise = bool((np.asarray(y_sharded) == np.asarray(y_single)).all())
    print(f"row-parallel ref int8 bitwise: {bitwise}", flush=True)
    if not bitwise:
        fails.append(("row-parallel-bitwise",))

    print("FAILURES: " + repr(fails) if fails else "ALL-PARITY-OK", flush=True)
""")


@pytest.mark.skipif(os.environ.get("REPRO_SKIP_SHARDED") == "1",
                    reason="sharded-serving CI job runs this file")
def test_sharded_serving_matrix_subprocess():
    src = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))
    r = subprocess.run([sys.executable, "-c", CODE], capture_output=True,
                       text=True, timeout=1800,
                       env={**os.environ, "PYTHONPATH": src})
    assert "ALL-PARITY-OK" in r.stdout, (r.stdout[-2000:], r.stderr[-3000:])
