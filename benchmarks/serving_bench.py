"""Continuous-batching serving benchmark (DESIGN.md §3.6 / §7).

Serves one *mixed-length* workload (three prompt lengths, staggered ``max_new`` —
the realistic occupancy case that equal-length grouping cannot batch well) through
both schedulers of ``serving/engine.py``:

* ``grouped``    — the pre-§3.6 baseline: equal-exact-length groups, each drained
                   to completion before the next starts.
* ``continuous`` — slot-table batcher: length-bucketed padded prefill into free
                   slots, retirement + refill mid-decode, per-slot ``cur_len``.

Reported per (path × scheduler): tokens/sec, slot occupancy (active-slot decode
steps / total decode-step slots) and mid-decode refill count. CPU wall-clock —
the structural win is occupancy; the kernel-level TPU projection lives in
``qgemm_bench``. Paths: fp baseline and the fused int8 kernels (+ int8 KV cache
in the full pass). Every tok/s figure is the best of ``TIMED_PASSES``
interleaved serves (grouped/continuous, and dense/paged in the prefix section,
alternate passes) — the gated comparisons are ratios between rows, and on a
shared runner a single ~1 s serve is hostage to whichever interference window
it lands in.

On hosts exposing ≥ 2 devices (the CI ``sharded-serving`` job forces 8 via
``XLA_FLAGS=--xla_force_host_platform_device_count=8``) every variant also runs
TP-sharded through a ``(n_dev/2, 2)`` host mesh (DESIGN.md §3.7), reported with
an ``@tp2`` path suffix — wall-clock is dominated by host-mesh collective
emulation, so these lines measure *that the sharded path serves*, not speedup.

A second section serves a **shared-system-prompt** workload (one common prefix,
per-request suffixes — the fleet-traffic shape) through the dense layout and
the paged pool + radix prefix index (DESIGN.md §3.8), measuring what paging
buys beyond scheduling: prefix hit rate (prompt tokens mapped copy-free from
cached pages / total prompt tokens), prefill tokens actually computed vs
saved, and the peak page footprint against the dense-equivalent capacity —
``capacity_x = dense_pages / peak_pages`` is how many times more concurrent
sequences the same HBM could hold at the observed sharing. Off-TPU this
section (and the latency section — every section except the speculative one)
runs with ``REPRO_KERNEL_EXEC=ref`` (kernels/ops.py): the paged rows measure
the XLA reference execution of the paged kernels, not the
Pallas interpret emulation whose overhead is a property of the emulator —
that dispatch is what holds the fp paged/dense tok/s ratio at the regress.py
floor (≥ 0.90). A third ``chunked`` row serves the same paged pool under the
token-budget scheduler (DESIGN.md §3.10), reported informationally: on an
overhead-bound CPU host the mixed ragged steps trade some throughput for the
bounded per-step latency the latency section gates.

A third section serves a **repetition-heavy** workload (tiled prompt motifs —
the templated/code traffic shape) with speculative decoding (DESIGN.md §3.9):
``speculate=4`` draft windows from the self-drafting n-gram drafter, verified
through the paged kernel's multi-token window, against the same engine at
``speculate=1``. Reported per variant and mode: tok/s, draft acceptance rate,
and emitted tokens per model step — acceptance is a deterministic
drafter/workload property (gated across runs like occupancy), while the
spec/nospec tok/s comparison gates within the snapshot (the two modes'
interleaved passes sample the same interference windows). Unlike the rest of
the benchmark, this section keeps the default kernel execution off-TPU: the
speculative win is launch amortization, which the interpret emulation's
per-launch cost preserves and the ref execution erases (see ``_spec_lines``).

A fourth section measures **latency**, not throughput: a cold-prompt workload
is driven step-by-step through ``ServeEngine.step`` and each call is timed —
once with every request submitted up front (``steady``) and once with half the
requests injected as a mid-run admission burst (``burst``) — for the unchunked
paged engine and the chunked token-budget scheduler (DESIGN.md §3.10).
Reported per (path × mode × phase): p50/p95 per-step latency and mean TTFT
(submit to first emitted token). The burst-phase p95 is the jitter win chunked
prefill exists for — an unchunked refill stalls every in-flight decode behind
a whole-prompt prefill launch — and gates snapshot-locally in ``regress.py``
(chunked ≤ unchunked).

A fifth section drives the **async server** (DESIGN.md §3.11): a
shared-prefix-family workload through the 2-replica ``AsyncServer``, measuring
what replica routing moves — the fleet prefix hit rate under prefix-affinity
vs seeded-random placement (affinity keeps a family's requests on the replica
whose radix index already holds their system prompt; random splits them and
each replica prefills the prefix cold) — plus an overload run where the
bounded admission queue rejects the excess past its deadline instead of
queueing it. The affinity ≥ random hit-rate comparison gates snapshot-locally
in ``regress.py``; TTFT/TPOT percentiles are informational on CPU hosts.

A sixth section serves the **config zoo** (DESIGN.md §3.13): mamba2 — an SSM
family the pre-§3.13 engine could only serve through exact-length grouping —
through both schedulers (the continuous ≥ grouped tok/s comparison gates
snapshot-locally in ``regress.py``: slot-table admission with masked-dt padded
prefill must not cost throughput against the grouped baseline it replaced,
and the occupancy column shows the win it exists for), and granite-moe
fused-int8 single-device vs expert-parallel on a ``(data, 1, expert=2)`` mesh
(informational wall-clock, like the ``@tp2`` rows: host-mesh collective
emulation dominates; the row measures *that* EP serves, parity is pinned by
tests/test_sharded_serving.py).

CSV (after the header rows):
``serving_bench,<path>[@tpN],<scheduler>,<tok_s>,<occupancy>,<refills_mid_decode>``
``serving_bench_prefix,<path>,<layout>,<tok_s>,<hit_rate>,<prefill_tokens>,<prefill_saved>,<peak_pages>,<capacity_x>``
``serving_bench_spec,<path>,<spec|nospec>,<tok_s>,<accept_rate>,<tokens_per_step>``
``serving_bench_latency,<path>,<chunked|unchunked>,<steady|burst>,<p50_step_ms>,<p95_step_ms>,<ttft_ms>``
``serving_bench_server,<path>,<router>,<steady|overload>,<ttft_p50_ms>,<ttft_p95_ms>,<tpot_p50_ms>,<tpot_p95_ms>,<reject_rate>,<hit_rate>``
``serving_bench_zoo,<config>,<mode>,<tok_s>,<occupancy>,<refills_mid_decode>``
"""
from __future__ import annotations

import os
import time

import jax
import numpy as np

PROMPT_LENS = (6, 10, 14)
BATCH_SIZE = 4
MAX_LEN = 64
PAGE_SIZE = 8
#: timed passes per row (best-of): one pass of this workload serves in ~1 s,
#: which on a shared CI runner is hostage to scheduler interference — observed
#: 5× tok/s swings between identical runs. Max-of-5 estimates the uncontended
#: throughput; the compile caches are shared (``_prep``) so extra passes cost
#: serve time only, and the gated occupancy/hit-rate invariants are
#: deterministic per pass anyway.
TIMED_PASSES = 5
#: per-step token budget for chunked serving rows (DESIGN.md §3.10): must be
#: ≥ BATCH_SIZE (every generating slot's decode row lands each step) with
#: headroom for prefill chunks — 16 splits the prefix workload's cold 27-30
#: token prompts across two page-aligned chunks while keeping the packed
#: ragged launch small enough that pure-decode steps stay cheap
CHUNK_BUDGET = 16
#: steps served before the latency section's mid-run admission burst lands
BURST_AT_STEP = 3


def _workload(cfg, n_req: int, seed: int = 0):
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(1, cfg.vocab,
                            size=PROMPT_LENS[i % len(PROMPT_LENS)]).astype(np.int32)
               for i in range(n_req)]
    # Budgets decorrelated from the length cycle (period 4 vs 3): equal-length
    # groups carry mixed budgets, so the grouped baseline idles slots behind the
    # longest request of each group — the occupancy gap continuous batching
    # closes. Budgets are decode-dominated (the serving-relevant regime; a
    # prefill-dominated workload mostly measures per-call dispatch overhead).
    max_new = [14 + 6 * (i % 4) for i in range(n_req)]
    return prompts, max_new


def _prefix_workload(cfg, n_req: int, shared_len: int = 24, seed: int = 1):
    """One shared system prompt + short per-request suffixes: the prefix-reuse
    case the paged layout (DESIGN.md §3.8) exists for."""
    rng = np.random.default_rng(seed)
    shared = rng.integers(1, cfg.vocab, size=shared_len).astype(np.int32)
    prompts = [np.concatenate([
        shared, rng.integers(1, cfg.vocab, size=3 + (i % 4)).astype(np.int32)])
        for i in range(n_req)]
    max_new = [10 + 4 * (i % 3) for i in range(n_req)]
    return prompts, max_new


def _spec_workload(cfg, n_req: int, seed: int = 2):
    """Repetition-heavy prompts (tiled motifs, the templated/code regime
    prompt-lookup drafting exists for — DESIGN.md §3.9) with decode-dominated
    budgets: the self-drafting n-gram drafter fills verify windows from the
    request's own history, so acceptance — and therefore the spec/nospec
    tok/s ratio — is a property of the workload's repetitiveness."""
    rng = np.random.default_rng(seed)
    prompts, max_new = [], []
    for i in range(n_req):
        motif = rng.integers(1, cfg.vocab, size=3 + i % 3).astype(np.int32)
        prompts.append(np.tile(motif, 4)[: PROMPT_LENS[i % len(PROMPT_LENS)]])
        # long decode budgets: greedy streams settle into attractor loops the
        # prompt-lookup drafter then rides — short budgets would mostly
        # measure the pre-loop transient where acceptance is poor
        max_new.append(36 + 4 * (i % 4))
    return prompts, max_new


def _latency_workload(cfg, n_req: int, seed: int = 3):
    """Cold long prompts, no sharing: the admission-cost shape. An unchunked
    refill runs the whole prompt as one bucketed prefill launch — the step
    every co-resident decode waits behind — while the chunked scheduler
    spreads it across budgeted steps. Decode budgets keep the tail
    decode-dominated so steady-state steps are measured too."""
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(1, cfg.vocab, size=20 + 4 * (i % 4)).astype(np.int32)
               for i in range(n_req)]
    max_new = [8 + 2 * (i % 3) for i in range(n_req)]
    return prompts, max_new


def _drive(eng, prompts, max_new, burst_at=None):
    """Serve the workload through ``ServeEngine.step``, timing each call.
    With ``burst_at``, only the first half of the requests is submitted up
    front and the rest land as one mid-run admission burst after that many
    steps. Returns ``(per-step latencies, per-request TTFTs)`` in ms — TTFT
    is submit-to-first-emitted-token, so for burst requests it includes the
    queue wait behind the in-flight decodes."""
    n = len(prompts)
    cut = n if burst_at is None else n // 2

    def submit(lo, hi):
        eng.submit([p.copy() for p in prompts[lo:hi]],
                   max_new=list(max_new[lo:hi]))
        now = time.perf_counter()
        return {r.rid: now for r in eng.queue[-(hi - lo):]}

    t_sub = submit(0, cut)
    finished, step_ms, ttft = [], [], {}
    k = 0
    while True:
        if burst_at is not None and k == burst_at:
            t_sub.update(submit(cut, n))
            burst_at = None
        t0 = time.perf_counter()
        alive = eng.step(finished)
        dt = time.perf_counter() - t0
        if not alive:
            assert burst_at is None, "engine idled before the burst landed"
            return step_ms, list(ttft.values())
        step_ms.append(dt * 1e3)
        k += 1
        now = time.perf_counter()
        for r in list(eng._slots) + finished:
            if r is not None and r.out and r.rid not in ttft:
                ttft[r.rid] = (now - t_sub[r.rid]) * 1e3


def _latency_lines(cfg, variants, n_req: int, steps):
    """The latency section: per-step p50/p95 and mean TTFT, chunked vs
    unchunked paged serving, in a steady phase (all requests up front) and a
    burst phase (half the requests injected mid-decode). The burst-phase p95
    is the jitter claim of DESIGN.md §3.10 — an unchunked admission runs the
    full prompt prefill as one launch between decode steps, so the burst
    shows up as p95 spikes the token-budget scheduler bounds away —
    and regress.py gates it snapshot-locally (chunked ≤ unchunked). Passes
    interleave across modes and phases; best-of keeps the per-metric MIN
    (the uncontended estimate, like the tok/s rows' max)."""
    from repro.serving.config import EngineConfig
    from repro.serving.engine import ServeEngine
    prompts, max_new = _latency_workload(cfg, n_req)
    lines = ["serving_bench_latency,path,mode,phase,p50_step_ms,p95_step_ms,"
             "ttft_ms"]
    modes = {"unchunked": {}, "chunked": dict(chunked=True,
                                              token_budget=CHUNK_BUDGET)}
    for tag, p, quant, path, kv in variants:
        kws, best = {}, {}
        for mode, extra in modes.items():
            config = EngineConfig(batch_size=BATCH_SIZE, max_len=MAX_LEN,
                                  path=path, kv_cache=kv,
                                  scheduler="continuous", cache_layout="paged",
                                  page_size=PAGE_SIZE, **extra)
            key = (tag, "", "paged-chunked" if extra else "paged")
            weng = ServeEngine(cfg, p, config=config, quant=quant)
            if key in steps:
                _attach_steps(weng, steps[key])
            # warm on THIS workload: the unchunked engines' bucketed prefill
            # lowerings depend on the prompt-length buckets, which differ
            # from the earlier sections' workloads
            _drive(weng, prompts, max_new, burst_at=BURST_AT_STEP)
            steps[key] = _extract_steps(weng)
            kws[mode] = (config, steps[key])
        for _ in range(TIMED_PASSES):
            for phase, burst_at in (("steady", None), ("burst", BURST_AT_STEP)):
                for mode, (config, shared) in kws.items():
                    eng = ServeEngine(cfg, p, config=config, quant=quant)
                    _attach_steps(eng, shared)
                    step_ms, ttfts = _drive(eng, prompts, max_new,
                                            burst_at=burst_at)
                    got = (float(np.percentile(step_ms, 50)),
                           float(np.percentile(step_ms, 95)),
                           float(np.mean(ttfts)))
                    prev = best.get((mode, phase))
                    best[(mode, phase)] = (got if prev is None else
                                           tuple(map(min, prev, got)))
        for (mode, phase), (p50, p95, tf) in best.items():
            lines.append(f"serving_bench_latency,{tag},{mode},{phase},"
                         f"{p50:.2f},{p95:.2f},{tf:.2f}")
    return lines


def _spec_lines(cfg, variants, n_req: int, steps):
    """The speculative section: speculate=4 vs plain decode per serving
    variant, through the paged layout (the verify window scores against the
    same paged pools + in-kernel int8 dequant as decode — DESIGN.md §3.9).
    spec/nospec timed passes interleave for the same reason the other
    sections' do: the regression gate compares their tok/s as a same-run
    ratio, so adjacent passes must see the same machine.

    This section runs under the *default* kernel execution (Mosaic on TPU,
    interpret emulation elsewhere), not the ref-exec the rest of the bench
    opts into: the speculative win is launch amortization — one verify launch
    replaces up to k decode launches — and the interpret emulation preserves
    that per-launch cost structure, while the ref execution's fused XLA
    decode erases launch cost on a toy CPU model and with it the signal the
    spec/nospec gate checks. The exec mode bakes into each engine step's jit
    trace, so this section's step-cache keys are its own (``specK``) — the
    other sections' ref-mode steps must not be reused here."""
    prompts, max_new = _spec_workload(cfg, n_req)
    lines = ["serving_bench_spec,path,mode,tok_s,accept_rate,tokens_per_step"]
    prev = os.environ.pop("REPRO_KERNEL_EXEC", None)
    try:
        for tag, p, quant, path, kv in variants:
            passes = {
                mode: _prep(cfg, p, prompts, max_new, quant=quant, path=path,
                            kv_cache=kv, scheduler="continuous",
                            cache_layout="paged", speculate=k, steps=steps,
                            key=(tag, f"spec{k}", "paged"))
                for mode, k in (("nospec", 1), ("spec", 4))}
            best = dict.fromkeys(passes, 0.0)
            engs = {}
            for _ in range(TIMED_PASSES):
                for mode, one_pass in passes.items():
                    tok_s, engs[mode] = one_pass()
                    best[mode] = max(best[mode], tok_s)
            for mode, eng in engs.items():
                lines.append(
                    f"serving_bench_spec,{tag},{mode},{best[mode]:.1f},"
                    f"{eng.stats().accept_rate:.3f},{eng.stats().tokens_per_step:.2f}")
    finally:
        if prev is not None:
            os.environ["REPRO_KERNEL_EXEC"] = prev
    return lines


#: jit'd step attributes shared across engines of one (variant, mesh, layout)
#: — sharing the function objects shares their compile caches, so each
#: lowering compiles once per process instead of once per engine
_STEP_ATTRS = {"decode": "_decode_step", "cold": "_admit_cold",
               "warm": "_admit_warm", "copy": "_copy_step",
               "admit": "_admit_step", "verify": "_verify_step",
               "chunk": "_chunk_step"}


def _extract_steps(eng):
    return {k: getattr(eng, a) for k, a in _STEP_ATTRS.items()
            if hasattr(eng, a)}


def _attach_steps(eng, shared):
    # hasattr guard both ways: a dense engine must not gain paged steps and a
    # chunked entry's "chunk" step must not land on an unchunked engine
    for k, a in _STEP_ATTRS.items():
        if k in shared and hasattr(eng, a):
            setattr(eng, a, shared[k])


def _prep(cfg, params, prompts, max_new, *, quant, path, kv_cache, scheduler,
          mesh=None, cache_layout="dense", speculate=1, chunked=False,
          token_budget=None, steps=None, key=None):
    """Warm the compile caches on one throwaway serve, then return a
    ``one_pass()`` closure that serves the workload on a fresh engine and
    returns ``(tok_s, engine)``. ``steps``/``key`` share the jit'd step
    objects — and therefore their compile caches — across engines of the same
    (variant, mesh, layout): the step functions do not depend on the scheduler
    or on which bench section runs them, so grouped/continuous and the
    main/shared-prefix sections compile each lowering once per process instead
    of once per engine (the quick-CI wall-clock was dominated by those
    recompiles)."""
    from repro.serving.config import EngineConfig
    from repro.serving.engine import ServeEngine
    config = EngineConfig(batch_size=BATCH_SIZE, max_len=MAX_LEN, path=path,
                          kv_cache=kv_cache, scheduler=scheduler,
                          cache_layout=cache_layout, page_size=PAGE_SIZE,
                          speculate=speculate, chunked=chunked,
                          token_budget=(token_budget or CHUNK_BUDGET)
                          if chunked else 64)

    shared = steps.get(key) if steps is not None and key is not None else None
    eng = ServeEngine(cfg, params, config=config, quant=quant, mesh=mesh)
    if shared is not None:
        _attach_steps(eng, shared)
    eng.submit([p.copy() for p in prompts], max_new=list(max_new))
    eng.run()                      # warm compile caches (fresh engines re-time)
    if steps is not None and key is not None and shared is None:
        steps[key] = _extract_steps(eng)

    def one_pass():
        eng2 = ServeEngine(cfg, params, config=config, quant=quant, mesh=mesh)
        _attach_steps(eng2, _extract_steps(eng))
        eng2.submit([p.copy() for p in prompts], max_new=list(max_new))
        t0 = time.perf_counter()
        done = eng2.run()
        dt = time.perf_counter() - t0
        return sum(len(r.out) for r in done) / dt, eng2

    return one_pass


def _prefix_lines(cfg, variants, n_req: int, steps):
    """The shared-prefix section: dense vs paged vs chunked (the §3.10
    token-budget scheduler on the paged pool, informational) per serving
    variant. The layouts' timed passes are *interleaved* (dense, paged,
    chunked, dense, ...): the regression gate compares their tok/s as a
    ratio, and on a shared runner an interference window spanning one
    layout's whole best-of block would skew the ratio arbitrarily — adjacent
    passes see the same machine."""
    prompts, max_new = _prefix_workload(cfg, n_req)
    lines = ["serving_bench_prefix,path,layout,tok_s,hit_rate,prefill_tokens,"
             "prefill_saved,peak_pages,capacity_x"]
    dense_pages = BATCH_SIZE * MAX_LEN // PAGE_SIZE
    for tag, p, quant, path, kv in variants:
        # three rows per variant: dense, paged (the gated configuration — the
        # regress.py floor holds fp paged/dense ≥ 0.90, which the ref-exec
        # kernel dispatch recovers on CPU hosts), and the §3.10 chunked
        # scheduler on the same paged pool, reported informationally — on an
        # overhead-bound CPU host the ragged mixed steps trade a little
        # throughput for the bounded per-step latency the latency section
        # measures (its win is the burst p95 gate, not tok/s)
        passes = {
            layout: _prep(cfg, p, prompts, max_new, quant=quant, path=path,
                          kv_cache=kv, scheduler="continuous",
                          cache_layout="paged" if layout == "chunked"
                          else layout, chunked=layout == "chunked",
                          steps=steps,
                          key=(tag, "", "paged-chunked"
                               if layout == "chunked" else layout))
            for layout in ("dense", "paged", "chunked")}
        best = dict.fromkeys(passes, 0.0)
        engs = {}
        for _ in range(TIMED_PASSES):
            for layout, one_pass in passes.items():
                tok_s, engs[layout] = one_pass()
                best[layout] = max(best[layout], tok_s)
        for layout, eng in engs.items():
            saved = eng.counters["prefix_tokens_reused"]
            peak = eng.counters["peak_pages_in_use"] or dense_pages
            lines.append(
                f"serving_bench_prefix,{tag},{layout},{best[layout]:.1f},"
                f"{eng.stats().prefix_hit_rate:.3f},{eng.counters['prefill_tokens']},"
                f"{saved},{peak},{dense_pages / peak:.2f}")
    return lines


def _server_workload(cfg, n_families: int = 4, per_family: int = 3,
                     shared_len: int = 16, seed: int = 4):
    """Fleet-traffic shape for the router section: ``n_families`` distinct
    shared system prompts (each two pages long), ``per_family`` requests each,
    submitted family-interleaved — random routing splits a family's requests
    across replicas (each replica prefills the shared prefix cold) while
    prefix-affinity keeps families together and the radix index pays off."""
    rng = np.random.default_rng(seed)
    fams = [rng.integers(1, cfg.vocab, size=shared_len).astype(np.int32)
            for _ in range(n_families)]
    return [np.concatenate([fams[f],
                            rng.integers(1, cfg.vocab,
                                         size=3 + (f + r) % 4).astype(np.int32)])
            for r in range(per_family) for f in range(n_families)]


def _fleet_hit_rate(metrics: dict) -> float:
    """Aggregate prefix hit rate across the fleet: prompt tokens mapped
    copy-free from cached pages / total prompt tokens, summed over replicas —
    the quantity routing policy actually moves."""
    engines = [r["engine"] for r in metrics["replicas"] if r["engine"]]
    reused = sum(e["prefix_tokens_reused"] for e in engines)
    prompt = sum(e["prompt_tokens"] for e in engines)
    return reused / prompt if prompt else 0.0


def _serve_async(cfg, params, prompts, *, router, steps, max_queue=None,
                 admission_timeout=1.0):
    """Drive one workload through a 2-replica ``AsyncServer`` and return its
    ``metrics()`` snapshot. The server is paused while every request is
    submitted, so routing decisions and (in the overload run) admission
    rejects are decided against a frozen fleet — deterministic per snapshot,
    which is what lets regress.py gate affinity-vs-random as a same-run
    comparison. Replica engines adopt the process-wide shared step objects
    (same shapes as the prefix section's paged fp engines)."""
    import asyncio

    from repro.serving.api import AdmissionError, Request
    from repro.serving.config import EngineConfig
    from repro.serving.server import AsyncServer

    config = EngineConfig(batch_size=BATCH_SIZE, max_len=MAX_LEN,
                          cache_layout="paged", page_size=PAGE_SIZE)

    async def drive():
        async with AsyncServer(cfg, params, config=config, replicas=2,
                               router=router, router_seed=0,
                               max_queue=max_queue,
                               admission_timeout=admission_timeout) as srv:
            shared = steps.get(("fp", "", "paged"))
            if shared is not None:
                for rep in srv.replicas:
                    _attach_steps(rep.engine, shared)
            srv.pause()

            async def one(p):
                try:
                    async for _ in srv.submit(Request(prompt=p.tolist(),
                                                      max_new=6)):
                        pass
                except AdmissionError:
                    pass

            tasks = [asyncio.ensure_future(one(p)) for p in prompts]
            # let every submission route (or reject) against the paused fleet
            await asyncio.sleep(2 * admission_timeout + 0.1)
            srv.resume()
            await asyncio.gather(*tasks)
            return srv.metrics()

    return asyncio.run(drive())


def _server_lines(cfg, params, steps):
    """The async-server section (DESIGN.md §3.11): one shared-prefix-family
    workload through the 2-replica ``AsyncServer`` under three loads —
    prefix-affinity vs seeded-random routing at steady offered load (the
    affinity ≥ random fleet hit-rate comparison regress.py gates
    snapshot-locally), plus an overload run (``max_queue`` = one engine batch,
    20 ms admission deadline) where backpressure rejects the excess instead of
    queueing it — the nonzero reject-rate row. TTFT/TPOT percentiles are
    informational on a CPU host (they include the deterministic pause window);
    the gated signal is the hit-rate ratio and that rejects stay 0 off
    overload."""
    prompts = _server_workload(cfg)
    lines = ["serving_bench_server,path,router,load,ttft_p50_ms,ttft_p95_ms,"
             "tpot_p50_ms,tpot_p95_ms,reject_rate,hit_rate"]
    runs = [("affinity", "steady", {}),
            ("random", "steady", {}),
            ("affinity", "overload", dict(max_queue=BATCH_SIZE,
                                          admission_timeout=0.02))]
    for router, load, kw in runs:
        m = _serve_async(cfg, params, prompts, router=router, steps=steps, **kw)
        srv, lat = m["server"], m["latency"]
        offered = srv["submitted"] + srv["rejected"]   # admitted + rejected
        rej = srv["rejected"] / offered if offered else 0.0
        lines.append(
            f"serving_bench_server,fp,{router},{load},"
            f"{lat['ttft_p50_s'] * 1e3:.1f},{lat['ttft_p95_s'] * 1e3:.1f},"
            f"{lat['tpot_p50_s'] * 1e3:.2f},{lat['tpot_p95_s'] * 1e3:.2f},"
            f"{rej:.3f},{_fleet_hit_rate(m):.3f}")
    return lines


def _zoo_lines(quick: bool, steps):
    """The config-zoo section (DESIGN.md §3.13): serving families the engine
    learned through the layer-polymorphic ``StateSpec`` registry.

    mamba2 (SSM: recurrent-state + conv-buffer pages, no KV) serves the main
    mixed-length workload through both schedulers — the continuous ≥ grouped
    tok/s comparison gates snapshot-locally in ``regress.py`` (slot-table
    admission with masked-dt padded prefill must not cost throughput against
    the exact-length grouping it replaced, while the occupancy column shows
    the structural win). Passes interleave like the main section's: the gate
    is a same-snapshot ratio.

    granite-moe serves fused-int8 through the continuous scheduler
    single-device and — when the host exposes ≥ 2 devices — expert-parallel on
    a ``(n_dev/2, 1, expert=2)`` mesh (``@ep2``). Like the ``@tp2`` rows these
    are informational wall-clock (host-mesh collective emulation dominates);
    bitwise parity vs single-device is pinned by tests/test_sharded_serving.py.
    Skipped in quick mode with the other quantized variants (quantize_tree
    dominates the quick-CI budget); the gated mamba2 pair runs in both modes.
    """
    from repro.configs import get
    from repro.core import qlinear as ql
    from repro.models import model as M
    from repro.models.quantize import quantize_tree

    lines = ["serving_bench_zoo,config,mode,tok_s,occupancy,refills_mid_decode"]

    zcfg = get("mamba2-130m", smoke=True)
    zparams = M.init_params(jax.random.PRNGKey(0), zcfg)
    prompts, max_new = _workload(zcfg, 10)
    passes = {
        scheduler: _prep(zcfg, zparams, prompts, max_new, quant=ql.FP,
                         path=None, kv_cache="fp", scheduler=scheduler,
                         steps=steps, key=("zoo-mamba2", "", "dense"))
        for scheduler in ("grouped", "continuous")}
    best = dict.fromkeys(passes, 0.0)
    engs = {}
    for _ in range(TIMED_PASSES):
        for scheduler, one_pass in passes.items():
            tok_s, engs[scheduler] = one_pass()
            best[scheduler] = max(best[scheduler], tok_s)
    for scheduler, eng in engs.items():
        lines.append(f"serving_bench_zoo,mamba2,{scheduler},"
                     f"{best[scheduler]:.1f},{eng.stats().occupancy:.2f},"
                     f"{eng.counters['mid_decode_admissions']}")

    if quick:
        return lines

    mcfg = get("granite-moe-3b-a800m", smoke=True)
    mparams = quantize_tree(M.init_params(jax.random.PRNGKey(0), mcfg),
                            ql.W8A8_INT8)
    mprompts, mmax_new = _workload(mcfg, 10)
    mmeshes = [("", None)]
    if len(jax.devices()) >= 2:
        from repro.launch.mesh import make_debug_mesh
        mmeshes.append(("@ep2",
                        make_debug_mesh(len(jax.devices()) // 2, 1, 2)))
    for mesh_tag, mesh in mmeshes:
        one_pass = _prep(mcfg, mparams, mprompts, mmax_new,
                         quant=ql.W8A8_INT8, path="fused-int8", kv_cache="fp",
                         scheduler="continuous", mesh=mesh, steps=steps,
                         key=("zoo-granite-moe", mesh_tag, "dense"))
        best_m, eng = 0.0, None
        for _ in range(TIMED_PASSES):
            tok_s, eng = one_pass()
            best_m = max(best_m, tok_s)
        lines.append(f"serving_bench_zoo,granite-moe{mesh_tag},continuous,"
                     f"{best_m:.1f},{eng.stats().occupancy:.2f},"
                     f"{eng.counters['mid_decode_admissions']}")
    return lines


def run(quick: bool = False):
    # Off-TPU, serve through the pure-jnp reference execution of the paged
    # kernels (kernels/ops.py _exec_mode): interpret emulation is a
    # correctness harness and its per-launch overhead would otherwise be the
    # dominant term in every paged row — emulator cost, not a serving signal.
    # On TPU the variable is ignored and the Mosaic kernels run. The
    # speculative section opts back out (_spec_lines): its gate measures
    # launch amortization, which needs the per-launch cost structure.
    prev = os.environ.get("REPRO_KERNEL_EXEC")
    os.environ["REPRO_KERNEL_EXEC"] = "ref"
    try:
        return _run(quick)
    finally:
        if prev is None:
            os.environ.pop("REPRO_KERNEL_EXEC", None)
        else:
            os.environ["REPRO_KERNEL_EXEC"] = prev


def _run(quick: bool = False):
    from repro.configs import get
    from repro.core import qlinear as ql
    from repro.models import model as M
    from repro.models.quantize import quantize_tree

    cfg = get("starcoder2-7b", smoke=True)
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    # one workload size for quick AND full passes: occupancy is a deterministic
    # scheduling invariant gated across runs (benchmarks/regress.py), so the
    # quick-CI snapshot must serve the exact workload of the committed full-run
    # baseline — quick only trims the variant grid below
    n_req = 10
    prompts, max_new = _workload(cfg, n_req)

    variants = [("fp", params, ql.FP, None, "fp")]
    if not quick:
        qparams = quantize_tree(params, ql.W8A8_INT8)
        variants += [("fused-int8", qparams, ql.W8A8_INT8, "fused-int8", "fp"),
                     ("fused-int8+kv8", qparams, ql.W8A8_INT8, "fused-int8", "int8")]

    # TP-sharded twins (DESIGN.md §3.7) whenever the host exposes enough devices
    # (CI: XLA_FLAGS=--xla_force_host_platform_device_count=8 → tp=2 on (4, 2)).
    meshes = [("", None)]
    if len(jax.devices()) >= 2:
        from repro.launch.mesh import make_debug_mesh
        tp = 2
        meshes.append((f"@tp{tp}", make_debug_mesh(len(jax.devices()) // tp, tp)))

    # one process-wide step cache: every (variant, mesh, layout) compiles its
    # decode/admit lowerings once, shared across schedulers AND the
    # shared-prefix section below (identical workloads and engine shapes, so
    # the reuse cannot perturb the gated occupancy / hit-rate invariants)
    steps: dict = {}
    lines = ["serving_bench,path,scheduler,tok_s,occupancy,refills_mid_decode"]
    for tag, p, quant, path, kv in variants:
        for mesh_tag, mesh in meshes:
            # both schedulers' timed passes interleave, mirroring
            # _prefix_lines: the regress.py invariant gate compares
            # continuous against grouped tok/s directly, so the two must
            # sample the same interference windows
            passes = {
                scheduler: _prep(cfg, p, prompts, max_new, quant=quant,
                                 path=path, kv_cache=kv, scheduler=scheduler,
                                 mesh=mesh, steps=steps,
                                 key=(tag, mesh_tag, "dense"))
                for scheduler in ("grouped", "continuous")}
            best = dict.fromkeys(passes, 0.0)
            engs = {}
            for _ in range(TIMED_PASSES):
                for scheduler, one_pass in passes.items():
                    tok_s, engs[scheduler] = one_pass()
                    best[scheduler] = max(best[scheduler], tok_s)
            for scheduler, eng in engs.items():
                lines.append(f"serving_bench,{tag}{mesh_tag},{scheduler},"
                             f"{best[scheduler]:.1f},{eng.stats().occupancy:.2f},"
                             f"{eng.counters['mid_decode_admissions']}")

    # shared-system-prompt workload: dense vs paged prefix reuse (§3.8);
    # single-device only — the paged capacity story is layout, not TP. Like
    # occupancy, the hit rate is a gated deterministic invariant: quick and
    # full passes must serve the same workload (quick trims variants only).
    lines += _prefix_lines(cfg, variants, n_req=12, steps=steps)

    # speculative decoding (§3.9): speculate=4 vs plain decode on a
    # repetition-heavy workload, paged layout; accept rate is a deterministic
    # drafter/workload invariant gated across runs like occupancy, the
    # spec/nospec tok/s ratio gates same-snapshot (regress.py)
    lines += _spec_lines(cfg, variants, n_req=10, steps=steps)

    # latency (§3.10): per-step p50/p95 + TTFT, chunked vs unchunked paged
    # serving, with and without an admission burst mid-run; the burst-phase
    # p95 (chunked ≤ unchunked) gates snapshot-locally in regress.py. Runs
    # after the prefix section so its engines reuse the ref-mode paged and
    # chunked steps warmed there (the spec section's steps are pallas-mode
    # and keyed separately — see _spec_lines).
    lines += _latency_lines(cfg, variants, n_req=8, steps=steps)

    # async server (§3.11): prefix-affinity vs random routing through the
    # 2-replica AsyncServer on a shared-prefix-family workload, plus an
    # overload run exercising bounded-admission backpressure; the fleet
    # hit-rate comparison (affinity ≥ random) gates snapshot-locally. fp
    # only — routing moves prefix reuse, which is layout, not quantization.
    lines += _server_lines(cfg, params, steps)

    # config zoo (§3.13): mamba2 through both schedulers (continuous ≥ grouped
    # gates snapshot-locally) and granite-moe fused-int8 single-device vs
    # expert-parallel — the zoo configs' own step caches key under "zoo-*".
    lines += _zoo_lines(quick, steps)
    return lines
