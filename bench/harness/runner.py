"""One run of one cell: build, warm up, measure a window, check, report.

The window drives ``AsyncServer.submit`` with one replica per chip of the
cell, behind the server's prefix-affinity router, on the traffic's
``EngineConfig`` and the fused-int8 paged path. Set-up (``setup_s``) is
everything from process start to the window: weights, calibration,
quantization, server start, warm-up of exactly the shapes the traffic uses on
every replica, and for sessions the conversations' history and their opening
turns' prefills (``client.drive``). Engine counters are summed over the
replicas and, with the server's and the router's, taken as their change over
the window. After the window the program's state
is freed and the plain reference (the configuration's model module) runs over
a sample, drawn from the seed, of the requests the window served on any
replica, those still in flight at its close with the tokens they were served.
"""
from __future__ import annotations

import asyncio
import dataclasses
import gc
import json
import os
import shutil
import sys
import tempfile
import time
from typing import List, Optional

import numpy as np

from bench.harness import client as C
from bench.harness import spec, trace as T, weights
from bench.harness.traffic import schedule, warmup_prompt

TRACE_AT = 0.4         # the traced sub-window opens at this share of the window
TRACE_MAX_S = 3.0      # and lasts at most this long
MEMORY_EVERY_S = 0.02  # device memory in use is read this often in the window


class CompileClock:
    """Backend compiles and persistent-cache reads that JAX reports."""

    def __init__(self):
        import jax

        self.total = 0.0
        self.count = 0
        self.names = []            # the compiled function of each, in order
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, fun_name="", **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.total += duration
            self.count += 1
            self.names.append(fun_name)

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1


@dataclasses.dataclass
class Run:
    """What a metric reader gets."""
    cell: spec.Cell
    recs: List[C.Record]
    window: C.Window
    setup_s: float
    counters: dict                 # engine counters, summed over replicas: delta over the window
    server: dict = dataclasses.field(default_factory=dict)  # server and router counters: delta
    trace: Optional[T.Trace] = None
    trace_window: Optional[C.Window] = None
    trace_counters: dict = dataclasses.field(default_factory=dict)  # engine: delta over the trace
    peaks: object = None


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def _delta(a: dict, b: dict) -> dict:
    return {k: b[k] - a.get(k, 0) for k in b if isinstance(b[k], (int, float))}


def engine_counters(srv) -> dict:
    """Every replica's engine counters, summed (a replica being rebuilt after
    a failure has no engine and adds nothing)."""
    out: dict = {}
    for r in srv.replicas:
        for k, v in (dict(r.engine.counters) if r.engine is not None else {}).items():
            out[k] = out.get(k, 0) + v
    return out


def server_counters(srv) -> dict:
    """The server's counters with the router's affinity hits."""
    return dict(srv.counters, affinity_hits=srv.router.affinity_hits)


async def _burst(srv, request_cls, prompts, max_new):
    """Queue every prompt, ``(replica, tokens)``, before the engines look, so
    each replica admits its share in one batch; wait for all to finish."""
    async def one(r, p):
        req = request_cls(prompt=p.tolist(), max_new=max_new, replica_hint=r)
        async for ev in srv.submit(req):
            if ev.kind == "error":
                raise RuntimeError(f"warm-up request failed: {ev.error}")

    target = srv.counters["submitted"] + len(prompts)
    srv.pause()
    tasks = [asyncio.ensure_future(one(r, p)) for r, p in prompts]
    while srv.counters["submitted"] < target:
        await asyncio.sleep(0.002)
    srv.resume()
    await asyncio.gather(*tasks)


async def _warm_up(srv, request_cls, sched, vocab: int):
    """Compile every program shape the traffic will use, on every replica,
    through the server (``replica_hint``; the replicas compile side by side).
    A chunked engine runs one packed step shape. Otherwise each admission is a
    prefill of (rows, bucket): rows a power of two up to the batch, bucket
    the engine's padded length of each prompt length the mix holds, each
    with the caches already on the chip; then the decode step. Every prompt
    here opens with a first token that no run request uses, and no two
    prompts on one replica share their first token."""
    eng = srv.replicas[0].engine
    ids = [0] * len(srv.replicas)          # warm-up first-token ids used, per replica

    def prompts(rows, length):
        out = []
        for r in range(len(ids)):
            out += [(r, warmup_prompt(vocab, ids[r] + i, length)) for i in range(rows)]
            ids[r] += rows
        return out

    # four tokens: steps right after a page-table push and steps without one
    # take the table as differently placed arguments, and compile apart
    if eng.chunked:
        # twice: the first step takes the caches as they were made, so only
        # the second burst pushes a table beside caches a step has returned
        for _ in range(2):
            await _burst(srv, request_cls, prompts(min(eng.B, 4), 16), 4)
        return
    buckets = sorted({eng._bucket(int(n)) for n in sched.prompt_lengths()})
    rows = [1 << i for i in range(eng.B.bit_length()) if 1 << i <= eng.B]
    # the engine's first call takes its caches as they were made, not yet
    # placed on the chip, and compiles apart from every later call of that
    # shape: spend it on an admission of its own before the grid
    await _burst(srv, request_cls, prompts(1, buckets[0]), 1)
    for b in buckets:
        for r in rows:
            await _burst(srv, request_cls, prompts(r, b), 1)
    await _burst(srv, request_cls, prompts(1, 8), 4)


async def _send_history(srv, request_cls, sched) -> int:
    """Sessions: send the history of every client's opening session at once,
    through the router, for one token each, so that each replica caches the
    conversations the router places on it; returns the tokens sent."""
    hist = [h for h in (sched.history(c) for c in range(sched.clients)) if len(h)]

    async def one(p):
        async for ev in srv.submit(request_cls(prompt=p.tolist(), max_new=1)):
            if ev.kind == "error":
                raise RuntimeError(f"history request failed: {ev.error}")

    await asyncio.gather(*(one(p) for p in hist))
    return sum(len(h) for h in hist)


def _sample(recs: List[C.Record], seed: int, want_tokens: int, max_requests: int):
    """The request with the most served tokens, then others drawn from the
    seed, until ``want_tokens`` served tokens or ``max_requests`` requests.
    A request still in flight when the window closed counts with the tokens
    it was served, so the sample spans the slots, not only the requests short
    enough to finish."""
    done = [r for r in recs if r.tokens and r.error is None]
    if not done:
        return []
    done.sort(key=lambda r: r.idx)
    first = max(done, key=lambda r: (len(r.tokens), -r.idx))
    rest = [r for r in done if r is not first]
    order = np.random.default_rng([int(seed), 4]).permutation(len(rest))
    out, n = [first], len(first.tokens)
    for i in order:
        if n >= want_tokens or len(out) >= max_requests:
            break
        out.append(rest[i])
        n += len(rest[i].tokens)
    return out


def logit_gaps(tree, ref, c: dict, seed: int, samples, control: bool = False) -> dict:
    """Over every served token of the sample, how far the reference's logit of
    the served token lies below the reference's best at that position: the
    widest such gap and the mean. The reference (``ref``, the configuration's
    model module) runs at the configuration's precision, calibrated on the
    seed's calibration tokens. With ``control`` the same for the token that
    the control (the reference at int4) puts first at each position."""
    cal = weights.calibration_tokens(c, seed)
    bits = c["quant"]["bits"]
    cols = ref.calibrate(tree, cal, c, bits)
    cols4 = ref.calibrate(tree, cal, c, 4) if control else None
    gaps, ctl = [], []
    for r in samples:
        p, toks = len(r.prompt), np.asarray(r.tokens, np.int32)
        seq = np.concatenate([r.prompt, toks[:-1]])
        rows = np.arange(p - 1, p - 1 + len(toks))
        lg = np.asarray(ref.logits(tree, seq, rows, c, bits, cols), np.float64)
        best = lg.max(-1)
        gaps.append(best - lg[np.arange(len(toks)), toks])
        if control:
            pick = np.asarray(ref.logits(tree, seq, rows, c, 4, cols4)).argmax(-1)
            ctl.append(best - lg[np.arange(len(toks)), pick])
    g = np.concatenate(gaps) if gaps else np.zeros(0)
    out = {"max_logit_gap": float(g.max()) if len(g) else float("inf"),
           "mean_logit_gap": float(g.mean()) if len(g) else float("inf"),
           "tokens": len(g), "not_argmax": int((g > 0).sum())}
    if control:
        h = np.concatenate(ctl)
        out.update(control_max_logit_gap=float(h.max()),
                   control_mean_logit_gap=float(h.mean()))
    return out


def judge(gaps: dict, failed: int, corr: dict) -> tuple:
    """(checks, correct): each number compared beside its limit, and whether
    every one holds. The same for the served tokens and for the control."""
    checks = {k: {"value": gaps[k], "limit": float(v)} for k, v in corr["limits"].items()}
    checks["failed_requests"] = {"value": failed, "limit": 0}
    checks["sampled_tokens"] = {"value": gaps["tokens"], "limit": corr["min_tokens"]}
    correct = (all(gaps[k] <= float(v) for k, v in corr["limits"].items())
               and failed == 0 and gaps["tokens"] >= corr["min_tokens"])
    return checks, correct


def load_limits(cell: str) -> dict:
    with open(spec.BENCH / "limits" / f"{cell}.json") as f:
        return json.load(f)


def start_jax():
    """JAX's devices, with every compiled program kept in the persistent cache
    (the directory comes from ``JAX_COMPILATION_CACHE_DIR``, which
    ``bench/run.py`` fixes inside the checkout): eager calibration compiles
    hundreds of small programs, each under JAX's default one-second floor."""
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return jax.devices()


def run_cell(cell: spec.Cell, seed: int, seconds: float, traced: bool, *,
             t_process: float, require_tpu: bool = True, limits: Optional[dict] = None,
             control: bool = False, clock: Optional[CompileClock] = None) -> dict:
    """One run; returns the result line's object. Raises ``SystemExit`` where
    the chip the cell asks for is missing. ``control`` adds the int4
    control's reading on the same sample (for ``bench/control.py``)."""
    import jax

    devs = start_jax()
    if require_tpu and (devs[0].platform != "tpu" or len(devs) < cell.chips):
        log(f"bench: the cell needs {cell.chips} TPU chip(s); JAX found "
            f"{len(devs)} {devs[0].platform} device(s). No result.")
        raise SystemExit(3)
    from bench.harness import model, peaks as P

    pk = P.peaks(devs[0].device_kind) if require_tpu else None
    limits = limits if limits is not None else load_limits(cell.name)
    clock = clock or CompileClock()
    c0, n00, h0, m0 = clock.total, clock.count, clock.hits, clock.misses
    marks: dict = {}
    c = cell.config
    cfg, qparams = model.build(c, cell.model.layout(c), seed, marks, clock)

    from repro.core import qlinear as ql
    from repro.serving.api import Request
    from repro.serving.config import EngineConfig
    from repro.serving.server import AsyncServer

    econf = EngineConfig(**cell.traffic["engine"])
    sched = schedule(cell.traffic, c["vocab"], seed)
    state: dict = {}
    mem: dict = {}

    async def serve():
        t = time.monotonic()
        k = clock.total
        srv = AsyncServer(cfg, qparams, config=econf, replicas=cell.chips,
                          quant=ql.W8A8_INT8, admission_timeout=3600.0)
        await srv.start()
        await _warm_up(srv, Request, sched, c["vocab"])
        marks["warmup_s"], marks["warmup_compile_s"] = (time.monotonic() - t,
                                                        clock.total - k)
        if sched.loop == "sessions":
            t = time.monotonic()
            sent = await _send_history(srv, Request, sched)
            marks["history_s"] = time.monotonic() - t
            log(f"bench: the sessions' history, sent in set-up: {sent} tokens")
        opened = time.monotonic()
        tr: dict = {}

        async def tracer(window):
            await asyncio.sleep(TRACE_AT * seconds)
            tr["dir"] = tempfile.mkdtemp(prefix="bench-trace-")
            opts = jax.profiler.ProfileOptions()
            # the program's spans (TraceAnnotation) describe the host; the
            # Python tracer would add an event per call and inflate host work
            opts.python_tracer_level = 0
            jax.profiler.start_trace(tr["dir"], profiler_options=opts)
            tr["t0"], tr["c0"] = time.monotonic(), engine_counters(srv)
            await asyncio.sleep(min(TRACE_MAX_S, 0.3 * seconds))
            tr["t1"], tr["c1"] = time.monotonic(), engine_counters(srv)
            jax.profiler.stop_trace()

        tasks = []

        async def memory(window):
            # bytes in use on each chip, read through the window: the serving
            # state with the step programs' temporaries, not the build's peak
            while time.monotonic() < window.end:
                for i, d in enumerate(devs[: cell.chips]):
                    b = (d.memory_stats() or {}).get("bytes_in_use")
                    if b is not None:
                        mem[i] = max(mem.get(i, 0), b)
                await asyncio.sleep(MEMORY_EVERY_S)

        def on_open(window):
            state["setup_s"] = window.start - t_process
            if sched.loop == "sessions":
                marks["opening_s"] = window.start - opened
            state.update(before=engine_counters(srv), srv_before=server_counters(srv),
                         n0=clock.count)
            tasks.append(asyncio.ensure_future(memory(window)))
            if traced:
                tasks.append(asyncio.ensure_future(tracer(window)))

        recs, window, lateness = await C.drive(srv, Request, sched, seconds, on_open)
        await asyncio.gather(*tasks)
        state.update(recs=recs, window=window, lateness=lateness,
                     counters=_delta(state["before"], engine_counters(srv)),
                     server=_delta(state["srv_before"], server_counters(srv)),
                     compiles=clock.names[state["n0"]:], tr=tr)
        await srv.aclose()

    asyncio.run(serve())
    mem_peak = max(mem.values(), default=None)
    life = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devs[: cell.chips]]
    log(f"bench: device memory: window peak in use {mem_peak} bytes on the fullest "
        f"chip, per chip {[mem.get(i) for i in range(cell.chips)]} (read every "
        f"{MEMORY_EVERY_S} s); process peak {max((m for m in life if m), default=None)} "
        f"bytes (the build holds the bfloat16 and int8 trees together)")
    del qparams
    gc.collect()

    recs, window = state["recs"], state["window"]
    log(f"bench: set-up {state['setup_s']:.3f} s: " + ", ".join(
        f"{k} {v:.3f}" for k, v in marks.items()) + f"; compile total "
        f"{clock.total - c0:.3f} s in {clock.count - n00} compiles, persistent cache "
        f"hits {clock.hits - h0}, misses {clock.misses - m0}")
    late = state["lateness"]
    log(f"bench: compiles inside the window: {len(state['compiles'])}")
    if state["compiles"]:
        log(f"bench: compiled inside the window: {', '.join(state['compiles'])}")
    log(f"bench: generator lateness: " + (
        f"max {max(late):.6f} s, mean {float(np.mean(late)):.6f} s over {len(late)} "
        f"arrivals" if late else "closed loop, none"))
    log(f"bench: window {window.seconds:.3f} s, requests due {len(recs)}, finished "
        f"{sum(r.finished for r in recs)}, errors {sum(r.error is not None for r in recs)}")
    log("bench: engine counters over the window: " + ", ".join(
        f"{k} {v}" for k, v in sorted(state["counters"].items()) if v))
    log("bench: server counters over the window: " + ", ".join(
        f"{k} {v}" for k, v in sorted(state["server"].items()) if v))

    trace_obj = tw = None
    trace_counters: dict = {}
    if traced:
        tr = state["tr"]
        tw = C.Window(tr["t0"], tr["t1"])
        trace_counters = _delta(tr["c0"], tr["c1"])
        trace_obj = T.reduce(tr["dir"], tw.seconds)
        shutil.rmtree(tr["dir"], ignore_errors=True)
    run = Run(cell=cell, recs=recs, window=window, setup_s=state["setup_s"],
              counters=state["counters"], server=state["server"], trace=trace_obj,
              trace_window=tw, trace_counters=trace_counters, peaks=pk)

    # ---- correctness: the reference over a sample of served requests
    t = time.monotonic()
    corr = limits["correct"]
    samples = _sample(recs, seed, corr["sample_tokens"], corr["max_requests"])
    tree = weights.make(cell.model.layout(c), seed)
    gaps = logit_gaps(tree, cell.model, c, seed, samples, control=control)
    del tree
    gc.collect()
    log(f"bench: reference over {len(samples)} requests (finished on replicas "
        f"{sorted({r.replica for r in samples if r.finished})}, "
        f"{sum(not r.finished for r in samples)} in flight at the close; longest context "
        f"{max((len(r.prompt) + len(r.tokens) for r in samples), default=0)}), "
        f"{gaps['tokens']} served tokens, {gaps['not_argmax']} of them not the "
        f"reference's argmax, {time.monotonic() - t:.3f} s")
    log(f"bench: served tokens: max_logit_gap {gaps['max_logit_gap']!r}, "
        f"mean_logit_gap {gaps['mean_logit_gap']!r}")
    if control:
        log(f"bench: control (int4): max_logit_gap {gaps['control_max_logit_gap']!r}, "
            f"mean_logit_gap {gaps['control_mean_logit_gap']!r}")

    metric_list = cell.per_layer if traced else cell.end_to_end
    metrics = spec.read_metrics(metric_list, run)
    failed = sum(r.error is not None for r in recs)
    checks, correct = judge(gaps, failed, corr)
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": cell.chips, "memory_peak_bytes": mem_peak}
    out = {"correct": correct, "attempted": len(recs), "failed": failed,
           "metrics": metrics, "device": device}
    if traced:
        device["busy_s"] = trace_obj.busy_s
        device["window_s"] = trace_obj.window_s
        out["breakdown"] = T.breakdown(trace_obj)
    if control:
        ctl = {k: gaps[f"control_{k}"] for k in ("max_logit_gap", "mean_logit_gap")}
        ctl_checks, ctl_correct = judge(dict(ctl, tokens=gaps["tokens"]), failed, corr)
        out["control"] = dict(ctl, correct=ctl_correct, check=ctl_checks)
        out["served"] = {k: gaps[k] for k in ("max_logit_gap", "mean_logit_gap")}
        for name, v in ctl_checks.items():
            log(f"control check {name}: {v['value']!r} (limit {v['limit']!r})")
        log(f"control correct: {ctl_correct}")
    out["check"] = checks
    for name, v in checks.items():
        log(f"check {name}: {v['value']!r} (limit {v['limit']!r})")
    return out


def main(argv=None, t_process: Optional[float] = None) -> int:
    import argparse

    t_process = time.monotonic() if t_process is None else t_process
    ap = argparse.ArgumentParser(description="Run one benchmark cell.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        cell = spec.load_cell(args.workload)
    except (KeyError, FileNotFoundError) as e:
        log(f"bench: {e}")
        return 2
    src = str(spec.ROOT / "src")
    if not os.path.isdir(src):
        log(f"bench: the program is not in this checkout ({src} missing). No result.")
        return 2
    sys.path.insert(0, src)
    out = run_cell(cell, args.seed, args.seconds, bool(args.trace), t_process=t_process)
    print(json.dumps(out), flush=True)
    return 0
