"""Async serving front end (DESIGN.md §3.11): AsyncServer streams token-exact
output vs a direct ``ServeEngine.run()`` of the same prompts on every path ×
KV mode × layout; bounded admission rejects with a typed error past the
deadline; prefix-affinity routing keeps shared-prefix traffic on one replica;
a killed replica's in-flight requests complete on survivors with no token
loss (and the replica restarts, or goes dead once its budget is spent)."""
import asyncio
import dataclasses

import jax
import numpy as np
import pytest

from repro.configs import get
from repro.core import qlinear as ql
from repro.models import model as M
from repro.models.quantize import quantize_tree
from repro.runtime import FailureInjector
from repro.serving import engine as E
from repro.serving.api import AdmissionError, FinishReason, Request
from repro.serving import server as server_mod
from repro.serving.config import EngineConfig
from repro.serving.server import AsyncServer

T = 32
LENS = [6, 9, 5, 12]
MAX_NEW = [5, 3, 6, 4]


@pytest.fixture(scope="module")
def small():
    cfg = dataclasses.replace(get("starcoder2-7b", smoke=True), dtype="float32")
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    qparams = quantize_tree(params, ql.W8A8_INT8)
    return cfg, params, qparams


def _prompts(cfg, lens=LENS, seed=0, shared=None):
    rng = np.random.default_rng(seed)
    pre = shared if shared is not None else np.zeros(0, np.int32)
    return [np.concatenate([pre, rng.integers(1, cfg.vocab, size=n)
                            .astype(np.int32)]) for n in lens]


def _reference(cfg, params, config, prompts, max_new, quant=None):
    """Direct synchronous ServeEngine.run() of the same workload."""
    eng = E.ServeEngine(cfg, params, config=config, quant=quant)
    eng.submit([p.copy() for p in prompts], max_new=list(max_new))
    done = eng.run()
    return {tuple(p.tolist()): r.out for p, r in zip(prompts, done)}


async def _collect(srv, req):
    toks, fin = [], None
    async for ev in srv.submit(req):
        if ev.kind == "token":
            toks.append(ev.token)
        elif ev.kind == "finished":
            fin = ev
        else:
            raise AssertionError(f"stream error: {ev.error}")
    return toks, fin


# pairwise coverage of every path, KV mode and layout
MATRIX = [("fake", "fp", "dense"), ("fake", "int8", "paged"),
          ("dequant-fp", "fp", "paged"), ("dequant-fp", "int8", "dense"),
          ("fused-int8", "fp", "dense"), ("fused-int8", "int8", "paged")]


class TestStreamingParity:
    @pytest.mark.parametrize("path,kv,layout", MATRIX)
    def test_streams_token_exact_vs_direct_engine(self, small, path, kv,
                                                  layout):
        cfg, params, qparams = small
        if path == "fake":
            serve_params, quant = params, ql.W8A8_CROSSQUANT
        else:
            serve_params, quant = qparams, ql.W8A8_INT8
        config = EngineConfig(batch_size=2, max_len=T, path=path,
                              kv_cache=kv, cache_layout=layout)
        prompts = _prompts(cfg)
        want = _reference(cfg, serve_params, config, prompts, MAX_NEW,
                          quant=quant)

        async def main():
            async with AsyncServer(cfg, serve_params, config=config,
                                   replicas=2, quant=quant) as srv:
                res = await asyncio.gather(*[
                    _collect(srv, Request(prompt=p.tolist(), max_new=mn))
                    for p, mn in zip(prompts, MAX_NEW)])
            for (toks, fin), p in zip(res, prompts):
                assert toks == want[tuple(p.tolist())], (path, kv, layout)
                assert fin.finish_reason == FinishReason.LENGTH
                assert fin.metrics.n_tokens == len(toks)
                assert fin.metrics.ttft_s >= 0.0

        asyncio.run(main())

    def test_chunked_config_streams_token_exact(self, small):
        cfg, params, _ = small
        config = EngineConfig(batch_size=2, max_len=T, cache_layout="paged",
                              chunked=True, token_budget=16)
        prompts = _prompts(cfg, seed=4)
        want = _reference(cfg, params, config, prompts, MAX_NEW)

        async def main():
            async with AsyncServer(cfg, params, config=config,
                                   replicas=2) as srv:
                res = await asyncio.gather(*[
                    _collect(srv, Request(prompt=p.tolist(), max_new=mn))
                    for p, mn in zip(prompts, MAX_NEW)])
            for (toks, _), p in zip(res, prompts):
                assert toks == want[tuple(p.tolist())]

        asyncio.run(main())

    def test_finish_reasons(self, small):
        """EOS truncates the stream with FinishReason.EOS; a prompt that fills
        its cache row retires as CACHE_FULL after the last append."""
        cfg, params, _ = small
        config = EngineConfig(batch_size=2, max_len=T)
        prompt = _prompts(cfg, lens=[8], seed=5)[0]
        base = _reference(cfg, params, config, [prompt], [6])
        eos = base[tuple(prompt.tolist())][2]     # third greedy token
        cfg_eos = EngineConfig(batch_size=2, max_len=T, eos_id=eos)
        long_prompt = _prompts(cfg, lens=[T - 2], seed=6)[0]

        async def main():
            async with AsyncServer(cfg, params, config=cfg_eos,
                                   replicas=1) as srv:
                toks, fin = await _collect(
                    srv, Request(prompt=prompt.tolist(), max_new=6))
                assert fin.finish_reason == FinishReason.EOS
                assert toks == base[tuple(prompt.tolist())][:3]
                toks, fin = await _collect(
                    srv, Request(prompt=long_prompt.tolist(), max_new=8))
                assert fin.finish_reason == FinishReason.CACHE_FULL
                assert len(toks) == 3     # admit fills T-2; two appends hit T

        asyncio.run(main())

    def test_kernel_proportion_metric(self, small):
        """kernel_stats=True reports the paper's §4.1 quantization-kernel
        proportion measured on the request's own served tokens."""
        cfg, params, _ = small
        config = EngineConfig(batch_size=1, max_len=T, path="fake")

        async def main():
            async with AsyncServer(cfg, params, config=config, replicas=1,
                                   quant=ql.W8A8_CROSSQUANT,
                                   kernel_stats=True) as srv:
                _, fin = await _collect(
                    srv, Request(prompt=_prompts(cfg)[0].tolist(), max_new=4))
                kp = fin.metrics.kernel_proportion
                assert kp is not None and 0.0 < kp <= 1.0

        asyncio.run(main())


class TestAdmission:
    def test_backpressure_rejects_past_deadline(self, small):
        """With every replica frozen, submits past max_queue wait for the
        admission deadline and then fail with the typed AdmissionError;
        resuming drains the queued requests to completion."""
        cfg, params, _ = small
        config = EngineConfig(batch_size=1, max_len=T)
        prompts = _prompts(cfg, lens=[6, 6, 6], seed=7)

        async def main():
            async with AsyncServer(cfg, params, config=config, replicas=2,
                                   max_queue=2,
                                   admission_timeout=0.05) as srv:
                srv.pause()
                tasks = [asyncio.create_task(
                    _collect(srv, Request(prompt=p.tolist(), max_new=3)))
                    for p in prompts[:2]]
                await asyncio.sleep(0.02)         # both hold admission slots
                t0 = asyncio.get_running_loop().time()
                with pytest.raises(AdmissionError) as ei:
                    await _collect(srv, Request(prompt=prompts[2].tolist(),
                                                max_new=3))
                assert ei.value.reason == "queue_full"
                assert asyncio.get_running_loop().time() - t0 >= 0.05
                assert srv.counters["rejected"] == 1
                srv.resume()
                for (toks, fin) in await asyncio.gather(*tasks):
                    assert len(toks) == 3 and fin.kind == "finished"

        asyncio.run(main())

    def test_pool_pressure_rejects_oversized_reservation(self, small):
        """Paged layout: a request whose worst-case page reservation exceeds
        the whole pool rejects with reason="pool_pressure" — no amount of
        waiting could ever serve it — while a right-sized request on the same
        server admits and finishes; the in-flight queue never fills."""
        cfg, params, _ = small
        config = EngineConfig(batch_size=1, max_len=T, cache_layout="paged",
                              page_size=8, n_pages=3)
        big = _prompts(cfg, lens=[20], seed=13)[0]   # 20+8-1 toks -> 4 pages
        ok = _prompts(cfg, lens=[6], seed=14)[0]     # 6+3-1 toks  -> 1 page

        async def main():
            async with AsyncServer(cfg, params, config=config, replicas=1,
                                   admission_timeout=0.05) as srv:
                with pytest.raises(AdmissionError) as ei:
                    await _collect(srv, Request(prompt=big.tolist(),
                                                max_new=8))
                assert ei.value.reason == "pool_pressure"
                assert srv.counters["rejected"] == 1
                toks, fin = await _collect(srv, Request(prompt=ok.tolist(),
                                                        max_new=3))
                assert len(toks) == 3 and fin.kind == "finished"

        asyncio.run(main())

    def test_pool_pressure_transient_admits_after_release(self, small):
        """Pinning every free page (as live sequences would) makes submits
        reject with reason="pool_pressure"; releasing the pages lets the same
        request admit and finish."""
        cfg, params, _ = small
        config = EngineConfig(batch_size=1, max_len=T, cache_layout="paged",
                              page_size=8)
        prompt = _prompts(cfg, lens=[6], seed=15)[0]

        async def main():
            async with AsyncServer(cfg, params, config=config, replicas=1,
                                   max_queue=4,
                                   admission_timeout=0.05) as srv:
                while srv.replicas[0].engine is None:     # replica warms up
                    await asyncio.sleep(0.01)
                pool = srv.replicas[0].engine.pool
                held = pool.alloc(pool.free_count)
                assert held is not None
                with pytest.raises(AdmissionError) as ei:
                    await _collect(srv, Request(prompt=prompt.tolist(),
                                                max_new=3))
                assert ei.value.reason == "pool_pressure"
                pool.decref(held)
                toks, fin = await _collect(srv, Request(prompt=prompt.tolist(),
                                                        max_new=3))
                assert len(toks) == 3 and fin.kind == "finished"

        asyncio.run(main())


class TestRouting:
    def test_affinity_keeps_shared_prefixes_together(self, small):
        """Two prefix families land on two different replicas (least-loaded
        seeds the split while both are busy); every follow-up request routes
        to the replica whose radix cache holds its prefix, so both engines
        see real §3.8 prefix hits."""
        cfg, params, _ = small
        config = EngineConfig(batch_size=2, max_len=T, cache_layout="paged",
                              page_size=8)
        rng = np.random.default_rng(8)
        pre_a = rng.integers(1, cfg.vocab, size=16).astype(np.int32)
        pre_b = rng.integers(1, cfg.vocab, size=16).astype(np.int32)
        fam_a = _prompts(cfg, lens=[5, 6, 7, 8], seed=9, shared=pre_a)
        fam_b = _prompts(cfg, lens=[5, 6, 7, 8], seed=10, shared=pre_b)

        async def main():
            async with AsyncServer(cfg, params, config=config,
                                   replicas=2) as srv:
                # freeze: the two seed requests dispatch while both replicas
                # are busy, so least-loaded splits them across the fleet
                srv.pause()
                seeds = [asyncio.create_task(
                    _collect(srv, Request(prompt=p.tolist(), max_new=3)))
                    for p in (fam_a[0], fam_b[0])]
                await asyncio.sleep(0.02)
                srv.resume()
                (ra, rb) = [fin.metrics.replica
                            for _, fin in await asyncio.gather(*seeds)]
                assert ra != rb
                for fam, home in ((fam_a, ra), (fam_b, rb)):
                    for p in fam[1:]:
                        _, fin = await _collect(
                            srv, Request(prompt=p.tolist(), max_new=3))
                        assert fin.metrics.replica == home
                        assert fin.metrics.prefix_reused >= 16
                assert srv.router.affinity_hits >= 6
                m = srv.metrics()
                for rep in m["replicas"]:
                    assert rep["engine"]["prefix_hit_rate"] > 0.0

        asyncio.run(main())


class TestReplicaFailure:
    def test_killed_replica_drains_to_survivor_token_exact(self, small):
        """Replica 0 dies mid-decode; its in-flight requests are requeued onto
        replica 1 as prompt+emitted continuations and every request's total
        stream equals the no-failure reference, token for token. Replica 0
        restarts and serves again."""
        cfg, params, _ = small
        config = EngineConfig(batch_size=2, max_len=T, cache_layout="paged")
        prompts = _prompts(cfg, seed=11)
        want = _reference(cfg, params, config, prompts, [8] * 4)

        async def main():
            inj = {0: FailureInjector(fail_at_steps=(3,))}
            async with AsyncServer(cfg, params, config=config, replicas=2,
                                   injectors=inj, max_restarts=2) as srv:
                res = await asyncio.gather(*[
                    _collect(srv, Request(prompt=p.tolist(), max_new=8,
                                          replica_hint=0))
                    for p in prompts])
                requeued = 0
                for (toks, fin), p in zip(res, prompts):
                    assert toks == want[tuple(p.tolist())], "token loss"
                    requeued += fin.metrics.requeues
                assert requeued >= 1          # the failure interrupted work
                m = srv.metrics()
                assert m["server"]["restarts"] == 1
                assert m["replicas"][0]["state"] == "live"
                assert m["replicas"][0]["restarts"] == 1
                # the restarted replica serves new traffic again
                _, fin = await _collect(srv, Request(
                    prompt=prompts[0].tolist(), max_new=4, replica_hint=0))
                assert fin.metrics.replica == 0

        asyncio.run(main())

    def test_restart_budget_exhaustion_marks_replica_dead(self, small):
        """max_restarts=0: the first failure kills replica 0 for good; its
        requests still complete on the survivor and later traffic never
        routes to the dead replica (even with a hint)."""
        cfg, params, _ = small
        config = EngineConfig(batch_size=2, max_len=T)
        prompts = _prompts(cfg, seed=12)
        want = _reference(cfg, params, config, prompts, [6] * 4)

        async def main():
            inj = {0: FailureInjector(fail_at_steps=(2,))}
            async with AsyncServer(cfg, params, config=config, replicas=2,
                                   injectors=inj, max_restarts=0) as srv:
                res = await asyncio.gather(*[
                    _collect(srv, Request(prompt=p.tolist(), max_new=6,
                                          replica_hint=0))
                    for p in prompts])
                for (toks, _), p in zip(res, prompts):
                    assert toks == want[tuple(p.tolist())]
                assert srv.metrics()["replicas"][0]["state"] == "dead"
                _, fin = await _collect(srv, Request(
                    prompt=prompts[0].tolist(), max_new=3, replica_hint=0))
                assert fin.metrics.replica == 1   # hint ignored: replica dead

        asyncio.run(main())


class TestReplicaBuild:
    def test_build_failure_raises_instead_of_failover(self, small, monkeypatch):
        """An engine that cannot be built will not build on a restart either:
        start() raises with the engine's error as the cause, and nothing is
        restarted or requeued."""
        cfg, params, _ = small

        def broken(*args, **kwargs):
            raise ValueError("engine cannot be built")

        monkeypatch.setattr(server_mod, "ServeEngine", broken)

        async def main():
            srv = AsyncServer(cfg, params, replicas=2,
                              config=EngineConfig(batch_size=2, max_len=T))
            with pytest.raises(RuntimeError, match="failed to build") as err:
                await srv.start()
            assert isinstance(err.value.__cause__, ValueError)
            assert srv.counters["restarts"] == 0
            assert all(r.health.state == "dead" for r in srv.replicas)

        asyncio.run(main())

    def test_pinned_replica_holds_params_and_caches_on_its_device(self, small):
        cfg, params, _ = small
        dev = jax.devices()[-1]

        async def main():
            async with AsyncServer(cfg, params, replicas=1, devices=[dev],
                                   config=EngineConfig(batch_size=2,
                                                       max_len=T)) as srv:
                eng = srv.replicas[0].engine
                for tree in (eng.params, eng.caches):
                    assert {d for x in jax.tree_util.tree_leaves(tree)
                            for d in x.devices()} == {dev}

        asyncio.run(main())
