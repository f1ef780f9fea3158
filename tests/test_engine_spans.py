"""Host spans and timing counters of the serving engine (serving/spans.py).

The engine times itself in three counters on every run (``step_ns``,
``sync_ns``, ``evict_ns``, perf_counter nanoseconds) and, inside a
``jax.profiler`` session, writes named spans into the trace's host plane on
the thread that steps it, with metadata as event stats.
"""
import asyncio
import dataclasses
import glob
import os

import jax
import numpy as np
import pytest

from repro.configs import get
from repro.models import model as M
from repro.serving import engine as E
from repro.serving.api import Request
from repro.serving.config import EngineConfig
from repro.serving.server import AsyncServer

T = 32
PS = 8
TIMERS = ("step_ns", "sync_ns", "evict_ns")


@pytest.fixture(scope="module")
def small():
    cfg = dataclasses.replace(get("starcoder2-7b", smoke=True), dtype="float32")
    return cfg, M.init_params(jax.random.PRNGKey(0), cfg)


def _prompts(cfg, n=6, seed=0):
    # distinct two-page prompts: each leaves two pages in the radix index
    rng = np.random.default_rng(seed)
    return [rng.integers(1, cfg.vocab, size=2 * PS).astype(np.int32) for _ in range(n)]


def _engine(cfg, params, n_pages):
    return E.ServeEngine(cfg, params, config=EngineConfig(
        batch_size=2, max_len=T, cache_layout="paged", page_size=PS, n_pages=n_pages))


@pytest.mark.parametrize("n_pages,evicts", [(8, True), (256, False)])
def test_timers_track_steps_syncs_and_eviction(small, n_pages, evicts):
    """Each request reserves 3 of 8 pages and leaves 2 cached: a pool of 8
    evicts; one of 256 never fills, and its eviction timer stays at zero."""
    cfg, params = small
    eng = _engine(cfg, params, n_pages)
    eng.submit(_prompts(cfg), max_new=4)
    eng.run()
    c = eng.counters
    assert all(isinstance(c[k], int) and c[k] >= 0 for k in TIMERS)
    assert 0 < c["sync_ns"] <= c["step_ns"]
    assert (c["pages_evicted"] > 0) is evicts
    assert (c["evict_ns"] > 0) == (c["pages_evicted"] > 0)


def _events(log_dir):
    """Every host event of the trace as (line key, name, start, end, stats)."""
    from jax.profiler import ProfileData

    path = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True)[0]
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for i, line in enumerate(plane.lines):
            for e in line.events:
                out.append((i, e.name, e.start_ns, e.start_ns + e.duration_ns,
                            dict(e.stats)))
    return out


def _start_trace(log_dir):
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(log_dir), profiler_options=opts)


def test_trace_nests_admission_eviction_and_sync_inside_the_step(small, tmp_path):
    cfg, params = small
    eng = _engine(cfg, params, 8)
    reqs = eng.submit(_prompts(cfg), max_new=4)
    eng.step([])                                  # compile outside the trace
    _start_trace(tmp_path)
    try:
        eng.run()
    finally:
        jax.profiler.stop_trace()
    assert eng.counters["pages_evicted"] > 0
    evs = _events(str(tmp_path))
    steps = [e for e in evs if e[1] == "engine.step"]
    assert steps and all("step_num" in e[4] for e in steps)

    def inside(name):
        # spans of ``name`` on a step's thread line, within that step
        return [e for e in evs if e[1] == name and any(
            s[0] == e[0] and s[2] <= e[2] and e[3] <= s[3] for s in steps)]

    for name in ("engine.admit", "radix.evict", "engine.sync", "engine.launch",
                 "engine.emit", "engine.push_table"):
        assert inside(name), name
    assert len({e[0] for e in steps}) == 1         # one thread stepped the engine
    # an eviction runs inside an admission, and says what it wanted and freed
    admits = inside("engine.admit")
    for ev in inside("radix.evict"):
        assert any(a[2] <= ev[2] and ev[3] <= a[3] for a in admits)
        assert ev[4]["want"] > 0 and "freed" in ev[4]
    # the admitted requests' ids ride as a stat; the name stays bare
    ids = set()
    for a in admits:
        ids.update(str(a[4]["rids"]).split())
    assert ids and ids <= {str(r.rid) for r in reqs}
    assert not any(e[1].startswith("engine.admit") and e[1] != "engine.admit"
                   for e in evs)


def test_server_spans_share_the_request_id(small, tmp_path):
    """Through the server, a request's admission wait and its engine admission
    carry the same id: the server's ``rid``."""
    cfg, params = small
    config = EngineConfig(batch_size=2, max_len=T, cache_layout="paged", page_size=PS)
    prompts = _prompts(cfg, n=4, seed=1)

    async def main():
        async with AsyncServer(cfg, params, config=config, replicas=1, max_queue=1,
                               admission_timeout=600.0) as srv:
            async def one(i):
                async for _ in srv.submit(Request(prompt=prompts[i].tolist(), max_new=3,
                                                  rid=f"r-{i}")):
                    pass
            await one(0)                          # compile outside the trace
            _start_trace(tmp_path)
            try:
                await asyncio.gather(*[one(i) for i in range(1, 4)])
            finally:
                jax.profiler.stop_trace()

    asyncio.run(main())
    evs = _events(str(tmp_path))
    waits = [e for e in evs if e[1] == "server.admission_wait"]
    assert {e[4]["rid"] for e in waits} == {"r-1", "r-2", "r-3"}
    # one in flight at a time: the later submits waited on the queue bound
    assert any(e[4].get("reason") == "queue_full" for e in waits)
    admitted = set()
    for e in evs:
        if e[1] == "engine.admit":
            admitted.update(str(e[4]["rids"]).split())
    assert admitted == {"r-1", "r-2", "r-3"}
    names = {e[1] for e in evs}
    assert {"replica.drain", "server.pool_check", "engine.step"} <= names
