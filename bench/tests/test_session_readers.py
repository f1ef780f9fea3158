"""Counters over replicas and the sessions cell's readers."""
from types import SimpleNamespace

import numpy as np
import pytest

from bench.harness import client as C
from bench.harness import runner, spec
from bench.harness import trace as T

NEW = ("prefix_hit_rate.sessions", "affinity_hit_rate.sessions", "mfu.sessions",
       "device_idle.sessions")
# readers the decode cell had, which read the sessions cell's counters and trace too
SHARED = ("slot_occupancy", "host_ms_per_step", "step_device_ms")


def _server(*counters):
    reps = [SimpleNamespace(engine=None if c is None else SimpleNamespace(counters=c))
            for c in counters]
    return SimpleNamespace(replicas=reps, counters={"routed": 9, "submitted": 9},
                           router=SimpleNamespace(affinity_hits=4))


def test_engine_counters_are_summed_over_replicas():
    a = {"prompt_tokens": 100, "prefix_tokens_reused": 40, "decode_steps": 7}
    b = {"prompt_tokens": 50, "prefix_tokens_reused": 5, "decode_steps": 3}
    assert runner.engine_counters(_server(a, b)) == {
        "prompt_tokens": 150, "prefix_tokens_reused": 45, "decode_steps": 10}
    # one replica: its own counters; a replica being rebuilt adds nothing
    assert runner.engine_counters(_server(a)) == a
    assert runner.engine_counters(_server(a, None)) == a
    assert runner.server_counters(_server(a)) == {"routed": 9, "submitted": 9,
                                                  "affinity_hits": 4}


def test_each_reader_is_declared_for_the_sessions_cell():
    per_layer = {m["name"]: m for m in spec.load_benchmark()["per_layer"]}
    for name in NEW:
        assert per_layer[name]["workloads"] == ["granite-moe.sessions-x4"]
        assert callable(spec.reader(name))
    for name in SHARED:
        assert "granite-moe.sessions-x4" in per_layer[name]["workloads"]


def _run(counters=None, server=None, recs=(), trace=None, tw=None, chips=4,
         trace_counters=None):
    cell = spec.load_cell("granite-moe.sessions-x4")
    cell.chips = chips
    from bench.harness import peaks
    return runner.Run(cell=cell, recs=list(recs), window=C.Window(0.0, 45.0), setup_s=0.0,
                      counters=counters or {}, server=server or {}, trace=trace,
                      trace_window=tw, trace_counters=trace_counters or {},
                      peaks=peaks.peaks("TPU v5 lite"))


# Counters over the window of one granite-moe.sessions-x4 run on four v5e chips
# (seed 1503001), as its stderr lines give them: the engines' summed over the
# four replicas, and the server's with the router's affinity hits.
RECORDED_ENGINE = {"active_slot_steps": 16711, "chunk_decode_rows": 16711,
                   "chunk_prefill_rows": 100101, "chunk_steps": 614, "decode_steps": 605,
                   "mid_decode_admissions": 241, "prefill_calls": 250,
                   "prefill_tokens": 100303, "prefix_hits": 111,
                   "prefix_tokens_reused": 66528, "prompt_tokens": 166831,
                   "step_ns": 179143712927, "sync_ns": 175149991463}
RECORDED_SERVER = {"affinity_hits": 112, "completed": 112, "routed": 240, "submitted": 240}


def test_prefix_hit_rate_over_the_window():
    read = spec.reader("prefix_hit_rate.sessions")
    assert read(_run(RECORDED_ENGINE)) == pytest.approx(100.0 * 66528 / 166831)
    assert read(_run({"prompt_tokens": 4000, "prefix_tokens_reused": 2200})) == \
        pytest.approx(55.0)
    assert read(_run({"prompt_tokens": 4000})) == 0.0
    assert read(_run({"prompt_tokens": 0, "prefix_tokens_reused": 0})) is None
    assert read(_run({})) is None


def test_affinity_hit_rate_over_the_window():
    read = spec.reader("affinity_hit_rate.sessions")
    assert read(_run(server=RECORDED_SERVER)) == pytest.approx(100.0 * 112 / 240)
    assert read(_run(server={"routed": 80, "affinity_hits": 52})) == pytest.approx(65.0)
    assert read(_run(server={"routed": 0, "affinity_hits": 0})) is None
    assert read(_run(server={"routed": 80})) is None


def test_engine_readers_read_the_summed_counters():
    c = RECORDED_ENGINE
    assert spec.reader("slot_occupancy")(_run(c)) == pytest.approx(
        100.0 * 16711 / (605 * 32))
    assert spec.reader("host_ms_per_step")(_run(c)) == pytest.approx(
        (179143712927 - 175149991463) / 605 / 1e6)


def _chip(name, spans):
    ev = [T.Event(name, s * 1e9, (e - s) * 1e9, "") for s, e in spans]
    return T.Chip(name=name, ops=ev, modules=ev)


def test_device_idle_is_averaged_over_the_chips():
    read = spec.reader("device_idle.sessions")
    tr = T.Trace(chips=[_chip("a", [(0.0, 2.0)]), _chip("b", [(0.0, 1.0), (2.0, 2.5)])],
                 host={}, window_s=3.0)
    assert read(_run(trace=tr)) == pytest.approx(100.0 * (1 - (2.0 + 1.5) / 2 / 3.0))
    assert read(_run()) is None


def test_mfu_counts_chunked_prompt_rows_and_decodes_over_all_chips():
    from bench.harness import flops

    read = spec.reader("mfu.sessions")
    tw = C.Window(10.0, 13.0)
    tr = T.Trace(chips=[], host={}, window_s=3.0)
    prompt = np.ones(1000, np.int32)
    # waiting for its first token over [9, 10.5] from its send, 0.5 s of it
    # inside the traced window; two decodes inside, one after
    r = C.Record(idx=0, prompt=prompt, max_new=4, due=9.0, submitted=9.0,
                 times=[10.5, 11.0, 12.0, 13.5], tokens=[1, 2, 3, 4])
    # waiting over [12, 14], its first token after the traced window: 1 s inside
    q = C.Record(idx=1, prompt=np.ones(2500, np.int32), max_new=4, due=12.0,
                 submitted=12.0, times=[14.0], tokens=[1])
    run = _run(recs=[r, q], trace=tr, tw=tw, trace_counters={"chunk_prefill_rows": 600})
    m = run.cell.model.work(run.cell.config)
    ctx = (0.5 * 1000 + 1.0 * 2500) / 1.5
    w = (flops.prompt_rows_work(m, 600, ctx) + flops.Work(bf16_ops=m.head_ops)
         + flops.token_work(m, 1001) + flops.token_work(m, 1002))
    want = 100.0 * w.least_seconds(run.peaks) / (3.0 * 4)
    assert read(run) == pytest.approx(want)
    one = _run(recs=[r, q], trace=tr, tw=tw, chips=1,
               trace_counters={"chunk_prefill_rows": 600})
    assert read(one) == pytest.approx(4 * want)
    # no prompt rows inside: the decodes and the head alone
    bare = _run(recs=[r], trace=tr, tw=tw, trace_counters={"chunk_prefill_rows": 0})
    w0 = (flops.Work(bf16_ops=m.head_ops) + flops.token_work(m, 1001)
          + flops.token_work(m, 1002))
    assert read(bare) == pytest.approx(100.0 * w0.least_seconds(run.peaks) / 12.0)
    assert read(_run(recs=[r])) is None                    # no trace
    assert read(_run(recs=[r], trace=tr, tw=tw)) is None   # no chunk counter
    late = C.Record(idx=1, prompt=prompt, max_new=1, due=20.0, submitted=20.0,
                    times=[20.5], tokens=[1])
    assert read(_run(recs=[late], trace=tr, tw=tw,
                     trace_counters={"chunk_prefill_rows": 0})) is None


def test_ttft_p95_counts_first_tokens_in_the_window_from_the_send():
    read = spec.reader("ttft_p95_ms")
    recs = [C.Record(idx=i, prompt=np.ones(4, np.int32), max_new=2, due=d,
                     times=[d + t, d + t + 0.1], tokens=[1, 1])
            for i, (d, t) in enumerate([(-1.0, 1.5), (1.0, 0.2), (2.0, 0.4), (44.9, 0.5)])]
    # first tokens at 0.5, 1.2, 2.4 in the window; 45.4 after its close
    assert read(_run(recs=recs)) == pytest.approx(
        1000.0 * float(np.percentile([1.5, 0.2, 0.4], 95)))
    assert read(_run()) is None
