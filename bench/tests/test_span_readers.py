"""Readers of the engine's timing counters and host spans, on hand-made runs."""
import pytest

from bench.harness import client as C
from bench.harness import runner, spec
from bench.harness import trace as T

NEW = ("host_ms_per_step", "evict_ms_per_admit", "idle_in_admit.decode")


def run_of(counters=None, trace=None):
    return runner.Run(cell=None, recs=[], window=C.Window(0.0, 45.0), setup_s=0.0,
                      counters=counters or {}, trace=trace)


def ev(start, end, name="op"):
    return T.Event(name, float(start), float(end - start), "")


def test_each_reader_is_declared_for_the_decode_cell():
    b = spec.load_benchmark()
    per_layer = {m["name"]: m for m in b["per_layer"]}
    cells = {"host_ms_per_step": ["granite-moe.decode-batch", "granite-moe.sessions-x4"]}
    for name in NEW:
        assert per_layer[name]["workloads"] == cells.get(name, ["granite-moe.decode-batch"])
        assert callable(spec.reader(name))


def test_host_ms_per_step_leaves_out_device_waits():
    read = spec.reader("host_ms_per_step")
    c = {"step_ns": 5_000_000_000, "sync_ns": 4_000_000_000, "decode_steps": 100}
    assert read(run_of(c)) == pytest.approx(10.0)
    assert read(run_of(dict(c, decode_steps=0))) is None
    # a program without the timers (the counters of an older engine)
    assert read(run_of({"decode_steps": 100})) is None


def test_evict_ms_per_admit():
    read = spec.reader("evict_ms_per_admit")
    assert read(run_of({"evict_ns": 2_000_000_000, "prefill_calls": 40})) == pytest.approx(50.0)
    assert read(run_of({"evict_ns": 0, "prefill_calls": 40})) == 0.0
    assert read(run_of({"evict_ns": 7, "prefill_calls": 0})) is None
    assert read(run_of({"prefill_calls": 40})) is None


def _trace(host, window_ns=200):
    ops = [ev(0, 10), ev(20, 30), ev(100, 110)]
    return T.Trace(chips=[T.Chip("/device:TPU:0", ops, [])], host=host,
                   window_s=window_ns / 1e9)


def test_idle_in_admit_counts_only_idle_time_inside_admissions():
    read = spec.reader("idle_in_admit.decode")
    # admissions [5, 25] and [40, 120], the second split in two overlapping
    # spans; the chip is busy in 5 + 5 + 10 ns of them, idle in 80 of 100
    host = {"/host:CPU/1/python": [ev(0, 200, "engine.step"), ev(5, 25, "engine.admit"),
                                   ev(40, 90, "engine.admit"), ev(80, 120, "engine.admit"),
                                   ev(30, 100, "radix.evict")]}
    tr = _trace(host)
    got = read(run_of(trace=tr))
    assert got == pytest.approx(100.0 * 80 / 200)
    # never more than the chip's whole idle share
    assert got <= spec.reader("device_idle.decode")(run_of(trace=tr))


def test_idle_in_admit_without_admissions_or_spans():
    read = spec.reader("idle_in_admit.decode")
    steps_only = {"/host:CPU/1/python": [ev(0, 200, "engine.step")]}
    assert read(run_of(trace=_trace(steps_only))) == 0.0
    # a program that writes no spans: nothing to read
    assert read(run_of(trace=_trace({"/host:CPU/1/python": [ev(5, 25, "admit")]}))) is None
    assert read(run_of()) is None
    assert read(run_of(trace=_trace(steps_only, window_ns=0))) is None
