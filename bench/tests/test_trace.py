"""Trace reduction: busy union, idle share, kernel sums and HLO shapes."""
import pytest

from bench.harness import trace as T


def ev(start, dur, name="op", text=""):
    return T.Event(name, float(start), float(dur), text)


def test_busy_is_the_union_of_overlapping_ops():
    evs = [ev(0, 10), ev(5, 10), ev(30, 5), ev(31, 1), ev(50, 0)]
    assert T.union_ns(evs) == 15 + 5
    tr = T.Trace(chips=[T.Chip("/device:TPU:0", evs, [])], host={}, window_s=100e-9)
    assert tr.busy_s == pytest.approx(20e-9)


def test_busy_is_averaged_over_chips():
    a = T.Chip("/device:TPU:0", [ev(0, 10)], [])
    b = T.Chip("/device:TPU:1", [ev(0, 30)], [])
    assert T.Trace(chips=[a, b], host={}, window_s=1.0).busy_s == pytest.approx(20e-9)


def test_idle_gaps_longest_first():
    evs = [ev(0, 10), ev(12, 3), ev(40, 10), ev(45, 1)]
    assert T.idle_gaps(evs) == [(15, 40), (10, 12)]


def test_shapes_of_an_hlo_instruction():
    text = ("long_name=%custom-call.3 = bf16[1024,4608]{1,0} custom-call(s8[1024,4608]{1,0} "
            "%a, s8[4608,4608]{1,0} %b, f32[1024,1]{1,0} %c, f32[1,4608]{1,0} %d)")
    assert T.shapes(text) == [("bf16", (1024, 4608)), ("s8", (1024, 4608)),
                              ("s8", (4608, 4608)), ("f32", (1024, 1)), ("f32", (1, 4608))]


def test_host_attribution_picks_the_event_over_the_gap():
    tr = T.Trace(chips=[T.Chip("/device:TPU:0", [ev(0, 10), ev(100, 10)], [])],
                 host={"/host:CPU/t1": [ev(0, 200, "outer"), ev(20, 70, "_plan_paged")]},
                 window_s=1.0)
    b = T.breakdown(tr)
    assert b["idle_gaps"] == [["_plan_paged", pytest.approx(90e-9)]]
    assert b["device_ops"] == [["op", pytest.approx(20e-9)]]


# A trace of four decode steps of granite-moe.decode-batch (64 slots), recorded
# on one TPU v5e chip by a --trace 1 run with a 0.3 s traced window.
DATA = "decode-batch.xplane.pb.gz"
WINDOW_S = 0.30175510999993094


@pytest.fixture(scope="module")
def chip_trace():
    import gzip

    from jax.profiler import ProfileData

    from bench.harness import spec

    with gzip.open(spec.BENCH / "tests" / "data" / DATA) as f:
        return T.from_profile(ProfileData.from_serialized_xspace(f.read()), WINDOW_S)


def test_recorded_trace_planes_and_programs(chip_trace):
    assert [c.name for c in chip_trace.chips] == ["/device:TPU:0"]
    mods = chip_trace.chips[0].modules
    assert [m.name.split("(")[0] for m in mods] == ["jit_decode_step"] * 4
    # the two whole steps in the window; the trace's edges cut the other two
    assert [m.dur_ns for m in mods[1:3]] == [117310116.0, 117380178.0]


def test_recorded_trace_busy_and_idle(chip_trace):
    chip = chip_trace.chips[0]
    leaf = [e for e in chip.ops if not e.name.startswith(("%while", "%conditional", "%call"))]
    assert len(leaf) == len(chip.ops) - 2            # the scan's while, twice
    # the union by a second route: merge sorted [start, end) intervals
    spans = sorted((e.start_ns, e.end_ns) for e in leaf)
    busy, cur_s, cur_e = 0.0, *spans[0]
    for s, e in spans[1:]:
        if s > cur_e:
            busy += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    busy += cur_e - cur_s
    assert chip_trace.busy_s == pytest.approx(busy / 1e9)
    assert 0 < chip_trace.busy_s < chip_trace.window_s


def test_recorded_trace_kernel_sum_and_shapes(chip_trace):
    from types import SimpleNamespace

    from bench.harness import work

    run = SimpleNamespace(trace=chip_trace)
    ev = work.kernel_events(run, "_paged_decode_attention")
    assert len(ev) == 71
    assert sum(e.dur_ns for e in ev) == pytest.approx(54620044.0)
    # the custom call's result, then its operands: page table, lengths, the
    # query (64 slots, 8 KV heads x 3 query heads, 64 wide), K and V codes in
    # their lane-dense view, then the scale pools
    assert T.shapes(ev[0].name)[:8] == [
        ("bf16", (64, 8, 3, 64)), ("s32", (8192,)), ("s32", (64,)),
        ("bf16", (64, 8, 3, 64)), ("s8", (8192, 8, 512)), ("s8", (8192, 8, 512)),
        ("f32", (8192, 1, 128)), ("f32", (8192, 1, 128))]
    assert T.short_name(ev[0].name) == "%_paged_decode_attention.8 bf16[64,8,3,64] custom-call"
