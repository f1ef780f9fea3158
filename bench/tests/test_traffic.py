"""The generator and the clients' arithmetic."""
import json

import numpy as np
import pytest

from bench.harness import client as C, spec
from bench.harness.traffic import Schedule


# a mix with shared prefixes (70% of prompts open with one of 4 of 256 tokens)
SHARED = dict(loop="open", rate_per_s=4.0, n_requests=4096, block=32, sizes_seed=1203,
              shared=dict(count=4, length=256, share=0.7),
              prompt=dict(dist="lognormal", median=200, sigma=0.8, lo=32, hi=1536),
              output=dict(dist="lognormal", median=128, sigma=0.8, lo=16, hi=512),
              engine=dict(batch_size=32, max_len=2048))
# an open loop of long unshared prompts
OPEN = dict(loop="open", rate_per_s=0.24, n_requests=2048, block=32, sizes_seed=1202,
            prompt=dict(dist="lognormal", median=2048, sigma=0.5, lo=1024, hi=6144),
            output=dict(dist="uniform", lo=16, hi=64),
            engine=dict(batch_size=16, max_len=8192))
MIXES = ["decode-batch", "open", "shared"]


def _mix(name):
    if name in ("shared", "open"):
        return {"shared": SHARED, "open": OPEN}[name]
    with open(spec.BENCH / "traffic" / f"{name}.json") as f:
        return json.load(f)


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_schedule(name):
    a, b = Schedule(_mix(name), 49152, 2**31 + 7), Schedule(_mix(name), 49152, 2**31 + 7)
    for i in range(40):
        ra, rb = a.request(i), b.request(i)
        assert np.array_equal(ra.prompt, rb.prompt)
        assert (ra.max_new, ra.offset_s, ra.prefix_id) == (rb.max_new, rb.offset_s,
                                                           rb.prefix_id)


@pytest.mark.parametrize("name", MIXES)
def test_seeds_share_sizes_and_gaps(name):
    mix = _mix(name)
    a, b = Schedule(mix, 49152, 1), Schedule(mix, 49152, 5_000_000_000)
    blk = mix["block"]
    n = 4 * blk
    assert sorted(a.plen[:n]) == sorted(b.plen[:n])
    assert sorted(a.out[:n]) == sorted(b.out[:n])
    assert not np.array_equal(a.plen[:n], b.plen[:n])          # another order
    if mix["loop"] == "open":
        gaps = lambda s: np.diff(np.concatenate([[0.0], s.offsets[:n]]))
        assert np.allclose(sorted(gaps(a)), sorted(gaps(b)))
    ra, rb = a.request(0), b.request(0)
    assert not np.array_equal(ra.prompt[1:], rb.prompt[1:]) or len(ra.prompt) < 3


@pytest.mark.parametrize("name", MIXES)
def test_requests_fit_and_share_only_as_told(name):
    mix = _mix(name)
    s = Schedule(mix, 49152, 11)
    shared = mix.get("shared")
    firsts = {int(p[0]) for p in s._prefixes}
    for i in range(200):
        r = s.request(i)
        assert len(r.prompt) + r.max_new <= mix["engine"]["max_len"]
        assert r.max_new >= 1 and r.prompt.min() >= 1
        own = r.prompt[shared["length"]:] if r.prefix_id >= 0 else r.prompt
        assert own[0] not in firsts          # no chance prefix between requests
        assert r.prompt[0] < 49152 - 1024    # warm-up prompts start above
        firsts.add(own[0])
        if r.prefix_id >= 0:
            other = s.request(next(j for j in range(200) if j != i
                                   and s.pid[j] == r.prefix_id))
            assert np.array_equal(r.prompt[: shared["length"]],
                                  other.prompt[: shared["length"]])
    if shared:
        share = np.mean(s.pid >= 0)
        assert abs(share - shared["share"]) < 0.05


def test_stagger_spreads_the_first_requests_and_keeps_the_rest():
    mix = _mix("decode-batch")
    assert mix["stagger"] and mix["loop"] == "closed"
    k = mix["clients"]
    a, b = Schedule(mix, 49152, 3), Schedule(mix, 49152, 2**31 + 3)
    plain = Schedule(dict(mix, stagger=False), 49152, 3)
    lo, hi = mix["output"]["lo"], mix["output"]["hi"]
    # each client's first request keeps a part of its output, the same set for
    # every seed, in another order; they end spread over one request's life
    first = a.out[:k]
    assert sorted(first) == sorted(b.out[:k]) and not np.array_equal(first, b.out[:k])
    assert first.min() >= 1 and first.max() <= hi and first.min() < lo
    assert np.std(first) > 0.2 * np.mean(first)
    # the requests that follow are whole, drawn as without stagger
    assert sorted(a.out[k:]) == sorted(plain.out[k:])
    assert a.out[k:].min() >= lo
    assert all(a.request(c).max_new == first[c] for c in range(k))
    assert a.client_requests(5)[:2] == [5, 5 + k]


def _rec(due, times):
    return C.Record(idx=0, prompt=np.ones(4, np.int32), max_new=len(times), due=due,
                    times=list(times), tokens=[1] * len(times))


def test_window_statistics_cover_every_request_and_gap():
    w = C.Window(10.0, 20.0)
    recs = [_rec(9.0, [9.5, 10.5, 11.0]),          # first token before the window
            _rec(12.0, [13.0, 13.1, 13.2, 19.9, 20.1]),
            _rec(15.0, [18.0])]
    # tokens inside [10, 20): 10.5, 11.0, 13.0, 13.1, 13.2, 19.9, 18.0
    assert C.output_tokens(recs, w) == 7
    # TTFT from the due time, for first tokens inside the window
    assert C.ttfts(recs, w) == pytest.approx([1.0, 3.0])
    # every gap whose later token is inside the window, not a mean per request
    g = C.gaps(recs, w)
    assert sorted(g) == pytest.approx(sorted([1.0, 0.5, 0.1, 0.1, 6.7]))
    assert C.p95(g) == pytest.approx(float(np.percentile(g, 95)))
    assert C.p95([]) is None
