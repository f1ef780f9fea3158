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


# ---------------------------------------------------------------- sessions

def _sessions(seed=2**31 + 11):
    from bench.harness.traffic import schedule
    return schedule(_mix("sessions"), 49155, seed)


def test_sessions_same_seed_same_sessions_and_sizes_across_seeds():
    a, b, other = _sessions(), _sessions(), _sessions(5_000_000_000)
    assert a.loop == "sessions"
    for s in range(20):
        assert a.turns[s] == b.turns[s]
        for k in range(int(a.turns[s])):
            assert np.array_equal(a.user_tokens(s, k), b.user_tokens(s, k))
    assert np.array_equal(a.out, b.out) and np.array_equal(a.prompt, b.prompt)
    assert np.array_equal(a.opening, b.opening)
    assert all(np.array_equal(a.history(c), b.history(c)) for c in range(8))
    # another seed: the same sessions' sizes in another order, other tokens
    k, blk = a.clients, a.mix["block"]
    for lo, hi in ((0, k), (k, k + blk)):
        assert sorted(map(tuple, a.out[lo:hi])) == sorted(map(tuple, other.out[lo:hi]))
        assert sorted(a.turns[lo:hi]) == sorted(other.turns[lo:hi])
        assert sorted(a.opening[lo:hi]) == sorted(other.opening[lo:hi])
    assert not np.array_equal(a.turns[k:k + blk], other.turns[k:k + blk])
    assert not np.array_equal(a.user_tokens(0, 0)[1:], other.user_tokens(0, 0)[1:])


def test_session_turn_resends_its_history():
    s = _sessions()
    sid = next(i for i in range(s.clients, s.n) if s.turns[i] >= 3)
    assert s.opening[sid] == 0 and len(s.history(sid)) == 0
    prompt = served = None
    for k in range(int(s.turns[sid])):
        p = s.turn(sid, k, prompt, served)
        if k:
            # the previous prompt, then its served answer, then a new user part
            assert np.array_equal(p.prompt[: len(prompt)], prompt)
            assert np.array_equal(p.prompt[len(prompt): len(prompt) + len(served)], served)
            assert len(p.prompt) == len(prompt) + len(served) + s.user[sid, k]
        else:
            assert p.prompt[0] == 1 + sid                   # unique first token
        assert len(p.prompt) == s.prompt[sid, k]            # as planned: answers whole
        prompt = p.prompt
        served = np.random.default_rng(k).integers(1, 49155, p.max_new).astype(np.int32)
    assert p.idx == s.turn(sid, 0).idx + k


def test_opening_turn_follows_a_history_of_planned_turns():
    s = _sessions()
    k = s.clients
    # each client's first session opens at a turn drawn uniformly from its turns
    assert (s.opening[:k] < s.turns[:k]).all() and (s.opening[k:] == 0).all()
    assert len(set(s.opening[:k].tolist())) >= 5 and (s.opening[:k] == 0).any()
    sid = next(c for c in range(k) if s.opening[c] >= 2)
    o = int(s.opening[sid])
    h = s.history(sid)
    # the earlier turns' user parts, each followed by an answer of its planned length
    at = 0
    for j in range(o):
        assert np.array_equal(h[at: at + s.user[sid, j]], s.user_tokens(sid, j))
        at += int(s.user[sid, j]) + int(s.out[sid, j])
    assert at == len(h) and h[0] == 1 + sid and h.min() >= 1
    p = s.turn(sid, o)
    assert np.array_equal(p.prompt[: len(h)], h)
    assert np.array_equal(p.prompt[len(h):], s.user_tokens(sid, o))
    assert len(p.prompt) == s.prompt[sid, o] and p.max_new == s.out[sid, o]
    assert s.history(next(c for c in range(k) if s.opening[c] == 0)).size == 0


def test_sessions_fit_max_len_and_stagger_the_first_answers():
    s = _sessions()
    mix = s.mix
    max_len = mix["engine"]["max_len"]
    for sid in range(s.n):
        t = int(s.turns[sid])
        assert 1 <= t <= mix["turns"]["hi"]
        assert (s.prompt[sid, :t] + s.out[sid, :t] <= max_len).all()
    assert sorted(s.prompt_lengths()) == sorted(
        int(s.prompt[sid, k]) for sid in range(s.n) for k in range(int(s.turns[sid])))
    k = s.clients
    lo = mix["output"]["lo"]
    # the answer of each client's opening turn is cut, and no other
    first = s.out[np.arange(k), s.opening[:k]]
    assert first.min() >= 1 and first.min() < lo and np.std(first) > 0.2 * np.mean(first)
    later = np.arange(s.out.shape[1]) != s.opening[:k, None]
    assert s.out[:k][later].min() >= lo and s.out[k:].min() >= lo
    assert s.client_sessions(3)[:2] == [3, 3 + k]


def test_sessions_end_where_the_next_turn_would_not_fit():
    from bench.harness.traffic import schedule
    base = _mix("sessions")
    mix = dict(base, n_sessions=200, stagger=False, engine=dict(base["engine"], max_len=2048))
    s = schedule(mix, 49155, 7)
    short = [sid for sid in range(s.n) if s.turns[sid] < mix["turns"]["lo"]]
    assert short                      # fewer turns than any session draws: cut
    for sid in short:
        t = int(s.turns[sid])
        assert s.prompt[sid, t] + s.out[sid, t] > 2048
    assert all((s.prompt[sid, : s.turns[sid]] + s.out[sid, : s.turns[sid]] <= 2048).all()
               for sid in range(s.n))


class _FakeServer:
    """Answers every request with ``max_new`` tokens, one every ``step`` s."""

    def __init__(self, step):
        self.step, self.sent = step, []

    async def submit(self, req):
        import asyncio
        from types import SimpleNamespace

        self.sent.append(req)
        for j in range(req.max_new):
            await asyncio.sleep(self.step)
            yield SimpleNamespace(kind="token", token=7 + j)
        yield SimpleNamespace(kind="finished", metrics=SimpleNamespace(prefix_reused=0,
                                                                      replica=0))


def _drive(sched, seconds):
    import asyncio
    from types import SimpleNamespace

    srv = _FakeServer(0.002)
    opened = []
    recs, w, _ = asyncio.run(C.drive(srv, lambda **kw: SimpleNamespace(**kw), sched,
                                     seconds, on_open=opened.append))
    return srv, recs, w, opened


def test_sessions_window_opens_after_every_opening_turn_has_its_first_token():
    from bench.harness.traffic import schedule

    mix = dict(_mix("sessions"), clients=6, n_sessions=64, block=8)
    sched = schedule(mix, 49155, 3)
    srv, recs, w, opened = _drive(sched, 0.3)
    assert opened == [w] and w.seconds == pytest.approx(0.3)
    # every client's opening turn, with its history, was sent and had its
    # first token before the window opened: its prefill falls in set-up
    openers = recs[:6]
    for c, r in enumerate(openers):
        s0 = sched.client_sessions(c)[0]
        assert r.idx == sched.turn(s0, int(sched.opening[s0])).idx
        assert len(r.prompt) == sched.prompt[s0, sched.opening[s0]]
        assert r.times and r.times[0] <= w.start and r.due < w.start
    # the next turn resends the opening turn with the tokens it was served
    nxt = {r.idx: r for r in recs[6:]}
    follow = [(o, nxt[o.idx + 1]) for c, o in enumerate(openers)
              if sched.opening[c] + 1 < sched.turns[c] and o.idx + 1 in nxt]
    assert follow
    for o, r in follow:
        assert r.due >= w.start
        assert np.array_equal(r.prompt[: len(o.prompt)], o.prompt)
        assert r.prompt[len(o.prompt): len(o.prompt) + len(o.tokens)].tolist() == o.tokens


def test_closed_loop_window_opens_at_once():
    mix = dict(_mix("decode-batch"), clients=4)
    sched = Schedule(mix, 49152, 3)
    srv, recs, w, opened = _drive(sched, 0.1)
    assert opened == [w] and recs and min(r.due for r in recs) >= w.start
