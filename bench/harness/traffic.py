"""One general traffic generator, driven by a mix file and ``--seed``.

A mix file (``bench/traffic/<mix>.json``) gives the loop (``closed`` with a
client count, ``open`` with a Poisson rate, or ``sessions``, below), the prompt
and output length distributions, optional shared prefixes, and the ``EngineConfig`` the traffic
sizes. Lengths and arrival gaps are drawn once from the mix's own
``sizes_seed``, so every run seed sees the same sizes and gaps; the run seed
permutes them within blocks of ``block`` requests and draws every token.

A closed loop with ``"stagger": true`` opens as it would run in steady state:
each client's first request keeps only a remaining part of its output, drawn
uniformly from its length, so completions and the admissions that follow them
spread over the window instead of coming in one wave when every request was
sent at once. The first requests are permuted among themselves.

Prompt token ids are random in ``[1, vocab)``, except that the first token of
each request's own part is unique within a run (``1 + index``), and so is the
first token of each shared prefix (``n_requests + 1 + k``): two requests share
a prefix only where the mix says so, never by a chance match of their first
tokens, which would send the engine down its prefix-reuse path (and its page
copy) at random.

``"loop": "sessions"`` is a closed loop of multi-turn chats: each client runs
one session at a time, and a turn is sent when the previous answer has come
back. A turn's prompt is the previous prompt, then the tokens the server
returned for the previous answer, then a new user part, as a chat client
resends its history. Turns per session, the first user part, later user parts
and answers have distributions of their own (``turns``, ``first``, ``user``,
``output``), drawn once from ``sizes_seed``; a session ends early where its
next prompt plus its answer would pass ``max_len``. The run seed permutes the
sessions within blocks of ``block`` and draws every user token; the first token
of a session is unique within a run (``1 + session``).

The window finds each client inside a conversation, as a running deployment
does: a client's first session opens at a turn drawn uniformly from its turns
(from ``sizes_seed``), and the turns before it are history (``history``),
which set-up sends once so that the server caches it, with answers drawn from
the run seed in place of served ones. With ``"stagger": true`` the answer of
each client's opening turn is cut as the closed loop's first requests are.
"""
from __future__ import annotations

import dataclasses
from typing import List

import numpy as np


WARMUP_IDS = 1024     # first-token ids at the top of the vocabulary kept for warm-up


def warmup_ids(vocab: int) -> int:
    return min(WARMUP_IDS, vocab // 4)


@dataclasses.dataclass
class Planned:
    idx: int
    prompt: np.ndarray      # int32 token ids
    max_new: int
    offset_s: float         # open loop: due time after the window opens
    prefix_id: int          # shared prefix index, or -1


def _lengths(rng: np.random.Generator, spec: dict, n: int) -> np.ndarray:
    lo, hi = int(spec["lo"]), int(spec["hi"])
    if spec["dist"] == "uniform":
        return rng.integers(lo, hi + 1, n)
    if spec["dist"] == "lognormal":
        out = np.empty(0, np.int64)
        mu, sigma = np.log(spec["median"]), spec["sigma"]
        while len(out) < n:        # truncated lognormal: redraw outside [lo, hi]
            x = np.rint(rng.lognormal(mu, sigma, 2 * n)).astype(np.int64)
            out = np.concatenate([out, x[(x >= lo) & (x <= hi)]])
        return out[:n]
    raise ValueError(f"unknown length distribution {spec['dist']!r}")


def _block_perm(rng: np.random.Generator, n: int, block: int) -> np.ndarray:
    return np.concatenate([s + rng.permutation(min(block, n - s))
                           for s in range(0, n, block)])


class Schedule:
    """The requests of one run, made lazily: request ``i`` is the same for the
    same mix and seed."""

    def __init__(self, mix: dict, vocab: int, seed: int):
        self.mix, self.vocab, self.seed = mix, vocab, int(seed)
        self.loop = mix["loop"]
        if self.loop not in ("closed", "open"):
            raise ValueError(f"loop must be 'closed' or 'open', got {self.loop!r}")
        self.clients = int(mix.get("clients") or 0)
        self.rate = float(mix.get("rate_per_s") or 0.0)
        n = self.n = int(mix["n_requests"])
        if n + 16 + warmup_ids(vocab) >= vocab:
            raise ValueError("n_requests leaves too few first-token ids in the vocabulary")
        sizes = np.random.default_rng(int(mix["sizes_seed"]))
        shared = mix.get("shared")
        own = _lengths(sizes, mix["prompt"], n)
        out = _lengths(sizes, mix["output"], n)
        pid = np.full(n, -1)
        if shared:
            use = sizes.random(n) < shared["share"]
            pid[use] = sizes.integers(0, shared["count"], int(use.sum()))
        plen = own + np.where(pid >= 0, shared["length"] if shared else 0, 0)
        max_len = int(mix["engine"]["max_len"])
        if plen.max() >= max_len:
            raise ValueError(f"a prompt of {plen.max()} tokens leaves no room in "
                             f"max_len {max_len}")
        # the output is cut where the cache would be full: prompt + output <= max_len
        out = np.minimum(out, max_len - plen)
        gaps = sizes.exponential(1.0 / self.rate, n) if self.loop == "open" else None
        order = np.random.default_rng([self.seed, 0])
        block = int(mix.get("block", 32))
        if self.loop == "closed" and mix.get("stagger"):
            k = self.clients
            out[:k] = 1 + np.floor(sizes.random(k) * out[:k]).astype(out.dtype)
            perm = np.concatenate([order.permutation(k),
                                   k + _block_perm(order, n - k, block)])
        else:
            perm = _block_perm(order, n, block)
        self.own, self.out, self.pid = own[perm], out[perm], pid[perm]
        self.plen = plen[perm]
        self.offsets = (np.cumsum(gaps[_block_perm(order, n, block)])
                        if gaps is not None else np.zeros(n))
        self._prefixes = []
        for k in range(shared["count"] if shared else 0):
            p = np.random.default_rng([self.seed, 2, k]).integers(
                1, vocab, shared["length"]).astype(np.int32)
            p[0] = n + 1 + k
            self._prefixes.append(p)

    def request(self, i: int) -> Planned:
        if i >= self.n:
            raise IndexError(f"the mix holds {self.n} requests; a run asked for more")
        rng = np.random.default_rng([self.seed, 1, i])
        own = rng.integers(1, self.vocab, int(self.own[i])).astype(np.int32)
        own[0] = 1 + i
        k = int(self.pid[i])
        prompt = np.concatenate([self._prefixes[k], own]) if k >= 0 else own
        return Planned(idx=i, prompt=prompt, max_new=int(self.out[i]),
                       offset_s=float(self.offsets[i]), prefix_id=k)

    def client_requests(self, c: int) -> List[int]:
        """Closed loop: the request indices client ``c`` sends, in order."""
        return list(range(c, self.n, self.clients))

    def prompt_lengths(self) -> np.ndarray:
        return self.plen


class Sessions:
    """The sessions of one run (``"loop": "sessions"``), made lazily: session
    ``s`` is the same for the same mix and seed. Client ``c`` runs sessions
    ``c, c + clients, ...``, the first of them from its turn ``opening[c]``."""

    loop = "sessions"

    def __init__(self, mix: dict, vocab: int, seed: int):
        self.mix, self.vocab, self.seed = mix, vocab, int(seed)
        self.clients = int(mix["clients"])
        n = self.n = int(mix["n_sessions"])
        if n + 16 + warmup_ids(vocab) >= vocab:
            raise ValueError("n_sessions leaves too few first-token ids in the vocabulary")
        self.max_len = int(mix["engine"]["max_len"])
        sizes = np.random.default_rng(int(mix["sizes_seed"]))
        turns = _lengths(sizes, mix["turns"], n)
        width = int(mix["turns"]["hi"])
        first = _lengths(sizes, mix["first"], n)
        user = _lengths(sizes, mix["user"], n * width).reshape(n, width)
        out = _lengths(sizes, mix["output"], n * width).reshape(n, width)
        user[:, 0] = first
        # a session keeps its turns while the prompt and the answer fit
        fits = np.cumprod(_prompts(user, out) + out <= self.max_len, 1).astype(bool)
        turns = np.minimum(turns, fits.sum(1))
        if turns.min() < 1:
            raise ValueError(f"a first turn does not fit max_len {self.max_len}")
        k = self.clients
        opening = np.zeros(n, np.int64)
        opening[:k] = np.floor(sizes.random(k) * turns[:k]).astype(np.int64)
        if mix.get("stagger"):
            at = (np.arange(k), opening[:k])
            out[at] = 1 + np.floor(sizes.random(k) * out[at]).astype(out.dtype)
        prompt = _prompts(user, out)      # a cut answer only shortens what follows
        order = np.random.default_rng([self.seed, 0])
        block = int(mix.get("block", 32))
        perm = np.concatenate([order.permutation(k), k + _block_perm(order, n - k, block)])
        self.turns, self.user, self.out = turns[perm], user[perm], out[perm]
        self.opening = opening[perm]
        self.prompt = prompt[perm]        # each turn's prompt length, answers served whole
        self._start = np.concatenate([[0], np.cumsum(self.turns)[:-1]])

    def client_sessions(self, c: int) -> List[int]:
        return list(range(c, self.n, self.clients))

    def user_tokens(self, s: int, k: int) -> np.ndarray:
        """The user part of turn ``k`` of session ``s``."""
        rng = np.random.default_rng([self.seed, 1, s, k])
        toks = rng.integers(1, self.vocab, int(self.user[s, k])).astype(np.int32)
        if k == 0:
            toks[0] = 1 + s
        return toks

    def history(self, s: int) -> np.ndarray:
        """What session ``s`` said before its opening turn: each earlier turn's
        user part and an answer of the planned length drawn from the run seed."""
        parts = []
        for k in range(int(self.opening[s])):
            answer = np.random.default_rng([self.seed, 3, s, k]).integers(
                1, self.vocab, int(self.out[s, k])).astype(np.int32)
            parts += [self.user_tokens(s, k), answer]
        return np.concatenate(parts) if parts else np.zeros(0, np.int32)

    def turn(self, s: int, k: int, prev_prompt=None, served=None) -> Planned:
        """Turn ``k`` of session ``s``: the previous turn's prompt, then the
        tokens served for it, then a new user part; the opening turn follows
        the history instead. ``idx`` numbers every turn of the run."""
        if s >= self.n:
            raise IndexError(f"the mix holds {self.n} sessions; a run asked for more")
        user = self.user_tokens(s, k)
        if k == self.opening[s]:
            prompt = np.concatenate([self.history(s), user])
        else:
            prompt = np.concatenate([prev_prompt, np.asarray(served, np.int32), user])
        return Planned(idx=int(self._start[s]) + k, prompt=prompt,
                       max_new=int(self.out[s, k]), offset_s=0.0, prefix_id=-1)

    def prompt_lengths(self) -> np.ndarray:
        """The length of every turn's prompt, where every answer was served
        whole."""
        return np.concatenate([self.prompt[s, : self.turns[s]] for s in range(self.n)])


def _prompts(user: np.ndarray, out: np.ndarray) -> np.ndarray:
    """Each turn's prompt length: every earlier user part and answer, and its
    own user part."""
    return np.cumsum(user + np.concatenate([np.zeros((len(out), 1), out.dtype),
                                            out[:, :-1]], 1), 1)


def schedule(mix: dict, vocab: int, seed: int):
    """The mix's generator: ``Sessions`` for ``"loop": "sessions"``, else a
    ``Schedule`` of single requests."""
    return (Sessions if mix["loop"] == "sessions" else Schedule)(mix, vocab, seed)


def warmup_prompt(vocab: int, w: int, length: int) -> np.ndarray:
    """A warm-up prompt whose first token no run request uses (``vocab - 1 - w``),
    so warm-up leaves nothing in the prefix cache that the window could hit."""
    if w >= warmup_ids(vocab):
        raise ValueError(f"warm-up prompt {w} past the {warmup_ids(vocab)} reserved ids")
    p = np.random.default_rng([7, w]).integers(1, vocab, length).astype(np.int32)
    p[0] = vocab - 1 - w
    return p
