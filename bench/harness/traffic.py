"""One general traffic generator, driven by a mix file and ``--seed``.

A mix file (``bench/traffic/<mix>.json``) gives the loop (``closed`` with a
client count, or ``open`` with a Poisson rate), the prompt and output length
distributions, optional shared prefixes, and the ``EngineConfig`` the traffic
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


def warmup_prompt(vocab: int, w: int, length: int) -> np.ndarray:
    """A warm-up prompt whose first token no run request uses (``vocab - 1 - w``),
    so warm-up leaves nothing in the prefix cache that the window could hit."""
    if w >= warmup_ids(vocab):
        raise ValueError(f"warm-up prompt {w} past the {warmup_ids(vocab)} reserved ids")
    p = np.random.default_rng([7, w]).integers(1, vocab, length).astype(np.int32)
    p[0] = vocab - 1 - w
    return p
