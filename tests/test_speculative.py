"""Speculative decoding (DESIGN.md §3.9): token-exactness harness.

Three layers of pinning, outermost first:

* **Engine parity** — ``ServeEngine(speculate=k)`` must emit, per request,
  exactly the tokens of the same engine with ``speculate=1``, on every
  path × KV-cache mode × cache layout combination. Greedy acceptance makes
  this exact by construction (a rejected draft position falls back to the
  verified argmax), so any drift is a masking/scatter bug in the verify path.
* **Mid-window retirement** — a request hitting EOS / ``max_new`` / cache-full
  inside a draft window must retire at exactly the token sequential decode
  would, and the rejected tail must not leak into pages a new admission will
  reuse (the engine asserts its page mappings are clean at that point).
* **Kernel vs oracle** — the (B, W·G, ps)-row verify kernel against the dense
  gather oracle over random injective page tables, including the ``q_win == 1``
  degenerate (bitwise the decode kernel) and all-sentinel table rows.

The drafter is host-side numpy with no exactness obligations (a wrong draft
only costs acceptance rate), so its tests are plain unit checks.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get
from repro.core import qlinear as ql
from repro.kernels import ops as kops
from repro.kernels import paged_layout
from repro.kernels import ref as kref
from repro.kernels.flash_attention import paged_decode_attention_pallas
from repro.models import model as M
from repro.models.quantize import quantize_tree
from repro.serving import engine as E
from repro.serving.drafter import NGramDrafter

T = 32           # cache length for every engine in this module
PS = 8           # page size for paged engines

COMBOS = [("fake", "fp"), ("fake", "int8"),
          ("dequant-fp", "fp"), ("dequant-fp", "int8"),
          ("fused-int8", "fp"), ("fused-int8", "int8")]


@pytest.fixture(scope="module")
def small():
    cfg = dataclasses.replace(get("starcoder2-7b", smoke=True), dtype="float32")
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    qparams = quantize_tree(params, ql.W8A8_INT8)
    return cfg, params, qparams


def _spec_prompts(cfg, seed=0):
    """Drafter-friendly mix: repeated motifs (n-gram lookups hit, windows fill)
    plus plain random prompts (lookups miss, slots degrade to 1-token steps).
    Lengths are staggered so mid-decode admissions land inside other slots'
    draft windows."""
    rng = np.random.default_rng(seed)
    motif = rng.integers(1, cfg.vocab, size=4).astype(np.int32)
    return [np.tile(motif, 3),                                   # 12, periodic
            rng.integers(1, cfg.vocab, size=7).astype(np.int32),  # random
            np.tile(motif[:3], 2),                               # 6, periodic
            rng.integers(1, cfg.vocab, size=9).astype(np.int32)]  # random


MAX_NEW = [6, 4, 7, 3]


def _serve(cfg, params, prompts, max_new, *, speculate, eos_id=None, **kw):
    eng = E.ServeEngine(cfg, params, batch_size=2, max_len=T, eos_id=eos_id,
                        speculate=speculate, **kw)
    eng.submit(prompts, max_new=max_new)
    done = eng.run()
    return {r.rid: r.out for r in done}, eng


class TestEngineParity:
    """speculate=4 ≡ speculate=1, token-exact, on every path × kv × layout."""

    @pytest.mark.parametrize("layout", ["dense", "paged"])
    @pytest.mark.parametrize("path,kv", COMBOS)
    def test_matches_nonspeculative(self, small, path, kv, layout):
        cfg, params, qparams = small
        if path == "fake":
            serve_params, quant = params, ql.W8A8_CROSSQUANT
        else:
            serve_params, quant = qparams, ql.W8A8_INT8
        kw = dict(quant=quant, path=path, kv_cache=kv)
        if layout == "paged":
            kw.update(cache_layout="paged", page_size=PS)
        prompts = _spec_prompts(cfg)
        base, _ = _serve(cfg, serve_params, prompts, MAX_NEW, speculate=1, **kw)
        spec, eng = _serve(cfg, serve_params, prompts, MAX_NEW, speculate=4, **kw)
        assert spec == base, (path, kv, layout)
        # the workload must actually have exercised multi-token windows
        assert eng.counters["spec_steps"] > 0
        assert eng.counters["spec_drafted"] > 0

    def test_speculation_accepts_on_periodic_prompts(self, small):
        """Motif prompts through a greedy random-init model are repetitive
        enough that the n-gram drafter must land accepted tokens — i.e. the
        harness genuinely tests multi-token acceptance, not just k=1 fallback."""
        cfg, params, _ = small
        spec, eng = _serve(cfg, params, _spec_prompts(cfg), MAX_NEW, speculate=4)
        assert eng.counters["spec_accepted"] > 0
        assert eng.stats().accept_rate > 0.0
        assert eng.stats().tokens_per_step > 1.0

    def test_window_sizes_agree(self, small):
        """Every window size k (incl. k=1 == plain engine) yields the same
        per-request tokens."""
        cfg, params, _ = small
        prompts = _spec_prompts(cfg, seed=5)
        outs = {k: _serve(cfg, params, prompts, MAX_NEW, speculate=k)[0]
                for k in (1, 2, 4)}
        assert outs[1] == outs[2] == outs[4]

    def test_window_longer_than_remaining_budget(self, small):
        """speculate far beyond max_new and the cache budget: the engine must
        clamp the draft so no request overruns max_new or the cache."""
        cfg, params, _ = small
        prompts = [np.tile(np.arange(1, 5, dtype=np.int32), 6),   # len 24, T=32
                   np.tile(np.arange(5, 8, dtype=np.int32), 2)]
        base, _ = _serve(cfg, params, prompts, [10, 2], speculate=1)
        spec, _ = _serve(cfg, params, prompts, [10, 2], speculate=8)
        assert spec == base
        assert all(len(v) <= m for v, m in zip(spec.values(), [10, 2]))

    def test_rejects_sampling_and_static_scheduler(self, small):
        cfg, params, _ = small
        with pytest.raises(ValueError, match="greedy"):
            E.ServeEngine(cfg, params, batch_size=2, max_len=T, speculate=4,
                          temperature=0.7)
        with pytest.raises(ValueError, match="continuous"):
            E.ServeEngine(cfg, params, batch_size=2, max_len=T, speculate=4,
                          scheduler="grouped")


class TestMidWindowRetirement:
    """A request finishing inside a draft window (EOS / max_new / cache-full)
    retires at exactly the sequential-decode token; the rejected tail never
    reaches its pages (ServeEngine asserts the mappings are clean — an
    AssertionError here IS the regression)."""

    @pytest.mark.parametrize("layout", ["dense", "paged"])
    def test_eos_inside_window(self, small, layout):
        cfg, params, _ = small
        kw = dict(cache_layout="paged", page_size=PS) if layout == "paged" else {}
        prompts = _spec_prompts(cfg, seed=7)
        # pick an EOS from a clean run: the 3rd token of request 0 guarantees
        # the stop lands mid-stream — and, with speculate=4 windows flowing,
        # mid-window for at least one request
        base, _ = _serve(cfg, params, prompts, MAX_NEW, speculate=1, **kw)
        eos = base[0][2]
        base_eos, _ = _serve(cfg, params, prompts, MAX_NEW, speculate=1,
                             eos_id=eos, **kw)
        spec_eos, eng = _serve(cfg, params, prompts, MAX_NEW, speculate=4,
                               eos_id=eos, **kw)
        assert spec_eos == base_eos
        assert any(v and v[-1] == eos for v in spec_eos.values())

    def test_freed_slot_reuse_after_mid_window_eos(self, small):
        """batch_size < n_requests with an EOS retire mid-window: the admission
        into the freed slot must decode as if the slot were fresh."""
        cfg, params, _ = small
        prompts = _spec_prompts(cfg, seed=11)
        base, _ = _serve(cfg, params, prompts, MAX_NEW, speculate=1,
                         cache_layout="paged", page_size=PS)
        eos = base[0][1]
        want, _ = _serve(cfg, params, prompts, MAX_NEW, speculate=1,
                         eos_id=eos, cache_layout="paged", page_size=PS)
        got, eng = _serve(cfg, params, prompts, MAX_NEW, speculate=4,
                          eos_id=eos, cache_layout="paged", page_size=PS)
        assert got == want
        assert eng.counters["mid_decode_admissions"] > 0


class TestDrafter:
    def test_ngram_hit_proposes_continuation(self):
        d = NGramDrafter(max_ngram=3)
        hist = np.array([1, 2, 3, 9, 8, 1, 2, 3], np.int32)
        np.testing.assert_array_equal(d.draft(hist, 3), [9, 8, 1])

    def test_prefers_longest_suffix_match(self):
        d = NGramDrafter(max_ngram=3)
        # suffix [2,3] occurs earlier (→ 7); plain [3] occurs even earlier (→ 5)
        hist = np.array([3, 5, 2, 3, 7, 2, 3], np.int32)
        np.testing.assert_array_equal(d.draft(hist, 2), [7, 2])

    def test_most_recent_occurrence_wins(self):
        d = NGramDrafter(max_ngram=1)
        hist = np.array([4, 10, 4, 20, 4], np.int32)
        np.testing.assert_array_equal(d.draft(hist, 1), [20])

    def test_miss_returns_empty(self):
        d = NGramDrafter()
        assert d.draft(np.array([1, 2, 3, 4], np.int32), 4).size == 0

    def test_degenerate_inputs(self):
        d = NGramDrafter()
        assert d.draft(np.zeros(0, np.int32), 3).size == 0      # empty history
        assert d.draft(np.array([7], np.int32), 3).size == 0    # pending only
        assert d.draft(np.array([1, 2, 1], np.int32), 0).size == 0   # n == 0

    def test_window_clamped_to_n(self):
        """A long continuation is truncated to the requested budget — the
        engine passes ``n = min(k-1, cache room, max_new room)``."""
        d = NGramDrafter(max_ngram=2)
        hist = np.array([5, 6, 1, 2, 3, 4, 5, 6], np.int32)
        got = d.draft(hist, 2)
        assert got.size <= 2
        np.testing.assert_array_equal(got, [1, 2])

    def test_continuation_shorter_than_budget(self):
        d = NGramDrafter(max_ngram=2)
        hist = np.array([1, 2, 9, 1, 2], np.int32)
        np.testing.assert_array_equal(d.draft(hist, 5), [9, 1, 2])


def _rand_table(rng, B, P, ps, maxP):
    """Random injective tables with sentinel tails past each row's pages."""
    tab = np.full((B, maxP), P, np.int32)
    kvl = np.zeros(B, np.int32)
    perm = rng.permutation(P)
    off = 0
    for b in range(B):
        n = int(rng.integers(1, min(maxP, P - off) + 1))
        tab[b, :n] = perm[off: off + n]
        off += n
        kvl[b] = int(rng.integers((n - 1) * ps + 1, n * ps + 1))
    return jnp.asarray(tab), jnp.asarray(kvl)


def _rand_pools(rng, P, ps, Hkv, D, kv_int8):
    """(k_pages, v_pages, k_scale_pages|None, v_scale_pages|None)."""
    if not kv_int8:
        return (jnp.asarray(rng.standard_normal((P, ps, Hkv, D)), jnp.float32),
                jnp.asarray(rng.standard_normal((P, ps, Hkv, D)), jnp.float32),
                None, None)
    return (jnp.asarray(rng.integers(-127, 128, (P, ps, Hkv, D)), jnp.int8),
            jnp.asarray(rng.integers(-127, 128, (P, ps, Hkv, D)), jnp.int8),
            jnp.asarray(0.002 + 0.05 * rng.random((P, ps, Hkv, 1)), jnp.float32),
            jnp.asarray(0.002 + 0.05 * rng.random((P, ps, Hkv, 1)), jnp.float32))


def _rand_qlen(rng, kvl, W):
    """Valid window rows per slot: 1 ≤ q_len ≤ min(W, kv_len)."""
    hi = np.minimum(np.asarray(kvl), W)
    return jnp.asarray([int(rng.integers(1, h + 1)) for h in hi], jnp.int32)


class TestVerifyKernelVsOracle:
    """(B, W) verify windows through the pallas kernel vs the gather oracle.

    Rows ≥ q_len are garbage-but-finite by contract, so comparisons slice to
    the valid window rows per slot."""

    @pytest.mark.parametrize("kv_int8", [False, True])
    @pytest.mark.parametrize("W", [1, 2, 4])
    @pytest.mark.parametrize("B,Hkv,G,D,P,ps,maxP",
                             [(2, 2, 2, 16, 8, 8, 4),
                              (1, 1, 4, 32, 4, 16, 2),
                              (3, 2, 1, 64, 16, 4, 8)])
    def test_window_sweep(self, B, Hkv, G, D, P, ps, maxP, W, kv_int8):
        rng = np.random.default_rng(100 * W + B + 7 * kv_int8)
        kp, vp, ksp, vsp = _rand_pools(rng, P, ps, Hkv, D, kv_int8)
        tab, kvl = _rand_table(rng, B, P, ps, maxP)
        qln = _rand_qlen(rng, kvl, W)
        q = jnp.asarray(rng.standard_normal((B, W, Hkv * G, D)), jnp.float32)
        skp, svp, sks, svs = paged_layout.stored(kp, vp, ksp, vsp)
        got = kops.paged_verify_attention(q, skp, svp, tab, kvl, qln,
                                          k_scale_pages=sks, v_scale_pages=svs)
        qg = jnp.transpose(q.reshape(B, W, Hkv, G, D), (0, 2, 1, 3, 4))
        ref = jnp.transpose(
            kref.paged_verify_attention_ref(qg, kp, vp, tab, kvl, qln,
                                            k_scale_pages=ksp,
                                            v_scale_pages=vsp),
            (0, 2, 1, 3, 4)).reshape(B, W, Hkv * G, D)
        for b in range(B):
            n = int(qln[b])
            np.testing.assert_allclose(got[b, :n], ref[b, :n],
                                       rtol=2e-5, atol=2e-5)
        assert np.isfinite(np.asarray(got)).all()

    @pytest.mark.parametrize("window,softcap", [(5, None), (None, 30.0)])
    def test_window_and_softcap(self, window, softcap):
        B, Hkv, G, D, P, ps, maxP, W = 2, 2, 2, 16, 8, 8, 4, 3
        rng = np.random.default_rng(31)
        kp, vp, ksp, vsp = _rand_pools(rng, P, ps, Hkv, D, True)
        tab, kvl = _rand_table(rng, B, P, ps, maxP)
        qln = _rand_qlen(rng, kvl, W)
        q = jnp.asarray(rng.standard_normal((B, W, Hkv * G, D)), jnp.float32)
        skp, svp, sks, svs = paged_layout.stored(kp, vp, ksp, vsp)
        got = kops.paged_verify_attention(q, skp, svp, tab, kvl, qln,
                                          k_scale_pages=sks, v_scale_pages=svs,
                                          window=window, softcap=softcap)
        qg = jnp.transpose(q.reshape(B, W, Hkv, G, D), (0, 2, 1, 3, 4))
        ref = jnp.transpose(
            kref.paged_verify_attention_ref(qg, kp, vp, tab, kvl, qln,
                                            k_scale_pages=ksp, v_scale_pages=vsp,
                                            window=window, softcap=softcap),
            (0, 2, 1, 3, 4)).reshape(B, W, Hkv * G, D)
        for b in range(B):
            n = int(qln[b])
            np.testing.assert_allclose(got[b, :n], ref[b, :n],
                                       rtol=2e-5, atol=2e-5)

    @pytest.mark.parametrize("kv_int8", [False, True])
    def test_w1_bitwise_equals_decode_kernel(self, kv_int8):
        """q_win=1 must be *bitwise* the decode kernel — the engine's
        speculate=1 path and all existing decode parity results carry over."""
        B, Hkv, G, D, P, ps, maxP = 2, 2, 2, 16, 8, 8, 4
        rng = np.random.default_rng(3)
        kp, vp, ksp, vsp = paged_layout.stored(
            *_rand_pools(rng, P, ps, Hkv, D, kv_int8))
        tab, kvl = _rand_table(rng, B, P, ps, maxP)
        q = jnp.asarray(rng.standard_normal((B, 1, Hkv * G, D)), jnp.float32)
        dec = kops.paged_decode_attention(q, kp, vp, tab, kvl,
                                          k_scale_pages=ksp, v_scale_pages=vsp)
        ver = kops.paged_verify_attention(q, kp, vp, tab, kvl,
                                          jnp.ones(B, jnp.int32),
                                          k_scale_pages=ksp, v_scale_pages=vsp)
        np.testing.assert_array_equal(np.asarray(dec), np.asarray(ver))

    def test_first_window_row_bitwise_equals_decode(self):
        """With q_len=1 in a W>1 launch, row 0 attends exactly the decode
        positions — bitwise equal to the decode kernel's output."""
        B, Hkv, G, D, P, ps, maxP, W = 2, 2, 2, 16, 8, 8, 4, 3
        rng = np.random.default_rng(4)
        kp, vp, _, _ = paged_layout.stored(
            *_rand_pools(rng, P, ps, Hkv, D, False))
        tab, kvl = _rand_table(rng, B, P, ps, maxP)
        q1 = jnp.asarray(rng.standard_normal((B, Hkv, G, D)), jnp.float32)
        qw = jnp.concatenate(
            [q1.reshape(B, Hkv, G, D)[:, :, None],
             jnp.asarray(rng.standard_normal((B, Hkv, W - 1, G, D)),
                         jnp.float32)], axis=2).reshape(B, Hkv, W * G, D)
        dec = paged_decode_attention_pallas(q1, kp, vp, tab, kvl,
                                            interpret=True)
        ver = paged_decode_attention_pallas(qw, kp, vp, tab, kvl, q_win=W,
                                            q_len=jnp.ones(B, jnp.int32),
                                            interpret=True)
        np.testing.assert_array_equal(
            np.asarray(dec), np.asarray(ver.reshape(B, Hkv, W, G, D)[:, :, 0]))

    def test_all_sentinel_row_is_finite(self):
        """A slot whose table row is all sentinel (freshly admitted, pages not
        yet mapped) must produce finite output — the engine discards it, but a
        NaN would poison the jit-donated cache buffers."""
        B, Hkv, G, D, P, ps, maxP, W = 2, 2, 2, 16, 8, 8, 4, 4
        rng = np.random.default_rng(5)
        kp, vp, ksp, vsp = _rand_pools(rng, P, ps, Hkv, D, True)
        tab, kvl = _rand_table(rng, B, P, ps, maxP)
        tab = tab.at[1].set(P)                  # row 1: every page sentinel
        kvl = kvl.at[1].set(1)
        qln = jnp.asarray([W, 1], jnp.int32)
        q = jnp.asarray(rng.standard_normal((B, W, Hkv * G, D)), jnp.float32)
        skp, svp, sks, svs = paged_layout.stored(kp, vp, ksp, vsp)
        out = kops.paged_verify_attention(q, skp, svp, tab, kvl, qln,
                                          k_scale_pages=sks, v_scale_pages=svs)
        assert np.isfinite(np.asarray(out)).all()
        # row 0 untouched by row 1's sentinels
        qg = jnp.transpose(q.reshape(B, W, Hkv, G, D), (0, 2, 1, 3, 4))
        ref = jnp.transpose(
            kref.paged_verify_attention_ref(qg, kp, vp, tab, kvl, qln,
                                            k_scale_pages=ksp,
                                            v_scale_pages=vsp),
            (0, 2, 1, 3, 4)).reshape(B, W, Hkv * G, D)
        np.testing.assert_allclose(out[0], ref[0], rtol=2e-5, atol=2e-5)
