"""Paged KV cache + radix prefix reuse (DESIGN.md §3.8).

Four property groups:

* **Dense parity** — the paged layout is a pure representation change: any mixed
  workload served through the page pool emits token-identical output to the
  dense slot table on every integer path × KV-cache mode (cold admissions are
  *bitwise* identical by construction: same prefill attention codepath, and the
  pool gather reproduces the dense (B, T, ...) row layout exactly).
* **Prefix reuse** — shared-prefix admissions map cached pages copy-free, only
  prefill the suffix, and emit the same tokens as a cold engine; int8 pages
  share bit-exactly (deterministic codes+scales); partial tail pages COW.
* **Allocator/refcount invariants** — the pool and radix index stay consistent
  under churn + eviction pressure.
* **Kernel parity** — the Pallas paged decode kernel vs the jnp oracle across a
  shape/table sweep (interpret mode).

Plus the two satellite pins: max_len-prompt headroom (admit-and-retire, no
silent clipped scatter) and head-of-line bucket scheduling.
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
from repro.models import model as M
from repro.models.quantize import quantize_tree
from repro.serving import engine as E
from repro.serving.paging import PagePool, RadixIndex

T = 32
PS = 8
LENS = [4, 7, 12, 9, 5]
MAX_NEW = [5, 3, 6, 2, 4]


@pytest.fixture(scope="module")
def small():
    cfg = dataclasses.replace(get("starcoder2-7b", smoke=True), dtype="float32")
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    qparams = quantize_tree(params, ql.W8A8_INT8)
    return cfg, params, qparams


def _mixed_prompts(cfg, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, cfg.vocab, size=n).astype(np.int32) for n in LENS]


def _shared_prefix_prompts(cfg, n_req=4, shared_len=16, seed=2):
    rng = np.random.default_rng(seed)
    shared = rng.integers(1, cfg.vocab, size=shared_len).astype(np.int32)
    return [np.concatenate([shared,
                            rng.integers(1, cfg.vocab, size=4 + i).astype(np.int32)])
            for i in range(n_req)]


def _serve(cfg, params, prompts, max_new, **kw):
    eng = E.ServeEngine(cfg, params, batch_size=2, max_len=T, **kw)
    eng.submit([p.copy() for p in prompts], max_new=max_new)
    done = eng.run()
    return {r.rid: r.out for r in done}, eng


class TestPagedDenseParity:
    @pytest.mark.parametrize("path,kv", [("fake", "fp"), ("fake", "int8"),
                                         ("dequant-fp", "fp"),
                                         ("dequant-fp", "int8"),
                                         ("fused-int8", "fp"),
                                         ("fused-int8", "int8")])
    def test_paged_matches_dense(self, small, path, kv):
        """Mixed lengths + staggered budgets through the page pool == the dense
        slot table, token-exact, with mid-decode churn on both engines."""
        cfg, params, qparams = small
        if path == "fake":
            serve_params, quant = params, ql.W8A8_CROSSQUANT
        else:
            serve_params, quant = qparams, ql.W8A8_INT8
        prompts = _mixed_prompts(cfg)
        dense, _ = _serve(cfg, serve_params, prompts, MAX_NEW, quant=quant,
                          path=path, kv_cache=kv)
        paged, eng = _serve(cfg, serve_params, prompts, MAX_NEW, quant=quant,
                            path=path, kv_cache=kv, cache_layout="paged",
                            page_size=PS)
        assert eng.counters["mid_decode_admissions"] > 0
        assert paged == dense, (path, kv)
        eng.pool.check()

    def test_model_level_parity(self, small):
        """Prefill logits through a paged cache are *bitwise* equal to the
        dense cache on both KV modes (cold paged prefill shares the dense
        attention codepath verbatim; the table scatter is a pure layout
        change). Decode serves through the Pallas paged kernel on every path —
        same f32 math with an online softmax over pages, so its logits agree
        with the dense plain-softmax to reassociation level and the sampled
        token is identical (the contract the serving parity tests gate)."""
        cfg, params, _ = small
        rng = np.random.default_rng(7)
        lens = [5, 11]
        toks = np.zeros((2, max(lens)), np.int32)
        for i, n in enumerate(lens):
            toks[i, :n] = rng.integers(1, cfg.vocab, size=n)
        for kv_int8 in (False, True):
            dense = M.init_cache(cfg, 2, T, dtype=jnp.float32, kv_int8=kv_int8)
            paged = M.init_cache(cfg, 2, T, dtype=jnp.float32, kv_int8=kv_int8,
                                 layout="paged", page_size=PS)
            paged["page_table"] = jnp.asarray([[0, 1, 2, 3], [4, 5, 6, 7]],
                                              jnp.int32)
            cl = jnp.asarray(lens, jnp.int32)
            ld, exd = M.apply(params, {"tokens": jnp.asarray(toks)}, cfg,
                              mode="prefill", caches=dense, cur_len=cl)
            lp, exp_ = M.apply(params, {"tokens": jnp.asarray(toks)}, cfg,
                               mode="prefill", caches=paged, cur_len=cl)
            np.testing.assert_array_equal(np.asarray(ld), np.asarray(lp))
            nxt = jnp.argmax(ld[:, -1], -1)[:, None].astype(jnp.int32)
            ld2, _ = M.apply(params, {"tokens": nxt}, cfg, mode="decode",
                             caches=exd["caches"], cur_len=cl + 1)
            lp2, _ = M.apply(params, {"tokens": nxt}, cfg, mode="decode",
                             caches=exp_["caches"], cur_len=cl + 1)
            np.testing.assert_allclose(np.asarray(ld2), np.asarray(lp2),
                                       rtol=2e-5, atol=2e-5)
            np.testing.assert_array_equal(np.asarray(jnp.argmax(ld2, -1)),
                                          np.asarray(jnp.argmax(lp2, -1)))


class TestPrefixReuse:
    @pytest.mark.parametrize("path,kv", [("fake", "fp"), ("fused-int8", "int8")])
    def test_warm_admissions_match_cold(self, small, path, kv):
        """Prefix-hit admissions emit exactly the tokens of a cold (reuse-off)
        paged engine — and of the dense engine — while measurably saving
        prefill tokens."""
        cfg, params, qparams = small
        serve_params = params if path == "fake" else qparams
        quant = ql.W8A8_CROSSQUANT if path == "fake" else ql.W8A8_INT8
        prompts = _shared_prefix_prompts(cfg)
        warm, ew = _serve(cfg, serve_params, prompts, 4, quant=quant, path=path,
                          kv_cache=kv, cache_layout="paged", page_size=PS)
        cold, ec = _serve(cfg, serve_params, prompts, 4, quant=quant, path=path,
                          kv_cache=kv, cache_layout="paged", page_size=PS,
                          prefix_reuse=False)
        dense, _ = _serve(cfg, serve_params, prompts, 4, quant=quant, path=path,
                          kv_cache=kv)
        assert warm == cold == dense, (path, kv)
        assert ew.counters["prefix_hits"] > 0
        assert ew.stats().prefix_hit_rate > 0.0
        assert ec.counters["prefix_hits"] == 0
        assert ew.counters["prefill_tokens"] < ec.counters["prefill_tokens"]
        assert (ew.counters["prefill_tokens"] + ew.counters["prefix_tokens_reused"]
                == ew.counters["prompt_tokens"])

    def test_shared_pages_are_copy_free(self, small):
        """A prefix-hit admission's leading page ids are literally the cached
        pages (no copy), and the radix index holds one reference on them."""
        cfg, params, _ = small
        prompts = _shared_prefix_prompts(cfg, n_req=2)
        eng = E.ServeEngine(cfg, params, batch_size=2, max_len=T,
                            cache_layout="paged", page_size=PS)
        eng.submit([prompts[0].copy()], max_new=4)
        eng.run()
        held = set(eng.radix.held_pages())
        assert len(held) == len(prompts[0]) // PS  # full prompt pages cached
        eng.submit([prompts[1].copy()], max_new=4)
        eng._admit([])
        slot = next(i for i, s in enumerate(eng._slots) if s is not None)
        n_shared = len(prompts[1]) // PS
        shared_now = eng._seq_pages[slot][:n_shared]
        assert set(shared_now) <= held        # same physical pages, no copy
        for p in shared_now:
            assert eng.pool.refs[p] == 2      # radix retain + this sequence

    def test_int8_shared_pages_bit_identical(self, small):
        """Why int8 pages share exactly: per-token quantization is
        deterministic, so the cached prefix pages a warm admission maps are
        byte-identical (codes AND scales) to the pages a cold prefill of the
        same tokens writes."""
        cfg, params, _ = small
        prompts = _shared_prefix_prompts(cfg, n_req=2)

        def pages_of(eng, prompt):
            eng.submit([prompt.copy()], max_new=2)
            eng._admit([])
            slot = next(i for i, s in enumerate(eng._slots) if s is not None)
            ids = eng._seq_pages[slot][: len(prompt) // PS]
            leaves = {}
            for key in ("k_pages", "v_pages", "k_scale_pages", "v_scale_pages"):
                leaves[key] = np.asarray(eng.caches["blocks"][0][key][:, ids])
            return leaves

        a = pages_of(E.ServeEngine(cfg, params, batch_size=2, max_len=T,
                                   cache_layout="paged", page_size=PS,
                                   kv_cache="int8"), prompts[0])
        b = pages_of(E.ServeEngine(cfg, params, batch_size=2, max_len=T,
                                   cache_layout="paged", page_size=PS,
                                   kv_cache="int8"), prompts[1])
        n = min(a["k_pages"].shape[1], b["k_pages"].shape[1])
        for key in a:
            np.testing.assert_array_equal(a[key][:, :n], b[key][:, :n])

    def test_partial_tail_copy_on_write(self, small):
        """A prompt matching k full pages plus part of a cached page copies the
        matched token rows into a fresh page (COW) instead of re-prefilling
        them — and still emits cold-identical tokens."""
        cfg, params, _ = small
        rng = np.random.default_rng(5)
        base = rng.integers(1, cfg.vocab, size=16).astype(np.int32)
        fork = np.concatenate([base[:12],
                               rng.integers(1, cfg.vocab, size=6).astype(np.int32)])
        eng = E.ServeEngine(cfg, params, batch_size=2, max_len=T,
                            cache_layout="paged", page_size=PS)
        eng.submit([base.copy()], max_new=3)
        eng.run()
        eng.submit([fork.copy()], max_new=4)
        got = {r.rid: r.out for r in eng.run()}
        assert eng.counters["cow_copies"] == 1
        assert eng.counters["prefix_tokens_reused"] >= PS + 4  # page 0 + 4 COW rows
        cold, _ = _serve(cfg, params, [base, fork], [3, 4],
                         cache_layout="paged", page_size=PS, prefix_reuse=False)
        assert got[1] == cold[1]
        eng.pool.check()

    def test_page_copy_moves_token_prefix_of_codes_and_scale_rows(self, small):
        """``_page_copy`` on an int8-KV cache in the stored layout: in every
        layer's code pools and scale rows, page ``dst`` reads (through the
        logical view) the first ``n_tok`` tokens of page ``src`` and zeros
        after them; every other page is untouched."""
        cfg = small[0]
        caches = M.init_cache(cfg, 2, T, kv_int8=True, layout="paged",
                              page_size=PS)
        rng = np.random.default_rng(9)
        caches["blocks"] = [
            {name: jnp.asarray(rng.integers(-127, 128, leaf.shape), leaf.dtype)
             if leaf.dtype == jnp.int8 else
             jnp.asarray(rng.random(leaf.shape), leaf.dtype)
             for name, leaf in blk.items()} for blk in caches["blocks"]]
        src, dst, n_tok = 1, 4, 3
        out = E._page_copy(caches, src, dst, n_tok)
        Hkv = cfg.n_kv_heads
        view = {"k_pages": lambda p: paged_layout.codes_view(p, Hkv),
                "k_scale_pages": lambda p: paged_layout.scales_view(p, Hkv, PS)}
        for before, after in zip(caches["blocks"], out["blocks"]):
            for name in before:
                v = view[name.replace("v_", "k_")]
                a, b = np.asarray(v(after[name])), np.asarray(v(before[name]))
                np.testing.assert_array_equal(a[:, dst, :n_tok], b[:, src, :n_tok])
                assert not a[:, dst, n_tok:].any(), name
                keep = np.arange(a.shape[1]) != dst
                np.testing.assert_array_equal(a[:, keep], b[:, keep])


class TestAllocatorInvariants:
    def test_pool_basics(self):
        pool = PagePool(4)
        a = pool.alloc(3)
        assert sorted(a) == [0, 1, 2] and pool.free_count == 1
        assert pool.alloc(2) is None          # insufficient: no partial grant
        pool.incref([a[0]])
        assert pool.decref([a[0]]) == []      # still held once
        assert pool.decref(a) == a            # all freed now
        pool.check()
        assert pool.free_count == 4

    def test_radix_match_insert_evict(self):
        pool = PagePool(8)
        idx = RadixIndex(4)
        toks = np.arange(12, dtype=np.int32)
        pages = pool.alloc(3)
        idx.insert(toks, pages[: len(toks) // 4], pool)
        got_pages, matched, partial = idx.match(np.arange(10, dtype=np.int32))
        assert got_pages == pages[:2] and matched == 8
        # rest [8, 9] partially matches the third cached chunk [8..11]
        assert partial is not None and partial.page == pages[2]
        assert partial.length == 2
        # partial: diverge inside the second chunk
        fork = np.asarray([0, 1, 2, 3, 4, 5, 99, 98], np.int32)
        got_pages, matched, partial = idx.match(fork)
        assert got_pages == [pages[0]] and matched == 4
        assert partial is not None and partial.page == pages[1]
        assert partial.length == 2
        # eviction frees LRU leaves only down to what's needed
        pool.decref(pages)                    # only the index holds them now
        freed = idx.evict(pool, pool.free_count + 2)
        assert freed == 2
        pool.check()

    def test_refcount_invariants_under_churn(self, small):
        """Small pool + shared-prefix churn: every page is either free or
        accounted to live sequences / the prefix index, before and after."""
        cfg, params, _ = small
        rng = np.random.default_rng(9)
        shared = rng.integers(1, cfg.vocab, size=8).astype(np.int32)
        prompts = []
        for i in range(8):
            sfx = rng.integers(1, cfg.vocab, size=3 + (i % 5)).astype(np.int32)
            prompts.append(np.concatenate([shared, sfx]) if i % 2 else sfx)
        eng = E.ServeEngine(cfg, params, batch_size=2, max_len=T,
                            cache_layout="paged", page_size=PS, n_pages=7)
        eng.submit(prompts, max_new=[2 + (i % 4) for i in range(8)])
        done = eng.run()
        assert len(done) == 8
        eng.pool.check()
        held = eng.radix.held_pages()
        assert len(held) == len(set(held))
        # all sequences retired: remaining references belong to the index alone
        assert all(eng.pool.refs[p] == 1 for p in held)
        assert eng.pool.used_count == len(held)
        assert eng.counters["peak_pages_in_use"] <= 7

    def test_matched_prefix_survives_eviction_pressure(self, small):
        """Planning must incref the matched prefix pages *before* evicting for
        its own allocation: an index-only prefix (refs == 1) would otherwise be
        evicted under pressure and handed straight back as a writable own page
        of the very plan that matched it — corrupting the reused prefix. Here
        the sacrificial cached prefix evicts instead, and the reused one stays
        intact (tokens equal a cold engine's)."""
        cfg, params, _ = small
        rng = np.random.default_rng(21)
        base = rng.integers(1, cfg.vocab, size=16).astype(np.int32)   # 2 pages
        other = rng.integers(1, cfg.vocab, size=9).astype(np.int32)   # 1 page
        eng = E.ServeEngine(cfg, params, batch_size=1, max_len=T,
                            cache_layout="paged", page_size=PS, n_pages=4)
        eng.submit([base.copy()], max_new=2)
        eng.run()
        eng.submit([other.copy()], max_new=2)
        eng.run()
        assert len(eng.radix.held_pages()) == 3   # 4-page pool, 1 free
        fork = np.concatenate([base,
                               rng.integers(1, cfg.vocab, size=1).astype(np.int32)])
        eng.submit([fork.copy()], max_new=15)     # needs 2 shared + 2 own
        got = eng.run()[0].out
        assert eng.counters["pages_evicted"] >= 1    # the sacrificial prefix went
        assert eng.counters["prefix_tokens_reused"] >= 16
        assert sorted(set(eng.radix.held_pages())) == sorted(eng.radix.held_pages())
        eng.pool.check()
        cold = E.ServeEngine(cfg, params, batch_size=1, max_len=T,
                             cache_layout="paged", page_size=PS, n_pages=4,
                             prefix_reuse=False)
        cold.submit([fork.copy()], max_new=15)
        assert got == cold.run()[0].out

    def test_unsatisfiable_pressure_fails_clean(self, small):
        """When eviction cannot help (the request needs more pages than the
        pool holds even after giving everything up), planning must release the
        references it took and the engine raise — never hand a matched page
        out twice."""
        cfg, params, _ = small
        rng = np.random.default_rng(22)
        base = rng.integers(1, cfg.vocab, size=16).astype(np.int32)
        eng = E.ServeEngine(cfg, params, batch_size=1, max_len=T,
                            cache_layout="paged", page_size=PS, n_pages=3)
        eng.submit([base.copy()], max_new=2)
        eng.run()
        held = set(eng.radix.held_pages())
        fork = np.concatenate([base,
                               rng.integers(1, cfg.vocab, size=1).astype(np.int32)])
        eng.submit([fork.copy()], max_new=15)     # needs 4 pages of a 3-page pool
        with pytest.raises(RuntimeError, match="page pool too small"):
            eng.run()
        eng.pool.check()
        assert set(eng.radix.held_pages()) == held   # prefix neither evicted
        assert all(eng.pool.refs[p] == 1 for p in held)  # nor leaked a ref

    def test_reservation_is_exact_not_one_over(self, small):
        """The final sampled token is never scattered (retire fires first), so
        a prompt of one page plus max_new = page_size + 1 fits exactly two
        pages — a 2-page pool must serve it rather than over-reserve a third."""
        cfg, params, _ = small
        rng = np.random.default_rng(23)
        eng = E.ServeEngine(cfg, params, batch_size=1, max_len=T,
                            cache_layout="paged", page_size=PS, n_pages=2)
        eng.submit([rng.integers(1, cfg.vocab, size=PS).astype(np.int32)],
                   max_new=PS + 1)
        out = eng.run()[0].out
        assert len(out) == PS + 1
        assert eng.counters["peak_pages_in_use"] == 2
        eng.pool.check()

    def test_pool_too_small_raises(self, small):
        cfg, params, _ = small
        eng = E.ServeEngine(cfg, params, batch_size=1, max_len=T,
                            cache_layout="paged", page_size=PS, n_pages=2)
        eng.submit([np.arange(1, 20, dtype=np.int32)], max_new=8)
        with pytest.raises(RuntimeError, match="page pool too small"):
            eng.run()


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


class TestPagedKernelVsOracle:
    @pytest.mark.parametrize("kv_int8", [False, True])
    @pytest.mark.parametrize("B,Hkv,G,D,P,ps,maxP",
                             [(2, 2, 2, 16, 8, 8, 4),
                              (1, 1, 4, 32, 4, 16, 2),
                              (3, 2, 1, 64, 16, 4, 8)])
    def test_sweep(self, B, Hkv, G, D, P, ps, maxP, kv_int8):
        """window= / softcap= edge paths vs the oracle, fp AND int8-KV pools
        (in-kernel per-token dequant at the score/prob level)."""
        rng = np.random.default_rng(B * 100 + D + kv_int8)
        H = Hkv * G
        q = jnp.asarray(rng.standard_normal((B, 1, H, D)), jnp.float32)
        kp, vp, ks, vs = _rand_pools(rng, P, ps, Hkv, D, kv_int8)
        tab, kvl = _rand_table(rng, B, P, ps, maxP)
        skp, svp, sks, svs = paged_layout.stored(kp, vp, ks, vs)
        for window, softcap in ((None, None), (5, None), (None, 30.0)):
            got = kops.paged_decode_attention(q, skp, svp, tab, kvl,
                                              k_scale_pages=sks,
                                              v_scale_pages=svs,
                                              window=window, softcap=softcap)
            want = kref.paged_decode_attention_ref(
                q.reshape(B, Hkv, G, D), kp, vp, tab, kvl,
                k_scale_pages=ks, v_scale_pages=vs,
                window=window, softcap=softcap)
            np.testing.assert_allclose(
                np.asarray(got.reshape(B, Hkv, G, D)), np.asarray(want),
                rtol=2e-5, atol=2e-5)

    @pytest.mark.parametrize("kv_int8", [False, True])
    def test_kv_len_scalar_broadcasts_like_vector(self, kv_int8):
        """ops.paged_decode_attention accepts a scalar kv_len (all slots
        aligned) and must compute exactly the (B,)-vector result."""
        rng = np.random.default_rng(31 + kv_int8)
        B, Hkv, G, D, P, ps, maxP = 2, 2, 2, 16, 8, 8, 4
        q = jnp.asarray(rng.standard_normal((B, 1, Hkv * G, D)), jnp.float32)
        kp, vp, ks, vs = paged_layout.stored(
            *_rand_pools(rng, P, ps, Hkv, D, kv_int8))
        tab = jnp.asarray([[0, 1, 2, P], [3, 4, 5, P]], jnp.int32)
        kw = dict(k_scale_pages=ks, v_scale_pages=vs)
        got_scalar = kops.paged_decode_attention(
            q, kp, vp, tab, jnp.asarray(17, jnp.int32), **kw)
        got_vector = kops.paged_decode_attention(
            q, kp, vp, tab, jnp.full((B,), 17, jnp.int32), **kw)
        np.testing.assert_array_equal(np.asarray(got_scalar),
                                      np.asarray(got_vector))

    @pytest.mark.parametrize("kv_int8", [False, True])
    def test_single_live_page_and_all_sentinel_row(self, kv_int8):
        """One slot holding a single live page (kv_len inside page 0) matches
        the oracle; a *free* slot — all-sentinel table row, the shape a retired
        slot decodes with in lock-step — must produce finite output without
        touching any live page's result."""
        rng = np.random.default_rng(57 + kv_int8)
        B, Hkv, G, D, P, ps, maxP = 2, 2, 2, 16, 8, 8, 4
        q = jnp.asarray(rng.standard_normal((B, 1, Hkv * G, D)), jnp.float32)
        kp, vp, ks, vs = _rand_pools(rng, P, ps, Hkv, D, kv_int8)
        tab = jnp.asarray([[5] + [P] * (maxP - 1), [P] * maxP], jnp.int32)
        kvl = jnp.asarray([3, 1], jnp.int32)   # free slots decode with cur_len 1
        skp, svp, sks, svs = paged_layout.stored(kp, vp, ks, vs)
        kw = dict(k_scale_pages=sks, v_scale_pages=svs)
        got = kops.paged_decode_attention(q, skp, svp, tab, kvl, **kw)
        want = kref.paged_decode_attention_ref(
            q.reshape(B, Hkv, G, D), kp, vp, tab, kvl,
            k_scale_pages=ks, v_scale_pages=vs)
        np.testing.assert_allclose(np.asarray(got[0]),
                                   np.asarray(want.reshape(B, 1, Hkv * G, D)[0]),
                                   rtol=2e-5, atol=2e-5)
        assert np.isfinite(np.asarray(got)).all()
        # the live row's result is independent of the free row's garbage
        got_solo = kops.paged_decode_attention(q[:1], skp, svp, tab[:1],
                                               kvl[:1], **kw)
        np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(got_solo[0]))


class TestHeadroomAndScheduling:
    @pytest.mark.parametrize("layout", ["dense", "paged"])
    def test_max_len_prompt_admits_and_retires(self, small, layout):
        """A prompt of exactly max_len fills its cache at admission: it emits
        the one token its prefill logits produce and retires before any decode
        step could scatter past the cache — and a neighbor slot's request is
        entirely unaffected."""
        cfg, params, _ = small
        rng = np.random.default_rng(3)
        full = rng.integers(1, cfg.vocab, size=T).astype(np.int32)
        other = rng.integers(1, cfg.vocab, size=5).astype(np.int32)
        kw = {"cache_layout": layout, "page_size": PS} if layout == "paged" else {}
        got, eng = _serve(cfg, params, [full, other], [6, 4], **kw)
        assert len(got[0]) == 1               # admit-and-retire, no decode
        bs1 = E.ServeEngine(cfg, params, batch_size=1, max_len=T, **kw)
        bs1.submit([other.copy()], max_new=4)
        assert got[1] == bs1.run()[0].out
        if layout == "paged":
            eng.pool.check()

    def test_submit_rejects_oversized(self, small):
        cfg, params, _ = small
        eng = E.ServeEngine(cfg, params, batch_size=1, max_len=T)
        with pytest.raises(ValueError):
            eng.submit([np.arange(1, T + 2, dtype=np.int32)])
        with pytest.raises(ValueError):
            eng.submit([np.zeros(0, np.int32)])

    def test_head_of_line_bucket_scan(self, small):
        """One odd-length head request must not pre-empt the larger same-bucket
        group behind it: the group admits together, in one prefill call, and
        the served tokens stay order-independent."""
        cfg, params, _ = small
        rng = np.random.default_rng(11)
        odd = rng.integers(1, cfg.vocab, size=5).astype(np.int32)    # bucket 8
        a = rng.integers(1, cfg.vocab, size=12).astype(np.int32)     # bucket 16
        b = rng.integers(1, cfg.vocab, size=13).astype(np.int32)     # bucket 16
        eng = E.ServeEngine(cfg, params, batch_size=2, max_len=T)
        eng.submit([odd, a, b], max_new=3)
        eng._admit([])
        assert sorted(r.rid for r in eng._slots if r is not None) == [1, 2]
        assert eng.counters["prefill_calls"] == 1
        done = {r.rid: r.out for r in eng.run()}
        ref = E.ServeEngine(cfg, params, batch_size=2, max_len=T)
        ref.submit([a, b, odd], max_new=3)     # bucket-sorted submission order
        ref_done = {r.rid: r.out for r in ref.run()}
        assert done[0] == ref_done[2] and done[1] == ref_done[0]


class TestCacheDtype:
    def test_default_follows_params_dtype(self, small):
        cfg, params, _ = small
        bf16 = jax.tree_util.tree_map(
            lambda x: x.astype(jnp.bfloat16)
            if jnp.issubdtype(x.dtype, jnp.floating) else x, params)
        eng = E.ServeEngine(cfg, bf16, batch_size=2, max_len=T)
        assert eng.caches["blocks"][0]["k"].dtype == jnp.bfloat16
        eng32 = E.ServeEngine(cfg, params, batch_size=2, max_len=T)
        assert eng32.caches["blocks"][0]["k"].dtype == jnp.float32
        eng.submit(_mixed_prompts(cfg)[:2], max_new=3)
        assert all(len(r.out) == 3 for r in eng.run())

    def test_explicit_override_and_int8_unaffected(self, small):
        cfg, params, _ = small
        eng = E.ServeEngine(cfg, params, batch_size=2, max_len=T,
                            cache_dtype=jnp.bfloat16)
        assert eng.caches["blocks"][0]["k"].dtype == jnp.bfloat16
        eng8 = E.ServeEngine(cfg, params, batch_size=2, max_len=T,
                             cache_dtype=jnp.bfloat16, kv_cache="int8")
        assert eng8.caches["blocks"][0]["k"].dtype == jnp.int8
        assert eng8.caches["blocks"][0]["k_scale"].dtype == jnp.float32
