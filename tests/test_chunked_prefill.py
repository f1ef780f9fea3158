"""Ragged chunked-prefill (DESIGN.md §3.10): kernel-vs-oracle + engine parity.

Three layers of pinning, innermost first:

* **Kernel vs oracle** — the packed-ragged Pallas kernel
  (``kernels.flash_attention._ragged_prefill_kernel``) against the gather
  oracle over random injective page tables: q_len/kv_len/prefix combos
  (including chunks that start mid-page — the packed-buffer overlay offset
  goes negative there), dead (q_len == 0) slots, all-sentinel table rows, and
  int8-KV scale pools on/off. The decode degenerate (q_len == 1,
  kv_len == cs + 1) must agree with the decode kernel.
* **Engine parity** — ``ServeEngine(chunked=True, token_budget=...)`` must
  emit, per request, exactly the tokens of the same engine without chunking,
  on every path × KV-cache combination, across budgets small enough to force
  multi-chunk prompts and admission bursts that overlap in-flight decodes.
* **Interactions** — chunked + speculate=4 serves draft windows as q_len > 1
  rows of the same packed launch and must stay token-exact vs plain decode;
  the §4.1 per-chunk quantization-kernel proportion is unchanged vs
  whole-prompt prefill (examples/serve_batch.py replay).
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
from repro.kernels.flash_attention import (paged_decode_attention_pallas,
                                           ragged_prefill_attention_pallas)
from repro.models import model as M
from repro.models.quantize import quantize_tree
from repro.serving import engine as E

T = 32           # cache length for every engine in this module
PS = 8           # page size for paged engines

COMBOS = [("fake", "fp"), ("fake", "int8"),
          ("dequant-fp", "fp"), ("dequant-fp", "int8"),
          ("fused-int8", "fp"), ("fused-int8", "int8")]


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


def _rand_chunks(rng, kvl, C, *, allow_dead=True):
    """Packed chunk extents: per slot a chunk length in [0, min(C, kvl)] with
    contiguous packing. Returns (q_start, q_len, Nt)."""
    qln = np.zeros(len(kvl), np.int32)
    for b, kv in enumerate(np.asarray(kvl)):
        lo = 0 if allow_dead else 1
        qln[b] = int(rng.integers(lo, min(C, int(kv)) + 1))
    qs = np.concatenate([[0], np.cumsum(qln)[:-1]]).astype(np.int32)
    return jnp.asarray(qs), jnp.asarray(qln), int(qln.sum())


def _packed_new(rng, Nt, Hkv, D):
    return (jnp.asarray(rng.standard_normal((max(Nt, 1), Hkv, D)), jnp.float32),
            jnp.asarray(rng.standard_normal((max(Nt, 1), Hkv, D)), jnp.float32))


def _kernel_vs_oracle(rng, B, Hkv, G, D, P, ps, maxP, C, kv_int8, *,
                      window=None, softcap=None, force_qln=None,
                      force_kvl=None, sentinel_row=None):
    kp, vp, ksp, vsp = _rand_pools(rng, P, ps, Hkv, D, kv_int8)
    tab, kvl = _rand_table(rng, B, P, ps, maxP)
    if force_kvl is not None:
        kvl = jnp.asarray(force_kvl, jnp.int32)
    qs, qln, Nt = _rand_chunks(rng, kvl, C)
    if force_qln is not None:
        qln = jnp.asarray(force_qln, jnp.int32)
        # chunk tokens are the newest kv_len tokens, so q_len <= kv_len
        kvl = jnp.maximum(kvl, qln)
        qs = jnp.asarray(np.concatenate(
            [[0], np.cumsum(np.asarray(qln))[:-1]]), jnp.int32)
        Nt = int(np.asarray(qln).sum())
    if force_kvl is not None or force_qln is not None:
        # rebuild the table so each row covers its (possibly forced) kv_len
        tab = np.full((B, maxP), P, np.int32)
        perm = rng.permutation(P)
        off = 0
        for b in range(B):
            n = -(-int(np.asarray(kvl)[b]) // ps)
            assert off + n <= P and n <= maxP, (off, n, P, maxP)
            tab[b, :n] = perm[off: off + n]
            off += n
        tab = jnp.asarray(tab)
    if sentinel_row is not None:
        tab = tab.at[sentinel_row].set(P)
    q = jnp.asarray(rng.standard_normal((max(Nt, 1), Hkv * G, D)), jnp.float32)
    kn, vn = _packed_new(rng, Nt, Hkv, D)
    skp, svp, sks, svs = paged_layout.stored(kp, vp, ksp, vsp)
    got = kops.ragged_prefill_attention(
        q, kn, vn, skp, svp, tab, qs, qln, kvl, chunk_cap=C,
        k_scale_pages=sks, v_scale_pages=svs, window=window, softcap=softcap)
    qg = q.reshape(max(Nt, 1), Hkv, G, D)
    ref = kref.ragged_prefill_attention_ref(
        qg, kn, vn, kp, vp, tab, qs, qln, kvl, chunk_cap=C,
        k_scale_pages=ksp, v_scale_pages=vsp, window=window,
        softcap=softcap).reshape(max(Nt, 1), Hkv * G, D)
    return np.asarray(got), np.asarray(ref), np.asarray(qs), np.asarray(qln)


class TestRaggedKernelVsOracle:
    """Packed ragged chunks through the pallas kernel vs the gather oracle.

    Valid rows must agree to 2e-5; rows no slot owns must be exactly zero in
    both (the kernel zero-inits its shared output block)."""

    @pytest.mark.parametrize("kv_int8", [False, True])
    @pytest.mark.parametrize("C", [4, 8, 16])
    @pytest.mark.parametrize("B,Hkv,G,D,P,ps,maxP",
                             [(2, 2, 2, 16, 8, 8, 4),
                              (1, 1, 4, 32, 4, 16, 2),
                              (3, 2, 1, 64, 16, 4, 8)])
    def test_chunk_sweep(self, B, Hkv, G, D, P, ps, maxP, C, kv_int8):
        rng = np.random.default_rng(1000 * C + 10 * B + kv_int8)
        got, ref, qs, qln = _kernel_vs_oracle(rng, B, Hkv, G, D, P, ps, maxP,
                                              C, kv_int8)
        np.testing.assert_allclose(got, ref, rtol=2e-5, atol=2e-5)
        assert np.isfinite(got).all()

    @pytest.mark.parametrize("window,softcap", [(5, None), (None, 30.0)])
    def test_window_and_softcap(self, window, softcap):
        B, Hkv, G, D, P, ps, maxP, C = 2, 2, 2, 16, 8, 8, 4, 8
        rng = np.random.default_rng(77)
        got, ref, _, _ = _kernel_vs_oracle(rng, B, Hkv, G, D, P, ps, maxP, C,
                                           True, window=window, softcap=softcap)
        np.testing.assert_allclose(got, ref, rtol=2e-5, atol=2e-5)

    @pytest.mark.parametrize("kv_int8", [False, True])
    def test_mid_page_chunk_start(self, kv_int8):
        """Chunk starts mid-page (prefix not a page multiple): the overlay
        offset for the straddling page is negative relative to the packed
        origin — exactly what the ps leading pad rows absorb."""
        B, Hkv, G, D, P, ps, maxP, C = 2, 2, 2, 16, 8, 8, 4, 8
        rng = np.random.default_rng(21 + kv_int8)
        # kvl chosen so cs = kvl - qln lands strictly inside a page
        got, ref, _, _ = _kernel_vs_oracle(
            rng, B, Hkv, G, D, P, ps, maxP, C, kv_int8,
            force_kvl=[ps + 3, 2 * ps + 5], force_qln=[5, 6])
        np.testing.assert_allclose(got, ref, rtol=2e-5, atol=2e-5)

    def test_page_aligned_chunk_boundaries(self):
        """Chunk exactly one page, starting and ending on page boundaries."""
        B, Hkv, G, D, P, ps, maxP, C = 2, 1, 2, 16, 8, 8, 4, 8
        rng = np.random.default_rng(31)
        got, ref, _, _ = _kernel_vs_oracle(
            rng, B, Hkv, G, D, P, ps, maxP, C, True,
            force_kvl=[2 * ps, 3 * ps], force_qln=[ps, ps])
        np.testing.assert_allclose(got, ref, rtol=2e-5, atol=2e-5)

    def test_dead_slot_rows_stay_zero(self):
        """A q_len == 0 slot contributes no packed rows, walks no pages, and
        leaves the shared output block untouched."""
        B, Hkv, G, D, P, ps, maxP, C = 3, 2, 2, 16, 8, 8, 4, 8
        rng = np.random.default_rng(41)
        got, ref, qs, qln = _kernel_vs_oracle(
            rng, B, Hkv, G, D, P, ps, maxP, C, True, force_qln=[4, 0, 5])
        np.testing.assert_allclose(got, ref, rtol=2e-5, atol=2e-5)
        assert np.isfinite(got).all()

    def test_all_sentinel_row_is_finite(self):
        """A freshly admitted slot whose table row is all sentinel must stay
        finite (NaN would poison the jit-donated cache buffers) and must not
        perturb any other slot's rows."""
        B, Hkv, G, D, P, ps, maxP, C = 2, 2, 2, 16, 8, 8, 4, 8
        rng = np.random.default_rng(51)
        got, ref, qs, qln = _kernel_vs_oracle(
            rng, B, Hkv, G, D, P, ps, maxP, C, True,
            force_kvl=[2 * ps, 1], force_qln=[6, 1], sentinel_row=1)
        assert np.isfinite(got).all()
        n0 = int(qln[0])
        np.testing.assert_allclose(got[:n0], ref[:n0], rtol=2e-5, atol=2e-5)

    @pytest.mark.parametrize("kv_int8", [False, True])
    def test_decode_degenerate_matches_decode_kernel(self, kv_int8):
        """q_len == 1 rows with kv_len == cs + 1 are single-token decode: the
        ragged launch must agree with the decode kernel on those rows (not
        bitwise — the fp overlay reads the packed k/v for the newest token
        where decode reads its scattered page — so the pool rows here are the
        scattered packed values, making both paths see identical inputs)."""
        B, Hkv, G, D, P, ps, maxP = 2, 2, 2, 16, 8, 8, 4
        rng = np.random.default_rng(61)
        kp, vp, ksp, vsp = _rand_pools(rng, P, ps, Hkv, D, kv_int8)
        tab, kvl = _rand_table(rng, B, P, ps, maxP)
        qs = jnp.asarray([0, 1], jnp.int32)
        qln = jnp.ones(B, jnp.int32)
        q = jnp.asarray(rng.standard_normal((B, Hkv * G, D)), jnp.float32)
        # the decode kernel attends the newest token from the pool; mirror by
        # packing that pool row as the overlay k/v so inputs agree exactly
        tabn, kvn = np.asarray(tab), np.asarray(kvl)
        rows_k, rows_v = [], []
        for b in range(B):
            pg = tabn[b, (kvn[b] - 1) // ps]
            r = (kvn[b] - 1) % ps
            kf = np.asarray(kp[pg, r], np.float32)
            vf = np.asarray(vp[pg, r], np.float32)
            if kv_int8:
                kf = kf * np.asarray(ksp[pg, r], np.float32)
                vf = vf * np.asarray(vsp[pg, r], np.float32)
            rows_k.append(kf)
            rows_v.append(vf)
        kn = jnp.asarray(np.stack(rows_k))
        vn = jnp.asarray(np.stack(rows_v))
        skp, svp, sks, svs = paged_layout.stored(kp, vp, ksp, vsp)
        got = kops.ragged_prefill_attention(
            q, kn, vn, skp, svp, tab, qs, qln, kvl, chunk_cap=4,
            k_scale_pages=sks, v_scale_pages=svs)
        qd = q.reshape(B, Hkv, G, D)
        dec = paged_decode_attention_pallas(qd, skp, svp, tab, kvl,
                                            k_scale=sks, v_scale=svs,
                                            interpret=True)
        np.testing.assert_allclose(
            np.asarray(got).reshape(B, Hkv, G, D), np.asarray(dec),
            rtol=2e-5, atol=2e-5)

    def test_full_budget_single_slot(self):
        """One slot consumes the whole packed block (cold prefill, cs == 0)."""
        B, Hkv, G, D, P, ps, maxP, C = 1, 2, 2, 16, 8, 8, 4, 16
        rng = np.random.default_rng(71)
        got, ref, _, _ = _kernel_vs_oracle(
            rng, B, Hkv, G, D, P, ps, maxP, C, False,
            force_kvl=[16], force_qln=[16])
        np.testing.assert_allclose(got, ref, rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# Engine-level parity: chunked scheduler vs the bucketed admission path.
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def small():
    cfg = dataclasses.replace(get("starcoder2-7b", smoke=True), dtype="float32")
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    qparams = quantize_tree(params, ql.W8A8_INT8)
    return cfg, params, qparams


def _prompts(seed=5, n=4, shared=16):
    """Shared-prefix workload: radix hits make later chunks start mid-page."""
    rng = np.random.default_rng(seed)
    cfg_vocab = 256      # starcoder2 smoke vocab: tokens must stay in range
    pre = rng.integers(1, cfg_vocab, size=shared).astype(np.int32)
    return [np.concatenate([pre, rng.integers(1, cfg_vocab, size=4 + i).astype(np.int32)])
            for i in range(n)]


MAX_NEW = [6, 4, 7, 3]


def _serve(small, path, kv, prompts=None, max_new=None, **kw):
    cfg, params, qparams = small
    p, q = (params, None) if path == "fake" else (qparams, ql.W8A8_INT8)
    eng = E.ServeEngine(cfg, p, quant=q, batch_size=3, max_len=T,
                        cache_layout="paged", page_size=PS, path=path,
                        kv_cache=kv, **kw)
    eng.submit(prompts if prompts is not None else _prompts(),
               max_new if max_new is not None else MAX_NEW)
    done = eng.run()
    return {r.rid: list(r.out) for r in done}, eng


class TestChunkedEngineParity:
    """chunked=True must be token-exact vs the bucketed admission engine.

    int8 KV note: a prompt split across chunks reads its *own* earlier chunks
    int8-dequantized from the pool, where whole-suffix prefill sees them in
    fp — so multi-chunk int8 prefill is not bitwise-identical attention.
    As with warm int8 prefix reuse (test_paged_serving), argmax token
    equality is pinned empirically at the test seeds; the first chunk's pool
    pages land bit-identically, later chunks drift by a few code units.
    """

    @pytest.mark.parametrize("path,kv", COMBOS)
    @pytest.mark.parametrize("tb", [9, 12])
    def test_paths_kv_combos(self, small, path, kv, tb):
        base, _ = _serve(small, path, kv)
        chk, eng = _serve(small, path, kv, chunked=True, token_budget=tb)
        assert chk == base
        st = eng.counters
        assert st["chunk_steps"] > 0
        assert st["chunk_prefill_rows"] > 0   # tb forces multi-chunk prompts

    @pytest.mark.parametrize("tb", [8, 10, 14, 16, 24, 64])
    def test_budget_sweep_fp(self, small, tb):
        """fp KV is bitwise chunk-invariant: every budget must be exact."""
        base, _ = _serve(small, "dequant-fp", "fp")
        chk, _ = _serve(small, "dequant-fp", "fp", chunked=True, token_budget=tb)
        assert chk == base

    def test_cold_no_sharing(self, small):
        prompts = [np.arange(1, 1 + n, dtype=np.int32) * 3 % 509 + 1
                   for n in (20, 7, 13, 24)]
        base, _ = _serve(small, "fake", "fp", prompts=prompts)
        chk, _ = _serve(small, "fake", "fp", prompts=prompts,
                        chunked=True, token_budget=10)
        assert chk == base

    def test_radix_stats_match(self, small):
        _, b = _serve(small, "fake", "fp")
        _, c = _serve(small, "fake", "fp", chunked=True, token_budget=12)
        assert c.stats().prefix_hit_rate == b.stats().prefix_hit_rate

    def test_int8_pool_divergence_is_bounded(self, small):
        """First chunk lands bit-identically; later chunks drift by at most a
        few code units (their hidden states attended the first chunk through
        the int8 dequant, the whole-suffix baseline saw it in fp)."""
        cfg, params, qparams = small
        outs = {}
        for chunked in (False, True):
            kw = dict(chunked=True, token_budget=9) if chunked else {}
            eng = E.ServeEngine(cfg, qparams, quant=ql.W8A8_INT8, batch_size=3,
                                max_len=T, cache_layout="paged", page_size=PS,
                                path="fused-int8", kv_cache="int8", **kw)
            eng.submit(_prompts()[:1], [1])
            eng.run()
            outs[chunked] = jax.tree.map(np.asarray, eng.caches)
        flat_a = [l for l in jax.tree.leaves(outs[False]) if l.ndim == 5]
        flat_b = [l for l in jax.tree.leaves(outs[True]) if l.ndim == 5]
        assert flat_a and len(flat_a) == len(flat_b)
        used = (len(_prompts()[0]) + PS - 1) // PS  # pages touched by slot 0
        for a, b in zip(flat_a, flat_b):
            # chunk 1 covers page 0 exactly (budget 9 -> page-aligned cut at 8)
            np.testing.assert_array_equal(a[:, 0], b[:, 0])
            da = np.abs(a[:, 1:used].astype(np.float32)
                        - b[:, 1:used].astype(np.float32))
            assert da.max() <= 16, da.max()

    def test_long_prompt_retires_at_cap(self, small):
        """A prompt of length T fills the cache; both paths emit 1 token."""
        rng = np.random.default_rng(9)
        prompts = [rng.integers(1, 512, size=T).astype(np.int32)]
        base, _ = _serve(small, "fake", "fp", prompts=prompts, max_new=[4])
        chk, _ = _serve(small, "fake", "fp", prompts=prompts, max_new=[4],
                        chunked=True, token_budget=8)
        assert chk == base
        assert all(len(v) == 1 for v in chk.values())


class TestChunkedInteractions:
    def test_admission_burst(self, small):
        """Requests injected mid-decode interleave with running slots."""
        late = [np.arange(2, 2 + n, dtype=np.int32) * 5 % 503 + 1
                for n in (18, 11)]
        base, _ = _serve(small, "dequant-fp", "int8")
        base_late, _ = _serve(small, "dequant-fp", "int8", prompts=late,
                              max_new=[5, 5])
        cfg, params, qparams = small
        eng = E.ServeEngine(cfg, qparams, quant=ql.W8A8_INT8, batch_size=3,
                            max_len=T, cache_layout="paged", page_size=PS,
                            path="dequant-fp", kv_cache="int8",
                            chunked=True, token_budget=10)
        eng.submit(_prompts(), MAX_NEW)
        finished = []
        for _ in range(3):
            assert eng.step(finished)
        eng.submit(late, [5, 5])          # burst lands mid-run
        while eng.step(finished):
            pass
        got = {r.rid: list(r.out) for r in finished}
        want = dict(base)
        want.update({k + len(base): v for k, v in base_late.items()})
        assert got == want
        assert eng.counters["mid_decode_admissions"] > 0

    def test_chunked_speculative(self, small):
        """Draft windows ride the same ragged launch; tokens stay exact."""
        base, _ = _serve(small, "dequant-fp", "int8")
        chk, eng = _serve(small, "dequant-fp", "int8", chunked=True,
                          token_budget=16, speculate=4)
        assert chk == base
        st = eng.counters
        assert st["spec_drafted"] > 0

    def test_budget_floor_enforced(self, small):
        cfg, params, _ = small
        with pytest.raises(ValueError):
            E.ServeEngine(cfg, params, batch_size=3, max_len=T,
                          cache_layout="paged", page_size=PS,
                          chunked=True, token_budget=8, speculate=4)

    def test_chunked_requires_paged(self, small):
        cfg, params, _ = small
        with pytest.raises(ValueError):
            E.ServeEngine(cfg, params, batch_size=3, max_len=T,
                          chunked=True, token_budget=16)


class TestRefExecParity:
    """``REPRO_KERNEL_EXEC=ref`` (kernels/ops.py) routes the paged serving
    kernels to the pure-jnp oracle off-TPU — the execution the serving
    benchmark times. Served tokens must not depend on the execution backend:
    the oracle IS the kernels' semantic ground truth, so a token flip here
    means the two executions disagree beyond argmax resolution."""

    @pytest.mark.parametrize("path,kv", [("dequant-fp", "fp"),
                                         ("fused-int8", "int8")])
    def test_ref_exec_tokens_match_pallas(self, small, path, kv, monkeypatch):
        base, _ = _serve(small, path, kv)
        monkeypatch.setenv("REPRO_KERNEL_EXEC", "ref")
        got, _ = _serve(small, path, kv)
        chk, eng = _serve(small, path, kv, chunked=True, token_budget=12)
        assert got == base
        assert chk == base
        assert eng.counters["chunk_prefill_rows"] > 0

    def test_bad_exec_mode_rejected(self, small, monkeypatch):
        monkeypatch.setenv("REPRO_KERNEL_EXEC", "mosaic")
        from repro.kernels import ops as kops
        with pytest.raises(AssertionError):
            kops._exec_mode()
