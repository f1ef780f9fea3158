"""Pallas TPU kernel: fused flash attention (forward).

Motivation (EXPERIMENTS.md §Perf): the XLA-level blockwise attention materializes
every (q_block × kv_block) score/probability tile through HBM — on deepseek-33b
train_4k those tiles are 87% of the projected HBM traffic (memory term 242 s vs
34 s of compute). A fused kernel keeps the tiles in VMEM: HBM traffic drops to the
q/k/v streams + the output, turning attention from memory-bound into MXU-bound.

Design (TPU-native, GQA-aware):
  grid = (B·H, Sq/bq, Skv/bk), kv innermost. Running max/denominator/accumulator
  live in VMEM scratch across the kv axis (online softmax). k/v BlockSpecs index the
  kv head h // G directly — the (B, Skv, H, D)-broadcasted kv tensor is never
  materialized. Causal/window masking from absolute positions; fully-masked tiles
  short-circuit via pl.when. Logit softcap (gemma2) supported.

Tiling: (bq, bk) = (512, 512) at D ≤ 256 keeps the working set
(q 512·D·4 + k/v 2·512·D·4 + scores 512·512·4 ≈ 2.6 MB at D=128) well inside VMEM
with room for double buffering; all matmul dims are 128-multiples (MXU-aligned).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
# query rows (chunk tokens × groups) per ragged-prefill grid step
RAGGED_ROW_TILE = 128


def _fa_kernel(q_ref, k_ref, v_ref, *refs,
               n_k: int, bq: int, bk: int, scale: float, causal: bool,
               window: Optional[int], softcap: Optional[float],
               n_heads: Optional[int] = None):
    """``n_heads`` is set iff a per-slot kv-length vector is present: ``refs`` then
    leads with ``kvlen_ref``, a (B,) int32 SMEM input indexed by the batch element
    ``program_id(0) // n_heads`` — keys at or beyond that slot's valid length are
    masked (right-padded serving prefill, DESIGN.md §3.6)."""
    if n_heads is not None:
        kvlen_ref, o_ref, m_ref, l_ref, acc_ref = refs
    else:
        o_ref, m_ref, l_ref, acc_ref = refs
    iq = pl.program_id(1)
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_pos = iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    k_pos = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    # Tiles strictly above the causal diagonal contribute nothing; skip the matmul.
    live = True
    if causal:
        live = (ik * bk) <= (iq * bq + bq - 1)
    if n_heads is not None:
        kvl = kvlen_ref[pl.program_id(0) // n_heads]
        # tiles entirely beyond this slot's valid kv length are dead as well
        live = jnp.logical_and(live, ik * bk < kvl)

    @pl.when(live)
    def _tile():
        q = q_ref[0].astype(jnp.float32)                  # (bq, D)
        k = k_ref[0, 0].astype(jnp.float32)               # (bk, D)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if softcap is not None:
            s = softcap * jnp.tanh(s / softcap)
        mask = jnp.ones((bq, bk), jnp.bool_)
        if causal:
            mask &= q_pos >= k_pos
        if window is not None:
            mask &= (q_pos - k_pos) < window
        if n_heads is not None:
            mask &= k_pos < kvl
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=1)
        m_ref[...] = m_new
        v = v_ref[0, 0].astype(jnp.float32)               # (bk, D)
        acc_ref[...] = acc_ref[...] * corr[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(ik == n_k - 1)
    def _emit():
        denom = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / denom[:, None]).astype(o_ref.dtype)


def _scale_rows(scale: jax.Array) -> jax.Array:
    """(P, Hkv, ps) per-token scales → (P, 1, L) f32 rows, lane ``h·ps + t``,
    zero-padded to L = a multiple of 128: Mosaic DMAs an HBM tile only when
    its minor dimension is lane-aligned, and one page's scales (Hkv·ps values)
    are fewer than 128 lanes at the engine's page sizes."""
    P, Hkv, ps = scale.shape
    rows = scale.astype(jnp.float32).reshape(P, 1, Hkv * ps)
    return jnp.pad(rows, ((0, 0), (0, 0), (0, (-(Hkv * ps)) % 128)))


def _walk_pages(tab_ref, b, n_pages_max: int, n_live, k_hbm, v_hbm, s_hbm,
                page_step, init, finish):
    """Double-buffered walk over slot ``b``'s first ``n_live`` pages.

    The pools stay in HBM (``memory_space=ANY``) as lane-dense (P, ps, Hkv·D)
    tiles; while page ``j`` computes, page ``j+1``'s K/V tiles (and, int8-KV,
    its two (1, L) scale rows from ``s_hbm``) are already in flight into the
    spare VMEM slot. Page indices come from the scalar-prefetched flattened
    (B·max_pages,) page table in SMEM, so the dense (B, T, Hkv, D) view is
    never materialized and pages past ``n_live`` are never fetched.

    Sentinel entries (≥ P) clamp to a valid page. For live rows the clamp is
    unreachable below kv_len (the engine maps every valid position to a real
    page), so the fetched bytes never contribute; a row whose table is *all*
    sentinel (a retired slot decoding in lock-step with kv_len ≥ 1) attends
    the clamped page and produces garbage-but-finite output — the engine
    discards it, and the oracle's (differently-)clamped gather is equally
    arbitrary there (pinned in tests/test_paged_serving.py).

    ``page_step(j, k_tile, v_tile, scales, carry)`` gets the VMEM tile refs
    and the (2, 1, L) scale rows (None on fp pools); ``finish(state)`` writes
    the outputs from the final carry."""
    P = k_hbm.shape[0]

    def body(kbuf, vbuf, sbuf, sem):
        def dmas(slot, j):
            page = jnp.minimum(
                tab_ref[b * n_pages_max + jnp.minimum(j, n_pages_max - 1)], P - 1)
            copies = [
                pltpu.make_async_copy(k_hbm.at[page], kbuf.at[slot],
                                      sem.at[slot, 0]),
                pltpu.make_async_copy(v_hbm.at[page], vbuf.at[slot],
                                      sem.at[slot, 1]),
            ]
            if s_hbm is not None:
                copies += [
                    pltpu.make_async_copy(s_hbm[i].at[page], sbuf.at[slot, i],
                                          sem.at[slot, 2 + i])
                    for i in range(2)]
            return copies

        @pl.when(n_live > 0)
        def _warmup():
            for c in dmas(0, 0):
                c.start()

        def step(j, carry):
            slot = jax.lax.rem(j, 2)

            @pl.when(j + 1 < n_live)
            def _prefetch():
                for c in dmas(1 - slot, j + 1):
                    c.start()

            for c in dmas(slot, j):
                c.wait()
            scales = sbuf[slot] if s_hbm is not None else None
            return page_step(j, kbuf.at[slot], vbuf.at[slot], scales, carry)

        finish(jax.lax.fori_loop(0, n_live, step, init))

    L = s_hbm[0].shape[-1] if s_hbm is not None else 128
    pl.run_scoped(
        body,
        kbuf=pltpu.VMEM((2,) + k_hbm.shape[1:], k_hbm.dtype),
        vbuf=pltpu.VMEM((2,) + v_hbm.shape[1:], v_hbm.dtype),
        sbuf=pltpu.VMEM((2, 2, 1, L), jnp.float32),
        sem=pltpu.SemaphoreType.DMA((2, 4)),
    )


def _online_update(carry, q, k, v, mask, ks, vs, *, scale: float,
                   softcap: Optional[float]):
    """One page of online softmax for one kv head: q (R, D), k/v (ps, D) f32,
    mask (R, ps), ``ks``/``vs`` (1, ps) int8-KV per-token scales or None.

    The K scale multiplies the score column and the V scale folds into the
    probability row — the exact application points of the dense
    ``layers.decode_attention`` int8 path (scale → softcap → mask → softmax).
    Masked positions drop through the probability row:
    ``p = where(mask, exp(s - m), 0)`` zeroes their l/acc contribution exactly
    (bitwise equal to a -1e30 score mask, whose exp underflows to 0.0 in f32)."""
    m_prev, l_prev, acc_prev = carry
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if ks is not None:
        s = s * ks
    if softcap is not None:
        s = softcap * jnp.tanh(s / softcap)
    m_new = jnp.maximum(m_prev, jnp.max(jnp.where(mask, s, NEG_INF), axis=1))
    p = jnp.where(mask, jnp.exp(s - m_new[:, None]), 0.0)
    corr = jnp.exp(m_prev - m_new)
    pv = p * vs if vs is not None else p
    return (m_new, l_prev * corr + jnp.sum(p, axis=1),
            acc_prev * corr[:, None] + jax.lax.dot_general(
                pv, v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32))


def _init_carry(n_kv_heads: int, R: int, D: int):
    return tuple((jnp.full((R,), NEG_INF, jnp.float32),
                  jnp.zeros((R,), jnp.float32),
                  jnp.zeros((R, D), jnp.float32))
                 for _ in range(n_kv_heads))


def _normalized(carry):
    _, l, acc = carry
    return acc / jnp.maximum(l, 1e-30)[:, None]


def _paged_decode_kernel(tab_ref, kvlen_ref, *rest,
                         ps: int, n_pages_max: int, n_kv_heads: int,
                         head_dim: int, scale: float, window: Optional[int],
                         softcap: Optional[float], kv_int8: bool,
                         q_win: int = 1):
    """Decode / draft-verify attention through a page table (DESIGN.md §3.8/§3.9).

    grid = (B,). Each slot walks its live pages (``_walk_pages``: the loop
    bound is ``ceil(kv_len / ps)``) with an online softmax across the page
    loop; the kv-head axis is a static unrolled loop over lane slices of the
    lane-dense (ps, Hkv·D) page tile — one (R, ps) score tile per head per
    page. Heads split in VMEM, never in the DMA, so D = 64 needs no padding.

    ``q_win > 1`` (speculative verify, DESIGN.md §3.9): q carries a draft
    window of ``q_win`` tokens per slot — rows ordered (window, group), so row
    ``r`` of the (q_win·G, ps) score tile belongs to window position
    ``r // G``. A third scalar-prefetch vector ``q_len`` (B,) gives each slot's
    *valid* window length (1 ≤ q_len ≤ q_win); ``kv_len`` counts the slot's
    total post-scatter length, window token i sitting at absolute position
    ``kv_len - q_len + i``. The causal mask is per-row: window token i attends
    keys ≤ its own position, so the same page pipeline serves every window row
    in one pass. Rows past ``q_len`` clamp to the last valid position —
    finite-but-garbage output the engine discards. ``q_win == 1`` (no q_len
    input) degenerates bitwise to single-token decode."""
    if q_win > 1:
        qlen_ref, q_ref, k_hbm, v_hbm, *refs = rest
    else:
        qlen_ref, (q_ref, k_hbm, v_hbm), refs = None, rest[:3], rest[3:]
    s_hbm, o_ref = (refs[:2], refs[2]) if kv_int8 else (None, refs[0])
    b = pl.program_id(0)
    kvl = kvlen_ref[b]
    R, D = q_ref.shape[2], head_dim          # R = q_win * G score-tile rows
    G = R // q_win
    if q_win > 1:
        qln = qlen_ref[b]
        # absolute position of each score-tile row's window token (clamped to
        # the newest valid token for rows past q_len)
        win_idx = jax.lax.broadcasted_iota(jnp.int32, (R, ps), 0) // G
        q_pos = (kvl - qln) + jnp.minimum(win_idx, qln - 1)

    def page_step(j, kt, vt, sc, carry):
        k_pos = j * ps + jax.lax.broadcasted_iota(jnp.int32, (R, ps), 1)
        if q_win > 1:
            # per-row causality: window token i attends keys ≤ its own
            # absolute position (row 0 ≡ the single-token decode mask)
            mask = k_pos <= q_pos
            if window is not None:
                mask &= (q_pos - k_pos) < window
        else:
            mask = k_pos < kvl
            if window is not None:
                # decode window semantics (layers.decode_attention): the
                # newest token sits at kvl - 1
                mask &= (kvl - 1 - k_pos) < window
        out = []
        for h in range(n_kv_heads):          # static unroll over kv heads
            lanes, srow = slice(h * D, (h + 1) * D), slice(h * ps, (h + 1) * ps)
            out.append(_online_update(
                carry[h], q_ref[0, h].astype(jnp.float32),
                kt[:, lanes].astype(jnp.float32),
                vt[:, lanes].astype(jnp.float32), mask,
                sc[0, :, srow] if kv_int8 else None,
                sc[1, :, srow] if kv_int8 else None,
                scale=scale, softcap=softcap))
        return tuple(out)

    def finish(state):
        for h in range(n_kv_heads):
            o_ref[0, h] = _normalized(state[h]).astype(o_ref.dtype)

    _walk_pages(tab_ref, b, n_pages_max, pl.cdiv(kvl, ps), k_hbm, v_hbm,
                s_hbm, page_step, _init_carry(n_kv_heads, R, D), finish)


def paged_decode_attention_pallas(
    q: jax.Array, k_pages: jax.Array, v_pages: jax.Array,
    page_table: jax.Array, kv_len: jax.Array, *,
    k_scale: Optional[jax.Array] = None, v_scale: Optional[jax.Array] = None,
    q_win: int = 1, q_len: Optional[jax.Array] = None,
    window: Optional[int] = None, softcap: Optional[float] = None,
    interpret: bool = False,
) -> jax.Array:
    """q: (B, Hkv, q_win·G, D); k/v pages: (P, ps, Hkv, D); page_table:
    (B, maxP) int32 (entries ≥ P are invalid — clamped in the kernel and
    masked by ``kv_len``); kv_len: (B,) int32 with kv_len ≤ maxP·ps
    → (B, Hkv, q_win·G, D). The pools stay in HBM, viewed lane-dense as
    (P, ps, Hkv·D); the kernel DMAs each live page's tile on demand
    (double-buffered — see ``_walk_pages``).

    ``k_scale``/``v_scale`` (both or neither): int8-KV per-token scales in the
    (P, Hkv, ps) row layout — ``ops.paged_decode_attention`` transposes the
    engine's (P, ps, Hkv, 1) scale pools, D× smaller than the code pools.
    They travel as one lane-aligned row per page (``_scale_rows``) on the
    same per-page DMA pipeline as the code tiles and apply in-kernel at the
    score/prob level (dense ``decode_attention`` numerics) — the int8 path
    never materializes a dense (B, T, ...) view either.

    ``q_win > 1`` + ``q_len`` (B,) int32: draft-window verify (DESIGN.md
    §3.9). q's third axis carries ``q_win`` window tokens × G group heads in
    (window, group) row order; ``kv_len`` counts each slot's total
    post-scatter length so window token i sits at ``kv_len - q_len + i``, and
    rows past ``q_len`` produce garbage-but-finite output the engine discards.
    """
    B, Hkv, R, D = q.shape
    assert R % q_win == 0, (R, q_win)
    P, ps = k_pages.shape[0], k_pages.shape[1]
    maxP = page_table.shape[1]
    assert page_table.shape == (B, maxP) and kv_len.shape == (B,)
    assert (q_len is not None) == (q_win > 1), "q_len iff q_win > 1"
    kv_int8 = k_scale is not None
    assert kv_int8 == (v_scale is not None), "pass both scale pools or neither"

    kernel = functools.partial(
        _paged_decode_kernel, ps=ps, n_pages_max=maxP, n_kv_heads=Hkv,
        head_dim=D, scale=D ** -0.5, window=window, softcap=softcap,
        kv_int8=kv_int8, q_win=q_win)
    n_pref = 2 if q_win == 1 else 3
    qmap = lambda b, *pref: (b, 0, 0, 0)
    in_specs = [
        pl.BlockSpec((1, Hkv, R, D), qmap),
        pl.BlockSpec(memory_space=pl.ANY),        # k pool, paged via DMA
        pl.BlockSpec(memory_space=pl.ANY),        # v pool
    ]
    args = [q, k_pages.reshape(P, ps, Hkv * D), v_pages.reshape(P, ps, Hkv * D)]
    if kv_int8:
        assert k_scale.shape == v_scale.shape == (P, Hkv, ps), (
            k_scale.shape, (P, Hkv, ps))
        in_specs += [pl.BlockSpec(memory_space=pl.ANY)] * 2
        args += [_scale_rows(k_scale), _scale_rows(v_scale)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=n_pref,
        grid=(B,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, Hkv, R, D), qmap),
    )
    pref = [page_table.reshape(-1).astype(jnp.int32), kv_len.astype(jnp.int32)]
    if q_win > 1:
        assert q_len.shape == (B,), q_len.shape
        pref.append(q_len.astype(jnp.int32))
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, R, D), q.dtype),
        interpret=interpret,
    )(*pref, *args)


def _ragged_prefill_kernel(tab_ref, qlen_ref, kvlen_ref,
                           q_ref, kn_ref, vn_ref, k_hbm, v_hbm, *refs,
                           ps: int, n_pages_max: int, n_kv_heads: int,
                           n_groups: int, head_dim: int, row_tile: int,
                           scale: float, window: Optional[int],
                           softcap: Optional[float], kv_int8: bool):
    """Ragged chunked-prefill attention over the paged pool (DESIGN.md §3.10).

    grid = (B, row tiles). Slot ``b``'s chunk arrives as its own zero-padded
    (C·G, Hkv·D) query rows — row ``r`` is chunk token ``r // G``, group
    ``r % G``, lanes (kv head, d) — and grid step (b, rb) scores rows
    ``[rb·TR, (rb+1)·TR)`` of it. A tile past the slot's ``q_len·G`` valid
    rows is dead: it walks no page and writes zeros. A live tile walks only
    the pages its newest token can see (causal skipping), through the same
    double-buffered DMA pipeline as ``_paged_decode_kernel`` — int8-KV scale
    rows included — so warm (radix-hit) suffix prefill, cold prefill, later
    chunks of the same prompt, and the q_len == 1 decode degenerate share one
    launch.

    The chunk starts at absolute position ``cs = kv_len - q_len`` (cs ==
    prefix_len for the first chunk); chunk token i sits at ``cs + i`` and the
    causal mask is per score-tile row. Key positions inside ``[cs, kv_len)``
    — the chunk's own tokens, already scattered into the pool before the
    launch — are *overlaid* with the slot's floating-point ``k_new``/``v_new``
    rows (and their int8-KV scales neutralized to 1.0): the chunk attends
    itself unquantized, exactly the in-flight fp-suffix overlay of
    ``layers.paged_prefill_attention``, so chunked numerics match the
    bucketed warm path. The per-slot overlay rows start at the chunk's first
    page boundary, so every per-page overlay slice is page-aligned even when
    a chunk starts mid-page."""
    s_hbm, o_ref = (refs[:2], refs[2]) if kv_int8 else (None, refs[0])
    b, rb = pl.program_id(0), pl.program_id(1)
    qn = qlen_ref[b]
    kvl = kvlen_ref[b]
    cs = kvl - qn                               # chunk's first absolute position
    TR, G, D = row_tile, n_groups, head_dim
    n_ov = kn_ref.shape[1]
    row0 = rb * TR
    last_tok = jnp.minimum((row0 + TR - 1) // G, qn - 1)
    n_live = jnp.where(row0 < qn * G, pl.cdiv(cs + last_tok + 1, ps), 0)
    tok = (row0 + jax.lax.broadcasted_iota(jnp.int32, (TR, ps), 0)) // G
    q_pos = cs + jnp.minimum(tok, jnp.maximum(qn - 1, 0))

    def page_step(j, kt, vt, sc, carry):
        k_pos = j * ps + jax.lax.broadcasted_iota(jnp.int32, (TR, ps), 1)
        mask = k_pos <= q_pos
        if window is not None:
            mask &= (q_pos - k_pos) < window
        # fp overlay of the chunk's own tokens: in-page rows at absolute
        # positions >= cs read the slot's fp k_new/v_new rows instead of the
        # pool (and skip the int8 scales). Overlay row r holds position
        # ps·(cs // ps) + r, so page j starts at a page-aligned row; the start
        # clamps so pure-history pages stay in-bounds — their rows all fail
        # the >= cs test, so the fetched rows never contribute.
        icd = (jax.lax.broadcasted_iota(jnp.int32, (ps, D), 0) + j * ps) >= cs
        ic2 = (jax.lax.broadcasted_iota(jnp.int32, (1, ps), 1) + j * ps) >= cs
        off = pl.multiple_of(
            jnp.clip(j - cs // ps, 0, n_ov // ps - 1) * ps, ps)
        ov_k = kn_ref[0, pl.ds(off, ps), :]                   # (ps, Hkv·D)
        ov_v = vn_ref[0, pl.ds(off, ps), :]
        out = []
        for h in range(n_kv_heads):          # static unroll over kv heads
            lanes, srow = slice(h * D, (h + 1) * D), slice(h * ps, (h + 1) * ps)
            out.append(_online_update(
                carry[h], q_ref[0, :, lanes].astype(jnp.float32),
                jnp.where(icd, ov_k[:, lanes], kt[:, lanes].astype(jnp.float32)),
                jnp.where(icd, ov_v[:, lanes], vt[:, lanes].astype(jnp.float32)),
                mask,
                jnp.where(ic2, 1.0, sc[0, :, srow]) if kv_int8 else None,
                jnp.where(ic2, 1.0, sc[1, :, srow]) if kv_int8 else None,
                scale=scale, softcap=softcap))
        return tuple(out)

    def finish(state):
        o_ref[0] = jnp.concatenate([_normalized(c) for c in state],
                                   axis=1).astype(o_ref.dtype)

    _walk_pages(tab_ref, b, n_pages_max, n_live, k_hbm, v_hbm, s_hbm,
                page_step, _init_carry(n_kv_heads, TR, D), finish)


def ragged_prefill_attention_pallas(
    q: jax.Array, k_new: jax.Array, v_new: jax.Array,
    k_pages: jax.Array, v_pages: jax.Array,
    page_table: jax.Array, q_start: jax.Array, q_len: jax.Array,
    kv_len: jax.Array, *, chunk_cap: int,
    k_scale: Optional[jax.Array] = None, v_scale: Optional[jax.Array] = None,
    window: Optional[int] = None, softcap: Optional[float] = None,
    interpret: bool = False,
) -> jax.Array:
    """q: (Hkv, N, G, D) packed ragged queries; k_new/v_new: (N, Hkv, D) the
    chunk tokens' fp K/V in the same packed layout; pools/page_table/scales
    exactly as :func:`paged_decode_attention_pallas`; q_start/q_len/kv_len:
    (B,) int32 per-slot packed offset, chunk length (≤ chunk_cap; 0 ⇒ dead
    slot) and total post-scatter visible length → (Hkv, N, G, D) with slot
    b's rows at ``[q_start[b], q_start[b] + q_len[b])`` and every other row
    zero.

    One launch serves cold prefill, warm suffix prefill, mid-prompt chunks
    and single-token decode rows (see ``_ragged_prefill_kernel``); the pools
    never materialize a dense view and dead slots skip their page walk. The
    packed rows are regrouped per slot here (queries lane-dense as
    (B, C·G, Hkv·D), overlay rows page-aligned as (B, ps·(⌈C/ps⌉ + 1),
    Hkv·D) f32), so the
    kernel's blocks are fixed-size tiles the grid pipelines and the VMEM
    footprint does not grow with N.
    """
    Hkv, N, G, D = q.shape
    P, ps = k_pages.shape[0], k_pages.shape[1]
    B, maxP = page_table.shape
    C = chunk_cap
    assert k_new.shape == v_new.shape == (N, Hkv, D), (k_new.shape, q.shape)
    assert q_start.shape == q_len.shape == kv_len.shape == (B,)
    assert C >= 1, C
    kv_int8 = k_scale is not None
    assert kv_int8 == (v_scale is not None), "pass both scale pools or neither"

    qs = q_start.astype(jnp.int32)
    rows = C * G
    TR = min(rows, RAGGED_ROW_TILE)
    n_tiles = pl.cdiv(rows, TR)
    # per-slot query rows: (B, C, G, Hkv·D) → (B, C·G, Hkv·D), padded to tiles
    tok = jnp.clip(qs[:, None] + jnp.arange(C)[None], 0, N - 1)      # (B, C)
    q_ld = jnp.transpose(q, (1, 2, 0, 3)).reshape(N, G, Hkv * D)[tok]
    q_ld = jnp.pad(q_ld.reshape(B, rows, Hkv * D),
                   ((0, 0), (0, n_tiles * TR - rows), (0, 0)))
    # per-slot fp overlay rows from the chunk's first page boundary: local
    # row r holds absolute position ps·(cs // ps) + r
    cs = kv_len.astype(jnp.int32) - q_len.astype(jnp.int32)
    n_ov = (pl.cdiv(C, ps) + 1) * ps
    ov = jnp.clip((qs - cs % ps)[:, None] + jnp.arange(n_ov)[None], 0, N - 1)
    kn = k_new.astype(jnp.float32).reshape(N, Hkv * D)[ov]
    vn = v_new.astype(jnp.float32).reshape(N, Hkv * D)[ov]

    kernel = functools.partial(
        _ragged_prefill_kernel, ps=ps, n_pages_max=maxP, n_kv_heads=Hkv,
        n_groups=G, head_dim=D, row_tile=TR, scale=D ** -0.5, window=window,
        softcap=softcap, kv_int8=kv_int8)

    def q_tile(b, rb, tab, qln, kvl):
        # dead tiles re-point at the last live one: no refetch
        last = jnp.maximum(pl.cdiv(qln[b] * G, TR) - 1, 0)
        return (b, jnp.minimum(rb, last), 0)

    in_specs = [
        pl.BlockSpec((1, TR, Hkv * D), q_tile),
        pl.BlockSpec((1, n_ov, Hkv * D), lambda b, rb, *_: (b, 0, 0)),
        pl.BlockSpec((1, n_ov, Hkv * D), lambda b, rb, *_: (b, 0, 0)),
        pl.BlockSpec(memory_space=pl.ANY),        # k pool, paged via DMA
        pl.BlockSpec(memory_space=pl.ANY),        # v pool
    ]
    args = [q_ld, kn, vn, k_pages.reshape(P, ps, Hkv * D),
            v_pages.reshape(P, ps, Hkv * D)]
    if kv_int8:
        assert k_scale.shape == v_scale.shape == (P, Hkv, ps), (
            k_scale.shape, (P, Hkv, ps))
        in_specs += [pl.BlockSpec(memory_space=pl.ANY)] * 2
        args += [_scale_rows(k_scale), _scale_rows(v_scale)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, n_tiles),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, TR, Hkv * D), lambda b, rb, *_: (b, rb, 0)),
    )
    pref = [page_table.reshape(-1).astype(jnp.int32),
            q_len.astype(jnp.int32), kv_len.astype(jnp.int32)]
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, n_tiles * TR, Hkv * D), q.dtype),
        interpret=interpret,
    )(*pref, *args)
    # back to the packed layout: slot b's token i → row q_start[b] + i
    out = out[:, :rows].reshape(B * C, G, Hkv, D)
    tgt = jnp.where(jnp.arange(C)[None] < q_len.astype(jnp.int32)[:, None],
                    qs[:, None] + jnp.arange(C)[None], N).reshape(-1)
    packed = jnp.zeros((N, G, Hkv, D), q.dtype).at[tgt].set(out, mode="drop")
    return jnp.transpose(packed, (2, 0, 1, 3))


def flash_attention_pallas(
    q: jax.Array, k: jax.Array, v: jax.Array,
    kv_len: Optional[jax.Array] = None, *,
    causal: bool = True, window: Optional[int] = None,
    softcap: Optional[float] = None, bq: int = 512, bk: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """q: (B, H, Sq, D); k/v: (B, Hkv, Skv, D) with H % Hkv == 0 → (B, H, Sq, D).

    Sq % bq == Skv % bk == 0 (ops.py pads). Positions are 0-based on both axes
    (prefill self-attention; for q_offset semantics pre-slice the kv).

    ``kv_len`` (B,) int32 masks, per batch element, keys at positions ≥ kv_len[b]
    — the per-slot valid prompt length of right-padded continuous-batching prefill
    (DESIGN.md §3.6). It rides in SMEM so the mask is one scalar compare per tile.
    """
    B, H, Sq, D = q.shape
    _, Hkv, Sk, _ = k.shape
    G = H // Hkv
    assert Sq % bq == 0 and Sk % bk == 0, (Sq, Sk, bq, bk)
    n_q, n_k = Sq // bq, Sk // bk
    grid = (B * H, n_q, n_k)
    scale = D ** -0.5

    kernel = functools.partial(
        _fa_kernel, n_k=n_k, bq=bq, bk=bk, scale=scale, causal=causal,
        window=window, softcap=softcap,
        n_heads=H if kv_len is not None else None)
    q3 = q.reshape(B * H, Sq, D)
    in_specs = [
        pl.BlockSpec((1, bq, D), lambda bh, iq, ik: (bh, iq, 0)),
        # kv head = (bh % H) // G: GQA indexing, no (B,H,Skv,D) broadcast
        pl.BlockSpec((1, 1, bk, D),
                     lambda bh, iq, ik: (bh // H, (bh % H) // G, ik, 0)),
        pl.BlockSpec((1, 1, bk, D),
                     lambda bh, iq, ik: (bh // H, (bh % H) // G, ik, 0)),
    ]
    args = [q3, k, v]
    if kv_len is not None:
        assert kv_len.shape == (B,), kv_len.shape
        in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
        args.append(kv_len.astype(jnp.int32))
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, bq, D), lambda bh, iq, ik: (bh, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, Sq, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq, D), jnp.float32),
        ],
        interpret=interpret,
    )(*args).reshape(B, H, Sq, D)
