"""Transformer building blocks: norms, RoPE, blockwise (flash-style) attention, MLPs.

All modules are functional: ``init_*`` builds a params dict, ``*_apply`` consumes it.
Quantized linears go through :mod:`repro.core.qlinear` so every GEMM obeys the model's
:class:`QuantConfig` (fp / fake CrossQuant / int8 static-c).
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import qlinear as ql
from repro.configs.base import ModelConfig
from repro.kernels import paged_layout
from repro.sharding import hints


@dataclasses.dataclass
class QuantContext:
    """Threaded through every layer: quant behaviour + (eager-only) calibration.

    ``int_exec`` picks the execution backend for *prepared* integer linears
    (``None``/"ref" | "dequant" | "pallas" — DESIGN.md §3.3); ``use_pallas=True``
    additionally routes prefill attention through the flash kernel.
    """
    cfg: ql.QuantConfig
    observer: object = None
    prefix: str = ""
    use_pallas: bool = False
    int_exec: Optional[str] = None

    def sub(self, name: str) -> "QuantContext":
        return QuantContext(self.cfg, self.observer, f"{self.prefix}/{name}",
                            self.use_pallas, self.int_exec)

    def linear(self, params: dict, x: jax.Array, name: str) -> jax.Array:
        return ql.apply(params, x, self.cfg, name=f"{self.prefix}/{name}",
                        observer=self.observer, use_pallas=self.use_pallas,
                        int_exec=self.int_exec)


# ======================================================================================
# Norms
# ======================================================================================

def init_norm(cfg: ModelConfig, d: Optional[int] = None) -> dict:
    d = d or cfg.d_model
    p = {"scale": jnp.ones((d,), jnp.float32)}
    if cfg.norm == "layernorm":
        p["bias"] = jnp.zeros((d,), jnp.float32)
    return p


def norm_apply(params: dict, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    xf = x.astype(jnp.float32)
    if cfg.norm == "rmsnorm":
        y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + 1e-6)
        y = y * params["scale"]
    else:
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.var(xf, axis=-1, keepdims=True)
        y = (xf - mu) * jax.lax.rsqrt(var + 1e-5) * params["scale"] + params["bias"]
    return y.astype(x.dtype)


# ======================================================================================
# RoPE
# ======================================================================================

def rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (..., S, H, D); positions: broadcastable to (..., S)."""
    d = x.shape[-1]
    half = d // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    angles = positions[..., None].astype(jnp.float32) * freqs        # (..., S, half)
    cos = jnp.cos(angles)[..., None, :]                              # (..., S, 1, half)
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ======================================================================================
# Attention
# ======================================================================================

def init_attention(key, cfg: ModelConfig) -> dict:
    d = cfg.d_model
    hd = cfg.n_heads * cfg.head_dim
    kvd = cfg.n_kv_heads * cfg.head_dim
    ks = jax.random.split(key, 4)
    return {
        "wq": ql.init(ks[0], d, hd),
        "wk": ql.init(ks[1], d, kvd),
        "wv": ql.init(ks[2], d, kvd),
        "wo": ql.init(ks[3], hd, d),
    }


def _softcap(logits: jax.Array, cap: Optional[float]) -> jax.Array:
    if cap is None:
        return logits
    return cap * jnp.tanh(logits / cap)


def _block_mask(q_pos, k_pos, causal: bool, window: Optional[int]):
    """(Bq, Bk) boolean validity mask from absolute positions."""
    m = jnp.ones((q_pos.shape[0], k_pos.shape[0]), bool)
    if causal:
        m &= q_pos[:, None] >= k_pos[None, :]
    if window is not None:
        m &= (q_pos[:, None] - k_pos[None, :]) < window
    return m


def blockwise_attention(
    q: jax.Array, k: jax.Array, v: jax.Array, *,
    causal: bool, window: Optional[int], softcap: Optional[float],
    q_offset: int | jax.Array = 0, kv_valid_len: Optional[jax.Array] = None,
    q_block: int = 1024, kv_block: int = 1024,
) -> jax.Array:
    """Memory-efficient multihead attention (online softmax over KV blocks).

    q: (B, Sq, H, D); k/v: (B, Sk, Hkv, D). GQA handled by head-group reshape so the
    kv tensor is never materialized at H heads. O(Sq·Sk) FLOPs, O(block²) memory.
    This is the jnp oracle mirrored by the Pallas flash kernel.
    """
    B, Sq, H, D = q.shape
    _, Sk, Hkv, _ = k.shape
    G = H // Hkv                                   # query heads per kv head
    scale = D ** -0.5

    # Pad to block multiples.
    pq = (-Sq) % q_block
    pk = (-Sk) % kv_block
    qp = jnp.pad(q, ((0, 0), (0, pq), (0, 0), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, pk), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, pk), (0, 0), (0, 0)))
    nq, nk = qp.shape[1] // q_block, kp.shape[1] // kv_block

    qp = qp.reshape(B, nq, q_block, Hkv, G, D)
    kp = kp.reshape(B, nk, kv_block, Hkv, D)
    vp = vp.reshape(B, nk, kv_block, Hkv, D)

    q_pos_base = jnp.arange(q_block)
    k_pos_base = jnp.arange(kv_block)

    def q_step(iq):
        qb = qp[:, iq]                                            # (B, Bq, Hkv, G, D)
        q_pos = q_offset + iq * q_block + q_pos_base

        def kv_step(carry, jk):
            m, l, acc = carry
            kb, vb = kp[:, jk], vp[:, jk]                         # (B, Bk, Hkv, D)
            k_pos = jk * kv_block + k_pos_base
            s = jnp.einsum("bqhgd,bkhd->bhgqk", qb, kb) * scale   # (B,Hkv,G,Bq,Bk)
            s = _softcap(s.astype(jnp.float32), softcap)
            valid = _block_mask(q_pos, k_pos, causal, window)
            # Padded key positions (Sk rounded up to kv_block) must never attend —
            # the causal mask happens to exclude them for suffix queries, but
            # non-causal/windowless paths would include the zero-padding otherwise.
            valid = valid & (k_pos[None, :] < Sk)
            valid = valid[None, None, None]                       # (1,1,1,Bq,Bk)
            if kv_valid_len is not None:
                # scalar or per-slot (B,) valid kv length (right-padded prompts)
                kvl = jnp.reshape(kv_valid_len, (-1, 1, 1, 1, 1))
                valid = valid & (k_pos[None, None, None, None, :] < kvl)
            s = jnp.where(valid, s, -1e30)
            m_new = jnp.maximum(m, s.max(axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + p.sum(axis=-1)
            acc_new = acc * corr[..., None] + jnp.einsum(
                "bhgqk,bkhd->bhgqd", p.astype(vb.dtype), vb).astype(jnp.float32)
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((B, Hkv, G, q_block), -jnp.inf, jnp.float32)
        l0 = jnp.zeros((B, Hkv, G, q_block), jnp.float32)
        a0 = jnp.zeros((B, Hkv, G, q_block, D), jnp.float32)
        # Nested remat: without it the scan's AD saves every (q_block, kv_block)
        # probability tile — a full S×S attention matrix per layer (1.75 GiB/device at
        # 4k on deepseek-33b, EXPERIMENTS.md §Perf) — which defeats the point of
        # blockwise attention. With it the backward recomputes tiles one at a time.
        (m, l, acc), _ = jax.lax.scan(jax.checkpoint(kv_step), (m0, l0, a0),
                                      jnp.arange(nk))
        out = acc / jnp.maximum(l, 1e-30)[..., None]              # (B,Hkv,G,Bq,D)
        return out

    outs = jax.lax.map(q_step, jnp.arange(nq))                    # (nq,B,Hkv,G,Bq,D)
    out = jnp.moveaxis(outs, 0, 1)                                # (B,nq,Hkv,G,Bq,D)
    out = out.transpose(0, 1, 4, 2, 3, 5).reshape(B, nq * q_block, H, D)
    return out[:, :Sq].astype(q.dtype)


def kv_quantize(x: jax.Array):
    """Per-token int8 KV quantization (DESIGN.md §3.3): reduce absmax over the head
    dim, one f32 scale per (batch, position, kv-head). x (B, S, Hkv, D) →
    (codes (B, S, Hkv, D) int8, scale (B, S, Hkv, 1) f32)."""
    from repro.core import quantizers as Q
    qr = Q.per_token_quant(x.astype(jnp.float32), 8)
    return qr.codes, qr.scale


def _scale_to_scores(scale: jax.Array) -> jax.Array:
    """(B, T, Hkv, 1) per-token KV scale → (B, Hkv, 1, T) score-broadcast layout."""
    return jnp.transpose(scale[..., 0], (0, 2, 1))[:, :, None, :]


# --------------------------------------------------------------------- paged KV

def _pool_write(cache: dict, flat_idx: jax.Array, k: jax.Array,
                v: jax.Array) -> dict:
    """Write the new tokens' K/V rows ``k``/``v`` (N, Hkv, D) at flat page
    positions ``flat_idx`` (N,) (page·ps + t) straight into the stored pools
    (``kernels/paged_layout.py``): int8 codes plus per-token scale rows on an
    int8-KV cache, the fp rows otherwise. Indices ≥ P·ps (sentinel page-table
    entries, padded batch rows) are dropped — pages of other sequences are
    never touched because the engine hands every live position exactly one
    page slot. Returns the new pool dict, its placement pinned."""
    ps, (Hkv, D) = cache["k_pages"].shape[1], k.shape[1:]
    codes = lambda name, rows: paged_layout.write_codes(cache[name], flat_idx,
                                                        rows)
    if "k_scale_pages" in cache:
        (kq, ks), (vq, vs) = kv_quantize(k), kv_quantize(v)
        scales = lambda name, rows: paged_layout.write_scales(
            cache[name], flat_idx, rows, ps)
        new = {"k_pages": codes("k_pages", kq), "v_pages": codes("v_pages", vq),
               "k_scale_pages": scales("k_scale_pages", ks),
               "v_scale_pages": scales("v_scale_pages", vs)}
    else:
        new = {"k_pages": codes("k_pages", k), "v_pages": codes("v_pages", v)}
    return {name: hints.constrain_kv_pages(
                pool, Hkv, ps if name.endswith("scale_pages") else D)
            for name, pool in new.items()}


def _pool_gather(cache: dict, page_table: jax.Array, n_kv_heads: int):
    """Materialize the logical (B, max_pages·ps, Hkv, D) f32 K and V of the
    pools through the page table, int8 codes dequantized with their per-token
    scales. Sentinel entries clamp to an arbitrary valid page — callers mask
    those positions before the softmax. With ``max_pages·ps == max_len`` the
    result is positionally identical to a dense (B, T, ...) cache row.
    Warm-prefix *prefill* only (``paged_prefill_attention`` reads the shared
    prefix back once per admission): decode never gathers — it runs the
    gather-free Pallas paged kernel on every path (DESIGN.md §3.8)."""
    P, ps = cache["k_pages"].shape[:2]
    B = page_table.shape[0]
    tab = jnp.clip(page_table, 0, P - 1)

    def logical(pages):                    # (B, maxP, ps, Hkv, D|1) → (B, T, ...)
        return pages.reshape((B, -1) + pages.shape[3:]).astype(jnp.float32)

    kf, vf = (logical(paged_layout.codes_view(cache[n][tab], n_kv_heads))
              for n in ("k_pages", "v_pages"))
    if "k_scale_pages" in cache:
        kf, vf = (f * logical(paged_layout.scales_view(cache[n][tab],
                                                       n_kv_heads, ps))
                  for f, n in ((kf, "k_scale_pages"), (vf, "v_scale_pages")))
    return kf, vf


def paged_prefill_attention(
    q: jax.Array, k_new: jax.Array, v_new: jax.Array, cache: dict,
    page_table: jax.Array, *, prefix_len: jax.Array, suffix_len: jax.Array,
    window: Optional[int], softcap: Optional[float],
) -> jax.Array:
    """Suffix prefill against a shared paged prefix (DESIGN.md §3.8).

    q/k_new/v_new: (B, S, H|Hkv, D) — the *suffix* tokens only, right-padded to
    S with per-slot valid count ``suffix_len``; ``prefix_len`` tokens per slot
    already live in the pool (mapped by ``page_table``). Prefix keys/values are
    read back from the pool (int8 codes dequantized with their per-token scale
    pages); suffix keys use the in-flight fp k/v — the same dense-prefill
    semantics as the cold path, so a zero-prefix row computes the cold result.
    Absolute positions: suffix query i sits at ``prefix_len[b] + i``.
    """
    B, S, H, D = q.shape
    Hkv = k_new.shape[2]
    G = H // Hkv
    kf, vf = _pool_gather(cache, page_table, Hkv)
    T = kf.shape[1]

    pl_ = jnp.reshape(prefix_len, (-1,)).astype(jnp.int32)
    sl = jnp.reshape(suffix_len, (-1,)).astype(jnp.int32)
    abs_pos = pl_[:, None] + jnp.arange(S)[None, :]                  # (B, S)
    row_valid = jnp.arange(S)[None, :] < sl[:, None]
    # overlay the in-flight suffix at its absolute positions (invalid rows drop)
    tgt = jnp.where(row_valid, jnp.clip(abs_pos, 0, T), T)
    rows = jnp.arange(B)[:, None]
    kf = kf.at[rows, tgt].set(k_new.astype(jnp.float32), mode="drop")
    vf = vf.at[rows, tgt].set(v_new.astype(jnp.float32), mode="drop")

    qg = q.reshape(B, S, Hkv, G, D).astype(jnp.float32)
    s = jnp.einsum("bshgd,bthd->bhgst", qg, kf) * (D ** -0.5)
    s = _softcap(s, softcap)
    k_pos = jnp.arange(T)[None, None, :]                             # (1, 1, T)
    valid = k_pos <= abs_pos[:, :, None]                             # causal
    valid &= k_pos < (pl_ + sl)[:, None, None]                       # total length
    if window is not None:
        valid &= (abs_pos[:, :, None] - k_pos) < window
    s = jnp.where(valid[:, None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhgst,bthd->bshgd", p, vf)
    return out.reshape(B, S, H, D).astype(q.dtype)


def decode_attention(
    q: jax.Array, k_cache: jax.Array, v_cache: jax.Array, *,
    cur_len: jax.Array, window: Optional[int], softcap: Optional[float],
    k_scale: Optional[jax.Array] = None, v_scale: Optional[jax.Array] = None,
) -> jax.Array:
    """Single-token attention against a (B, T, Hkv, D) cache. The T axis may be
    sequence-sharded over the model mesh axis (flash-decoding via GSPMD partial
    softmax — see sharding/planner). ``cur_len`` is a scalar or per-slot (B,)
    vector of valid cache lengths (DESIGN.md §3.6).

    With ``k_scale``/``v_scale`` the cache holds int8 codes and per-token f32 scales:
    the QK product runs on raw codes and the scale is applied to the *score column*
    (one multiply per (t, kv-head) instead of dequantizing the (T, D) cache), and the
    V scale folds into the probability row the same way.
    """
    B, _, H, D = q.shape
    Hkv = k_cache.shape[2]
    G = H // Hkv
    qg = q.reshape(B, Hkv, G, D)
    kf = k_cache.astype(jnp.float32) if k_scale is not None else k_cache
    s = jnp.einsum("bhgd,bthd->bhgt", qg, kf) * (D ** -0.5)
    s = s.astype(jnp.float32)
    if k_scale is not None:
        s = s * _scale_to_scores(k_scale)
    s = _softcap(s, softcap)
    t_pos = jnp.arange(k_cache.shape[1])
    cl = jnp.reshape(cur_len, (-1, 1, 1, 1))                 # (B|1, 1, 1, 1)
    valid = t_pos[None, None, None, :] < cl
    if window is not None:
        valid &= (cl - 1 - t_pos[None, None, None, :]) < window
    s = jnp.where(valid, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    if v_scale is not None:
        out = jnp.einsum("bhgt,bthd->bhgd", p * _scale_to_scores(v_scale),
                         v_cache.astype(jnp.float32))
    else:
        out = jnp.einsum("bhgt,bthd->bhgd", p.astype(v_cache.dtype), v_cache)
    return out.reshape(B, 1, H, D).astype(q.dtype)


def verify_attention(
    q: jax.Array, k_cache: jax.Array, v_cache: jax.Array, *,
    cur_len: jax.Array, q_len: jax.Array, window: Optional[int],
    softcap: Optional[float],
    k_scale: Optional[jax.Array] = None, v_scale: Optional[jax.Array] = None,
) -> jax.Array:
    """Draft-window attention against a (B, T, Hkv, D) cache (DESIGN.md §3.9):
    the W window tokens are already scattered, ``cur_len`` is each slot's total
    post-scatter length and ``q_len`` (1 ≤ q_len ≤ W) its valid window rows —
    window token i sits at absolute position ``cur_len - q_len + i`` and
    attends keys ≤ its own position (rows ≥ q_len clamp to the newest valid
    position; their output is garbage-but-finite and discarded). W == 1 is the
    single-token :func:`decode_attention` mask. int8-KV scales apply at the
    same score-column / probability-row points as decode.
    q: (B, W, H, D) → (B, W, H, D)."""
    B, W, H, D = q.shape
    Hkv = k_cache.shape[2]
    G = H // Hkv
    qg = q.reshape(B, W, Hkv, G, D)
    kf = k_cache.astype(jnp.float32) if k_scale is not None else k_cache
    s = jnp.einsum("bwhgd,bthd->bhwgt", qg, kf) * (D ** -0.5)
    s = s.astype(jnp.float32)
    if k_scale is not None:
        s = s * _scale_to_scores(k_scale)[:, :, None]        # (B,Hkv,1,1,T)
    s = _softcap(s, softcap)
    cl = jnp.broadcast_to(jnp.reshape(cur_len, (-1,)).astype(jnp.int32), (B,))
    qln = jnp.broadcast_to(jnp.reshape(q_len, (-1,)).astype(jnp.int32), (B,))
    q_pos = ((cl - qln)[:, None]
             + jnp.minimum(jnp.arange(W)[None, :], (qln - 1)[:, None]))  # (B,W)
    t_pos = jnp.arange(k_cache.shape[1])[None, None, None, None, :]
    qp = q_pos[:, None, :, None, None]
    valid = t_pos <= qp
    if window is not None:
        valid &= (qp - t_pos) < window
    s = jnp.where(valid, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    if v_scale is not None:
        out = jnp.einsum("bhwgt,bthd->bwhgd",
                         p * _scale_to_scores(v_scale)[:, :, None],
                         v_cache.astype(jnp.float32))
    else:
        out = jnp.einsum("bhwgt,bthd->bwhgd", p.astype(v_cache.dtype), v_cache)
    return out.reshape(B, W, H, D).astype(q.dtype)


def _prefill_attention(q, k, v, cfg: ModelConfig, ctx: QuantContext, *,
                       window: Optional[int], seq_lens: Optional[jax.Array]):
    """Self-attention over a (right-padded) prefill window — the one codepath
    shared by the dense layout and the cold (no-prefix) paged layout, so the two
    stay bitwise-identical (DESIGN.md §3.8 parity argument)."""
    S = q.shape[1]
    if ctx.use_pallas and S >= 128:
        # Fused flash-attention kernel (kernels/flash_attention.py): removes the
        # S²-score-tile HBM traffic that dominates training cells (§Roofline).
        from repro.kernels import ops as kops
        return kops.flash_attention(
            q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
            v.transpose(0, 2, 1, 3), kv_len=seq_lens, causal=cfg.causal,
            window=window, softcap=cfg.attn_softcap).transpose(0, 2, 1, 3)
    return blockwise_attention(
        q, k, v, causal=cfg.causal, window=window, softcap=cfg.attn_softcap,
        kv_valid_len=seq_lens,
        q_block=min(1024, max(S, 16)), kv_block=min(1024, max(S, 16)))


def _paged_attention(q, k, v, cache: dict, page_table: Optional[jax.Array],
                     cfg: ModelConfig, ctx: QuantContext, *,
                     cur_len, prefix_len, window: Optional[int], decode: bool,
                     q_len=None):
    """Attention against a paged pool (DESIGN.md §3.8): scatter the new K/V
    through the page table into the pools' stored layout
    (``kernels/paged_layout.py``), then attend on the pools as stored. Every
    decode path — fp pools and int8 codes + per-token scale pools alike, on
    all serving paths — runs the gather-free Pallas paged kernel
    (``ops.paged_decode_attention``): the scale rows ride the same
    scalar-prefetched page indices as the code tiles and
    dequantize in-kernel at the score/prob level, the dense
    ``decode_attention`` application points, so the dense (B, max_pages·ps, ...)
    view is never materialized at decode. Returns (out, new_cache)."""
    if page_table is None:
        raise ValueError("paged cache without a page_table")
    B, S = q.shape[0], q.shape[1]
    P, ps = cache["k_pages"].shape[0], cache["k_pages"].shape[1]

    if q_len is not None:
        # ---- draft-window verify (DESIGN.md §3.9): scatter the whole window
        # through the table (rows ≥ q_len drop), then score every window row
        # in one fused-kernel pass. cur_len is the *total* post-scatter
        # length; window token i of slot b sits at cur_len[b] - q_len[b] + i.
        cl = jnp.broadcast_to(jnp.reshape(cur_len, (-1,)).astype(jnp.int32), (B,))
        qln = jnp.broadcast_to(jnp.reshape(q_len, (-1,)).astype(jnp.int32), (B,))
        abs_pos = (cl - qln)[:, None] + jnp.arange(S)[None, :]       # (B, S)
        row_valid = jnp.arange(S)[None, :] < qln[:, None]
        entry = jnp.take_along_axis(
            page_table, jnp.clip(abs_pos // ps, 0, page_table.shape[1] - 1),
            axis=1)
        flat = jnp.where(row_valid, entry * ps + abs_pos % ps, P * ps).reshape(-1)
        merge = lambda t: t.reshape((B * S,) + t.shape[2:])
        new_cache = _pool_write(cache, flat, merge(k), merge(v))
        from repro.kernels import ops as kops
        out = kops.paged_verify_attention(
            q, new_cache["k_pages"], new_cache["v_pages"], page_table, cl, qln,
            k_scale_pages=new_cache.get("k_scale_pages"),
            v_scale_pages=new_cache.get("v_scale_pages"),
            window=window, softcap=cfg.attn_softcap)
        return out, new_cache

    if decode:
        cl = jnp.broadcast_to(jnp.reshape(cur_len, (-1,)).astype(jnp.int32), (B,))
        pos = jnp.clip(cl - 1, 0, page_table.shape[1] * ps - 1)
        entry = jnp.take_along_axis(page_table, (pos // ps)[:, None], axis=1)[:, 0]
        flat = entry * ps + pos % ps           # sentinel entry (==P) ⇒ dropped
        new_cache = _pool_write(cache, flat, k[:, 0], v[:, 0])
        from repro.kernels import ops as kops
        out = kops.paged_decode_attention(
            q, new_cache["k_pages"], new_cache["v_pages"], page_table, cl,
            k_scale_pages=new_cache.get("k_scale_pages"),
            v_scale_pages=new_cache.get("v_scale_pages"),
            window=window, softcap=cfg.attn_softcap)
        return out, new_cache

    # ---- prefill: scatter the (suffix) window through the table, then attend
    sl = (jnp.full((B,), S, jnp.int32) if cur_len is None
          else jnp.broadcast_to(jnp.reshape(cur_len, (-1,)).astype(jnp.int32), (B,)))
    pl_ = (jnp.zeros((B,), jnp.int32) if prefix_len is None
           else jnp.broadcast_to(jnp.reshape(prefix_len, (-1,)).astype(jnp.int32),
                                 (B,)))
    abs_pos = pl_[:, None] + jnp.arange(S)[None, :]                  # (B, S)
    row_valid = jnp.arange(S)[None, :] < sl[:, None]
    entry = jnp.take_along_axis(
        page_table, jnp.clip(abs_pos // ps, 0, page_table.shape[1] - 1), axis=1)
    flat = jnp.where(row_valid, entry * ps + abs_pos % ps, P * ps).reshape(-1)
    merge = lambda t: t.reshape((B * S,) + t.shape[2:])
    new_cache = _pool_write(cache, flat, merge(k), merge(v))
    if prefix_len is None:
        # cold admission: exactly the dense prefill attention (bitwise parity)
        out = _prefill_attention(q, k, v, cfg, ctx, window=window,
                                 seq_lens=None if cur_len is None else sl)
    else:
        out = paged_prefill_attention(
            q, k, v, new_cache, page_table, prefix_len=pl_, suffix_len=sl,
            window=window, softcap=cfg.attn_softcap)
    return out, new_cache


def _chunked_attention(q, k, v, cache: dict, page_table: jax.Array,
                       cfg: ModelConfig, chunk: dict, *,
                       window: Optional[int]):
    """Packed ragged chunk step (DESIGN.md §3.10): scatter every packed token
    through the page table at its own absolute position, then score the whole
    ragged block in one ``ragged_prefill_attention`` launch. ``chunk`` carries
    per-slot extents (``q_start``/``q_len``/``kv_len`` (B,)) and per-token
    routing (``positions``/``slot_ids`` (Nt,), sentinel ``slot_ids == B`` for
    pad rows). Decode rows are 1-token chunks; prefill chunks, draft-verify
    windows and cold admissions are longer ones — one launch serves them all.
    Returns (out (1, Nt, H, D), new_cache)."""
    B_tab, maxP = page_table.shape
    Nt = q.shape[1]
    P, ps = cache["k_pages"].shape[0], cache["k_pages"].shape[1]

    pos = jnp.reshape(chunk["positions"], (-1,)).astype(jnp.int32)    # (Nt,)
    sid = jnp.reshape(chunk["slot_ids"], (-1,)).astype(jnp.int32)     # (Nt,)
    row_valid = sid < B_tab
    entry = page_table[jnp.clip(sid, 0, B_tab - 1),
                       jnp.clip(pos // ps, 0, maxP - 1)]
    flat = jnp.where(row_valid, entry * ps + pos % ps, P * ps)
    new_cache = _pool_write(cache, flat, k[0], v[0])
    from repro.kernels import ops as kops
    out = kops.ragged_prefill_attention(
        q[0], k[0], v[0], new_cache["k_pages"], new_cache["v_pages"],
        page_table, chunk["q_start"], chunk["q_len"], chunk["kv_len"],
        chunk_cap=Nt,
        k_scale_pages=new_cache.get("k_scale_pages"),
        v_scale_pages=new_cache.get("v_scale_pages"),
        window=window, softcap=cfg.attn_softcap)
    return out[None], new_cache


def attention_apply(
    params: dict, x: jax.Array, cfg: ModelConfig, ctx: QuantContext, *,
    local: bool = False, positions: Optional[jax.Array] = None,
    cache: Optional[dict] = None, cur_len: Optional[jax.Array] = None,
    page_table: Optional[jax.Array] = None, prefix_len: Optional[jax.Array] = None,
    q_len: Optional[jax.Array] = None, chunk: Optional[dict] = None,
) -> Tuple[jax.Array, Optional[dict]]:
    """Full attention sublayer (pre-norm residual is handled by the caller).

    cache: {"k": (B,T,Hkv,D), "v": ...} — prefill writes it, decode reads+appends.
    Paged caches (``k_pages``/``v_pages`` pools + ``page_table``, DESIGN.md §3.8)
    scatter through the table instead; ``prefix_len`` marks suffix prefill
    against a shared paged prefix. Returns (output, new_cache).

    Per-slot length contract (DESIGN.md §3.6): ``cur_len`` may be a scalar (all
    slots aligned) or a (B,) int32 vector. Prefill prompts are right-padded —
    positions start at 0 (at ``prefix_len[b]`` on the paged suffix path),
    ``cur_len`` holds the valid prompt length per slot and masks padded keys;
    decode ``cur_len`` is the per-slot post-append length: the new token
    scatters into cache position ``cur_len - 1`` of its own slot.

    ``q_len`` (B,) marks a *draft-window verify* batch (DESIGN.md §3.9): the S
    axis is a speculative window — all S tokens scatter into the cache (rows ≥
    q_len[b] drop) and every window row is scored in one pass; ``cur_len`` is
    the per-slot *total* post-scatter length, so window token i sits at
    ``cur_len - q_len + i``. The flag is explicit because verify shares
    prefill's S > 1 shape while reading+appending a live cache like decode.

    ``chunk`` marks a *packed ragged chunk* batch (DESIGN.md §3.10): the S axis
    is a packed token row mixing decode tokens and prefill chunks of many
    slots; see :func:`_chunked_attention` for the dict contract. Paged caches
    only.
    """
    B, S, d = x.shape
    H, Hkv, D = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = ctx.linear(params["wq"], x, "wq").reshape(B, S, H, D)
    k = ctx.linear(params["wk"], x, "wk").reshape(B, S, Hkv, D)
    v = ctx.linear(params["wv"], x, "wv").reshape(B, S, Hkv, D)

    is_chunked = cache is not None and chunk is not None
    is_verify = cache is not None and q_len is not None and not is_chunked
    is_decode = cache is not None and S == 1 and q_len is None and not is_chunked
    paged = cache is not None and "k_pages" in cache
    if is_chunked and not paged:
        raise ValueError("chunked serving needs a paged cache")
    if positions is None:
        if is_chunked:
            # every packed token carries its own absolute position
            positions = jnp.reshape(chunk["positions"], (1, -1))
        elif is_verify:
            # window token i at absolute position cur_len - q_len + i; rows ≥
            # q_len clamp to the newest valid position (dropped downstream)
            cl_ = jnp.reshape(cur_len, (-1, 1))
            ql_ = jnp.reshape(q_len, (-1, 1))
            positions = (cl_ - ql_) + jnp.minimum(jnp.arange(S)[None, :],
                                                  ql_ - 1)
        elif is_decode and cur_len is not None:
            positions = jnp.reshape(cur_len, (-1, 1)) - 1        # (B|1, 1)
        elif paged and prefix_len is not None:
            # paged suffix prefill: suffix token i of slot b is absolute
            # position prefix_len[b] + i
            positions = (jnp.reshape(prefix_len, (-1, 1))
                         + jnp.arange(S)[None, :])
        else:
            # train and (right-padded) prefill: absolute positions start at 0
            positions = jnp.arange(S)[None, :]
    if cfg.use_rope:
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)

    window = cfg.window if local else None
    new_cache = None
    if is_chunked:
        out, new_cache = _chunked_attention(q, k, v, cache, page_table, cfg,
                                            chunk, window=window)
        y = ctx.linear(params["wo"], out.reshape(B, S, H * D), "wo")
        return y, new_cache
    if paged:
        out, new_cache = _paged_attention(
            q, k, v, cache, page_table, cfg, ctx, cur_len=cur_len,
            prefix_len=prefix_len, window=window, decode=is_decode,
            q_len=q_len if is_verify else None)
        y = ctx.linear(params["wo"], out.reshape(B, S, H * D), "wo")
        return y, new_cache
    kv_int8 = cache is not None and "k_scale" in cache
    if is_verify:
        # dense draft-window verify (DESIGN.md §3.9): scatter all S window
        # tokens at their absolute positions (rows ≥ q_len drop via the T
        # sentinel), then score the window against the updated cache.
        cl = jnp.broadcast_to(jnp.reshape(cur_len, (-1,)).astype(jnp.int32), (B,))
        qln = jnp.broadcast_to(jnp.reshape(q_len, (-1,)).astype(jnp.int32), (B,))
        T = cache["k"].shape[1]
        abs_pos = (cl - qln)[:, None] + jnp.arange(S)[None, :]       # (B, S)
        row_valid = jnp.arange(S)[None, :] < qln[:, None]
        idx = jnp.where(row_valid, jnp.clip(abs_pos, 0, T - 1), T)   # T drops
        rows = jnp.arange(B)[:, None]
        if kv_int8:
            kq, ks = kv_quantize(k)
            vq, vs = kv_quantize(v)
            new_cache = {
                "k": cache["k"].at[rows, idx].set(kq, mode="drop"),
                "v": cache["v"].at[rows, idx].set(vq, mode="drop"),
                "k_scale": cache["k_scale"].at[rows, idx].set(ks, mode="drop"),
                "v_scale": cache["v_scale"].at[rows, idx].set(vs, mode="drop"),
            }
            out = verify_attention(q, new_cache["k"], new_cache["v"],
                                   cur_len=cl, q_len=qln, window=window,
                                   softcap=cfg.attn_softcap,
                                   k_scale=new_cache["k_scale"],
                                   v_scale=new_cache["v_scale"])
        else:
            k_cache = cache["k"].at[rows, idx].set(
                k.astype(cache["k"].dtype), mode="drop")
            v_cache = cache["v"].at[rows, idx].set(
                v.astype(cache["v"].dtype), mode="drop")
            new_cache = {"k": k_cache, "v": v_cache}
            out = verify_attention(q, k_cache, v_cache, cur_len=cl, q_len=qln,
                                   window=window, softcap=cfg.attn_softcap)
    elif is_decode:
        # decode: scatter the new token at each slot's own append position, then
        # attend over that slot's valid cache prefix.
        cl = jnp.broadcast_to(jnp.reshape(cur_len, (-1,)).astype(jnp.int32), (B,))
        idx = jnp.clip(cl - 1, 0, cache["k"].shape[1] - 1)       # (B,)
        rows = jnp.arange(B)
        if kv_int8:
            kq, ks = kv_quantize(k)
            vq, vs = kv_quantize(v)
            new_cache = {
                "k": cache["k"].at[rows, idx].set(kq[:, 0]),
                "v": cache["v"].at[rows, idx].set(vq[:, 0]),
                "k_scale": cache["k_scale"].at[rows, idx].set(ks[:, 0]),
                "v_scale": cache["v_scale"].at[rows, idx].set(vs[:, 0]),
            }
            out = decode_attention(q, new_cache["k"], new_cache["v"],
                                   cur_len=cl, window=window,
                                   softcap=cfg.attn_softcap,
                                   k_scale=new_cache["k_scale"],
                                   v_scale=new_cache["v_scale"])
        else:
            k_cache = cache["k"].at[rows, idx].set(k[:, 0].astype(cache["k"].dtype))
            v_cache = cache["v"].at[rows, idx].set(v[:, 0].astype(cache["v"].dtype))
            new_cache = {"k": k_cache, "v": v_cache}
            out = decode_attention(q, k_cache, v_cache, cur_len=cl,
                                   window=window, softcap=cfg.attn_softcap)
    else:
        seq_lens = None
        if cache is not None and cur_len is not None:
            # right-padded prefill: keys beyond each slot's prompt length are pad
            seq_lens = jnp.reshape(cur_len, (-1,))
        out = _prefill_attention(q, k, v, cfg, ctx, window=window,
                                 seq_lens=seq_lens)
        if cache is not None:
            # prefill: write kv into the cache prefix (in-flight attention above runs
            # on the unquantized k/v; only the *stored* cache is int8)
            T = cache["k"].shape[1]
            pad = ((0, 0), (0, T - S), (0, 0), (0, 0))
            if kv_int8:
                kq, ks = kv_quantize(k)
                vq, vs = kv_quantize(v)
                new_cache = {
                    "k": jnp.pad(kq, pad), "v": jnp.pad(vq, pad),
                    "k_scale": jnp.pad(ks, pad), "v_scale": jnp.pad(vs, pad),
                }
            else:
                new_cache = {
                    "k": jnp.pad(k.astype(cache["k"].dtype), pad),
                    "v": jnp.pad(v.astype(cache["v"].dtype), pad),
                }
    if new_cache is not None:
        # Keep the slot table's (B→dp, T→model) placement on the freshly written
        # cache leaves (codes AND int8-KV per-token scales): the decode-step scatter
        # otherwise loses the spec and GSPMD reshards the whole cache every step
        # (no-op outside a sharded serving plan — DESIGN.md §3.7).
        new_cache = {kk: hints.constrain_kv_cache(vv) for kk, vv in new_cache.items()}
    y = ctx.linear(params["wo"], out.reshape(B, S, H * D), "wo")
    return y, new_cache


# ======================================================================================
# MLP
# ======================================================================================

def init_mlp(key, cfg: ModelConfig, d_ff: Optional[int] = None) -> dict:
    d, f = cfg.d_model, d_ff or cfg.d_ff
    ks = jax.random.split(key, 3)
    p = {"up": ql.init(ks[0], d, f), "down": ql.init(ks[1], f, d)}
    if cfg.act.endswith("_glu"):
        p["gate"] = ql.init(ks[2], d, f)
    return p


def mlp_apply(params: dict, x: jax.Array, cfg: ModelConfig, ctx: QuantContext) -> jax.Array:
    up = ctx.linear(params["up"], x, "up")
    if cfg.act == "silu_glu":
        h = jax.nn.silu(ctx.linear(params["gate"], x, "gate")) * up
    elif cfg.act == "gelu_glu":
        h = jax.nn.gelu(ctx.linear(params["gate"], x, "gate")) * up
    elif cfg.act == "gelu":
        h = jax.nn.gelu(up)
    elif cfg.act == "relu2":
        h = jnp.square(jax.nn.relu(up))
    else:
        raise ValueError(cfg.act)
    return ctx.linear(params["down"], h, "down")
