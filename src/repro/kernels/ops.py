"""Public jit'd wrappers for the Pallas kernels.

Handles (a) padding to block multiples (zero padding is exact for integer GEMMs and
for row-absmax quantization), (b) backend dispatch: real Mosaic lowering on TPU,
``interpret=True`` everywhere else (CPU CI and the correctness tests) — and, for
the paged serving kernels, ``REPRO_KERNEL_EXEC=ref`` routes off-TPU calls to the
pure-jnp oracle instead (:func:`_exec_mode`: interpret emulation is a correctness
harness, not an execution backend), (c) block-size selection for small shapes,
(d) the custom-kernel boundary under a TP-sharded serving plan (DESIGN.md §3.7):
each wrapper body runs as a GSPMD-*manual* region (``hints.manual_kernel``) so
every device computes the exact single-device kernel result on gathered operands
— a no-op outside a hinted mesh.

The hinted mesh is threaded into the jitted wrappers as a *static* argument: jit's
trace cache does not key on contextvars, so reading the hint inside the traced body
would silently reuse whichever of the manual/plain lowerings was traced first.
"""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp

from repro.kernels import act_quantize as _aq
from repro.kernels import flash_attention as _fa
from repro.kernels import paged_layout as _layout
from repro.kernels import qgemm as _qg
from repro.kernels import ref as _ref
from repro.sharding import hints


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _exec_mode() -> str:
    """Execution backend for the paged serving kernels: ``pallas`` (real
    Mosaic lowering on TPU, ``interpret=True`` emulation elsewhere) or
    ``ref`` (the pure-jnp oracle from :mod:`repro.kernels.ref`, XLA-compiled).

    ``REPRO_KERNEL_EXEC=ref`` routes off-TPU calls to the oracle: interpret
    emulation exists to *test* the kernels (it lowers the per-page DMA
    pipeline to per-step dynamic slices), and its overhead is emulator cost,
    not a serving signal — the serving benchmark opts in so CPU rows measure
    the XLA execution of the same math. On TPU the Mosaic kernels always run;
    the variable is read at call time and threaded into the jitted wrappers
    as a static argument (like ``mesh``: jit's trace cache does not key on
    environment reads inside the traced body)."""
    mode = os.environ.get("REPRO_KERNEL_EXEC", "pallas")
    assert mode in ("pallas", "ref"), f"REPRO_KERNEL_EXEC={mode!r}"
    return "pallas" if jax.default_backend() == "tpu" else mode


def _pad_to(x: jax.Array, axis: int, mult: int) -> jax.Array:
    pad = (-x.shape[axis]) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def _pick_block(dim: int, preferred: int, align: int = 128) -> int:
    """Largest multiple of ``align`` ≤ preferred that is reasonable for ``dim``."""
    if dim <= align:
        return align
    return min(preferred, ((dim + align - 1) // align) * align, preferred)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "mesh"))
def _qgemm_w8a8(qx, qw, a, sw, *, bm, bn, bk, mesh):
    M, K = qx.shape
    N = qw.shape[1]
    bm = _pick_block(M, bm)
    bn = _pick_block(N, bn)
    bk = _pick_block(K, bk)

    def body(qx, qw, a, sw):
        qxp = _pad_to(_pad_to(qx, 0, bm), 1, bk)
        qwp = _pad_to(_pad_to(qw, 0, bk), 1, bn)
        ap = _pad_to(a.astype(jnp.float32), 0, bm)
        swp = _pad_to(sw.reshape(1, -1).astype(jnp.float32), 1, bn)
        out = _qg.qgemm_w8a8_pallas(qxp, qwp, ap, swp, bm=bm, bn=bn, bk=bk,
                                    interpret=_interpret())
        return out[:M, :N]

    return hints.manual_kernel(body, (qx, qw, a, sw), mesh=mesh)


def qgemm_w8a8(qx: jax.Array, qw: jax.Array, a: jax.Array, sw: jax.Array,
               *, bm: int = 256, bn: int = 256, bk: int = 512) -> jax.Array:
    """int8 GEMM + separable dequant. qx (M,K) int8; qw (K,N) int8; a (M,1); sw (N,)."""
    return _qgemm_w8a8(qx, qw, a, sw, bm=bm, bn=bn, bk=bk,
                       mesh=hints.current_mesh())


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "mesh", "exec_mode"))
def _qgemm_w8a8_sparse(qx, qw, a, sw, mask, *, bm, bn, bk, mesh, exec_mode):
    M, K = qx.shape
    N = qw.shape[1]
    bm = _pick_block(M, bm)
    bn = _pick_block(N, bn)
    bk = _pick_block(K, bk)

    def body(qx, qw, a, sw, mask):
        if exec_mode == "ref":
            return _ref.qgemm_w8a8_sparse_ref(qx, qw, a, sw, mask)
        qxp = _pad_to(_pad_to(qx, 0, bm), 1, bk)
        qwp = _pad_to(_pad_to(qw, 0, bk), 1, bn)
        ap = _pad_to(a.astype(jnp.float32), 0, bm)
        swp = _pad_to(sw.reshape(1, -1).astype(jnp.float32), 1, bn)
        mp = _pad_to(_pad_to(mask.astype(jnp.int32), 0, bk), 1, bn)
        Kp, Np = qwp.shape
        occ = mp.reshape(Kp // bk, bk, Np // bn, bn).sum(axis=(1, 3))
        dense_args = (qxp, qwp, ap, swp)
        # Dense fallback when occupancy is full: the sparse kernel is bitwise
        # identical there but pays an SMEM gate per grid step for nothing. Both
        # branches produce the same values (skipping all-zero int8 blocks is
        # exact), so the runtime switch cannot perturb token parity.
        out = jax.lax.cond(
            jnp.all(occ > 0),
            lambda ops: _qg.qgemm_w8a8_pallas(
                *ops, bm=bm, bn=bn, bk=bk, interpret=_interpret()),
            lambda ops: _qg.qgemm_w8a8_sparse_pallas(
                *ops, occ, bm=bm, bn=bn, bk=bk, interpret=_interpret()),
            dense_args)
        return out[:M, :N]

    return hints.manual_kernel(body, (qx, qw, a, sw, mask), mesh=mesh)


def qgemm_w8a8_sparse(qx: jax.Array, qw: jax.Array, a: jax.Array, sw: jax.Array,
                      mask: jax.Array, *, bm: int = 256, bn: int = 256,
                      bk: int = 512) -> jax.Array:
    """Block-sparse int8 GEMM over N:M-pruned weights (DESIGN.md §3.12).

    mask (K, N) uint8 {0,1}: the *unpacked* keep-mask whose zeros already zero
    ``qw`` (models/quantize.py sparsify_tree). The wrapper reduces it to per-
    (bk, bn)-block occupancy for the kernel's scalar-prefetch gate; with every
    block occupied it dispatches the plain dense kernel instead.
    """
    return _qgemm_w8a8_sparse(qx, qw, a, sw, mask, bm=bm, bn=bn, bk=bk,
                              mesh=hints.current_mesh(), exec_mode=_exec_mode())


@functools.partial(jax.jit, static_argnames=("group", "bm", "bn", "mesh"))
def _qgemm_w4a8(qx, qw4, a, sw, *, group, bm, bn, mesh):
    M, K = qx.shape
    N = qw4.shape[1]
    assert K % group == 0, f"K={K} must divide group={group} (pad offline)"
    bm = _pick_block(M, bm)
    bn = _pick_block(N, bn)

    def body(qx, qw4, a, sw):
        qxp = _pad_to(qx, 0, bm)
        qw4p = _pad_to(qw4, 1, bn)
        ap = _pad_to(a.astype(jnp.float32), 0, bm)
        swp = _pad_to(sw.astype(jnp.float32), 1, bn)
        out = _qg.qgemm_w4a8_pallas(qxp, qw4p, ap, swp, group=group, bm=bm, bn=bn,
                                    interpret=_interpret())
        return out[:M, :N]

    return hints.manual_kernel(body, (qx, qw4, a, sw), mesh=mesh)


def qgemm_w4a8(qx: jax.Array, qw4: jax.Array, a: jax.Array, sw: jax.Array,
               *, group: int = 128, bm: int = 256, bn: int = 256) -> jax.Array:
    """W4A8 grouped GEMM. qx (M,K) int8; qw4 (K//2,N) packed; sw (K//group,N)."""
    return _qgemm_w4a8(qx, qw4, a, sw, group=group, bm=bm, bn=bn,
                       mesh=hints.current_mesh())


@functools.partial(jax.jit, static_argnames=("causal", "window", "softcap",
                                             "bq", "bk", "mesh"))
def _flash_attention(q, k, v, kv_len, *, causal, window, softcap, bq, bk, mesh):
    B, H, Sq, D = q.shape
    Sk = k.shape[2]
    bq = min(bq, max(128, 1 << (Sq - 1).bit_length()))
    bk = min(bk, max(128, 1 << (Sk - 1).bit_length()))
    if ((-Sk) % bk) and not causal and kv_len is None:
        # non-causal paths must not attend to padded keys: window trick can't help,
        # so mask by giving padded keys a -inf-producing value via a huge negative
        # bias channel is fragile — instead run causal=False only on block-aligned
        # inputs (encoder S=4096 aligns; assert keeps this honest). A kv_len bound
        # subsumes this: it masks the block padding along with the slot padding.
        raise ValueError("non-causal flash_attention requires Skv % bk == 0")

    def body(q, k, v, kv_len):
        qp = _pad_to(q, 2, bq)
        kp = _pad_to(k, 2, bk)
        vp = _pad_to(v, 2, bk)
        kvl = None
        if kv_len is not None:
            kvl = jnp.broadcast_to(
                jnp.clip(jnp.reshape(kv_len, (-1,)).astype(jnp.int32), 0, Sk), (B,))
        out = _fa.flash_attention_pallas(qp, kp, vp, kvl, causal=causal,
                                         window=window, softcap=softcap,
                                         bq=bq, bk=bk, interpret=_interpret())
        return out[:, :, :Sq]

    return hints.manual_kernel(body, (q, k, v, kv_len), mesh=mesh)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, kv_len=None, *,
                    causal: bool = True, window=None, softcap=None,
                    bq: int = 512, bk: int = 512) -> jax.Array:
    """Fused flash attention. q (B,H,Sq,D); k/v (B,Hkv,Skv,D) → (B,H,Sq,D).

    ``kv_len`` (scalar or (B,) int32) masks keys at positions ≥ kv_len[b] per batch
    element — the per-slot valid length of right-padded continuous-batching prefill
    (DESIGN.md §3.6).

    Pads Sq/Skv to block multiples; padded keys are masked by position (the kernel
    masks k_pos ≥ true Skv via the window/causal machinery — here by pre-masking:
    padded kv rows are zeroed AND excluded through an explicit Skv bound below)."""
    return _flash_attention(q, k, v, kv_len, causal=causal, window=window,
                            softcap=softcap, bq=bq, bk=bk,
                            mesh=hints.current_mesh())


def _logical_pools(k_pages, v_pages, k_scale_pages, v_scale_pages,
                   n_kv_heads: int):
    """The ref route's view of the stored pools (``kernels/paged_layout.py``):
    [(P, ps, Hkv, D) codes ×2] and the (P, ps, Hkv, 1) scale-pool kwargs of
    the oracles (empty on fp pools)."""
    kv = [_layout.codes_view(p, n_kv_heads) for p in (k_pages, v_pages)]
    if k_scale_pages is None:
        return kv, {}
    ps = k_pages.shape[1]
    return kv, dict(
        k_scale_pages=_layout.scales_view(k_scale_pages, n_kv_heads, ps),
        v_scale_pages=_layout.scales_view(v_scale_pages, n_kv_heads, ps))


@functools.partial(jax.jit, static_argnames=("window", "softcap", "mesh",
                                             "exec_mode"))
def _paged_decode_attention(q, k_pages, v_pages, page_table, kv_len,
                            k_scale_pages, v_scale_pages, *,
                            window, softcap, mesh, exec_mode):
    B, S, H, D = q.shape
    Hkv = k_pages.shape[2] // D
    G = H // Hkv

    def body(q, k_pages, v_pages, page_table, kv_len, k_scale_pages,
             v_scale_pages):
        qg = q.reshape(B, Hkv, G, D)
        kvl = jnp.broadcast_to(
            jnp.reshape(kv_len, (-1,)).astype(jnp.int32), (B,))
        if exec_mode == "ref":
            kv, sc = _logical_pools(k_pages, v_pages, k_scale_pages,
                                    v_scale_pages, Hkv)
            out = _ref.paged_decode_attention_ref(
                qg, *kv, page_table, kvl, **sc, window=window, softcap=softcap)
            return out.reshape(B, 1, H, D)
        out = _fa.paged_decode_attention_pallas(
            qg, k_pages, v_pages, page_table, kvl,
            k_scale=k_scale_pages, v_scale=v_scale_pages,
            window=window, softcap=softcap, interpret=_interpret())
        return out.reshape(B, 1, H, D)

    return hints.manual_kernel(
        body, (q, k_pages, v_pages, page_table, kv_len, k_scale_pages,
               v_scale_pages), mesh=mesh)


def paged_decode_attention(q: jax.Array, k_pages: jax.Array, v_pages: jax.Array,
                           page_table: jax.Array, kv_len: jax.Array, *,
                           k_scale_pages=None, v_scale_pages=None,
                           window=None, softcap=None) -> jax.Array:
    """Paged single-token decode attention (DESIGN.md §3.8): q (B,1,H,D) against
    pools in their stored layout (``kernels/paged_layout.py``: (P, ps, Hkv·D)
    codes) addressed through a (B, maxP) int32 page table with per-slot valid
    lengths ``kv_len`` (scalar or (B,)) → (B,1,H,D).

    The pools pass to the kernel untouched: it DMAs each logical page's
    physical tile via scalar-prefetch page indices, so neither a relayout of
    the pool nor the dense (B, T, Hkv, D) view is ever materialized. With
    ``k_scale_pages``/``v_scale_pages`` ((P, 1, L) f32 rows) the pools hold
    int8 codes: the per-token scale rows ride the same prefetched page indices
    and apply in-kernel at the score/prob level, the exact application points of
    the dense ``layers.decode_attention`` int8 path — every paged decode path
    (fp, int8-KV) serves through this kernel. Under a TP-sharded serving plan
    the body runs as one GSPMD-manual region."""
    return _paged_decode_attention(q, k_pages, v_pages, page_table, kv_len,
                                   k_scale_pages, v_scale_pages,
                                   window=window, softcap=softcap,
                                   mesh=hints.current_mesh(),
                                   exec_mode=_exec_mode())


@functools.partial(jax.jit, static_argnames=("window", "softcap", "mesh",
                                             "exec_mode"))
def _paged_verify_attention(q, k_pages, v_pages, page_table, kv_len, q_len,
                            k_scale_pages, v_scale_pages, *,
                            window, softcap, mesh, exec_mode):
    B, W, H, D = q.shape
    Hkv = k_pages.shape[2] // D
    G = H // Hkv

    def body(q, k_pages, v_pages, page_table, kv_len, q_len, k_scale_pages,
             v_scale_pages):
        if exec_mode == "ref":
            kv, sc = _logical_pools(k_pages, v_pages, k_scale_pages,
                                    v_scale_pages, Hkv)
            out = _ref.paged_verify_attention_ref(
                jnp.transpose(q.reshape(B, W, Hkv, G, D), (0, 2, 1, 3, 4)),
                *kv, page_table,
                jnp.broadcast_to(
                    jnp.reshape(kv_len, (-1,)).astype(jnp.int32), (B,)),
                jnp.broadcast_to(
                    jnp.reshape(q_len, (-1,)).astype(jnp.int32), (B,)),
                **sc, window=window, softcap=softcap)
            return jnp.transpose(out, (0, 2, 1, 3, 4)).reshape(B, W, H, D)
        # (B, W, H, D) → the kernel's (window, group)-ordered score-tile rows
        qg = jnp.transpose(q.reshape(B, W, Hkv, G, D),
                           (0, 2, 1, 3, 4)).reshape(B, Hkv, W * G, D)
        # W == 1 forces q_len == 1 everywhere, and the verify mask at
        # q_len == 1 reduces exactly to the decode mask — dispatch to the
        # plain decode launch (no q_len prefetch operand)
        qw = dict(q_win=W, q_len=jnp.broadcast_to(
            jnp.reshape(q_len, (-1,)).astype(jnp.int32), (B,))) if W > 1 else {}
        out = _fa.paged_decode_attention_pallas(
            qg, k_pages, v_pages, page_table,
            jnp.broadcast_to(jnp.reshape(kv_len, (-1,)).astype(jnp.int32), (B,)),
            k_scale=k_scale_pages, v_scale=v_scale_pages, **qw,
            window=window, softcap=softcap, interpret=_interpret())
        return jnp.transpose(out.reshape(B, Hkv, W, G, D),
                             (0, 2, 1, 3, 4)).reshape(B, W, H, D)

    return hints.manual_kernel(
        body, (q, k_pages, v_pages, page_table, kv_len, q_len, k_scale_pages,
               v_scale_pages), mesh=mesh)


def paged_verify_attention(q: jax.Array, k_pages: jax.Array, v_pages: jax.Array,
                           page_table: jax.Array, kv_len: jax.Array,
                           q_len: jax.Array, *,
                           k_scale_pages=None, v_scale_pages=None,
                           window=None, softcap=None) -> jax.Array:
    """Paged draft-window verify attention (DESIGN.md §3.9): q (B, W, H, D) —
    W window tokens per slot, already scattered into the stored pools —
    against the same page table / pools as ``paged_decode_attention``, with
    per-slot total post-scatter length ``kv_len`` and valid window rows
    ``q_len`` (window token i sits at ``kv_len - q_len + i``; rows ≥ q_len are
    garbage-but-finite) → (B, W, H, D).

    Same kernel, same double-buffered page DMA pipeline, same in-kernel int8-KV
    dequant points as decode — the only change is the per-row causal mask, so
    W == 1 is bitwise the decode step. Runs as one GSPMD-manual region under a
    TP-sharded plan: window rows ride the same replicated-q / sharded-kv-heads
    placement as decode queries."""
    return _paged_verify_attention(q, k_pages, v_pages, page_table, kv_len,
                                   q_len, k_scale_pages, v_scale_pages,
                                   window=window, softcap=softcap,
                                   mesh=hints.current_mesh(),
                                   exec_mode=_exec_mode())


@functools.partial(jax.jit, static_argnames=("chunk_cap", "window", "softcap",
                                             "mesh", "exec_mode"))
def _ragged_prefill_attention(q, k_new, v_new, k_pages, v_pages, page_table,
                              q_start, q_len, kv_len, k_scale_pages,
                              v_scale_pages, *, chunk_cap, window, softcap,
                              mesh, exec_mode):
    Nt, H, D = q.shape
    Hkv = k_pages.shape[2] // D
    G = H // Hkv

    def body(q, k_new, v_new, k_pages, v_pages, page_table, q_start, q_len,
             kv_len, k_scale_pages, v_scale_pages):
        B = page_table.shape[0]
        if exec_mode == "ref":
            kv, sc = _logical_pools(k_pages, v_pages, k_scale_pages,
                                    v_scale_pages, Hkv)
            out = _ref.ragged_prefill_attention_ref(
                q.reshape(Nt, Hkv, G, D), k_new, v_new, *kv, page_table,
                jnp.reshape(q_start, (-1,)).astype(jnp.int32),
                jnp.broadcast_to(
                    jnp.reshape(q_len, (-1,)).astype(jnp.int32), (B,)),
                jnp.broadcast_to(
                    jnp.reshape(kv_len, (-1,)).astype(jnp.int32), (B,)),
                chunk_cap=chunk_cap, **sc, window=window, softcap=softcap)
            return out.reshape(Nt, H, D)
        # packed (Nt, H, D) → the kernel's head-major (Hkv, Nt, G, D)
        qg = jnp.transpose(q.reshape(Nt, Hkv, G, D), (1, 0, 2, 3))
        out = _fa.ragged_prefill_attention_pallas(
            qg, k_new, v_new, k_pages, v_pages, page_table,
            jnp.reshape(q_start, (-1,)).astype(jnp.int32),
            jnp.broadcast_to(jnp.reshape(q_len, (-1,)).astype(jnp.int32), (B,)),
            jnp.broadcast_to(jnp.reshape(kv_len, (-1,)).astype(jnp.int32), (B,)),
            chunk_cap=chunk_cap, k_scale=k_scale_pages, v_scale=v_scale_pages,
            window=window, softcap=softcap, interpret=_interpret())
        return jnp.transpose(out, (1, 0, 2, 3)).reshape(Nt, H, D)

    return hints.manual_kernel(
        body, (q, k_new, v_new, k_pages, v_pages, page_table, q_start, q_len,
               kv_len, k_scale_pages, v_scale_pages), mesh=mesh)


def ragged_prefill_attention(q: jax.Array, k_new: jax.Array, v_new: jax.Array,
                             k_pages: jax.Array, v_pages: jax.Array,
                             page_table: jax.Array, q_start: jax.Array,
                             q_len: jax.Array, kv_len: jax.Array, *,
                             chunk_cap: int, k_scale_pages=None,
                             v_scale_pages=None, window=None,
                             softcap=None) -> jax.Array:
    """Ragged chunked-prefill attention over the paged pool (DESIGN.md §3.10):
    q (Nt, H, D) — a packed ragged query block whose slot b owns rows
    ``[q_start[b], q_start[b] + q_len[b])`` (q_len ≤ ``chunk_cap``; 0 marks a
    dead slot) — against the same stored pools / (B, maxP) page table /
    optional int8-KV scale pools as ``paged_decode_attention`` → (Nt, H, D),
    rows no slot owns zeroed.

    ``kv_len`` (B,) counts each slot's total visible tokens *after* this
    chunk's scatter, so the chunk spans absolute positions
    ``[kv_len - q_len, kv_len)`` and the causal mask is
    ``k_pos <= (kv_len - q_len) + i`` per chunk token i — cold prefill, warm
    radix-hit suffix prefill, mid-prompt chunks and single-token decode rows
    (q_len == 1) all serve through this one launch, with the chunk's own
    tokens read from the packed fp ``k_new``/``v_new`` (N, Hkv, D) instead of
    their freshly scattered (possibly int8) pool pages — the
    ``paged_prefill_attention`` fp-suffix overlay, in-kernel. Same
    double-buffered per-page DMA pipeline and in-kernel int8-KV dequant
    points as decode; runs as one GSPMD-manual region under a TP-sharded
    plan."""
    return _ragged_prefill_attention(q, k_new, v_new, k_pages, v_pages,
                                     page_table, q_start, q_len, kv_len,
                                     k_scale_pages, v_scale_pages,
                                     chunk_cap=chunk_cap, window=window,
                                     softcap=softcap,
                                     mesh=hints.current_mesh(),
                                     exec_mode=_exec_mode())


@functools.partial(jax.jit, static_argnames=("bits", "alpha", "bm", "bk", "mesh"))
def _act_quantize_padded(x, bcol, dyn_alpha, *, bits, alpha, bm, bk, mesh):
    """Shared pad → kernel → slice for the static- and traced-alpha wrappers.

    Zero row padding is exact (padded rows produce a = eps^alpha scale, sliced
    away); K padding pads bcol with 1 to avoid division by zero. Exactly one of
    ``alpha`` (static float) and ``dyn_alpha`` (traced scalar) is set.
    """
    M, K = x.shape
    bm = _pick_block(M, bm)
    bk = _pick_block(K, bk)

    def body(x, bcol, dyn_alpha):
        xp = _pad_to(x, 0, bm)
        xp = _pad_to(xp, 1, bk)
        pad_k = xp.shape[1] - K
        bcolp = jnp.concatenate([bcol.astype(jnp.float32),
                                 jnp.ones((pad_k,), jnp.float32)]) if pad_k else bcol
        al = alpha if dyn_alpha is None else dyn_alpha
        q, a = _aq.act_quantize_pallas(xp, bcolp, bits=bits, alpha=al, bm=bm, bk=bk,
                                       interpret=_interpret())
        return q[:M, :K], a[:M]

    return hints.manual_kernel(body, (x, bcol, dyn_alpha), mesh=mesh)


def act_quantize(x: jax.Array, bcol: jax.Array, *, bits: int = 8,
                 alpha: float = 0.15, bm: int = 256, bk: int = 512):
    """Fused CrossQuant activation quantization. x (M,K); bcol (K,) = c^(1-alpha).

    Returns (codes (M,K) int8, a (M,1) f32).
    """
    return _act_quantize_padded(x, bcol, None, bits=bits, alpha=alpha, bm=bm, bk=bk,
                                mesh=hints.current_mesh())


def act_quantize_dyn(x: jax.Array, bcol: jax.Array, alpha: jax.Array, *,
                     bits: int = 8, bm: int = 256, bk: int = 512):
    """:func:`act_quantize` with a *traced* CrossQuant exponent.

    The fused serving path slices ``qalpha`` out of a scanned prepared tree, so the
    exponent is a runtime scalar: it enters the kernel through SMEM instead of being
    baked into the lowering (one compiled kernel for all layers, DESIGN.md §3.3).
    """
    return _act_quantize_padded(x, bcol, jnp.asarray(alpha, jnp.float32),
                                bits=bits, alpha=None, bm=bm, bk=bk,
                                mesh=hints.current_mesh())
