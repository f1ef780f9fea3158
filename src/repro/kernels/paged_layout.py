"""The stored layout of the paged K/V pools (DESIGN.md §3.8).

The pools are stored exactly as the paged kernels DMA them, so no call
converts a pool:

  codes   (P, ps, Hkv·D): one lane-dense (ps, Hkv·D) tile per page, heads
          h-major in the lane axis (lane ``h·D + d``). The kernels split heads
          in VMEM, so D = 64 needs no lane padding, and with ps a multiple of
          the dtype's sublane tile a page is whole tiles.
  scales  (P, 1, L) f32 (int8-KV only): one row per page, lane ``h·ps + t``
          holds token t's scale for kv head h, zero-padded to L, the next
          multiple of 128 lanes: Mosaic DMAs an HBM tile only when its minor
          dimension is lane-aligned, and a page's Hkv·ps scales are fewer than
          128 at the engine's page sizes.

Everything here is derived from shapes (Hkv, D, ps): no configuration selects
the layout. Writers scatter straight into it; the logical (P, ps, Hkv, D|1)
view (what ``kernels/ref.py`` takes) is a free reshape for codes and a slice
and transpose of the small scale pool, for oracles and the warm-prefix gather.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

LANES = 128


def scale_width(n_kv_heads: int, page_size: int) -> int:
    """L: lanes of one page's scale row, Hkv·ps rounded up to 128."""
    return -(-n_kv_heads * page_size // LANES) * LANES


def code_pool(n_pages: int, page_size: int, n_kv_heads: int, head_dim: int,
              dtype) -> jax.Array:
    return jnp.zeros((n_pages, page_size, n_kv_heads * head_dim), dtype)


def scale_pool(n_pages: int, page_size: int, n_kv_heads: int) -> jax.Array:
    return jnp.zeros((n_pages, 1, scale_width(n_kv_heads, page_size)),
                     jnp.float32)


def write_codes(pool: jax.Array, flat_idx: jax.Array, rows: jax.Array) -> jax.Array:
    """Write ``rows`` (N, Hkv, D) at flat token positions ``flat_idx`` (N,)
    (page·ps + t) of a (P, ps, Hkv·D) pool. Positions ≥ P·ps (sentinel
    page-table entries, padded rows) are dropped."""
    ps = pool.shape[1]
    return pool.at[flat_idx // ps, flat_idx % ps].set(
        rows.reshape(rows.shape[0], -1).astype(pool.dtype), mode="drop")


def write_scales(pool: jax.Array, flat_idx: jax.Array, rows: jax.Array,
                 page_size: int) -> jax.Array:
    """Write per-token scales ``rows`` (N, Hkv, 1) at flat token positions
    ``flat_idx`` (N,) of a (P, 1, L) scale pool (lane h·ps + t); positions
    ≥ P·ps are dropped."""
    n_kv_heads = rows.shape[1]
    page, t = flat_idx // page_size, flat_idx % page_size
    lanes = jnp.arange(n_kv_heads)[None, :] * page_size + t[:, None]
    return pool.at[page[:, None], 0, lanes].set(
        rows[..., 0].astype(pool.dtype), mode="drop")


def copy_tokens(pool: jax.Array, src, dst, n_tok, page_size: int) -> jax.Array:
    """Copy the first ``n_tok`` tokens of page ``src`` into page ``dst`` of a
    stored code or scale pool (page axis -3, after any leading axes); the
    rest of ``dst`` is zeroed."""
    row = pool[..., src, :, :]
    if row.shape[-2] == page_size:       # code tile: token t is row t
        tok = jnp.arange(page_size)[:, None]
    else:                                # scale row: token t is lane h·ps + t
        tok = (jnp.arange(row.shape[-1]) % page_size)[None, :]
    return pool.at[..., dst, :, :].set(
        jnp.where(tok < n_tok, row, jnp.zeros_like(row)))


def codes_view(pool: jax.Array, n_kv_heads: int) -> jax.Array:
    """(..., ps, Hkv·D) stored codes → the logical (..., ps, Hkv, D) view."""
    return pool.reshape(pool.shape[:-1] + (n_kv_heads, -1))


def scales_view(pool: jax.Array, n_kv_heads: int, page_size: int) -> jax.Array:
    """(..., 1, L) stored scale rows → the logical (..., ps, Hkv, 1) view."""
    rows = pool[..., 0, :n_kv_heads * page_size]
    rows = rows.reshape(rows.shape[:-1] + (n_kv_heads, page_size))
    return jnp.swapaxes(rows, -1, -2)[..., None]


def scale_rows(scale: jax.Array) -> jax.Array:
    """Logical (P, ps, Hkv, 1) per-token scales → the stored (P, 1, L) rows."""
    P, ps, Hkv = scale.shape[:3]
    rows = jnp.swapaxes(scale[..., 0], 1, 2).astype(jnp.float32)
    rows = rows.reshape(P, 1, Hkv * ps)
    return jnp.pad(rows, ((0, 0), (0, 0),
                          (0, scale_width(Hkv, ps) - Hkv * ps)))


def stored(k_pages: jax.Array, v_pages: jax.Array, k_scale_pages=None,
           v_scale_pages=None) -> tuple:
    """Logical (P, ps, Hkv, D) pools and, int8-KV, their (P, ps, Hkv, 1)
    scale pools → the same values in the stored layout, as
    (k_pages, v_pages, k_scale_pages|None, v_scale_pages|None). The engine
    allocates and writes its pools stored, so this only builds pools from
    logical values (kernel checks against the oracles)."""
    codes = [p.reshape(p.shape[:2] + (-1,)) for p in (k_pages, v_pages)]
    scales = [None if s is None else scale_rows(s)
              for s in (k_scale_pages, v_scale_pages)]
    return (*codes, *scales)


def splits_in_heads(lanes: int, n_kv_heads: int, head_lanes: int,
                    n_shards: int) -> bool:
    """Whether a stored pool's lane axis (``lanes`` wide, ``head_lanes`` per
    head: D for codes, ps for scales) splits over ``n_shards`` devices in
    whole kv heads, the same heads on a device in the code and scale pools.
    A padded scale row does not: its padding sits after the last head."""
    return n_kv_heads % n_shards == 0 and lanes == n_kv_heads * head_lanes
