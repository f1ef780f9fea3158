"""Layer-polymorphic state registry (DESIGN.md §3.13).

Every sublayer kind declares, through a :class:`StateSpec`, how its decoding
state is laid out in the two cache layouts the serving stack supports:

  dense   per-slot leaves with a leading ``batch_size`` slot-table axis
          (DESIGN.md §3.6) — attention KV rows, SSM recurrent state + conv
          window.
  paged   fixed-size physical pools addressed through a top-level routing
          table whose ids come from the shared ref-counted ``PagePool``
          (serving/paging.py). Attention pages hold ``page_size`` tokens of
          KV and a slot needs ``ceil(len / page_size)`` of them; an SSM
          layer's state has no sequence axis, so its "page" is one
          fixed-size checkpoint — a recurrent-state slab plus the K-1-token
          pre-conv window — and a slot needs exactly one, shared across all
          its SSM layers (the same id indexes every layer's pool).

``models/model.py::init_cache`` builds cache pytrees from this registry
instead of hard-coding attention leaves, which is what lets ``ServeEngine``
treat mamba2/zamba2 slots identically to attention slots: admission plans
page needs per kind, the routing tables (``page_table`` (B, max_len/ps) for
token-paged kinds, ``state_table`` (B,) for checkpoint-paged kinds) travel
inside the cache pytree, and retirement decrefs both kinds of ids in the one
pool.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, Optional

import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.kernels import paged_layout as L


@dataclasses.dataclass(frozen=True)
class StateSpec:
    """How one sublayer kind stores decoding state.

    ``table``: cache key of the top-level routing table its paged leaves are
    addressed through. ``paged_kv``: True when pages hold per-token KV (page
    need grows with sequence length); False for fixed-size state checkpoints
    (one page per slot, length-independent).
    """
    kind: str
    table: str
    paged_kv: bool
    dense_leaves: Callable[..., dict]
    paged_leaves: Callable[..., dict]


def _attn_dense(cfg: ModelConfig, batch_size: int, max_len: int, dtype,
                kv_int8: bool) -> dict:
    kv_shape = (batch_size, max_len, cfg.n_kv_heads, cfg.head_dim)
    if kv_int8:
        return {
            "k": jnp.zeros(kv_shape, jnp.int8),
            "v": jnp.zeros(kv_shape, jnp.int8),
            "k_scale": jnp.zeros(kv_shape[:3] + (1,), jnp.float32),
            "v_scale": jnp.zeros(kv_shape[:3] + (1,), jnp.float32),
        }
    return {"k": jnp.zeros(kv_shape, dtype), "v": jnp.zeros(kv_shape, dtype)}


def _attn_paged(cfg: ModelConfig, n_pages: int, page_size: int, dtype,
                kv_int8: bool) -> dict:
    """Per-layer K/V pools in the layout the paged kernels DMA from
    (``kernels/paged_layout.py``): (P, ps, Hkv·D) pages, heads h-major in the
    lane axis — int8 codes plus (P, 1, L) f32 per-token scale rows (lane
    h·ps + t, padded to 128 lanes) on an int8-KV cache. Every writer scatters
    into, and every reader reads, this layout as stored."""
    codes = lambda dt: L.code_pool(n_pages, page_size, cfg.n_kv_heads,
                                   cfg.head_dim, dt)
    if kv_int8:
        scales = lambda: L.scale_pool(n_pages, page_size, cfg.n_kv_heads)
        return {"k_pages": codes(jnp.int8), "v_pages": codes(jnp.int8),
                "k_scale_pages": scales(), "v_scale_pages": scales()}
    return {"k_pages": codes(dtype), "v_pages": codes(dtype)}


def _ssm_conv_channels(cfg: ModelConfig) -> int:
    return cfg.d_inner + 2 * cfg.ssm_groups * cfg.ssm_state


def _ssm_dense(cfg: ModelConfig, batch_size: int, max_len: int, dtype,
               kv_int8: bool) -> dict:
    # Recurrence state always stays f32 regardless of kv_int8 (DESIGN.md §3.3).
    return {
        "state": jnp.zeros((batch_size, cfg.ssm_heads, cfg.ssm_head_dim,
                            cfg.ssm_state), jnp.float32),
        "conv": jnp.zeros((batch_size, cfg.ssm_conv - 1,
                           _ssm_conv_channels(cfg)), jnp.float32),
    }


def _ssm_paged(cfg: ModelConfig, n_pages: int, page_size: int, dtype,
               kv_int8: bool) -> dict:
    return {
        "state_pages": jnp.zeros((n_pages, cfg.ssm_heads, cfg.ssm_head_dim,
                                  cfg.ssm_state), jnp.float32),
        "conv_pages": jnp.zeros((n_pages, cfg.ssm_conv - 1,
                                 _ssm_conv_channels(cfg)), jnp.float32),
    }


_ATTN = StateSpec(kind="attn", table="page_table", paged_kv=True,
                  dense_leaves=_attn_dense, paged_leaves=_attn_paged)
_SSM = StateSpec(kind="ssm", table="state_table", paged_kv=False,
                 dense_leaves=_ssm_dense, paged_leaves=_ssm_paged)

REGISTRY: Dict[str, StateSpec] = {
    "attn": _ATTN,
    "attn_local": _ATTN,
    "attn_moe": _ATTN,
    "ssm": _SSM,
}


def spec_for(kind: str) -> StateSpec:
    return REGISTRY[kind]


def cache_kinds(block_spec) -> list:
    """All sublayer kinds a cache for ``block_spec`` (models.model.BlockSpec)
    must cover, including the hybrid shared-attention block."""
    kinds = list(block_spec.sublayers) + list(block_spec.tail)
    if block_spec.shared_attn:
        kinds.append("attn")
    return kinds


def family_flags(block_spec) -> tuple:
    """(has_paged_kv, has_state_checkpoint) for a BlockSpec: whether a paged
    cache for it carries token-paged KV pools / fixed-size state pools. Drives
    the engine's page-need arithmetic: a slot needs ``ceil(len / page_size)``
    KV pages when the first holds, plus exactly one state page when the
    second does."""
    kinds = cache_kinds(block_spec)
    has_kv = any(spec_for(k).paged_kv for k in kinds)
    has_state = any(not spec_for(k).paged_kv for k in kinds)
    return has_kv, has_state
