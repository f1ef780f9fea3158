"""Unified language model covering all 10 assigned architectures.

The layer stack is expressed as a *block spec*: a static list of sublayer kinds that is
repeated ``n_blocks`` times and executed with ``lax.scan`` over stacked parameters
(small HLO, cheap remat). Heterogeneous stacks map onto this:

  dense global        -> [attn] × L
  gemma2 alternating  -> [attn_local, attn_global] × L/2
  moe                 -> [attn+moe] × L
  mamba2              -> [ssm] × L
  zamba2 hybrid       -> ([ssm] × attn_every + shared-attn) × L//k  (+ ssm tail),
                         shared attention/MLP params are closed over (weight sharing)

Modes: ``train`` (full logits), ``prefill`` (writes caches, last-position logits),
``decode`` (one token against caches). An ``unroll`` python-loop path supports eager
calibration (observers cannot run under scan).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core import qlinear as ql
from repro.models import frontends, moe as moe_lib, ssm as ssm_lib, state as state_lib
from repro.sharding import hints
from repro.models.layers import (
    QuantContext, attention_apply, init_attention, init_mlp, init_norm, mlp_apply,
    norm_apply,
)


# ======================================================================================
# Block spec
# ======================================================================================

@dataclasses.dataclass(frozen=True)
class BlockSpec:
    sublayers: Tuple[str, ...]       # attn | attn_local | attn_moe | ssm
    n_blocks: int
    tail: Tuple[str, ...] = ()       # unscanned remainder layers (hybrid)
    shared_attn: bool = False        # zamba2: shared block applied after each super-block


def block_spec(cfg: ModelConfig) -> BlockSpec:
    L = cfg.n_layers
    if cfg.family in ("dense", "vlm", "audio"):
        if cfg.layer_pattern == "local_global":
            assert L % 2 == 0
            return BlockSpec(("attn_local", "attn"), L // 2)
        return BlockSpec(("attn",), L)
    if cfg.family == "moe":
        return BlockSpec(("attn_moe",), L)
    if cfg.family == "ssm":
        return BlockSpec(("ssm",), L)
    if cfg.family == "hybrid":
        k = cfg.attn_every
        return BlockSpec(("ssm",) * k, L // k, tail=("ssm",) * (L % k), shared_attn=True)
    raise ValueError(cfg.family)


# ======================================================================================
# Init
# ======================================================================================

def _init_sublayer(key, kind: str, cfg: ModelConfig) -> dict:
    ks = jax.random.split(key, 4)
    if kind == "ssm":
        return {"norm": init_norm(cfg), "ssm": ssm_lib.init_mamba(ks[0], cfg)}
    p = {"norm1": init_norm(cfg), "attn": init_attention(ks[0], cfg),
         "norm2": init_norm(cfg)}
    if kind == "attn_moe":
        p["moe"] = moe_lib.init_moe(ks[1], cfg)
    else:
        p["mlp"] = init_mlp(ks[1], cfg)
    return p


def init_params(key, cfg: ModelConfig, dtype=None) -> dict:
    dtype = dtype or jnp.float32
    spec = block_spec(cfg)
    ks = jax.random.split(key, 8)

    def stack(base_key, kind):
        keys = jax.random.split(base_key, spec.n_blocks)
        return jax.vmap(lambda k: _init_sublayer(k, kind, cfg))(keys)

    params: Dict[str, Any] = {
        "embed": {"w": (jax.random.normal(ks[0], (cfg.vocab_padded, cfg.d_model)) * 0.02)},
        "blocks": [stack(jax.random.fold_in(ks[1], i), kind)
                   for i, kind in enumerate(spec.sublayers)],
        "final_norm": init_norm(cfg),
    }
    if spec.tail:
        params["tail"] = [_init_sublayer(jax.random.fold_in(ks[2], i), kind, cfg)
                          for i, kind in enumerate(spec.tail)]
    if spec.shared_attn:
        params["shared_attn"] = {
            "norm1": init_norm(cfg),
            "attn": init_attention(ks[3], cfg),
            "norm2": init_norm(cfg),
            "mlp": init_mlp(ks[4], cfg),
        }
    if not cfg.tie_embeddings:
        params["lm_head"] = ql.init(ks[5], cfg.d_model, cfg.vocab_padded)
    if cfg.frontend != "none":
        params["frontend"] = frontends.init_frontend(ks[6], cfg)
    params = jax.tree_util.tree_map(lambda x: x.astype(dtype)
                                    if jnp.issubdtype(x.dtype, jnp.floating) else x, params)
    return params


def init_params_on_device(key, cfg: ModelConfig, dtype=None) -> dict:
    """:func:`init_params` as one compiled program on the default device, in
    ``dtype`` (default ``cfg.dtype``). Each leaf is drawn and cast inside the
    program, so no float32 tree is ever materialized: at full width that tree
    alone would be 4 bytes per parameter (12.3 GiB for granite-moe-3b-a800m)
    on a 16 GiB chip."""
    dtype = jnp.dtype(dtype or cfg.dtype)
    return jax.jit(init_params, static_argnums=(1, 2))(key, cfg, dtype)


# ======================================================================================
# Sublayer application
# ======================================================================================

def _apply_sublayer(kind: str, p: dict, x, cfg: ModelConfig, ctx: QuantContext, *,
                    cache=None, cur_len=None, decode=False, page_table=None,
                    prefix_len=None, q_len=None, chunk=None, state_table=None):
    """Returns (x, new_cache, aux)."""
    aux = jnp.zeros((), jnp.float32)
    if kind == "ssm":
        h, new_cache = ssm_lib.mamba_apply(p["ssm"], norm_apply(p["norm"], x, cfg), cfg,
                                           ctx.sub("ssm"), cache=cache, decode=decode,
                                           cur_len=cur_len, state_table=state_table)
        return x + h, new_cache, aux
    local = kind == "attn_local"
    h, new_cache = attention_apply(p["attn"], norm_apply(p["norm1"], x, cfg), cfg,
                                   ctx.sub("attn"), local=local, cache=cache,
                                   cur_len=cur_len, page_table=page_table,
                                   prefix_len=prefix_len, q_len=q_len, chunk=chunk)
    x = x + h
    if kind == "attn_moe":
        h, aux = moe_lib.moe_apply(p["moe"], norm_apply(p["norm2"], x, cfg), cfg,
                                   ctx.sub("moe"))
    else:
        h = mlp_apply(p["mlp"], norm_apply(p["norm2"], x, cfg), cfg, ctx.sub("mlp"))
    return x + h, new_cache, aux


def _shared_block(p: dict, x, cfg: ModelConfig, ctx: QuantContext, *,
                  cache=None, cur_len=None, page_table=None, prefix_len=None):
    h, new_cache = attention_apply(p["attn"], norm_apply(p["norm1"], x, cfg), cfg,
                                   ctx.sub("shared_attn"), cache=cache, cur_len=cur_len,
                                   page_table=page_table, prefix_len=prefix_len)
    x = x + h
    x = x + mlp_apply(p["mlp"], norm_apply(p["norm2"], x, cfg), cfg, ctx.sub("shared_mlp"))
    return x, new_cache


# ======================================================================================
# Cache construction
# ======================================================================================

def init_cache(cfg: ModelConfig, batch_size: int, max_len: int, dtype=jnp.bfloat16,
               *, kv_int8: bool = False, layout: str = "dense",
               page_size: int = 16, n_pages: Optional[int] = None) -> dict:
    """Pytree of per-layer caches, stacked (n_blocks, ...) to be scanned.

    ``layout="dense"`` (default): the batch axis is a *slot table* (DESIGN.md
    §3.6): each of the ``batch_size`` rows holds one in-flight sequence at its
    own length (``cur_len`` vector), so a continuous batcher can retire and
    refill individual slots without touching the others
    (serving/engine.py::_slot_scatter does the per-slot cache writes).

    ``layout="paged"`` (DESIGN.md §3.8/§3.13): instead of a dense
    ``(B, max_len)`` row per slot, every layer holds a physical pool built by
    its :mod:`repro.models.state` StateSpec and slots address it through
    top-level routing tables — ``page_table`` (batch_size, max_len//page_size)
    int32 for token-paged attention KV, ``state_table`` (batch_size,) int32 for
    fixed-size SSM state checkpoints (recurrent-state slab + pre-conv window,
    one page per slot regardless of length). Entry value ``n_pages`` is the
    *invalid* sentinel in both tables (reads clamp, the scatter drops).
    ``n_pages`` defaults to the dense-equivalent capacity
    ``batch_size * max_len / page_size``; serving engines pass less and rely
    on prefix sharing. Both table kinds draw ids from the same ref-counted
    pool (serving/paging.py), so a hybrid slot's KV pages and state page
    retire together.

    ``kv_int8=True`` stores attention K/V as int8 codes plus per-token f32 scales
    (layers.kv_quantize) — ~2×/4× less decode HBM traffic vs bf16/f32 caches
    (DESIGN.md §3.3). SSM recurrence state always stays f32.
    """
    spec = block_spec(cfg)
    has_kv, has_state = state_lib.family_flags(spec)
    stack = lambda tree: jax.tree_util.tree_map(
        lambda x: jnp.broadcast_to(x, (spec.n_blocks,) + x.shape), tree)
    if layout == "paged":
        if max_len % page_size:
            raise ValueError(f"page_size {page_size} must divide "
                             f"max_len {max_len}")
        n_pages = n_pages or batch_size * (max_len // page_size)

        def one_paged(kind):
            return state_lib.spec_for(kind).paged_leaves(
                cfg, n_pages, page_size, dtype, kv_int8)

        cache: Dict[str, Any] = {
            "blocks": [stack(one_paged(kind)) for kind in spec.sublayers]}
        if spec.tail:
            cache["tail"] = [one_paged(k) for k in spec.tail]
        if spec.shared_attn:
            cache["shared"] = stack(one_paged("attn"))
        if has_kv:
            cache["page_table"] = jnp.full(
                (batch_size, max_len // page_size), n_pages, jnp.int32)
        if has_state:
            cache["state_table"] = jnp.full((batch_size,), n_pages, jnp.int32)
        return cache
    if layout != "dense":
        raise ValueError(f"unknown cache layout {layout!r}")

    def one(kind):
        return state_lib.spec_for(kind).dense_leaves(
            cfg, batch_size, max_len, dtype, kv_int8)

    cache = {"blocks": [stack(one(kind)) for kind in spec.sublayers]}
    if spec.tail:
        cache["tail"] = [one(k) for k in spec.tail]
    if spec.shared_attn:
        cache["shared"] = stack(one("attn"))
    return cache


# ======================================================================================
# Forward
# ======================================================================================

def _embed(params, batch, cfg: ModelConfig):
    if cfg.frontend == "audio_stub":
        x = frontends.audio_stub_apply(params["frontend"], batch["frames"])
    else:
        x = jnp.take(params["embed"]["w"], batch["tokens"], axis=0)
        if cfg.frontend == "vision_stub" and "patch_embeds" in batch:
            # prefill carries patch embeddings; decode steps are text-token-only
            x = frontends.vision_stub_apply(params["frontend"], x,
                                            batch["patch_embeds"], cfg)
    if cfg.embed_scale:
        x = x * jnp.asarray(cfg.d_model ** 0.5, x.dtype)
    x = hints.constrain_batch(x)
    return x.astype(jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32)


def _lm_head(params, x, cfg: ModelConfig, ctx: QuantContext):
    """Returns logits over cfg.vocab_padded; padded ids carry -1e9 (never sampled,
    ~zero softmax mass) so callers can treat the padded width as the vocabulary."""
    x = norm_apply(params["final_norm"], x, cfg)
    if cfg.tie_embeddings:
        logits = x @ params["embed"]["w"].T.astype(x.dtype)
    else:
        logits = ctx.linear(params["lm_head"], x, "lm_head")
    logits = logits.astype(jnp.float32)
    # vocab_padded divides every production TP degree by construction: pin the
    # logits' padded-vocab dim to the model axis (and batch to dp) so the softcap /
    # pad-mask / sampling ops below run sharded instead of replicating a (B, S, V)
    # stack per device. No-op without sharding hints.
    logits = hints.constrain_vocab(logits)
    if cfg.final_softcap is not None:
        logits = cfg.final_softcap * jnp.tanh(logits / cfg.final_softcap)
    if cfg.vocab_padded != cfg.vocab:
        pad_mask = (jnp.arange(cfg.vocab_padded) >= cfg.vocab) * -1e9
        logits = logits + pad_mask
    return logits


def apply(
    params: dict, batch: dict, cfg: ModelConfig, *,
    ctx: Optional[QuantContext] = None, mode: str = "train",
    caches: Optional[dict] = None, cur_len: Optional[jax.Array] = None,
    prefix_len: Optional[jax.Array] = None, q_len: Optional[jax.Array] = None,
    chunk: Optional[dict] = None,
    unroll: bool = False, remat: bool = False,
) -> Tuple[jax.Array, dict]:
    """Returns (logits, {"aux_loss": scalar, "caches": updated-or-None}).

    mode: train (no caches) | prefill (build caches) | decode (read+update caches)
    | verify (speculative draft window, DESIGN.md §3.9).

    ``mode="verify"``: tokens (B, W) are a speculative draft window — column 0
    the pending token, the rest drafted continuations. All W tokens scatter
    into the caches at positions ``cur_len - q_len + i`` (``q_len`` (B,) valid
    window rows; invalid rows drop) and every window position's logits return
    (B, W, V) so the engine can greedily accept the longest matching prefix.
    ``cur_len`` is the per-slot *total* post-scatter length. Attention-only
    families — the SSM recurrence cannot rewind rejected tokens.

    ``cur_len`` may be a scalar (all slots aligned) or a per-slot (B,) int32 vector
    (DESIGN.md §3.6). Prefill: tokens are right-padded, positions start at 0, and
    ``cur_len`` holds per-slot prompt lengths — the returned logits are taken at
    each slot's own last valid position. Decode: ``cur_len`` is the per-slot
    post-append length; the token scatters into cache position ``cur_len - 1``.

    Paged caches (``init_cache(layout="paged")``, DESIGN.md §3.8) carry their
    ``page_table`` inside the cache pytree; it is threaded to every attention
    layer unchanged (the serving engine owns its contents). ``prefix_len`` (B,)
    marks prefill batches whose slots already hold a shared prefix of that many
    tokens in their pages: the batch tokens are the *suffix*, positions start at
    ``prefix_len[b]``, and ``cur_len`` counts suffix tokens only.

    ``mode="chunked"`` (DESIGN.md §3.10): tokens (1, Nt) are a *packed ragged
    token row* mixing many slots' work — single decode tokens, page-aligned
    prefill chunks, cold admissions — served in one launch against a paged
    cache. ``chunk`` carries per-slot extents (``q_start``/``q_len``/``kv_len``
    (B,)) and per-token routing (``positions``/``slot_ids`` (Nt,)); logits
    return for every packed row (1, Nt, V) and the engine gathers each slot's
    last valid row. Attention-only families, paged caches only.
    """
    ctx = ctx or QuantContext(cfg.quant)
    spec = block_spec(cfg)
    decode = mode == "decode"
    verify = mode == "verify"
    chunked = mode == "chunked"
    if verify and q_len is None:
        raise ValueError("mode='verify' needs q_len (per-slot valid window rows)")
    if verify and cfg.family in ("ssm", "hybrid"):
        raise ValueError(f"speculative verify needs attention-only caches; "
                         f"family {cfg.family!r} carries SSM state")
    if q_len is not None and not verify:
        raise ValueError("q_len is only meaningful under mode='verify'")
    if chunked and chunk is None:
        raise ValueError("mode='chunked' needs a chunk dict (per-slot extents "
                         "+ per-token routing, DESIGN.md §3.10)")
    if chunk is not None and not chunked:
        raise ValueError("chunk is only meaningful under mode='chunked'")
    if chunked and cfg.family in ("ssm", "hybrid"):
        raise ValueError(f"chunked serving needs attention-only caches; "
                         f"family {cfg.family!r} carries SSM state")
    x = _embed(params, batch, cfg)
    aux_total = jnp.zeros((), jnp.float32)

    use_cache = mode in ("prefill", "decode", "verify", "chunked")
    if use_cache and caches is None:
        raise ValueError("prefill/decode/verify need caches (init_cache)")
    page_table = caches.get("page_table") if use_cache else None
    state_table = caches.get("state_table") if use_cache else None
    if prefix_len is not None and page_table is None:
        raise ValueError("prefix_len needs a paged cache (its page_table routes "
                         "the shared prefix)")

    def block_fn(x, block_params, block_caches, shared_cache, cur_len, bctx=None):
        bctx = bctx or ctx
        x = hints.constrain_batch(x)
        aux_sum = jnp.zeros((), jnp.float32)
        new_caches: List[Any] = []
        for i, kind in enumerate(spec.sublayers):
            c = block_caches[i] if use_cache else None
            x, nc, aux = _apply_sublayer(kind, block_params[i], x, cfg,
                                         bctx.sub(f"S{i}"),
                                         cache=c, cur_len=cur_len, decode=decode,
                                         page_table=page_table,
                                         prefix_len=prefix_len, q_len=q_len,
                                         chunk=chunk, state_table=state_table)
            aux_sum += aux
            new_caches.append(nc if nc is not None else c)
        new_shared = shared_cache
        if spec.shared_attn:
            x, new_shared = _shared_block(params["shared_attn"], x, cfg, ctx,
                                          cache=shared_cache, cur_len=cur_len,
                                          page_table=page_table,
                                          prefix_len=prefix_len)
        return x, new_caches, new_shared, aux_sum

    if unroll:
        take = lambda tree, i: jax.tree_util.tree_map(lambda a: a[i], tree)
        for b in range(spec.n_blocks):
            bp = [take(params["blocks"][i], b) for i in range(len(spec.sublayers))]
            bc = ([take(caches["blocks"][i], b) for i in range(len(spec.sublayers))]
                  if use_cache else [None] * len(spec.sublayers))
            sc = take(caches["shared"], b) if (use_cache and spec.shared_attn) else None
            # Per-layer ctx prefix: calibration observers record per-layer column
            # stats under names calibration.stack_tables maps back to param paths.
            x, _, _, aux = block_fn(x, bp, bc, sc, cur_len, bctx=ctx.sub(f"L{b}"))
            aux_total += aux
    else:
        def scan_body(carry, xs):
            x, aux_acc = carry
            bp = xs["p"]
            bc = xs.get("c", [None] * len(spec.sublayers))
            sc = xs.get("s")
            x, ncs, nsc, aux = block_fn(x, bp, bc, sc, cur_len)
            ys = {}
            if use_cache:
                ys["c"] = ncs
                if spec.shared_attn:
                    ys["s"] = nsc
            return (x, aux_acc + aux), ys

        body = jax.checkpoint(scan_body, policy=None) if remat else scan_body
        xs: Dict[str, Any] = {"p": params["blocks"]}
        if use_cache:
            xs["c"] = caches["blocks"]
            if spec.shared_attn:
                xs["s"] = caches["shared"]
        (x, aux_total), ys = jax.lax.scan(body, (x, aux_total), xs)
        if use_cache:
            caches = dict(caches)
            caches["blocks"] = ys["c"]
            if spec.shared_attn:
                caches["shared"] = ys["s"]

    # hybrid tail (unscanned remainder layers)
    if spec.tail:
        new_tail = []
        for i, kind in enumerate(spec.tail):
            c = caches["tail"][i] if use_cache else None
            x, nc, aux = _apply_sublayer(kind, params["tail"][i], x, cfg,
                                         ctx.sub(f"T{i}"),
                                         cache=c, cur_len=cur_len, decode=decode,
                                         state_table=state_table)
            aux_total += aux
            new_tail.append(nc if nc is not None else c)
        if use_cache:
            caches["tail"] = new_tail

    if mode == "prefill":
        if cur_len is None:
            x = x[:, -1:]
        else:
            # per-slot last valid position (right-padded prompts, §3.6)
            last = jnp.reshape(jnp.asarray(cur_len, jnp.int32), (-1,)) - 1
            last = jnp.clip(last, 0, x.shape[1] - 1)
            idx = jnp.broadcast_to(last[:, None, None], (x.shape[0], 1, x.shape[2]))
            x = jnp.take_along_axis(x, idx, axis=1)
        logits = _lm_head(params, x, cfg, ctx)
    else:
        logits = _lm_head(params, x, cfg, ctx)
    return logits, {"aux_loss": aux_total, "caches": caches if use_cache else None}


# ======================================================================================
# Loss
# ======================================================================================

def loss_fn(params, batch, cfg: ModelConfig, *, ctx=None, remat: bool = True):
    """Causal-LM (or encoder classification) cross entropy + MoE aux loss."""
    logits, extras = apply(params, batch, cfg, ctx=ctx, mode="train", remat=remat)
    if cfg.is_encoder_only:
        labels = batch["labels"]
        mask = jnp.ones(labels.shape, jnp.float32)
    else:
        tokens = batch["tokens"]
        labels = jnp.concatenate([tokens[:, 1:], tokens[:, :1]], axis=1)
        mask = jnp.ones(labels.shape, jnp.float32).at[:, -1].set(0.0)
        if cfg.frontend == "vision_stub":
            mask = mask.at[:, : cfg.n_patches].set(0.0)
    if "loss_mask" in batch:
        mask = mask * batch["loss_mask"]
    logp = jax.nn.log_softmax(logits, axis=-1)
    ll = jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    ce = -(ll * mask).sum() / jnp.maximum(mask.sum(), 1.0)
    loss = ce + 0.01 * extras["aux_loss"]
    return loss, {"ce": ce, "aux": extras["aux_loss"],
                  "ppl": jnp.exp(jnp.minimum(ce, 20.0))}
