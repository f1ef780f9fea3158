"""Divisibility-aware sharding planner (DESIGN.md §3.4).

Head counts and widths in the assigned pool rarely divide the model axis (56/40/36/24
heads vs tp=16), so hand-written per-model shardings would either error or silently
replicate. The planner chooses, per (arch × workload), the strongest tier whose
divisibility constraints hold, and emits concrete ``NamedSharding`` pytrees for params,
optimizer state, batches and KV/SSM caches. Every rule degrades gracefully: a dimension
that does not divide its target axis is replicated, never an error.

Tiers (attention handling):
  tp_full    q, kv heads and ffn sharded over "model"
  tp_kv_rep  kv replicated (GQA repeat stays shard-local), q + ffn sharded
  tp_ffn     attention replicated, ffn/vocab sharded
MoE: EP over "model" when E divides, else expert-internal TP (d_ff_expert divides).
Decode KV caches are sequence-sharded over "model" (flash-decoding via GSPMD partial
softmax) — the only layout that fits TB-scale 32k caches when kv-heads don't divide.
Training additionally FSDP-shards weight input dims over the data axes (ZeRO-3:
all-gather per scanned layer).
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig, ShapeConfig
from repro.kernels.paged_layout import splits_in_heads


@dataclasses.dataclass(frozen=True)
class Plan:
    tier: str                  # tp_full | tp_kv_rep | tp_ffn
    moe_mode: str              # none | ep | expert_tp | expert_axis
    dp_axes: Tuple[str, ...]   # batch axes, e.g. ("pod", "data")
    tp_axis: str               # "model"
    dp: int
    tp: int
    fsdp: bool                 # shard weight free dims over dp axes (training)
    seq_shard_kv: bool         # decode caches: T over model
    # Dedicated expert-parallel mesh axis (DESIGN.md §3.13): when the mesh carries
    # an "expert" axis that divides n_experts, stacked (E, ...) expert trees shard
    # on E over it (moe_mode == "expert_axis") — orthogonal to the model axis, so
    # tp×ep meshes compose. None on 2-axis meshes (legacy "ep" then shards experts
    # over the model axis as before).
    ep_axis: Optional[str] = None
    ep: int = 1

    def describe(self) -> dict:
        return dataclasses.asdict(self)


def _axis_size(mesh: Mesh, axes) -> int:
    if isinstance(axes, str):
        axes = (axes,)
    return int(np.prod([mesh.shape[a] for a in axes]))


def make_serve_plan(cfg: ModelConfig, mesh: Mesh,
                    force_tier: Optional[str] = None) -> Plan:
    """Serving-mode plan for ``ServeEngine`` (DESIGN.md §3.7): a decode-kind plan
    whose specs also cover *prepared integer* trees — int8/packed-int4 weights and
    their scale leaves (``sw``, ``bcol``, ``qalpha``) and packed sparsity ``mask``
    leaves follow the same model-axis split as the weight they dequantize — and
    slot-table KV caches including the int8-KV per-token scale leaves."""
    shape = ShapeConfig(name="serve", seq_len=0, global_batch=0, kind="decode")
    return make_plan(cfg, shape, mesh, force_tier=force_tier)


def make_plan(cfg: ModelConfig, shape: ShapeConfig, mesh: Mesh,
              force_tier: Optional[str] = None) -> Plan:
    dp_axes = tuple(a for a in ("pod", "data") if a in mesh.shape)
    tp = mesh.shape["model"]
    dp = _axis_size(mesh, dp_axes)

    if cfg.n_heads % tp == 0 and cfg.n_kv_heads % tp == 0:
        tier = "tp_full"
    elif cfg.n_heads % tp == 0:
        tier = "tp_kv_rep"
    else:
        tier = "tp_ffn"
    if force_tier:
        tier = force_tier
    if tier == "dp_only":
        # Small models waste a 16-wide TP axis (32-wide GEMM shards, per-layer
        # collectives dwarfing compute — mamba2-130m baseline, EXPERIMENTS.md
        # §Perf). dp_only folds the model axis into data parallelism: batch shards
        # over (data, model), weights FSDP over the full mesh, no TP collectives.
        dp_axes = dp_axes + ("model",)
        dp = _axis_size(mesh, dp_axes)

    ep_axis = None
    ep = 1
    moe_mode = "none"
    if cfg.n_experts and "expert" in mesh.shape and tier != "dp_only" \
            and cfg.n_experts % mesh.shape["expert"] == 0:
        # Dedicated expert axis: experts shard over it, expert-internal dims stay
        # whole (each expert GEMM runs entirely on one ep shard — its int32
        # contraction is shard-local, hence bitwise vs single-device).
        ep_axis, ep, moe_mode = "expert", mesh.shape["expert"], "expert_axis"
    elif cfg.n_experts and tier != "dp_only":
        if cfg.n_experts % tp == 0:
            moe_mode = "ep"
        elif (cfg.d_ff_expert or cfg.d_ff) % tp == 0:
            moe_mode = "expert_tp"

    return Plan(
        tier=tier, moe_mode=moe_mode, dp_axes=dp_axes, tp_axis="model",
        dp=dp, tp=tp, fsdp=(shape.kind == "train"), ep_axis=ep_axis, ep=ep,
        # KV caches are the dominant serving bytes at 32k context; sequence-shard them
        # over the model axis for decode (flash-decoding partial softmax) AND prefill
        # (the cache write pays one reshard; holding 32 × 32k × Hkv caches replicated
        # over model does not fit HBM — EXPERIMENTS.md §Perf).
        seq_shard_kv=(shape.kind in ("decode", "prefill")),
    )


# ======================================================================================
# Parameter shardings
# ======================================================================================

def _path_str(path) -> str:
    parts = []
    for p in path:
        if hasattr(p, "key"):
            parts.append(str(p.key))
        elif hasattr(p, "idx"):
            parts.append(str(p.idx))
        else:
            parts.append(str(p))
    return "/".join(parts)


def _maybe(axis: str | Tuple[str, ...], dim: int, mesh: Mesh):
    """Return the axis if the dim divides it, else None (replicate)."""
    return axis if dim % _axis_size(mesh, axis) == 0 else None


def _param_spec(pathstr: str, shape: Tuple[int, ...], cfg: ModelConfig,
                plan: Plan, mesh: Mesh) -> P:
    names = pathstr.split("/")
    leaf = names[-1]
    parent = names[-2] if len(names) >= 2 else ""
    tp, dpa = plan.tp_axis, plan.dp_axes
    nd = len(shape)

    def build(out_axis: Optional[int], model_ok: bool, fsdp_axis: Optional[int] = None):
        """spec with `model` on dim `out_axis` (negative index) and optional FSDP dim.

        Hybrid ZeRO-3: when the weight carries no model-axis shard (tier degraded or
        dim not divisible), FSDP uses (data..., model) so parameters/optimizer shard
        over the *full* mesh — the difference between 35 GiB/dev and 4 GiB/dev on
        deepseek-33b train (EXPERIMENTS.md §Perf)."""
        spec: list = [None] * nd
        placed_model = False
        if model_ok and out_axis is not None and _maybe(tp, shape[out_axis], mesh):
            spec[out_axis] = tp
            placed_model = True
        if plan.fsdp and fsdp_axis is not None and spec[fsdp_axis] is None:
            full = dpa if tp in dpa else tuple(dpa) + (tp,)
            candidates = (dpa,) if placed_model else (full, dpa)
            for axes in candidates:
                if _maybe(axes, shape[fsdp_axis], mesh):
                    spec[fsdp_axis] = axes
                    break
        return P(*spec)

    # ---- scalars / vectors: norms, biases, A_log, D, dt_bias, conv, router ----------
    # (quantization-metadata leaves — sw/bcol/qalpha — are handled with their weight
    # below: scale vectors must split along the same model axis as the weight dim
    # they dequantize, or every sharded serving step pays a per-layer reshard.)
    if parent in ("router",) or leaf in ("scale", "bias", "conv_w", "conv_b", "A_log",
                                         "D", "dt_bias", "norm_scale"):
        return P(*([None] * nd))

    # ---- dp_only: pure FSDP over the folded (data+model) mesh, no TP placement -------
    if plan.tier == "dp_only":
        if leaf in ("w", "qw", "qw4") and nd >= 2:
            if _maybe(dpa, shape[-2], mesh):
                return build(out_axis=None, model_ok=False, fsdp_axis=-2)
            return build(out_axis=None, model_ok=False, fsdp_axis=-1)
        return P(*([None] * nd))

    # ---- embedding / lm head ---------------------------------------------------------
    if parent == "embed":
        spec: list = [None] * nd
        placed = False
        if _maybe(tp, shape[-2], mesh):
            spec[-2] = tp                                    # vocab over model
            placed = True
        if plan.fsdp:
            full = dpa if tp in dpa else tuple(dpa) + (tp,)
            for axes in ((dpa,) if placed else (full, dpa)):
                if _maybe(axes, shape[-1], mesh):
                    spec[-1] = axes
                    break
        return P(*spec)
    if parent == "lm_head":
        return build(out_axis=-1, model_ok=True, fsdp_axis=-2)

    # Shared experts are plain dense MLPs: shard d_ff over model like any MLP.
    # (Treating them as stacked-expert tensors would shard the layer-stack axis,
    # which XLA then all-gathers wholesale outside the scan — 7.5 GiB/device on
    # llama4 decode, EXPERIMENTS.md §Perf.)
    moe = "moe" in names and parent in ("up", "gate", "down") and "shared" not in names
    if moe:
        if plan.ep_axis is not None:
            # Dedicated expert axis (§3.13): EVERY stacked expert leaf — weights
            # AND their quantization metadata (sw/bcol/qalpha) and packed sparsity
            # masks — shards its E dim over the expert axis, so each ep shard holds
            # whole experts with their scales co-located (no per-step reshard, and
            # the int32 expert GEMM never crosses shards → bitwise). Expert-internal
            # dims stay whole in this mode; the router was replicated above.
            e_dim = 1 if names[0] == "blocks" else 0
            spec = [None] * nd
            if nd > e_dim and _maybe(plan.ep_axis, shape[e_dim], mesh):
                spec[e_dim] = plan.ep_axis
            return P(*spec)
        if nd < 3 or leaf not in ("w", "qw", "qw4"):
            # prepared-tree scale vectors ((L, E, d_out) sw etc.): replicate — tiny
            return P(*([None] * nd))
        if plan.moe_mode == "ep":
            spec = [None] * nd
            spec[-3] = tp                                    # experts over model
            if plan.fsdp and _maybe(dpa, shape[-2], mesh):
                spec[-2] = dpa
            return P(*spec)
        if plan.moe_mode == "expert_tp":
            ax = -1 if parent in ("up", "gate") else -2      # shard d_ff_expert
            return build(out_axis=ax, model_ok=True, fsdp_axis=(-2 if ax == -1 else -1))
        return build(out_axis=None, model_ok=False, fsdp_axis=-2)

    attn_ok = plan.tier in ("tp_full", "tp_kv_rep")
    table = {
        "wq":  (-1, attn_ok, -2),
        "wk":  (-1, plan.tier == "tp_full", -2),
        "wv":  (-1, plan.tier == "tp_full", -2),
        "wo":  (-2, attn_ok, -1),
        "up":   (-1, True, -2),
        "gate": (-1, True, -2),
        "down": (-2, True, -1),
        "in_proj":  (-1, True, -2),
        "out_proj": (-2, True, -1),
        "proj": (-1, True, -2),                              # frontend stub
    }
    if parent in table and leaf in ("w", "qw", "qw4"):
        ax, ok, fa = table[parent]
        return build(out_axis=ax, model_ok=ok, fsdp_axis=fa)
    if parent in table and leaf == "sw":
        # Dequant scale vector(s) follow the weight's model-axis split. Column-
        # parallel (d_out last on the weight): shard sw's d_out. Row-parallel int4
        # (d_in sharded): sw is (..., G, d_out) with G = d_in/group — shard the
        # group axis, which stays aligned with the weight's d_in shard exactly when
        # tp divides G (whole groups per shard). Anything else replicates. The
        # group axis only exists when the per-layer rank is 2: a scanned int8 sw is
        # (n_blocks, d_out) — its leading dim is the layer-stack axis, which must
        # never shard (XLA all-gathers the whole stack outside the scan otherwise).
        ax, ok, _ = table[parent]
        rank = nd - (1 if names[0] == "blocks" else 0)
        if ok and ax == -1 and _maybe(tp, shape[-1], mesh):
            return P(*([None] * (nd - 1) + [tp]))
        if ok and ax == -2 and rank == 2 and _maybe(tp, shape[-2], mesh):
            return P(*([None] * (nd - 2) + [tp, None]))
        return P(*([None] * nd))
    if parent in table and leaf == "bcol":
        # Per-input-channel b = c^(1-α) divides the activation before the GEMM:
        # shard along d_in exactly when the weight is row-parallel (its d_in is the
        # model-sharded contraction dim), so the act-quantize divide runs on the
        # shard each device already holds.
        ax, ok, _ = table[parent]
        if ok and ax == -2 and _maybe(tp, shape[-1], mesh):
            return P(*([None] * (nd - 1) + [tp]))
        return P(*([None] * nd))
    if parent in table and leaf == "mask":
        # Bit-packed N:M keep-mask (packed along d_in — §3.12): rides its weight's
        # model-axis split. Column-parallel: shard d_out (last axis, unpacked).
        # Row-parallel: the shard would land on the *packed* axis — allowed only at
        # byte granularity (same contract as packed int4 qw4), so tp must divide
        # d_in//8; otherwise replicate — the mask is metadata the kernel wrapper
        # gathers anyway, so replication costs capacity, never correctness.
        ax, ok, _ = table[parent]
        if ok and ax == -1 and _maybe(tp, shape[-1], mesh):
            return P(*([None] * (nd - 1) + [tp]))
        if ok and ax == -2 and _maybe(tp, shape[-2], mesh):
            return P(*([None] * (nd - 2) + [tp, None]))
        return P(*([None] * nd))
    # qalpha (effective-alpha scalar, leading stack dims only) and anything else
    # unrecognized: replicate
    return P(*([None] * nd))


def param_shardings(param_tree, cfg: ModelConfig, plan: Plan, mesh: Mesh):
    """param_tree: pytree of arrays or ShapeDtypeStructs → pytree of NamedSharding."""
    def one(path, leaf):
        spec = _param_spec(_path_str(path), leaf.shape, cfg, plan, mesh)
        return NamedSharding(mesh, spec)
    return jax.tree_util.tree_map_with_path(one, param_tree)


# ======================================================================================
# Batch / cache shardings
# ======================================================================================

def batch_shardings(batch_tree, plan: Plan, mesh: Mesh):
    def one(path, leaf):
        spec: list = [None] * len(leaf.shape)
        if leaf.shape and _maybe(plan.dp_axes, leaf.shape[0], mesh):
            spec[0] = plan.dp_axes
        return NamedSharding(mesh, P(*spec))
    return jax.tree_util.tree_map_with_path(one, batch_tree)


def cache_shardings(cache_tree, cfg: ModelConfig, plan: Plan, mesh: Mesh):
    """KV caches (B,T,Hkv,D) [+ leading n_blocks when stacked]: B→dp, T→model (decode).
    int8 KV per-token scale leaves (``k_scale``/``v_scale``, (B,T,Hkv,1)) carry the
    same (B→dp, T→model) split as the codes they dequantize — a slot's scale row
    must live with its code row or every decode-step scatter pays a reshard.
    Paged pools in their stored layout (``*_pages``: (P, ps, Hkv·D) codes,
    (P, 1, L) int8 scale rows — DESIGN.md §3.8, ``kernels/paged_layout.py``):
    physical page axis→dp, the lane axis→model when it splits in whole kv
    heads (there is no contiguous T axis to sequence-shard; capacity scales
    with the dp-split page axis instead), with the int8 scale pools following
    their code pools' heads where their row splits the same way (a row padded
    past its heads stays whole); the ``page_table`` and any unrecognized leaf
    replicate. These placements govern *storage*: at the
    decode step the paged kernel consumes code and scale pools alike as
    operands of one ``hints.manual_kernel`` region (gathered at that boundary),
    so scale pools sharding differently from their codes would only add a
    reshard — following the code pools keeps scatter and gather symmetric.
    SSM caches: B→dp, heads→model when divisible."""
    leaves = jax.tree_util.tree_flatten_with_path(cache_tree)[0]
    # every attention pool of one cache shares the page size (codes' axis -2)
    ps = next((leaf.shape[-2] for path, leaf in leaves
               if _path_str(path).endswith("k_pages")), None)

    def one(path, leaf):
        pathstr = _path_str(path)
        names = pathstr.split("/")
        stacked = "tail" not in names
        nd = len(leaf.shape)
        off = 1 if stacked else 0
        spec: list = [None] * nd
        last = names[-1]
        if last in ("k_pages", "v_pages", "k_scale_pages", "v_scale_pages"):
            if _maybe(plan.dp_axes, leaf.shape[off + 0], mesh):
                spec[off + 0] = plan.dp_axes
            head_lanes = ps if "scale" in last else cfg.head_dim
            if splits_in_heads(leaf.shape[-1], cfg.n_kv_heads, head_lanes,
                               _axis_size(mesh, plan.tp_axis)):
                spec[-1] = plan.tp_axis
        elif last in ("k", "v", "k_scale", "v_scale"):
            if _maybe(plan.dp_axes, leaf.shape[off + 0], mesh):
                spec[off + 0] = plan.dp_axes
            if plan.seq_shard_kv and _maybe(plan.tp_axis, leaf.shape[off + 1], mesh):
                spec[off + 1] = plan.tp_axis
        elif last == "state_pages":                  # (P, H, Pd, N) — §3.13
            if _maybe(plan.dp_axes, leaf.shape[off + 0], mesh):
                spec[off + 0] = plan.dp_axes
            if _maybe(plan.tp_axis, leaf.shape[off + 1], mesh):
                spec[off + 1] = plan.tp_axis
        elif last == "conv_pages":                   # (P, K-1, C)
            if _maybe(plan.dp_axes, leaf.shape[off + 0], mesh):
                spec[off + 0] = plan.dp_axes
        elif last == "state":                        # (B, H, P, N)
            if _maybe(plan.dp_axes, leaf.shape[off + 0], mesh):
                spec[off + 0] = plan.dp_axes
            if _maybe(plan.tp_axis, leaf.shape[off + 1], mesh):
                spec[off + 1] = plan.tp_axis
        elif last == "conv":                         # (B, K-1, C)
            if _maybe(plan.dp_axes, leaf.shape[off + 0], mesh):
                spec[off + 0] = plan.dp_axes
        return NamedSharding(mesh, P(*spec))
    return jax.tree_util.tree_map_with_path(one, cache_tree)


def replicated(tree, mesh: Mesh):
    return jax.tree_util.tree_map(
        lambda leaf: NamedSharding(mesh, P(*([None] * len(leaf.shape)))), tree)
