"""Activation-sharding hints (contextvars) consumed inside model code.

GSPMD propagates most activation shardings from parameter/input shardings, but a few
internal tensors need explicit constraints to avoid pathological layouts — notably the
MoE dispatch buffer (must be expert-sharded, not replicated) and the post-embedding
activations (a gather output can lose its batch sharding, after which the partitioner
replicates whole activation stacks). Launchers set these hints around tracing; unit
tests and eager code leave them unset (every constraint degrades to a no-op).

Axis *sizes* are carried in the hints (from the concrete mesh) because
``jax.sharding.get_abstract_mesh()`` is empty under a plain ``with mesh:`` scope —
divisibility checks cannot read the mesh from inside a trace.
"""
from __future__ import annotations

import contextlib
import contextvars
from typing import Dict, Optional, Tuple

import jax
from jax.sharding import PartitionSpec as P

_EP_AXIS: contextvars.ContextVar[Optional[str]] = contextvars.ContextVar(
    "ep_axis", default=None)
_DP_AXES: contextvars.ContextVar[Optional[Tuple[str, ...]]] = contextvars.ContextVar(
    "dp_axes", default=None)
_TP_AXIS: contextvars.ContextVar[Optional[str]] = contextvars.ContextVar(
    "tp_axis", default=None)
_AXIS_SIZES: contextvars.ContextVar[Dict[str, int]] = contextvars.ContextVar(
    "axis_sizes", default={})
_MESH: contextvars.ContextVar = contextvars.ContextVar("hint_mesh", default=None)
_KV_SEQ_AXIS: contextvars.ContextVar[Optional[str]] = contextvars.ContextVar(
    "kv_seq_axis", default=None)
_TOKEN_GROUPS: contextvars.ContextVar[bool] = contextvars.ContextVar(
    "token_groups", default=True)


@contextlib.contextmanager
def sharding_hints(ep_axis: Optional[str] = None,
                   dp_axes: Optional[Tuple[str, ...]] = None,
                   tp_axis: Optional[str] = None,
                   mesh=None,
                   kv_seq_axis: Optional[str] = None,
                   token_groups: bool = True):
    sizes = dict(mesh.shape) if mesh is not None else {}
    t1 = _EP_AXIS.set(ep_axis)
    t2 = _DP_AXES.set(dp_axes)
    t3 = _TP_AXIS.set(tp_axis)
    t4 = _AXIS_SIZES.set(sizes)
    t5 = _MESH.set(mesh)
    t6 = _KV_SEQ_AXIS.set(kv_seq_axis)
    t7 = _TOKEN_GROUPS.set(token_groups)
    try:
        yield
    finally:
        _EP_AXIS.reset(t1)
        _DP_AXES.reset(t2)
        _TP_AXIS.reset(t3)
        _AXIS_SIZES.reset(t4)
        _MESH.reset(t5)
        _KV_SEQ_AXIS.reset(t6)
        _TOKEN_GROUPS.reset(t7)


def _constrain(x: jax.Array, spec: P) -> jax.Array:
    """with_sharding_constraint that does not require an ambient mesh context:
    when the hints carry a concrete mesh (serving engine, launchers), the spec is
    bound to it as a NamedSharding; otherwise the plain-spec form is used (the
    dry-run already traces under ``with mesh:``)."""
    mesh = _MESH.get()
    if mesh is not None:
        return jax.lax.with_sharding_constraint(
            x, jax.sharding.NamedSharding(mesh, spec))
    return jax.lax.with_sharding_constraint(x, spec)


def _axis_size(axes) -> int:
    sizes = _AXIS_SIZES.get()
    if not sizes:
        return 1 << 62   # unknown mesh: fail every divisibility check -> no-op
    if isinstance(axes, str):
        axes = (axes,)
    n = 1
    for a in axes:
        if a not in sizes:
            return 1 << 62
        n *= sizes[a]
    return n


def constrain_experts(x: jax.Array) -> jax.Array:
    """x: (E, C, d) stacked expert buffers — pin E to the EP axis (when divisible)
    and the capacity axis to the data axes (token parallelism inside the expert
    computation). Without the C constraint the dispatch buffer replicates across the
    data axis: 7.5 GB/device on granite-moe train_4k (EXPERIMENTS.md §Perf)."""
    ep = _EP_AXIS.get()
    dp = _DP_AXES.get()
    spec = [None] * x.ndim
    if ep is not None and x.shape[0] % _axis_size(ep) == 0:
        spec[0] = ep
    if dp is not None and x.ndim >= 2 and x.shape[1] % _axis_size(dp) == 0:
        spec[1] = dp
    if all(s is None for s in spec):
        return x
    return _constrain(x, P(*spec))


def constrain_batch(x: jax.Array) -> jax.Array:
    """(B, ...) activations — pin the leading batch axis to the data axes.

    GSPMD mostly propagates batch sharding from the input tokens, but gathers
    (embedding lookups) and microbatch reshapes can lose it, after which the
    partitioner replicates entire activation stacks (observed: 265 GB/device temps on
    mamba2 train_4k before this constraint — EXPERIMENTS.md §Perf iteration 0)."""
    axes = _DP_AXES.get()
    if axes is None:
        return x
    if x.ndim == 0 or x.shape[0] % _axis_size(axes) != 0:
        return x
    return _constrain(x, P(axes, *([None] * (x.ndim - 1))))


def constrain_tokens(x: jax.Array) -> jax.Array:
    """x: (N, d) flat token activations — pin to the data axes if hinted."""
    axes = _DP_AXES.get()
    if axes is None:
        return x
    if x.shape[0] % _axis_size(axes) != 0:
        return x
    return _constrain(x, P(axes, *([None] * (x.ndim - 1))))


def token_group_count(n_tokens: int) -> int:
    """Number of dp-aligned token groups for grouped MoE dispatch (GShard-style
    per-group capacity). Equals the data-axis size when it divides the token count,
    else 1 (single global dispatch — tests, eager mode).

    Grouping changes the *capacity arithmetic*: per-group capacity admits a
    different set of (token, k) assignments than one global dispatch whenever an
    expert overflows, so grouped and global dispatch are not token-exact. Callers
    that need mesh-invariant numerics — the serving engine, whose EP parity
    contract is bitwise vs single-device (§3.13) — trace under
    ``sharding_hints(..., token_groups=False)``, which forces global dispatch."""
    if not _TOKEN_GROUPS.get():
        return 1
    axes = _DP_AXES.get()
    if axes is None:
        return 1
    g = _axis_size(axes)
    if g >= (1 << 62) or n_tokens % g != 0:
        return 1
    return g


def constrain_token_groups(x: jax.Array) -> jax.Array:
    """(G, N/G, ...) grouped tokens — pin the group axis to the data axes so every
    per-group dispatch gather/scatter has a sharded batch dimension (SPMD partitions
    batched gathers on their parallel dims; unbatched dispatch gathers replicate the
    whole (N·K, d) expansion — 48 GiB/device on granite prefill_32k,
    EXPERIMENTS.md §Perf)."""
    axes = _DP_AXES.get()
    if axes is None:
        return x
    if x.shape[0] % _axis_size(axes) != 0:
        return x
    return _constrain(x, P(axes, *([None] * (x.ndim - 1))))


def constrain_grouped_experts(x: jax.Array) -> jax.Array:
    """(G, E, C, d) grouped expert buffers — G → data axes, E → EP axis."""
    ep = _EP_AXIS.get()
    dp = _DP_AXES.get()
    spec = [None] * x.ndim
    if dp is not None and x.shape[0] % _axis_size(dp) == 0:
        spec[0] = dp
    if ep is not None and x.ndim >= 2 and x.shape[1] % _axis_size(ep) == 0:
        spec[1] = ep
    if all(s is None for s in spec):
        return x
    return _constrain(x, P(*spec))


def constrain_microbatches(x: jax.Array) -> jax.Array:
    """(n_micro, B_micro, ...) stacked microbatches — dp on axis 1, never axis 0
    (the scan axis must stay unsharded or every scan step pays a reshard)."""
    axes = _DP_AXES.get()
    if axes is None:
        return x
    if x.ndim < 2 or x.shape[1] % _axis_size(axes) != 0:
        return x
    return _constrain(x, P(None, axes, *([None] * (x.ndim - 2))))


def constrain_gemm_acc(acc: jax.Array, expert_leading: bool = False) -> jax.Array:
    """int32 GEMM accumulator of a quantized linear (DESIGN.md §3.7) — pin to the
    natural output layout (batch → dp, d_out → model, everything else replicated)
    *while still int32*.

    For a row-parallel weight (contraction dim sharded over the model axis) this
    forces the cross-shard partial-sum reduction to happen on the integer
    accumulator BEFORE the f32 dequant multiply. Without the pin the partitioner
    is free to sink the all-reduce past the elementwise dequant, summing partially
    dequantized f32 shards — numerically close, but no longer the bitwise-exact
    integer contraction the single-device path computes, and exactly the
    per-channel/per-token scale-handling trap ZeroQuant-V2 documents for
    quantized-TP serving.

    ``expert_leading=True`` marks stacked-expert accumulators ((E, C, d_out) or
    (E, C, G, d_out)): dim 0 is the expert axis (pinned to the EP axis when
    hinted and divisible — the expert_tp case leaves it replicated) and dim 1 the
    capacity axis (→ dp), mirroring constrain_experts."""
    tp = _TP_AXIS.get()
    dp = _DP_AXES.get()
    if tp is None and dp is None:
        return acc
    spec = [None] * acc.ndim
    if expert_leading:
        ep = _EP_AXIS.get()
        if ep is not None and acc.shape[0] % _axis_size(ep) == 0:
            spec[0] = ep
        if dp is not None and acc.ndim >= 3 and acc.shape[1] % _axis_size(dp) == 0:
            spec[1] = dp
    elif dp is not None and acc.ndim >= 2 and acc.shape[0] % _axis_size(dp) == 0:
        spec[0] = dp
    used = {a for s in spec if s is not None
            for a in ((s,) if isinstance(s, str) else s)}
    if tp is not None and tp not in used and acc.shape[-1] % _axis_size(tp) == 0:
        spec[-1] = tp
    return _constrain(acc, P(*spec))


def constrain_kv_cache(x: jax.Array) -> jax.Array:
    """(B, T, Hkv, D|1) attention-cache leaf (codes or int8-KV per-token scales) —
    pin B to the data axes and, when the plan sequence-shards decode caches, T to
    the model axis. Applied to freshly written cache leaves so the per-step scatter
    output keeps the slot table's placement instead of GSPMD resharding the whole
    cache every decode step."""
    dp = _DP_AXES.get()
    kv_seq = _KV_SEQ_AXIS.get()
    if (dp is None and kv_seq is None) or x.ndim < 2:
        return x
    spec = [None] * x.ndim
    if dp is not None and x.shape[0] % _axis_size(dp) == 0:
        spec[0] = dp
    if kv_seq is not None and x.shape[1] % _axis_size(kv_seq) == 0:
        spec[1] = kv_seq
    if all(s is None for s in spec):
        return x
    return _constrain(x, P(*spec))


def constrain_kv_pages(x: jax.Array) -> jax.Array:
    """(P, ps, Hkv, D|1) paged-KV pool leaf (codes, fp pages or int8 per-token
    scale pages — DESIGN.md §3.8) — pin the physical page axis to the data axes
    and the kv-head axis to the model axis when they divide, mirroring
    planner.cache_shardings so the per-step page scatter keeps the pool's
    placement instead of GSPMD resharding the whole pool every decode step.
    The page table itself stays replicated (tiny, host-owned)."""
    dp = _DP_AXES.get()
    tp = _TP_AXIS.get()
    if (dp is None and tp is None) or x.ndim < 4:
        return x
    spec = [None] * x.ndim
    if dp is not None and x.shape[0] % _axis_size(dp) == 0:
        spec[0] = dp
    if tp is not None and x.shape[2] % _axis_size(tp) == 0:
        spec[2] = tp
    if all(s is None for s in spec):
        return x
    return _constrain(x, P(*spec))


def constrain_state_pages(x: jax.Array) -> jax.Array:
    """Paged SSM state pools (DESIGN.md §3.13): ``state_pages`` (P, H, Pd, N) or
    ``conv_pages`` (P, K-1, C) — pin the physical page axis to the data axes and,
    for the 4-d recurrent-state pool, the head axis to the model axis, mirroring
    planner.cache_shardings. Deliberately NOT routed through constrain_kv_pages:
    that helper pins dim 2 of any 4-d leaf (the kv-head axis of a KV pool), which
    on a state pool would land on the head-*dim* axis instead of the head axis."""
    dp = _DP_AXES.get()
    tp = _TP_AXIS.get()
    if (dp is None and tp is None) or x.ndim < 3:
        return x
    spec = [None] * x.ndim
    if dp is not None and x.shape[0] % _axis_size(dp) == 0:
        spec[0] = dp
    if tp is not None and x.ndim >= 4 and x.shape[1] % _axis_size(tp) == 0:
        spec[1] = tp
    if all(s is None for s in spec):
        return x
    return _constrain(x, P(*spec))


def constrain_vocab(logits: jax.Array) -> jax.Array:
    """(B, S, V_padded) logits — batch to dp, padded vocab to the model axis (the
    whole point of vocab_padded: logits shard over model instead of replicating)."""
    tp = _TP_AXIS.get()
    dp = _DP_AXES.get()
    if tp is None and dp is None:
        return logits
    spec = [None] * logits.ndim
    if dp is not None and logits.shape[0] % _axis_size(dp) == 0:
        spec[0] = dp
    if tp is not None and logits.shape[-1] % _axis_size(tp) == 0:
        spec[-1] = tp
    if all(s is None for s in spec):
        return logits
    return _constrain(logits, P(*spec))


def current_mesh():
    """The hinted concrete mesh, or None. Kernel wrappers thread this into their
    jitted bodies as a *static* argument: jit's trace cache does not key on
    contextvars, so reading the hint inside a traced body would silently reuse
    whichever lowering (manual-region or plain) happened to be traced first."""
    return _MESH.get()


def manual_kernel(fn, args: tuple, mesh=None):
    """Run a Pallas kernel wrapper body as a GSPMD-*manual* region (DESIGN.md
    §3.7): ``shard_map`` over ``mesh`` with fully replicated in/out specs, so
    every device computes the exact single-device result on gathered operands.

    Why not sharding constraints: off-TPU the kernels run in interpret mode — the
    "kernel" is ordinary HLO emulating the grid (fori over blocks + dynamic
    slices), and this XLA version miscompiles parts of that emulation once
    operand shardings propagate into it (observed: concatenating a model-sharded
    ``bcol`` with its block padding multiplies the values by the data-axis size —
    a partitioner bug, reproduced standalone). A manual region takes the
    partitioner out of the loop entirely. Weights stay *stored* sharded — the
    per-device HBM win — and are gathered at this boundary; partitioning the
    kernel grid itself over the mesh (Mosaic) is future work. No-op when ``mesh``
    is None.

    ``args`` may carry ``None`` leaves for optional operands — e.g. the paged
    decode kernel's int8-KV per-token scale pools (DESIGN.md §3.8), absent on
    fp pools: the per-leaf ``tree_map`` leaves them un-spec'd, so one boundary
    serves both the fp and int8-KV operand tuples (any operand relayout, like
    the scale pools' (P, ps, Hkv, 1)→(P, Hkv, ps) transpose, belongs *inside*
    ``fn`` where the partitioner cannot touch it)."""
    if mesh is None:
        return fn(*args)
    replicated = jax.tree_util.tree_map(lambda _: P(), args)
    return jax.shard_map(fn, mesh=mesh, in_specs=replicated, out_specs=P(),
                         check_vma=False)(*args)
