"""Serving engine: prefill / decode step builders + a slot-table continuous batcher.

Step functions are pure and jit/pjit-ready: the dry-run lowers exactly these. The
engine serves raw-fp params (fp or fake-quant CrossQuant activations — the
paper-faithful W8A8 evaluation path) or a prepared integer tree from
``models.quantize.quantize_tree``, executed through one of three integer backends
(``path`` — DESIGN.md §3.3):

* ``"fake"``       — fp weights, fake-quant activations (accuracy-evaluation path).
* ``"dequant-fp"`` — prepared tree, codes dequantized to f32 before an fp GEMM
                     (weight-storage savings only; the serving baseline).
* ``"fused-int8"`` — prepared tree through the Pallas ``act_quantize → qgemm``
                     kernels: true int8×int8→int32 contractions per layer
                     (Mosaic on TPU, ``interpret=True`` off-TPU so CI runs it).

``kv_cache="int8"`` additionally stores decode K/V as int8 codes + per-token scales
(models.layers.kv_quantize), cutting decode-step cache HBM traffic.

Continuous batching (DESIGN.md §3.6): ``ServeEngine`` keeps a fixed slot table of
``batch_size`` sequences with **per-slot lengths** — ``cur_len`` is a ``(B,)`` int32
vector all the way down to the attention masks and cache scatter positions. New
requests are admitted into free slots mid-decode via length-bucketed padded prefill
(a small static set of prefill shapes bounds recompilation); finished requests retire
and free their slot immediately. The decode step is a single jit'd function that
folds greedy/temperature/top-k sampling in on-device, so the host loop only moves
int32 token ids.

Paged KV cache + radix prefix reuse (DESIGN.md §3.8): ``cache_layout="paged"``
swaps the dense per-slot cache rows for a physical page pool addressed through a
page table, with a host-side ref-counted allocator and a radix index over prompt
chunks (serving/paging.py). Previously prefilled prefixes map into new requests
copy-free (CrossQuant codes+scales are deterministic, so int8 pages share
bit-exactly), partial tail pages copy-on-write, only the suffix prefills, and
LRU-unreferenced cached prefixes evict under pool pressure.
"""
from __future__ import annotations

import contextlib
import dataclasses
import warnings
from typing import Callable, List, Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np

from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.core import qlinear as ql
from repro.kernels import paged_layout
from repro.models import model as M, state as state_lib
from repro.models.layers import QuantContext
from repro.serving import drafter, paging, spans
from repro.serving.api import FinishReason
from repro.serving.config import SERVE_PATHS, EngineConfig, EngineStats
from repro.sharding import hints, planner

#: one DeprecationWarning per process for the legacy-kwarg ServeEngine surface
#: (tests reset this to assert the shim warns exactly once)
_LEGACY_KWARGS_WARNED = False


def _make_ctx(cfg: ModelConfig, quant: Optional[ql.QuantConfig],
              path: Optional[str]) -> QuantContext:
    if path not in SERVE_PATHS:
        raise ValueError(f"unknown serving path {path!r}; "
                         f"pick one of {sorted(k for k in SERVE_PATHS if k)}")
    return QuantContext(quant or cfg.quant, **SERVE_PATHS[path])


def _make_sampler(temperature: float, top_k: int):
    """On-device sampler: greedy at temperature 0, else temperature + top-k.

    Padded vocab ids carry -1e9 logits (models.model._lm_head), so they are never
    sampled on either branch."""

    def sample(logits: jax.Array, key: jax.Array) -> jax.Array:
        if temperature <= 0.0:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        scaled = logits / temperature
        if top_k and top_k > 0:
            kth = jax.lax.top_k(scaled, top_k)[0][..., -1:]
            scaled = jnp.where(scaled < kth, -jnp.inf, scaled)
        return jax.random.categorical(key, scaled, axis=-1).astype(jnp.int32)

    return sample


def make_prefill_step(cfg: ModelConfig, quant: Optional[ql.QuantConfig] = None,
                      *, path: Optional[str] = None):
    ctx = _make_ctx(cfg, quant, path)

    def prefill_step(params, batch, caches):
        """batch["tokens"] (B, S) right-padded prompts → (last-valid-position logits
        (B, 1, V), filled caches). An optional batch["lens"] (B,) int32 gives per-slot
        prompt lengths (absent → all slots are length S)."""
        S = (batch["frames"].shape[1] if "frames" in batch else batch["tokens"].shape[1])
        if cfg.is_encoder_only:
            logits, _ = M.apply(params, batch, cfg, ctx=ctx, mode="train")
            return logits[:, -1:], caches
        lens = batch.get("lens")
        cur = jnp.asarray(S, jnp.int32) if lens is None else lens
        logits, ex = M.apply(params, batch, cfg, ctx=ctx, mode="prefill",
                             caches=caches, cur_len=cur)
        return logits, ex["caches"]

    return prefill_step


def make_decode_step(cfg: ModelConfig, quant: Optional[ql.QuantConfig] = None,
                     *, path: Optional[str] = None):
    ctx = _make_ctx(cfg, quant, path)

    def decode_step(params, tokens, caches, cur_len):
        """tokens (B,1) + caches + cur_len (scalar int32 or (B,) vector of per-slot
        post-append lengths) → (logits (B,1,V), updated caches)."""
        logits, ex = M.apply(params, {"tokens": tokens}, cfg, ctx=ctx, mode="decode",
                             caches=caches, cur_len=cur_len)
        return logits, ex["caches"]

    return decode_step


# ======================================================================================
# Slot-scatter cache ops (admission into a live batch)
# ======================================================================================

def _map_batch_axis(caches: dict, fn_stacked, fn_flat) -> dict:
    """Apply per-leaf fns keyed by where the slot axis sits: scanned leaves
    (``blocks``/``shared``) are stacked (n_blocks, B, ...) — batch axis 1; hybrid
    ``tail`` leaves are unstacked (B, ...) — batch axis 0."""
    out = dict(caches)
    out["blocks"] = jax.tree_util.tree_map(fn_stacked, caches["blocks"])
    if "tail" in caches:
        out["tail"] = jax.tree_util.tree_map(fn_flat, caches["tail"])
    if "shared" in caches:
        out["shared"] = jax.tree_util.tree_map(fn_stacked, caches["shared"])
    return out


def _slot_scatter(live: dict, new: dict, slots: jax.Array) -> dict:
    """Write the (Bp, ...)-batched ``new`` cache rows into the live slot table at
    ``slots`` (Bp,) int32. Sentinel indices ≥ B (padding rows of the admission
    batch) are dropped — the live state of every other slot is untouched."""
    paired_stacked = jax.tree_util.tree_map(
        lambda l, n: l.at[:, slots].set(n, mode="drop"), live["blocks"],
        new["blocks"])
    out = dict(live)
    out["blocks"] = paired_stacked
    if "tail" in live:
        out["tail"] = jax.tree_util.tree_map(
            lambda l, n: l.at[slots].set(n, mode="drop"), live["tail"], new["tail"])
    if "shared" in live:
        out["shared"] = jax.tree_util.tree_map(
            lambda l, n: l.at[:, slots].set(n, mode="drop"), live["shared"],
            new["shared"])
    return out


def make_admit_step(cfg: ModelConfig, quant: Optional[ql.QuantConfig] = None, *,
                    path: Optional[str] = None, temperature: float = 0.0,
                    top_k: int = 0):
    """Padded prefill of newly admitted requests into a *live* slot table.

    The returned function prefills a small (Bp, S_bucket) admission batch — Bp is
    the power-of-two row bucket covering the number of admitted requests, so the
    set of prefill lowerings is the static (row bucket × length bucket) grid —
    against a *fresh zero cache* (stateful caches like the SSM recurrence can
    never leak a retired request's state), then scatters the new cache rows into
    the live slot table at the admitted slot indices. Mid-decode slots are never
    touched: a single-slot refill costs a Bp=1 prefill, not a full-batch one.
    """
    ctx = _make_ctx(cfg, quant, path)
    sample = _make_sampler(temperature, top_k)

    def admit_step(params, tokens, lens, slots, caches, key):
        """tokens (Bp, S) right-padded; lens (Bp,) int32 prompt lengths; slots
        (Bp,) int32 target slot per row (≥ B ⇒ padding row, dropped); caches =
        live slot caches. Returns (first sampled token (Bp,) int32, caches with
        the admitted slots' rows replaced)."""
        Bp = tokens.shape[0]
        # fresh zero cache with the admission batch size; dtype/layout (incl. the
        # int8 KV leaves) comes from the live cache leaves themselves
        fresh = _map_batch_axis(
            caches,
            lambda x: jnp.zeros(x.shape[:1] + (Bp,) + x.shape[2:], x.dtype),
            lambda x: jnp.zeros((Bp,) + x.shape[1:], x.dtype))
        logits, ex = M.apply(params, {"tokens": tokens}, cfg, ctx=ctx,
                             mode="prefill", caches=fresh, cur_len=lens)
        merged = _slot_scatter(caches, ex["caches"], slots)
        return sample(logits[:, -1], key), merged

    return admit_step


def make_paged_admit_step(cfg: ModelConfig, quant: Optional[ql.QuantConfig] = None,
                          *, path: Optional[str] = None, temperature: float = 0.0,
                          top_k: int = 0, warm: bool = False):
    """Admission prefill straight into the live page pool (DESIGN.md §3.8).

    Unlike the dense slot table (fresh zero cache + ``_slot_scatter``), paged
    admission writes K/V through each admitted row's page table into pages the
    allocator handed it exclusively — other slots' pages are untouched by
    construction, so no scatter-merge step is needed. ``warm=False`` traces the
    cold path: plain right-padded prefill attention, bitwise-identical to the
    dense layout. ``warm=True`` traces the shared-prefix path: the batch rows
    are prompt *suffixes*, ``prefix`` (Bp,) counts tokens already present in the
    mapped pages, and attention reads the prefix back from the pool
    (layers.paged_prefill_attention). The engine dispatches per admission batch,
    so cold batches never pay the warm lowering (or its gather).
    """
    ctx = _make_ctx(cfg, quant, path)
    sample = _make_sampler(temperature, top_k)

    def admit_step(params, tokens, lens, prefix, row_tables, row_states, caches,
                   key):
        """tokens (Bp, S) right-padded suffixes; lens (Bp,) suffix lengths;
        prefix (Bp,) shared-prefix lengths (ignored on the cold lowering);
        row_tables (Bp, maxP) per-row page tables and row_states (Bp,) per-row
        state-page ids (sentinel-filled padding rows write nowhere; each is
        consumed only when the cache carries its routing table — §3.13).
        Returns (first sampled token (Bp,), updated caches with the live
        tables restored)."""
        c = dict(caches)
        if "page_table" in c:
            c["page_table"] = row_tables
        if "state_table" in c:
            c["state_table"] = row_states
        logits, ex = M.apply(params, {"tokens": tokens}, cfg, ctx=ctx,
                             mode="prefill", caches=c, cur_len=lens,
                             prefix_len=prefix if warm else None)
        out = dict(ex["caches"])
        for table in ("page_table", "state_table"):
            if table in caches:
                out[table] = caches[table]
        return sample(logits[:, -1], key), out

    return admit_step


def _page_copy(caches: dict, src, dst, n_tok):
    """Copy-on-write of a partially shared tail page (DESIGN.md §3.8): duplicate
    the first ``n_tok`` tokens of physical page ``src`` into the freshly
    allocated ``dst`` across every layer's pools (codes and int8 scale rows
    alike); tokens ≥ n_tok stay zero, exactly as a cold prefill would leave
    them before writing the suffix."""
    def cp(block):                      # leaves (n_blocks, P, ...)
        if "k_pages" not in block:
            return block
        ps = block["k_pages"].shape[2]
        return {name: paged_layout.copy_tokens(leaf, src, dst, n_tok, ps)
                for name, leaf in block.items()}

    out = dict(caches)
    out["blocks"] = [cp(b) for b in caches["blocks"]]
    return out


def make_serve_decode_step(cfg: ModelConfig, quant: Optional[ql.QuantConfig] = None,
                           *, path: Optional[str] = None, temperature: float = 0.0,
                           top_k: int = 0):
    """One fused decode step: model forward + on-device sampling → token ids only."""
    ctx = _make_ctx(cfg, quant, path)
    sample = _make_sampler(temperature, top_k)

    def decode_step(params, tokens, caches, cur_len, key):
        """tokens (B,) int32 pending inputs; cur_len (B,) int32 post-append lengths
        → (next token (B,) int32, updated caches)."""
        logits, ex = M.apply(params, {"tokens": tokens[:, None]}, cfg, ctx=ctx,
                             mode="decode", caches=caches, cur_len=cur_len)
        return sample(logits[:, -1], key), ex["caches"]

    return decode_step


def make_serve_verify_step(cfg: ModelConfig, quant: Optional[ql.QuantConfig] = None,
                           *, path: Optional[str] = None):
    """One fused speculative verify step (DESIGN.md §3.9): score a (B, W) draft
    window — column 0 each slot's pending token, columns 1.. its drafted
    continuation — in a single forward pass and greedily argmax every window
    position on-device, so the host acceptance loop only compares int32 ids.
    Greedy-only: the engine's acceptance rule (token i accepted iff it equals
    the sample at window position i-1) is token-exact by construction only
    when sampling is deterministic."""
    ctx = _make_ctx(cfg, quant, path)

    def verify_step(params, tokens, caches, cur_len, q_len, key):
        """tokens (B, W) int32 draft windows; cur_len (B,) int32 *total*
        post-scatter lengths; q_len (B,) int32 valid window rows (1 ≤ q_len ≤
        W; shorter windows right-pad and their tail rows scatter nowhere)
        → (greedy samples (B, W) int32 — position i samples the token after
        window token i — and the updated caches)."""
        del key                                    # greedy: sampler is argmax
        logits, ex = M.apply(params, {"tokens": tokens}, cfg, ctx=ctx,
                             mode="verify", caches=caches, cur_len=cur_len,
                             q_len=q_len)
        return jnp.argmax(logits, axis=-1).astype(jnp.int32), ex["caches"]

    return verify_step


def make_chunked_step(cfg: ModelConfig, quant: Optional[ql.QuantConfig] = None,
                      *, path: Optional[str] = None, temperature: float = 0.0,
                      top_k: int = 0):
    """One fused mixed-budget step (DESIGN.md §3.10): a packed ragged token row
    — single decode tokens, draft-verify windows and page-aligned prefill
    chunks of many slots side by side — served in one ``mode="chunked"``
    forward pass. Returns per-slot sampled tokens (from each slot's last valid
    packed row) plus the per-row greedy argmax (the speculative acceptance
    stream), so the host scheduler only moves int32 ids."""
    ctx = _make_ctx(cfg, quant, path)
    sample = _make_sampler(temperature, top_k)

    def chunked_step(params, tokens, q_start, q_len, kv_len, positions,
                     slot_ids, caches, key):
        """tokens (1, Nt) packed row; q_start/q_len/kv_len (B,) per-slot chunk
        extents (q_len == 0 ⇒ slot idle this step); positions/slot_ids (Nt,)
        per-token routing (slot_ids == B ⇒ padding row, scatters nowhere)
        → (sampled next token (B,) int32, per-row argmax (Nt,) int32, caches)."""
        chunk = {"q_start": q_start, "q_len": q_len, "kv_len": kv_len,
                 "positions": positions, "slot_ids": slot_ids}
        logits, ex = M.apply(params, {"tokens": tokens}, cfg, ctx=ctx,
                             mode="chunked", caches=caches, chunk=chunk)
        last = jnp.clip(q_start + jnp.maximum(q_len, 1) - 1, 0,
                        logits.shape[1] - 1)
        tok = sample(logits[0, last], key)
        rowmax = jnp.argmax(logits[0], axis=-1).astype(jnp.int32)
        return tok, rowmax, ex["caches"]

    return chunked_step


# ======================================================================================
# Tensor-parallel sharded serving (DESIGN.md §3.7)
# ======================================================================================

def _hinted(fn, plan: "planner.Plan", mesh: Mesh):
    """Wrap a step function so it traces under the plan's sharding hints: batch /
    vocab / KV-cache constraints and the row-parallel int32-accumulator pin
    (qlinear) all read these contextvars at trace time."""

    def wrapped(*args):
        # token_groups=False: grouped MoE dispatch uses *per-group* capacity, which
        # admits a different token-drop set than the single-device global dispatch
        # whenever an expert overflows — serving's EP parity contract is bitwise vs
        # single-device (§3.13), so serving steps always trace global dispatch.
        with hints.sharding_hints(
                dp_axes=plan.dp_axes, tp_axis=plan.tp_axis, mesh=mesh,
                kv_seq_axis=plan.tp_axis if plan.seq_shard_kv else None,
                ep_axis=plan.ep_axis, token_groups=False):
            return fn(*args)

    return wrapped


def shard_serving_state(params, caches, cfg: ModelConfig, plan: "planner.Plan",
                        mesh: Mesh):
    """Planner specs for a serving step's carried state: (param shardings, cache
    shardings, replicated). Params cover raw-fp *and* prepared integer trees —
    qw/qw4 split over the model axis with their sw/bcol scale leaves following the
    same dim, qalpha replicated; caches cover fp and int8-with-per-token-scales KV
    plus SSM state (planner.cache_shardings)."""
    param_sh = planner.param_shardings(params, cfg, plan, mesh)
    cache_sh = planner.cache_shardings(caches, cfg, plan, mesh)
    return param_sh, cache_sh, NamedSharding(mesh, P())


# ======================================================================================
# Host-side continuous batcher
# ======================================================================================

@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray            # (len,) int32
    max_new: int
    out: List[int] = dataclasses.field(default_factory=list)
    done: bool = False
    finish_reason: Optional[FinishReason] = None   # set at retirement
    prefix_reused: int = 0        # §3.8 radix hit length (prompt tokens)
    tag: Optional[str] = None     # the caller's id, for span metadata


def default_buckets(max_len: int, lo: int = 8) -> List[int]:
    """Power-of-two padded-prefill lengths up to the cache size: [8, 16, ..., T]."""
    out, b = [], lo
    while b < max_len:
        out.append(b)
        b *= 2
    out.append(max_len)
    return out


class ServeEngine:
    """Continuous batcher over a fixed-size slot table (DESIGN.md §3.6).

    Mixed-length prompts are admitted into free slots via length-bucketed padded
    prefill; finished requests retire and their slot refills immediately without
    draining the rest of the batch. Decode advances all slots in lock-step with a
    per-slot ``cur_len`` vector; sampling (greedy by default, temperature/top-k
    otherwise) happens on-device inside the jit'd step.

    ``eos_id=None`` (default) disables EOS termination — token 0 is the pad token,
    so an implicit ``eos=0`` would silently truncate on any pad-token sample; pass
    the tokenizer's real EOS id explicitly.

    ``cache_layout="paged"`` (DESIGN.md §3.8) replaces the dense per-slot rows
    with a page pool + page table: a ref-counted block allocator
    (serving/paging.py) maps each sequence onto ``page_size``-token pages, a
    radix index over prompt chunks maps previously prefilled prefixes into new
    requests **copy-free** (partial tail pages copy-on-write), only the prompt
    suffix is prefilled, and retirement decrefs pages with LRU eviction of
    unreferenced cached prefixes under pool pressure. ``n_pages`` defaults to
    the dense-equivalent capacity ``batch_size · max_len / page_size``; smaller
    pools trade on sharing. Token-exact vs the dense layout on every path × KV
    mode (tests/test_paged_serving.py). ``prefix_reuse=False`` keeps the paged
    layout but always cold-prefills (the parity baseline).

    ``speculate=k`` (DESIGN.md §3.9) turns each decode step into a k-token
    verify step: a self-drafting prompt-lookup drafter (serving/drafter.py)
    proposes up to ``k`` continuation tokens per slot from n-gram matches
    against the slot's own history, the model scores the whole window in one
    multi-token kernel launch (same paged/dense attention path decode uses),
    and greedy acceptance keeps every accepted token equal to what plain
    decode would have sampled — output is **token-exact** vs ``speculate=1``
    (tests/test_speculative.py). Requires greedy sampling, the continuous
    scheduler and attention-only caches; ``stats().accept_rate`` /
    ``stats().tokens_per_step`` report what the workload's repetitiveness
    bought.

    ``cache_dtype`` sets the fp KV-cache dtype, defaulting to the params dtype
    (a bf16 model serves a bf16 cache); ``kv_cache="int8"`` is unaffected.

    ``scheduler="grouped"`` keeps the admission policy of the pre-§3.6 engine
    (equal-exact-length groups, drained to completion) as the throughput baseline
    for ``benchmarks/serving_bench.py``.

    ``mesh=`` (+ optional ``plan=``, default ``planner.make_serve_plan``) serves
    TP-sharded (DESIGN.md §3.7): params/caches are placed per the plan's
    ``NamedSharding`` pytrees and both steps are jit'd with matching in/out
    shardings. Token-exact vs single-device serving on every path × KV mode
    (tests/test_sharded_serving.py).

    SSM / hybrid families serve through the same continuous slot-table scheduler
    as attention (DESIGN.md §3.13): right-padded admission prefill masks dt to
    zero at padded positions, which makes them decay-1/update-0 no-ops on the
    recurrence (ssm.mamba_apply) — the carried state is exactly the exact-length
    state, so mamba2/zamba2 get length-bucketed admission, mid-decode
    retire+refill and donated-cache decode identically to attention families.
    Under ``cache_layout="paged"`` their per-layer state checkpoints live in
    fixed-size pools (one ``state_table``-routed page per slot, allocated from
    the same ref-counted pool as attention KV pages; a hybrid slot holds both
    kinds and retires them together). Speculation and radix prefix reuse stay
    attention-only — the recurrence can neither rewind rejected draft tokens
    nor restart from a mid-prompt page boundary (serving/config.py raises
    typed errors for those combinations).

    Expert-parallel MoE serving: a mesh with an ``"expert"`` axis shards the
    stacked ``(E, ...)`` expert trees over it (planner moe_mode
    ``"expert_axis"``) — each ep shard holds whole experts with their scale
    leaves, the router stays replicated, and the int32 expert GEMMs never
    cross shards, so fused-int8 EP serving is bitwise vs single-device.
    """

    def __init__(self, cfg: ModelConfig, params, *,
                 config: Optional[EngineConfig] = None,
                 quant: Optional[ql.QuantConfig] = None,
                 mesh: Optional[Mesh] = None,
                 plan: Optional["planner.Plan"] = None,
                 sparsity_plan=None,
                 **legacy):
        if config is not None and legacy:
            raise TypeError("pass either config= or legacy engine kwargs, "
                            f"not both (got config plus {sorted(legacy)})")
        if config is None:
            # Deprecation shim (DESIGN.md §3.11): the legacy 20-kwarg surface
            # keeps working — it builds the same validated EngineConfig, so an
            # invalid combination raises identically on both surfaces — and
            # warns once per process.
            global _LEGACY_KWARGS_WARNED
            if not _LEGACY_KWARGS_WARNED:
                warnings.warn(
                    "ServeEngine(cfg, params, **kwargs) is deprecated; pass "
                    "ServeEngine(cfg, params, config=EngineConfig(...)) "
                    "(DESIGN.md §3.11)", DeprecationWarning, stacklevel=2)
                _LEGACY_KWARGS_WARNED = True
            config = EngineConfig.from_kwargs(**legacy)
        config.check_model(cfg)   # typed rejections: spec/prefix-reuse/chunked on state
        self.config = config
        batch_size, max_len = config.batch_size, config.max_len
        path, eos_id = config.path, config.eos_id
        kv_cache, cache_layout = config.kv_cache, config.cache_layout
        page_size, n_pages = config.page_size, config.n_pages
        prefix_reuse, cache_dtype = config.prefix_reuse, config.cache_dtype
        scheduler, prefill_buckets = config.scheduler, config.prefill_buckets
        chunked, token_budget = config.chunked, config.token_budget
        speculate, drafter_ngram = config.speculate, config.drafter_ngram
        temperature, top_k = config.temperature, config.top_k
        seed = config.seed
        self.paged = cache_layout == "paged"
        self.chunked = chunked
        self.token_budget = token_budget
        self.spec = speculate
        if speculate > 1:
            self.drafter = drafter.NGramDrafter(max_ngram=drafter_ngram)
        self.sparsity_plan = sparsity_plan
        if config.sparsity != "none":
            # N:M structured sparsity at engine build (DESIGN.md §3.12): prune the
            # tree the engine will serve — prepared int8 leaves are rescaled to
            # their survivors and gain packed ``mask`` leaves the fused path's
            # sparse GEMM reads; fp trees are magnitude-pruned in place so every
            # path sees the same masked weights. A ``sparsity_plan``
            # (models.quantize.make_sparsity_plan) restricts pruning to the layers
            # whose §4.1 kernel proportion says it is safe; without one, every
            # quantizable leaf is pruned. Leaves already carrying a mask pass
            # through untouched, so pre-sparsified checkpoints serve as-is.
            from repro.models import quantize as MQ
            if sparsity_plan is None:
                self.sparsity_plan = MQ.SparsityPlan(nm=MQ.parse_nm(config.sparsity))
            params = MQ.sparsify_tree(params, self.sparsity_plan)
        self.cfg, self.params = cfg, params
        self.B, self.T = batch_size, max_len
        self.eos = eos_id
        self.kv_int8 = kv_cache == "int8"
        self.scheduler = scheduler
        # Which state kinds this family's cache carries (models/state.py §3.13):
        # has_kv → token-paged attention KV (page need grows with length);
        # has_state → fixed-size SSM checkpoints (one state page per slot).
        self.has_kv, self.has_state = state_lib.family_flags(M.block_spec(cfg))
        self.buckets = sorted(b for b in (prefill_buckets or default_buckets(max_len))
                              if b <= max_len)
        if cache_dtype is None:
            # fp KV caches follow the params dtype (a bf16 model serves a bf16
            # cache) instead of silently promoting the whole pool to f32
            flt = [leaf for leaf in jax.tree_util.tree_leaves(params)
                   if hasattr(leaf, "dtype")
                   and jnp.issubdtype(leaf.dtype, jnp.floating)]
            cache_dtype = flt[0].dtype if flt else jnp.float32
        self.cache_dtype = np.dtype(cache_dtype)
        decode = make_serve_decode_step(cfg, quant, path=path,
                                        temperature=temperature, top_k=top_k)
        verify = (make_serve_verify_step(cfg, quant, path=path)
                  if speculate > 1 else None)
        chunk_step = (make_chunked_step(cfg, quant, path=path,
                                        temperature=temperature, top_k=top_k)
                      if chunked else None)
        if self.paged:
            # Paged pool + page table (DESIGN.md §3.8): the pool defaults to the
            # dense-equivalent capacity; passing less relies on prefix sharing +
            # eviction for the capacity win the benchmark measures.
            self.ps = page_size
            self.maxP = max_len // page_size
            self.n_pages = n_pages or batch_size * self.maxP
            self.pool = paging.PagePool(self.n_pages)
            # Radix prefix reuse needs position-indexed KV pages to restart a
            # prompt mid-way; a state checkpoint cannot (check_model rejects
            # prefix_reuse on stateful families — this guard is the backstop).
            self.radix = (paging.RadixIndex(page_size)
                          if prefix_reuse and not self.has_state else None)
            if self.has_kv:
                self._table = np.full((batch_size, self.maxP), self.n_pages,
                                      np.int32)
            if self.has_state:
                self._state_table = np.full((batch_size,), self.n_pages,
                                            np.int32)
            self._state_pages_held = 0
            self._table_dirty = False
            self._seq_pages: List[List[int]] = [[] for _ in range(batch_size)]
            self.caches = M.init_cache(cfg, batch_size, max_len,
                                       dtype=self.cache_dtype,
                                       kv_int8=self.kv_int8, layout="paged",
                                       page_size=page_size, n_pages=self.n_pages)
            admit_cold = make_paged_admit_step(cfg, quant, path=path,
                                               temperature=temperature,
                                               top_k=top_k, warm=False)
            admit_warm = make_paged_admit_step(cfg, quant, path=path,
                                               temperature=temperature,
                                               top_k=top_k, warm=True)
        else:
            self.caches = M.init_cache(cfg, batch_size, max_len,
                                       dtype=self.cache_dtype,
                                       kv_int8=self.kv_int8)
            admit = make_admit_step(cfg, quant, path=path, temperature=temperature,
                                    top_k=top_k)
        self.mesh = mesh
        self.plan = None
        # Every step donates its ``caches`` argument: the engine owns exactly one
        # live cache pytree (each call's output replaces ``self.caches``), so XLA
        # scatters the decode-step KV append — and the int8-KV scale append —
        # into the existing buffers instead of copying the whole multi-GiB cache
        # per token. Without donation the per-step full-cache copy dominated the
        # slot-table decode and inverted continuous-vs-grouped throughput on the
        # 4-leaf int8-KV cache (EXPERIMENTS.md §Perf).
        if mesh is None:
            self._decode_step = jax.jit(decode, donate_argnums=2)
            if verify is not None:
                self._verify_step = jax.jit(verify, donate_argnums=2)
            if chunk_step is not None:
                self._chunk_step = jax.jit(chunk_step, donate_argnums=7)
            if self.paged:
                self._admit_cold = jax.jit(admit_cold, donate_argnums=6)
                self._admit_warm = jax.jit(admit_warm, donate_argnums=6)
                self._copy_step = jax.jit(_page_copy, donate_argnums=0)
            else:
                self._admit_step = jax.jit(admit, donate_argnums=4)
        else:
            # TP-sharded serving (DESIGN.md §3.7): place the prepared integer tree
            # (weights + scale leaves), the slot-table caches (incl. int8-KV
            # per-token scales — and on the paged layout the page pools + their
            # replicated page table) and jit the steps with NamedSharding-
            # constrained in/out shardings so GSPMD partitions prefill/decode.
            # Host tokens, lens, slots, cur_len and the PRNG key stay replicated.
            # Cache in/out shardings match, so the carried state never
            # reshard-pingpongs.
            self.plan = plan or planner.make_serve_plan(cfg, mesh)
            param_sh, cache_sh, repl = shard_serving_state(
                params, self.caches, cfg, self.plan, mesh)
            self._repl_sh = repl
            self.params = jax.device_put(params, param_sh)
            self.caches = jax.device_put(self.caches, cache_sh)
            self._decode_step = jax.jit(
                _hinted(decode, self.plan, mesh),
                in_shardings=(param_sh, repl, cache_sh, repl, repl),
                out_shardings=(repl, cache_sh), donate_argnums=2)
            if verify is not None:
                # draft-window tokens/q_len stay replicated like decode tokens;
                # the window axis follows the batch through the same cache specs
                self._verify_step = jax.jit(
                    _hinted(verify, self.plan, mesh),
                    in_shardings=(param_sh, repl, cache_sh, repl, repl, repl),
                    out_shardings=(repl, cache_sh), donate_argnums=2)
            if chunk_step is not None:
                # packed row + chunk extents stay replicated like decode
                # tokens; the ragged kernel runs as one GSPMD-manual region
                self._chunk_step = jax.jit(
                    _hinted(chunk_step, self.plan, mesh),
                    in_shardings=(param_sh,) + (repl,) * 6 + (cache_sh, repl),
                    out_shardings=(repl, repl, cache_sh), donate_argnums=7)
            if self.paged:
                admit_sh = dict(in_shardings=(param_sh, repl, repl, repl, repl,
                                              repl, cache_sh, repl),
                                out_shardings=(repl, cache_sh))
                self._admit_cold = jax.jit(_hinted(admit_cold, self.plan, mesh),
                                           donate_argnums=6, **admit_sh)
                self._admit_warm = jax.jit(_hinted(admit_warm, self.plan, mesh),
                                           donate_argnums=6, **admit_sh)
                self._copy_step = jax.jit(
                    _page_copy, in_shardings=(cache_sh, repl, repl, repl),
                    out_shardings=cache_sh, donate_argnums=0)
            else:
                self._admit_step = jax.jit(
                    _hinted(admit, self.plan, mesh),
                    in_shardings=(param_sh, repl, repl, repl, cache_sh, repl),
                    out_shardings=(repl, cache_sh), donate_argnums=4)
        self.queue: List[Request] = []
        self._slots: List[Optional[Request]] = [None] * batch_size
        self._pos = np.zeros(batch_size, np.int32)       # tokens in cache per slot
        self._pending = np.zeros(batch_size, np.int32)   # next input token per slot
        # chunked prefill progress (DESIGN.md §3.10): while a slot is
        # mid-prefill, _prefill_target holds its prompt length (0 ⇒ generating)
        # and _prefill_off the tokens already in its pages (radix prefix +
        # scattered chunks)
        self._prefill_off = np.zeros(batch_size, np.int32)
        self._prefill_target = np.zeros(batch_size, np.int32)
        self._key = jax.random.PRNGKey(seed)
        self._greedy = temperature <= 0.0
        self._step = 0
        self._n_steps = 0
        self._next_rid = 0
        #: optional per-token hook, called as ``on_token(request, token)``
        #: after every emitted token (the request is already retired when
        #: ``request.done``) — the async server streams through this
        self.on_token: Optional[Callable[[Request, int], None]] = None
        self.counters = {
            "prefill_calls": 0, "decode_steps": 0,
            "active_slot_steps": 0, "mid_decode_admissions": 0,
            # paged layout (DESIGN.md §3.8); zero on dense engines
            "prefix_hits": 0, "prefix_tokens_reused": 0,
            "prompt_tokens": 0, "prefill_tokens": 0,
            "cow_copies": 0, "pages_evicted": 0,
            "peak_pages_in_use": 0,
            # state-pool occupancy split (DESIGN.md §3.13): how many pool pages
            # currently hold attention KV tokens vs fixed-size SSM state
            # checkpoints, plus their peaks; zero on dense engines
            "kv_pages_in_use": 0, "state_pages_in_use": 0,
            "peak_kv_pages_in_use": 0, "peak_state_pages_in_use": 0,
            # speculative decoding (DESIGN.md §3.9); zero if spec==1
            "spec_steps": 0, "spec_slot_steps": 0, "spec_drafted": 0,
            "spec_accepted": 0, "spec_emitted": 0,
            # chunked serving (DESIGN.md §3.10); zero if chunked=False
            "chunk_steps": 0, "chunk_prefill_rows": 0,
            "chunk_decode_rows": 0,
            # host time, perf_counter nanoseconds: inside step(), blocked on
            # sampled tokens (engine.sync), inside radix eviction
            "step_ns": 0, "sync_ns": 0, "evict_ns": 0}

    # ---------------------------------------------------------------- submission

    def submit(self, prompts: List[np.ndarray],
               max_new: Union[int, Sequence[int]] = 16) -> List[Request]:
        if isinstance(max_new, int):
            max_new = [max_new] * len(prompts)
        reqs = []
        for p, mn in zip(prompts, max_new):
            p = np.asarray(p, np.int32)
            if not 0 < len(p) <= self.T:
                raise ValueError(f"prompt length {len(p)} not in (0, {self.T}]")
            reqs.append(Request(self._next_rid, p, mn))
            self._next_rid += 1
        self.queue.extend(reqs)
        return reqs

    # ---------------------------------------------------------------- scheduling

    def _bucket(self, plen: int) -> int:
        for b in self.buckets:
            if b >= plen:
                return b
        return self.T

    def stats(self) -> EngineStats:
        """Statistics snapshot (DESIGN.md §3.11): slot occupancy, prefix hit
        rate, draft acceptance and tokens per speculative step, derived from a
        copy of the raw counters, with a stable ``to_dict()`` schema shared by
        ``benchmarks/serving_bench.py`` and the async server's metrics
        endpoint."""
        return EngineStats.from_counters(self.counters, self.B)

    def _read(self, *xs):
        """Blocking device-to-host read of a step's sampled tokens."""
        with spans.span("engine.sync", self.counters, "sync_ns"):
            out = [np.asarray(x) for x in xs]
        return out[0] if len(out) == 1 else out

    @contextlib.contextmanager
    def _admit_span(self):
        """``engine.admit`` around one admission phase; ``rids`` lists the
        requests it took off the queue (their ``tag``, else their rid)."""
        with spans.span("engine.admit") as sp:
            queued = list(self.queue) if sp.is_enabled() else None
            yield
            if queued is not None:
                left = {id(r) for r in self.queue}
                sp.set_metadata(rids=" ".join(
                    str(r.tag or r.rid) for r in queued if id(r) not in left))

    def _next_key(self) -> jax.Array:
        if self._greedy:            # sampler ignores the key: skip the fold_in op
            return self._key
        key = jax.random.fold_in(self._key, self._step)
        self._step += 1
        return key

    def _emit(self, slot: int, tok: int, finished: List[Request]) -> None:
        """Record one sampled token for a slot; retire the request when done.

        Capacity headroom: a prompt of length ``max_len`` fills its cache row at
        admission, so it is admitted-and-retired immediately with the single
        token its prefill logits produced — the decode step never scatters past
        the cache (the ``_pos >= T`` retire fires before any decode for that
        slot; pinned by tests/test_paged_serving.py)."""
        r = self._slots[slot]
        r.out.append(tok)
        if self.eos is not None and tok == self.eos:
            reason = FinishReason.EOS
        elif len(r.out) >= r.max_new:
            reason = FinishReason.LENGTH
        elif self._pos[slot] >= self.T:            # cache full: no room to append
            reason = FinishReason.CACHE_FULL
        else:
            reason = None
        if reason is not None:
            r.done = True
            r.finish_reason = reason
            finished.append(r)
            self._slots[slot] = None
            self._pos[slot] = 0
            self._pending[slot] = 0
            self._prefill_off[slot] = 0
            self._prefill_target[slot] = 0
            if self.paged:
                # drop this sequence's page references; pages retained by the
                # radix index as cached prefixes survive (theirs is a separate
                # reference), everything else returns to the free list
                self.pool.decref(self._seq_pages[slot])
                self._seq_pages[slot] = []
                if self.has_kv:
                    self._table[slot, :] = self.n_pages
                if self.has_state:
                    # sentinel the state route too: the freed checkpoint page
                    # may be handed to the next admission, whose prefill starts
                    # from a zero init_state rather than reading it (§3.13)
                    self._state_table[slot] = self.n_pages
                    self._state_pages_held -= 1
                self._table_dirty = True
                self._note_pool()
        else:
            self._pending[slot] = tok
        if self.on_token is not None:
            self.on_token(r, tok)

    # ------------------------------------------------------------ paged planning

    def _match_prefix(self, prompt: np.ndarray):
        """Radix walk + the prefix-usability caps shared by planning and
        bucketing: a request keeps ≥ 1 suffix token (the first sampled token
        comes from the suffix prefill logits), so the usable full-page match is
        clamped to ``(plen-1)//ps`` pages — and a clamped match invalidates the
        partial tail hit (it hangs off the *unclamped* depth). Returns
        ``(shared_pages, matched_tokens, cow_src_page_or_None, j)``."""
        plen, ps = len(prompt), self.ps
        if self.radix is None:
            return [], 0, None, 0
        pages, _, partial = self.radix.match(prompt)
        n_full = min(len(pages), (plen - 1) // ps)
        if n_full < len(pages):                # truncated ⇒ tail hit is invalid
            partial = None
        j = min(partial.length, plen - 1 - n_full * ps) if partial else 0
        return (pages[:n_full], n_full * ps,
                partial.page if j > 0 else None, j)

    def _plan_paged(self, r: Request) -> Optional[dict]:
        """Page plan for one request: walk the radix index for a shared prefix,
        then reserve this sequence's worst-case page count (prompt + decode
        budget, capped at the cache length — so decode never allocates, and an
        admission either owns every page it will ever touch or stays queued).
        Evicts LRU cached prefixes under pool pressure; returns None when the
        pool cannot cover the request even after eviction.

        Reference order matters: the shared pages (and the COW source page) are
        incref'd *before* evict/alloc — a matched prefix held only by the index
        has refs == 1 and would otherwise be evicted under pressure and handed
        straight back as a writable own page of the very plan that matched it.
        """
        plen, ps = len(r.prompt), self.ps
        shared, matched, cow_src, j = self._match_prefix(r.prompt)
        self.pool.incref(shared)
        if cow_src is not None:                # pin the COW source over evict
            self.pool.incref([cow_src])
        prefix = matched + j
        # worst-case cache footprint: the prompt plus every *appended* decode
        # token — the final sampled token retires the request without ever
        # being scattered (see _emit), so the budget contributes max_new - 1.
        # Token-paged KV need grows with length; a state checkpoint (§3.13) is
        # one extra fixed-size page regardless of length.
        need = (-(-min(plen + max(r.max_new - 1, 0), self.T) // ps)
                if self.has_kv else 0)
        own_n = need - len(shared) + (1 if self.has_state else 0)
        own = self.pool.alloc(own_n)
        if own is None and self.radix is not None:
            with spans.span("radix.evict", self.counters, "evict_ns",
                            want=own_n) as sp:
                freed = self.radix.evict(self.pool, own_n)
                sp.set_metadata(freed=freed)
            self.counters["pages_evicted"] += freed
            own = self.pool.alloc(own_n)
        if cow_src is not None:                # copy is issued before any write
            self.pool.decref([cow_src])
        if own is None:
            self.pool.decref(shared)
            return None
        cow = (cow_src, own[0], j) if cow_src is not None else None
        state_page = own[-1] if self.has_state else None
        kv_own = own[:-1] if self.has_state else own
        return {"prefix": prefix, "suffix": plen - prefix,
                "pages": shared + kv_own, "n_shared": len(shared), "cow": cow,
                "state_page": state_page}

    def _suffix_estimate(self, r: Request) -> int:
        """Prefill-window estimate for bucketing (continuous, paged): prompt
        minus the currently cached shared prefix (same capping rules as
        ``_plan_paged`` via ``_match_prefix``). Commit-time replanning may
        shrink the suffix further (new prefixes inserted this round) — still
        fits the bucket; growth (eviction raced the estimate) defers the
        request to the next admission round."""
        if not self.paged:
            return len(r.prompt)
        _, matched, _, j = self._match_prefix(r.prompt)
        return len(r.prompt) - matched - j

    def _admit_paged_batch(self, batch: List[Request], bucket: int,
                           free: List[int], finished: List[Request]) -> int:
        """Admit up to ``len(free)`` paged requests in one suffix-prefill call.
        Returns the number admitted; the rest rejoin the queue head."""
        plans, deferred = [], []
        for r in batch:
            plan = self._plan_paged(r)
            if plan is None or plan["suffix"] > bucket:
                if plan is not None:       # un-reserve: replanned next round
                    self.pool.decref(plan["pages"])
                deferred.append(r)
            else:
                plans.append((r, plan))
        if deferred:
            self.queue = deferred + self.queue
        if not plans:
            return 0

        rows = 1 << (len(plans) - 1).bit_length() if len(plans) > 1 else 1
        tokens = np.zeros((rows, bucket), np.int32)
        lens = np.ones(rows, np.int32)
        prefixes = np.zeros(rows, np.int32)
        row_tables = np.full((rows, self.maxP), self.n_pages, np.int32)
        row_states = np.full(rows, self.n_pages, np.int32)
        mid_decode = any(s is not None for s in self._slots)
        warm = False
        for j, (slot, (r, plan)) in enumerate(zip(free, plans)):
            suffix = r.prompt[plan["prefix"]:]
            tokens[j, : len(suffix)] = suffix
            lens[j] = len(suffix)
            prefixes[j] = plan["prefix"]
            row_tables[j, : len(plan["pages"])] = plan["pages"]
            if plan["cow"] is not None:
                src, dst, ncopy = plan["cow"]
                with spans.span("engine.launch"):
                    self.caches = self._copy_step(
                        self.caches, jnp.asarray(src, jnp.int32),
                        jnp.asarray(dst, jnp.int32), jnp.asarray(ncopy, jnp.int32))
                self.counters["cow_copies"] += 1
            self._slots[slot] = r
            # the slot's reference list covers both page kinds: retirement
            # decrefs KV pages and the state checkpoint page together (§3.13)
            self._seq_pages[slot] = plan["pages"] + (
                [plan["state_page"]] if self.has_state else [])
            if self.has_kv:
                self._table[slot, :] = self.n_pages
                self._table[slot, : len(plan["pages"])] = plan["pages"]
            if self.has_state:
                row_states[j] = plan["state_page"]
                self._state_table[slot] = plan["state_page"]
                self._state_pages_held += 1
            warm = warm or plan["prefix"] > 0
            r.prefix_reused = plan["prefix"]
            self.counters["prompt_tokens"] += len(r.prompt)
            self.counters["prefill_tokens"] += plan["suffix"]
            self.counters["prefix_tokens_reused"] += plan["prefix"]
            self.counters["prefix_hits"] += 1 if plan["prefix"] > 0 else 0
        self._table_dirty = True
        step = self._admit_warm if warm else self._admit_cold
        with spans.span("engine.launch"):
            tok, self.caches = step(
                self.params, jnp.asarray(tokens), jnp.asarray(lens),
                jnp.asarray(prefixes), jnp.asarray(row_tables),
                jnp.asarray(row_states), self.caches, self._next_key())
        tok = self._read(tok)
        self.counters["prefill_calls"] += 1
        if mid_decode:
            self.counters["mid_decode_admissions"] += 1
        self._note_pool()
        for j, (slot, (r, plan)) in enumerate(zip(free, plans)):
            if self.radix is not None:
                # register the full prompt pages as a cached prefix (content is
                # on-device once the admit step above retires)
                self.radix.insert(r.prompt,
                                  plan["pages"][: len(r.prompt) // self.ps],
                                  self.pool)
            self._pos[slot] = len(r.prompt)
            self._emit(slot, int(tok[j]), finished)
        return len(plans)

    def _admit_dense_batch(self, batch: List[Request], bucket: int,
                           free: List[int], finished: List[Request]) -> int:
        # admission batch: rows padded to a power-of-two bucket so the set of
        # prefill lowerings is the static (row bucket × length bucket) grid;
        # sentinel slot index B marks padding rows (dropped by the scatter)
        rows = 1 << (len(batch) - 1).bit_length() if len(batch) > 1 else 1
        tokens = np.zeros((rows, bucket), np.int32)
        lens = np.ones(rows, np.int32)
        slot_ids = np.full(rows, self.B, np.int32)
        mid_decode = any(s is not None for s in self._slots)
        for j, (slot, r) in enumerate(zip(free, batch)):
            tokens[j, : len(r.prompt)] = r.prompt
            lens[j] = len(r.prompt)
            slot_ids[j] = slot
            self._slots[slot] = r
            self.counters["prompt_tokens"] += len(r.prompt)
            self.counters["prefill_tokens"] += len(r.prompt)
        with spans.span("engine.launch"):
            tok, self.caches = self._admit_step(
                self.params, jnp.asarray(tokens), jnp.asarray(lens),
                jnp.asarray(slot_ids), self.caches, self._next_key())
        tok = self._read(tok)
        self.counters["prefill_calls"] += 1
        if mid_decode:
            self.counters["mid_decode_admissions"] += 1
        for j, (slot, r) in enumerate(zip(free, batch)):
            self._pos[slot] = len(r.prompt)
            self._emit(slot, int(tok[j]), finished)
        return len(batch)

    def _admit(self, finished: List[Request]) -> None:
        if not self.queue or all(s is not None for s in self._slots):
            return
        with self._admit_span():
            while self.queue:
                free = [i for i, s in enumerate(self._slots) if s is None]
                if not free:
                    return
                if self.scheduler == "grouped":
                    # Legacy batcher: whole-batch groups of one exact length, drained to
                    # completion before the next group starts.
                    if len(free) < self.B:
                        return
                    bucket = len(self.queue[0].prompt)
                    batch, rest = [], []
                    for r in self.queue:
                        (batch if len(batch) < len(free)
                         and len(r.prompt) == bucket else rest).append(r)
                    self.queue = rest
                    self._admit_dense_batch(batch, bucket, free, finished)
                    return
                # Continuous: pick the *largest admittable same-bucket group* over
                # the whole queue, not queue[0]'s bucket — one odd-length
                # head-of-line request must not split the majority bucket behind it
                # into extra (smaller) prefill calls. Ties go to the bucket whose
                # first request arrived earliest (FIFO fairness); the loop keeps
                # admitting remaining buckets while slots stay free.
                groups: dict = {}
                first: dict = {}
                for i, r in enumerate(self.queue):
                    b = self._bucket(self._suffix_estimate(r))
                    groups.setdefault(b, []).append(r)
                    first.setdefault(b, i)
                bucket = max(groups,
                             key=lambda b: (min(len(groups[b]), len(free)), -first[b]))
                batch = groups[bucket][: len(free)]
                taken = {id(r) for r in batch}
                self.queue = [r for r in self.queue if id(r) not in taken]
                if self.paged:
                    admitted = self._admit_paged_batch(batch, bucket, free, finished)
                else:
                    admitted = self._admit_dense_batch(batch, bucket, free, finished)
                if admitted == 0:
                    return                     # pool exhausted: wait for retirements

    # ---------------------------------------------------------------- main loop

    def _push_table(self) -> None:
        """Sync the host routing tables to the device cache pytree. Retired
        slots' rows are sentinel-cleared *before* the next decode step: a free
        slot still decodes (lock-step shapes) and its garbage token must
        scatter nowhere — a stale table row would corrupt a page the allocator
        may have already handed to another sequence or the prefix index. The
        same applies to the (B,) state table of checkpoint-paged families
        (§3.13); a hybrid engine pushes both."""
        with spans.span("engine.push_table"):
            out = dict(self.caches)
            if self.has_kv:
                table = jnp.asarray(self._table)
                if self.mesh is not None:
                    table = jax.device_put(table, self._repl_sh)
                out["page_table"] = table
            if self.has_state:
                stable = jnp.asarray(self._state_table)
                if self.mesh is not None:
                    stable = jax.device_put(stable, self._repl_sh)
                out["state_table"] = stable
        self.caches = out
        self._table_dirty = False

    def _note_pool(self) -> None:
        """Refresh the §3.13 pool-occupancy counters after any alloc/decref:
        the one ref-counted pool backs both page kinds, so KV occupancy is
        whatever the engine's own state checkpoints don't account for (radix-
        held cached prefixes count as KV — they are token pages)."""
        held = self._state_pages_held
        kv = self.pool.used_count - held
        c = self.counters
        c["state_pages_in_use"] = held
        c["kv_pages_in_use"] = kv
        c["peak_state_pages_in_use"] = max(c["peak_state_pages_in_use"], held)
        c["peak_kv_pages_in_use"] = max(c["peak_kv_pages_in_use"], kv)
        c["peak_pages_in_use"] = max(c["peak_pages_in_use"],
                                     self.pool.used_count)

    def _spec_step(self, active: List[int], finished: List[Request]) -> None:
        """One speculative verify step (DESIGN.md §3.9): draft ≤ spec-1 tokens
        per active slot from its own prompt+output history, score the whole
        window in one fused verify pass, then greedily accept the longest
        prefix whose draft tokens match the model's own samples. Rejection
        falls back to the verified sample, so the emitted stream is token-exact
        vs non-speculative decode; every accepted token advances ``_pos``
        exactly as a plain decode step would, and a request retiring mid-window
        (EOS / max_new / full cache) discards the rest of its window with its
        page mappings torn down before any later step could touch them."""
        W = self.spec
        toks = np.zeros((self.B, W), np.int32)
        toks[:, 0] = self._pending
        wl = np.ones(self.B, np.int32)
        for i in active:
            r = self._slots[i]
            # window budget: room left in the cache row (the pending token
            # scatters at _pos) and tokens left to emit before max_new retires
            n_d = min(W - 1, self.T - self._pos[i] - 1,
                      r.max_new - len(r.out) - 1)
            if n_d > 0:
                hist = np.concatenate([r.prompt,
                                       np.asarray(r.out, np.int32)])
                d = self.drafter.draft(hist, n_d)
                wl[i] = 1 + len(d)
                toks[i, 1:1 + len(d)] = d
        with spans.span("engine.launch"):
            cur = jnp.asarray(self._pos + wl, jnp.int32)   # post-scatter totals
            out, self.caches = self._verify_step(
                self.params, jnp.asarray(toks), self.caches, cur,
                jnp.asarray(wl), self._next_key())
        out = self._read(out)                          # (B, W) greedy samples
        self.counters["decode_steps"] += 1
        self.counters["spec_steps"] += 1
        self.counters["spec_slot_steps"] += len(active)
        self.counters["active_slot_steps"] += len(active)
        with spans.span("engine.emit"):
            for i in active:
                n = 1                                      # pending always lands
                while n < wl[i] and toks[i, n] == out[i, n - 1]:
                    n += 1
                self.counters["spec_drafted"] += int(wl[i]) - 1
                self.counters["spec_accepted"] += n - 1
                r = self._slots[i]
                for j in range(n):
                    # advance per emitted token: retire conditions (max_new,
                    # EOS, cache-full) must fire at exactly the same token as
                    # a sequential non-speculative decode would
                    self._pos[i] += 1
                    self._emit(i, int(out[i, j]), finished)
                    self.counters["spec_emitted"] += 1
                    if self._slots[i] is not r:
                        # retired mid-window: the unemitted tail (and the
                        # rejected scattered tokens) must be unreachable — the
                        # retire path has to sentinel the slot's table row and
                        # drop its page refs before any later scatter/attend
                        if self.paged:
                            assert (not self._seq_pages[i]
                                    and (self._table[i] == self.n_pages).all()), \
                                "mid-window retirement left stale page mappings"
                        break

    # ------------------------------------------------------------ chunked mode

    def _admit_chunked(self, finished: List[Request]) -> None:
        """FIFO admission into free slots (DESIGN.md §3.10): page planning,
        COW and radix matching are exactly ``_admit_paged_batch``'s, but no
        prefill step runs — the admitted slot enters the *mid-prefill* state
        and its prompt is served chunk-by-chunk out of each step's leftover
        token budget. Radix insertion waits for the final chunk (pages carry
        content only once scattered)."""
        if not self.queue or all(s is not None for s in self._slots):
            return
        with self._admit_span():
            while self.queue:
                free = [i for i, s in enumerate(self._slots) if s is None]
                if not free:
                    return
                r = self.queue[0]
                plan = self._plan_paged(r)
                if plan is None:
                    return                 # pool pressure: wait for retirements
                self.queue.pop(0)
                slot = free[0]
                if plan["cow"] is not None:
                    src, dst, ncopy = plan["cow"]
                    with spans.span("engine.launch"):
                        self.caches = self._copy_step(
                            self.caches, jnp.asarray(src, jnp.int32),
                            jnp.asarray(dst, jnp.int32),
                            jnp.asarray(ncopy, jnp.int32))
                    self.counters["cow_copies"] += 1
                self._slots[slot] = r
                self._seq_pages[slot] = plan["pages"]
                self._table[slot, :] = self.n_pages
                self._table[slot, : len(plan["pages"])] = plan["pages"]
                self._table_dirty = True
                self._prefill_off[slot] = plan["prefix"]
                self._prefill_target[slot] = len(r.prompt)
                r.prefix_reused = plan["prefix"]
                self.counters["prompt_tokens"] += len(r.prompt)
                self.counters["prefill_tokens"] += plan["suffix"]
                self.counters["prefix_tokens_reused"] += plan["prefix"]
                self.counters["prefix_hits"] += 1 if plan["prefix"] > 0 else 0
                self._note_pool()

    def _chunked_step(self, finished: List[Request]) -> None:
        """One mixed-budget engine step (DESIGN.md §3.10): admit, pack decode
        rows (draft windows under ``speculate``) for every generating slot
        first, fill the remaining token budget with prefill chunks (page-
        aligned ends where possible — chunks may *start* mid-page after a
        partial radix hit), launch once, then emit/advance on the host."""
        self._admit_chunked(finished)
        gen = [i for i, s in enumerate(self._slots)
               if s is not None and self._prefill_target[i] == 0]
        pre = [i for i, s in enumerate(self._slots)
               if s is not None and self._prefill_target[i] > 0]
        if not gen and not pre:
            if self.queue:
                # nothing in flight yet the queue head could not be admitted —
                # no retirement will ever free enough pages
                raise RuntimeError(
                    f"page pool too small: {self.n_pages} pages of "
                    f"{self.ps} cannot hold request {self.queue[0].rid} "
                    f"(prompt {len(self.queue[0].prompt)} + budget "
                    f"{self.queue[0].max_new})")
            return
        if self._table_dirty:
            self._push_table()
        if not pre and self.spec == 1 and not self.kv_int8:
            # Pure-decode step: every resident slot is generating, so the
            # packed ragged launch would score token_budget padded rows where
            # the decode kernel scores B. Dispatch the lean decode launch —
            # for an fp KV cache its q_len == 1 numerics are exactly the
            # ragged kernel's decode rows (tests/test_chunked_prefill.py
            # parity), so emitted tokens do not depend on which branch served
            # the step. int8 KV stays on the ragged launch: the two kernels'
            # dequant/accumulation orders differ within tolerance, and on a
            # chunk-quantized pool that is enough to flip an argmax tie.
            # Speculative chunked serving also keeps the ragged launch: draft
            # windows need the per-row causal mask every step.
            with spans.span("engine.launch"):
                cur = jnp.asarray(self._pos + 1, jnp.int32)
                tok, self.caches = self._decode_step(
                    self.params, jnp.asarray(self._pending), self.caches, cur,
                    self._next_key())
            tok = self._read(tok)
            self._pos[gen] += 1
            self.counters["decode_steps"] += 1
            self.counters["active_slot_steps"] += len(gen)
            with spans.span("engine.emit"):
                for i in gen:
                    self._emit(i, int(tok[i]), finished)
            return
        Nt = self.token_budget
        toks = np.zeros(Nt, np.int32)
        positions = np.zeros(Nt, np.int32)
        slot_ids = np.full(Nt, self.B, np.int32)
        q_start = np.zeros(self.B, np.int32)
        q_len = np.zeros(self.B, np.int32)
        kv_len = np.zeros(self.B, np.int32)
        wl = np.ones(self.B, np.int32)
        off = 0
        # ---- decode rows first (token_budget >= B*spec: they always fit)
        for i in gen:
            r = self._slots[i]
            window = [int(self._pending[i])]
            if self.spec > 1:
                n_d = min(self.spec - 1, self.T - self._pos[i] - 1,
                          r.max_new - len(r.out) - 1)
                if n_d > 0:
                    hist = np.concatenate([r.prompt,
                                           np.asarray(r.out, np.int32)])
                    window += list(self.drafter.draft(hist, n_d))
            W = len(window)
            toks[off: off + W] = window
            positions[off: off + W] = self._pos[i] + np.arange(W)
            slot_ids[off: off + W] = i
            q_start[i], q_len[i], kv_len[i] = off, W, self._pos[i] + W
            wl[i] = W
            off += W
        # ---- leftover budget → prefill chunks (FIFO over mid-prefill slots)
        for i in pre:
            room = Nt - off
            if room <= 0:
                break
            start = int(self._prefill_off[i])
            plen = int(self._prefill_target[i])
            end = min(plen, start + room)
            if end < plen:
                # prefer a page-aligned chunk end; fall back to the raw budget
                # cut when a whole page doesn't fit (progress must never stall)
                aligned = (end // self.ps) * self.ps
                if aligned > start:
                    end = aligned
            toks[off: off + end - start] = self._slots[i].prompt[start:end]
            positions[off: off + end - start] = np.arange(start, end)
            slot_ids[off: off + end - start] = i
            q_start[i], q_len[i], kv_len[i] = off, end - start, end
            off += end - start
        with spans.span("engine.launch"):
            tok, rowmax, self.caches = self._chunk_step(
                self.params, jnp.asarray(toks[None]), jnp.asarray(q_start),
                jnp.asarray(q_len), jnp.asarray(kv_len), jnp.asarray(positions),
                jnp.asarray(slot_ids), self.caches, self._next_key())
        tok, rowmax = self._read(tok, rowmax)
        self.counters["chunk_steps"] += 1
        self.counters["chunk_decode_rows"] += int(sum(wl[i] for i in gen))
        if gen:
            self.counters["decode_steps"] += 1
            self.counters["active_slot_steps"] += len(gen)
        served_pre = [i for i in pre if q_len[i] > 0]
        if served_pre:
            self.counters["prefill_calls"] += 1
            self.counters["chunk_prefill_rows"] += int(
                sum(q_len[i] for i in served_pre))
            if gen:
                self.counters["mid_decode_admissions"] += 1
        # ---- generating slots: emit (speculative acceptance under spec > 1)
        if self.spec > 1 and gen:
            self.counters["spec_steps"] += 1
            self.counters["spec_slot_steps"] += len(gen)
        with spans.span("engine.emit"):
            for i in gen:
                if self.spec > 1:
                    r = self._slots[i]
                    out_w = rowmax[q_start[i]: q_start[i] + wl[i]]
                    n = 1                                  # pending always lands
                    while n < wl[i] and toks[q_start[i] + n] == out_w[n - 1]:
                        n += 1
                    self.counters["spec_drafted"] += int(wl[i]) - 1
                    self.counters["spec_accepted"] += n - 1
                    for j in range(n):
                        self._pos[i] += 1
                        self._emit(i, int(out_w[j]), finished)
                        self.counters["spec_emitted"] += 1
                        if self._slots[i] is not r:
                            assert (not self._seq_pages[i]
                                    and (self._table[i] == self.n_pages).all()), \
                                "mid-window retirement left stale page mappings"
                            break
                else:
                    self._pos[i] += 1
                    self._emit(i, int(tok[i]), finished)
            # ---- mid-prefill slots: advance; final chunk emits the first token
            for i in served_pre:
                end = int(kv_len[i])
                self._prefill_off[i] = end
                if end == self._prefill_target[i]:
                    r = self._slots[i]
                    self._prefill_target[i] = 0
                    self._pos[i] = len(r.prompt)
                    if self.radix is not None:
                        # the full prompt is on device now: register its pages as
                        # a cached prefix (same point the admit step does it)
                        self.radix.insert(r.prompt,
                                          self._seq_pages[i][: len(r.prompt)
                                                             // self.ps],
                                          self.pool)
                    self._emit(i, int(tok[i]), finished)

    def step(self, finished: List[Request]) -> bool:
        """One engine iteration: admissions plus at most one model launch.
        Appends retired requests to ``finished``; returns False once the
        engine is idle (empty queue, no slots in flight). Exposed so callers
        — the latency benchmark drives this directly — can time individual
        steps and inject mid-run traffic between them. Spanned as
        ``engine.step`` and timed into ``counters["step_ns"]``."""
        if not (self.queue or any(s is not None for s in self._slots)):
            return False
        self._n_steps += 1
        with spans.span("engine.step", self.counters, "step_ns",
                        step_num=self._n_steps):
            if self.chunked:
                self._chunked_step(finished)
            else:
                self._step_once(finished)
        return True

    def _step_once(self, finished: List[Request]) -> None:
        self._admit(finished)
        active = [i for i, s in enumerate(self._slots) if s is not None]
        if not active:
            if self.queue and self.paged:
                # nothing in flight yet the queue head could not be
                # admitted — no retirement will ever free enough pages
                raise RuntimeError(
                    f"page pool too small: {self.n_pages} pages of "
                    f"{self.ps} cannot hold request {self.queue[0].rid} "
                    f"(prompt {len(self.queue[0].prompt)} + budget "
                    f"{self.queue[0].max_new})")
            assert not self.queue, "scheduler stalled with queued requests"
            return        # everything admitted retired at its first token
        if self.paged and self._table_dirty:
            self._push_table()
        if self.spec > 1:
            self._spec_step(active, finished)
            return
        with spans.span("engine.launch"):
            cur = jnp.asarray(self._pos + 1, jnp.int32)   # post-append lengths
            tok, self.caches = self._decode_step(
                self.params, jnp.asarray(self._pending), self.caches, cur,
                self._next_key())
        tok = self._read(tok)
        self._pos[active] += 1
        self.counters["decode_steps"] += 1
        self.counters["active_slot_steps"] += len(active)
        with spans.span("engine.emit"):
            for i in active:
                self._emit(i, int(tok[i]), finished)

    def run(self) -> List[Request]:
        finished: List[Request] = []
        while self.step(finished):
            pass
        return sorted(finished, key=lambda r: r.rid)
