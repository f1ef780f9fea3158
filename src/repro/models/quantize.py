"""Whole-model weight quantization: walk a params pytree and convert every quantizable
linear to its prepared integer form (int8 static-c CrossQuant or packed int4 groups).

This is the offline PTQ step of a serving deployment: run once, checkpoint the
quantized tree, serve from it. Embeddings, lm_head, router, norms, convs and the SSM
recurrence parameters stay fp (paper scope: activations *entering linear layers*)."""
from __future__ import annotations

import dataclasses
from typing import Dict, Iterable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import packing
from repro.core import qlinear as ql
from repro.core import quantizers as Q

QUANTIZABLE_PARENTS = ("wq", "wk", "wv", "wo", "up", "gate", "down",
                       "in_proj", "out_proj")


def _pathstr(path) -> str:
    out = []
    for p in path:
        out.append(str(p.key) if hasattr(p, "key") else str(getattr(p, "idx", p)))
    return "/".join(out)


# One compiled program per weight shape: the b-fold, scale and round fuse into
# passes over the weight, where op-by-op execution would hold several float32
# copies of a whole expert stack (4 GiB each at granite-moe-3b-a800m width).
_prepare_int8 = jax.jit(ql.prepare_int8, static_argnums=1)
_prepare_int4 = jax.jit(ql.prepare_int4, static_argnums=1)


def quantize_tree(params, cfg: ql.QuantConfig,
                  tables: Optional[Dict[str, np.ndarray]] = None):
    """Returns a new params pytree with prepared quantized linears.

    tables: calibration column-absmax per linear name (core.calibration.Observer);
    missing names fall back to c=1 (pure per-token row scaling)."""
    tables = tables or {}

    def convert(node, prefix):
        if isinstance(node, dict):
            if "w" in node and prefix and prefix.split("/")[-1] in QUANTIZABLE_PARENTS:
                w = node["w"]
                if w.ndim >= 2:
                    cmax = node.get("cmax")
                    if cmax is None and prefix in tables:
                        cmax = jnp.asarray(tables[prefix])
                    if cfg.w_bits <= 4:
                        return _prepare_int4({"w": w}, cfg, cmax)
                    return _prepare_int8({"w": w}, cfg, cmax)
            return {k: convert(v, f"{prefix}/{k}" if prefix else k)
                    for k, v in node.items()}
        if isinstance(node, list):
            return [convert(v, f"{prefix}/{i}") for i, v in enumerate(node)]
        return node

    return convert(params, "")


def dequantize_tree(qparams, cfg: ql.QuantConfig):
    """Invert :func:`quantize_tree`'s *weight* quantization: every prepared linear
    becomes ``{"w": dequant(q)/b, "cmax": ...}`` — an fp tree whose weights carry
    exactly the integer path's weight rounding.

    Serving this tree with ``mode="fake", act_quant="crossquant", static_c=True,
    w_prequantized=True`` is the fake-quant twin of the fused int path: the
    activation fake-quant applies the same ``t_i^α · c_j^(1-α)`` grid the kernels
    use, so logits agree up to f32 association (the §3.3 parity tests pin this).
    Leaves prepared without calibration (``qalpha == 1``) re-attach ``cmax = 1``;
    their fake twin is per-token activation quantization.
    """
    def convert(node):
        if isinstance(node, dict):
            if "qw" in node or "qw4" in node:
                b = node["bcol"]
                if "qw" in node:
                    wb = node["qw"].astype(jnp.float32) * node["sw"][..., None, :]
                else:
                    wb = ql.dequant_int4_weight(node["qw4"], node["sw"], cfg.w_group)
                w = wb / b[..., :, None]
                alpha = node["qalpha"][..., None]
                denom = jnp.where(alpha < 1.0, 1.0 - alpha, 1.0)
                cmax = jnp.where(alpha < 1.0, b ** (1.0 / denom), jnp.ones_like(b))
                return {"w": w, "cmax": cmax}
            return {k: convert(v) for k, v in node.items()}
        if isinstance(node, list):
            return [convert(v) for v in node]
        return node

    return convert(qparams)


def fake_quantize_weights(params, cfg: ql.QuantConfig):
    """Offline PTQ for the *fake-quant* evaluation path: replace every quantizable
    linear's ``w`` with its fake-quantized value. Serving with
    ``cfg.w_prequantized=True`` is then bitwise identical to in-graph weight fake
    quantization, but the decode/prefill graphs carry no weight-quant compute (which
    XLA otherwise hoists into stacked f32 copies of the whole weight tree —
    EXPERIMENTS.md §Perf)."""
    from repro.core.qlinear import _fake_weight

    def convert(node, prefix):
        if isinstance(node, dict):
            if "w" in node and prefix and prefix.split("/")[-1] in QUANTIZABLE_PARENTS:
                if node["w"].ndim >= 2:
                    return {**node, "w": _fake_weight(node["w"], cfg)}
            return {k: convert(v, f"{prefix}/{k}" if prefix else k)
                    for k, v in node.items()}
        if isinstance(node, list):
            return [convert(v, f"{prefix}/{i}") for i, v in enumerate(node)]
        return node

    return convert(params, "")


# --------------------------------------------------------------------------------------
# N:M structured sparsity (DESIGN.md §3.12)
# --------------------------------------------------------------------------------------

def parse_nm(spec: str) -> Tuple[int, int]:
    """``"2:4"`` -> ``(2, 4)`` (keep n of every m consecutive input channels)."""
    try:
        n, m = (int(p) for p in spec.split(":"))
    except ValueError:
        raise ValueError(f"sparsity spec {spec!r} is not 'N:M'") from None
    if not 0 < n < m:
        raise ValueError(f"sparsity spec {spec!r} needs 0 < N < M")
    return n, m


@dataclasses.dataclass
class SparsityPlan:
    """Which linears to prune, at what N:M, and the §4.1 evidence for the choice.

    ``layers=None`` prunes every eligible leaf (the serving default when no
    calibration traffic is available); :func:`make_sparsity_plan` instead measures
    each linear's CrossQuant quantization-kernel proportion and lists only the
    layers where it stays under ``threshold`` — small kernel ⇒ the activation grid
    already preserves the layer's information, so the extra weight compression is
    where it is safest (paper §4.1; ZeroQuant-V2's per-layer sensitivity)."""

    nm: Tuple[int, int] = (2, 4)
    layers: Optional[Tuple[str, ...]] = None   # leaf paths, e.g. "blocks/0/attn/wq"
    fractions: Dict[str, float] = dataclasses.field(default_factory=dict)
    threshold: float = 0.0

    def wants(self, prefix: str) -> bool:
        return self.layers is None or prefix in self.layers


def nm_keep_mask(score: jax.Array, n: int, m: int) -> jax.Array:
    """Boolean keep-mask holding the top-``n`` scores of every ``m`` consecutive
    input channels (axis -2), independently per output channel. Ties break toward
    the lower channel index (argsort is stable), so exactly ``n`` survive per
    group. A trailing remainder when ``d_in % m != 0`` stays dense."""
    *lead, K, N = score.shape
    kg = (K // m) * m
    head = score[..., :kg, :].reshape(*lead, kg // m, m, N)
    order = jnp.argsort(-head, axis=-2)            # descending within the group
    rank = jnp.argsort(order, axis=-2)             # each element's rank
    keep = (rank < n).reshape(*lead, kg, N)
    if kg < K:
        tail = jnp.ones((*lead, K - kg, N), bool)
        keep = jnp.concatenate([keep, tail], axis=-2)
    return keep


def _activation_weight(cm, alpha, d_in: int):
    """Residual activation factor that turns |wb| into the full |w|·c score.

    The prepared weight already carries ``c^(1-α)`` (the folded ``b`` column), so
    multiplying by ``c^α`` recovers magnitude × activation-absmax — the
    Wanda-style score — without unfolding. Uncalibrated leaves (α=1, b=1) get the
    whole ``c`` here."""
    cm = jnp.maximum(jnp.asarray(cm, jnp.float32), Q.EPS)
    cm = jnp.broadcast_to(cm, cm.shape[:-1] + (d_in,))
    return cm ** jnp.asarray(alpha, jnp.float32)[..., None]


def sparsify_tree(qparams, plan: SparsityPlan,
                  tables: Optional[Dict[str, np.ndarray]] = None):
    """Prune the linears named by ``plan`` to N:M structured sparsity.

    Works on either tree form:

    * **prepared int8** (post :func:`quantize_tree`): scores ``|qw·sw|`` — the
      b-folded weight, i.e. magnitude already weighted by ``c^(1-α)`` — times the
      residual ``c^α`` when calibration tables are available, zeroes the losers,
      then *refits* ``sw`` to the survivors before requantizing. Refitting is the
      point of pruning before per-channel scaling: the pruned weights no longer
      claim dynamic range, so every int8 code lands on a surviving value.
    * **fp** (pre-quantization, fake/fp serving): scores ``|w|·cmax`` (or plain
      magnitude without calibration) and zeroes the pruned fp weights in place.

    Either way each pruned leaf gains a bit-packed ``mask`` leaf
    (:func:`repro.core.packing.pack_mask`). Packed-int4 leaves and leaves already
    carrying a mask pass through untouched.
    """
    tables = tables or {}
    n, m = plan.nm

    def table_cmax(node, prefix):
        cm = node.get("cmax")
        if cm is None and prefix in tables:
            cm = jnp.asarray(tables[prefix])
        return cm

    def prune_prepared(node, prefix):
        qw, sw = node["qw"], node["sw"]
        wb = qw.astype(jnp.float32) * sw[..., None, :]
        score = jnp.abs(wb)
        cm = table_cmax(node, prefix)
        if cm is not None:
            score = score * _activation_weight(cm, node["qalpha"], qw.shape[-2])[..., :, None]
        mask = nm_keep_mask(score, n, m)
        wbp = jnp.where(mask, wb, 0.0)
        sw2 = jnp.maximum(jnp.max(jnp.abs(wbp), axis=-2), Q.EPS) / Q.qmax(8)
        qw2 = jnp.clip(jnp.round(wbp / sw2[..., None, :]),
                       -Q.qmax(8), Q.qmax(8)).astype(jnp.int8)
        return {**node, "qw": qw2, "sw": sw2.astype(jnp.float32),
                "mask": packing.pack_mask(mask)}

    def prune_fp(node, prefix):
        w = node["w"]
        score = jnp.abs(w).astype(jnp.float32)
        cm = table_cmax(node, prefix)
        if cm is not None:
            cm = jnp.maximum(jnp.asarray(cm, jnp.float32), Q.EPS)
            score = score * cm[..., :, None]
        mask = nm_keep_mask(score, n, m)
        return {**node, "w": jnp.where(mask, w, 0.0).astype(w.dtype),
                "mask": packing.pack_mask(mask)}

    def convert(node, prefix):
        if isinstance(node, dict):
            leaf = prefix.split("/")[-1] if prefix else ""
            if leaf in QUANTIZABLE_PARENTS and "mask" not in node and plan.wants(prefix):
                if "qw" in node:
                    return prune_prepared(node, prefix)
                if "w" in node and node["w"].ndim >= 2:
                    return prune_fp(node, prefix)
            return {k: convert(v, f"{prefix}/{k}" if prefix else k)
                    for k, v in node.items()}
        if isinstance(node, list):
            return [convert(v, f"{prefix}/{i}") for i, v in enumerate(node)]
        return node

    return convert(qparams, "")


def make_sparsity_plan(cfg, params, batches: Iterable, *, nm: Tuple[int, int] = (2, 4),
                       threshold: float = 0.05, bits: int = 8, alpha: float = 0.15,
                       ) -> SparsityPlan:
    """Measure each linear's §4.1 quantization-kernel proportion on calibration
    traffic and plan N:M pruning for the layers where it stays under ``threshold``.

    The proportion is ``|K(Q)| / |Q|`` under the CrossQuant grid (eager observer
    pass, like :mod:`repro.core.calibration`); a *stacked* leaf (one param array
    per sublayer across the scanned blocks) is gated on its **worst** layer, so a
    single outlier-heavy layer keeps the whole leaf dense."""
    from repro.core import kernel_analysis as KA
    from repro.core.calibration import stack_tables
    from repro.models import model as M
    from repro.models.layers import QuantContext

    per_name: Dict[str, list] = {}

    class _Shim:
        def observe(self, name, x):
            x2 = jnp.asarray(x).reshape(-1, x.shape[-1]).astype(jnp.float32)
            frac = float(KA.crossquant_kernel_fraction(x2, bits=bits, alpha=alpha))
            per_name.setdefault(name, []).append(frac)

    ctx = QuantContext(ql.W8A8_CROSSQUANT, observer=_Shim())
    for batch in batches:
        M.apply(params, batch, cfg, ctx=ctx, mode="train", unroll=True)

    stacked = stack_tables({k: np.float32(np.mean(v)) for k, v in per_name.items()})
    fractions = {path: float(np.max(v)) for path, v in stacked.items()}
    layers = tuple(sorted(p for p, f in fractions.items()
                          if f <= threshold and p.split("/")[-1] in QUANTIZABLE_PARENTS))
    return SparsityPlan(nm=nm, layers=layers, fractions=fractions,
                        threshold=threshold)


def sparsity_summary(qparams) -> Dict[str, float]:
    """``{leaf path: kept fraction}`` for every masked leaf (popcount / elements)."""
    out: Dict[str, float] = {}

    def walk(node, prefix):
        if isinstance(node, dict):
            if "mask" in node:
                ref = node["qw"] if "qw" in node else node["w"]
                kept = int(np.unpackbits(np.asarray(node["mask"])).sum())
                out[prefix] = kept / ref.size
                return
            for k, v in node.items():
                walk(v, f"{prefix}/{k}" if prefix else k)
        elif isinstance(node, list):
            for i, v in enumerate(node):
                walk(v, f"{prefix}/{i}")

    walk(qparams, "")
    return out


def quantized_bytes(params, *, deploy_sparse: bool = False) -> int:
    """Total bytes of **every** leaf — integer codes, scale/aux vectors (``sw``,
    ``bcol``, ``qalpha``, the int8-KV ``k_scale``/``v_scale``) and packed ``mask``
    leaves alike. Nothing is exempt: serving capacity math (README, serving_bench
    ``capacity_x``) divides HBM by this number, so auxiliary leaves must be paid
    for where they live.

    ``deploy_sparse=True`` costs each masked int8 leaf at its N:M *deployment*
    size — surviving codes (mask popcount) plus the packed mask — instead of the
    dense zero-carrying layout this repo stores; the difference is the HBM a 2:4
    hardware format hands back as extra KV pages."""
    if not deploy_sparse:
        return sum(x.size * x.dtype.itemsize
                   for x in jax.tree_util.tree_leaves(params))

    def walk(node) -> int:
        if isinstance(node, dict):
            if "qw" in node and "mask" in node:
                aux = sum(walk(v) for k, v in node.items() if k != "qw")
                kept = int(np.unpackbits(np.asarray(node["mask"])).sum())
                return aux + kept * node["qw"].dtype.itemsize
            return sum(walk(v) for v in node.values())
        if isinstance(node, list):
            return sum(walk(v) for v in node)
        return node.size * node.dtype.itemsize

    return walk(params)


def pad_head_params(params, cfg_from, cfg_to):
    """Transplant params into a head-padded layout (configs.with_padded_heads).

    Padding is PER KV GROUP (GQA maps head h to kv group h // G, so appending heads
    at the tail would reassign existing heads to different kv groups): each group
    gains zero q-columns (padded heads emit q=0) and zero wo-rows (padded heads
    contribute nothing) — the padded model computes exactly the same function, but
    its attention projections divide the TP degree.
    """
    import jax.numpy as jnp
    dh = cfg_to.head_dim
    hkv = cfg_from.n_kv_heads
    g0 = cfg_from.n_heads // hkv
    g1 = cfg_to.n_heads // hkv
    assert dh == cfg_from.head_dim and cfg_to.n_kv_heads == hkv
    if g0 == g1:
        return params

    def pad_wq(w):            # (..., d, H0*dh) -> (..., d, H1*dh)
        lead = w.shape[:-1]
        wg = w.reshape(*lead, hkv, g0, dh)
        pad = [(0, 0)] * wg.ndim
        pad[-2] = (0, g1 - g0)
        return jnp.pad(wg, pad).reshape(*lead, hkv * g1 * dh)

    def pad_wo(w):            # (..., H0*dh, d) -> (..., H1*dh, d)
        lead, d_out = w.shape[:-2], w.shape[-1]
        wg = w.reshape(*lead, hkv, g0, dh, d_out)
        pad = [(0, 0)] * wg.ndim
        pad[-3] = (0, g1 - g0)
        return jnp.pad(wg, pad).reshape(*lead, hkv * g1 * dh, d_out)

    def convert(node, parent=""):
        if isinstance(node, dict):
            if parent == "wq" and "w" in node:
                return {**node, "w": pad_wq(node["w"])}
            if parent == "wo" and "w" in node:
                return {**node, "w": pad_wo(node["w"])}
            return {k: convert(v, k) for k, v in node.items()}
        if isinstance(node, list):
            return [convert(v, parent) for v in node]
        return node

    return convert(params)
