"""Model module ``decoder``: a pre-norm decoder of GQA + RoPE attention and a
dense or mixture-of-experts MLP, with no biases beyond LayerNorm's and no
shared expert (granite-3.0 MoE, starcoder2).

A configuration file names its model module with the key ``"model"``; the
harness loads ``bench/models/<model>.py`` by that name (``spec.model_module``)
and reads everything that depends on the architecture from it:

- ``layout(c)``: path -> (shape, kind, scale) of every weight leaf, the layout
  the serving program takes; ``weights.make`` draws the leaves from the seed;
- ``calibrate(tree, tokens, c, bits)`` and ``logits(tree, seq, rows, c, bits,
  cols)``: the plain reference, below;
- ``work(c)``: the operations one token needs (``flops.ModelWork``), which
  the ``mfu.*`` readers count;
- ``attention(c)``: the paged attention layers' shape, from which
  ``flops.paged_decode`` counts the cached-context bytes per token that
  ``paged_decode_roofline`` reads; None for a model without them.

To add an architecture, add a configuration file that names a new module and
the module ``bench/models/<name>.py`` with these five functions: no other file
of the harness changes. A traffic mix and the cell's limits are files too.

The plain reference: the configuration's forward pass, in float32 arithmetic.

Straight ``jax.numpy`` at ``Precision.HIGHEST``, no cache, no batching, no
kernels, and nothing imported from the program. It takes the bfloat16 weights
that ``weights.make`` draws from the seed and follows the configuration file:
pre-norm blocks (RMSNorm or LayerNorm), rotary positions on the two halves of
each head, causal grouped-query attention with softmax scale
``head_dim ** -0.5``, then a dense MLP or a mixture of experts that routes
every token to its ``top_k`` experts by a float32 softmax router, with the gate
weights renormalised over those ``top_k`` and no capacity limit; the head is
the tied embedding (or ``lm_head``).

At the precision the configuration states (``quant``): every linear of the
blocks is CrossQuant W8A8 with static column scales (arXiv:2410.07505, eq. 5):
``b_j = c_j ** (1 - alpha)`` from the calibrated column absmax ``c_j`` of the
linear's input, weights ``W * b`` rounded per output channel, activations
``x / (t_i ** alpha * b_j)`` rounded with the row absmax ``t_i``; keys and
values rounded per token and head. The column absmax comes from the
reference's own calibration pass over the calibration tokens, run as the
configuration states: each linear quantized with the column absmax of the
batch itself. The head, the router and the norms stay in float.

``bits=4`` is the control: the same recipe, calibration included, one step
below the configuration's int8. ``bits=None`` is the unquantized model.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

from bench.harness import flops
from bench.harness.weights import vocab_padded

HI = lax.Precision.HIGHEST
EPS = 1e-8
Q_BLOCK = 256          # query rows per attention block
T_BLOCK = 512          # tokens per expert block


def _qmax(bits):
    return 2 ** (bits - 1) - 1


def _col_absmax(x):
    return jnp.max(jnp.abs(x).reshape(-1, x.shape[-1]), axis=0)


def _linear(x, w, col, bits, alpha):
    """x (..., d_in) @ w (d_in, d_out). With ``bits``: CrossQuant static-c
    with column absmax ``col`` (d_in,)."""
    w = w.astype(jnp.float32)
    if bits:
        qm = _qmax(bits)
        b = jnp.maximum(col, EPS) ** (1.0 - alpha)
        wb = w * b[:, None]
        sw = jnp.maximum(jnp.max(jnp.abs(wb), axis=0, keepdims=True), EPS) / qm
        w = jnp.clip(jnp.round(wb / sw), -qm, qm) * sw
        a = jnp.maximum(jnp.max(jnp.abs(x), -1, keepdims=True), EPS) ** alpha / qm
        x = jnp.clip(jnp.round(x / (a * b)), -qm, qm) * a
    return jnp.einsum("...i,io->...o", x, w, precision=HI)


def _round_rows(x, bits):
    """Round each (token, head) row of keys or values to ``bits``."""
    qm = _qmax(bits)
    s = jnp.maximum(jnp.max(jnp.abs(x), -1, keepdims=True), EPS) / qm
    return jnp.clip(jnp.round(x / s), -qm, qm) * s


def _norm(x, p, kind):
    if kind == "rmsnorm":
        y = x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-6)
        return y * p["scale"].astype(jnp.float32)
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return ((x - mu) * lax.rsqrt(var + 1e-5) * p["scale"].astype(jnp.float32)
            + p["bias"].astype(jnp.float32))


def _rope(x, pos, theta):
    """x (T, H, D): rotate the pairs (i, i + D/2) by pos * theta**(-i / (D/2))."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos[:, None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(q, k, v):
    """Causal GQA: q (T, H, D), k/v (T, Hkv, D) -> (T, H, D), by query blocks."""
    T, H, D = q.shape
    G = H // k.shape[1]
    qn = min(Q_BLOCK, T)
    qb = q.reshape(T // qn, qn, k.shape[1], G, D)

    def block(args):
        i, qi = args
        s = jnp.einsum("qhgd,khd->hgqk", qi, k, precision=HI) * D ** -0.5
        rows = i * qn + jnp.arange(qn)
        s = jnp.where(rows[:, None] >= jnp.arange(T)[None, :], s, -jnp.inf)
        p = jax.nn.softmax(s, -1)
        return jnp.einsum("hgqk,khd->qhgd", p, v, precision=HI)

    out = lax.map(block, (jnp.arange(T // qn), qb))
    return out.reshape(T, H, D)


def _act(c, x, p, cols, bits):
    """The MLP's hidden activation (one expert or the dense MLP)."""
    up = _linear(x, p["up"], cols["ffn_in"], bits, c["alpha"])
    act = c["act"]
    if act == "silu_glu":
        return jax.nn.silu(_linear(x, p["gate"], cols["ffn_in"], bits, c["alpha"])) * up
    if act == "gelu_glu":
        return jax.nn.gelu(_linear(x, p["gate"], cols["ffn_in"], bits, c["alpha"])) * up
    if act == "gelu":
        return jax.nn.gelu(up)
    if act == "relu2":
        return jnp.square(jax.nn.relu(up))
    raise ValueError(f"unknown activation {act!r}")


def _moe(c, p, x, cols, bits, calibrate):
    """Every token through its top_k experts, gates renormalised, no capacity.
    Returns (output, column absmax of the experts' hidden rows that a routed
    token produced)."""
    N, d = x.shape
    E, K = c["n_experts"], c["top_k"]
    probs = jax.nn.softmax(jnp.einsum("td,de->te", x, p["router"]["w"].astype(
        jnp.float32), precision=HI), -1)
    top_w, top_i = lax.top_k(probs, K)
    if K > 1:
        top_w = top_w / jnp.sum(top_w, -1, keepdims=True)
    gates = jnp.zeros((N, E)).at[jnp.arange(N)[:, None], top_i].set(top_w)
    experts = {k: p[k]["w"] for k in ("up", "gate", "down") if k in p}
    if calibrate:
        cols = dict(cols, ffn_in=_col_absmax(x))
        h = jax.vmap(lambda w: _act(c, x, w, cols, bits))(experts)       # (E, N, f)
        routed = (gates.T > 0)[..., None]
        down = jnp.max(jnp.where(routed, jnp.abs(h), 0.0).reshape(-1, h.shape[-1]), 0)
        cols = dict(cols, down=down)
    tb = min(T_BLOCK, N)
    n = N // tb

    def block(args):
        xb, gb = args
        h = jax.vmap(lambda w: _act(c, xb, w, cols, bits))(experts)
        y = jax.vmap(lambda hh, w: _linear(hh, w, cols["down"], bits, c["alpha"]))(
            h, experts["down"])                                         # (E, Tb, d)
        return jnp.einsum("te,etd->td", gb, y, precision=HI)

    out = lax.map(block, (x.reshape(n, tb, d), gates.reshape(n, tb, E)))
    return out.reshape(N, d), cols["down"]


def _layer(c, x, p, cols, bits, calibrate):
    """One block over x (B, T, d). ``calibrate``: quantize each linear with the
    column absmax of its own input here, and return those column maxima;
    otherwise use ``cols`` and round keys and values."""
    B, T, d = x.shape
    H, Hkv, D = c["n_heads"], c["n_kv_heads"], c["head_dim"]
    al = c["alpha"]
    seen = {}
    h = _norm(x, p["norm1"], c["norm"])
    seen["attn_in"] = _col_absmax(h) if calibrate else cols["attn_in"]
    a = p["attn"]
    pos = jnp.arange(T)
    lin = lambda inp, w, col: _linear(inp, w["w"], col, bits, al)
    q = _rope(lin(h, a["wq"], seen["attn_in"]).reshape(B * T, H, D), jnp.tile(pos, B),
              c["rope_theta"]).reshape(B, T, H, D)
    k = _rope(lin(h, a["wk"], seen["attn_in"]).reshape(B * T, Hkv, D), jnp.tile(pos, B),
              c["rope_theta"]).reshape(B, T, Hkv, D)
    v = lin(h, a["wv"], seen["attn_in"]).reshape(B, T, Hkv, D)
    if bits and not calibrate:
        k, v = _round_rows(k, bits), _round_rows(v, bits)
    o = jax.vmap(_attention)(q, k, v).reshape(B, T, H * D)
    seen["wo"] = _col_absmax(o) if calibrate else cols["wo"]
    x = x + lin(o, a["wo"], seen["wo"])
    h = _norm(x, p["norm2"], c["norm"])
    if c.get("n_experts"):
        y, seen["down"] = _moe(c, p["moe"], h.reshape(B * T, d), dict(cols, **seen),
                               bits, calibrate)
        seen["ffn_in"] = _col_absmax(h) if calibrate else cols["ffn_in"]
        return x + y.reshape(B, T, d), seen
    mlp = {k: w["w"] for k, w in p["mlp"].items()}
    seen["ffn_in"] = _col_absmax(h) if calibrate else cols["ffn_in"]
    hid = _act(c, h, mlp, seen, bits)
    seen["down"] = _col_absmax(hid) if calibrate else cols["down"]
    return x + _linear(hid, mlp["down"], seen["down"], bits, al), seen


def _blank_cols(c):
    f = c["d_ff_expert"] if c.get("n_experts") else c["d_ff"]
    z = lambda n: jnp.zeros((n,), jnp.float32)
    return {"attn_in": z(c["d_model"]), "wo": z(c["n_heads"] * c["head_dim"]),
            "ffn_in": z(c["d_model"]), "down": z(f)}


@functools.partial(jax.jit, static_argnames=("cfg", "bits"))
def _calibrate(tree, tokens, *, cfg, bits):
    """Per-layer column absmax of every linear's input over ``tokens`` (B, T)."""
    c = dict(cfg)
    x = tree["embed"]["w"][tokens].astype(jnp.float32)

    def body(x, p):
        return _layer(c, x, p, _blank_cols(c), bits, True)

    _, cols = lax.scan(body, x, tree["blocks"][0])
    return cols


@functools.partial(jax.jit, static_argnames=("cfg", "bits"))
def _logits(tree, cols, tokens, rows, *, cfg, bits):
    c = dict(cfg)
    x = tree["embed"]["w"][tokens].astype(jnp.float32)[None]

    def body(x, pc):
        p, col = pc
        return _layer(c, x, p, col, bits, False)[0], None

    x, _ = lax.scan(body, x, (tree["blocks"][0], cols))
    h = _norm(x[0, rows], tree["final_norm"], c["norm"])
    head = (tree["lm_head"]["w"].astype(jnp.float32) if "lm_head" in tree
            else tree["embed"]["w"].astype(jnp.float32).T)
    return jnp.einsum("nd,dv->nv", h, head, precision=HI)[:, : c["vocab"]]


def _round_up(n: int, m: int) -> int:
    return m * math.ceil(n / m)


def _padded_length(n: int) -> int:
    """``T_BLOCK`` or the next power of two: few shapes to compile, each a
    multiple of the attention and expert blocks."""
    return max(T_BLOCK, 1 << (n - 1).bit_length())


def _static(c: dict) -> tuple:
    flat = {k: v for k, v in c.items() if isinstance(v, (int, float, str, bool))}
    flat["alpha"] = float(c.get("quant", {}).get("alpha", 0.15))
    return tuple(sorted(flat.items()))


def calibrate(tree, tokens, c: dict, bits):
    """The column tables of the configuration's calibration, at ``bits``."""
    if not bits:
        return None
    return _calibrate(tree, jnp.asarray(tokens, jnp.int32), cfg=_static(c), bits=bits)


def logits(tree, tokens, rows, c: dict, bits=None, cols=None):
    """Float32 logits over the real vocabulary at positions ``rows`` of the
    sequence ``tokens`` (numpy int arrays), at ``bits`` with the calibration
    tables ``cols``. The sequence is padded at its end to ``T_BLOCK`` or the
    next power of two and ``rows`` to a multiple of 128, which a causal model
    allows; the padding is cut from the result."""
    import numpy as np

    T, n = len(tokens), len(rows)
    tok = np.zeros(_padded_length(T), np.int32)
    tok[:T] = tokens
    r = np.zeros(_round_up(n, 128), np.int32)
    r[:n] = rows
    if cols is None:
        L = c["n_layers"]
        cols = jax.tree_util.tree_map(lambda z: jnp.zeros((L,) + z.shape), _blank_cols(c))
    return _logits(tree, cols, jnp.asarray(tok), jnp.asarray(r), cfg=_static(c),
                   bits=bits)[:n]


def layout(c: dict) -> dict:
    """path -> (shape, kind, scale); kind is ``normal``, ``ones`` or ``zeros``.
    Per-layer leaves are stacked under ``blocks/0``, as the program stacks
    them; the scales are the program's initializer's (embedding 0.02, a
    linear ``d_in ** -0.5``, norms at one and zero)."""
    L, d, D = c["n_layers"], c["d_model"], c["head_dim"]
    hq, hkv = c["n_heads"] * D, c["n_kv_heads"] * D
    out = {"embed/w": ((vocab_padded(c), d), "normal", 0.02)}

    def norm(prefix, stacked):
        shape = (L, d) if stacked else (d,)
        out[f"{prefix}/scale"] = (shape, "ones", 0.0)
        if c["norm"] == "layernorm":
            out[f"{prefix}/bias"] = (shape, "zeros", 0.0)

    def lin(path, d_in, d_out, lead=(L,)):
        out[f"{path}/w"] = (lead + (d_in, d_out), "normal", d_in ** -0.5)

    b = "blocks/0"
    norm(f"{b}/norm1", True)
    norm(f"{b}/norm2", True)
    lin(f"{b}/attn/wq", d, hq)
    lin(f"{b}/attn/wk", d, hkv)
    lin(f"{b}/attn/wv", d, hkv)
    lin(f"{b}/attn/wo", hq, d)
    glu = c["act"].endswith("_glu")
    if c.get("n_experts"):
        E, f = c["n_experts"], c["d_ff_expert"]
        out[f"{b}/moe/router/w"] = ((L, d, E), "normal", d ** -0.5)
        lin(f"{b}/moe/up", d, f, (L, E))
        lin(f"{b}/moe/down", f, d, (L, E))
        if glu:
            lin(f"{b}/moe/gate", d, f, (L, E))
    else:
        f = c["d_ff"]
        lin(f"{b}/mlp/up", d, f)
        lin(f"{b}/mlp/down", f, d)
        if glu:
            lin(f"{b}/mlp/gate", d, f)
    norm("final_norm", False)
    if not c.get("tie_embeddings", True):
        lin("lm_head", d, vocab_padded(c), ())
    return out


def _linear_params(c: dict) -> tuple:
    """(int8 linear weights a token passes through in one block, router
    weights in one block)."""
    d, D = c["d_model"], c["head_dim"]
    hq, hkv = c["n_heads"] * D, c["n_kv_heads"] * D
    attn = d * (hq + 2 * hkv) + hq * d
    mats = 3 if c["act"].endswith("_glu") else 2
    if c.get("n_experts"):
        return attn + c["top_k"] * mats * d * c["d_ff_expert"], d * c["n_experts"]
    return attn + mats * d * c["d_ff"], 0


def work(c: dict) -> flops.ModelWork:
    """One token through the model: int8 linears in every block; bf16
    attention (QK and PV) per attended position, the router, and the head."""
    lin, router = _linear_params(c)
    L = c["n_layers"]
    return flops.ModelWork(int8_ops=2.0 * lin * L, bf16_ops=L * 2.0 * router,
                           bf16_ops_per_position=L * 4.0 * c["n_heads"] * c["head_dim"],
                           head_ops=2.0 * c["d_model"] * c["vocab"])


def attention(c: dict) -> dict:
    """Every layer is a paged GQA layer."""
    return {"layers": c["n_layers"], "n_heads": c["n_heads"],
            "n_kv_heads": c["n_kv_heads"], "head_dim": c["head_dim"]}
