"""Random weights from the seed, made by the benchmark.

One jitted call draws every leaf on the device and casts it to bfloat16 there,
so no float32 tree is ever held. The layout is the one the serving program
takes (stacked per-layer leaves under ``blocks/0``); the plain reference reads
the same tree by name. The draw is the benchmark's own: leaf ``k`` in sorted
path order comes from ``fold_in(key, k)``, with the scales the program's own
initializer uses (embedding 0.02, a linear ``d_in ** -0.5``, norms at one and
zero), so the reference can regenerate the weights without the program.
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp


def vocab_padded(c: dict) -> int:
    return -(-c["vocab"] // 256) * 256


def layout(c: dict) -> Dict[str, Tuple[tuple, str, float]]:
    """path -> (shape, kind, scale); kind is ``normal``, ``ones`` or ``zeros``."""
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


def key_from_seed(seed: int) -> jax.Array:
    """A PRNG key from any non-negative seed below 2**64."""
    seed = int(seed)
    if not 0 <= seed < 2 ** 64:
        raise ValueError(f"seed {seed} outside [0, 2**64)")
    k = jax.random.PRNGKey(0)
    k = jax.random.fold_in(k, jnp.uint32(seed & 0xFFFFFFFF))
    return jax.random.fold_in(k, jnp.uint32(seed >> 32))


def calibration_tokens(c: dict, seed: int):
    """The calibration batch of the configuration's PTQ recipe, from the seed:
    ``quant.calibration`` = [rows, tokens] random ids."""
    import numpy as np

    rows, n = c["quant"]["calibration"]
    return np.random.default_rng([int(seed), 3]).integers(1, c["vocab"], (rows, n))


def _nest(flat: dict) -> dict:
    tree: dict = {}
    for path, v in flat.items():
        parts = path.split("/")
        node = tree
        for p in parts[:-1]:
            node = node.setdefault(p, {})
        node[parts[-1]] = v
    tree["blocks"] = [tree["blocks"]["0"]]
    return tree


def make(c: dict, seed: int) -> dict:
    """The bfloat16 weight tree of configuration ``c`` for ``seed``."""
    lay = layout(c)
    paths = sorted(lay)

    def draw(key):
        flat = {}
        for k, path in enumerate(paths):
            shape, kind, scale = lay[path]
            if kind == "normal":
                x = jax.random.normal(jax.random.fold_in(key, k), shape) * scale
            else:
                x = jnp.full(shape, 1.0 if kind == "ones" else 0.0)
            flat[path] = x.astype(jnp.bfloat16)
        return _nest(flat)

    return jax.jit(draw)(key_from_seed(seed))
