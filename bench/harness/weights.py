"""Random weights from the seed, made by the benchmark.

One jitted call draws every leaf on the device and casts it to bfloat16 there,
so no float32 tree is ever held. The layout (path -> shape, kind, scale) is the
model module's (``bench/models/<model>.py``, ``layout(c)``), the one the
serving program takes; the plain reference reads the same tree by name. The
draw is the benchmark's own: leaf ``k`` in sorted path order comes from
``fold_in(key, k)``, so the reference can regenerate the weights without the
program. A path segment of digits under a group makes that group a list
(``blocks/0`` -> ``blocks[0]``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def vocab_padded(c: dict) -> int:
    return -(-c["vocab"] // 256) * 256


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


def _lists(node):
    """Groups keyed by digits only become lists, in the order of the digits."""
    if not isinstance(node, dict):
        return node
    out = {k: _lists(v) for k, v in node.items()}
    if out and all(k.isdigit() for k in out):
        return [out[k] for k in sorted(out, key=int)]
    return out


def _nest(flat: dict) -> dict:
    tree: dict = {}
    for path, v in flat.items():
        parts = path.split("/")
        node = tree
        for p in parts[:-1]:
            node = node.setdefault(p, {})
        node[parts[-1]] = v
    return _lists(tree)


def make(lay: dict, seed: int) -> dict:
    """The bfloat16 weight tree of layout ``lay`` (a model module's
    ``layout(c)``) for ``seed``."""
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
