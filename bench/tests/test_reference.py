"""The plain reference against the program's own float forward, and the
weight layout against the program's initializer, at a small size."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.harness import model, spec, weights

reference = spec.model_module("decoder")

BASE = dict(name="small", n_layers=2, d_model=64, n_heads=4, n_kv_heads=2, head_dim=16,
            vocab=500, tie_embeddings=True, dtype="float32", quant=dict(alpha=0.15))
CONFIGS = {
    # every expert has room, so the program's capacity drops nothing
    "moe": dict(BASE, arch="granite-moe-3b-a800m", family="moe", d_ff=64, n_experts=8,
                top_k=2, d_ff_expert=64, n_shared_experts=0, capacity_factor=100.0,
                act="silu_glu", norm="rmsnorm", rope_theta=1e4),
    "dense": dict(BASE, arch="starcoder2-7b", family="dense", d_ff=128, n_experts=0,
                  act="gelu", norm="layernorm", rope_theta=1e6),
}


@pytest.mark.parametrize("kind", sorted(CONFIGS))
def test_layout_is_the_programs(kind):
    from repro.models import model as M

    c = CONFIGS[kind]
    shapes = jax.eval_shape(lambda k: M.init_params(k, model.program_config(c)),
                            jax.random.PRNGKey(0))
    tree = weights.make(reference.layout(c), 5)
    assert jax.tree_util.tree_structure(shapes) == jax.tree_util.tree_structure(tree)
    for a, b in zip(jax.tree_util.tree_leaves(shapes), jax.tree_util.tree_leaves(tree)):
        assert a.shape == b.shape


@pytest.mark.parametrize("kind", sorted(CONFIGS))
def test_float_reference_is_the_programs_float_forward(kind):
    from repro.core import qlinear as ql
    from repro.models import model as M
    from repro.models.layers import QuantContext

    c = CONFIGS[kind]
    tree = weights.make(reference.layout(c), 5)
    f32 = jax.tree_util.tree_map(lambda x: x.astype(jnp.float32), tree)
    toks = np.random.default_rng(0).integers(1, c["vocab"], 40).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        got, _ = M.apply(f32, {"tokens": jnp.asarray(toks[None])}, model.program_config(c),
                         ctx=QuantContext(ql.FP), mode="train")
    want = np.asarray(reference.logits(tree, toks, np.arange(40), c))
    np.testing.assert_allclose(np.asarray(got[0, :, : c["vocab"]]), want, atol=1e-5)
