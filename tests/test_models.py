"""Model-zoo tests: per-arch smoke (assignment requirement), prefill/decode parity,
arch-specific features (softcap, windows, shared blocks, frontends)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import SHAPES, all_archs, cell_supported, get
from repro.core import qlinear as ql
from repro.models import model as M
from repro.models.layers import QuantContext, blockwise_attention
from repro.kernels.ref import flash_attention_ref


def _batch(cfg, key, B=2, S=32):
    if cfg.frontend == "audio_stub":
        return {"frames": jax.random.normal(key, (B, S, cfg.frontend_dim),
                                            jnp.bfloat16),
                "labels": jax.random.randint(key, (B, S), 0, cfg.vocab)}
    batch = {"tokens": jax.random.randint(key, (B, S), 0, cfg.vocab)}
    if cfg.frontend == "vision_stub":
        batch["patch_embeds"] = jax.random.normal(
            key, (B, cfg.n_patches, cfg.frontend_dim), jnp.bfloat16)
    return batch


class TestArchSmoke:
    """One reduced-config forward/train step per assigned architecture: output shapes
    + no NaNs (the per-arch smoke tests required by the assignment)."""

    @pytest.mark.parametrize("arch", all_archs())
    def test_forward_and_loss(self, arch, key):
        cfg = get(arch, smoke=True)
        params = M.init_params(key, cfg)
        batch = _batch(cfg, key)
        logits, extras = M.apply(params, batch, cfg, mode="train")
        B = 2
        S = 32
        assert logits.shape == (B, S, cfg.vocab_padded)
        assert not bool(jnp.any(jnp.isnan(logits)))
        loss, metrics = M.loss_fn(params, batch, cfg, remat=False)
        assert bool(jnp.isfinite(loss))
        assert float(loss) > 0

    @pytest.mark.parametrize("arch", all_archs())
    def test_grad_step_finite(self, arch, key):
        cfg = get(arch, smoke=True)
        params = M.init_params(key, cfg)
        batch = _batch(cfg, key)
        (loss, _), grads = jax.value_and_grad(
            lambda p: M.loss_fn(p, batch, cfg, remat=True), has_aux=True)(params)
        leaves = jax.tree_util.tree_leaves(grads)
        assert all(bool(jnp.all(jnp.isfinite(g))) for g in leaves)

    @pytest.mark.parametrize("arch", ["deepseek-coder-33b", "gemma2-9b",
                                      "mamba2-130m", "zamba2-1.2b",
                                      "granite-moe-3b-a800m"])
    def test_quantized_forward(self, arch, key):
        cfg = get(arch, smoke=True)
        params = M.init_params(key, cfg)
        batch = _batch(cfg, key)
        for qc in (ql.W8A8_CROSSQUANT, ql.W4A8_G128):
            logits, _ = M.apply(params, batch, cfg, ctx=QuantContext(qc), mode="train")
            assert not bool(jnp.any(jnp.isnan(logits)))


class TestPrefillDecodeParity:
    """decode(prefill(x)) must equal the train-mode forward at the same positions.
    MoE archs use a generous capacity factor to exclude capacity-drop differences."""

    @pytest.mark.parametrize("arch", ["deepseek-coder-33b", "gemma2-9b",
                                      "starcoder2-7b", "mamba2-130m", "zamba2-1.2b",
                                      "nemotron-4-15b"])
    def test_parity(self, arch, key):
        cfg = get(arch, smoke=True)
        params = M.init_params(key, cfg)
        B, S, T = 2, 16, 32
        toks = jax.random.randint(key, (B, S), 0, cfg.vocab)
        caches = M.init_cache(cfg, B, T, dtype=jnp.float32)
        logits_p, ex = M.apply(params, {"tokens": toks}, cfg, mode="prefill",
                               caches=caches, cur_len=jnp.asarray(S, jnp.int32))
        nxt = jnp.argmax(logits_p[:, -1], -1)[:, None].astype(jnp.int32)
        logits_d, _ = M.apply(params, {"tokens": nxt}, cfg, mode="decode",
                              caches=ex["caches"], cur_len=jnp.asarray(S + 1, jnp.int32))
        full = jnp.concatenate([toks, nxt], axis=1)
        logits_f, _ = M.apply(params, {"tokens": full}, cfg, mode="train")
        # bf16 residual streams: one-ulp differences at logit magnitude ~4 are 0.06.
        np.testing.assert_allclose(np.asarray(logits_p[:, -1]),
                                   np.asarray(logits_f[:, S - 1]), atol=0.1)
        np.testing.assert_allclose(np.asarray(logits_d[:, -1]),
                                   np.asarray(logits_f[:, S]), atol=0.1)

    def test_moe_parity_high_capacity(self, key):
        cfg = dataclasses.replace(get("granite-moe-3b-a800m", smoke=True),
                                  capacity_factor=8.0)
        params = M.init_params(key, cfg)
        B, S, T = 2, 16, 32
        toks = jax.random.randint(key, (B, S), 0, cfg.vocab)
        caches = M.init_cache(cfg, B, T, dtype=jnp.float32)
        logits_p, ex = M.apply(params, {"tokens": toks}, cfg, mode="prefill",
                               caches=caches, cur_len=jnp.asarray(S, jnp.int32))
        nxt = jnp.argmax(logits_p[:, -1], -1)[:, None].astype(jnp.int32)
        logits_d, _ = M.apply(params, {"tokens": nxt}, cfg, mode="decode",
                              caches=ex["caches"], cur_len=jnp.asarray(S + 1, jnp.int32))
        full = jnp.concatenate([toks, nxt], axis=1)
        logits_f, _ = M.apply(params, {"tokens": full}, cfg, mode="train")
        np.testing.assert_allclose(np.asarray(logits_d[:, -1]),
                                   np.asarray(logits_f[:, S]), atol=0.05)


class TestBlockwiseAttention:
    """The jnp flash-attention oracle itself, against plain softmax attention."""

    @pytest.mark.parametrize("S,H,Hkv,D", [(32, 4, 2, 16), (65, 8, 8, 8),
                                           (128, 4, 1, 32)])
    def test_matches_plain_attention(self, S, H, Hkv, D, key):
        B = 2
        ks = jax.random.split(key, 3)
        q = jax.random.normal(ks[0], (B, S, H, D))
        k = jax.random.normal(ks[1], (B, S, Hkv, D))
        v = jax.random.normal(ks[2], (B, S, Hkv, D))
        out = blockwise_attention(q, k, v, causal=True, window=None, softcap=None,
                                  q_block=16, kv_block=16)
        kr = jnp.repeat(k, H // Hkv, axis=2)
        vr = jnp.repeat(v, H // Hkv, axis=2)
        want = flash_attention_ref(q.transpose(0, 2, 1, 3), kr.transpose(0, 2, 1, 3),
                                   vr.transpose(0, 2, 1, 3), causal=True)
        np.testing.assert_allclose(np.asarray(out),
                                   np.asarray(want.transpose(0, 2, 1, 3)),
                                   atol=2e-3)

    def test_sliding_window_masks_far_tokens(self, key):
        B, S, H, D, W = 1, 64, 2, 8, 8
        ks = jax.random.split(key, 3)
        q = jax.random.normal(ks[0], (B, S, H, D))
        k = jax.random.normal(ks[1], (B, S, H, D))
        v = jax.random.normal(ks[2], (B, S, H, D))
        out_w = blockwise_attention(q, k, v, causal=True, window=W, softcap=None,
                                    q_block=16, kv_block=16)
        # Truncating the KV to the window for the last query must give the same output.
        out_trunc = blockwise_attention(
            q[:, -1:], k[:, S - W:], v[:, S - W:], causal=False, window=None,
            softcap=None, q_block=16, kv_block=16)
        np.testing.assert_allclose(np.asarray(out_w[:, -1]),
                                   np.asarray(out_trunc[:, 0]), atol=2e-3)

    def test_softcap_applied(self, key):
        B, S, H, D = 1, 16, 1, 8
        q = jax.random.normal(key, (B, S, H, D)) * 10
        k = jax.random.normal(jax.random.fold_in(key, 1), (B, S, H, D)) * 10
        v = jax.random.normal(jax.random.fold_in(key, 2), (B, S, H, D))
        out_cap = blockwise_attention(q, k, v, causal=True, window=None, softcap=5.0,
                                      q_block=16, kv_block=16)
        out_raw = blockwise_attention(q, k, v, causal=True, window=None, softcap=None,
                                      q_block=16, kv_block=16)
        assert not np.allclose(np.asarray(out_cap), np.asarray(out_raw), atol=1e-3)


class TestCellSupport:
    def test_40_cells_partition(self):
        """10 archs × 4 shapes = 40 cells; 31 live + 9 documented skips."""
        live = skip = 0
        for arch in all_archs():
            cfg = get(arch)
            for shape in SHAPES.values():
                ok, why = cell_supported(cfg, shape)
                live += ok
                skip += not ok
                if not ok:
                    assert why
        assert live + skip == 40
        assert live == 31 and skip == 9

    def test_encoder_only_skips_decode(self):
        cfg = get("hubert-xlarge")
        ok, why = cell_supported(cfg, SHAPES["decode_32k"])
        assert not ok and "encoder" in why

    def test_long_context_only_subquadratic(self):
        assert cell_supported(get("mamba2-130m"), SHAPES["long_500k"])[0]
        assert cell_supported(get("zamba2-1.2b"), SHAPES["long_500k"])[0]
        assert not cell_supported(get("deepseek-coder-33b"), SHAPES["long_500k"])[0]


def test_init_params_on_device_is_the_cast_eager_init():
    """The compiled init draws exactly the eager init's values, cast to
    cfg.dtype — without a float32 tree in between."""
    cfg = get("granite-moe-3b-a800m", smoke=True)
    key = jax.random.PRNGKey(3)
    got = M.init_params_on_device(key, cfg)
    want = M.init_params(key, cfg, jnp.dtype(cfg.dtype))
    assert jax.tree_util.tree_structure(got) == jax.tree_util.tree_structure(want)
    for g, w in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)):
        assert g.dtype == w.dtype
        np.testing.assert_array_equal(np.asarray(g, np.float32),
                                      np.asarray(w, np.float32))
