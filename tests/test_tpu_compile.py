"""Compile the serving kernels for a TPU v5e chip that is described, not attached.

Interpret mode (what every other kernel test runs off-TPU) accepts layouts the
chip's compiler refuses — a DMA'd tile whose minor dimension is not lane-aligned, a
head slice of a D = 64 page, a VMEM footprint past the scoped limit. These tests hand
each ``*_pallas`` function shapes on one described v5e chip with ``interpret=False``,
so Mosaic lowers and compiles it exactly as on the chip (nothing runs). The
``ops.py`` wrappers are bypassed on purpose: they see the CPU backend and would pick
interpret mode.

Widths: granite-moe-3b-a800m (D = 64, 8 KV heads, group 3) and starcoder2-7b
(D = 128, 4 KV heads, group 9), at the engine's serving shapes: batch 8, max_len 512,
page size 8, a 256-token chunked budget and a 4-token verify window.

The topology is described inside a module fixture, never at import: only one
process may load the TPU compiler library at a time, and under several pytest
workers the one given this file must be the only one that tries.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import act_quantize as aq
from repro.kernels import flash_attention as fa
from repro.kernels import qgemm as qg

# kv heads, group, head_dim, d_model, and N of the hot GEMM (expert up / MLP up)
WIDTHS = [
    pytest.param(dict(Hkv=8, G=3, D=64, d=1536, N=512), id="granite-moe-3b-a800m"),
    pytest.param(dict(Hkv=4, G=9, D=128, d=4608, N=18432), id="starcoder2-7b"),
]
B, MAX_LEN, PS = 8, 512, 8             # engine batch, cache length, page size
MAXP = MAX_LEN // PS
P = B * MAXP                           # dense-equivalent page pool
BUDGET = 256                           # chunked token budget (= chunk_cap)
Q_WIN = 4                              # speculative verify window


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # a compile for a described chip cannot be read back without the chip:
    # keep the persistent cache out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler library in this installation
        jax.config.update("jax_enable_compilation_cache", enabled)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)


def _compile(fn, one_chip, *shapes):
    """Lower and compile ``fn`` for the described chip (raises what Mosaic
    would raise on the chip) and check that a Mosaic kernel is in it."""
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip) for s, dt in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text, "the kernel did not lower to Mosaic"


def _pools(w, kv_int8):
    code = jnp.int8 if kv_int8 else jnp.bfloat16
    pools = [((P, PS, w["Hkv"], w["D"]), code)] * 2
    if kv_int8:
        pools += [((P, w["Hkv"], PS), jnp.float32)] * 2
    return pools


@pytest.mark.parametrize("sparse", [False, True], ids=["dense", "block_sparse"])
@pytest.mark.parametrize("w", WIDTHS)
def test_qgemm_w8a8(one_chip, w, sparse):
    M, K, N = 256, w["d"], w["N"]
    shapes = [((M, K), jnp.int8), ((K, N), jnp.int8), ((M, 1), jnp.float32),
              ((1, N), jnp.float32)]
    if sparse:          # occupancy of the (bk=512, bn=256) weight blocks
        fn = qg.qgemm_w8a8_sparse_pallas
        shapes.append(((K // 512, N // 256), jnp.int32))
    else:
        fn = qg.qgemm_w8a8_pallas
    _compile(fn, one_chip, *shapes)


@pytest.mark.parametrize("traced_alpha", [False, True])
@pytest.mark.parametrize("w", WIDTHS)
def test_act_quantize(one_chip, w, traced_alpha):
    shapes = [((256, w["d"]), jnp.bfloat16), ((w["d"],), jnp.float32)]
    if traced_alpha:
        fn = lambda x, b, al: aq.act_quantize_pallas(x, b, alpha=al)
        shapes.append(((), jnp.float32))
    else:
        fn = lambda x, b: aq.act_quantize_pallas(x, b, alpha=0.15)
    _compile(fn, one_chip, *shapes)


@pytest.mark.parametrize("w", WIDTHS)
def test_flash_attention(one_chip, w):
    S, H = 256, w["Hkv"] * w["G"]
    _compile(lambda q, k, v, kvl: fa.flash_attention_pallas(
                 q, k, v, kvl, bq=S, bk=S),
             one_chip, ((B, H, S, w["D"]), jnp.bfloat16),
             ((B, w["Hkv"], S, w["D"]), jnp.bfloat16),
             ((B, w["Hkv"], S, w["D"]), jnp.bfloat16), ((B,), jnp.int32))


@pytest.mark.parametrize("q_win", [1, Q_WIN], ids=["decode", "verify"])
@pytest.mark.parametrize("kv_int8", [False, True], ids=["fp_kv", "int8_kv"])
@pytest.mark.parametrize("w", WIDTHS)
def test_paged_decode(one_chip, w, kv_int8, q_win):
    def fn(q, kp, vp, tab, kvl, *rest):
        rest = list(rest)
        kw = dict(k_scale=rest.pop(0), v_scale=rest.pop(0)) if kv_int8 else {}
        if q_win > 1:
            kw.update(q_win=q_win, q_len=rest.pop(0))
        return fa.paged_decode_attention_pallas(q, kp, vp, tab, kvl, **kw)

    shapes = [((B, w["Hkv"], q_win * w["G"], w["D"]), jnp.bfloat16)]
    shapes += _pools(w, kv_int8)[:2] + [((B, MAXP), jnp.int32), ((B,), jnp.int32)]
    shapes += _pools(w, kv_int8)[2:]
    if q_win > 1:
        shapes.append(((B,), jnp.int32))
    _compile(fn, one_chip, *shapes)


@pytest.mark.parametrize("kv_int8", [False, True], ids=["fp_kv", "int8_kv"])
@pytest.mark.parametrize("w", WIDTHS)
def test_ragged_prefill(one_chip, w, kv_int8):
    Hkv, G, D = w["Hkv"], w["G"], w["D"]
    N = BUDGET                          # packed rows of one chunked step

    def fn(q, kn, vn, kp, vp, tab, qs, qln, kvl, *scales):
        kw = dict(k_scale=scales[0], v_scale=scales[1]) if kv_int8 else {}
        return fa.ragged_prefill_attention_pallas(
            q, kn, vn, kp, vp, tab, qs, qln, kvl, chunk_cap=BUDGET, **kw)

    pools = _pools(w, kv_int8)
    _compile(fn, one_chip, ((Hkv, N, G, D), jnp.bfloat16),
             ((N, Hkv, D), jnp.bfloat16), ((N, Hkv, D), jnp.bfloat16),
             *pools[:2], ((B, MAXP), jnp.int32), ((B,), jnp.int32),
             ((B,), jnp.int32), ((B,), jnp.int32), *pools[2:])
