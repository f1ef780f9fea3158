"""Model modules: ``bench/models/decoder.py`` draws and computes exactly what
the harness drew and computed before the module was split out of it, and a
configuration can bring a module of its own from outside the benchmark's tree.

The hashes below were recorded on the CPU from the harness as it stood before
``decoder.py`` existed (``weights.make`` with its own layout, and
``harness/reference.py``), at the small sizes of ``CONFIGS``: a SHA-256 prefix
of every weight leaf's bytes, and of the float32 reference logits at int8 (the
configuration's precision), int4 (the control) and unquantized."""
import hashlib
import json

import jax
import numpy as np
import pytest

from bench.harness import flops, spec, weights

decoder = spec.model_module("decoder")

BASE = dict(name="small", model="decoder", n_layers=2, d_model=64, n_heads=4,
            n_kv_heads=2, head_dim=16, vocab=500, tie_embeddings=True, dtype="bfloat16",
            quant=dict(bits=8, alpha=0.15, kv_bits=8, calibration=[2, 32]))
CONFIGS = {
    "moe": dict(BASE, arch="granite-moe-3b-a800m", family="moe", d_ff=64, n_experts=8,
                top_k=2, d_ff_expert=64, n_shared_experts=0, capacity_factor=100.0,
                act="silu_glu", norm="rmsnorm", rope_theta=1e4),
    "dense": dict(BASE, arch="starcoder2-7b", family="dense", d_ff=128, n_experts=0,
                  act="gelu", norm="layernorm", rope_theta=1e6, tie_embeddings=False),
}
SEED = 2**31 + 5
LEAVES = {
    "moe": {
        "['blocks'][0]['attn']['wk']['w']": "59c782af0069cd80",
        "['blocks'][0]['attn']['wo']['w']": "592643f3a4da60c4",
        "['blocks'][0]['attn']['wq']['w']": "7a82dcf54d619328",
        "['blocks'][0]['attn']['wv']['w']": "172626e823dc517c",
        "['blocks'][0]['moe']['down']['w']": "06312e6090601848",
        "['blocks'][0]['moe']['gate']['w']": "fe73e09fcc6aaab2",
        "['blocks'][0]['moe']['router']['w']": "88b13280dc4e3a91",
        "['blocks'][0]['moe']['up']['w']": "06dd2300ea4f3c21",
        "['blocks'][0]['norm1']['scale']": "1ede9ebfa1ad011b",
        "['blocks'][0]['norm2']['scale']": "1ede9ebfa1ad011b",
        "['embed']['w']": "755cf9d2d3674472",
        "['final_norm']['scale']": "e72710531b01d91e"
    },
    "dense": {
        "['blocks'][0]['attn']['wk']['w']": "59c782af0069cd80",
        "['blocks'][0]['attn']['wo']['w']": "592643f3a4da60c4",
        "['blocks'][0]['attn']['wq']['w']": "7a82dcf54d619328",
        "['blocks'][0]['attn']['wv']['w']": "172626e823dc517c",
        "['blocks'][0]['mlp']['down']['w']": "b204cd718d40ee3a",
        "['blocks'][0]['mlp']['up']['w']": "475394f2bd87bfd7",
        "['blocks'][0]['norm1']['bias']": "5341e6b2646979a7",
        "['blocks'][0]['norm1']['scale']": "1ede9ebfa1ad011b",
        "['blocks'][0]['norm2']['bias']": "5341e6b2646979a7",
        "['blocks'][0]['norm2']['scale']": "1ede9ebfa1ad011b",
        "['embed']['w']": "755cf9d2d3674472",
        "['final_norm']['bias']": "38723a2e5e8a17aa",
        "['final_norm']['scale']": "e72710531b01d91e",
        "['lm_head']['w']": "8f60d5ff9fb408f5"
    }
}
LOGITS = {
    "moe": {
        "8": "c885e00e1a431e3d",
        "4": "22ce041a945db093",
        "_float": "39715da76b5c06c7"
    },
    "dense": {
        "8": "d92f131a9f4983aa",
        "4": "8c12017f1c6df735",
        "_float": "2a4280693a8f0c61"
    }
}


def _hash(a) -> str:
    a = np.asarray(a)
    if a.dtype == jax.numpy.bfloat16:
        a = a.view(np.uint16)
    return hashlib.sha256(np.ascontiguousarray(a).tobytes()).hexdigest()[:16]


@pytest.mark.parametrize("kind", sorted(CONFIGS))
def test_weight_draw_is_unchanged(kind):
    tree = weights.make(decoder.layout(CONFIGS[kind]), SEED)
    got = {jax.tree_util.keystr(p): _hash(v)
           for p, v in jax.tree_util.tree_leaves_with_path(tree)}
    assert got == LEAVES[kind]


@pytest.mark.parametrize("bits", ["8", "4", "_float"])
@pytest.mark.parametrize("kind", sorted(CONFIGS))
def test_reference_logits_are_unchanged(kind, bits):
    c = CONFIGS[kind]
    tree = weights.make(decoder.layout(c), SEED)
    seq = np.random.default_rng(1).integers(1, c["vocab"], 50).astype(np.int32)
    rows = np.arange(10, 50)
    if bits == "_float":
        got = decoder.logits(tree, seq, rows, c)
    else:
        cols = decoder.calibrate(tree, weights.calibration_tokens(c, SEED), c, int(bits))
        got = decoder.logits(tree, seq, rows, c, int(bits), cols)
    assert _hash(np.asarray(got, np.float32)) == LOGITS[kind][bits]


# A model module of another architecture, kept outside the benchmark's tree: a
# bigram model whose logits are the embedding of the previous token times the
# head. Nothing of the harness knows it.
BIGRAM = '''
import jax.numpy as jnp

from bench.harness import flops
from bench.harness.weights import vocab_padded


def layout(c):
    return {"embed/w": ((vocab_padded(c), c["d_model"]), "normal", 0.02),
            "head/0/w": ((c["d_model"], vocab_padded(c)), "normal", c["d_model"] ** -0.5)}


def calibrate(tree, tokens, c, bits):
    return None


def logits(tree, seq, rows, c, bits=None, cols=None):
    h = tree["embed"]["w"][jnp.asarray(seq)[jnp.asarray(rows)]].astype(jnp.float32)
    return (h @ tree["head"][0]["w"].astype(jnp.float32))[:, : c["vocab"]]


def work(c):
    return flops.ModelWork(int8_ops=0.0, bf16_ops=0.0, bf16_ops_per_position=0.0,
                           head_ops=2.0 * c["d_model"] * c["vocab"])


def attention(c):
    return None
'''


def test_a_new_architecture_is_files_only(tmp_path):
    """A cell of a new architecture needs a configuration, a model module, a
    mix and limits: the harness finds the module by the name in the
    configuration, draws its weights and counts its work."""
    bench = tmp_path / "bench"
    for d in ("configs", "models", "traffic", "limits"):
        (bench / d).mkdir(parents=True)
    (bench / "models" / "bigram.py").write_text(BIGRAM)
    config = dict(name="bigram-small", model="bigram", d_model=32, vocab=300)
    (bench / "configs" / "bigram-small.json").write_text(json.dumps(config))
    (bench / "traffic" / "short.json").write_text(json.dumps(dict(
        loop="closed", clients=2, n_requests=8, sizes_seed=1,
        prompt=dict(dist="uniform", lo=4, hi=8), output=dict(dist="uniform", lo=2, hi=4),
        engine=dict(batch_size=2, max_len=16))))
    (bench / "limits" / "bigram.short.json").write_text("{}")
    b = spec.load_benchmark()
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(dict(
        b, configs=[dict(name="bigram-small", source="-", file="bench/configs/bigram-small.json",
                         reduced=[], why="-")],
        workloads=[dict(name="bigram.short", config="bigram-small", traffic="short",
                        chips=1, why="-")])))

    cell = spec.load_cell("bigram.short", root=tmp_path)
    assert cell.model.__file__ == str(bench / "models" / "bigram.py")
    assert cell.model is not decoder
    tree = weights.make(cell.model.layout(cell.config), 3)
    assert tree["embed"]["w"].shape == (512, 32) and isinstance(tree["head"], list)
    seq = np.arange(1, 9)
    got = cell.model.logits(tree, seq, np.arange(8), cell.config)
    assert got.shape == (8, 300)
    assert flops.token_work(cell.model.work(cell.config), 7).bf16_ops == 2 * 32 * 300
    assert cell.model.attention(cell.config) is None
