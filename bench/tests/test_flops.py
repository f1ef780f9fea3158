"""Operation and byte counts against hand counts at one shape each."""
import json

from bench.harness import flops, spec

decoder = spec.model_module("decoder")


# starcoder2-7b's published widths (arXiv:2402.19173) at 16 of its 32 layers: a
# dense GQA model with a two-matrix MLP
STARCODER2 = dict(n_layers=16, d_model=4608, n_heads=36, n_kv_heads=4, head_dim=128,
                  d_ff=18432, vocab=49152, n_experts=0, act="gelu")


def _config(name):
    if name == "starcoder2-7b-l16":
        return STARCODER2
    with open(spec.BENCH / "configs" / f"{name}.json") as f:
        return json.load(f)


def test_qgemm_w8a8_hand_count():
    w = flops.qgemm_w8a8(M=1024, K=4608, N=4608)
    assert w.int8_ops == 2 * 1024 * 4608 * 4608 == 43_486_543_872
    # int8 x, int8 w, f32 row and column scales, bf16 out
    assert w.bytes == 4_718_592 + 21_233_664 + 22_528 + 9_437_184
    assert w.bf16_ops == 0


def test_paged_decode_hand_count():
    w = flops.paged_decode([100, 200], n_heads=24, n_kv_heads=8, head_dim=64)
    assert w.bf16_ops == 4 * 24 * 64 * 300 == 1_843_200
    # 300 cached tokens x 8 heads x (64 K + 64 V int8 codes + 2 f32 scales),
    # 2 rows of bf16 query and output (24 x 64 each), 2 int32 lengths
    assert w.bytes == 326_400 + 12_288 + 8


def test_granite_token_hand_count():
    c = _config("granite-moe-3b-a800m")
    w = flops.token_work(decoder.work(c), ctx=1)
    attn = 1536 * (1536 + 2 * 512) + 1536 * 1536          # q, k, v, o
    experts = 8 * 3 * 1536 * 512                          # top 8 of gate, up, down
    assert attn + experts == 25_165_824
    assert w.int8_ops == 2 * 25_165_824 * 32 == 1_610_612_736
    assert w.bf16_ops == 32 * (4 * 24 * 64 + 2 * 1536 * 40) + 2 * 1536 * 49155


def test_starcoder2_token_hand_count():
    c = _config("starcoder2-7b-l16")
    w = flops.token_work(decoder.work(c), ctx=1)
    per_layer = 4608 * (4608 + 2 * 512) + 4608 * 4608 + 2 * 4608 * 18432
    assert per_layer == 217_055_232
    assert w.int8_ops == 2 * per_layer * 16 == 6_945_767_424
    assert w.bf16_ops == 16 * 4 * 36 * 128 + 2 * 4608 * 49152


def test_prefill_is_its_tokens():
    c = _config("starcoder2-7b-l16")
    p = flops.prefill_work(decoder.work(c), prompt=10, reused=0)
    assert p.int8_ops == 10 * flops.token_work(decoder.work(c), 1).int8_ops
    # causal: token i attends to i + 1 positions; the head runs once
    attn = sum(flops.token_work(decoder.work(c), i + 1).bf16_ops for i in range(10))
    head = 2 * c["d_model"] * c["vocab"]
    assert p.bf16_ops == attn - 9 * head
    assert flops.prefill_work(decoder.work(c), prompt=10, reused=10) == flops.Work()


def test_prompt_rows_are_tokens_at_one_context_without_the_head():
    m = decoder.work(_config("granite-moe-3b-a800m"))
    w = flops.prompt_rows_work(m, rows=5, ctx=300)
    one = flops.token_work(m, 300)
    assert w.int8_ops == 5 * one.int8_ops
    assert w.bf16_ops == 5 * (one.bf16_ops - m.head_ops)
    assert flops.prompt_rows_work(m, rows=0, ctx=300) == flops.Work()
