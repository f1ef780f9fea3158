"""Operations and bytes that the work needs, from shapes and lengths.

These are the least a computation must do, not what a kernel happens to do:
padding rows count where a kernel's operands carry them (a kernel's roofline is
judged on the call it was given), and never in the model's work (``mfu``), so
padding waste shows there.
"""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Work:
    int8_ops: float = 0.0
    bf16_ops: float = 0.0
    bytes: float = 0.0

    def __add__(self, o: "Work") -> "Work":
        return Work(self.int8_ops + o.int8_ops, self.bf16_ops + o.bf16_ops,
                    self.bytes + o.bytes)

    def scale(self, k: float) -> "Work":
        return Work(self.int8_ops * k, self.bf16_ops * k, self.bytes * k)

    def least_seconds(self, pk) -> float:
        """The least time at the chip's peaks: compute and memory overlap, so
        the larger bound holds."""
        compute = self.int8_ops / pk.int8_ops + self.bf16_ops / pk.bf16_flops
        return max(compute, self.bytes / pk.hbm_bw)


def qgemm_w8a8(M: int, K: int, N: int) -> Work:
    """int8 (M, K) @ int8 (K, N) -> bf16 (M, N), with an f32 scale per row of
    the activations and per column of the weights."""
    return Work(int8_ops=2.0 * M * K * N,
                bytes=M * K + K * N + 4.0 * (M + N) + 2.0 * M * N)


def paged_decode(kv_lens, n_heads: int, n_kv_heads: int, head_dim: int) -> Work:
    """One decode-attention call over int8 K/V pages with an f32 scale per
    token and KV head: each slot's query (bf16) reads its ``kv_len`` cached
    tokens once and writes one bf16 output row."""
    tokens = float(sum(kv_lens))
    rows = len(kv_lens)
    return Work(bf16_ops=4.0 * n_heads * head_dim * tokens,
                bytes=tokens * n_kv_heads * (2 * head_dim + 2 * 4)
                + rows * n_heads * head_dim * 2 * 2 + rows * 4)


def linear_params(c: dict) -> tuple:
    """(int8 linear weights a token passes through in one block, router
    weights in one block)."""
    d, D = c["d_model"], c["head_dim"]
    hq, hkv = c["n_heads"] * D, c["n_kv_heads"] * D
    attn = d * (hq + 2 * hkv) + hq * d
    mats = 3 if c["act"].endswith("_glu") else 2
    if c.get("n_experts"):
        return attn + c["top_k"] * mats * d * c["d_ff_expert"], d * c["n_experts"]
    return attn + mats * d * c["d_ff"], 0


def token_work(c: dict, ctx: int) -> Work:
    """One token through the model, attending to ``ctx`` positions (itself
    included): int8 linears in every block; bf16 attention (QK and PV), router
    and the head."""
    lin, router = linear_params(c)
    L = c["n_layers"]
    attn = 4.0 * c["n_heads"] * c["head_dim"] * ctx
    return Work(int8_ops=2.0 * lin * L,
                bf16_ops=L * (attn + 2.0 * router) + 2.0 * c["d_model"] * c["vocab"])


def prefill_work(c: dict, prompt: int, reused: int) -> Work:
    """The prompt tokens a request computes (those after the ``reused`` prefix
    served from cache), each attending to every earlier position. The head
    runs once, for the last token."""
    n = prompt - reused
    if n <= 0:
        return Work()
    lin, router = linear_params(c)
    L = c["n_layers"]
    ctx_sum = n * reused + n * (n + 1) / 2.0
    return Work(int8_ops=2.0 * lin * L * n,
                bf16_ops=L * (4.0 * c["n_heads"] * c["head_dim"] * ctx_sum
                              + 2.0 * router * n) + 2.0 * c["d_model"] * c["vocab"])
