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


@dataclasses.dataclass(frozen=True)
class ModelWork:
    """What one token costs a model, as its module's ``work(c)`` gives it:
    int8 and bf16 operations that do not depend on the context, bf16
    operations per attended position (attention's QK and PV over every layer
    that attends), and the head's operations for one sampled row."""
    int8_ops: float
    bf16_ops: float
    bf16_ops_per_position: float
    head_ops: float


def token_work(m: ModelWork, ctx: int) -> Work:
    """One decoded token, attending to ``ctx`` positions (itself included)."""
    return Work(int8_ops=m.int8_ops,
                bf16_ops=m.bf16_ops_per_position * ctx + m.bf16_ops + m.head_ops)


def prefill_work(m: ModelWork, prompt: int, reused: int) -> Work:
    """The prompt tokens a request computes (those after the ``reused`` prefix
    served from cache), each attending to every earlier position. The head
    runs once, for the last token."""
    n = prompt - reused
    if n <= 0:
        return Work()
    ctx_sum = n * reused + n * (n + 1) / 2.0
    return Work(int8_ops=m.int8_ops * n,
                bf16_ops=m.bf16_ops_per_position * ctx_sum + m.bf16_ops * n + m.head_ops)


def prompt_rows_work(m: ModelWork, rows: float, ctx: float) -> Work:
    """``rows`` prompt tokens computed in packed chunks, each attending to
    ``ctx`` positions; no head (a prompt's head runs with its first token)."""
    return Work(int8_ops=m.int8_ops * rows,
                bf16_ops=(m.bf16_ops_per_position * ctx + m.bf16_ops) * rows)
