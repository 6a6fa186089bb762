"""Operations and bytes the benchmark charges to the work it drives.

Counted from a configuration's published sizes, never from the program:
the arithmetic of a dense decoder layer (copied from the analytic model of
``benchmarks/roofline.py``: projections, causal attention averaged over the
context, SwiGLU feed-forward, the output head). Model FLOP utilisation
counts what the forward and backward passes require: recomputation does
not count, and causal attention counts only the unmasked half.
"""

from __future__ import annotations


def matmul_params(m: dict) -> int:
    """Parameters that take part in a matrix product per token: every
    layer's projections and feed-forward, and the output head."""
    d, hd = m["d_model"], m["n_heads"] * m["d_head"]
    kvd = m["n_kv_heads"] * m["d_head"]
    layer = d * (hd + 2 * kvd) + hd * d + 3 * d * m["d_ff"]
    return m["n_layers"] * layer + d * m["vocab_size"]


def param_count(m: dict) -> int:
    """All parameters: matrix ones, the embedding table when untied, and
    the norm scales."""
    n = matmul_params(m)
    if not m["tie_embeddings"]:
        n += m["vocab_size"] * m["d_model"]
    if m["norm"] == "rmsnorm":
        n += (2 * m["n_layers"] + 1) * m["d_model"]
    elif m["norm"] == "layernorm":
        n += 2 * (2 * m["n_layers"] + 1) * m["d_model"]
    return n


def attn_flops_per_token(m: dict, ctx: float) -> float:
    """Forward attention FLOPs of one token attending to ``ctx`` positions
    (scores and the weighted sum of values), over all layers."""
    return 4.0 * ctx * m["n_heads"] * m["d_head"] * m["n_layers"]


def forward_flops(m: dict, ctx: float) -> float:
    """Forward FLOPs of one token with ``ctx`` positions attended."""
    return 2.0 * matmul_params(m) + attn_flops_per_token(m, ctx)


def train_flops_per_token(m: dict, seq: int) -> float:
    """Forward and backward FLOPs per token of a causal training row of
    ``seq`` predicted tokens: 3 x forward, attention averaged over the
    causal context (seq + 1) / 2."""
    return 3.0 * forward_flops(m, (seq + 1) / 2.0)


def prefill_flops(m: dict, prompt: int) -> float:
    """Forward FLOPs of a causal prompt of ``prompt`` tokens."""
    return prompt * forward_flops(m, (prompt + 1) / 2.0)


def decode_flops(m: dict, prompt: int, n_new: int) -> float:
    """Forward FLOPs of ``n_new`` decoded tokens after a ``prompt``-token
    prefill: token ``j`` (1-based) attends to ``prompt + j`` positions."""
    ctx_sum = n_new * prompt + n_new * (n_new + 1) / 2.0
    return 2.0 * matmul_params(m) * n_new + (
        attn_flops_per_token(m, 1.0) * ctx_sum)
