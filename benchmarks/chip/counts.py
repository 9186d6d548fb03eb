"""Operations and bytes that the work needs, from shapes alone.

These count what a decode step or a prefill must do, not what today's
implementation happens to move: weights at their configured bits plus
one float32 scale per output channel, the bfloat16 output table that the
logits read, and the int8 K/V cache (plus one float32 scale per position
and head, for K and for V) over *live* positions only. An implementation
that gathers dead pages or stores 4-bit weights in int8 bytes moves more
than this, so its roofline share reads lower; none can read above 100%.
Two operations per multiply-accumulate.
"""
from __future__ import annotations


def linear_params(dims: dict) -> int:
    """Weights of the linears of one layer."""
    d, hd, f = dims["d_model"], dims["head_dim"], dims["d_ff"]
    q, kv = dims["n_heads"] * hd, dims["n_kv_heads"] * hd
    return d * q + 2 * d * kv + q * d + 3 * d * f


def out_channels(dims: dict) -> int:
    """Output channels of the linears of one layer (one scale each)."""
    d, hd, f = dims["d_model"], dims["head_dim"], dims["d_ff"]
    q, kv = dims["n_heads"] * hd, dims["n_kv_heads"] * hd
    return q + 2 * kv + d + 2 * f + d


def weight_bytes(dims: dict, bits: int) -> int:
    """Linears at ``bits`` with f32 scales, f32 norms, the bf16 table the
    logits read."""
    L, d = dims["n_layers"], dims["d_model"]
    return (L * linear_params(dims) * bits // 8 + L * out_channels(dims) * 4
            + (2 * L + 1) * d * 4 + dims["vocab"] * d * 2)


def kv_bytes_per_position(dims: dict) -> int:
    """Stored K and V of one position in every layer: int8 values and an
    f32 scale per head."""
    kv, hd = dims["n_kv_heads"], dims["head_dim"]
    return dims["n_layers"] * 2 * kv * (hd + 4)


def decode_step_bytes(dims: dict, bits: int, rows: int,
                      live_positions: int) -> int:
    """One packed decode step over ``rows`` requests that together attend
    ``live_positions`` positions: weights once, each row's embedding row,
    the live K/V."""
    return (weight_bytes(dims, bits) + rows * dims["d_model"] * 2
            + live_positions * kv_bytes_per_position(dims))


def attention_ops(dims: dict, positions: int) -> int:
    """Q.K and P.V over ``positions`` keys, every layer, one query."""
    return (dims["n_layers"] * 4 * dims["n_heads"] * dims["head_dim"]
            * positions)


def token_ops(dims: dict) -> int:
    """Linears of every layer for one token."""
    return 2 * dims["n_layers"] * linear_params(dims)


def logits_ops(dims: dict) -> int:
    return 2 * dims["vocab"] * dims["d_model"]


def decode_step_ops(dims: dict, rows: int, live_positions: int) -> int:
    return rows * (token_ops(dims) + logits_ops(dims)) \
        + attention_ops(dims, live_positions)


def prefill_ops(dims: dict, prompt_len: int) -> int:
    """One prompt: every position through the linears, causal attention
    (position p attends p + 1 keys), logits at the last position."""
    keys = prompt_len * (prompt_len + 1) // 2
    return (prompt_len * token_ops(dims) + attention_ops(dims, keys)
            + logits_ops(dims))
