"""Operations and bytes the algorithm needs, from a configuration's
shapes and the context lengths of a step.

These count what serving the tokens requires, whatever kernel or
program implements it: each weight read once per step, the live keys
and values of each row's context read once, bf16 storage.  Attention
FLOPs are causal: a query at position i attends to i + 1 keys.
"""
from __future__ import annotations

from typing import Dict, Sequence

BYTES = 2          # bf16 weights, activations, cache


def _vd(c) -> int:
    return c.get("v_head_dim") or c["head_dim"]


def layer_matmul_params(c) -> int:
    """Weights of one layer that multiply every token."""
    D, F, H = c["d_model"], c["d_ff"], c["num_heads"]
    K, hd = c["num_kv_heads"], c["head_dim"]
    vd = _vd(c)
    return D * H * hd + D * K * hd + D * K * vd + H * vd * D + 3 * D * F


def weight_bytes(c) -> int:
    """Bytes of the weights one decode step reads: every layer's
    products, the norms and the output head (the embedding table when
    tied)."""
    L, D, V = c["num_layers"], c["d_model"], c["vocab_size"]
    norms = 0 if c.get("norm") == "nonparametric_ln" else 2 * L * D + D
    return BYTES * (L * layer_matmul_params(c) + V * D + norms)


def kv_bytes_per_token(c) -> int:
    """Cache bytes one token holds over all layers."""
    return BYTES * c["num_layers"] * c["num_kv_heads"] * (c["head_dim"]
                                                          + _vd(c))


def attn_flops_per_key(c) -> int:
    """FLOPs of one query against one key, over all heads and layers."""
    return 2 * c["num_layers"] * c["num_heads"] * (c["head_dim"] + _vd(c))


def decode_step(c, contexts: Sequence[int]) -> Dict[str, float]:
    """One decode step over rows whose new token sees ``contexts[r]``
    keys (itself included).  Returns flops, bytes (the whole step) and
    attn_flops, attn_bytes (attention alone: the cache read, plus each
    layer's queries in and outputs out)."""
    L, D, V, H = c["num_layers"], c["d_model"], c["vocab_size"], c["num_heads"]
    rows, keys = len(contexts), int(sum(contexts))
    proj = 2 * (L * layer_matmul_params(c) + D * V)
    attn_flops = keys * attn_flops_per_key(c)
    cache_read = keys * kv_bytes_per_token(c)
    attn_bytes = cache_read + BYTES * rows * L * H * (c["head_dim"] + _vd(c))
    step_bytes = (weight_bytes(c) + cache_read
                  + rows * (kv_bytes_per_token(c) + BYTES * D))
    return {"flops": float(rows * proj + attn_flops),
            "bytes": float(step_bytes), "attn_flops": float(attn_flops),
            "attn_bytes": float(attn_bytes)}


def prefill_flops(c, tokens: int, start: int, logits_rows: int = 1) -> float:
    """FLOPs of prefilling ``tokens`` prompt tokens at positions
    start .. start + tokens - 1 over everything before them, with
    ``logits_rows`` rows of output logits."""
    L, D, V = c["num_layers"], c["d_model"], c["vocab_size"]
    keys = tokens * start + tokens * (tokens + 1) // 2
    return float(2 * L * layer_matmul_params(c) * tokens
                 + keys * attn_flops_per_key(c) + 2 * D * V * logits_rows)
