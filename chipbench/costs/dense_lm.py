"""Operations and bytes of a dense decoder from its shapes alone.

``dims`` holds ``num_layers``, ``d_model``, ``num_heads``,
``num_kv_heads``, ``head_dim``, ``d_ff`` and ``vocab_size``; the MLP is
not gated (two matrices). FLOPs are model FLOPs: 2 per weight touched
per token, plus attention's 4 x heads x head_dim per key attended, plus
the head for each row whose logits are computed. Nothing recomputed or
padded counts.
"""
from __future__ import annotations

from typing import Dict


def layer_params(dims: Dict) -> int:
    d, hq, hkv, dh = (dims["d_model"], dims["num_heads"],
                      dims["num_kv_heads"], dims["head_dim"])
    attn = 2 * d * hq * dh + 2 * d * hkv * dh
    return attn + 2 * d * dims["d_ff"]


def forward_flops(dims: Dict, tokens: int, keys_attended: int,
                  logit_rows: int) -> int:
    """``tokens`` new tokens that attend to ``keys_attended`` keys in all
    (summed over the tokens), with logits for ``logit_rows`` of them."""
    L = dims["num_layers"]
    return (2 * L * layer_params(dims) * tokens
            + 4 * L * dims["num_heads"] * dims["head_dim"] * keys_attended
            + 2 * dims["d_model"] * dims["vocab_size"] * logit_rows)


def prefill_flops(dims: Dict, prompt: int) -> int:
    """A causal prefill of ``prompt`` tokens, logits of the last only."""
    return forward_flops(dims, prompt, prompt * (prompt + 1) // 2, 1)


def decode_flops(dims: Dict, batch: int, keys_attended: int) -> int:
    """One decode step of ``batch`` sequences attending to
    ``keys_attended`` keys in all."""
    return forward_flops(dims, batch, keys_attended, batch)


def decode_bytes(dims: Dict, batch: int, keys_attended: int,
                 w_bytes: int = 2, kv_bytes: int = 2,
                 norm_bytes: int = 4) -> int:
    """HBM bytes one decode step of ``batch`` sequences must move at
    least: every layer weight and the head, the batch's embedding rows,
    the norm scales, the keys and values of the ``keys_attended`` keys
    (summed over the sequences) and the new token's keys and values."""
    L, d = dims["num_layers"], dims["d_model"]
    kv = dims["num_kv_heads"] * dims["head_dim"]
    weights = (L * layer_params(dims) + d * dims["vocab_size"]) * w_bytes
    norms = (2 * L + 1) * d * norm_bytes
    embed_rows = batch * d * w_bytes
    cache_read = 2 * L * keys_attended * kv * kv_bytes
    cache_write = 2 * L * batch * kv * kv_bytes
    return weights + norms + embed_rows + cache_read + cache_write
