"""jit'd wrapper: GQA decode attention against a KV cache, or against one
layer of a cache stacked over the layers, read in place."""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from repro.kernels.decode_attn.kernel import (decode_attention_kernel,
                                             head_group)


@functools.partial(jax.jit, static_argnames=(
    "softcap", "scale", "block_kv", "interpret"))
def decode_attention(q, cache_k, cache_v, lengths, *, layer=None,
                     softcap: float = 0.0, scale: float = 0.0,
                     block_kv: int = 512, interpret: bool = False):
    """q: (B, 1, Hq, D); lengths: (B,) number of valid cache positions per
    sequence. Without ``layer``, cache_k/v are (B, S, Hkv, D); with it,
    (L, B, S, Hkv, D), and the attention reads layer ``layer`` of them in
    place. With heads of 128, a 16-bit cache holds one KV head or an even
    number of them. Returns (B, 1, Hq, D)."""
    if layer is None:
        cache_k, cache_v, layer = cache_k[None], cache_v[None], 0
    b, _, hq, d = q.shape
    s, hkv = cache_k.shape[2], cache_k.shape[3]
    group = head_group(cache_k.dtype, hkv, d)
    if hkv % group:
        raise ValueError(f"decode_attention: {hkv} KV heads of "
                         f"{cache_k.dtype} do not split into groups of "
                         f"{group}")
    out = decode_attention_kernel(
        q.reshape(b, hkv // group, hq // hkv * group, d).astype(
            cache_k.dtype),
        cache_k, cache_v, lengths.astype(jnp.int32),
        jnp.asarray(layer, jnp.int32).reshape(1),
        scale=scale or 1.0 / math.sqrt(d), softcap=softcap,
        block_kv=min(block_kv, s), interpret=interpret)
    return out.reshape(b, 1, hq, d).astype(q.dtype)
