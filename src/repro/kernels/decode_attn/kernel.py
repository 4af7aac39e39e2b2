"""Decode attention TPU kernel: one query token vs a long KV cache.

Grid = (batch*heads, num_kv_blocks); KV blocks stream through VMEM while
the partial-softmax state (m, l, acc) accumulates in scratch — the
flash-decoding pattern. Per-sequence lengths mask the tail; block sizes are
lane-aligned (block_kv = 128/256/512).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr,
                   acc_scr, *, scale: float, softcap: float, block_kv: int,
                   num_kv_blocks: int):
    bi = pl.program_id(0)
    ki = pl.program_id(1)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0].astype(jnp.float32)                  # (1, d)
    k = k_ref[0].astype(jnp.float32)                  # (block_kv, d)
    v = v_ref[0].astype(jnp.float32)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if softcap:
        s = jnp.tanh(s / softcap) * softcap
    kpos = ki * block_kv + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    valid = kpos < len_ref[bi]
    s = jnp.where(valid, s, NEG_INF)

    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    corr = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)
    l_scr[...] = l_scr[...] * corr + jnp.sum(p, axis=1, keepdims=True)
    acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_scr[...] = m_new

    @pl.when(ki == num_kv_blocks - 1)
    def _finalize():
        o_ref[0] = (acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)
                    ).astype(o_ref.dtype)


def decode_attention_kernel(q, k, v, lengths, *, scale: float,
                            softcap: float = 0.0, block_kv: int = 256,
                            interpret: bool = False):
    """q: (BH, 1, D); k, v: (BH, S, D); lengths: (BH,) valid KV lengths."""
    bh, _, d = q.shape
    skv = k.shape[1]
    nkv = skv // block_kv

    kernel = functools.partial(
        _decode_kernel, scale=scale, softcap=softcap, block_kv=block_kv,
        num_kv_blocks=nkv)

    return pl.pallas_call(
        kernel,
        grid=(bh, nkv),
        in_specs=[
            # whole lengths vector in SMEM: a rank-1 SMEM block must be the
            # full array or a multiple of 128 long
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, d), lambda b, j: (b, 0, 0)),
            pl.BlockSpec((1, block_kv, d), lambda b, j: (b, j, 0)),
            pl.BlockSpec((1, block_kv, d), lambda b, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, d), lambda b, j: (b, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, 1, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((1, 1), jnp.float32),
            pltpu.VMEM((1, 1), jnp.float32),
            pltpu.VMEM((1, d), jnp.float32),
        ],
        interpret=interpret,
    )(lengths, q, k, v)
