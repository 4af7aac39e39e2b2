"""Decode attention TPU kernel: one query token per sequence against a KV
cache stacked over the layers, read in place.

Grid = (batch, num_kv_blocks). Each step DMAs one block of ``block_kv``
consecutive positions of one (layer, sequence) with all of its KV heads,
a contiguous stretch of the cache, and the ``G = Hq / Hkv`` query heads of
each KV head attend to it; the partial-softmax state (m, l, acc) of every
head accumulates in scratch, the flash-decoding pattern. The layer index
and the per-sequence lengths are prefetched scalars. The K/V index map
clamps the block to the sequence's last live one, so the pipeline fetches
no block past the length (a repeated block index is not fetched again),
and ``pl.when`` skips their compute.

The KV heads are taken a group at a time (``head_group``): as many
neighbouring heads as share a 32-bit row of the block, two in bfloat16.
One strided load of the block's 32-bit rows gives a group's keys, ordered
(position, head), and the group's query heads attend to them in one
matmul whose scores of another head's keys are masked.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def head_group(dtype, heads: int, head_dim: int) -> int:
    """KV heads per group: those that share a 32-bit row of the cache, or
    all of them where fewer, or where a head is not one 128-lane row (the
    strided load takes rows of 128 lanes alone)."""
    if head_dim != 128:
        return heads
    return min(heads, max(1, 4 // jnp.dtype(dtype).itemsize))


def _head_groups(ref, group: int):
    """Each group's keys or values in a (1, 1, block_kv, heads, D) block,
    as (block_kv * group, D) rows ordered (position, head in the group)."""
    flat = ref.reshape(-1, ref.shape[-1])         # rows (position, head)
    n = ref.shape[-2] // group
    if n == 1:
        return [flat[...]]
    if group == 1:
        return [flat[i::n, :] for i in range(n)]
    words = flat.bitcast(jnp.uint32)              # rows (position, group)
    return [pltpu.bitcast(words[i::n, :], flat.dtype) for i in range(n)]


def _decode_kernel(len_ref, layer_ref, q_ref, k_ref, v_ref, o_ref, m_scr,
                   l_scr, acc_scr, *, scale: float, softcap: float,
                   block_kv: int, group: int):
    del layer_ref                       # read by the index maps alone
    b, j = pl.program_id(0), pl.program_id(1)
    length = len_ref[b]
    start = j * block_kv
    g = q_ref.shape[-2] // group        # query heads per KV head

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def attend(last: bool):
        """The block's attention; in the last live block, positions past
        the length may hold anything, NaN included: their scores are
        masked, and their values zeroed before p @ v."""
        groups = zip(_head_groups(k_ref, group), _head_groups(v_ref, group))
        for i, (k, v) in enumerate(groups):
            if last:
                pos = start + jax.lax.broadcasted_iota(
                    jnp.int32, v.shape, 0) // group
                v = jnp.where(pos < length, v.astype(jnp.float32),
                              0.0).astype(v.dtype)
            s = jax.lax.dot_general(
                q_ref[i], k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            if softcap:
                s = jnp.tanh(s / softcap) * softcap
            col = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            valid = None
            if group > 1:               # a query head sees its own KV head
                valid = jax.lax.broadcasted_iota(
                    jnp.int32, s.shape, 0) // g == col % group
            if last:
                live = start + col // group < length
                valid = live if valid is None else valid & live
            if valid is not None:
                s = jnp.where(valid, s, NEG_INF)
            m_prev = m_scr[i]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            corr = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new)
            l_scr[i] = l_scr[i] * corr + jnp.sum(p, axis=1, keepdims=True)
            acc_scr[i] = acc_scr[i] * corr + jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_scr[i] = m_new

    @pl.when(start + block_kv <= length)
    def _full():
        attend(last=False)

    @pl.when((start < length) & (length < start + block_kv))
    def _last():
        attend(last=True)

    @pl.when(j == pl.num_programs(1) - 1)
    def _finalize():
        o_ref[...] = (acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)
                      ).astype(o_ref.dtype)


def decode_attention_kernel(q, k, v, lengths, layer, *, scale: float,
                            softcap: float = 0.0, block_kv: int = 512,
                            interpret: bool = False):
    """q: (B, Hkv / group, group * G, D), the query heads of each group of
    ``head_group`` KV heads; k, v: (L, B, S, Hkv, D); lengths: (B,) int32
    valid positions of each sequence; layer: (1,) int32. Returns q's shape.
    The last block may run past S: its rows lie past every length."""
    b, ngroups, rows, d = q.shape
    hkv = k.shape[3]
    nkv = pl.cdiv(k.shape[2], block_kv)

    def kv_block(bi, j, len_ref, layer_ref):
        last = jnp.maximum(len_ref[bi] - 1, 0) // block_kv
        return (layer_ref[0], bi, jnp.minimum(j, last), 0, 0)

    def q_block(bi, j, len_ref, layer_ref):
        return (bi, 0, 0, 0)

    kv_spec = pl.BlockSpec((1, 1, block_kv, hkv, d), kv_block)
    q_spec = pl.BlockSpec((None, ngroups, rows, d), q_block)
    kernel = functools.partial(_decode_kernel, scale=scale, softcap=softcap,
                               block_kv=block_kv, group=hkv // ngroups)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, nkv),
            in_specs=[q_spec, kv_spec, kv_spec],
            out_specs=q_spec,
            scratch_shapes=[
                pltpu.VMEM((ngroups, rows, 1), jnp.float32),
                pltpu.VMEM((ngroups, rows, 1), jnp.float32),
                pltpu.VMEM((ngroups, rows, d), jnp.float32),
            ]),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        name="decode_attn",
        interpret=interpret,
    )(lengths, layer, q, k, v)
