"""jit'd wrapper for the CIAO cached gather."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.ciao_gather.kernel import ciao_gather_kernel


def _to_words(table):
    """(N, D) table -> (N, 1, W) 32-bit words, bit for bit. The kernel moves
    whole rows, so packing 16- or 8-bit elements in pairs or quads is exact
    and spares the kernel a sub-32-bit row layout."""
    n, d = table.shape
    size = table.dtype.itemsize
    if size == 4:
        return table.reshape(n, 1, d)
    if size > 4 or d * size % 4:
        raise ValueError(
            f"ciao_gather packs rows into 32-bit words: a {table.dtype} row "
            f"of {d} elements is {d * size} bytes, not a multiple of 4")
    per = 4 // size
    packed = jax.lax.bitcast_convert_type(
        table.reshape(n, d // per, per), jnp.uint32)
    return packed.reshape(n, 1, d // per)


def _from_words(words, dtype, d):
    t = words.shape[0]
    if jnp.dtype(dtype).itemsize == 4:
        return words.reshape(t, d)
    return jax.lax.bitcast_convert_type(
        words.reshape(t, -1), dtype).reshape(t, d)


@functools.partial(jax.jit, static_argnames=(
    "c_main", "c_iso", "block_t", "interpret"))
def ciao_gather(table, indices, streams, iso_map, *, c_main: int = 256,
                c_iso: int = 64, block_t: int = 128,
                interpret: bool = False):
    """Gather ``table[indices]`` through the two-partition VMEM cache.

    table: (N, D) with rows a multiple of 4 bytes; indices: (T,) int32 row
    ids; streams: (T,) int32 stream id per request; iso_map: (S,) int32
    isolation bits from the host detector. Returns (out (T, D), stats
    (S, 2) [hits, misses])."""
    t = indices.shape[0]
    s = iso_map.shape[0]
    bt = min(block_t, t)
    pad = (-t) % bt
    if pad:
        # route padding to a phantom stream so real stats stay exact
        indices = jnp.pad(indices, (0, pad), constant_values=indices[-1])
        streams = jnp.pad(streams, (0, pad), constant_values=s)
        iso_map = jnp.pad(iso_map, (0, 1))
    words, stats = ciao_gather_kernel(
        _to_words(table), indices.astype(jnp.int32),
        streams.astype(jnp.int32), iso_map.astype(jnp.int32),
        c_main=c_main, c_iso=c_iso, block_t=bt, interpret=interpret)
    out = _from_words(words[:t], table.dtype, table.shape[1])
    return out, stats[:s]
