"""CIAO software-managed VMEM cache kernel (the paper's §III-B on TPU).

Irregular row-gather (embedding rows / KV pages / SpMV index arrays — the
paper's §VI motivation) from an HBM-resident table, staged through a
**two-partition direct-mapped VMEM block cache**:

  * partition 0 ("L1D")        — slots [0, c_main)
  * partition 1 ("unused smem") — slots [c_main, c_main + c_iso): request
    *streams* flagged as interferers by the host-side
    :class:`InterferenceDetector` are redirected here, exactly like CIAO
    redirects interfering warps — isolation is structural (the partition is
    a pure function of the stream's isolation bit), so the single-copy
    coherence invariant of §IV-B holds by construction.

Tags live in **SMEM scratch**, data rows in **VMEM scratch** — the TPU
analogue of the paper's tags-in-the-opposite-bank-group placement: a tag
probe and the data access touch different memories and proceed in parallel.

Per-stream hit/miss counters are emitted (SMEM-accumulated) as the VTA-style
feedback the host scheduler consumes.

Layout. The request indices, streams and isolation bits are scalar-prefetched
into SMEM. Rows sit on a leading untiled axis — the table is ``(N, 1, W)``,
the cache ``(C, 1, W)`` and the output block ``(block_t, 1, W)`` — so one row
is one whole tile and a dynamic row index needs no sublane alignment. A miss
DMAs its row from the HBM table into its cache slot and waits for it; every
request then reads its row from the cache. ``W`` counts 32-bit words
(``ops.py`` packs narrower dtypes).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _gather_kernel(idx_ref, stream_ref, iso_ref, table_ref, out_ref,
                   stats_ref, tags_scr, data_scr, cnt_scr, sem, *,
                   block_t: int, c_main: int, c_iso: int, num_streams: int,
                   num_blocks: int):
    step = pl.program_id(0)

    @pl.when(step == 0)
    def _init():
        def clear(s, c):
            tags_scr[s] = jnp.int32(-1)
            return c
        jax.lax.fori_loop(0, c_main + c_iso, clear, 0)
        for s in range(num_streams):
            cnt_scr[s, 0] = jnp.int32(0)
            cnt_scr[s, 1] = jnp.int32(0)

    def body(i, c):
        g = step * block_t + i
        idx = idx_ref[g]
        stream = stream_ref[g]
        # partition choice: direct-mapped slot in main or isolated region
        slot = jnp.where(iso_ref[stream] > 0,
                         c_main + jax.lax.rem(idx, jnp.int32(c_iso)),
                         jax.lax.rem(idx, jnp.int32(c_main)))
        miss = tags_scr[slot] != idx

        @pl.when(miss)
        def _fill():
            copy = pltpu.make_async_copy(table_ref.at[idx],
                                         data_scr.at[slot], sem)
            copy.start()
            copy.wait()
            tags_scr[slot] = idx

        out_ref[i] = data_scr[slot]
        # per-stream hit/miss counters (VTA-style feedback)
        col = miss.astype(jnp.int32)
        cnt_scr[stream, col] = cnt_scr[stream, col] + 1
        return c

    jax.lax.fori_loop(0, block_t, body, 0)

    @pl.when(step == num_blocks - 1)
    def _emit():
        for s in range(num_streams):
            stats_ref[s, 0] = cnt_scr[s, 0]
            stats_ref[s, 1] = cnt_scr[s, 1]


def ciao_gather_kernel(table, indices, streams, iso_map, *,
                       c_main: int = 256, c_iso: int = 64,
                       block_t: int = 128, interpret: bool = False):
    """table: (N, 1, W) 32-bit words; indices/streams: (T,) int32 with
    ``T % block_t == 0``; iso_map: (S,) int32. Returns
    (out (T, 1, W), stats (S, 2) int32 [hits, misses] per stream)."""
    t = indices.shape[0]
    n, one, w = table.shape
    assert one == 1 and table.dtype.itemsize == 4, table.shape
    num_streams = iso_map.shape[0]
    c_iso = max(c_iso, 1)
    nb = t // block_t

    kernel = functools.partial(
        _gather_kernel, block_t=block_t, c_main=c_main, c_iso=c_iso,
        num_streams=num_streams, num_blocks=nb)

    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(nb,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],  # table in HBM
            out_specs=[
                pl.BlockSpec((block_t, 1, w), lambda i, *_: (i, 0, 0)),
                pl.BlockSpec(memory_space=pltpu.SMEM),
            ],
            scratch_shapes=[
                pltpu.SMEM((c_main + c_iso,), jnp.int32),         # tags
                pltpu.VMEM((c_main + c_iso, 1, w), table.dtype),  # data
                pltpu.SMEM((num_streams, 2), jnp.int32),          # counters
                pltpu.SemaphoreType.DMA(()),
            ]),
        out_shape=[
            jax.ShapeDtypeStruct((t, 1, w), table.dtype),
            jax.ShapeDtypeStruct((num_streams, 2), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(indices, streams, iso_map, table)
