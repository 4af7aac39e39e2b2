"""Held experts TPU kernel: every expert a chip holds, and the shared expert
beside them where there is one, on a few rows, each weight read from HBM
once.

The serving path evaluates every held expert on every row and weights each
expert's activations by its routing weight, 0 where the row was not routed
to it (``moe.moe_serve``). On a few rows that is bound by the weights'
bytes. The grid is one axis of steps: block f of expert e's ff columns at
step ``e * nf + f``, then the shared expert's blocks. Each step's blocks of
``w_in``, ``w_gate`` and ``w_out`` are fetched by the pipeline while the MXU
works on the step before. The step's output, ``activate(x @ w_in,
x @ w_gate) * weight`` in float32, cast to the weights' type, times the
``w_out`` block, accumulates in a resident float32 (rows, d) block, cast
once after the last step. The shared expert takes weight 1. The weights
are stacked over the layers and read in place at a prefetched layer index.
A block index that does not change from one step to the next is not
fetched again, so the shared expert's first blocks are fetched once, at
the start, and the held experts' last blocks are not fetched again during
the shared steps.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# VMEM beyond the pipeline's buffers, for the compiler's own temporaries
# (the (rows, block) float32 activations among them)
VMEM_SLACK = 16 << 20


def ff_block(ff: int, block: int) -> int:
    """The ff columns of a step: ``block`` where it divides ``ff``, else
    all of them."""
    return block if ff % block == 0 else ff


def _kernel(layer_ref, x_ref, w_ref, *refs, held_steps: int, nf: int,
            activate, gated: bool, shared: bool):
    del layer_ref                       # read by the index maps alone
    n = 3 if gated else 2
    held, refs = refs[:n], refs[n:]
    if shared:
        common, refs = refs[:n], refs[n:]
    o_ref, acc_ref = refs
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def expert(ws, weight):
        x = x_ref[...]
        h = jnp.dot(x, ws[0][...], preferred_element_type=jnp.float32)
        g = (jnp.dot(x, ws[1][...], preferred_element_type=jnp.float32)
             if gated else None)
        a = activate(h, g)
        if weight is not None:
            a = a * weight
        acc_ref[...] += jnp.dot(a.astype(ws[-1].dtype), ws[-1][...],
                                preferred_element_type=jnp.float32)

    @pl.when(i < held_steps)
    def _held():
        expert(held, w_ref[i // nf])

    if shared:
        @pl.when(i >= held_steps)
        def _shared():
            expert(common, None)

    @pl.when(i == pl.num_programs(0) - 1)
    def _out():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def held_experts_kernel(x, weights, held, layer, shared=None, *, activate,
                        block_ff: int = 256, interpret: bool = False):
    """x: (R, d); weights: (E, R, 1) float32, each held expert's routing
    weight of each row; held: (w_in, w_gate, w_out), or (w_in, w_out) for
    an activation without a gate, each stacked over the layers: (L, E, d,
    ff) and (L, E, ff, d); shared: None or the shared expert's, (L, d, sff)
    and (L, sff, d); layer: (1,) int32; activate: (h, g) -> activations,
    g None without a gate. Returns (R, d) in x's type."""
    rows, d = x.shape
    experts, ff = held[0].shape[1], held[0].shape[-1]
    bf = ff_block(ff, block_ff)
    nf = ff // bf
    held_steps = experts * nf
    steps = held_steps

    def in_block(i, layer_ref):
        j = jnp.minimum(i, held_steps - 1)
        return (layer_ref[0], j // nf, 0, j % nf)

    def out_block(i, layer_ref):
        j = jnp.minimum(i, held_steps - 1)
        return (layer_ref[0], j // nf, j % nf, 0)

    def whole(i, layer_ref):
        return (0, 0)

    specs = [pl.BlockSpec((None, None, d, bf), in_block)] * (len(held) - 1)
    specs.append(pl.BlockSpec((None, None, bf, d), out_block))
    args = list(held)
    step_bytes = 3 * d * bf * held[0].dtype.itemsize
    if shared is not None:
        sbf = ff_block(shared[0].shape[-1], block_ff)

        def s_in_block(i, layer_ref):
            return (layer_ref[0], 0, jnp.maximum(i - held_steps, 0))

        def s_out_block(i, layer_ref):
            return (layer_ref[0], jnp.maximum(i - held_steps, 0), 0)

        specs += [pl.BlockSpec((None, d, sbf), s_in_block)] * (
            len(shared) - 1)
        specs.append(pl.BlockSpec((None, sbf, d), s_out_block))
        args += list(shared)
        steps += shared[0].shape[-1] // sbf
        step_bytes += 3 * d * sbf * shared[0].dtype.itemsize
    row_spec = pl.BlockSpec((rows, d), whole)
    kernel = functools.partial(
        _kernel, held_steps=held_steps, nf=nf, activate=activate,
        gated=len(held) == 3, shared=shared is not None)
    # every input double-buffered; the routing weights' lanes pad to 128
    vmem = 2 * (step_bytes + 2 * rows * d * x.dtype.itemsize
                + experts * rows * 128 * 4) + rows * d * 4 + VMEM_SLACK
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(steps,),
            in_specs=[row_spec,
                      pl.BlockSpec((experts, rows, 1),
                                   lambda i, layer_ref: (0, 0, 0)),
                      *specs],
            out_specs=row_spec,
            scratch_shapes=[pltpu.VMEM((rows, d), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((rows, d), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",), vmem_limit_bytes=vmem),
        name="held_experts",
        interpret=interpret,
    )(layer, x, weights, *args)
