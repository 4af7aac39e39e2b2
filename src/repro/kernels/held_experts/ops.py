"""Wrapper: the held experts of an expert layer, and its shared expert, on a
few rows in one pass over their weights. It is traced inside its caller's
jit, which fixes the activation."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.held_experts.kernel import held_experts_kernel

# rows are padded to a whole number of 16-bit sublane tiles
ROW_TILE = 16


def _weights(p, names, layer):
    """``p``'s weights among ``names``, each stacked over the layers: as
    they are with ``layer``, else with a leading axis of one."""
    ws = tuple(p[n] for n in names if n in p)
    return ws if layer is not None else tuple(w[None] for w in ws)


def held_experts(x, weights, params, shared=None, *, activate, layer=None,
                 block_ff: int = 256, interpret: bool = False):
    """x: (..., d); weights: (..., E), each held expert's routing weight of
    each row (0 where the row was not routed to it); params: the held
    experts' ``w_in``, ``w_gate`` (for a gated activation) and ``w_out``,
    (E, d, ff) and (E, ff, d); shared: None or the shared expert's MLP
    weights, (d, sff) and (sff, d), which every row takes with weight 1;
    activate: (h, g) -> activations, g None without a gate.
    With ``layer``, every weight is stacked over the layers and read at
    ``layer`` in place. Returns ``sum_e weights[e] * expert_e(x)`` (plus
    ``shared(x)``) in x's shape and type."""
    d = x.shape[-1]
    rows = x.size // d
    pad = -rows % ROW_TILE
    xf = jnp.pad(x.reshape(rows, d), ((0, pad), (0, 0)))
    w = jnp.pad(weights.reshape(rows, -1).astype(jnp.float32),
                ((0, pad), (0, 0))).T[..., None]
    names = ("w_in", "w_gate", "w_out")
    out = held_experts_kernel(
        xf, w, _weights(params, names, layer),
        jnp.asarray(0 if layer is None else layer, jnp.int32).reshape(1),
        None if shared is None else _weights(shared, names, layer),
        activate=activate, block_ff=block_ff, interpret=interpret)
    return out[:rows].reshape(x.shape)
