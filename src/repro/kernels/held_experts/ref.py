"""Pure-jnp oracle for the held experts kernel."""
from __future__ import annotations

import jax.numpy as jnp


def held_experts_ref(x, weights, params, shared=None, *, activate):
    """``sum_e weights[..., e] * expert_e(x)`` (plus ``shared(x)``) in
    float32, each expert's activations cast to the weights' type before
    its output projection. x: (..., d); weights: (..., E)."""
    def ffn(w_in, w_gate, w_out, wgt):
        h = jnp.einsum("...d,edf->...ef", x, w_in,
                       preferred_element_type=jnp.float32)
        g = (jnp.einsum("...d,edf->...ef", x, w_gate,
                        preferred_element_type=jnp.float32)
             if w_gate is not None else None)
        a = (activate(h, g) * wgt[..., None]).astype(w_out.dtype)
        return jnp.einsum("...ef,efd->...d", a, w_out,
                          preferred_element_type=jnp.float32)

    out = ffn(params["w_in"], params.get("w_gate"), params["w_out"],
              weights.astype(jnp.float32))
    if shared is not None:
        out = out + ffn(*(None if shared.get(n) is None else shared[n][None]
                          for n in ("w_in", "w_gate", "w_out")),
                        jnp.ones(weights.shape[:-1] + (1,), jnp.float32))
    return out.astype(x.dtype)
