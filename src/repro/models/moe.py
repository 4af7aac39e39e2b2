"""Mixture-of-Experts with shard_map expert parallelism.

Token-choice top-k routing with capacity-bounded, sort-based dispatch —
the TPU-native adaptation of megablocks-style grouped matmul:

* **EP mode** (arctic: 128 experts % 16 == 0): experts sharded over the
  ``model`` axis. Activations are batch-sharded over ``data``/``pod`` and
  replicated over ``model``, so each model shard gathers *its own* experts'
  tokens from its local batch locally (no all-to-all needed), computes the
  grouped matmul, scatter-adds weighted outputs, and a single
  ``psum('model')`` combines expert contributions — the same collective
  volume as a TP FFN, with perfectly balanced expert placement.
  Expert weights are additionally FSDP-sharded over ``data`` and
  all-gathered inside the shard_map body (transpose = reduce-scatter on the
  backward pass).

* **TP mode** (granite: 40 experts % 16 != 0): experts replicated, the
  per-expert d_ff sharded over ``model``; the same body runs with
  ``E_local == E`` and psum combining ff-shard partials.

Tokens beyond an expert's capacity are dropped (GShard semantics) in
training; tests use a high capacity factor and compare against the dense
oracle. The serving path (``moe_serve``) drops none: it runs every held
expert on every token and weights each output by the routing, as the dense
oracle does. On one TPU chip and a few rows (``streams_held_experts``) the
``held_experts`` kernel computes it, reading each expert weight once
(``moe_serve_streamed``).

A config with ``moe_held`` holds a share of the experts (a chip's under
expert parallelism): the router keeps all ``num_experts`` outputs and picks
the top k of them, and the layer computes only the held experts' part of
the result; what the other experts would add is left out.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from jax.experimental.shard_map import shard_map

from repro.kernels.held_experts.ops import held_experts
from repro.models.layers import GATED, mlp_activate, nd_init

BIG = jnp.iinfo(jnp.int32).max


def moe_init(cfg, key, dtype):
    d, e = cfg.d_model, cfg.moe_held or cfg.num_experts
    ff = cfg.moe_d_ff or cfg.d_ff
    ks = jax.random.split(key, 4)
    gated = cfg.mlp_activation in GATED
    p = {
        "router": nd_init(ks[0], (d, cfg.num_experts), d, jnp.float32),
        "w_in": nd_init(ks[1], (e, d, ff), d, dtype),
        "w_out": nd_init(ks[2], (e, ff, d), ff, dtype),
    }
    if cfg.moe_parallelism == "ep":
        s = {
            "router": ("p_embed", "p_none"),
            "w_in": ("p_experts", "p_embed", "p_none"),
            "w_out": ("p_experts", "p_ff_in", "p_none"),
        }
    else:  # tp: ff over model, experts replicated
        s = {
            "router": ("p_embed", "p_none"),
            "w_in": ("p_none", "p_embed", "p_expert_ff"),
            "w_out": ("p_none", "p_expert_ff", "p_embed"),
        }
    if gated:
        p["w_gate"] = nd_init(ks[3], (e, d, ff), d, dtype)
        s["w_gate"] = s["w_in"]
    return p, s


def _dispatch_compute(x, ids, combine, w_in, w_gate, w_out, *,
                      activation: str, capacity: int, e0, e_local: int,
                      fsdp_axis: str):
    """Per-device MoE body. x: (Bl, S, d); ids/combine: (Bl, S, k)."""
    bl, s, d = x.shape
    k = ids.shape[-1]
    t = bl * s
    x_f = x.reshape(t, d)
    a = ids.reshape(t * k)                       # expert id per assignment
    tok = jnp.repeat(jnp.arange(t), k)           # token per assignment
    wgt = combine.reshape(t * k)

    if fsdp_axis:
        w_in = jax.lax.all_gather(w_in, fsdp_axis, axis=1, tiled=True)
        w_out = jax.lax.all_gather(w_out, fsdp_axis, axis=1, tiled=True)
        if w_gate is not None:
            w_gate = jax.lax.all_gather(w_gate, fsdp_axis, axis=1, tiled=True)

    mine = (a >= e0) & (a < e0 + e_local)
    key = jnp.where(mine, a - e0, BIG)
    order = jnp.argsort(key)                     # my assignments first, grouped
    sk = key[order]
    # rank within expert group: position - first index of the group
    change = jnp.concatenate([jnp.ones((1,), bool), sk[1:] != sk[:-1]])
    first = jnp.where(change, jnp.arange(t * k), 0)
    first = jax.lax.associative_scan(jnp.maximum, first)
    rank = jnp.arange(t * k) - first
    valid = (sk < BIG) & (rank < capacity)
    dest = jnp.where(valid, sk * capacity + rank, e_local * capacity)

    tok_o = tok[order]
    gathered = jnp.zeros((e_local * capacity + 1, d), x.dtype)
    gathered = gathered.at[dest].add(jnp.where(valid[:, None], x_f[tok_o], 0))
    gx = gathered[:-1].reshape(e_local, capacity, d)

    h = jnp.einsum("ecd,edf->ecf", gx, w_in, preferred_element_type=jnp.float32)
    g = (jnp.einsum("ecd,edf->ecf", gx, w_gate,
                    preferred_element_type=jnp.float32)
         if w_gate is not None else None)
    h = mlp_activate(activation, h, g).astype(x.dtype)
    y = jnp.einsum("ecf,efd->ecd", h, w_out,
                   preferred_element_type=jnp.float32).astype(x.dtype)

    y_f = y.reshape(e_local * capacity, d)
    y_assign = jnp.where(valid[:, None], y_f[jnp.where(valid, dest, 0)], 0)
    out = jnp.zeros((t, d), x.dtype)
    out = out.at[tok_o].add(y_assign * wgt[order][:, None].astype(x.dtype))
    return out.reshape(bl, s, d)


def _route(cfg, params, x):
    """The router's top-k experts of each token and their weights, a softmax
    over the k chosen logits: (gate_w, ids), each (B, S, k)."""
    logits = (x.astype(jnp.float32) @ params["router"])
    gate_w, ids = jax.lax.top_k(logits, cfg.num_experts_per_tok)
    return jax.nn.softmax(gate_w, axis=-1), ids


def moe_apply(env, cfg, params, x, *, capacity_factor: float = 2.0):
    """x: (B, S, d) -> (B, S, d). Router in fp32 outside shard_map."""
    b, s, d = x.shape
    e, k = cfg.num_experts, cfg.num_experts_per_tok
    held = cfg.moe_held or e
    with jax.named_scope("moe_route"):
        gate_w, ids = _route(cfg, params, x)                   # (B,S,k)

    dp = env.dp
    t_local = (b * s) // dp
    ep = cfg.moe_parallelism == "ep"
    tp = env.tp
    e_local = held // tp if ep else held
    capacity = max(8, int(capacity_factor * t_local * k / e))
    capacity = min(capacity, t_local * k)

    mesh = env.mesh
    x_spec = env.pspec("act_batch", None, None)
    id_spec = env.pspec("act_batch", None, None)
    if ep:
        w_spec = env.pspec("p_experts", "p_embed", None)
        w2_spec = env.pspec("p_experts", "p_ff_in", None)
    else:
        w_spec = env.pspec(None, None, "p_expert_ff")
        w2_spec = env.pspec(None, "p_expert_ff", None)
    model_ax = "model" if "model" in mesh.axis_names else None
    fsdp_ax = "data" if (ep and "data" in mesh.axis_names
                         and env.rules.get("p_embed") == "data") else ""

    def body(x_l, ids_l, wgt_l, w_in, w_gate, w_out):
        e0 = cfg.moe_held_start
        if ep and model_ax:
            e0 = e0 + jax.lax.axis_index(model_ax) * e_local
        out = _dispatch_compute(
            x_l, ids_l, wgt_l, w_in, w_gate, w_out,
            activation=cfg.mlp_activation, capacity=capacity,
            e0=e0, e_local=e_local, fsdp_axis=fsdp_ax)
        if model_ax:
            out = jax.lax.psum(out, model_ax)
        return out

    w_gate = params.get("w_gate")
    gate_spec = w_spec if w_gate is not None else None
    in_specs = [x_spec, id_spec, id_spec, w_spec,
                gate_spec if w_gate is not None else P(), w2_spec]
    out_spec = env.pspec("act_batch", None, None)
    with jax.named_scope("moe_experts"):
        if w_gate is None:
            w_gate = jnp.zeros((), x.dtype)  # placeholder, unused

            def body_nogate(x_l, i_l, g_l, wi, _pl, wo):
                return body(x_l, i_l, g_l, wi, None, wo)
            fn = shard_map(body_nogate, mesh=mesh, in_specs=tuple(in_specs),
                           out_specs=out_spec, check_rep=False)
        else:
            fn = shard_map(body, mesh=mesh, in_specs=tuple(in_specs),
                           out_specs=out_spec, check_rep=False)
        return fn(x, ids, gate_w, params["w_in"], w_gate, params["w_out"])


def _serve_route(cfg, params, x):
    """The serving path's routing: each held expert's routing weight of
    each token, (B, S, held) float32, 0 where the token was not routed to
    it; and counts (held,) int32, the routed assignments that land on each
    held expert."""
    held = cfg.moe_held or cfg.num_experts
    with jax.named_scope("moe_route"):
        gate_w, ids = _route(cfg, params, x)                   # (B,S,k)
        routed = jax.nn.one_hot(ids - cfg.moe_held_start, held,
                                dtype=jnp.float32)             # (B,S,k,E)
        w = jnp.einsum("bske,bsk->bse", routed, gate_w)
        counts = routed.sum((0, 1, 2)).astype(jnp.int32)
    return w, counts


def moe_serve(cfg, params, x):
    """The serving path: every held expert on every token, each expert's
    activations weighted by its routing weight (0 where the token was not
    routed to it) before the output projection, so no token is dropped.
    Returns (out, counts): out (B, S, d), and counts (held,) int32, the
    routed assignments that land on each held expert."""
    w, counts = _serve_route(cfg, params, x)
    with jax.named_scope("moe_experts"):
        h = jnp.einsum("bsd,edf->bsef", x, params["w_in"],
                       preferred_element_type=jnp.float32)
        g = (jnp.einsum("bsd,edf->bsef", x, params["w_gate"],
                        preferred_element_type=jnp.float32)
             if "w_gate" in params else None)
        h = (mlp_activate(cfg.mlp_activation, h, g)
             * w[..., None]).astype(x.dtype)
        out = jnp.einsum("bsef,efd->bsd", h, params["w_out"],
                         preferred_element_type=jnp.float32)
    return out.astype(x.dtype), counts


# v5e's ridge point in rows: its bf16 peak (197 TFLOP/s) over its HBM
# bandwidth (819 GB/s). Evaluating every held expert on R rows takes 2R
# operations for each 2-byte weight, so at or below this many rows the
# layer is bound by its weights' bytes.
RIDGE_ROWS = int(197e12 / 819e9)


def streams_held_experts(mesh_size: int, platform: str, rows: int) -> bool:
    """Whether the serving path runs its held experts through the
    ``held_experts`` kernel (``moe_serve_streamed``): on one TPU chip (a
    custom call is not partitioned over a mesh), for at most
    ``RIDGE_ROWS`` rows, where streaming the weights bounds the layer.
    Above them the layer is bound by its operations, and XLA's einsums
    (``moe_serve``) keep it."""
    return mesh_size == 1 and platform == "tpu" and rows <= RIDGE_ROWS


def moe_serve_streamed(cfg, params, x, shared=None, *, layer=None):
    """``moe_serve`` through the ``held_experts`` kernel, which reads each
    held expert's weights once; with ``shared``, the shared expert's MLP
    weights, the layer's output also holds the shared expert's, folded
    into the kernel as one more expert of weight 1 and accumulated in
    float32. With ``layer``, ``params`` and ``shared`` are stacked over the
    layers and the kernel reads its layer's in place. Returns (out, counts)
    as ``moe_serve`` does."""
    route = params
    if layer is not None:
        route = {"router": jax.lax.dynamic_index_in_dim(
            params["router"], layer, 0, keepdims=False)}
    w, counts = _serve_route(cfg, route, x)
    with jax.named_scope("moe_experts"):
        out = held_experts(
            x, w, params, shared, layer=layer,
            activate=functools.partial(mlp_activate, cfg.mlp_activation))
    return out, counts


def moe_ref(cfg, params, x):
    """Dense oracle: ``moe_serve``'s output. No capacity limit — matches
    moe_apply when nothing is dropped."""
    return moe_serve(cfg, params, x)[0]
