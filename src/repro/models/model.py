"""Unified scan-over-layers LM covering all 10 assigned architectures.

Public API:
  init_params(cfg, key, run)          -> params pytree
  param_specs(cfg)                    -> parallel pytree of logical-axis tuples
  forward_train(env, cfg, params, batch, run) -> (B, S, d) final hidden
  loss_fn(env, cfg, params, batch, run)       -> scalar CE loss
  init_cache(cfg, batch, max_len)     -> decode cache pytree
  cache_specs(cfg)                    -> logical-axis tuples for the cache
  prefill(env, cfg, params, batch, run)       -> (last_logits, cache, pos)
  decode_step(env, cfg, params, token, pos, cache, run) -> (logits, cache)
  input_specs(cfg, shape, run)        -> ShapeDtypeStruct stand-ins per mode

Layer stacks are scanned over the repeating block ``pattern`` (HLO size is
O(1) in depth); remainder layers run unscanned. Decode positions are
per-sequence ``(B,)`` vectors so the serving engine can batch ragged
sequences.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import (
    ATTN_BLOCKS, BLOCK_GLOBAL_ATTN, BLOCK_LOCAL_ATTN, BLOCK_RGLRU, BLOCK_SSD,
    ModelConfig, RunConfig, ShapeConfig)
from repro.kernels.decode_attn.ops import decode_attention
from repro.models import attention as attn
from repro.models import layers as L
from repro.models import moe as moe_mod
from repro.models import rglru as rglru_mod
from repro.models import ssd as ssd_mod
from repro.parallel.sharding import ShardEnv

# The named scopes (``jax.named_scope``) of the train, prefill and decode
# programs. They are metadata only: each compiled instruction's ``op_name``
# carries the scopes it lies in, and a profile's operations can be summed by
# them. Every block's work lies in exactly one block scope.
SCOPES = (
    "embed",       # token (and frontend or encoder) embedding
    "layers",      # the layer scan and the remainder blocks; what lies under
                   # it and under no block scope is the scan's own work (the
                   # per-layer slicing of the stacked weights; in decode, the
                   # per-layer read of the carried cache where no kernel
                   # reads it in place, and the write of an RG-LRU block's
                   # state into it)
    "attn_qkv",    # ln1, the q/k/v projections, rotary
    "kv_write",    # writing the new keys and values into the cache
    "attend",      # the attention itself (attention_core / decode_attend,
                   # or the decode_attn kernel on the carried cache)
    "attn_out",    # the output projection and its residual
    "cross_attn",  # the cross-attention of an encoder-decoder block
    "mlp",         # ln2, the MLP or MoE, and its residual
    "rglru",       # ln1, the RG-LRU mixer and its residual
    "ssd",         # ln1, the SSD mixer and its residual
    "head",        # the final norm and the logits (and the loss)
    "moe_route",   # in mlp: the router, its top-k and their weights
    "moe_experts", # in mlp: the routed experts' matmuls (and dispatch)
    "moe_shared",  # in mlp: the shared expert (the MLP beside the experts)
    "ssd_state",   # in ssd: the state recurrence and its write into the
                   # stacked cache (decode), or the chunked scan (prefill)
)


# ============================================================ block builders
def _ffn_init(cfg: ModelConfig, ks, p, s, dtype):
    """ln2 and the FFN after a block's mixer: the MLP, or the MoE and (with
    ``moe_dense_residual``, a shared expert) the MLP beside it."""
    p["ln2"], s["ln2"] = L.rmsnorm_init(cfg.d_model)
    if cfg.num_experts:
        p["moe"], s["moe"] = moe_mod.moe_init(cfg, ks[1], dtype)
    if not cfg.num_experts or cfg.moe_dense_residual:
        p["mlp"], s["mlp"] = L.mlp_init(
            ks[2], cfg.d_model, cfg.d_ff, cfg.mlp_activation, dtype)


def _block_init(cfg: ModelConfig, kind: str, key, dtype):
    ks = jax.random.split(key, 8)
    if kind in ATTN_BLOCKS:
        p: Dict[str, Any] = {}
        s: Dict[str, Any] = {}
        p["ln1"], s["ln1"] = L.rmsnorm_init(cfg.d_model)
        p["attn"], s["attn"] = attn.attn_init(cfg, ks[0], dtype)
        _ffn_init(cfg, ks, p, s, dtype)
        if cfg.is_encoder_decoder:
            p["ln_cross"], s["ln_cross"] = L.rmsnorm_init(cfg.d_model)
            p["cross"], s["cross"] = attn.attn_init(cfg, ks[3], dtype, cross=True)
        return p, s
    if kind == BLOCK_RGLRU:
        p, s = {}, {}
        p["ln1"], s["ln1"] = L.rmsnorm_init(cfg.d_model)
        p["rglru"], s["rglru"] = rglru_mod.rglru_init(cfg, ks[0], dtype)
        p["ln2"], s["ln2"] = L.rmsnorm_init(cfg.d_model)
        p["mlp"], s["mlp"] = L.mlp_init(
            ks[1], cfg.d_model, cfg.d_ff, cfg.mlp_activation, dtype)
        return p, s
    if kind == BLOCK_SSD:
        p, s = {}, {}
        p["ln1"], s["ln1"] = L.rmsnorm_init(cfg.d_model)
        p["ssd"], s["ssd"] = ssd_mod.ssd_init(cfg, ks[0], dtype)
        if cfg.ssd_ffn:
            _ffn_init(cfg, ks, p, s, dtype)
        return p, s
    raise ValueError(kind)


def _encoder_block_init(cfg, key, dtype):
    ks = jax.random.split(key, 3)
    p, s = {}, {}
    p["ln1"], s["ln1"] = L.rmsnorm_init(cfg.d_model)
    p["attn"], s["attn"] = attn.attn_init(cfg, ks[0], dtype)
    p["ln2"], s["ln2"] = L.rmsnorm_init(cfg.d_model)
    p["mlp"], s["mlp"] = L.mlp_init(
        ks[1], cfg.d_model, cfg.d_ff, cfg.mlp_activation, dtype)
    return p, s


def _add_layers_axis(specs):
    return jax.tree.map(
        lambda sp: ("layers",) + sp,
        specs,
        is_leaf=lambda x: isinstance(x, tuple) and all(
            isinstance(e, (str, type(None))) for e in x))


def _stack_init(init_one, repeats: int, key):
    keys = jax.random.split(key, repeats)
    params = jax.vmap(lambda k: init_one(k)[0])(keys)
    _, specs = init_one(key)
    return params, _add_layers_axis(specs)


# ================================================================= full init
def _init(cfg: ModelConfig, key, dtype):
    ks = jax.random.split(key, 12)
    p: Dict[str, Any] = {}
    s: Dict[str, Any] = {}
    p["embed"], s["embed"] = L.embed_init(ks[0], cfg.vocab_size, cfg.d_model, dtype)
    if not cfg.tie_embeddings:
        p["lm_head"], s["lm_head"] = L.lm_head_init(
            ks[1], cfg.d_model, cfg.vocab_size, dtype)

    reps = cfg.scan_repeats
    if reps:
        stack_p, stack_s = {}, {}
        for i, kind in enumerate(cfg.pattern):
            stack_p[f"b{i}"], stack_s[f"b{i}"] = _stack_init(
                lambda k, kind=kind: _block_init(cfg, kind, k, dtype),
                reps, ks[2 + i % 4])
        p["stack"], s["stack"] = stack_p, stack_s
    rem_p, rem_s = [], []
    for i, kind in enumerate(cfg.remainder_blocks):
        bp, bs = _block_init(cfg, kind, jax.random.fold_in(ks[6], i), dtype)
        rem_p.append(bp)
        rem_s.append(bs)
    if rem_p:
        p["rem"], s["rem"] = tuple(rem_p), tuple(rem_s)
    p["final_norm"], s["final_norm"] = L.rmsnorm_init(cfg.d_model)

    if cfg.is_encoder_decoder:
        enc_p, enc_s = {}, {}
        enc_p["stack"], enc_s["stack"] = _stack_init(
            lambda k: _encoder_block_init(cfg, k, dtype),
            cfg.num_encoder_layers, ks[7])
        enc_p["final_norm"], enc_s["final_norm"] = L.rmsnorm_init(cfg.d_model)
        p["encoder"], s["encoder"] = enc_p, enc_s
    return p, s


def init_params(cfg: ModelConfig, key, run: Optional[RunConfig] = None):
    dtype = jnp.dtype((run or RunConfig()).param_dtype)
    return _init(cfg, key, dtype)[0]


def param_specs(cfg: ModelConfig):
    box = {}

    def f(key):
        params, specs = _init(cfg, key, jnp.bfloat16)
        box["s"] = specs
        return params

    jax.eval_shape(f, jax.random.PRNGKey(0))
    return box["s"]


def param_shapes(cfg: ModelConfig, run: Optional[RunConfig] = None):
    dtype = jnp.dtype((run or RunConfig()).param_dtype)
    return jax.eval_shape(
        lambda k: _init(cfg, k, dtype)[0], jax.random.PRNGKey(0))


# ============================================================== block apply
def _mask_kind(cfg, kind, prefix_len):
    if kind == BLOCK_LOCAL_ATTN:
        return "local"
    if cfg.prefix_lm and prefix_len is not None:
        return "prefix"
    return "causal"


def _residual(cfg, x, y):
    """x plus the block branch y, times the residual multiplier."""
    if cfg.residual_multiplier != 1.0:
        y = y * cfg.residual_multiplier
    return x + y


def _qkv(env, cfg, bp, x, positions):
    with jax.named_scope("attn_qkv"):
        h = L.rmsnorm(bp["ln1"], x, cfg.norm_eps)
        q, k, v = attn.project_qkv(env, cfg, bp["attn"], h,
                                   positions=positions, use_rope=cfg.use_rope)
    return h, q, k, v


def _attn_out(env, cfg, bp, x, o):
    with jax.named_scope("attn_out"):
        return _residual(cfg, x, attn.output_proj(env, cfg, bp["attn"], o))


def _self_attn(env, cfg, bp, x, mask, positions, prefix_len, chunk):
    """Self-attention of a block over a whole sequence and its residual;
    returns (x, ln1 output, k, v)."""
    h, q, k, v = _qkv(env, cfg, bp, x, positions)
    with jax.named_scope("attend"):
        o = attn.attention_core(env, cfg, q, k, v, mask_kind=mask,
                                prefix_len=prefix_len, chunk=chunk)
    return _attn_out(env, cfg, bp, x, o), h, k, v


def _attn_tail(env, cfg, bp, x, h, *, positions, chunk, enc_out,
               enc_positions, serving=False):
    """What follows a block's self-attention over a whole sequence: the MLP
    beside it in a parallel block, else the cross-attention of an
    encoder-decoder block and the FFN (``_ffn_part``). Returns
    (x, (ck, cv) or None)."""
    if cfg.parallel_block:
        with jax.named_scope("mlp"):
            return _residual(cfg, x, L.mlp_apply(env, bp["mlp"], h,
                                                 cfg.mlp_activation)), None
    cross = None
    if cfg.is_encoder_decoder and enc_out is not None:
        with jax.named_scope("cross_attn"):
            hc = L.rmsnorm(bp["ln_cross"], x, cfg.norm_eps)
            cq, ck, cv = attn.project_qkv(
                env, cfg, bp["cross"], hc, kv_x=enc_out, positions=positions,
                kv_positions=enc_positions, use_rope=False)
            co = attn.attention_core(env, cfg, cq, ck, cv, mask_kind="full",
                                     chunk=chunk)
            x = _residual(cfg, x, attn.output_proj(env, cfg, bp["cross"], co))
        cross = (ck, cv)
    return _ffn_part(env, cfg, bp, x, serving=serving)[0], cross


def _ffn_part(env, cfg, bp, x, serving=False):
    """ln2, the FFN and its residual. Returns (x, counts). An expert layer
    drops tokens past capacity in training; on the serving path
    (``serving``) it drops none and counts the routed assignments that land
    on each expert it holds (``moe.moe_serve``); elsewhere counts is None."""
    counts = None
    with jax.named_scope("mlp"):
        h2 = L.rmsnorm(bp["ln2"], x, cfg.norm_eps)
        if cfg.num_experts:
            if serving:
                f, counts = moe_mod.moe_serve(cfg, bp["moe"], h2)
            else:
                f = moe_mod.moe_apply(env, cfg, bp["moe"], h2)
            if cfg.moe_dense_residual:
                with jax.named_scope("moe_shared"):
                    f = f + L.mlp_apply(env, bp["mlp"], h2,
                                        cfg.mlp_activation)
        else:
            f = L.mlp_apply(env, bp["mlp"], h2, cfg.mlp_activation)
        return _residual(cfg, x, f), counts


def apply_block_train(env, cfg, kind, bp, x, *, positions, prefix_len,
                      chunk, enc_out=None, enc_positions=None):
    if kind in ATTN_BLOCKS:
        x, h, _, _ = _self_attn(env, cfg, bp, x,
                                _mask_kind(cfg, kind, prefix_len),
                                positions, prefix_len, chunk)
        return _attn_tail(env, cfg, bp, x, h, positions=positions,
                          chunk=chunk, enc_out=enc_out,
                          enc_positions=enc_positions)[0]
    if kind == BLOCK_RGLRU:
        with jax.named_scope("rglru"):
            x = _residual(cfg, x, rglru_mod.rglru_forward(
                env, cfg, bp["rglru"], L.rmsnorm(bp["ln1"], x, cfg.norm_eps)))
        return _ffn_part(env, cfg, bp, x)[0]
    if kind == BLOCK_SSD:
        with jax.named_scope("ssd"):
            x = _residual(cfg, x, ssd_mod.ssd_forward(
                env, cfg, bp["ssd"], L.rmsnorm(bp["ln1"], x, cfg.norm_eps)))
        return _ffn_part(env, cfg, bp, x)[0] if cfg.ssd_ffn else x
    raise ValueError(kind)


def apply_block_prefill(env, cfg, kind, bp, x, cache_entry, *, positions,
                        prefix_len, chunk, enc_out=None, enc_positions=None):
    """Like train, but fills ``cache_entry`` and returns (x, new_entry). An
    expert layer takes the serving path (``_ffn_part``)."""
    if kind in ATTN_BLOCKS:
        x, h, k, v = _self_attn(env, cfg, bp, x,
                                _mask_kind(cfg, kind, prefix_len),
                                positions, prefix_len, chunk)
        new = dict(cache_entry)
        with jax.named_scope("kv_write"):
            if kind == BLOCK_LOCAL_ATTN and \
                    cache_entry["k"].shape[1] < k.shape[1]:
                new["k"], new["v"] = attn.write_ring_cache(
                    cache_entry["k"], cache_entry["v"], k, v)
            else:
                new["k"], new["v"] = attn.write_full_cache(
                    cache_entry["k"], cache_entry["v"], k, v, 0)
        x, cross = _attn_tail(env, cfg, bp, x, h, positions=positions,
                              chunk=chunk, enc_out=enc_out,
                              enc_positions=enc_positions, serving=True)
        if cross is not None:
            with jax.named_scope("kv_write"):
                new["ck"], new["cv"] = (c.astype(new[n].dtype)
                                        for c, n in zip(cross, ("ck", "cv")))
        return x, new
    if kind == BLOCK_RGLRU:
        with jax.named_scope("rglru"):
            out, (h_last, conv) = rglru_mod.rglru_forward(
                env, cfg, bp["rglru"], L.rmsnorm(bp["ln1"], x, cfg.norm_eps),
                return_state=True)
            x = _residual(cfg, x, out)
        return (_ffn_part(env, cfg, bp, x, serving=True)[0],
                {"h": h_last, "conv": conv})
    if kind == BLOCK_SSD:
        with jax.named_scope("ssd"):
            out, (h_last, conv) = ssd_mod.ssd_forward(
                env, cfg, bp["ssd"], L.rmsnorm(bp["ln1"], x, cfg.norm_eps),
                return_state=True)
            x = _residual(cfg, x, out)
        if cfg.ssd_ffn:
            x = _ffn_part(env, cfg, bp, x, serving=True)[0]
        return x, {"h": h_last, "conv": conv}
    raise ValueError(kind)


def apply_block_decode(env, cfg, kind, bp, x_t, cache_entry, *, pos,
                       layer=None):
    """One-token step. x_t: (B, 1, d); pos: (B,) absolute position.

    With ``layer``, the leaves of ``cache_entry`` are stacked over the
    layers and the block's own are those at ``layer``: the new key and
    value are written in place at (layer, slot, position) and, on one TPU
    chip, a full-length cache's self-attention reads its layer there
    (``_in_place_attention``); the recurrent state is rewritten at
    ``layer`` (an SSD's under ``ssd_state``). Returns (x_t, new_entry,
    counts): an expert layer's counts of the serving path (``_ffn_part``),
    else None."""
    if kind in ATTN_BLOCKS:
        h, q, k, v = _qkv(env, cfg, bp, x_t, pos[:, None])
        ring = kind == BLOCK_LOCAL_ATTN
        new = dict(cache_entry)
        with jax.named_scope("kv_write"):
            new["k"], new["v"] = _decode_write_vec(
                cache_entry["k"], cache_entry["v"], k, v, pos, ring, layer)
        in_place = None if ring or layer is None else _in_place_attention(env)
        if in_place is None:
            cache_k, cache_v = (_at_layer(new[n], layer) for n in ("k", "v"))
        with jax.named_scope("attend"):
            if in_place is not None:
                o = in_place(q, new["k"], new["v"], pos + 1, layer=layer,
                             softcap=cfg.attn_logit_softcap,
                             scale=attn._scale(cfg))
            else:
                o = attn.decode_attend(
                    env, cfg, q, cache_k, cache_v, pos, ring=ring,
                    window=cfg.local_window if ring else 0)
        x_t = _attn_out(env, cfg, bp, x_t, o)
        if cfg.parallel_block:
            with jax.named_scope("mlp"):
                return _residual(cfg, x_t, L.mlp_apply(
                    env, bp["mlp"], h, cfg.mlp_activation)), new, None
        if cfg.is_encoder_decoder and "ck" in cache_entry:
            cross_k, cross_v = (_at_layer(cache_entry[n], layer)
                                for n in ("ck", "cv"))
            with jax.named_scope("cross_attn"):
                hc = L.rmsnorm(bp["ln_cross"], x_t, cfg.norm_eps)
                cq = jnp.einsum("bsd,dhk->bshk", hc, bp["cross"]["wq"])
                if cfg.attn_bias:
                    cq = cq + bp["cross"]["bq"]
                co = attn.decode_attend(env, cfg, cq, cross_k, cross_v, pos,
                                        ring=False, cross=True)
                x_t = _residual(cfg, x_t,
                                attn.output_proj(env, cfg, bp["cross"], co))
        x_t, counts = _ffn_part(env, cfg, bp, x_t, serving=True)
        return x_t, new, counts
    if kind not in (BLOCK_RGLRU, BLOCK_SSD):
        raise ValueError(kind)
    state = tuple(_at_layer(cache_entry[n], layer) for n in ("h", "conv"))

    def write(h_new, conv):
        return {n: _set_layer(cache_entry[n], new, layer)
                for n, new in (("h", h_new), ("conv", conv))}
    if kind == BLOCK_RGLRU:
        with jax.named_scope("rglru"):
            out, new_state = rglru_mod.rglru_step(
                env, cfg, bp["rglru"], L.rmsnorm(bp["ln1"], x_t, cfg.norm_eps),
                state)
            x_t = _residual(cfg, x_t, out)
        new = write(*new_state)
    else:
        with jax.named_scope("ssd"):
            out, new_state = ssd_mod.ssd_step(
                env, cfg, bp["ssd"], L.rmsnorm(bp["ln1"], x_t, cfg.norm_eps),
                state)
            x_t = _residual(cfg, x_t, out)
            with jax.named_scope("ssd_state"):
                new = write(*new_state)
    counts = None
    if kind == BLOCK_RGLRU or cfg.ssd_ffn:
        x_t, counts = _ffn_part(env, cfg, bp, x_t, serving=True)
    return x_t, new, counts


def _in_place_attention(env):
    """The ``decode_attn`` kernel where a stacked decode step's
    self-attention over a full-length cache may read its layer of the
    carried cache in place, and only up to each slot's length: on one TPU
    chip (a custom call is not partitioned over a mesh). Elsewhere None:
    the layer's keys and values are sliced out for ``attn.decode_attend``."""
    if env.mesh.size != 1 or env.mesh.devices.flat[0].platform != "tpu":
        return None
    return decode_attention


def _at_layer(leaf, layer):
    """The block's own cache leaf: ``leaf`` at ``layer`` of a stacked cache,
    or ``leaf`` itself where ``layer`` is None."""
    if layer is None:
        return leaf
    return jax.lax.dynamic_index_in_dim(leaf, layer, 0, keepdims=False)


def _set_layer(leaf, new, layer):
    """``leaf`` with ``new`` in place of the block's own entry."""
    if layer is None:
        return new
    return jax.lax.dynamic_update_index_in_dim(leaf, new, layer, 0)


def _decode_write_vec(cache_k, cache_v, k_t, v_t, pos, ring: bool,
                      layer=None):
    """Per-sequence cache write. k_t: (B, 1, H, D); pos: (B,). With
    ``layer``, the caches are stacked over the layers and each token lands
    at (layer, slot, position)."""
    w = cache_k.shape[-3]
    slots = (pos % w) if ring else pos
    idx = (jnp.arange(k_t.shape[0]), slots)
    if layer is not None:
        idx = (layer,) + idx
    cache_k = cache_k.at[idx].set(k_t[:, 0].astype(cache_k.dtype))
    cache_v = cache_v.at[idx].set(v_t[:, 0].astype(cache_v.dtype))
    return cache_k, cache_v


# ======================================================== stacks (scan/rem)
def _remat(fn, policy: str):
    if policy == "none":
        return fn
    if policy == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims)
    return jax.checkpoint(fn)  # "full"


def _run_stack_train(env, cfg, params, x, *, positions, prefix_len, run,
                     enc_out=None, enc_positions=None, encoder: bool = False):
    stack = params.get("stack")
    chunk = run.attn_chunk

    def body(x, lp):
        if encoder:
            x, _, _, _ = _self_attn(env, cfg, lp, x, "full", positions, None,
                                    chunk)
            with jax.named_scope("mlp"):
                h2 = L.rmsnorm(lp["ln2"], x, cfg.norm_eps)
                return x + L.mlp_apply(env, lp["mlp"], h2,
                                       cfg.mlp_activation), None
        for i, kind in enumerate(cfg.pattern):
            x = apply_block_train(env, cfg, kind, lp[f"b{i}"], x,
                                  positions=positions, prefix_len=prefix_len,
                                  chunk=chunk, enc_out=enc_out,
                                  enc_positions=enc_positions)
        return x, None

    body = _remat(body, run.remat_policy)
    with jax.named_scope("layers"):
        if stack is not None:
            x, _ = jax.lax.scan(lambda c, lp: body(c, lp), x, stack)
        for i, kind in enumerate(() if encoder else cfg.remainder_blocks):
            x = apply_block_train(env, cfg, kind, params["rem"][i], x,
                                  positions=positions, prefix_len=prefix_len,
                                  chunk=chunk, enc_out=enc_out,
                                  enc_positions=enc_positions)
    return x


def _run_stack_prefill(env, cfg, params, x, cache, *, positions, prefix_len,
                       run, enc_out=None, enc_positions=None):
    chunk = run.attn_chunk

    def body(x, lp_lc):
        lp, lc = lp_lc
        new_entries = {}
        for i, kind in enumerate(cfg.pattern):
            x, new_entries[f"b{i}"] = apply_block_prefill(
                env, cfg, kind, lp[f"b{i}"], x, lc[f"b{i}"],
                positions=positions, prefix_len=prefix_len, chunk=chunk,
                enc_out=enc_out, enc_positions=enc_positions)
        return x, new_entries

    with jax.named_scope("layers"):
        if params.get("stack") is not None:
            x, new_cache_stack = jax.lax.scan(
                body, x, (params["stack"], cache["stack"]))
        else:
            new_cache_stack = cache.get("stack")
        new_rem = []
        for i, kind in enumerate(cfg.remainder_blocks):
            x, entry = apply_block_prefill(
                env, cfg, kind, params["rem"][i], x, cache["rem"][i],
                positions=positions, prefix_len=prefix_len, chunk=chunk,
                enc_out=enc_out, enc_positions=enc_positions)
            new_rem.append(entry)
    out_cache = {"stack": new_cache_stack}
    if new_rem:
        out_cache["rem"] = tuple(new_rem)
    return x, out_cache


def _run_stack_decode(env, cfg, params, x_t, cache, *, pos):
    """The decode step's layers. The stacked cache rides in the scan's carry
    beside ``x_t``, and each block writes its layer of it in place; the
    scan's xs are the stacked weights and the layer index. Returns
    (x_t, cache, counts): counts, for a model with experts, the routed
    assignments on each held expert of each layer, (num_layers, held)
    int32, else None."""
    def stacked(counts):
        counts = [n for n in counts if n is not None]
        return jnp.stack(counts) if counts else None

    def body(carry, lp_layer):
        x_t, stack = carry
        lp, layer = lp_layer
        stack = dict(stack)
        counts = []
        for i, kind in enumerate(cfg.pattern):
            x_t, stack[f"b{i}"], n = apply_block_decode(
                env, cfg, kind, lp[f"b{i}"], x_t, stack[f"b{i}"], pos=pos,
                layer=layer)
            counts.append(n)
        return (x_t, stack), stacked(counts)

    counts = []
    with jax.named_scope("layers"):
        new_cache_stack = cache.get("stack")
        if params.get("stack") is not None:
            (x_t, new_cache_stack), per_rep = jax.lax.scan(
                body, (x_t, new_cache_stack),
                (params["stack"], jnp.arange(cfg.scan_repeats)))
            if per_rep is not None:
                counts.append(per_rep.reshape(-1, per_rep.shape[-1]))
        new_rem, rem_counts = [], []
        for i, kind in enumerate(cfg.remainder_blocks):
            x_t, entry, n = apply_block_decode(
                env, cfg, kind, params["rem"][i], x_t, cache["rem"][i],
                pos=pos)
            new_rem.append(entry)
            rem_counts.append(n)
        counts.append(stacked(rem_counts))
    out_cache = {"stack": new_cache_stack}
    if new_rem:
        out_cache["rem"] = tuple(new_rem)
    counts = [n for n in counts if n is not None]
    return x_t, out_cache, (jnp.concatenate(counts) if counts else None)


# ============================================================== embeddings
def _embed_tokens(env, cfg, params, tokens):
    x = L.embed_lookup(env, params["embed"], tokens, cfg.embed_scale)
    if cfg.embed_multiplier != 1.0:
        x = x * cfg.embed_multiplier
    return x


def _embed_inputs(env, cfg, params, batch):
    """Token (+frontend) embedding. Returns (x, positions, prefix_len)."""
    tokens = batch["tokens"]
    x = _embed_tokens(env, cfg, params, tokens)
    prefix_len = None
    if cfg.frontend == "vision":
        patches = batch["patch_embeds"].astype(x.dtype)
        x = jnp.concatenate([patches, x], axis=1)
        prefix_len = patches.shape[1]
    return x, jnp.arange(x.shape[1]), prefix_len


def _encode(env, cfg, params, batch, run):
    src = batch["src_embeds"]
    pos = jnp.arange(src.shape[1])
    dtype = params["embed"]["table"].dtype
    enc = _run_stack_train(env, cfg, params["encoder"], src.astype(dtype),
                           positions=pos, prefix_len=None, run=run,
                           encoder=True)
    return L.rmsnorm(params["encoder"]["final_norm"], enc, cfg.norm_eps), pos


def _embed(env, cfg, params, batch, run):
    """The encoder's output where there is an encoder, and the embedded
    inputs: (enc_out, enc_positions, x, positions, prefix_len)."""
    with jax.named_scope("embed"):
        enc_out = enc_pos = None
        if cfg.is_encoder_decoder:
            enc_out, enc_pos = _encode(env, cfg, params, batch, run)
        return (enc_out, enc_pos) + _embed_inputs(env, cfg, params, batch)


# ================================================================== public
def forward_train(env: ShardEnv, cfg: ModelConfig, params, batch,
                  run: RunConfig):
    enc_out, enc_pos, x, positions, prefix_len = _embed(env, cfg, params,
                                                        batch, run)
    x = _run_stack_train(env, cfg, params, x, positions=positions,
                         prefix_len=prefix_len, run=run,
                         enc_out=enc_out, enc_positions=enc_pos)
    with jax.named_scope("head"):
        return L.rmsnorm(params["final_norm"], x, cfg.norm_eps)


def _logits(env, cfg, params, x):
    logits = L.unembed(env, params["embed"], x, cfg.tie_embeddings,
                       head=params.get("lm_head"),
                       cap=cfg.final_logit_softcap)
    if cfg.logits_scaling != 1.0:
        logits = logits / cfg.logits_scaling
    return logits


def _ce(logits, targets, weights):
    logits = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, targets[..., None].clip(0), axis=-1)[..., 0]
    nll = (lse - gold) * weights
    return nll.sum(), weights.sum()


def loss_fn(env: ShardEnv, cfg: ModelConfig, params, batch, run: RunConfig):
    x = forward_train(env, cfg, params, batch, run)
    with jax.named_scope("head"):
        targets = batch["targets"]
        if cfg.frontend == "vision":           # loss over text suffix only
            x = x[:, -targets.shape[1]:]
        weights = (targets >= 0).astype(jnp.float32)
        if run.loss_chunk and x.shape[1] % run.loss_chunk == 0 and \
                x.shape[1] > run.loss_chunk:
            nc = x.shape[1] // run.loss_chunk
            xs = x.reshape(x.shape[0], nc, run.loss_chunk,
                           x.shape[-1]).swapaxes(0, 1)
            ts = targets.reshape(targets.shape[0], nc,
                                 run.loss_chunk).swapaxes(0, 1)
            ws = weights.reshape(weights.shape[0], nc,
                                 run.loss_chunk).swapaxes(0, 1)

            @jax.checkpoint
            def chunk_loss(carry, xtw):
                xc, tc, wc = xtw
                n, d = _ce(_logits(env, cfg, params, xc), tc, wc)
                return (carry[0] + n, carry[1] + d), None

            (num, den), _ = jax.lax.scan(chunk_loss, (0.0, 0.0), (xs, ts, ws))
        else:
            num, den = _ce(_logits(env, cfg, params, x), targets, weights)
        return num / jnp.maximum(den, 1.0)


# ==================================================================== cache
def _cache_entry_struct(cfg, kind, batch: int, max_len: int, cross_len: int,
                        kv_dtype=jnp.bfloat16):
    hkv, dh = max(cfg.num_kv_heads, 1), max(cfg.head_dim, 1)
    if kind in ATTN_BLOCKS:
        length = max_len if kind == BLOCK_GLOBAL_ATTN else min(
            cfg.local_window or max_len, max_len)
        e = {"k": ((batch, length, hkv, dh), kv_dtype),
             "v": ((batch, length, hkv, dh), kv_dtype)}
        if cfg.is_encoder_decoder:
            e["ck"] = ((batch, cross_len, hkv, dh), kv_dtype)
            e["cv"] = ((batch, cross_len, hkv, dh), kv_dtype)
        return e
    if kind == BLOCK_RGLRU:
        rw = cfg.rglru_width or cfg.d_model
        return {"h": ((batch, rw), jnp.float32),
                "conv": ((batch, cfg.conv_width - 1, rw), jnp.float32)}
    if kind == BLOCK_SSD:
        return {"h": ((batch, cfg.ssm_num_heads, cfg.ssm_head_dim,
                       cfg.ssm_state_dim), jnp.float32),
                "conv": ((batch, cfg.conv_width - 1,
                          cfg.d_inner + 2 * cfg.ssm_state_dim), jnp.float32)}
    raise ValueError(kind)


def _cache_tree(cfg, batch, max_len, cross_len, make_leaf, kv_dtype):
    tree: Dict[str, Any] = {}
    reps = cfg.scan_repeats
    if reps:
        stack = {}
        for i, kind in enumerate(cfg.pattern):
            entry = _cache_entry_struct(cfg, kind, batch, max_len, cross_len,
                                        kv_dtype)
            stack[f"b{i}"] = {k: make_leaf((reps,) + shape, dt)
                              for k, (shape, dt) in entry.items()}
        tree["stack"] = stack
    rem = []
    for kind in cfg.remainder_blocks:
        entry = _cache_entry_struct(cfg, kind, batch, max_len, cross_len,
                                    kv_dtype)
        rem.append({k: make_leaf(shape, dt) for k, (shape, dt) in entry.items()})
    if rem:
        tree["rem"] = tuple(rem)
    return tree


def init_cache(cfg: ModelConfig, batch: int, max_len: int,
               cross_len: int = 0, kv_dtype=jnp.bfloat16):
    return _cache_tree(cfg, batch, max_len, cross_len or max_len,
                       lambda s, d: jnp.zeros(s, d), kv_dtype)


def cache_struct(cfg: ModelConfig, batch: int, max_len: int,
                 cross_len: int = 0, kv_dtype=jnp.bfloat16):
    return _cache_tree(cfg, batch, max_len, cross_len or max_len,
                       jax.ShapeDtypeStruct, kv_dtype)


def cache_specs(cfg: ModelConfig):
    """Logical-axis tuples matching the cache tree."""
    def leaf_spec(key, ndim, stacked):
        if key in ("k", "v", "ck", "cv"):
            sp = ("act_batch", "act_kv_seq", None, None)
        elif key == "h":
            sp = (("act_batch", "act_inner") if ndim - (1 if stacked else 0) == 2
                  else ("act_batch", "act_inner", None, None))
        else:  # conv
            sp = ("act_batch", None, "act_inner")
        return (("layers",) + sp) if stacked else sp

    tree: Dict[str, Any] = {}
    reps = cfg.scan_repeats
    cross = cfg.is_encoder_decoder
    if reps:
        stack = {}
        for i, kind in enumerate(cfg.pattern):
            entry = _cache_entry_struct(cfg, kind, 1, 8, 8)
            stack[f"b{i}"] = {k: leaf_spec(k, len(shape) + 1, True)
                              for k, (shape, dt) in entry.items()}
        tree["stack"] = stack
    rem = []
    for kind in cfg.remainder_blocks:
        entry = _cache_entry_struct(cfg, kind, 1, 8, 8)
        rem.append({k: leaf_spec(k, len(shape), False)
                    for k, (shape, dt) in entry.items()})
    if rem:
        tree["rem"] = tuple(rem)
    return tree


# =========================================================== prefill/decode
def prefill(env: ShardEnv, cfg: ModelConfig, params, batch, run: RunConfig,
            max_len: int = 0, kv_dtype=jnp.bfloat16):
    """Run the prompt, fill the cache, return (last_logits, cache, pos)."""
    enc_out, enc_pos, x, positions, prefix_len = _embed(env, cfg, params,
                                                        batch, run)
    s = x.shape[1]
    b = x.shape[0]
    cache = init_cache(cfg, b, max(max_len or s, s),
                       cross_len=(enc_out.shape[1] if enc_out is not None else 0),
                       kv_dtype=kv_dtype)
    x, cache = _run_stack_prefill(env, cfg, params, x, cache,
                                  positions=positions, prefix_len=prefix_len,
                                  run=run, enc_out=enc_out,
                                  enc_positions=enc_pos)
    with jax.named_scope("head"):
        x = L.rmsnorm(params["final_norm"], x, cfg.norm_eps)
        logits = _logits(env, cfg, params, x[:, -1:])[:, 0]
    pos = jnp.full((b,), s - 1, jnp.int32)
    return logits, cache, pos


def decode_step(env: ShardEnv, cfg: ModelConfig, params, token, pos, cache,
                run: RunConfig, expert_counts: bool = False):
    """One decode step. token: (B, 1) int32; pos: (B,) absolute position of
    the *new* token. Returns (logits (B, V), new_cache), and with
    ``expert_counts`` for a model with experts the routed assignments that
    land on each held expert of each layer, (num_layers, held) int32."""
    with jax.named_scope("embed"):
        x = _embed_tokens(env, cfg, params, token)
    x, cache, counts = _run_stack_decode(env, cfg, params, x, cache, pos=pos)
    with jax.named_scope("head"):
        x = L.rmsnorm(params["final_norm"], x, cfg.norm_eps)
        logits = _logits(env, cfg, params, x)[:, 0]
    return (logits, cache, counts) if expert_counts else (logits, cache)


# ============================================================== input_specs
def input_specs(cfg: ModelConfig, shape: ShapeConfig,
                run: Optional[RunConfig] = None) -> Dict[str, Any]:
    """ShapeDtypeStruct stand-ins for every model input of this cell.

    train:   tokens/targets (+frontend embeddings)
    prefill: tokens (+frontend embeddings)
    decode:  token (B,1) + pos (B,) + cache of seq_len
    """
    b, s = shape.global_batch, shape.seq_len
    i32 = jnp.int32
    bf16 = jnp.bfloat16
    f = jax.ShapeDtypeStruct
    d = cfg.d_model

    if shape.mode == "train":
        if cfg.is_encoder_decoder:
            tgt = max(s // 4, 8)
            return {"src_embeds": f((b, s, d), bf16),
                    "tokens": f((b, tgt), i32),
                    "targets": f((b, tgt), i32)}
        if cfg.frontend == "vision":
            text = s - cfg.frontend_len
            return {"patch_embeds": f((b, cfg.frontend_len, d), bf16),
                    "tokens": f((b, text), i32),
                    "targets": f((b, text), i32)}
        return {"tokens": f((b, s), i32), "targets": f((b, s), i32)}

    if shape.mode == "prefill":
        if cfg.is_encoder_decoder:
            return {"src_embeds": f((b, s, d), bf16),
                    "tokens": f((b, 8), i32)}
        if cfg.frontend == "vision":
            return {"patch_embeds": f((b, cfg.frontend_len, d), bf16),
                    "tokens": f((b, s - cfg.frontend_len), i32)}
        return {"tokens": f((b, s), i32)}

    # decode: one new token against a cache of seq_len
    cache = cache_struct(cfg, b, s, cross_len=s if cfg.is_encoder_decoder else 0)
    return {"token": f((b, 1), i32), "pos": f((b,), i32), "cache": cache}
