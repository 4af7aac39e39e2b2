"""Mamba-2 SSD (state-space duality) block, chunked for the MXU.

The SSD recurrence  h_t = exp(dA_t) h_{t-1} + dt_t B_t x_t^T ;  y_t = C_t h_t
is evaluated chunk-wise (chunk Q = cfg.ssm_chunk): within a chunk the dual
quadratic form (C B^T ⊙ L) X runs as dense Q×Q matmuls (MXU-aligned); across
chunks a lax.scan carries the (nh, P, N) state. This is the TPU-native
blocking of the SSD algorithm — intra-chunk compute is batched matmul, the
sequential dependency is only O(S/Q).

Layout: d_inner = expand*d_model, heads nh = d_inner/P, single B/C group
(ngroups=1), scalar A per head, depthwise conv width 4 over (x, B, C).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.layers import conv1d_apply, conv1d_init, conv1d_step, nd_init


def ssd_init(cfg, key, dtype):
    d, di, n = cfg.d_model, cfg.d_inner, cfg.ssm_state_dim
    nh = cfg.ssm_num_heads
    ks = jax.random.split(key, 4)
    conv_ch = di + 2 * n
    conv_p, conv_s = conv1d_init(ks[2], cfg.conv_width, conv_ch, dtype)
    p = {
        # fused in_proj -> [z(di), x(di), B(n), C(n), dt(nh)]
        "w_in": nd_init(ks[0], (d, 2 * di + 2 * n + nh), d, dtype),
        "w_out": nd_init(ks[1], (di, d), di, dtype),
        "conv": conv_p,
        "a_log": jnp.log(jnp.arange(1, nh + 1, dtype=jnp.float32)),
        "dt_bias": jnp.zeros((nh,), jnp.float32),
        "d_skip": jnp.ones((nh,), jnp.float32),
        "norm_scale": jnp.zeros((di,), jnp.float32),
    }
    s = {
        "w_in": ("p_embed", "p_inner"), "w_out": ("p_inner", "p_embed"),
        "conv": conv_s, "a_log": ("p_none",), "dt_bias": ("p_none",),
        "d_skip": ("p_none",), "norm_scale": ("p_inner",),
    }
    return p, s


def _split_proj(cfg, proj):
    di, n, nh = cfg.d_inner, cfg.ssm_state_dim, cfg.ssm_num_heads
    z = proj[..., :di]
    xbc = proj[..., di:di + di + 2 * n]
    dt = proj[..., di + di + 2 * n:]
    return z, xbc, dt


def _gated_norm(params, y, z, eps):
    y = y * jax.nn.silu(z.astype(jnp.float32))
    var = jnp.mean(jnp.square(y), axis=-1, keepdims=True)
    y = y * jax.lax.rsqrt(var + eps)
    return y * (1.0 + params["norm_scale"])


def ssd_forward(env, cfg, params, x, *, state=None, conv_state=None,
                return_state: bool = False):
    """x: (B, S, d). Chunked SSD. state: (B, nh, P, N) fp32."""
    bsz, s, _ = x.shape
    di, n, nh, p_dim = cfg.d_inner, cfg.ssm_state_dim, cfg.ssm_num_heads, cfg.ssm_head_dim
    q = min(cfg.ssm_chunk, s)
    while s % q:
        q -= 1
    nc = s // q

    proj = x @ params["w_in"]
    proj = env.constrain(proj, "act_batch", "act_seq", "act_mlp")
    z, xbc, dt = _split_proj(cfg, proj)
    if conv_state is not None:
        hist = jnp.concatenate([conv_state.astype(xbc.dtype), xbc], axis=1)
        xbc_c = jax.nn.silu(conv1d_apply(params["conv"], hist)[:, conv_state.shape[1]:])
        new_conv = hist[:, -(cfg.conv_width - 1):]
    else:
        xbc_c = jax.nn.silu(conv1d_apply(params["conv"], xbc))
        new_conv = xbc[:, -(cfg.conv_width - 1):]
    xs = xbc_c[..., :di].reshape(bsz, s, nh, p_dim)
    bmat = xbc_c[..., di:di + n]                                # (B,S,N)
    cmat = xbc_c[..., di + n:]                                  # (B,S,N)

    dt = jax.nn.softplus(dt.astype(jnp.float32) + params["dt_bias"])  # (B,S,nh)
    da = -jnp.exp(params["a_log"]) * dt                               # (B,S,nh) <= 0

    with jax.named_scope("ssd_state"):
        # chunk views
        xs_c = xs.reshape(bsz, nc, q, nh, p_dim).astype(jnp.float32)
        b_c = bmat.reshape(bsz, nc, q, n).astype(jnp.float32)
        c_c = cmat.reshape(bsz, nc, q, n).astype(jnp.float32)
        da_c = da.reshape(bsz, nc, q, nh)
        dt_c = dt.reshape(bsz, nc, q, nh)

        cum = jnp.cumsum(da_c, axis=2)                                    # (B,nc,q,nh)
        seg = cum[:, :, :, None, :] - cum[:, :, None, :, :]               # (B,nc,q,q,nh)
        causal = jnp.tril(jnp.ones((q, q), bool))[None, None, :, :, None]
        # mask BEFORE exp: on causal entries seg <= 0, so exp never overflows;
        # masking after exp produces inf * 0 = NaN in the backward pass.
        l_mat = jnp.exp(jnp.where(causal, seg, -1e30))

        # intra-chunk: Y = (C B^T ⊙ L ⊙ dt_j) X
        cb = jnp.einsum("bcin,bcjn->bcij", c_c, b_c)                      # (B,nc,q,q)
        att = cb[..., None] * l_mat * dt_c[:, :, None, :, :]              # (B,nc,q,q,nh)
        y_intra = jnp.einsum("bcijh,bcjhp->bcihp", att, xs_c)

        # state to carry: S_c = sum_j exp(cum_Q - cum_j) dt_j B_j x_j^T
        decay_end = jnp.exp(cum[:, :, -1:, :] - cum)                      # (B,nc,q,nh)
        sin = jnp.einsum("bcjh,bcjn,bcjhp->bchpn",
                         decay_end * dt_c, b_c, xs_c)                     # (B,nc,nh,P,N)
        chunk_decay = jnp.exp(cum[:, :, -1, :])                           # (B,nc,nh)

        def chunk_step(h, inp):
            s_in, dec, c_blk, cum_blk = inp
            # inter-chunk contribution: y_i += C_i exp(cum_i) h_prev
            y_inter = jnp.einsum("bin,bih,bhpn->bihp",
                                 c_blk, jnp.exp(cum_blk), h)
            h_new = dec[:, :, None, None] * h + s_in
            return h_new, y_inter

        if state is None:
            state = jnp.zeros((bsz, nh, p_dim, n), jnp.float32)
        h_last, y_inter = jax.lax.scan(
            chunk_step, state,
            (sin.swapaxes(0, 1), chunk_decay.swapaxes(0, 1),
             c_c.swapaxes(0, 1), cum.swapaxes(0, 1)))
        y = y_intra + y_inter.swapaxes(0, 1)                              # (B,nc,q,nh,P)
        y = y.reshape(bsz, s, nh, p_dim)
    y = y + params["d_skip"][:, None] * xs.astype(jnp.float32)
    y = y.reshape(bsz, s, di)
    y = _gated_norm(params, y, z, cfg.norm_eps).astype(x.dtype)
    out = y @ params["w_out"]
    out = env.constrain(out, "act_batch", "act_seq", "act_embed")
    if return_state:
        return out, (h_last, new_conv.astype(jnp.float32))
    return out


def ssd_step(env, cfg, params, x_t, state_tuple):
    """One decode step. x_t: (B, 1, d); state (B, nh, P, N) fp32."""
    h, conv_state = state_tuple
    di, n, nh, p_dim = cfg.d_inner, cfg.ssm_state_dim, cfg.ssm_num_heads, cfg.ssm_head_dim
    proj = x_t[:, 0] @ params["w_in"]
    z, xbc, dt = _split_proj(cfg, proj)
    xbc_c, new_conv = conv1d_step(params["conv"], xbc, conv_state.astype(xbc.dtype))
    xbc_c = jax.nn.silu(xbc_c)
    xs = xbc_c[..., :di].reshape(-1, nh, p_dim).astype(jnp.float32)
    bvec = xbc_c[..., di:di + n].astype(jnp.float32)
    cvec = xbc_c[..., di + n:].astype(jnp.float32)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + params["dt_bias"])
    da = jnp.exp(-jnp.exp(params["a_log"]) * dt)                      # (B,nh)
    with jax.named_scope("ssd_state"):
        h_new = (da[:, :, None, None] * h
                 + jnp.einsum("bh,bn,bhp->bhpn", dt, bvec, xs))
        y = jnp.einsum("bn,bhpn->bhp", cvec, h_new)
    y = y + params["d_skip"][:, None] * xs
    y = y.reshape(-1, di)
    y = _gated_norm(params, y, z, cfg.norm_eps).astype(x_t.dtype)
    out = y @ params["w_out"]
    return out[:, None, :], (h_new, new_conv.astype(jnp.float32))
