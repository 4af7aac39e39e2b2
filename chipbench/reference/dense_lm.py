"""The plain reference of the served dense model, and its weights.

Imports nothing of the system under test. The weights are the
benchmark's: :func:`layer_weights` draws layer ``l`` from the seed's key
alone, so the benchmark makes all layers in one jitted call for the
system, and this reference makes them again, one layer at a time, once
the system's state is freed.

The forward pass follows the configuration file: token embedding (no
scaling), then per layer ``x += attn(rmsnorm(x))`` and
``x += mlp(rmsnorm(x))``, then a final RMSNorm and the untied head.
RMSNorm multiplies by ``1 + scale``. Attention is causal GQA with rotary
embeddings on the two halves of each head at absolute positions
(``theta``), scaled by ``1/sqrt(head_dim)``; query head ``j`` reads
key/value head ``j // (num_heads / num_kv_heads)``. The MLP is
``relu(x @ w_in) ** 2 @ w_out``. Everything is float32 at the highest
matmul precision; ``quant="fp8"`` instead rounds every weight matrix
(per output column) and every matmul input (per row) to float8 e4m3,
the control of PERF.md.
"""
from __future__ import annotations

import math
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

# std of the norm scales (RMSNorm multiplies by 1 + scale)
NORM_STD = 0.1


def _normal(key, shape, fan_in, dtype):
    w = jax.random.normal(key, shape, jnp.float32) / math.sqrt(fan_in)
    return w.astype(dtype)


def layer_weights(key, l, dims: Dict, dtype=jnp.bfloat16) -> Dict:
    """Layer ``l``'s weights: matrices in ``dtype``, norm scales f32."""
    d, hq, hkv, dh, ff = (dims["d_model"], dims["num_heads"],
                          dims["num_kv_heads"], dims["head_dim"],
                          dims["d_ff"])
    ks = jax.random.split(jax.random.fold_in(key, l), 8)
    return {
        "ln1": jax.random.normal(ks[0], (d,), jnp.float32) * NORM_STD,
        "wq": _normal(ks[1], (d, hq, dh), d, dtype),
        "wk": _normal(ks[2], (d, hkv, dh), d, dtype),
        "wv": _normal(ks[3], (d, hkv, dh), d, dtype),
        "wo": _normal(ks[4], (hq, dh, d), hq * dh, dtype),
        "ln2": jax.random.normal(ks[5], (d,), jnp.float32) * NORM_STD,
        "w_in": _normal(ks[6], (d, ff), d, dtype),
        "w_out": _normal(ks[7], (ff, d), ff, dtype),
    }


def outer_weights(key, dims: Dict, dtype=jnp.bfloat16) -> Dict:
    """Embedding, final norm scale and head."""
    d, v = dims["d_model"], dims["vocab_size"]
    ks = jax.random.split(jax.random.fold_in(key, 1 << 20), 3)
    return {"embed": _normal(ks[0], (v, d), d, dtype),
            "final": jax.random.normal(ks[1], (d,), jnp.float32) * NORM_STD,
            "head": _normal(ks[2], (d, v), d, dtype)}


# ---------------------------------------------------------- forward

def _q8(x, axis):
    """Round to float8 e4m3 with one scale per slice along ``axis``."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / 448.0, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _mm(x, w, quant):
    """x (S, K) @ w (K, N) in float32, or through float8 for the control."""
    if quant == "fp8":
        x, w = _q8(x, -1), _q8(w, 0)
    return jnp.dot(x, w, precision=jax.lax.Precision.HIGHEST)


def _rmsnorm(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + scale)


def _rope(x, theta):
    """x (S, H, D) at positions 0..S-1."""
    s, _, d = x.shape
    half = d // 2
    freq = theta ** (-np.arange(0, half, dtype=np.float32) * 2.0 / d)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None, None] * freq
    sin, cos = jnp.sin(ang), jnp.cos(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def layer_forward(x, w, dims: Dict, quant: Optional[str] = None):
    """One layer over one sequence ``x`` (S, d), float32."""
    s, d = x.shape
    hq, hkv, dh = dims["num_heads"], dims["num_kv_heads"], dims["head_dim"]
    w = {k: v.astype(jnp.float32) for k, v in w.items()}
    h = _rmsnorm(x, w["ln1"], dims["norm_eps"])
    q = _mm(h, w["wq"].reshape(d, hq * dh), quant).reshape(s, hq, dh)
    k = _mm(h, w["wk"].reshape(d, hkv * dh), quant).reshape(s, hkv, dh)
    v = _mm(h, w["wv"].reshape(d, hkv * dh), quant).reshape(s, hkv, dh)
    q, k = _rope(q, dims["rope_theta"]), _rope(k, dims["rope_theta"])
    g = hq // hkv
    k = jnp.repeat(k, g, axis=1)
    v = jnp.repeat(v, g, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k,
                        precision=jax.lax.Precision.HIGHEST) / math.sqrt(dh)
    causal = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(causal[None], scores, -jnp.inf)
    p = jax.nn.softmax(scores, axis=-1)
    o = jnp.einsum("hqk,khd->qhd", p, v,
                   precision=jax.lax.Precision.HIGHEST).reshape(s, hq * dh)
    x = x + _mm(o, w["wo"].reshape(hq * dh, d), quant)
    h2 = _rmsnorm(x, w["ln2"], dims["norm_eps"])
    r = jax.nn.relu(_mm(h2, w["w_in"], quant))
    return x + _mm(r * r, w["w_out"], quant)


def logits(x, outer, dims: Dict, quant: Optional[str] = None):
    x = _rmsnorm(x, outer["final"].astype(jnp.float32), dims["norm_eps"])
    return _mm(x, outer["head"].astype(jnp.float32), quant)


class Reference:
    """The reference forward over sequences padded to ``length``, one
    jitted program per piece, weights drawn anew from ``key``. The key
    is an argument of the programs, so every seed runs the same ones."""

    def __init__(self, key, dims: Dict, length: int,
                 quants=(None,)):
        self.key, self.dims, self.length = key, dims, length
        self.quants = quants

        def layer(key, x, l, quant):
            return layer_forward(x, layer_weights(key, l, dims), dims, quant)

        def head(key, x, quant):
            return logits(x, outer_weights(key, dims), dims, quant)

        def embed(key, tokens):
            return outer_weights(key, dims)["embed"][tokens].astype(
                jnp.float32)
        self._layer = jax.jit(layer, static_argnums=3)
        self._head = jax.jit(head, static_argnums=2)
        self._embed = jax.jit(embed)

    def forward(self, tokens: np.ndarray) -> Dict:
        """Logits (S, vocab) per quant for one sequence of ``tokens``."""
        n = len(tokens)
        padded = np.zeros(self.length, np.int32)
        padded[:n] = tokens
        x0 = self._embed(self.key, jnp.asarray(padded))
        out = {}
        with jax.default_matmul_precision("highest"):
            for quant in self.quants:
                x = x0
                for l in range(self.dims["num_layers"]):
                    x = self._layer(self.key, x, l, quant)
                out[quant] = self._head(self.key, x, quant)[:n]
        return out


def widest_gap(ref_logits, tokens: np.ndarray, first: int):
    """Largest amount by which token ``tokens[i]`` (for i >= first) lies
    below the best logit at the position before it."""
    lg = np.asarray(ref_logits, np.float32)[first - 1:len(tokens) - 1]
    t = np.asarray(tokens[first:])
    return float(np.max(lg.max(-1) - lg[np.arange(len(t)), t]))


def control_gap(ref_logits, ctl_logits, first: int, last: int):
    """Largest gap, in the reference's logits, of the token that the
    control puts first, at positions ``first - 1`` .. ``last - 1``."""
    r = np.asarray(ref_logits, np.float32)[first - 1:last]
    c = np.asarray(ctl_logits, np.float32)[first - 1:last]
    pick = c.argmax(-1)
    return float(np.max(r.max(-1) - r[np.arange(len(pick)), pick]))
