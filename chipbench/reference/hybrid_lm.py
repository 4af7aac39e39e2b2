"""The plain reference of the served hybrid model (granite-4.0-h), and its
weights.

Imports nothing of the system under test. The configuration file gives the
model in the published ``config.json``'s keys. The weights are the
benchmark's: :func:`layer_weights` draws layer ``l`` from the seed's key
alone (each held expert from its own index), so the benchmark makes all
layers in one jitted call for the system, and this reference makes them
again, one layer at a time, once the system's state is freed.

The forward pass follows the published equations: token embedding times
``embedding_multiplier``; per layer ``x += r * mixer(rmsnorm(x))`` and
``x += r * (experts(h) + shared(h))`` with ``h = rmsnorm(x)`` and ``r`` the
``residual_multiplier``; a final RMSNorm, the tied embedding as the head,
and the logits divided by ``logits_scaling``. RMSNorm multiplies by
``1 + scale``. The mixer is, by ``layer_types``:

* ``mamba``: Mamba-2 with one group. ``in_proj`` gives [z, x B C, dt]; a
  causal depthwise convolution with bias and SiLU over x B C;
  ``dt = softplus(dt + dt_bias)``, ``A = -exp(A_log)``; then token by token
  ``h = exp(dt A) h + dt x B^T`` and ``y = h C + D x``, a plain sequential
  recurrence; ``rmsnorm(y * silu(z))`` and ``out_proj``.
* ``attention``: causal GQA with no positional encoding, scaled by
  ``attention_multiplier``; query head ``j`` reads key/value head
  ``j // (heads / kv_heads)``.

The experts: router logits over all ``router_experts``, the top
``num_experts_per_tok`` of them and a softmax over those; every held expert
(``num_local_experts`` of them from ``held_experts_start``) is evaluated
densely on every token, a SwiGLU ``(silu(h w_gate) * h w_in) w_out``,
weighted by its routing weight (zero where it was not chosen); the shared
expert is a SwiGLU of width ``shared_intermediate_size``. Everything is
float32 at the highest matmul precision; ``quant="fp8"`` instead rounds
every weight matrix (per output column) and every matmul input (per row)
to float8 e4m3, the control of PERF.md.
"""
from __future__ import annotations

import math
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference.dense_lm import NORM_STD, _mm, _normal, _q8, _rmsnorm

HIGHEST = jax.lax.Precision.HIGHEST
# the attention's query rows per block, so that the scores fit
QUERY_BLOCK = 512
# dt drawn log-uniform in this range, as Mamba-2 initialises it
DT_RANGE = (1e-3, 1e-1)
# A = -exp(A_log) drawn uniform in this range, as Mamba-2 initialises it
A_RANGE = (1.0, 16.0)


def shapes(cfg: Dict) -> Dict:
    """The sizes the equations use, from the configuration's keys."""
    d = cfg["hidden_size"]
    di = cfg["mamba_expand"] * d
    return {"d": d, "di": di, "n": cfg["mamba_d_state"],
            "nh": cfg["mamba_n_heads"], "p": cfg["mamba_d_head"],
            "w": cfg["mamba_d_conv"], "hq": cfg["num_attention_heads"],
            "hkv": cfg["num_key_value_heads"],
            "dh": d // cfg["num_attention_heads"],
            "ff": cfg["intermediate_size"],
            "sff": cfg["shared_intermediate_size"],
            "held": cfg["num_local_experts"],
            "start": cfg["held_experts_start"],
            "experts": cfg["router_experts"],
            "k": cfg["num_experts_per_tok"], "v": cfg["vocab_size"]}


def _experts(key, idx, shape, fan_in, dtype):
    """One matrix per expert of ``idx``, each drawn from its own index."""
    return jax.vmap(lambda j: _normal(jax.random.fold_in(key, j), shape,
                                      fan_in, dtype))(idx)


def layer_weights(key, l, kind: str, cfg: Dict, dtype=jnp.bfloat16) -> Dict:
    """Layer ``l``'s weights (its mixer of ``kind``, its experts held here
    and its shared expert): matrices in ``dtype``, the router too; norm
    scales and the Mamba-2 scalars in float32."""
    s = shapes(cfg)
    d, ff, sff = s["d"], s["ff"], s["sff"]
    ks = jax.random.split(jax.random.fold_in(key, l), 17)
    w = {"ln1": jax.random.normal(ks[0], (d,), jnp.float32) * NORM_STD,
         "ln2": jax.random.normal(ks[1], (d,), jnp.float32) * NORM_STD}
    if kind == "mamba":
        di, n, nh, cw = s["di"], s["n"], s["nh"], s["w"]
        conv = di + 2 * n
        dt = jnp.exp(jax.random.uniform(
            ks[6], (nh,), jnp.float32, *map(math.log, DT_RANGE)))
        w.update({
            "in_proj": _normal(ks[2], (d, 2 * di + 2 * n + nh), d, dtype),
            "conv_w": _normal(ks[3], (cw, conv), cw, dtype),
            "conv_b": (jax.random.normal(ks[4], (conv,), jnp.float32)
                       * NORM_STD).astype(dtype),
            "a_log": jnp.log(jax.random.uniform(ks[5], (nh,), jnp.float32,
                                                *A_RANGE)),
            "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),     # softplus^-1(dt)
            "d_skip": 1.0 + jax.random.normal(ks[7], (nh,),
                                              jnp.float32) * NORM_STD,
            "norm": jax.random.normal(ks[8], (di,), jnp.float32) * NORM_STD,
            "out_proj": _normal(ks[9], (di, d), di, dtype)})
    elif kind == "attention":
        hq, hkv, dh = s["hq"], s["hkv"], s["dh"]
        w.update({"wq": _normal(ks[2], (d, hq, dh), d, dtype),
                  "wk": _normal(ks[3], (d, hkv, dh), d, dtype),
                  "wv": _normal(ks[4], (d, hkv, dh), d, dtype),
                  "wo": _normal(ks[5], (hq, dh, d), hq * dh, dtype)})
    else:
        raise ValueError(f"unknown layer type {kind!r}")
    idx = s["start"] + jnp.arange(s["held"])
    w.update({"router": _normal(ks[10], (d, s["experts"]), d, dtype),
              "w_in": _experts(ks[11], idx, (d, ff), d, dtype),
              "w_gate": _experts(ks[12], idx, (d, ff), d, dtype),
              "w_out": _experts(ks[13], idx, (ff, d), ff, dtype),
              "s_in": _normal(ks[14], (d, sff), d, dtype),
              "s_gate": _normal(ks[15], (d, sff), d, dtype),
              "s_out": _normal(ks[16], (sff, d), sff, dtype)})
    return w


def outer_weights(key, cfg: Dict, dtype=jnp.bfloat16) -> Dict:
    """The tied embedding and the final norm scale. The embedding's std is
    1 / (embedding_multiplier x sqrt(hidden_size)), so an embedded token has
    about unit norm: drawn larger, a token's own row stands out in the tied
    head's logits by embedding_multiplier x sqrt(hidden_size) x std standard
    deviations, and every answer repeats its last token."""
    s = shapes(cfg)
    ks = jax.random.split(jax.random.fold_in(key, 1 << 20), 2)
    fan_in = s["d"] * cfg["embedding_multiplier"] ** 2
    return {"embed": _normal(ks[0], (s["v"], s["d"]), fan_in, dtype),
            "final": jax.random.normal(ks[1], (s["d"],), jnp.float32)
            * NORM_STD}


# ---------------------------------------------------------- forward

def _emm(x, w, quant):
    """Every expert on every row: x (S, K) @ w (E, K, N) -> (E, S, N)."""
    if quant == "fp8":
        x, w = _q8(x, -1), _q8(w, 1)
    return jnp.einsum("sk,ekn->esn", x, w, precision=HIGHEST)


def _swiglu(x, w_in, w_gate, w_out, quant):
    a = jax.nn.silu(_mm(x, w_gate, quant)) * _mm(x, w_in, quant)
    return _mm(a, w_out, quant)


def _route(h, w, s, quant):
    """Each token's routing weight on each held expert, (S, held): the
    softmax over its top-k router logits, zero where not chosen."""
    top, ids = jax.lax.top_k(_mm(h, w["router"], quant), s["k"])
    gate = jax.nn.softmax(top, axis=-1)
    held = jax.nn.one_hot(ids - s["start"], s["held"], dtype=jnp.float32)
    return jnp.einsum("skh,sk->sh", held, gate)


def _moe(h, w, s, quant):
    wts = _route(h, w, s, quant)
    a = (jax.nn.silu(_emm(h, w["w_gate"], quant)) * _emm(h, w["w_in"], quant)
         * wts.T[:, :, None])                                # (held, S, ff)
    w_out = w["w_out"]
    if quant == "fp8":
        a, w_out = _q8(a, -1), _q8(w_out, 1)
    experts = jnp.einsum("esf,efd->sd", a, w_out, precision=HIGHEST)
    return experts + _swiglu(h, w["s_in"], w["s_gate"], w["s_out"], quant)


def _attention(h, w, s, scale, quant):
    n, d = h.shape
    hq, hkv, dh = s["hq"], s["hkv"], s["dh"]
    q = _mm(h, w["wq"].reshape(d, hq * dh), quant).reshape(n, hq, dh)
    k = _mm(h, w["wk"].reshape(d, hkv * dh), quant).reshape(n, hkv, dh)
    v = _mm(h, w["wv"].reshape(d, hkv * dh), quant).reshape(n, hkv, dh)
    k = jnp.repeat(k, hq // hkv, axis=1)
    v = jnp.repeat(v, hq // hkv, axis=1)
    qb = math.gcd(n, QUERY_BLOCK)

    def block(i):
        qi = jax.lax.dynamic_slice_in_dim(q, i * qb, qb)
        scores = jnp.einsum("qhd,khd->hqk", qi, k, precision=HIGHEST) * scale
        causal = jnp.arange(n)[None, :] <= (i * qb + jnp.arange(qb))[:, None]
        p = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf), -1)
        return jnp.einsum("hqk,khd->qhd", p, v, precision=HIGHEST)
    o = jax.lax.map(block, jnp.arange(n // qb)).reshape(n, hq * dh)
    return _mm(o, w["wo"].reshape(hq * dh, d), quant)


def _mamba(h, w, s, eps, quant):
    n = h.shape[0]
    di, ns, nh, p, cw = s["di"], s["n"], s["nh"], s["p"], s["w"]
    proj = _mm(h, w["in_proj"], quant)
    z, xbc, dt = (proj[:, :di], proj[:, di:2 * di + 2 * ns],
                  proj[:, 2 * di + 2 * ns:])
    past = jnp.concatenate([jnp.zeros((cw - 1, xbc.shape[1])), xbc])
    xbc = jax.nn.silu(w["conv_b"] + sum(w["conv_w"][j] * past[j:j + n]
                                        for j in range(cw)))
    xs = xbc[:, :di].reshape(n, nh, p)
    b, c = xbc[:, di:di + ns], xbc[:, di + ns:]
    dt = jax.nn.softplus(dt + w["dt_bias"])
    a = -jnp.exp(w["a_log"])

    def step(state, inp):
        x_t, b_t, c_t, dt_t = inp
        state = (jnp.exp(dt_t * a)[:, None, None] * state
                 + dt_t[:, None, None] * x_t[:, :, None] * b_t[None, None])
        return state, jnp.einsum("hpn,n->hp", state, c_t, precision=HIGHEST)
    _, y = jax.lax.scan(step, jnp.zeros((nh, p, ns)), (xs, b, c, dt))
    y = (y + w["d_skip"][None, :, None] * xs).reshape(n, di)
    y = _rmsnorm(y * jax.nn.silu(z), w["norm"], eps)
    return _mm(y, w["out_proj"], quant)


def layer_forward(x, w, kind: str, cfg: Dict,
                  quant: Optional[str] = None):
    """One layer over one sequence ``x`` (S, d), float32."""
    s, eps, r = shapes(cfg), cfg["rms_norm_eps"], cfg["residual_multiplier"]
    w = {k: v.astype(jnp.float32) for k, v in w.items()}
    h = _rmsnorm(x, w["ln1"], eps)
    if kind == "mamba":
        y = _mamba(h, w, s, eps, quant)
    else:
        y = _attention(h, w, s, cfg["attention_multiplier"], quant)
    x = x + r * y
    return x + r * _moe(_rmsnorm(x, w["ln2"], eps), w, s, quant)


def logits(x, outer, cfg: Dict, quant: Optional[str] = None):
    x = _rmsnorm(x, outer["final"].astype(jnp.float32), cfg["rms_norm_eps"])
    return (_mm(x, outer["embed"].astype(jnp.float32).T, quant)
            / cfg["logits_scaling"])


class Reference:
    """The reference forward over sequences padded to ``length``, one
    jitted program per layer type and piece, weights drawn anew from
    ``key``. The key and the layer are arguments of the programs, so every
    seed runs the same ones."""

    def __init__(self, key, cfg: Dict, length: int, quants=(None,)):
        self.key, self.cfg, self.length = key, cfg, length
        self.quants = quants

        def layer(key, x, l, kind, quant):
            return layer_forward(x, layer_weights(key, l, kind, cfg), kind,
                                 cfg, quant)

        def head(key, x, quant):
            return logits(x, outer_weights(key, cfg), cfg, quant)

        def embed(key, tokens):
            return (outer_weights(key, cfg)["embed"][tokens].astype(
                jnp.float32) * cfg["embedding_multiplier"])
        self._layer = jax.jit(layer, static_argnums=(3, 4))
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
                for l, kind in enumerate(self.cfg["layer_types"]):
                    x = self._layer(self.key, x, l, kind, quant)
                out[quant] = self._head(self.key, x, quant)[:n]
        return out
