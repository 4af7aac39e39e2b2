"""Pallas kernels vs pure-jnp oracles (interpret=True), sweeping shapes and
dtypes as required for each kernel."""
import functools
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attn.ops import flash_attention
from repro.kernels.flash_attn.ref import attention_ref
from repro.kernels.decode_attn.ops import decode_attention
from repro.kernels.decode_attn.ref import decode_ref
from repro.kernels.ciao_gather.ops import ciao_gather
from repro.kernels.held_experts.ops import held_experts
from repro.kernels.held_experts.ref import held_experts_ref
from repro.kernels.ciao_gather.ref import cache_sim_ref, gather_ref
from repro.models.attention import decode_attend
from repro.models.layers import mlp_activate
from repro.parallel.sharding import local_env


def _fold(q, k, v):
    b, sq, hq, d = q.shape
    g = hq // k.shape[2]
    kb = jnp.repeat(k, g, 2).transpose(0, 2, 1, 3).reshape(b * hq, -1, d)
    vb = jnp.repeat(v, g, 2).transpose(0, 2, 1, 3).reshape(b * hq, -1, d)
    qb = q.transpose(0, 2, 1, 3).reshape(b * hq, sq, d)
    return qb, kb, vb


@pytest.mark.parametrize("dtype,atol", [(jnp.float32, 2e-5),
                                        (jnp.bfloat16, 3e-2)])
@pytest.mark.parametrize("b,s,hq,hkv,d,causal,window,cap", [
    (1, 128, 2, 2, 64, True, 0, 0.0),
    (2, 256, 4, 2, 64, True, 0, 0.0),       # GQA
    (1, 128, 8, 1, 32, True, 64, 50.0),     # MQA + local + softcap
    (2, 192, 4, 4, 128, True, 0, 0.0),      # pad (192 % 128 != 0)
    (1, 128, 2, 2, 64, False, 0, 0.0),      # bidirectional
])
def test_flash_attention_vs_oracle(b, s, hq, hkv, d, causal, window, cap,
                                   dtype, atol):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (b, s, hq, d), dtype)
    k = jax.random.normal(ks[1], (b, s, hkv, d), dtype)
    v = jax.random.normal(ks[2], (b, s, hkv, d), dtype)
    out = flash_attention(q, k, v, causal=causal, window=window,
                          softcap=cap, interpret=True)
    qb, kb, vb = _fold(q, k, v)
    ref = attention_ref(qb.astype(jnp.float32), kb.astype(jnp.float32),
                        vb.astype(jnp.float32), causal=causal,
                        window=window, softcap=cap)
    ref = ref.reshape(b, hq, s, d).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(out.astype(jnp.float32), ref, atol=atol)


@pytest.mark.parametrize("dtype,atol", [(jnp.float32, 2e-5),
                                        (jnp.bfloat16, 3e-2)])
@pytest.mark.parametrize("b,s,hq,hkv,d", [
    (2, 256, 4, 2, 64),
    (3, 512, 4, 4, 128),
    (1, 300, 8, 2, 32),                     # pad
])
def test_decode_attention_vs_oracle(b, s, hq, hkv, d, dtype, atol):
    ks = jax.random.split(jax.random.PRNGKey(1), 4)
    q = jax.random.normal(ks[0], (b, 1, hq, d), dtype)
    ck = jax.random.normal(ks[1], (b, s, hkv, d), dtype)
    cv = jax.random.normal(ks[2], (b, s, hkv, d), dtype)
    lens = jax.random.randint(ks[3], (b,), 1, s + 1)
    out = decode_attention(q, ck, cv, lens, interpret=True)
    qb, kb, vb = _fold(q, ck, cv)
    ref = decode_ref(qb.astype(jnp.float32), kb.astype(jnp.float32),
                     vb.astype(jnp.float32), jnp.repeat(lens, hq))
    ref = ref.reshape(b, hq, 1, d).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(out.astype(jnp.float32), ref, atol=atol)


@pytest.mark.parametrize("dtype,atol", [(jnp.float32, 2e-5),
                                        (jnp.bfloat16, 2e-2)])
@pytest.mark.parametrize("cap", [0.0, 50.0])
@pytest.mark.parametrize("hq,hkv", [(48, 8), (32, 8)])   # nemotron, granite
def test_decode_attention_reads_a_stacked_cache_in_place(hq, hkv, cap, dtype,
                                                         atol):
    """One layer of a stacked cache, read in place, against
    ``attention.decode_attend`` on that layer alone. Every other layer and
    every position past a slot's length hold NaN: a finite, matching output
    shows that the kernel reads its own layer and nothing past a length."""
    layers, layer, bkv, d = 3, 1, 128, 128
    lens = np.array([1, bkv, bkv + 1, 3 * bkv], np.int32)
    b, s = len(lens), int(lens.max())
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(ks[0], (b, 1, hq, d), dtype)
    k = jax.random.normal(ks[1], (b, s, hkv, d), dtype)
    v = jax.random.normal(ks[2], (b, s, hkv, d), dtype)
    live = (np.arange(s)[None, :] < lens[:, None])[:, :, None, None]
    stack_k, stack_v = (jnp.full((layers,) + x.shape, jnp.nan, dtype).at[
        layer].set(jnp.where(live, x, jnp.nan)) for x in (k, v))
    out = decode_attention(q, stack_k, stack_v, jnp.asarray(lens),
                           layer=jnp.int32(layer), softcap=cap, block_kv=bkv,
                           interpret=True)
    cfg = types.SimpleNamespace(head_dim=d, query_scale=0.0,
                                attn_logit_softcap=cap)
    clean_k, clean_v = (jnp.where(live, x, 0) for x in (k, v))
    ref = decode_attend(local_env(), cfg, q, clean_k, clean_v,
                        jnp.asarray(lens - 1), ring=False)
    assert bool(jnp.isfinite(out).all())
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=atol)


@pytest.mark.parametrize("dtype,atol", [(jnp.float32, 2e-5),
                                        (jnp.bfloat16, 3e-2)])
@pytest.mark.parametrize("activation", ["swiglu", "gelu"])
@pytest.mark.parametrize("shared", [False, True])
def test_held_experts_reads_stacked_weights_in_place(shared, activation,
                                                     dtype, atol):
    """Three held experts of ff 256 in blocks of 128, and a shared expert
    of ff 384, on 20 rows (padded to 32): one layer of weights stacked over
    three, read in place, against the oracle on that layer alone. Every
    other layer holds NaN, so a finite, matching output shows that the
    kernel reads its own layer alone."""
    layers, layer, e, d, ff, sff, rows = 3, 1, 3, 128, 256, 384, 20
    ks = iter(jax.random.split(jax.random.PRNGKey(4), 10))
    names = (("w_in", "w_gate", "w_out") if activation == "swiglu"
             else ("w_in", "w_out"))

    def weights(shape_in, shape_out):
        return {n: jax.random.normal(next(ks), shape_out if n == "w_out"
                                     else shape_in, dtype) * 0.1
                for n in names}
    held = weights((e, d, ff), (e, ff, d))
    common = weights((d, sff), (sff, d)) if shared else None

    def stacked(p):
        return None if p is None else {
            n: jnp.full((layers,) + w.shape, jnp.nan, dtype).at[layer].set(w)
            for n, w in p.items()}
    x = jax.random.normal(next(ks), (rows, 1, d), dtype)
    w = jax.random.uniform(next(ks), (rows, 1, e)) * (
        jax.random.uniform(next(ks), (rows, 1, e)) < 0.6)
    act = functools.partial(mlp_activate, activation)
    out = held_experts(x, w, stacked(held), stacked(common),
                       layer=jnp.int32(layer), activate=act, block_ff=128,
                       interpret=True)
    ref = held_experts_ref(x, w, held, common, activate=act)
    assert out.shape == x.shape and out.dtype == dtype
    assert bool(jnp.isfinite(out).all())
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=atol)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("n,d,t,c_main,c_iso", [
    (500, 128, 384, 64, 16),
    (1000, 256, 640, 128, 32),
    (64, 128, 130, 16, 8),                  # pad + tiny cache
])
def test_ciao_gather_vs_oracle(n, d, t, c_main, c_iso, dtype):
    rng = np.random.default_rng(0)
    table = jax.random.normal(jax.random.PRNGKey(2), (n, d), dtype)
    streams = rng.integers(0, 4, t).astype(np.int32)
    idx = np.where(streams == 3, rng.integers(0, 8, t),
                   rng.integers(0, n, t)).astype(np.int32)
    iso = np.array([0, 0, 0, 1], np.int32)
    out, stats = ciao_gather(table, jnp.array(idx), jnp.array(streams),
                             jnp.array(iso), c_main=c_main, c_iso=c_iso,
                             interpret=True)
    np.testing.assert_array_equal(np.asarray(out),
                                  np.asarray(gather_ref(table,
                                                        jnp.array(idx))))
    ref_stats = cache_sim_ref(idx, streams, iso, c_main=c_main,
                              c_iso=c_iso, num_streams=4)
    np.testing.assert_array_equal(np.asarray(stats), ref_stats)


def test_ciao_gather_isolation_protects_main():
    """The CIAO property at kernel level: isolating a hammering stream
    lifts the other streams' hit rates (its hot set stops evicting theirs)."""
    rng = np.random.default_rng(1)
    n, d, t = 256, 128, 2048
    table = jnp.ones((n, d), jnp.float32)
    streams = rng.integers(0, 4, t).astype(np.int32)
    # streams 0-2 each loop a small private set; stream 3 sweeps everything
    priv = (streams[:, None] * 8 + rng.integers(0, 8, (t, 1))).ravel()
    sweep = rng.integers(0, n, t)
    idx = np.where(streams == 3, sweep, priv).astype(np.int32)

    def misses(iso_bit):
        _, stats = ciao_gather(table, jnp.array(idx), jnp.array(streams),
                               jnp.array([0, 0, 0, iso_bit], np.int32),
                               c_main=32, c_iso=16, interpret=True)
        return float(np.asarray(stats)[:3, 1].sum())

    # isolating the sweeping stream cuts the victims' misses dramatically
    assert misses(1) < misses(0) / 3
