"""Decode steps over a batch of sequences at different positions: every
family's logits match the full forward at float32, step after step, and each
step leaves every cache position it does not write bit for bit as it was."""
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import reduced_config
from repro.configs import granite_4_0_h_small
from repro.configs.base import RunConfig
from repro.kernels.decode_attn.ops import decode_attention
from repro.models import model as M
from repro.parallel.sharding import local_env

ENV = local_env()
FAMILIES = {
    "dense": "nemotron-4-15b",
    "local-attention": "gemma2-2b",
    "rglru": "recurrentgemma-9b",
    "ssd": "mamba2-2.7b",
    "cross-attention": "seamless-m4t-medium",
}
# prompt lengths of the three slots, and the decode steps after them: the
# last slot runs from 14 to 18, past the reduced gemma2-2b's local window
# of 16, so its ring cache wraps
PROMPTS, STEPS = (4, 9, 14), 5
SRC = 16                                  # encoder frames (seamless)


def _batched(caches):
    """The slots' batch-1 caches joined into one batch: the stacked leaves
    hold the batch on axis 1, the remainder blocks' on axis 0."""
    def join(axis):
        return lambda *leaves: jnp.concatenate(leaves, axis=axis)
    out = {"stack": jax.tree.map(join(1), *[c["stack"] for c in caches])}
    if "rem" in caches[0]:
        out["rem"] = jax.tree.map(join(0), *[c["rem"] for c in caches])
    return out


def _unwritten_unchanged(old, new, pos):
    """Each attention cache leaf of ``new`` equals ``old`` but at the one
    position per slot that the step writes (every layer of it); a cross
    cache is not written at all. Recurrent states are rewritten whole."""
    for entry_old, entry_new in zip(old["stack"].values(),
                                    new["stack"].values()):
        for key in entry_old:
            a, b = np.asarray(entry_old[key]), np.asarray(entry_new[key])
            if key in ("ck", "cv"):
                assert np.array_equal(a, b), key
            elif key in ("k", "v"):
                written = np.zeros(a.shape[:3], bool)     # (layer, slot, T)
                written[:, np.arange(a.shape[1]), pos % a.shape[2]] = True
                assert np.array_equal(a[~written], b[~written]), key
                assert not np.array_equal(a[written], b[written]), key


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_ragged_decode_steps_match_the_full_forward(family):
    cfg = reduced_config(FAMILIES[family])
    run = RunConfig(remat_policy="none", param_dtype="float32")
    key = jax.random.PRNGKey(0)
    params = M.init_params(cfg, key, run)
    total = max(PROMPTS) + STEPS
    tokens = jax.random.randint(key, (len(PROMPTS), total), 0,
                                cfg.vocab_size)
    batch = {"tokens": tokens}
    if cfg.is_encoder_decoder:
        batch["src_embeds"] = 0.02 * jax.random.normal(
            jax.random.PRNGKey(1), (len(PROMPTS), SRC, cfg.d_model))
    x = M.forward_train(ENV, cfg, params, batch, run)
    full = M._logits(ENV, cfg, params, x)

    caches = []
    for b, n in enumerate(PROMPTS):
        one = {k: v[b:b + 1] for k, v in batch.items()}
        one["tokens"] = tokens[b:b + 1, :n]
        caches.append(M.prefill(ENV, cfg, params, one, run, max_len=total,
                                kv_dtype=jnp.float32)[1])
    cache = _batched(caches)
    step = jax.jit(lambda t, n, kv: M.decode_step(ENV, cfg, params, t, n,
                                                  kv, run))
    pos = np.array(PROMPTS)
    for _ in range(STEPS):
        token = tokens[np.arange(len(PROMPTS)), pos][:, None]
        logits, new = step(token, jnp.asarray(pos, jnp.int32), cache)
        np.testing.assert_allclose(
            logits, full[np.arange(len(PROMPTS)), pos], atol=2e-2)
        _unwritten_unchanged(cache, new, pos)
        cache, pos = new, pos + 1


@pytest.mark.parametrize("name", ["nemotron-4-15b", "granite-4.0-h-small"])
def test_stacked_decode_reads_the_cache_in_place_like_decode_attend(
        name, monkeypatch):
    """The stacked decode step with the ``decode_attn`` kernel (interpret
    mode, as on one TPU chip) gives the logits and the cache of the path
    that slices each layer's keys and values out for ``decode_attend``,
    step after step, for a dense config and a hybrid with an attention
    layer."""
    cfg = reduced_config(granite_4_0_h_small.CONFIG
                         if name == "granite-4.0-h-small" else name)
    run = RunConfig(remat_policy="none", param_dtype="float32")
    key = jax.random.PRNGKey(0)
    params = M.init_params(cfg, key, run)
    total = max(PROMPTS) + STEPS
    tokens = jax.random.randint(key, (len(PROMPTS), total), 0,
                                cfg.vocab_size)
    cache = _batched([M.prefill(ENV, cfg, params,
                                {"tokens": tokens[b:b + 1, :n]}, run,
                                max_len=total, kv_dtype=jnp.float32)[1]
                      for b, n in enumerate(PROMPTS)])

    def steps():
        kv, pos, out = cache, np.array(PROMPTS), []
        step = jax.jit(lambda t, n, c: M.decode_step(ENV, cfg, params, t, n,
                                                     c, run))
        for _ in range(STEPS):
            token = tokens[np.arange(len(PROMPTS)), pos][:, None]
            logits, kv = step(token, jnp.asarray(pos, jnp.int32), kv)
            out.append((logits, kv))
            pos = pos + 1
        return out

    want = steps()
    monkeypatch.setattr(M, "_in_place_attention", lambda env: partial(
        decode_attention, interpret=True))
    got = steps()
    for (lg, kv), (lw, kw) in zip(got, want):
        np.testing.assert_allclose(lg, lw, atol=1e-5)
        for a, b in zip(jax.tree.leaves(kv), jax.tree.leaves(kw)):
            np.testing.assert_allclose(a, b, atol=1e-5)
