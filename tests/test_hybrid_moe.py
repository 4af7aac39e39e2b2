"""The hybrid model with experts (granite-4.0-h) at a small size on the CPU,
against the plain reference ``chipbench/reference/hybrid_lm.py`` on the
benchmark's seeded weights: prefill then decode through the cache gives
the reference's logits; the four shares of the experts, with the shared
expert counted once, give the uncut layer; a router that sends every token
to one expert drops nothing on the serving path; the float8 control fails
the tolerance; bf16 routing departs from float32 routing only at near
ties of the router's logits; and the ``held_experts`` kernel gives the
serving path's layer, with the shared expert folded in, at the few rows
for which the model takes it."""
import dataclasses
import functools
import pathlib
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from chipbench import harness  # noqa: E402
from chipbench.drivers import serve_hybrid as H  # noqa: E402
from chipbench.reference import hybrid_lm  # noqa: E402
from repro.configs import reduced_config  # noqa: E402
from repro.configs.base import RunConfig  # noqa: E402
from repro.models import layers as L  # noqa: E402
from repro.models import model as M  # noqa: E402
from repro.models import moe as MOE  # noqa: E402
from repro.parallel.sharding import local_env  # noqa: E402

ENV = local_env()
F32 = RunConfig(remat_policy="none", param_dtype="float32")
# one period of the published pattern at a tiny width: 16 experts, top 4,
# four shares of 4
TINY = dict(num_hidden_layers=10, hidden_size=64, num_attention_heads=4,
            num_key_value_heads=2, attention_multiplier=1 / 16,
            mamba_n_heads=8, mamba_d_head=16, mamba_d_state=16,
            mamba_chunk_size=8, intermediate_size=32,
            shared_intermediate_size=64, router_experts=16,
            num_local_experts=4, held_experts_start=0, num_experts_per_tok=4,
            vocab_size=512)
SHARES = 4
LENGTH, PROMPT = 48, 21
# float32 system against the float32 reference: they differ by summation
# order and by the SSD's chunked form against the sequential recurrence, a
# few 1e-8 on logits of std about 5e-3. The float8 control departs by about 4e-3.
LOGIT_ATOL = 2e-6


def _cfg(**over):
    base = harness.load_json(
        harness.HERE / "configs/granite-4.0-h-small-pp4ep4.json")
    cfg = dict(base, **dict(TINY, **over))
    cfg["layer_types"] = base["layer_types"][:cfg["num_hidden_layers"]]
    return cfg


def _params(cfg, key):
    """The benchmark's weights for the system, in float32."""
    p = H.make_params(H.model_config(cfg), RunConfig(param_dtype="bfloat16"),
                      key, cfg)
    return jax.tree.map(lambda a: a.astype(jnp.float32), p)


def _tokens(cfg, seed):
    return np.asarray(jax.random.randint(
        jax.random.PRNGKey(seed), (LENGTH,), 0, cfg["vocab_size"]), np.int32)


@pytest.mark.parametrize("start", [0, 12])
def test_prefill_then_decode_match_the_reference(start):
    """Logits of the prompt's last position (prefill) and of every token
    decoded after it through the cache, for a share of the experts."""
    cfg = _cfg(held_experts_start=start)
    mcfg = H.model_config(cfg)
    key = harness.jax_key(7 + start, 0)
    params = _params(cfg, key)
    toks = _tokens(cfg, start)
    want = np.asarray(hybrid_lm.Reference(key, cfg, 64).forward(toks)[None])
    lg, cache, _ = M.prefill(ENV, mcfg, params,
                             {"tokens": jnp.asarray(toks[None, :PROMPT])},
                             F32, max_len=64, kv_dtype=jnp.float32)
    step = jax.jit(lambda t, n, c: M.decode_step(ENV, mcfg, params, t, n, c,
                                                 F32))
    got = [lg[0]]
    for t in range(PROMPT, LENGTH):
        lg, cache = step(jnp.asarray(toks[None, t:t + 1]), jnp.asarray([t]),
                         cache)
        got.append(lg[0])
    got = np.stack([np.asarray(g) for g in got])
    np.testing.assert_allclose(got, want[PROMPT - 1:], rtol=0,
                               atol=LOGIT_ATOL)


def test_the_float8_control_fails_the_tolerance():
    cfg = _cfg()
    key = harness.jax_key(7, 0)
    out = hybrid_lm.Reference(key, cfg, 64, (None, "fp8")).forward(
        _tokens(cfg, 0))
    err = np.abs(np.asarray(out["fp8"]) - np.asarray(out[None])).max()
    assert err > 100 * LOGIT_ATOL


def _moe_case(start=0, held=0, experts=16):
    from repro.configs.granite_4_0_h_small import CONFIG
    return dataclasses.replace(
        CONFIG, d_model=64, d_ff=64, moe_d_ff=32, num_experts=experts,
        num_experts_per_tok=4, moe_held=held, moe_held_start=start)


def _held(params, start, held):
    return dict(params, **{k: params[k][start:start + held]
                           for k in ("w_in", "w_gate", "w_out")})


def _reference(params, shared, x, start, held):
    """The reference's expert layer (``hybrid_lm._moe``) over the experts
    ``params`` holds, ``held`` of them from ``start``, plus ``shared``."""
    s = {"held": held, "start": start, "k": 4}
    w = {"router": params["router"], "w_in": params["w_in"],
         "w_gate": params["w_gate"], "w_out": params["w_out"],
         "s_in": shared["w_in"], "s_gate": shared["w_gate"],
         "s_out": shared["w_out"]}
    with jax.default_matmul_precision("highest"):
        return jax.vmap(lambda xs: hybrid_lm._moe(xs, w, s, None))(x)


def test_the_shares_of_the_experts_add_up_to_the_uncut_layer():
    """Each chip of a stage computes its held experts' part; the four
    parts, with the shared expert (which every chip computes alike) counted
    once, are the uncut layer, the reference's with all 16 experts held."""
    cfg = _moe_case()
    params, _ = MOE.moe_init(cfg, jax.random.PRNGKey(0), jnp.float32)
    shared, _ = L.mlp_init(jax.random.PRNGKey(1), 64, 64, "swiglu",
                           jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 16, 64))
    held = cfg.num_experts // SHARES
    parts = [MOE.moe_serve(
        dataclasses.replace(cfg, moe_held=held, moe_held_start=s),
        _held(params, s, held), x)[0]
        for s in range(0, cfg.num_experts, held)]
    total = sum(parts) + L.mlp_apply(ENV, shared, x, "swiglu")
    uncut = _reference(params, shared, x, 0, cfg.num_experts)
    np.testing.assert_allclose(total, uncut, rtol=0, atol=1e-4)
    assert all(float(jnp.abs(p).max()) > 0 for p in parts)


@pytest.mark.parametrize("start,held", [(0, 16), (4, 4), (8, 4)])
def test_skewed_routing_drops_nothing_when_serving(start, held):
    """A router that gives expert 5 the top logit of every token sends all
    32 tokens to it, past the training capacity; the serving path drops
    none and matches the reference's layer over the share."""
    cfg = _moe_case(start, held if held < 16 else 0)
    params, _ = MOE.moe_init(_moe_case(), jax.random.PRNGKey(0), jnp.float32)
    params["router"] = params["router"].at[:, 5].set(0).at[0, 5].set(100.0)
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 16, 64)).at[..., 0].set(
        1.0)
    params = _held(params, start, held)
    out, counts = MOE.moe_serve(cfg, params, x)
    none = {n: jnp.zeros((64, 8) if n != "w_out" else (8, 64))
            for n in ("w_in", "w_gate", "w_out")}
    want = _reference(params, none, x, start, held)
    np.testing.assert_allclose(out, want, rtol=0, atol=1e-4)
    if start <= 5 < start + held:
        assert int(counts[5 - start]) == 32
        trained = MOE.moe_apply(ENV, cfg, params, x)
        assert float(jnp.abs(trained - want).max()) > 1e-3
    assert int(counts.sum()) == int(np.isin(
        np.asarray(jax.lax.top_k(x @ params["router"], 4)[1]),
        np.arange(start, start + held)).sum())


def test_bf16_routing_departs_only_at_near_ties():
    """The system routes on bf16 activations and the reference on float32
    ones: where the top-4 of 16 differ, the 4th and 5th float32 logits
    nearly tie. Over 4096 tokens a few per cent of decisions flip."""
    cfg = _moe_case()
    params, _ = MOE.moe_init(cfg, jax.random.PRNGKey(0), jnp.bfloat16)
    x = jax.random.normal(jax.random.PRNGKey(3), (1, 4096, 64))
    r = params["router"]
    exact = x @ r.astype(jnp.float32)
    rounded = x.astype(jnp.bfloat16).astype(jnp.float32) @ r
    top = jax.lax.top_k(exact, 5)[0][0]
    a = np.sort(np.asarray(jax.lax.top_k(exact, 4)[1][0]), -1)
    b = np.sort(np.asarray(jax.lax.top_k(rounded, 4)[1][0]), -1)
    flips = (a != b).any(-1)
    margin = np.asarray(top[:, 3] - top[:, 4])
    assert 0 < flips.mean() < 0.05
    # the inputs' bf16 rounding moves a logit by about 1e-2 here
    assert margin[flips].max() < 0.05 < np.median(margin)


@pytest.fixture
def interpreted(monkeypatch):
    """The ``held_experts`` kernel in interpret mode, as the CPU runs it."""
    monkeypatch.setattr(MOE, "held_experts", functools.partial(
        MOE.held_experts, interpret=True))


@pytest.mark.parametrize("shared", [False, True])
@pytest.mark.parametrize("rows", [1, 8, 13, 96])
def test_held_experts_kernel_matches_moe_serve(rows, shared, interpreted):
    """The kernel (interpreted) against ``moe_serve``, plus the shared
    expert's ``mlp_apply`` where it is folded in, over 4 of 16 experts held
    from 4: the first token is routed to no held expert, and the others
    leave some held experts with no weight."""
    cfg = _moe_case(4, 4)
    params, _ = MOE.moe_init(_moe_case(), jax.random.PRNGKey(0), jnp.float32)
    params["router"] = params["router"].at[0, 4:8].set(-100.0)
    params = _held(params, 4, 4)
    mlp, _ = L.mlp_init(jax.random.PRNGKey(1), 64, 64, "swiglu", jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(2), (rows, 1, 64))
    x = x.at[:, :, 0].set(0.0).at[0, 0, 0].set(10.0)
    w, _ = MOE._serve_route(cfg, params, x)
    assert not bool(w[0].any()) and not bool(w.all())
    want, want_counts = MOE.moe_serve(cfg, params, x)
    if shared:
        want = want + L.mlp_apply(ENV, mlp, x, "swiglu")
    got, counts = MOE.moe_serve_streamed(cfg, params, x,
                                         mlp if shared else None)
    assert got.shape == want.shape
    np.testing.assert_allclose(got, want, rtol=0, atol=2e-5)
    np.testing.assert_array_equal(counts, want_counts)


def test_decode_step_through_the_kernel_reads_each_layer_in_place(
        monkeypatch, interpreted):
    """granite's reduced config holds two periods, so the decode scan runs
    twice: with every expert layer through the kernel (interpreted), which
    reads its layer of the stacked weights, the decode step gives the XLA
    path's logits, cache and counts."""
    from repro.configs.granite_4_0_h_small import CONFIG
    cfg = reduced_config(CONFIG)
    assert cfg.scan_repeats == 2
    params = M.init_params(cfg, jax.random.PRNGKey(5), F32)
    cache = M.init_cache(cfg, 3, 16, kv_dtype=jnp.float32)
    token = jax.random.randint(jax.random.PRNGKey(6), (3, 1), 0,
                               cfg.vocab_size)
    pos = jnp.asarray([0, 4, 9])

    def step():
        return M.decode_step(ENV, cfg, params, token, pos, cache, F32,
                             expert_counts=True)
    want = step()
    rule = MOE.streams_held_experts
    monkeypatch.setattr(MOE, "streams_held_experts",
                        lambda n, platform, rows: rule(n, "tpu", rows))
    got = step()
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(g, w, rtol=0, atol=1e-5)
    assert int(got[2].sum()) == cfg.num_layers * 3 * cfg.num_experts_per_tok


@pytest.mark.parametrize("mesh_size,platform,rows,streams", [
    (1, "tpu", 96, True),           # a decode step of 96 slots
    (1, "tpu", MOE.RIDGE_ROWS, True),
    (1, "tpu", 256, False),         # a prefill: bound by its operations
    (4, "tpu", 96, False),          # a mesh: the custom call is not split
    (1, "cpu", 96, False),
])
def test_held_experts_kernel_only_where_weight_bytes_bound_one_chip(
        mesh_size, platform, rows, streams):
    assert 200 < MOE.RIDGE_ROWS < 256
    assert MOE.streams_held_experts(mesh_size, platform, rows) is streams
