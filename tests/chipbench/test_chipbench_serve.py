"""The serving driver end to end on the CPU at a tiny size, sound and
with the timed path broken underneath; and its control."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import harness
from chipbench.drivers import serve

_REAL_PROGRAMS = serve.programs


def _run(spec, seed=2**33 + 11):
    return harness.execute(spec["cell"]["name"], seed, 1.0, False,
                           jax.devices()[:1], spec)


def test_sound_run_is_correct(serve_spec, gap_limit):
    line = _run(serve_spec)
    assert line["correct"], line["checks"]
    assert line["checks"]["widest_gap"]["value"] < gap_limit / 3
    assert line["attempted"] > 0


def _broken(fault):
    def programs(*a, **kw):
        fns = _REAL_PROGRAMS(*a, **kw)
        dec, ins = fns["decode"], fns["insert"]
        if fault == "state_unchanged":
            def decode(p, tok, pos, cache):
                nt, _ = dec(p, tok, pos, jax.tree.map(jnp.copy, cache))
                return nt, cache
            fns["decode"] = decode
        elif fault == "half_batch":
            def decode(p, tok, pos, cache):
                nt, cache = dec(p, tok, pos, cache)
                half = nt.shape[0] // 2
                return nt.at[half:].set(tok[half:, 0]), cache
            fns["decode"] = decode
        elif fault == "wrong_slot":
            def insert(bc, c, slot):
                return ins(bc, c, (slot + 1) % 4)
            fns["insert"] = insert
        elif fault == "token_altered":
            def decode(p, tok, pos, cache):
                nt, cache = dec(p, tok, pos, cache)
                return (nt + 1) % 512, cache
            fns["decode"] = decode
        return fns
    return programs


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch",
                                   "wrong_slot", "token_altered"])
def test_fault_is_caught(serve_spec, monkeypatch, fault, gap_limit):
    monkeypatch.setattr(serve, "programs", _broken(fault))
    line = _run(serve_spec)
    assert not line["correct"]
    assert line["checks"]["widest_gap"]["value"] > gap_limit


def test_control_fails(serve_spec, gap_limit):
    """The control: the reference in float8 e4m3 in the program's place.
    At each served position, the token it puts first lies further below
    the float32 reference's best than the limit allows."""
    drv = harness.driver_module(serve_spec)
    st = drv.setup(serve_spec, 5, jax.devices()[:1], 1.0)
    drv.window(st, 1.0)
    drv.release(st)
    res = drv.compare(st, quants=(None, "fp8"))
    assert res["gaps"][None] < gap_limit < res["gaps"]["fp8"]


def test_weights_layer_by_layer_match_the_stack(serve_spec):
    """The reference's weights, drawn one layer at a time, are the ones
    the system was given, bit for bit."""
    from chipbench.reference import dense_lm
    from repro.configs.base import RunConfig
    cfg = serve_spec["config"]
    dims = serve.dims_of(cfg)
    key = harness.jax_key(3, 0)
    params = serve.make_params(serve.model_config(cfg),
                               RunConfig(param_dtype="bfloat16"), key, dims)
    one = jax.jit(lambda k, l: dense_lm.layer_weights(k, l, dims))
    for l in range(dims["num_layers"]):
        w = one(key, l)
        np.testing.assert_array_equal(
            np.asarray(w["w_in"], np.float32),
            np.asarray(params["stack"]["b0"]["mlp"]["w_in"][l], np.float32))


@pytest.mark.parametrize("cv", [1.0, 2.0])
def test_every_seed_gets_the_same_work(serve_spec, cv):
    """The seed orders the requests and their gaps; the set of lengths
    and gaps is the mix's, and every request falls due in the window."""
    from chipbench import gen
    mix = dict(serve_spec["traffic"], cache_len=24,
               arrivals={"kind": "gamma", "cv": cv})
    a = gen.serve_requests(mix, 2**33 + 1, 5.0)
    b = gen.serve_requests(mix, 12, 5.0)
    assert len(a) == len(b) == 80

    def sizes(reqs):
        return sorted((r["prompt_len"], r["output_len"]) for r in reqs)

    def gaps(reqs):
        due = [r["due"] for r in reqs] + [5.0]
        return sorted(np.round(np.diff(due), 9))
    assert sizes(a) == sizes(b)
    assert gaps(a) == pytest.approx(gaps(b))
    assert [r["due"] for r in a] != [r["due"] for r in b]
    assert all(0 <= r["due"] < 5.0 for r in a)
    assert all(r["prompt_len"] + r["output_len"] <= 24 for r in a)


def test_a_request_is_issued_when_it_falls_due(serve_spec):
    """Above what one slot serves, requests queue: each is issued at the
    time it fell due, so its first token holds the wait, and those still
    waiting at the close are sent but not served."""
    spec = dict(serve_spec, traffic=dict(
        serve_spec["traffic"], slots=1, warm_slots=1, rate_per_s=40.0,
        output_len={"kind": "lognormal", "median": 35, "sigma": 0.1,
                    "min": 30, "max": 40}))
    drv = harness.driver_module(spec)
    st = drv.setup(spec, 9, jax.devices()[:1], 0.5)
    drv.window(st, 0.5)
    served = [r for r in drv._requests(st) if "due" in r]
    assert drv.counts(st) == (20, 0)
    assert st["pending"] and len(served) + len(st["pending"]) == 20
    for r in served:
        assert r["t_issue"] == st["t_start"] + r["due"]
    # one slot: each first token waits for the answer before it
    served.sort(key=lambda r: r["due"])
    assert len(served) >= 2
    for a, b in zip(served, served[1:]):
        assert b["t_tokens"][0] >= a["t_done"]


def test_trace_starts_at_the_offset(serve_spec):
    drv = harness.driver_module(serve_spec)
    st = drv.setup(serve_spec, 4, jax.devices()[:1], 1.0)
    calls = []
    drv.window(st, 1.0, at=(0.6, lambda: calls.append(len(st["steps"]))))
    assert calls == [st["traced_from"][0]]
    n_before = st["n_steps_before"]
    assert n_before < st["traced_from"][0] < len(st["steps"])
