"""Serving cells of a hybrid model with experts (granite-4.0-h): the open-
loop loop of :mod:`chipbench.drivers.serve` over the same model path
(``repro.models.model.prefill`` and ``decode_step``), with its own model,
weights and reference (``chipbench.reference.hybrid_lm``).

The configuration file gives the model in the published ``config.json``'s
keys; ``layer_types`` is one whole period of the layer pattern, so the
model's stack is that period once. The experts held here are
``num_local_experts`` of them from ``held_experts_start``; the router keeps
its ``router_experts`` outputs. The decode program also returns the routed
assignments that land on each held expert of each layer, which the driver
keeps per step under ``expert_counts``.
"""
from __future__ import annotations

import collections
import time
from typing import Dict, List

import numpy as np

from chipbench import gen
from chipbench.drivers.serve import (  # noqa: F401  (the loop, as served)
    _admit, _decode, _requests, counts, end_to_end, release, sample,
    warm_lengths, window)
from chipbench.drivers import serve
from chipbench.harness import jax_key, rng, span
from chipbench.reference import dense_lm, hybrid_lm

# the warm requests are drawn from this many of the mix's requests a slot
WARM_POOL = 64

# what the model computes, beside the sizes: anything else is refused
FIXED = {"hidden_act": "silu", "normalization_function": "rmsnorm",
         "position_embedding_type": "nope", "mamba_n_groups": 1,
         "mamba_conv_bias": True, "mamba_proj_bias": False,
         "attention_bias": False, "tie_word_embeddings": True}
BLOCKS = {"mamba": "ssd", "attention": "global"}


def model_config(cfg: dict):
    from repro.configs.base import ModelConfig
    for k, v in FIXED.items():
        if cfg[k] != v:
            raise ValueError(f"{k} = {cfg[k]!r}: the model computes {v!r}")
    d, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    if cfg["mamba_n_heads"] * cfg["mamba_d_head"] != cfg["mamba_expand"] * d:
        raise ValueError("mamba_n_heads x mamba_d_head != mamba_expand x "
                         "hidden_size")
    return ModelConfig(
        name=cfg["name"], family="hybrid",
        num_layers=cfg["num_hidden_layers"], d_model=d, num_heads=heads,
        num_kv_heads=cfg["num_key_value_heads"], head_dim=d // heads,
        d_ff=cfg["shared_intermediate_size"], vocab_size=cfg["vocab_size"],
        pattern=tuple(BLOCKS[t] for t in cfg["layer_types"]),
        query_scale=cfg["attention_multiplier"], use_rope=False,
        mlp_activation="swiglu", num_experts=cfg["router_experts"],
        num_experts_per_tok=cfg["num_experts_per_tok"],
        moe_d_ff=cfg["intermediate_size"], moe_dense_residual=True,
        moe_held=cfg["num_local_experts"],
        moe_held_start=cfg["held_experts_start"],
        ssm_state_dim=cfg["mamba_d_state"], ssm_head_dim=cfg["mamba_d_head"],
        ssm_expand=cfg["mamba_expand"], ssm_chunk=cfg["mamba_chunk_size"],
        conv_width=cfg["mamba_d_conv"], ssd_ffn=True, tie_embeddings=True,
        embed_scale=False, embed_multiplier=cfg["embedding_multiplier"],
        residual_multiplier=cfg["residual_multiplier"],
        logits_scaling=cfg["logits_scaling"], norm_eps=cfg["rms_norm_eps"])


def _block(w: dict, kind: str) -> dict:
    """One layer's reference weights in the model's block layout."""
    import jax.numpy as jnp
    b = {"ln1": {"scale": w["ln1"]}, "ln2": {"scale": w["ln2"]},
         "moe": {"router": w["router"].astype(jnp.float32),
                 "w_in": w["w_in"], "w_gate": w["w_gate"],
                 "w_out": w["w_out"]},
         "mlp": {"w_in": w["s_in"], "w_gate": w["s_gate"],
                 "w_out": w["s_out"]}}
    if kind == "mamba":
        b["ssd"] = {"w_in": w["in_proj"], "w_out": w["out_proj"],
                    "conv": {"w": w["conv_w"], "b": w["conv_b"]},
                    "a_log": w["a_log"], "dt_bias": w["dt_bias"],
                    "d_skip": w["d_skip"], "norm_scale": w["norm"]}
    else:
        b["attn"] = {k: w[k] for k in ("wq", "wk", "wv", "wo")}
    return b


def make_params(mcfg, run, key, cfg: dict):
    """All weights in one jitted call, in the program's layout and the
    types it serves them in: the one period of layers is the stack, each
    block with a leading axis of one."""
    import jax
    from repro.models import model as M
    want = M.param_shapes(mcfg, run)

    def chipbench_params(key):
        stack = {f"b{l}": jax.tree.map(lambda a: a[None], _block(
            hybrid_lm.layer_weights(key, l, kind, cfg), kind))
            for l, kind in enumerate(cfg["layer_types"])}
        ow = hybrid_lm.outer_weights(key, cfg)
        return {"embed": {"table": ow["embed"]}, "stack": stack,
                "final_norm": {"scale": ow["final"]}}
    got = jax.eval_shape(chipbench_params, key)
    if jax.tree.structure(got) != jax.tree.structure(want) or any(
            (a.shape, a.dtype) != (b.shape, b.dtype)
            for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want))):
        raise ValueError("the benchmark's weights do not match the "
                         "model's parameter layout")
    return jax.jit(chipbench_params)(key)


def programs(mcfg, run, cache_len: int, kv_dtype):
    """The serving programs of :func:`serve.programs`, but the decode step
    also returns the routed assignments on each held expert of each
    layer, (num_layers, held) int32."""
    import jax
    import jax.numpy as jnp
    from repro.models import model as M
    from repro.parallel.sharding import local_env
    env = local_env()

    def chipbench_decode(params, token, pos, cache):
        lg, cache, n = M.decode_step(env, mcfg, params, token, pos, cache,
                                     run, expert_counts=True)
        return jnp.argmax(lg, -1).astype(jnp.int32), cache, n

    return dict(serve.programs(mcfg, run, cache_len, kv_dtype),
                decode=jax.jit(chipbench_decode, donate_argnums=3))


def _counted(decode, sink: list):
    """``decode`` as the serving loop calls it, (tokens, cache); each
    step's counts go to ``sink``, left on the device."""
    def step(params, token, pos, cache):
        nt, cache, n = decode(params, token, pos, cache)
        sink.append(n)
        return nt, cache
    step.lower = decode.lower
    return step


def warm_requests(mix: dict, seed: int) -> List[Dict]:
    """The ``warm_slots`` requests that set-up puts into slots, as a system
    with every slot full holds them: a request keeps its slot as long as
    its answer runs, so the answers in flight are the mix's drawn in
    proportion to their length, each with a stratified share still to
    come. The seed only assigns them to slots.

    ``gen.warm_requests`` draws them in proportion to their number, so
    shorter: early in the window their completions then outrun the
    arrivals of a rate above the knee, and how many slots stand empty, and
    so the tokens per second, turns on the seed's arrivals (PERF.md)."""
    k = mix["warm_slots"]
    if not k:
        return []
    prompts, outputs = gen._lengths(mix, WARM_POOL * k)
    by_len = np.argsort(outputs, kind="stable")
    cdf = np.cumsum(outputs[by_len]) / outputs.sum()
    pick = by_len[np.searchsorted(cdf, (np.arange(k) + 0.5) / k)]
    left = (np.arange(k) + 0.5) / k
    left = left[np.random.default_rng(1).permutation(k)]
    order = rng(seed, 2).permutation(k)
    return [{"id": gen.WARM_ID + i, "prompt_len": int(prompts[pick[j]]),
             "output_len": max(2, int(np.ceil(left[j] * outputs[pick[j]])))}
            for i, j in enumerate(order)]


def setup(spec: dict, seed: int, devices, seconds: float) -> dict:
    """As :func:`serve.setup`: weights, cache and programs; the window's
    requests; every prompt length prefilled once; the warm requests
    (:func:`warm_requests`) in their slots."""
    import jax.numpy as jnp
    from repro.configs.base import RunConfig
    cfg, mix = spec["config"], spec["traffic"]
    mcfg = model_config(cfg)
    run = RunConfig(remat_policy="none", param_dtype=cfg["param_dtype"])
    B, C = mix["slots"], mix["cache_len"]
    st = {"spec": spec, "seed": seed, "dims": cfg, "B": B, "C": C,
          "key": jax_key(seed, 0), "expert_counts": [],
          "requests": gen.serve_requests(mix, seed, seconds),
          "pending": collections.deque(), "sent": 0,
          "slots": [None] * B, "done": [], "steps": [], "admitted": [],
          "cur": np.zeros(B, np.int32), "pos": np.zeros(B, np.int32),
          "traced_from": None}
    fns = programs(mcfg, run, C, jnp.dtype(cfg["kv_dtype"]))
    st["fns"] = dict(fns, decode=_counted(fns["decode"],
                                          st["expert_counts"]))
    warm = warm_requests(mix, seed)
    with span("chipbench.serve.weights"):
        st["params"] = make_params(mcfg, run, st["key"], cfg)
        st["cache"] = st["fns"]["cache"](B)
    warm_lengths(st, {r["prompt_len"] for r in st["requests"] + warm})
    with span("chipbench.serve.fill"):
        for slot, req in enumerate(warm):
            _admit(st, req, slot, time.perf_counter())
        _decode(st)
    return st


def program_args(state: dict, program: str) -> List[tuple]:
    """Abstract arguments of each run of ``program`` (``"decode"`` or
    ``"prefill"``) in the traced part of the window, as
    ``chipbench.scopes.program_args`` gives them for the dense driver."""
    import jax
    import jax.numpy as jnp
    from repro.configs.base import RunConfig
    from repro.models import model as M
    cfg = state["spec"]["config"]
    params = M.param_shapes(model_config(cfg),
                            RunConfig(param_dtype=cfg["param_dtype"]))
    if program == "decode":
        B = state["B"]
        return [(params, jax.ShapeDtypeStruct((B, 1), jnp.int32),
                 jax.ShapeDtypeStruct((B,), jnp.int32),
                 state["fns"]["cache"].eval_shape(B))]
    lengths = {p for _, p in state["admitted"][state["traced_from"][1]:]}
    return [(params, jax.ShapeDtypeStruct((1, p), jnp.int32))
            for p in sorted(lengths)]


def compare(st: dict, quants=(None,)) -> Dict:
    """Widest gaps of the sample's served tokens under the reference
    (and, with ``quants=(None, "fp8")``, of the control's picks)."""
    ref = hybrid_lm.Reference(st["key"], st["dims"], st["C"], quants)
    gaps = {q: 0.0 for q in quants}
    served = 0
    for req in sample(st):
        seq = np.concatenate([req["prompt"],
                              np.asarray(req["tokens"], np.int32)])
        first = len(req["prompt"])
        out = ref.forward(seq)
        served += len(req["tokens"])
        gaps[None] = max(gaps[None], dense_lm.widest_gap(out[None], seq,
                                                         first))
        for q in quants[1:]:
            gaps[q] = max(gaps[q], dense_lm.control_gap(
                out[None], out[q], first, len(seq) - 1))
    return {"gaps": gaps, "served": served}


def check(st: dict) -> List[list]:
    lim = st["spec"]["limits"]
    res = compare(st)
    st["compared_tokens"] = res["served"]
    return [["finished_none", int(not st["done"]), 0],
            ["widest_gap", res["gaps"][None], lim["widest_gap"]]]
