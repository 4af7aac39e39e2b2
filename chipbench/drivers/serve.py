"""Serving cells: an open-loop continuous-batching loop over the model
path ``repro.models.model.prefill`` and ``decode_step``, checked against
the float32 reference of ``chipbench.reference``.

Requests fall due on the mix's schedule (``gen.serve_requests``) whether
or not earlier ones have finished. Each step: every due request that
finds a free slot is prefilled at batch 1 at its own length into it, in
the order they fell due; then one decode step runs over all slots, and
its tokens are read back to the host. A request is issued when it fell
due, so its first token holds its wait for a slot. Set-up
fills ``warm_slots`` slots with requests part-way through their answers,
so the window starts near the steady state of the mix's rate.
"""
from __future__ import annotations

import collections
import time
from typing import Dict, List

import numpy as np

from chipbench import gen
from chipbench.harness import jax_key, rng, span
from chipbench.reference import dense_lm

MODEL_KEYS = ("num_layers", "d_model", "num_heads", "num_kv_heads",
              "head_dim", "d_ff", "vocab_size", "mlp_activation",
              "tie_embeddings", "rope_theta", "norm_eps")

def model_config(cfg: dict):
    from repro.configs.base import ModelConfig
    return ModelConfig(name=cfg["name"], family="dense",
                       pattern=("global",), embed_scale=False,
                       **{k: cfg[k] for k in MODEL_KEYS})


def make_params(mcfg, run, key, dims):
    """All weights in one jitted call, in the program's layout and the
    types it serves them in."""
    import jax
    import jax.numpy as jnp
    from repro.models import model as M
    want = M.param_shapes(mcfg, run)
    L = dims["num_layers"]

    def chipbench_params(key):
        lw = jax.vmap(lambda l: dense_lm.layer_weights(key, l, dims))(
            jnp.arange(L))
        ow = dense_lm.outer_weights(key, dims)
        return {"embed": {"table": ow["embed"]},
                "lm_head": {"w": ow["head"]},
                "stack": {"b0": {
                    "ln1": {"scale": lw["ln1"]},
                    "attn": {k: lw[k] for k in ("wq", "wk", "wv", "wo")},
                    "ln2": {"scale": lw["ln2"]},
                    "mlp": {"w_in": lw["w_in"], "w_out": lw["w_out"]}}},
                "final_norm": {"scale": ow["final"]}}
    got = jax.eval_shape(chipbench_params, key)
    if jax.tree.structure(got) != jax.tree.structure(want) or any(
            (a.shape, a.dtype) != (b.shape, b.dtype)
            for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want))):
        raise ValueError("the benchmark's weights do not match the "
                         "model's parameter layout")
    return jax.jit(chipbench_params)(key)


def programs(mcfg, run, cache_len: int, kv_dtype):
    """The jitted prefill, slot insertion and decode step. Their names
    are what the trace's program runs are called."""
    import jax
    import jax.numpy as jnp
    from repro.models import model as M
    from repro.parallel.sharding import local_env
    env = local_env()

    def chipbench_prefill(params, tokens):
        lg, cache, _ = M.prefill(env, mcfg, params, {"tokens": tokens},
                                 run, max_len=cache_len, kv_dtype=kv_dtype)
        return jnp.argmax(lg, -1).astype(jnp.int32), cache

    def chipbench_insert(batch_cache, cache, slot):
        return jax.tree.map(
            lambda a, b: jax.lax.dynamic_update_slice_in_dim(a, b, slot, 1),
            batch_cache, cache)

    def chipbench_decode(params, token, pos, cache):
        lg, cache = M.decode_step(env, mcfg, params, token, pos, cache, run)
        return jnp.argmax(lg, -1).astype(jnp.int32), cache

    def chipbench_cache(batch):
        return M.init_cache(mcfg, batch, cache_len, kv_dtype=kv_dtype)

    return {"prefill": jax.jit(chipbench_prefill),
            "insert": jax.jit(chipbench_insert, donate_argnums=0),
            "decode": jax.jit(chipbench_decode, donate_argnums=3),
            "cache": jax.jit(chipbench_cache, static_argnums=0)}


def dims_of(cfg: dict) -> Dict:
    return {k: cfg[k] for k in MODEL_KEYS}


def setup(spec: dict, seed: int, devices, seconds: float) -> dict:
    """Weights, cache and programs; the window's requests; every prompt
    length they and the warm requests use prefilled once; the warm
    requests in their slots."""
    import jax.numpy as jnp
    from repro.configs.base import RunConfig
    cfg, mix = spec["config"], spec["traffic"]
    mcfg = model_config(cfg)
    run = RunConfig(remat_policy="none", param_dtype=cfg["param_dtype"])
    dims = dims_of(cfg)
    B, C = mix["slots"], mix["cache_len"]
    st = {"spec": spec, "seed": seed, "dims": dims, "B": B, "C": C,
          "key": jax_key(seed, 0), "fns": programs(
              mcfg, run, C, jnp.dtype(cfg["kv_dtype"])),
          "requests": gen.serve_requests(mix, seed, seconds),
          "pending": collections.deque(), "sent": 0,
          "slots": [None] * B, "done": [], "steps": [], "admitted": [],
          "cur": np.zeros(B, np.int32), "pos": np.zeros(B, np.int32),
          "traced_from": None}
    warm = gen.warm_requests(mix, seed)
    with span("chipbench.serve.weights"):
        st["params"] = make_params(mcfg, run, st["key"], dims)
        st["cache"] = st["fns"]["cache"](B)
    warm_lengths(st, {r["prompt_len"] for r in st["requests"] + warm})
    with span("chipbench.serve.fill"):
        for slot, req in enumerate(warm):
            _admit(st, req, slot, time.perf_counter())
        _decode(st)
    return st


def warm_lengths(st: dict, lengths) -> None:
    """Each prompt length prefilled once into slot 0, which the fill or
    the first admission overwrites."""
    import jax.numpy as jnp
    with span("chipbench.serve.warm"):
        for p in sorted(lengths):
            tok, c = st["fns"]["prefill"](
                st["params"], jnp.asarray(np.zeros((1, p), np.int32)))
            st["cache"] = st["fns"]["insert"](st["cache"], c, 0)
            np.asarray(tok)


def _admit(st: dict, req: dict, slot: int, t_issue: float) -> None:
    import jax.numpy as jnp
    toks = gen.prompt_tokens(st["seed"], req, st["dims"]["vocab_size"])
    with span("chipbench.serve.prefill"):
        first, c = st["fns"]["prefill"](st["params"],
                                        jnp.asarray(toks[None]))
        st["cache"] = st["fns"]["insert"](st["cache"], c, slot)
        first = int(np.asarray(first)[0])
    now = time.perf_counter()
    req.update(slot=slot, t_issue=t_issue, prompt=toks, tokens=[first],
               t_tokens=[now])
    st["slots"][slot] = req
    st["cur"][slot] = first
    st["pos"][slot] = len(toks)
    st["admitted"].append((now, len(toks)))


def _admit_pending(st: dict) -> None:
    """Due requests into free slots, in the order they fell due."""
    for slot, req in enumerate(st["slots"]):
        if not st["pending"]:
            return
        if req is None:
            nxt = dict(st["pending"].popleft())
            _admit(st, nxt, slot, st["t_start"] + nxt["due"])


def _decode(st: dict) -> float:
    """One decode step over every slot; returns the time its tokens were
    on the host. Finished requests leave their slots."""
    import jax.numpy as jnp
    live = [s for s, r in enumerate(st["slots"]) if r is not None]
    keys = int(sum(int(st["pos"][s]) + 1 for s in live))
    with span("chipbench.serve.decode"):
        nt, st["cache"] = st["fns"]["decode"](
            st["params"], jnp.asarray(st["cur"][:, None]),
            jnp.asarray(st["pos"]), st["cache"])
        nt = np.asarray(nt)
    now = time.perf_counter()
    st["steps"].append((now, len(live), keys))
    for slot in live:
        req = st["slots"][slot]
        req["tokens"].append(int(nt[slot]))
        req["t_tokens"].append(now)
        st["cur"][slot] = nt[slot]
        st["pos"][slot] += 1
        if len(req["tokens"]) >= req["output_len"]:
            req["t_done"] = now
            st["done"].append(req)
            st["slots"][slot] = None
    return now


def window(st: dict, seconds: float, at=None) -> None:
    """Serve the mix's schedule for ``seconds``. ``at``, a pair
    ``(offset_s, fn)``, calls ``fn`` at the first step boundary
    ``offset_s`` into the window (the harness starts its trace there)."""
    reqs = st["requests"]
    t_start = time.perf_counter()
    st["t_start"] = t_start
    st["n_steps_before"] = len(st["steps"])
    st["n_admitted_before"] = len(st["admitted"])
    i, now = 0, t_start
    while now - t_start < seconds:
        if at is not None and st["traced_from"] is None \
                and now - t_start >= at[0]:
            at[1]()
            st["traced_from"] = (len(st["steps"]), len(st["admitted"]))
            now = time.perf_counter()
        while i < len(reqs) and reqs[i]["due"] <= now - t_start:
            st["pending"].append(reqs[i])
            i += 1
        _admit_pending(st)
        if any(r is not None for r in st["slots"]):
            now = _decode(st)
        else:
            nxt = reqs[i]["due"] if i < len(reqs) else seconds
            time.sleep(max(0.0, min(nxt, seconds) - (now - t_start)))
            now = time.perf_counter()
    st["t_end"] = now
    st["sent"] = i


def release(st: dict) -> None:
    """Frees the weights and the cache before the reference runs."""
    import jax
    for k in ("params", "cache"):
        for leaf in jax.tree.leaves(st.pop(k, None)):
            leaf.delete()


def _requests(st: dict) -> List[dict]:
    return st["done"] + [r for r in st["slots"] if r is not None]


def end_to_end(st: dict) -> dict:
    t0, t1 = st["t_start"], st["t_end"]
    reqs = _requests(st)
    tokens = sum(sum(t0 <= t <= t1 for t in r["t_tokens"]) for r in reqs)
    itl = [1e3 * (b - a) for r in reqs
           for a, b in zip(r["t_tokens"], r["t_tokens"][1:])
           if a >= t0 and b <= t1]
    out = {"serve_output_tokens_per_s": tokens / (t1 - t0)}
    if itl:
        out["serve_itl_p95_ms"] = float(np.percentile(itl, 95))
    return out


def counts(st: dict):
    """Requests sent in the window; none can fail short of the run."""
    return st["sent"], 0


def sample(st: dict) -> List[dict]:
    """The finished requests compared: the one with the most served
    tokens and ``sample.requests - 1`` more drawn from the seed."""
    done = st["done"]
    if not done:
        return []
    n = st["spec"]["traffic"]["sample"]["requests"]
    longest = max(range(len(done)), key=lambda i: len(done[i]["tokens"]))
    rest = [i for i in range(len(done)) if i != longest]
    pick = rng(st["seed"], 3).permutation(len(rest))[:n - 1]
    return [done[longest]] + [done[rest[i]] for i in pick]


def compare(st: dict, quants=(None,)) -> Dict:
    """Widest gaps of the sample's served tokens under the reference
    (and, with ``quants=(None, "fp8")``, of the control's picks)."""
    ref = dense_lm.Reference(st["key"], st["dims"], st["C"], quants)
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
