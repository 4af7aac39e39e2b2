"""Bring-up smoke test of the main paths on one TPU chip.

    python chip_smoke.py            # one chip: simulator, serving, kernels
    python chip_smoke.py --chips 4  # nemotron-4-15b tensor-parallel decode
                                    # on a 2x2 host, and nothing else

Everything runs in this one process, which holds the chip. The phases:

* ``simulator`` — the 84-cell fig8 grid (12 Table II workloads x 7
  policies, trace scale 0.5) through ``run_grid(engine="jax")``, the
  jitted stepper on the chip, checked record for record against the C
  stepper (``engine="batched"``) on the host, with no retry and no
  fallback cell;
* ``serving`` — gemma2-2b at full width with random bf16 weights: 8
  prompts of 1024 tokens are prefilled into a 2048-token cache and
  decoded greedily for 16 steps; a fresh prefill over the 1040 tokens
  must give the last decode step's logits;
* ``kernels`` — ``flash_attention``, ``decode_attention`` and
  ``ciao_gather`` compiled for the chip (never interpreted), each against
  its ``ref.py``;
* ``tp4`` (``--chips 4``) — nemotron-4-15b at full width and depth on a
  (data=1, model=4) mesh, prefill plus 8 decode steps, with each device's
  memory in use; then the model cut to 2 layers runs on one chip and on
  the mesh, whose logits must agree.

Each phase prints one JSON line with its wall time, its compile time (JAX's
trace, lowering and backend-compile events, persistent-cache loads
included) and its check. The last line is
``{"ok": true, "device": {...}}`` when every phase passed. Without a TPU
the script exits 2 and prints no result.

JAX's persistent compilation cache sits where ``JAX_COMPILATION_CACHE_DIR``
says; where it is unset, at ``.jax_cache/`` next to this file.
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import os
import pathlib
import sys
import time
import traceback

ROOT = pathlib.Path(__file__).resolve().parent
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

# without the repo's sources, fail here, before anything is printed
import repro  # noqa: E402,F401

_COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                   "/jax/core/compile/jaxpr_to_mlir_module_duration",
                   "/jax/core/compile/backend_compile_duration")
_compile_s = [0.0]


def _on_duration(event: str, secs: float, **_) -> None:
    if event in _COMPILE_EVENTS:
        _compile_s[0] += secs


jax.monitoring.register_event_duration_secs_listener(_on_duration)


def setup_compile_cache() -> str:
    """The persistent cache directory: ``$JAX_COMPILATION_CACHE_DIR`` when
    set (JAX reads it itself), else ``<checkout>/.jax_cache``."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = ROOT / ".jax_cache"
    jax.config.update("jax_compilation_cache_dir", str(path))
    return str(path)


def _rel_err(got, want) -> float:
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    return float(np.linalg.norm(got - want) / max(np.linalg.norm(want),
                                                  1e-30))


class Check(dict):
    """A phase's check results. ``require`` records a condition; a phase
    with any failed condition fails (``run_phase``)."""

    def require(self, name: str, cond: bool, value=None) -> None:
        self[name] = value if value is not None else bool(cond)
        if not cond:
            self.setdefault("failed", []).append(name)


# ----------------------------------------------------------------- phases

def phase_simulator(check: Check, scale: float = 0.5,
                    workloads=None) -> None:
    from benchmarks.bench_batched import FULL_SET, POLICIES
    from repro.core import _cstep
    from repro.core.runner import (ExperimentGrid, FailedCell,
                                   last_batched_perf, run_grid)
    check.require("c_stepper", _cstep.available(), _cstep.available())
    grid = ExperimentGrid(name="fig8", policies=POLICIES, scale=scale,
                          workloads=tuple(workloads or FULL_SET))
    t0 = time.perf_counter()
    # jobs=1: no worker process may start once this one holds the chip
    got = run_grid(grid, engine="jax", strict=True, jobs=1)
    check["jax_wall_s"] = time.perf_counter() - t0
    perf = last_batched_perf()
    check["jax_stepper_s"] = perf["stepper_s"]
    check.require("jax_retries", perf["retries"] == 0, perf["retries"])
    check.require("jax_fallback_cells", perf["fallback_cells"] == 0,
                  perf["fallback_cells"])
    t0 = time.perf_counter()
    want = run_grid(grid, engine="batched", strict=True, jobs=1)
    check["c_wall_s"] = time.perf_counter() - t0
    check.require("cells", len(got) == len(want) == len(grid.workloads)
                  * len(grid.policies), len(got))
    check.require("no_failed_cells",
                  not any(isinstance(r, FailedCell) for r in got + want))
    differ = [f"{a.workload}/{a.policy}:" + ",".join(
        f for f in vars(a) if getattr(a, f) != getattr(b, f))
        for a, b in zip(got, want) if a != b]
    check.require("records_equal", not differ, differ[:12] or True)


def phase_serving(check: Check, cfg=None, batch: int = 8,
                  prompt_len: int = 1024, cache_len: int = 2048,
                  steps: int = 16, seed: int = 0, tol: float = 5e-2,
                  dtype: str = "bfloat16") -> None:
    """``tol`` bounds the relative L2 distance of the last decode step's
    logits from a fresh prefill's. The two paths round the bf16 residual
    stream at different points: a few roundings per layer, each a
    relative 2**-8, add up as a random walk to ~0.04 over 26 layers. A
    control — the prefill with the last token changed — must land well
    outside, so the bound still tells a wrong cache slot, position or
    mask from drift. With ``dtype="float32"`` (params and KV cache) the
    drift shrinks by ~2**16 and a fault does not. The first step is
    compared too, for the record: drift that grew with the steps would
    point at the cache."""
    from repro.configs import get_config
    from repro.configs.base import RunConfig
    from repro.models import model as M
    from repro.parallel.sharding import local_env
    cfg = cfg or get_config("gemma2-2b")
    run = RunConfig(remat_policy="none", param_dtype=dtype)
    kv_dtype = jnp.dtype(dtype)
    env = local_env()
    check["model"] = cfg.name
    check["dtype"] = dtype
    params = jax.jit(lambda k: M.init_params(cfg, k, run))(
        jax.random.PRNGKey(seed))
    check["param_bytes"] = sum(x.nbytes for x in jax.tree.leaves(params))
    prompts = jax.random.randint(jax.random.PRNGKey(seed + 1),
                                 (batch, prompt_len), 0, cfg.vocab_size)
    prefill = jax.jit(lambda p, t, n: M.prefill(env, cfg, p, {"tokens": t},
                                                run, max_len=n,
                                                kv_dtype=kv_dtype),
                      static_argnums=2)
    decode = jax.jit(lambda p, tok, pos, c: M.decode_step(
        env, cfg, p, tok, pos, c, run), donate_argnums=3)
    logits, cache, pos = prefill(params, prompts, cache_len)
    tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
    fed, step_s = [], []
    finite = bool(jnp.isfinite(logits).all())
    for i in range(steps):
        fed.append(tok)
        t0 = time.perf_counter()
        logits, cache = decode(params, tok, pos + 1 + i, cache)
        tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
        finite &= bool(jnp.isfinite(logits).all())
        step_s.append(time.perf_counter() - t0)
        if i == 0:
            first = logits
    # host clock around each step, synced by the finiteness read; the
    # first step includes its compile
    check["decode_first_step_s"] = step_s[0]
    check["decode_step_s_median"] = float(np.median(step_s[1:] or step_s))
    del cache
    seq = jnp.concatenate([prompts] + fed, axis=1)
    ref, _, _ = prefill(params, seq, seq.shape[1])
    check.require("finite", finite and bool(jnp.isfinite(ref).all()))
    err = _rel_err(logits, ref)
    # the first step's logits follow the prompt and one fed token
    check["logits_rel_l2_at_step"] = {
        1: _rel_err(first, prefill(params, seq[:, :prompt_len + 1],
                                   prompt_len + 1)[0]),
        steps: err}
    wrong = seq.at[:, -1].set((seq[:, -1] + 1) % cfg.vocab_size)
    control = _rel_err(logits, prefill(params, wrong, seq.shape[1])[0])
    check.require("control_rel_l2", control > 4 * tol, control)
    check["tokens"] = list(seq.shape)
    check["logits_max_abs_diff"] = float(jnp.max(jnp.abs(
        logits.astype(jnp.float32) - ref.astype(jnp.float32))))
    check["argmax_agree"] = float(jnp.mean(
        jnp.argmax(logits, -1) == jnp.argmax(ref, -1)))
    check.require("logits_rel_l2", err <= tol, err)
    check["logits_rel_l2_tol"] = tol


def _compiled(fn, *args):
    """AOT-compile ``fn`` for ``args``; returns (callable, HLO text)."""
    c = jax.jit(fn).lower(*args).compile()
    return c, c.as_text()


def phase_kernels(check: Check, seq: int = 2048, kv_len: int = 8192,
                  rows: int = 262144, width: int = 256,
                  requests: int = 65536, interpret: bool = False) -> None:
    from repro.kernels.ciao_gather.ops import ciao_gather
    from repro.kernels.ciao_gather.ref import cache_sim_ref, gather_ref
    from repro.kernels.decode_attn.ops import decode_attention
    from repro.kernels.decode_attn.ref import decode_ref
    from repro.kernels.flash_attn.ops import flash_attention
    from repro.kernels.flash_attn.ref import attention_ref

    hq, hkv, d, cap = 8, 4, 256, 50.0          # gemma2-2b attention
    custom = "tpu_custom_call"

    def fold(x, b, h):
        g = h // x.shape[2]
        x = jnp.repeat(x, g, 2)
        return x.transpose(0, 2, 1, 3).reshape(b * h, x.shape[1], d)

    def ref_precise(fn, *a, **kw):
        with jax.default_matmul_precision("float32"):
            return jax.jit(functools.partial(fn, **kw))(*a)

    # flash attention: 2 sequences, causal, softcap
    ks = jax.random.split(jax.random.PRNGKey(0), 8)
    b = 2
    q = jax.random.normal(ks[0], (b, seq, hq, d), jnp.bfloat16)
    k = jax.random.normal(ks[1], (b, seq, hkv, d), jnp.bfloat16)
    v = jax.random.normal(ks[2], (b, seq, hkv, d), jnp.bfloat16)
    fa, text = _compiled(lambda q, k, v: flash_attention(
        q, k, v, causal=True, softcap=cap, interpret=interpret), q, k, v)
    if not interpret:
        check.require("flash_custom_call", custom in text)
    out = fa(q, k, v).astype(jnp.float32)
    ref = ref_precise(attention_ref, fold(q, b, hq).astype(jnp.float32),
                      fold(k, b, hq).astype(jnp.float32),
                      fold(v, b, hq).astype(jnp.float32),
                      causal=True, softcap=cap)
    ref = ref.reshape(b, hq, seq, d).transpose(0, 2, 1, 3)
    diff = float(jnp.max(jnp.abs(out - ref)))
    check.require("flash_max_abs_diff", diff <= 3e-2, diff)

    # decode attention: 8 sequences of mixed length in an 8k cache
    b = 8
    q1 = jax.random.normal(ks[3], (b, 1, hq, d), jnp.bfloat16)
    ck = jax.random.normal(ks[4], (b, kv_len, hkv, d), jnp.bfloat16)
    cv = jax.random.normal(ks[5], (b, kv_len, hkv, d), jnp.bfloat16)
    lens = jnp.array(np.linspace(1, kv_len, b).astype(np.int32))
    da, text = _compiled(lambda q, k, v, n: decode_attention(
        q, k, v, n, softcap=cap, interpret=interpret), q1, ck, cv, lens)
    if not interpret:
        check.require("decode_custom_call", custom in text)
    out = da(q1, ck, cv, lens).astype(jnp.float32)
    ref = ref_precise(decode_ref, fold(q1, b, hq).astype(jnp.float32),
                      fold(ck, b, hq).astype(jnp.float32),
                      fold(cv, b, hq).astype(jnp.float32),
                      jnp.repeat(lens, hq), softcap=cap)
    ref = ref.reshape(b, hq, 1, d).transpose(0, 2, 1, 3)
    diff = float(jnp.max(jnp.abs(out - ref)))
    check.require("decode_max_abs_diff", diff <= 3e-2, diff)

    # ciao_gather: streams 0-2 loop private hot sets, stream 3 hammers the
    # whole table and is isolated
    rng = np.random.default_rng(0)
    table = jax.random.normal(ks[6], (rows, width), jnp.float32)
    streams = rng.integers(0, 4, requests).astype(np.int32)
    hot = streams * 64 + rng.integers(0, 64, requests)
    idx = np.where(streams == 3, rng.integers(0, rows, requests),
                   hot).astype(np.int32)
    iso = np.array([0, 0, 0, 1], np.int32)
    args = (table, jnp.asarray(idx), jnp.asarray(streams), jnp.asarray(iso))
    cg, text = _compiled(lambda t, i, s, m: ciao_gather(
        t, i, s, m, interpret=interpret), *args)
    if not interpret:
        check.require("gather_custom_call", custom in text)
    out, stats = cg(*args)
    same = bool(jnp.array_equal(out, gather_ref(table, args[1])))
    check.require("gather_rows_exact", same)
    want = cache_sim_ref(idx, streams, iso, c_main=256, c_iso=64,
                         num_streams=4)
    stats = np.asarray(stats)
    check.require("gather_stats_exact", np.array_equal(stats, want),
                  stats.tolist())


def phase_tp4(check: Check, cfg=None, batch: int = 8, prompt_len: int = 512,
              cache_len: int = 1024, steps: int = 8, cut_layers: int = 2,
              seed: int = 0, tol: float = 5e-2) -> None:
    from jax.sharding import Mesh, SingleDeviceSharding
    from repro.configs import get_config
    from repro.configs.base import RunConfig
    from repro.models import model as M
    from repro.parallel.sharding import local_env, make_env, tree_shardings
    from repro.train.train_step import batch_logical_specs

    cfg = cfg or get_config("nemotron-4-15b")
    devs = jax.devices()
    check.require("devices", len(devs) >= 4, len(devs))
    mesh = Mesh(np.array(devs[:4]).reshape(1, 4), ("data", "model"))
    run = RunConfig(remat_policy="none", param_dtype="bfloat16")
    check["model"] = cfg.name

    def serve(c, env_pre, env_dec, params, p_sh, in_sh):
        """prefill + decode of fixed tokens (the same in every run, so
        runs compare step by step); returns every step's logits."""
        bls = batch_logical_specs(c, "decode")
        cache_struct = M.cache_struct(c, batch, cache_len)
        tok_sh = in_sh(bls["token"], jax.ShapeDtypeStruct((batch, 1),
                                                          jnp.int32))
        pos_sh = in_sh(bls["pos"], jax.ShapeDtypeStruct((batch,),
                                                        jnp.int32))
        cache_sh = in_sh(bls["cache"], cache_struct)
        prefill = jax.jit(
            lambda p, t: M.prefill(env_pre, c, p, {"tokens": t}, run,
                                   max_len=cache_len),
            in_shardings=(p_sh, tok_sh),
            out_shardings=(None, cache_sh, pos_sh))
        decode = jax.jit(
            lambda p, tok, pos, cc: M.decode_step(env_dec, c, p, tok, pos,
                                                  cc, run),
            in_shardings=(p_sh, tok_sh, pos_sh, cache_sh),
            out_shardings=(None, cache_sh), donate_argnums=3)
        toks = jax.random.randint(jax.random.PRNGKey(seed + 1),
                                  (batch, prompt_len + steps), 0,
                                  c.vocab_size)
        logits, cache, pos = prefill(params, toks[:, :prompt_len])
        out = [logits]
        for i in range(steps):
            tok = toks[:, prompt_len + i][:, None]
            logits, cache = decode(params, tok, pos + 1 + i, cache)
            out.append(logits)
        return [np.asarray(x.astype(jnp.float32)) for x in out]

    def mesh_run(c, params=None):
        env_pre = make_env(mesh, "prefill")
        env_dec = make_env(mesh, "decode")
        shapes = M.param_shapes(c, run)
        p_sh = tree_shardings(env_dec, M.param_specs(c), shapes)
        if params is None:
            params = jax.jit(lambda k: M.init_params(c, k, run),
                             out_shardings=p_sh)(jax.random.PRNGKey(seed))
        else:
            params = jax.device_put(params, p_sh)
        used = [(d.memory_stats() or {}).get("bytes_in_use", 0)
                for d in devs[:4]]
        held = dict.fromkeys(devs[:4], 0)
        for leaf in jax.tree.leaves(params):
            for shard in leaf.addressable_shards:
                held[shard.device] += shard.data.nbytes
        logits = serve(c, env_pre, env_dec, params, p_sh,
                       lambda spec, st: tree_shardings(env_dec, spec, st))
        return logits, used, list(held.values())

    # full width and depth, sharded over the 4 chips
    t0 = time.perf_counter()
    logits, used, held = mesh_run(cfg)
    check["full_layers"] = cfg.num_layers
    check["full_s"] = time.perf_counter() - t0
    check["full_bytes_in_use_per_device"] = used
    check["full_param_bytes_per_device"] = held
    check.require("params_spread", min(held) > 0.2 * sum(held))
    check.require("full_finite", all(np.isfinite(x).all() for x in logits))

    # the same widths cut to a few layers: one chip vs the mesh
    cut = dataclasses.replace(cfg, num_layers=cut_layers)
    one = SingleDeviceSharding(devs[0])
    params = jax.jit(lambda k: M.init_params(cut, k, run),
                     out_shardings=one)(jax.random.PRNGKey(seed))
    env1 = local_env()
    ref = serve(cut, env1, env1, params, one, lambda spec, st: one)
    got = mesh_run(cut, params)[0]
    errs = [_rel_err(g, r) for g, r in zip(got, ref)]
    check["cut_layers"] = cut_layers
    check["cut_rel_l2_per_step"] = errs
    check.require("cut_logits_rel_l2", max(errs) <= tol, max(errs))
    check["cut_logits_rel_l2_tol"] = tol


# ------------------------------------------------------------------- main

def run_phase(name: str, fn, **kw) -> bool:
    check = Check()
    c0, t0 = _compile_s[0], time.perf_counter()
    ok = True
    try:
        fn(check, **kw)
        ok = not check.get("failed")
    except Exception as exc:                      # reported, never hidden
        ok = False
        check["error"] = f"{type(exc).__name__}: {exc}"[:2000]
        traceback.print_exc()
    line = {"phase": name, "ok": ok,
            "wall_s": time.perf_counter() - t0,
            "compile_s": _compile_s[0] - c0, "check": check}
    print(json.dumps(line, default=str), flush=True)
    return ok


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)
    cache = setup_compile_cache()
    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX sees {dev.platform}); nothing run",
              file=sys.stderr)
        return 2
    print(json.dumps({"compile_cache": cache, "jax": jax.__version__}),
          flush=True)
    # traces come from their seeds or the committed curated set, never
    # from an untracked local cache file
    os.environ["REPRO_WORKLOAD_CACHE_DIR"] = ""
    if args.chips == 4:
        oks = [run_phase("tp4", phase_tp4)]
    else:
        oks = [run_phase("simulator", phase_simulator),
               run_phase("serving", phase_serving),
               run_phase("kernels", phase_kernels)]
    result = {"ok": all(oks),
              "device": {"platform": dev.platform, "kind": dev.device_kind,
                         "count": len(devs)}}
    print(json.dumps(result), flush=True)
    return 0 if all(oks) else 1


if __name__ == "__main__":
    sys.exit(main())
