"""Device time of the model's programs by named scope.

The model wraps the work of its blocks in named scopes (``SCOPES`` in
``repro.models.model``), and every compiled instruction carries the scopes
it lies in in its ``op_name``. A trace names its device operations by the
instruction's text without that metadata. So the readers here lower and
compile the driver's programs again with the run's shapes and types (the
lowering is the run's own, so JAX's caches hand back the executable that
ran), map each instruction to the innermost model scope in its
``op_name``, and sum the self time of the trace's operations by scope:

* an operation's self time is its duration less the union of the
  operations of the same device that start inside it (a ``while`` encloses
  its body's operations);
* within each run of a program in the window, each operation goes to the
  scope of its instruction; operations of no model scope (XLA's copies,
  the benchmark's own work) go to ``unscoped``;
* the sums are divided by the number of runs.

A model without scopes, or a compiled program that carries none, gives
nothing to read.
"""
from __future__ import annotations

import bisect
import re
import sys
import time
from typing import Dict, List, Optional, Sequence, Tuple

from chipbench import trace as T

UNSCOPED = "unscoped"
# an instruction line of ``as_text()``, or a trace operation's name
_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%([\w.\-]+) = ")
_OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="([^"]*)"')
# where a run's operations match no compiled instruction, the map is not
# of the program that ran, and its buckets would be wrong
MIN_MATCHED = 0.99

Key = Tuple[str, str]


def instruction_key(text: str) -> Optional[Key]:
    """(name, result shape) of an instruction's text."""
    m = _INSTRUCTION.match(text)
    if not m:
        return None
    rest = text[m.end():]
    if not rest.startswith("("):
        return m.group(1), rest.partition(" ")[0]
    depth = 0               # a tuple: up to its closing parenthesis
    for i, c in enumerate(rest):
        depth += (c == "(") - (c == ")")
        if depth == 0:
            break
    return m.group(1), rest[:i + 1]


def scope_of(op_name: str, scopes: Sequence[str]) -> Optional[str]:
    """The innermost of ``scopes`` among the path segments of an
    ``op_name`` (its last segment is the operation itself)."""
    for seg in reversed(op_name.split("/")[:-1]):
        if seg in scopes:
            return seg
    return None


def instruction_map(hlo_text: str, scopes: Sequence[str]
                    ) -> Dict[Key, Optional[str]]:
    """Instruction (name, result shape) -> innermost model scope or None,
    from a compiled program's ``as_text()``."""
    out: Dict[Key, Optional[str]] = {}
    for line in hlo_text.splitlines():
        key = instruction_key(line)
        if key is not None:
            m = _OP_NAME.search(line)
            out[key] = scope_of(m.group(1), scopes) if m else None
    return out


# ------------------------------------------------------- trace reduction

def self_times(ops: Sequence[Sequence]) -> List[float]:
    """Self time of each ``[name, start, duration]``: its duration less
    the union of the operations that start inside it, cut at its end."""
    order = sorted(range(len(ops)), key=lambda i: (ops[i][1], -ops[i][2]))
    starts = [ops[i][1] for i in order]
    out = [0.0] * len(ops)
    for pos, i in enumerate(order):
        s, d = ops[i][1], ops[i][2]
        end = s + d
        covered, lo, hi = 0.0, None, None
        j = pos + 1
        while j < len(order) and starts[j] < end:
            cs = starts[j]
            ce = min(cs + ops[order[j]][2], end)
            if hi is None or cs > hi:
                covered += 0.0 if hi is None else hi - lo
                lo, hi = cs, ce
            else:
                hi = max(hi, ce)
            j += 1
        covered += 0.0 if hi is None else hi - lo
        out[i] = d - covered
    return out


def buckets(events: dict, prefix: str,
            maps: Sequence[Dict[Key, Optional[str]]]) -> Optional[dict]:
    """Self time by scope of the runs of the programs ``prefix``* in the
    window, per run: ``{"runs", "module_ms", "buckets": {scope: ms},
    "matched"}``. Each run is read with the map that matches most of its
    operations. None where the window ran none of them."""
    lo, hi = T.window_of(events)
    runs, module_ns, matched, total = 0, 0.0, 0.0, 0.0
    sums: Dict[str, float] = {}
    for dev in events["devices"].values():
        mods = sorted((s, s + d) for n, s, d in dev["modules"]
                      if n.startswith(prefix) and lo <= s < hi)
        if not mods:
            continue
        ops = dev["ops"]
        if "self_times" not in dev:
            dev["self_times"] = self_times(ops)
        selfs = dev["self_times"]
        starts = [m[0] for m in mods]
        per_run: List[list] = [[] for _ in mods]
        for op, st in zip(ops, selfs):
            k = bisect.bisect_right(starts, op[1]) - 1
            if k >= 0 and op[1] < mods[k][1]:
                per_run[k].append((instruction_key(op[0]), st))
        for (s, e), run_ops in zip(mods, per_run):
            keys = {key for key, _ in run_ops}
            best = max(maps, key=lambda m: len(keys & m.keys()))
            runs += 1
            module_ns += e - s
            for key, st in run_ops:
                total += st
                if key in best:
                    matched += st
                scope = best.get(key) or UNSCOPED
                sums[scope] = sums.get(scope, 0.0) + st
    if not runs:
        return None
    return {"runs": runs, "module_ms": module_ns / runs / 1e6,
            "buckets": {k: v / runs / 1e6 for k, v in sums.items()},
            "matched": matched / total if total else 0.0}


# ------------------------------------------------- the programs' maps

_cache_events = {"hits": 0, "misses": 0}


def _count_cache_event(event: str, **_) -> None:
    if event == "/jax/compilation_cache/cache_hits":
        _cache_events["hits"] += 1
    elif event == "/jax/compilation_cache/cache_misses":
        _cache_events["misses"] += 1


def _listen() -> None:
    import jax
    if not _cache_events.get("listening"):
        jax.monitoring.register_event_listener(_count_cache_event)
        _cache_events["listening"] = 1


def program_args(state: dict, program: str) -> List[tuple]:
    """Abstract arguments of each run of the driver's ``program``
    (``"decode"`` or ``"prefill"``) in the traced part of the window, with
    the run's shapes and types. The run's arrays are on the default device
    and not committed to it, so these name no device either: the lowering
    is then the run's own, and JAX's caches hold its executable."""
    import jax
    import jax.numpy as jnp
    from repro.configs.base import RunConfig
    from repro.models import model as M
    from chipbench.drivers.serve import model_config
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


def program_maps(state: dict, program: str, scopes: Sequence[str]
                 ) -> Tuple[List[Dict[Key, Optional[str]]], dict]:
    """The instruction maps of the driver's ``program`` for the runs in the
    traced window, and what building them cost: seconds lowering and
    compiling, and the persistent cache's hits and misses (none where
    JAX's cache in the process holds the run's own executable)."""
    _listen()
    fn = state["fns"][program]
    maps, cost = [], {"programs": 0, "lower_s": 0.0, "compile_s": 0.0}
    h0, m0 = _cache_events["hits"], _cache_events["misses"]
    for args in program_args(state, program):
        t0 = time.perf_counter()
        lowered = fn.lower(*args)
        t1 = time.perf_counter()
        maps.append(instruction_map(lowered.compile().as_text(), scopes))
        cost["lower_s"] += t1 - t0
        cost["compile_s"] += time.perf_counter() - t1
        cost["programs"] += 1
    cost["hits"] = _cache_events["hits"] - h0
    cost["misses"] = _cache_events["misses"] - m0
    return maps, cost


def read(ctx: dict, program: str) -> Optional[dict]:
    """:func:`buckets` of the driver's ``program`` in the traced window,
    computed once per run and kept in ``ctx``; None where the model has no
    scopes, the window ran no such program, the compiled program carries
    no scope, or the maps match too little of what ran."""
    memo = ctx.setdefault("scopes", {})
    if program not in memo:
        memo[program] = _read(ctx, program)
    return memo[program]


def _read(ctx: dict, program: str) -> Optional[dict]:
    from repro.models import model as M
    scopes = getattr(M, "SCOPES", None)
    t, st = ctx["trace"], ctx["state"]
    if not t or not scopes or st.get("traced_from") is None:
        return None
    prefix = f"jit_chipbench_{program}"
    if not T.module_stats(t["events"], prefix)["count"]:
        return None
    t0 = time.perf_counter()
    maps, cost = program_maps(st, program, scopes)
    t1 = time.perf_counter()
    scoped = any(v for m in maps for v in m.values())
    res = buckets(t["events"], prefix, maps) if scoped else None
    cost["reduce_s"] = time.perf_counter() - t1
    cost["added_s"] = time.perf_counter() - t0
    print(f"chipbench.scopes {prefix}: " + ", ".join(
        f"{k} {v:.3f}" if isinstance(v, float) else f"{k} {v}"
        for k, v in cost.items()), file=sys.stderr)
    if not scoped:
        # as from a persistent cache entry that a program without scopes
        # wrote under the same key (the key leaves metadata out)
        print(f"chipbench.scopes {prefix}: the compiled program carries no "
              "model scope; nothing read", file=sys.stderr)
        return None
    if res["matched"] < MIN_MATCHED:
        print(f"chipbench.scopes {prefix}: the maps match "
              f"{res['matched']:.3f} of the operations' time; nothing read",
              file=sys.stderr)
        return None
    print(f"chipbench.scopes {prefix}: per run of {res['module_ms']:.4f} ms: "
          + ", ".join(f"{k} {v:.4f}" for k, v in sorted(
              res["buckets"].items())), file=sys.stderr)
    return res


def decode_ms(ctx: dict, *names: str) -> Optional[float]:
    """Milliseconds per decode step in the scopes ``names``."""
    res = read(ctx, "decode")
    return None if res is None else sum(res["buckets"].get(n, 0.0)
                                        for n in names)
