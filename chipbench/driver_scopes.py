"""Device time by named scope, as :mod:`chipbench.scopes` reads it, for a
driver that gives its own programs' abstract arguments
(``program_args(state, program)`` in the driver's module): the instruction
maps come from the driver's own programs, lowered and compiled again with
the run's shapes, and the trace's operations are summed by
:func:`chipbench.scopes.buckets`."""
from __future__ import annotations

import sys
import time
from typing import Optional

from chipbench import harness, scopes
from chipbench import trace as T


def read(ctx: dict, program: str) -> Optional[dict]:
    """:func:`chipbench.scopes.buckets` of the driver's ``program`` in the
    traced window, once per run; None where the model has no scopes, the
    window ran no such program, or the maps match too little of it."""
    memo = ctx.setdefault("driver_scopes", {})
    if program not in memo:
        memo[program] = _read(ctx, program)
    return memo[program]


def _read(ctx: dict, program: str) -> Optional[dict]:
    from repro.models import model as M
    names = getattr(M, "SCOPES", None)
    t, st = ctx["trace"], ctx["state"]
    if not t or not names or st.get("traced_from") is None:
        return None
    prefix = f"jit_chipbench_{program}"
    if not T.module_stats(t["events"], prefix)["count"]:
        return None
    t0 = time.perf_counter()
    fn = st["fns"][program]
    drv = harness.driver_module(ctx["spec"])
    maps = [scopes.instruction_map(fn.lower(*args).compile().as_text(),
                                   names)
            for args in drv.program_args(st, program)]
    res = scopes.buckets(t["events"], prefix, maps)
    print(f"chipbench.driver_scopes {prefix}: {len(maps)} maps in "
          f"{time.perf_counter() - t0:.3f} s", file=sys.stderr)
    if res is None or not any(v for m in maps for v in m.values()):
        return None
    if res["matched"] < scopes.MIN_MATCHED:
        print(f"chipbench.driver_scopes {prefix}: the maps match "
              f"{res['matched']:.3f} of the operations' time; nothing read",
              file=sys.stderr)
        return None
    print(f"chipbench.driver_scopes {prefix}: per run of "
          f"{res['module_ms']:.4f} ms: " + ", ".join(
              f"{k} {v:.4f}" for k, v in sorted(res["buckets"].items())),
          file=sys.stderr)
    return res


def decode_ms(ctx: dict, *names: str) -> Optional[float]:
    """Milliseconds per decode step in the scopes ``names``."""
    res = read(ctx, "decode")
    return None if res is None else sum(res["buckets"].get(n, 0.0)
                                        for n in names)
