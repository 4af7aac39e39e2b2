"""The knee of a serving cell, found once when the cell is defined: one
set-up, then a window at each offered rate in turn; one JSON line per
rate.

    python3 chipbench/sweep.py --workload <cell> --seed <n> --seconds <s> --rates <r> [<r> ...]

Each line holds the requests sent in the window and those still waiting
at its close, output tokens per second, time to first token (p50 and
p95, of the requests served in the window), the p95 gap between tokens,
and the mean number of live slots per step. The knee is the highest rate
at which the waiting queue does not grow over the window; the cell's
rate sits at about four fifths of it (PERF.md).
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)
    import numpy as np
    from chipbench import gen, harness
    spec = harness.resolve(args.workload)
    harness.setup_compile_cache()
    devices = harness.accelerators(spec["cell"]["chips"])
    if devices is None:
        print("chipbench: no TPU; nothing run", file=sys.stderr)
        return 3
    drv = harness.driver_module(spec)
    mix = spec["traffic"]
    st = drv.setup(spec, args.seed, devices, args.seconds)
    drv.warm_lengths(st, gen.prompt_lengths(mix["prompt_len"]))
    for rate in args.rates:
        st["requests"] = gen.serve_requests(dict(mix, rate_per_s=rate),
                                            args.seed, args.seconds)
        st["pending"].clear()
        st["done"] = []
        # requests still live from the last rate count as warm ones here
        for r in st["slots"]:
            if r is not None:
                r.pop("due", None)
        drv.window(st, args.seconds)
        e2e = drv.end_to_end(st)
        steps = st["steps"][st["n_steps_before"]:]
        ttft = [1e3 * (r["t_tokens"][0] - r["t_issue"])
                for r in drv._requests(st) if "due" in r]
        print(json.dumps({
            "rate_per_s": rate, "sent": st["sent"],
            "waiting_at_close": len(st["pending"]),
            "tokens_per_s": e2e["serve_output_tokens_per_s"],
            "ttft_p50_ms": float(np.percentile(ttft, 50)) if ttft else None,
            "ttft_p95_ms": float(np.percentile(ttft, 95)) if ttft else None,
            "itl_p95_ms": e2e.get("serve_itl_p95_ms"),
            "live_slots_mean": float(np.mean([n for _, n, _ in steps])),
            "steps": len(steps)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
