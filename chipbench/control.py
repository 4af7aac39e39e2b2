"""The control of a serving cell's correctness check, which the
benchmark's own runs never run: for each seed, one window of the cell at
its own load, then the sound reading and the control's reading of
``widest_gap`` on the same sample.

    python3 chipbench/control.py --workload <cell> --seconds <s> --seeds <n> [<n> ...]

The control is the float32 reference computed through float8 e4m3 in the
system's place; at every served position it reads the reference's gap of
the token the control puts first. Prints one JSON line per seed.
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
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    from chipbench import harness
    spec = harness.resolve(args.workload)
    harness.setup_compile_cache()
    devices = harness.accelerators(spec["cell"]["chips"])
    if devices is None:
        print("chipbench: no TPU; nothing run", file=sys.stderr)
        return 3
    drv = harness.driver_module(spec)
    for seed in args.seeds:
        st = drv.setup(spec, seed, devices, args.seconds)
        drv.window(st, args.seconds)
        drv.release(st)
        res = drv.compare(st, quants=(None, "fp8"))
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "widest_gap": res["gaps"][None],
                          "control_widest_gap": res["gaps"]["fp8"],
                          "compared_tokens": res["served"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
