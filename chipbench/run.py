"""Run one benchmark cell once on the chips of this machine.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Sets the cell up (weights or traces from the seed, every shape it will
use compiled), measures for ``--seconds``, checks what the window
produced against the cell's reference, and prints one JSON line last on
standard output. With ``--trace 0`` its metrics are the cell's end-to-end
metrics; with ``--trace 1`` the window is profiled and the metrics are
the per-layer ones. Each number compared is printed beside its limit, on
standard error and under ``checks`` in the line. Exits 3 with no result
where JAX finds fewer TPU chips than the cell asks for.
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
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from chipbench import harness
    spec = harness.resolve(args.workload)
    import repro  # noqa: F401  (the system under test must be there)
    counter = harness.CompileCounter().register()
    cache = harness.setup_compile_cache()
    chips = spec["cell"]["chips"]
    devices = harness.accelerators(chips)
    if devices is None:
        import jax
        print(f"chipbench: the cell needs {chips} TPU chip(s); JAX sees "
              f"{[d.platform for d in jax.devices()]}; nothing run",
              file=sys.stderr)
        return 3
    print(f"chipbench: compile cache {cache}", file=sys.stderr)
    line = harness.execute(args.workload, args.seed, args.seconds,
                           bool(args.trace), devices, spec, counter)
    for name, c in line["checks"].items():
        print(f"check {name} = {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
