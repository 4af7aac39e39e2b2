"""Run lifecycle CLI: inspect and garbage-collect ledgered runs
(``python -m repro.runs ...``).

The run ledger (:mod:`repro.core.ledger`) accumulates one directory per
run under ``results/runs/`` — sweeps run with ``run_grid(run_id=...)``,
auto-ledgered crash recordings (``$REPRO_RUN_LEDGER=1``), chaos CI
artifacts. This module is the operator's toolbox over that tree:

* ``list`` — every run with status/progress/age; orphaned ``running``
  runs (the process died and the run's files went silent) are repaired
  to ``interrupted`` on sight.
* ``show`` — one run's manifest plus per-chunk shard and resplit state;
  ``--assert-status`` makes it a CI assertion tool.
* ``gc``   — age-based retention (``--older-than 7d``); live runs (a
  ``running`` manifest that is not stale) are protected unless
  ``--force``.

A run is executed, and resumed, by ``run_grid(..., run_id=/resume=)``.

Exit codes: 0 ok; 1 usage/run errors or a failed assertion.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from typing import List, Optional

from repro.core import ledger as _ledger


def _fmt_age(seconds: float) -> str:
    seconds = max(seconds, 0.0)
    for unit, span in (("d", 86400.0), ("h", 3600.0), ("m", 60.0)):
        if seconds >= span:
            return f"{seconds / span:.1f}{unit}"
    return f"{seconds:.0f}s"


def _parse_age(text: str) -> float:
    """``7d`` / ``12h`` / ``30m`` / ``45s`` (bare numbers are days)."""
    text = text.strip().lower()
    mult = {"d": 86400.0, "h": 3600.0, "m": 60.0, "s": 1.0}
    if text and text[-1] in mult:
        return float(text[:-1]) * mult[text[-1]]
    return float(text) * 86400.0


def _ledgers() -> List[_ledger.RunLedger]:
    root = _ledger.runs_root()
    if not root.is_dir():
        return []
    out = []
    for path in sorted(root.iterdir()):
        if path.is_dir() and (path / "manifest.json").exists():
            try:
                out.append(_ledger.RunLedger(path.name))
            except ValueError:
                continue
    return out


def _run_info(led: _ledger.RunLedger, stale_after: Optional[float],
              repair: bool) -> dict:
    led.load()
    if repair:
        led.repair_if_stale(stale_after)
        status = str(led.manifest.get("status", "unknown"))
    else:
        status = led.probe_status(stale_after)
    return {
        "run_id": led.run_id,
        "status": status,
        "cells": led.manifest.get("cells"),
        "shards": len(led.completed_keys()),
        "resplits": len(led.load_resplits()),
        "interruptions": int(led.manifest.get("interruptions", 0) or 0),
        "age_s": time.time() - led.last_activity_ts(),
        "engine": led.manifest.get("engine"),
    }


# ------------------------------------------------------------ subcommands

def _cmd_list(args) -> int:
    infos = [_run_info(led, args.stale_after, repair=not args.no_repair)
             for led in _ledgers()]
    if args.json:
        print(json.dumps(infos, indent=1, sort_keys=True))
        return 0
    if not infos:
        print(f"# no runs under {_ledger.runs_root()}")
        return 0
    print(f"{'RUN':<32} {'STATUS':<12} {'SHARDS':>6} {'CELLS':>5} "
          f"{'AGE':>7}")
    for inf in infos:
        print(f"{inf['run_id']:<32} {inf['status']:<12} "
              f"{inf['shards']:>6} {str(inf['cells'] or '?'):>5} "
              f"{_fmt_age(inf['age_s']):>7}")
    return 0


def _cmd_show(args) -> int:
    led = _ledger.RunLedger(args.run_id)
    try:
        led.load()
    except ValueError as exc:
        print(f"error: {exc}", file=sys.stderr)
        return 1
    info = _run_info(led, args.stale_after, repair=not args.no_repair)
    info["grid"] = led.manifest.get("grid")
    info["grid_hash"] = led.manifest.get("grid_hash")
    info["chunks"] = led.completed_keys()
    info["resplit_parents"] = sorted(led.load_resplits())
    if args.json:
        print(json.dumps(info, indent=1, sort_keys=True))
    else:
        print(f"run {info['run_id']}: status={info['status']} "
              f"cells={info['cells']} shards={info['shards']} "
              f"engine={info['engine']} age={_fmt_age(info['age_s'])} "
              f"interruptions={info['interruptions']}")
        for key in info["chunks"]:
            print(f"  chunk {key}  done")
        for parent in info["resplit_parents"]:
            print(f"  resplit {parent} -> children adopted")
    if args.assert_status and info["status"] != args.assert_status:
        print(f"error: status {info['status']!r} != "
              f"{args.assert_status!r}", file=sys.stderr)
        return 1
    return 0


def _cmd_gc(args) -> int:
    cutoff = _parse_age(args.older_than)
    now = time.time()
    removed, kept = [], []
    for led in _ledgers():
        led.load()
        age = now - led.last_activity_ts()
        status = led.probe_status(args.stale_after)
        if age < cutoff:
            kept.append((led.run_id, "young", age))
            continue
        if status == "running" and not args.force:
            kept.append((led.run_id, "live", age))
            continue
        removed.append((led.run_id, status, age))
        if not args.dry_run:
            led.remove()
    verb = "would remove" if args.dry_run else "removed"
    for run_id, status, age in removed:
        print(f"# {verb} {run_id} ({status}, idle {_fmt_age(age)})")
    for run_id, why, age in kept:
        if why == "live":
            print(f"# kept {run_id}: still running (use --force)")
    print(f"# gc: {len(removed)} {verb.split()[-1]}, {len(kept)} kept")
    return 0


# ------------------------------------------------------------------ main

def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m repro.runs",
        description="Run-ledger lifecycle tools (see module docstring). "
                    "$REPRO_RUNS_DIR overrides the ledger root.")
    sub = ap.add_subparsers(dest="cmd", required=True)

    def common(p):
        p.add_argument("--stale-after", type=float, default=None,
                       help="seconds of silence before a 'running' run "
                            "counts as interrupted (default 600)")
        p.add_argument("--no-repair", action="store_true",
                       help="report staleness but do not rewrite "
                            "manifests")
        p.add_argument("--json", action="store_true")

    p = sub.add_parser("list", help="list runs with status/progress/age")
    common(p)
    p.set_defaults(fn=_cmd_list)

    p = sub.add_parser("show", help="one run's manifest + chunk state")
    p.add_argument("run_id")
    common(p)
    p.add_argument("--assert-status", default=None,
                   help="exit 1 unless the run has this status")
    p.set_defaults(fn=_cmd_show)

    p = sub.add_parser("gc", help="age-based retention over results/runs")
    p.add_argument("--older-than", required=True,
                   help="remove runs idle longer than this (7d, 12h, "
                        "30m, 45s; bare number = days)")
    p.add_argument("--dry-run", action="store_true")
    p.add_argument("--force", action="store_true",
                   help="remove even runs that look live")
    p.add_argument("--stale-after", type=float, default=None)
    p.set_defaults(fn=_cmd_gc)

    args = ap.parse_args(argv)
    return args.fn(args)


if __name__ == "__main__":
    sys.exit(main())
