"""Run lifecycle CLI (python -m repro.runs): show/list/gc over ledgers
that ``run_grid(run_id=...)`` wrote, orphaned-run repair, and the CI
assertion flag. Everything goes through ``runs.main(argv)``
in-process."""
import json
import os
import time

import pytest

from repro import runs as runs_cli
from repro.core import faults
from repro.core.ledger import RunLedger, grid_hash, runs_root
from repro.core.runner import (ExperimentGrid, expand_grid,
                               last_batched_perf, run_grid)

GRID = ExperimentGrid(name="cli", workloads=("syrk", "kmn"),
                      policies=("gto", "ciao-c"), scale=0.05)


@pytest.fixture(autouse=True)
def _isolated_runs_dir(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_RUNS_DIR", str(tmp_path / "runs"))
    monkeypatch.delenv("REPRO_RUN_LEDGER", raising=False)
    faults.clear()
    yield
    faults.clear()


def _backdate(led, seconds):
    """Age every ledger file so staleness/gc probes see an idle run."""
    old = time.time() - seconds
    paths = [led.manifest_path]
    for sub in (led.chunk_dir, led.resplit_dir):
        if sub.is_dir():
            paths.extend(sub.glob("*.json"))
    for p in paths:
        os.utime(p, (old, old))


def _pending(run_id, status="pending"):
    """A ledger opened for GRID with nothing run yet."""
    led = RunLedger(run_id)
    led.open({"grid_hash": grid_hash(GRID), "engine": "batched",
              "cells": len(expand_grid(GRID))}, status=status)
    return led


# ---------------------------------------------------------------- show

def test_create_work_show_roundtrip(capsys):
    base = run_grid(GRID, engine="batched")
    assert run_grid(GRID, engine="batched", run_id="run1") == base
    assert runs_cli.main(["show", "run1",
                          "--assert-status", "complete"]) == 0
    assert runs_cli.main(["show", "run1",
                          "--assert-status", "running"]) == 1
    # the ledgered run resumes to the serial records, re-running nothing
    recs = run_grid(GRID, engine="batched", resume="run1")
    assert recs == base
    assert last_batched_perf()["stepper_s"] == 0.0


def test_work_missing_run_errors(capsys):
    assert runs_cli.main(["show", "nope"]) == 1
    assert "no readable manifest" in capsys.readouterr().err


# ----------------------------------------------------------- list + gc

def test_list_shows_runs(capsys):
    _pending("l1")
    capsys.readouterr()
    runs_cli.main(["list", "--json"])
    infos = json.loads(capsys.readouterr().out)
    assert [i["run_id"] for i in infos] == ["l1"]
    assert infos[0]["status"] == "pending"
    assert infos[0]["cells"] == 4


def test_gc_age_based_retention(capsys):
    _pending("old")
    _pending("new")
    _backdate(RunLedger("old"), 3 * 86400)
    # dry run removes nothing
    assert runs_cli.main(["gc", "--older-than", "1d", "--dry-run"]) == 0
    assert (runs_root() / "old").exists()
    assert runs_cli.main(["gc", "--older-than", "1d"]) == 0
    assert not (runs_root() / "old").exists()
    assert (runs_root() / "new").exists()


def test_gc_protects_live_runs_without_force(capsys):
    led = _pending("live", status="running")
    _backdate(led, 3 * 86400)
    led.save_chunk("c1", [])            # a fresh shard: the run is alive
    assert runs_cli.main(["gc", "--older-than", "0s"]) == 0
    assert (runs_root() / "live").exists()
    assert "still running" in capsys.readouterr().out
    assert runs_cli.main(["gc", "--older-than", "0s", "--force"]) == 0
    assert not (runs_root() / "live").exists()


def test_parse_age_grammar():
    assert runs_cli._parse_age("7d") == 7 * 86400.0
    assert runs_cli._parse_age("12h") == 12 * 3600.0
    assert runs_cli._parse_age("30m") == 1800.0
    assert runs_cli._parse_age("45s") == 45.0
    assert runs_cli._parse_age("2") == 2 * 86400.0


# -------------------------------------------------------- orphan repair

def _orphan(run_id):
    """A run whose process died without finish(): status still
    'running', files long silent."""
    led = _pending(run_id, status="running")
    _backdate(led, 7200)
    return led


def test_list_repairs_orphaned_running_run(capsys):
    _orphan("orph")
    capsys.readouterr()
    runs_cli.main(["list", "--stale-after", "600", "--json"])
    infos = json.loads(capsys.readouterr().out)
    assert infos[0]["status"] == "interrupted"
    # and the repair is persisted, not just displayed
    assert RunLedger("orph").load()["status"] == "interrupted"
    assert RunLedger("orph").load()["interruptions"] == 1


def test_no_repair_flag_only_reports(capsys):
    _orphan("orph2")
    capsys.readouterr()
    runs_cli.main(["list", "--stale-after", "600", "--no-repair",
                   "--json"])
    infos = json.loads(capsys.readouterr().out)
    assert infos[0]["status"] == "interrupted"      # probed...
    assert RunLedger("orph2").load()["status"] == "running"  # ...not written


def test_resume_of_orphan_counts_interruption(monkeypatch):
    monkeypatch.setenv("REPRO_BATCH_TOKEN_BUDGET", "60000")
    base = run_grid(GRID, engine="batched")
    run_grid(GRID, engine="batched", run_id="orph3")
    led = RunLedger("orph3")
    led.load()
    led.manifest["status"] = "running"
    led._write_manifest()
    _backdate(led, 7200)
    recs = run_grid(GRID, engine="batched", resume="orph3")
    assert recs == base
    assert led.load()["interruptions"] == 1
    assert led.load()["status"] == "complete"


def test_recently_written_run_is_not_stale():
    led = _pending("fresh", status="running")
    _backdate(led, 7200)
    led.save_chunk("c1", [])            # a freshly written shard
    assert led.probe_status(stale_after=600.0) == "running"
    _backdate(led, 7200)                # ...and the same run gone silent
    assert led.probe_status(stale_after=600.0) == "interrupted"
