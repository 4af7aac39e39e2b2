"""Run ledger (repro.core.ledger): checkpoint shards, resume semantics,
and the central property — a run interrupted after any prefix of chunks
(or SIGKILLed mid-chunk) still reassembles records **bit-identical** to
an uninterrupted serial run, re-executing only the incomplete chunks."""
import json
import os
import signal
import subprocess
import sys
import time

import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import _cstep, faults
from repro.core.faults import InjectedFault
from repro.core.ledger import RunLedger, chunk_key, grid_hash, runs_root
from repro.core.runner import (ExperimentGrid, FailedCell, grid_from_doc,
                               grid_to_doc, last_batched_perf, run_grid)

GRID = ExperimentGrid(name="led", workloads=("syrk", "kmn"),
                      policies=("gto", "ciao-c", "best-swl"), scale=0.05,
                      best_swl_limits=(2, 8))
BACKENDS = ["numpy"] + (["c"] if _cstep.available() else [])


@pytest.fixture(autouse=True)
def _isolated_runs_dir(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_RUNS_DIR", str(tmp_path / "runs"))
    monkeypatch.delenv("REPRO_RUN_LEDGER", raising=False)
    faults.clear()
    yield
    faults.clear()


def _base():
    if not hasattr(_base, "recs"):
        _base.recs = run_grid(GRID, engine="batched")
    return _base.recs


# ------------------------------------------------------------ unit level

def test_grid_hash_tracks_grid_content():
    assert grid_hash(GRID) == grid_hash(GRID)
    other = ExperimentGrid(name="led", workloads=("syrk",),
                           policies=("gto",), scale=0.05)
    assert grid_hash(GRID) != grid_hash(other)


def test_chunk_key_is_order_independent():
    assert chunk_key(["3:0", "4:1"]) == chunk_key(["4:1", "3:0"])
    assert chunk_key(["3:0"]) != chunk_key(["4:1"])


def test_run_id_path_traversal_rejected():
    for bad in ("a/b", "../up", ".hidden"):
        with pytest.raises(ValueError):
            RunLedger(bad)


def test_manifest_written_and_finished(tmp_path):
    recs = run_grid(GRID, engine="batched", run_id="m1")
    assert recs == _base()
    man = json.loads((runs_root() / "m1" / "manifest.json").read_text())
    assert man["status"] == "complete"
    assert man["grid_hash"] == grid_hash(GRID)
    assert man["cells"] == len(recs)
    assert list((runs_root() / "m1" / "chunks").glob("*.json"))


def test_resume_missing_run_raises():
    with pytest.raises(ValueError, match="cannot resume"):
        run_grid(GRID, engine="batched", resume="never-ran")


def test_resume_grid_mismatch_raises():
    run_grid(GRID, engine="batched", run_id="g1")
    other = ExperimentGrid(name="led", workloads=("syrk",),
                           policies=("gto",), scale=0.05)
    with pytest.raises(ValueError, match="grid"):
        run_grid(other, engine="batched", resume="g1")


def test_run_id_resume_conflict_raises():
    with pytest.raises(ValueError, match="conflicts"):
        run_grid(GRID, engine="batched", run_id="a", resume="b")


def test_fresh_run_id_clears_stale_shards():
    """Reusing a run_id without resume= must start clean, not splice
    another run's shards in."""
    run_grid(GRID, engine="batched", run_id="r1")
    recs = run_grid(GRID, engine="batched", run_id="r1")
    assert recs == _base()
    assert last_batched_perf()["chunks_resumed"] == 0


def test_corrupt_shard_is_rerun_not_trusted():
    run_grid(GRID, engine="batched", run_id="c1")
    shards = sorted((runs_root() / "c1" / "chunks").glob("*.json"))
    shards[0].write_text("{ not json")
    recs = run_grid(GRID, engine="batched", resume="c1")
    assert recs == _base()
    assert not any(isinstance(r, FailedCell) for r in recs)


def test_full_resume_runs_nothing_new():
    run_grid(GRID, engine="batched", run_id="f1", jobs=2)
    recs = run_grid(GRID, engine="batched", resume="f1", jobs=2)
    assert recs == _base()
    perf = last_batched_perf()
    assert perf["chunks_resumed"] == perf["chunks"]
    assert perf["stepper_s"] == 0.0         # no chunk actually executed


def test_auto_ledger_env(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_RUN_LEDGER", "1")
    recs = run_grid(GRID, engine="batched")
    assert recs == _base()
    autos = [p for p in runs_root().iterdir() if p.name.startswith("led-")]
    assert autos, "expected an auto-generated ledger directory"


def test_process_engine_cells_get_per_cell_shards():
    grid = ExperimentGrid(name="led-proc", workloads=("syrk",),
                          policies=("gto", "ciao-p"), scale=0.2)
    base = run_grid(grid, engine="process")
    run_grid(grid, engine="process", run_id="p1")
    recs = run_grid(grid, engine="process", resume="p1")
    assert recs == base


# ------------------------------------------------------------ grid doc

def test_grid_doc_round_trips_grid_hash():
    doc = grid_to_doc(GRID)
    assert grid_hash(grid_from_doc(doc)) == grid_hash(GRID)
    # docs are plain JSON: survive a serialization round trip too
    assert grid_hash(grid_from_doc(json.loads(json.dumps(doc)))) \
        == grid_hash(GRID)


# -------------------------------------------- interrupt → resume property

_PROP_BASE = {}    # (backend, jobs) -> uninterrupted records


def _prop_base(backend, jobs):
    if (backend, jobs) not in _PROP_BASE:
        _PROP_BASE[backend, jobs] = run_grid(GRID, engine="batched",
                                             jobs=jobs)
    return _PROP_BASE[backend, jobs]


@pytest.mark.parametrize("jobs", [1, 2])
@pytest.mark.parametrize("backend", BACKENDS)
@settings(max_examples=2, deadline=None)
@given(st.integers(min_value=1, max_value=4))
def test_interrupted_run_resumes_bit_identical(backend, jobs, kill_after):
    """Kill a strict run after ``kill_after`` chunk dispatches, resume
    from its ledger: only incomplete chunks re-run, and the final
    records equal the uninterrupted run's bit for bit — on each stepper
    and worker count, over a limit-sweep grid.

    Environment handling is manual (no monkeypatch): function-scoped
    fixtures don't reset between hypothesis examples."""
    import tempfile
    saved = {k: os.environ.get(k)
             for k in ("REPRO_RUNS_DIR", "REPRO_BATCHED_BACKEND")}
    os.environ["REPRO_RUNS_DIR"] = tempfile.mkdtemp(prefix="repro-led-")
    os.environ["REPRO_BATCHED_BACKEND"] = backend
    try:
        base = _prop_base(backend, jobs)
        run_id = f"prop-{kill_after}-{backend}-{jobs}"
        trigger = f"{kill_after + 1}+"   # let kill_after dispatches pass
        try:
            with faults.injected(f"chunk.dispatch@{trigger}=raise"):
                run_grid(GRID, engine="batched", jobs=jobs, strict=True,
                         run_id=run_id)
        except InjectedFault:
            pass                          # the simulated crash
        recs = run_grid(GRID, engine="batched", jobs=jobs, resume=run_id)
        assert recs == base
        perf = last_batched_perf()
        assert perf["chunks_resumed"] >= min(kill_after, perf["chunks"])
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


# ------------------------------------------- a real process death (SIGKILL)

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# the victim: one strict ledgered run of the grid given as a JSON doc
_VICTIM = """
import json, sys
from repro.core.runner import grid_from_doc, run_grid
run_grid(grid_from_doc(json.loads(sys.argv[2])), engine="batched",
         strict=True, run_id=sys.argv[1])
"""


@pytest.mark.parametrize("backend", BACKENDS)
def test_sigkilled_run_resumes_bit_identical(backend, monkeypatch):
    """A real process dies mid-run: the subprocess writes its first
    shard, stalls inside its second chunk dispatch, and is SIGKILLed
    there. Its manifest still says ``running``; an in-process resume
    re-runs only what is missing and equals the serial run bit for
    bit."""
    # small token budget => several chunks, so the kill lands mid-run
    monkeypatch.setenv("REPRO_BATCH_TOKEN_BUDGET", "60000")
    monkeypatch.setenv("REPRO_BATCHED_BACKEND", backend)
    base = run_grid(GRID, engine="batched")
    run_id = f"kill-{backend}"
    env = dict(os.environ)
    env["PYTHONPATH"] = (os.path.join(_REPO, "src") + os.pathsep
                         + env.get("PYTHONPATH", ""))
    env["REPRO_FAULT_PLAN"] = "chunk.dispatch@2=delay:60"
    victim = subprocess.Popen([sys.executable, "-c", _VICTIM, run_id,
                               json.dumps(grid_to_doc(GRID))],
                              cwd=_REPO, env=env,
                              stdout=subprocess.DEVNULL,
                              stderr=subprocess.DEVNULL)
    led = RunLedger(run_id)
    try:
        t0 = time.time()
        while time.time() - t0 < 60.0 and not led.completed_keys():
            assert victim.poll() is None, "victim exited before a shard"
            time.sleep(0.05)
        assert led.completed_keys(), "victim never wrote a shard"
    finally:
        os.kill(victim.pid, signal.SIGKILL)
        victim.wait(timeout=60)
    assert led.load()["status"] == "running"
    recs = run_grid(GRID, engine="batched", resume=run_id)
    assert recs == base
    perf = last_batched_perf()
    assert 1 <= perf["chunks_resumed"] < perf["chunks"]
    assert led.load()["status"] == "complete"
