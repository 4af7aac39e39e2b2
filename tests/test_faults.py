"""Fault-injection harness (repro.core.faults): plan grammar, trigger
arithmetic, actions, and the zero-cost-when-disabled contract."""
import os
import subprocess
import sys

import pytest

from repro.core import faults
from repro.core.faults import FaultSpec, InjectedFault, parse_plan


# ------------------------------------------------------------- grammar

def test_parse_single_clause():
    plan = parse_plan("chunk.dispatch@1=raise")
    assert plan is not None and len(plan.specs) == 1
    s = plan.specs[0]
    assert (s.site, s.trigger, s.action, s.key) == \
        ("chunk.dispatch", "1", "raise", None)


def test_parse_full_grammar():
    plan = parse_plan(
        "chunk.dispatch[syrk/ciao-c]@%4=raise,"
        "cache.load@2-3=corrupt; stepper.step@5+=delay:0.25")
    assert [s.site for s in plan.specs] == \
        ["chunk.dispatch", "cache.load", "stepper.step"]
    assert plan.specs[0].key == "syrk/ciao-c"
    assert plan.specs[2].param == 0.25


def test_parse_empty_is_none():
    assert parse_plan("") is None
    assert parse_plan(" , ; ") is None


@pytest.mark.parametrize("bad", [
    "chunk.dispatch",                # no trigger/action
    "chunk.dispatch@x=raise",        # bad trigger
    "chunk.dispatch@1=explode",      # unknown action
    "chunk.dispatch@%0=raise",       # modulo zero
])
def test_parse_rejects_malformed(bad):
    with pytest.raises(ValueError):
        parse_plan(bad)


# ------------------------------------------------------------- triggers

@pytest.mark.parametrize("trigger,expect", [
    ("*", [True, True, True, True, True]),
    ("3", [False, False, True, False, False]),
    ("3+", [False, False, True, True, True]),
    ("2-4", [False, True, True, True, False]),
    ("%2", [False, True, False, True, False]),
])
def test_trigger_arithmetic(trigger, expect):
    spec = FaultSpec(site="s", action="raise", trigger=trigger)
    assert [spec.hits(n) for n in range(1, 6)] == expect


def test_counters_per_clause_and_key_scoped():
    plan = parse_plan("cell.run[syrk]@2=raise")
    plan.fire("cell.run", key="kmn/gto/base")       # key miss: no count
    plan.fire("cell.run", key="syrk/gto/base")      # count 1
    with pytest.raises(InjectedFault):
        plan.fire("cell.run", key="syrk/ciao-c/base")   # count 2 fires
    assert plan.counts == [2] and plan.fired == [1]


# ------------------------------------------------------------- actions

def test_raise_action_type():
    plan = parse_plan("records.save@*=raise")
    with pytest.raises(InjectedFault):
        plan.fire("records.save")
    # InjectedFault is a RuntimeError so generic handlers still catch it
    assert issubclass(InjectedFault, RuntimeError)


def test_delay_action_sleeps(monkeypatch):
    slept = []
    monkeypatch.setattr(faults.time, "sleep", slept.append)
    plan = parse_plan("stepper.step@*=delay:0.125")
    plan.fire("stepper.step")
    assert slept == [0.125]


def test_corrupt_action_garbles_file(tmp_path):
    p = tmp_path / "cache.npz"
    p.write_bytes(b"A" * 1000)
    plan = parse_plan("cache.load@*=corrupt")
    plan.fire("cache.load", path=str(p))
    data = p.read_bytes()
    assert len(data) == 500 and data.startswith(b"\x00CORRUPTED")


def test_corrupt_without_path_raises():
    plan = parse_plan("records.save@*=corrupt")
    with pytest.raises(InjectedFault):
        plan.fire("records.save", path=None)


# ------------------------------------------------ install / fire / env

def test_fire_is_noop_without_plan():
    faults.clear()
    assert faults.active() is None
    faults.fire("chunk.dispatch")          # must not raise


def test_injected_context_restores_previous():
    faults.clear()
    with faults.injected("cell.run@*=raise") as plan:
        assert faults.active() is plan
        with pytest.raises(InjectedFault):
            faults.fire("cell.run")
    assert faults.active() is None


def test_install_accepts_text_and_clear():
    try:
        plan = faults.install("cell.run@1=raise")
        assert faults.active() is plan
    finally:
        faults.clear()
    assert faults.active() is None


def test_env_plan_installed_at_import():
    """$REPRO_FAULT_PLAN is parsed at import so spawn workers inherit
    it; check in a subprocess to avoid touching this process's plan."""
    code = ("from repro.core import faults; "
            "p = faults.active(); "
            "assert p is not None and p.specs[0].site == 'cell.run'")
    env = dict(os.environ, REPRO_FAULT_PLAN="cell.run@1=raise",
               PYTHONPATH="src")
    subprocess.run([sys.executable, "-c", code], check=True, env=env,
                   cwd=os.path.dirname(os.path.dirname(__file__)))


# --------------------------------------------- ledger / serving sites

def test_distributed_and_serving_sites_registered():
    for site in ("chunk.resplit", "serve.admit", "serve.preempt",
                 "serve.page_alloc"):
        assert site in faults.SITES
        # every site name parses in a clause
        assert parse_plan(f"{site}@1=raise").specs[0].site == site


def test_lease_sites_fire_through_ledger(tmp_path, monkeypatch):
    """``records.save`` fires in ``RunLedger.save_chunk`` before the
    shard is written: a raised save leaves no shard behind."""
    monkeypatch.setenv("REPRO_RUNS_DIR", str(tmp_path / "runs"))
    from repro.core.ledger import RunLedger
    led = RunLedger("f1")
    led.open({"grid_hash": "h"})
    with faults.injected("records.save[chunk:k]@1=raise"):
        with pytest.raises(InjectedFault):
            led.save_chunk("k", [{"i": 0}])
    assert led.load_chunk("k") is None
    led.save_chunk("k", [{"i": 0}])
    assert led.load_chunk("k") == [{"i": 0}]
