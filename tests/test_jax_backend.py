"""JAX backend equivalence: the jitted stepper must reproduce the C and
numpy steppers bit-for-bit, per cell, in mixed batches.

Mirrors ``tests/test_batched.py``'s pinning for the third stepper:

* the golden seed-core snapshots — all single-SM golden cells as ONE
  heterogeneous jitted batch; every numeric field must match the
  snapshot exactly (which also pins jax == C == numpy, since both other
  backends are pinned to the same snapshots).
* a mixed batch across the special memory paths (CIAO-P smem
  redirection, statPCAL bypass) equal across all three steppers.
* the runner: ``engine="jax"`` records equal ``engine="batched"`` on a
  grid that mixes batchable cells, an MSHR-gated variant (per-cell
  fallback) and a multi-SM grid (jax chunks fall back to "auto").
* the runner never answers for a failing jax stepper with another one:
  ``run_grid(engine="jax")`` raises.
* the gating contract: multi-SM / object-policy batches raise.
* the batch axis is vmap-able: one jitted iteration under ``jax.vmap``
  over an outer grid axis equals two independent iterations.

Everything here skips cleanly when jax is not importable — the rest of
the suite never depends on it.
"""
import dataclasses
import gzip
import json
import pathlib

import numpy as np
import pytest

from repro.core import _cstep
from repro.core import jax_backend
from repro.core.batched import BatchCell, BatchedSMEngine, run_batched
from repro.core.simulator import SimConfig
from repro.workloads import make_workload

pytestmark = pytest.mark.skipif(
    not jax_backend.available(),
    reason=f"jax unavailable: {jax_backend.unavailable_reason()}")

GOLDEN = pathlib.Path(__file__).parent / "golden" / "golden_cells.json.gz"

SIM_FIELDS = ("policy", "cycles", "instructions", "ipc", "l1_hit_rate",
              "vta_hits", "mean_active_warps", "timeline", "pairs")


def test_golden_cells_one_mixed_batch_jax():
    """All golden single-SM cells as one heterogeneous jitted batch."""
    doc = json.loads(gzip.decompress(GOLDEN.read_bytes()).decode())
    cells = [c for c in doc["cells"] if c["kind"] == "sm"]
    wls = {}
    batch = []
    for c in cells:
        key = (c["workload"], c["seed"], c["scale"])
        if key not in wls:
            wls[key] = make_workload(c["workload"], seed=c["seed"],
                                     scale=c["scale"])
        batch.append(BatchCell(wls[key], c["policy"],
                               dict(c["policy_kwargs"])))
    results = run_batched(batch, backend="jax")
    for c, res in zip(cells, results):
        got = dataclasses.asdict(res)
        got["timeline"] = [list(t) for t in got["timeline"]]
        for field in SIM_FIELDS:
            assert got[field] == c["result"][field], \
                f"{c['workload']}/{c['policy']}: mismatch in {field}"
        for key, val in c["result"]["stats"].items():
            assert got["stats"].get(key) == val, \
                f"{c['workload']}/{c['policy']}: stat {key!r} mismatch"


def test_three_steppers_agree_on_smem_paths():
    """numpy vs C vs jax across the CIAO-P smem redirection + statPCAL
    bypass paths in one mixed batch."""
    wl = make_workload("nw", seed=11, scale=0.12)       # 35% smem app
    wl2 = make_workload("syrk", seed=11, scale=0.12)
    cells = [BatchCell(wl, "ciao-p"), BatchCell(wl, "ciao-c"),
             BatchCell(wl2, "statpcal", {"limit": 2}),
             BatchCell(wl2, "ccws"), BatchCell(wl2, "best-swl",
                                               {"limit": 4})]
    ref = run_batched(cells, backend="numpy")
    got = run_batched(cells, backend="jax")
    assert got == ref
    if _cstep.available():
        assert run_batched(cells, backend="c") == ref


def test_runner_engine_jax_matches_batched(tmp_path, monkeypatch):
    """engine="jax" records equal engine="batched", including an
    MSHR-gated variant (per-cell fallback path)."""
    monkeypatch.setenv("REPRO_WORKLOAD_CACHE_DIR", str(tmp_path))
    from repro.core.onchip import OnChipConfig
    from repro.core.runner import ExperimentGrid, run_grid
    gated = SimConfig(onchip=OnChipConfig(mshr_gate=True))
    grid = ExperimentGrid(name="t", workloads=("syrk", "kmn"),
                          policies=("gto", "ciao-c", "best-swl"),
                          scale=0.06, best_swl_limits=(2, 8),
                          variants={"base": None, "gated": gated})
    assert run_grid(grid, engine="jax") == run_grid(grid,
                                                    engine="batched")


@pytest.mark.parametrize("inject", ["stepper", "dispatch"])
def test_runner_engine_jax_failure_raises(tmp_path, monkeypatch, inject):
    """A failing jax stepper raises out of run_grid(engine="jax"), also
    without strict=True: no C, numpy or scalar rung answers in its
    place, and the retry still runs on the jax rung first."""
    monkeypatch.setenv("REPRO_WORKLOAD_CACHE_DIR", str(tmp_path))
    from repro.core import faults
    from repro.core.runner import ExperimentGrid, run_grid
    grid = ExperimentGrid(name="t3", workloads=("syrk",),
                          policies=("gto", "ciao-c"), scale=0.05)
    calls = []

    def broken(eng):
        calls.append(eng.B)
        raise RuntimeError("device stepper failed")

    if inject == "stepper":
        monkeypatch.setattr(jax_backend, "run_engine", broken)
        with pytest.raises(RuntimeError, match="device stepper failed"):
            run_grid(grid, engine="jax", retries=1)
        assert len(calls) == 2               # first try + one retry
    else:
        with faults.injected("chunk.dispatch@*=raise"), \
                pytest.raises(faults.InjectedFault):
            run_grid(grid, engine="jax", retries=1)
        # the C stepper still answers the same grid when asked by name
        assert len(run_grid(grid, engine="batched")) == 2


def test_runner_engine_jax_multi_sm_falls_back(tmp_path, monkeypatch):
    """Multi-SM grids under engine="jax" fall back to the default
    stepper per chunk and still produce equal records."""
    monkeypatch.setenv("REPRO_WORKLOAD_CACHE_DIR", str(tmp_path))
    from repro.core.gpu import GPUConfig
    from repro.core.runner import ExperimentGrid, run_grid
    grid = ExperimentGrid(name="t2", workloads=("syrk",),
                          policies=("gto", "ciao-c"), scale=0.05,
                          gpu=GPUConfig(num_sms=2))
    assert run_grid(grid, engine="jax") == run_grid(grid,
                                                    engine="batched")


def test_gating_contract(monkeypatch):
    """Multi-SM batches and custom policy objects are rejected with a
    reason; supports_engine mirrors what run() raises."""
    from repro.core import batched as batched_mod
    from repro.core.gpu import GPUConfig
    from repro.core.policies import GTOPolicy

    wl = make_workload("syrk", seed=0, scale=0.05)
    eng = BatchedSMEngine([BatchCell(wl, "gto")], backend="jax",
                          gpu=GPUConfig(num_sms=2))
    assert "multi-SM" in jax_backend.supports_engine(eng)
    with pytest.raises(RuntimeError, match="multi-SM"):
        eng.run()

    class OddPolicy(GTOPolicy):
        def epoch_tick(self, active, finished, dram_load=(0, 0)):
            pass        # any override outside the known families

    real = batched_mod.make_policy
    monkeypatch.setattr(
        batched_mod, "make_policy",
        lambda name, nw, det, **kw: OddPolicy(nw, det)
        if name == "odd" else real(name, nw, det, **kw))
    eng2 = BatchedSMEngine([BatchCell(wl, "odd")], backend="jax")
    assert "object" in jax_backend.supports_engine(eng2)
    with pytest.raises(RuntimeError, match="object"):
        eng2.run()


def test_iteration_is_vmappable():
    """The state pytree's leading batch axis composes with vmap: one
    jitted iteration over an outer (2, B, ...) stacking equals two
    independent iterations (the accelerator grid-axis contract)."""
    import jax
    import jax.numpy as jnp

    wl = make_workload("bicg", seed=5, scale=0.04)
    eng = BatchedSMEngine([BatchCell(wl, "gto"),
                           BatchCell(wl, "ciao-c")], backend="jax")
    S = jax_backend._static_of(eng)
    state, cst = jax_backend._arrays_of(eng)
    with jax.enable_x64(True):
        step = jax.jit(
            lambda st, c: jax_backend._iteration(S, c, dict(st)))
        one = {k: np.asarray(v) for k, v in step(state, cst).items()}
        two = {k: np.asarray(v)
               for k, v in step(one, cst).items()}
        stacked = {k: jnp.stack([jnp.asarray(v), jnp.asarray(one[k])])
                   for k, v in state.items()}
        vstep = jax.jit(jax.vmap(
            lambda st, c: jax_backend._iteration(S, c, dict(st)),
            in_axes=(0, None)))
        vout = vstep(stacked, cst)
        for k in state:
            np.testing.assert_array_equal(
                np.asarray(vout[k][0]), one[k], f"vmap lane 0: {k}")
            np.testing.assert_array_equal(
                np.asarray(vout[k][1]), two[k], f"vmap lane 1: {k}")
