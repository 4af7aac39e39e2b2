"""What every cell shares: the cell's files found by name, the seed, the
compile counter, the profiler window and the result line.

A cell is an entry of ``workloads`` in ``BENCHMARK.json``. Its
configuration file ``chipbench/configs/<config>.json`` names a ``kind``,
and ``chipbench/drivers/<kind>.py`` runs it; its traffic mix is
``chipbench/traffic/<traffic>.json``; its correctness limits are
``chipbench/limits/<cell>.json``; each per-layer metric is read by
``chipbench/metrics/<metric>.py``. Adding any of these is adding files.
"""
from __future__ import annotations

import glob
import importlib
import importlib.util
import json
import os
import pathlib
import shutil
import tempfile
import time
from typing import Any, Dict, List, Optional

ROOT = pathlib.Path(__file__).resolve().parents[1]
HERE = ROOT / "chipbench"

# a traced run profiles the last this many seconds of its window, after
# the same lead-in as an untraced run: the profiler's collection grows
# with the traced time, and the whole traced run must end within 360 s
TRACE_SECONDS = 10.0

# JAX's compile events; a run with any of them inside the window compiled
# (or re-traced) there
COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")


class CompileCounter:
    """Counts JAX's compile events and their seconds while registered."""

    def __init__(self) -> None:
        self.count = 0
        self.seconds = 0.0

    def __call__(self, event: str, secs: float, **_) -> None:
        if event in COMPILE_EVENTS:
            self.count += 1
            self.seconds += secs

    def register(self) -> "CompileCounter":
        import jax
        jax.monitoring.register_event_duration_secs_listener(self)
        return self


def setup_compile_cache() -> str:
    """The persistent cache: ``$JAX_COMPILATION_CACHE_DIR`` when set (JAX
    reads it itself), else ``<checkout>/.jax_cache``."""
    import jax
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = ROOT / ".jax_cache"
    jax.config.update("jax_compilation_cache_dir", str(path))
    # cache every program, however quick its compile
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return str(path)


def rng(seed: int, *salt: int):
    """numpy Generator from the run's seed (any size) and a salt."""
    import numpy as np
    return np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, *salt])


def jax_key(seed: int, *salt: int):
    """A JAX key from the run's seed (any size) and a salt."""
    import jax
    key = jax.random.key(seed & 0x7FFFFFFF)
    for s in (seed >> 31, *salt):
        key = jax.random.fold_in(key, s)
    return key


# ------------------------------------------------------------ the files

def load_json(path: pathlib.Path) -> Any:
    with open(path) as f:
        return json.load(f)


def benchmark() -> dict:
    return load_json(ROOT / "BENCHMARK.json")


def _load_module(path: pathlib.Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def resolve(name: str, bench: Optional[dict] = None) -> dict:
    """Every file a cell needs, found by the names in ``BENCHMARK.json``."""
    bench = bench or benchmark()
    cells = {c["name"]: c for c in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    cell = cells[name]
    conf = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    config = load_json(ROOT / conf["file"])
    traffic = load_json(HERE / "traffic" / f"{cell['traffic']}.json")
    limits = load_json(HERE / "limits" / f"{name}.json")
    driver = HERE / "drivers" / f"{config['kind']}.py"
    if not driver.exists():
        raise FileNotFoundError(driver)
    end_to_end = [m for m in bench["end_to_end"]
                  if name in m.get("workloads", [name])]
    per_layer = [m for m in bench["per_layer"]
                 if name in m.get("workloads", [name])]
    for m in per_layer:
        if not (HERE / "metrics" / f"{m['name']}.py").exists():
            raise FileNotFoundError(f"no reader for {m['name']}")
    return {"cell": cell, "config": config, "traffic": traffic,
            "limits": limits, "driver": driver, "end_to_end": end_to_end,
            "per_layer": per_layer}


def driver_module(spec: dict):
    return importlib.import_module(f"chipbench.drivers.{spec['config']['kind']}")


def read_metric(name: str, ctx: dict) -> Optional[float]:
    mod = _load_module(HERE / "metrics" / f"{name}.py",
                       "chipbench_metric_" + name.replace(".", "_"))
    return mod.read(ctx)


def peaks(device_kind: str) -> dict:
    table = load_json(HERE / "peaks.json")["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       "chipbench/peaks.json")
    return table[device_kind]


# ------------------------------------------------------------ a run

def accelerators(chips: int):
    """The first ``chips`` TPU devices, or None where JAX has fewer."""
    import jax
    devs = [d for d in jax.devices() if d.platform == "tpu"]
    return devs[:chips] if len(devs) >= chips else None


def memory_peak(devices) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


class TracedTail:
    """Profiles the end of a window into a temporary directory: ``start``
    opens the trace, the window's span and a mark; ``stop`` closes them
    and returns the events (see :mod:`chipbench.trace`)."""

    def __init__(self, mark) -> None:
        self.mark = mark

    def start(self) -> None:
        import jax
        self.tmp = tempfile.mkdtemp(prefix="chipbench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0      # the benchmark's spans only
        opts.host_tracer_level = 1
        jax.profiler.start_trace(self.tmp, profiler_options=opts)
        from chipbench import trace
        self.span = span(trace.WINDOW_SPAN)
        self.span.__enter__()
        self.mark()

    def stop(self) -> dict:
        import jax
        from chipbench import trace
        try:
            self.mark()
            self.span.__exit__(None, None, None)
            jax.profiler.stop_trace()
            files = glob.glob(os.path.join(self.tmp, "**", "*.xplane.pb"),
                              recursive=True)
            return trace.load_events(files[0])
        finally:
            shutil.rmtree(self.tmp, ignore_errors=True)


def span(name: str):
    """A host span in the profiler's trace (a no-op when not tracing)."""
    import jax
    return jax.profiler.TraceAnnotation(name)


def marker(device):
    """A tiny program whose runs mark the window's ends on the device's
    clock (see :mod:`chipbench.trace`); compiled here, before any
    window."""
    import jax
    import jax.numpy as jnp
    from chipbench import trace

    def chipbench_mark(x):
        return x + 1

    fn = jax.jit(chipbench_mark)
    x = jax.device_put(jnp.zeros((8, 128), jnp.float32), device)
    fn(x).block_until_ready()

    def mark():
        with span(trace.MARK_SPAN):
            fn(x).block_until_ready()
    return mark


def checks_line(checks: List[list]) -> Dict[str, dict]:
    return {n: {"value": v, "limit": lim} for n, v, lim in checks}


def execute(name: str, seed: int, seconds: float, traced: bool,
            devices, spec: Optional[dict] = None,
            counter: Optional[CompileCounter] = None) -> dict:
    """Set up, measure and check one cell on ``devices``; returns the
    result line (``correct``, ``attempted``, ``failed``, ``metrics``,
    ``device``, ``breakdown`` when traced, ``checks`` last). A traced
    run profiles the window's last ``TRACE_SECONDS``."""
    from chipbench import trace as T
    spec = spec or resolve(name)
    drv = driver_module(spec)
    counter = counter or CompileCounter().register()
    t0 = time.perf_counter()
    state = drv.setup(spec, seed, devices, seconds)
    mark = marker(devices[0])
    setup_s = time.perf_counter() - t0
    c0 = counter.count
    tail = TracedTail(mark) if traced else None
    drv.window(state, seconds, at=(max(0.0, seconds - TRACE_SECONDS),
                                   tail.start) if traced else None)
    events = tail.stop() if traced else None
    compiles = counter.count - c0
    mem = memory_peak(devices) if devices[0].platform != "cpu" else 0
    drv.release(state)
    t1 = time.perf_counter()
    checks = drv.check(state) + [["compiles_in_window", compiles, 0]]
    check_s = time.perf_counter() - t1
    correct = all(v <= lim for _, v, lim in checks)
    attempted, failed = drv.counts(state)
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": mem}
    line: Dict[str, Any] = {"correct": correct, "attempted": attempted,
                            "failed": failed}
    metrics: Dict[str, dict] = {}
    if traced:
        red = T.reduce(events)
        ctx = {"trace": red, "state": state, "spec": spec,
               "peaks": peaks(dev.device_kind) if dev.platform == "tpu"
               else None}
        for m in spec["per_layer"]:
            val = read_metric(m["name"], ctx)
            if val is not None:
                metrics[m["name"]] = {"value": val, "unit": m["unit"]}
        device["busy_s"] = red["busy_s"]
        device["window_s"] = red["window_s"]
        line["trace"] = {"truncated": red["truncated"],
                         "device_ops": red["device_ops"]}
    else:
        vals = dict(drv.end_to_end(state), setup_s=setup_s)
        for m in spec["end_to_end"]:
            if vals.get(m["name"]) is not None:
                metrics[m["name"]] = {"value": vals[m["name"]],
                                      "unit": m["unit"]}
    line["metrics"] = metrics
    line["device"] = device
    if traced:
        line["breakdown"] = {"device_ops": red["top_ops"],
                             "idle_gaps": red["idle_gaps"]}
    line["check_s"] = check_s
    line["checks"] = checks_line(checks)
    return line
