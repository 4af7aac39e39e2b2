"""Records ``data/trace_scoped.json`` on one TPU chip: a traced window of a
small serving cell (the nemotron stage's layout at 2 layers and narrow
widths), cut to a few runs of the decode and prefill programs, with the
instruction maps of those programs (``chipbench.scopes``).

    python3 tests/chipbench/record_trace_scoped.py <out.json>

Exits 3 where JAX finds no TPU.
"""
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

SEED = 1300000001
SECONDS = 3.0
TRACED = 1.0
RUNS = 3          # runs of each program kept


def spec():
    from chipbench import harness
    cfg = dict(harness.load_json(
        harness.HERE / "configs/nemotron-4-15b-pp4stage.json"),
        num_layers=2, d_model=1024, num_heads=8, num_kv_heads=2,
        head_dim=128, d_ff=4096, vocab_size=8192)
    mix = dict(harness.load_json(harness.HERE / "traffic/chat-decode.json"),
               slots=8, cache_len=512, rate_per_s=30.0, warm_slots=8,
               prompt_len={"kind": "lognormal", "median": 192, "sigma": 0.3,
                           "round_to": 64, "min": 64, "max": 320},
               output_len={"kind": "lognormal", "median": 48, "sigma": 0.6,
                           "min": 4, "max": 160})
    return {"cell": {"name": "serve.scoped-trace", "chips": 1},
            "config": cfg, "traffic": mix, "limits": {},
            "driver": harness.HERE / "drivers" / "serve.py",
            "end_to_end": [], "per_layer": []}


def cut(events, maps):
    """The first ``RUNS`` runs of each program in the window, the marks,
    and the operations that start inside them, each named by its
    instruction's name and shape; each map cut to those instructions."""
    from chipbench import scopes
    from chipbench import trace as T
    lo, hi = T.window_of(events)
    dev = events["devices"][min(events["devices"])]
    keep = [m for m in dev["modules"] if m[0].startswith(T.MARK_PROGRAM)]
    for prefix in maps:
        keep += sorted((m for m in dev["modules"] if m[0].startswith(prefix)
                        and lo <= m[1] < hi), key=lambda m: m[1])[:RUNS]
    ops = []
    for name, s, d in dev["ops"]:
        if any(ms <= s < ms + md for _, ms, md in keep):
            key = scopes.instruction_key(name)
            ops.append([f"%{key[0]} = {key[1]}", s, d])
    seen = {scopes.instruction_key(o[0]) for o in ops}
    kept_maps = {p: [[[k[0], k[1], v] for k, v in m.items() if k in seen]
                     for m in ms] for p, ms in maps.items()}
    host = [h for h in events["host"]
            if h[0] in (T.WINDOW_SPAN, T.MARK_SPAN)]
    return {"devices": {0: {"ops": ops, "modules": sorted(
        keep, key=lambda m: m[1])}}, "host": host}, kept_maps


def main(out: str) -> int:
    from chipbench import harness, scopes
    from repro.models import model as M
    devices = harness.accelerators(1)
    if devices is None:
        print("no TPU; nothing recorded", file=sys.stderr)
        return 3
    harness.setup_compile_cache()
    sp = spec()
    drv = harness.driver_module(sp)
    st = drv.setup(sp, SEED, devices, SECONDS)
    tail = harness.TracedTail(harness.marker(devices[0]))
    drv.window(st, SECONDS, at=(SECONDS - TRACED, tail.start))
    events = tail.stop()
    maps = {f"jit_chipbench_{p}": scopes.program_maps(st, p, M.SCOPES)[0]
            for p in ("decode", "prefill")}
    small, kept = cut(events, maps)
    for prefix in maps:
        res = scopes.buckets(small, prefix, [
            {(n, s): v for n, s, v in m} for m in kept[prefix]])
        print(prefix, json.dumps(res), file=sys.stderr)
    pathlib.Path(out).write_text(json.dumps({
        "about": f"Recorded on a {devices[0].device_kind} by "
                 "tests/chipbench/record_trace_scoped.py: the first "
                 f"{RUNS} runs of the decode and prefill programs of a "
                 "small serving cell in a traced window, each operation "
                 "named by its instruction's name and shape, and the "
                 "instruction maps of each program [name, shape, scope].",
        "events": small, "maps": kept}))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
