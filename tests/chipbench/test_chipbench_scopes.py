"""Device time by the model's named scopes (``chipbench.scopes``): self time
on hand-made nested intervals, the instruction maps of a tiny serving
cell's programs compiled on the CPU, and a small scoped trace recorded on
a TPU v5e (``data/trace_scoped.json``, written by
``record_trace_scoped.py``)."""
import json
import pathlib

import jax
import pytest

from chipbench import harness
from chipbench import scopes as S
from chipbench import trace as T
from repro.models import model as M

DATA = pathlib.Path(__file__).parent / "data" / "trace_scoped.json"
DECODE_BUCKETS = (("layers",), (S.UNSCOPED,),
                  ("attn_qkv", "kv_write", "attend", "attn_out"), ("mlp",),
                  ("embed", "head"))


def _nested():
    # a while [0, 100) enclosing body ops, one of which encloses another;
    # a parent [200, 250) whose body op runs past it, to 260; a lone op
    return [["%while.1 = (s32[], f32[4]{0}) while(%t)", 0, 100],
            ["%fusion.1 = f32[4]{0} fusion(%a)", 5, 15],
            ["%fusion.2 = f32[4]{0} fusion(%b)", 20, 20],
            ["%copy.1 = f32[4]{0} copy(%c)", 25, 5],
            ["%fusion.3 = f32[4]{0} fusion(%d)", 50, 10],
            ["%while.2 = (s32[], f32[4]{0}) while(%u)", 200, 50],
            ["%fusion.4 = f32[4]{0} fusion(%e)", 240, 20],
            ["%copy.2 = f32[8]{0} copy(%f)", 300, 10]]


def test_self_time_on_nested_intervals():
    ops = _nested()
    assert S.self_times(ops) == [55, 15, 15, 5, 10, 40, 20, 10]
    # the self times tile the union of the intervals
    union = sum(e - s for s, e in T.merge((s, s + d) for _, s, d in ops))
    assert sum(S.self_times(ops)) == union == 170


def test_buckets_sum_to_the_modules_time():
    ops = _nested()
    events = {"devices": {0: {
        "ops": ops,
        "modules": [["jit_chipbench_decode(7)", 0, 100],
                    ["jit_chipbench_decode(7)", 200, 60],
                    ["jit_chipbench_decode(7)", 300, 10],
                    ["jit_chipbench_decode(7)", 900, 10]]}},
        "host": [["chipbench.window", 0, 400]]}
    dec = {("while.1", "(s32[], f32[4]{0})"): "layers",
           ("while.2", "(s32[], f32[4]{0})"): "layers",
           ("fusion.1", "f32[4]{0}"): "attn_qkv",
           ("fusion.2", "f32[4]{0}"): "mlp",
           ("copy.1", "f32[4]{0}"): None,
           ("fusion.3", "f32[4]{0}"): "head",
           ("fusion.4", "f32[4]{0}"): "attend",
           ("copy.2", "f32[8]{0}"): None}
    other = {("fusion.1", "f32[4]{0}"): "mlp"}
    res = S.buckets(events, "jit_chipbench_decode", [other, dec])
    # the run at 900 lies outside the window
    assert res["runs"] == 3 and res["matched"] == 1.0
    assert res["module_ms"] == pytest.approx(170 / 3 / 1e6)
    assert res["buckets"] == pytest.approx({
        "layers": 95 / 3e6, "attn_qkv": 15 / 3e6, "mlp": 15 / 3e6,
        S.UNSCOPED: 15 / 3e6, "head": 10 / 3e6, "attend": 20 / 3e6})
    assert sum(res["buckets"].values()) == pytest.approx(res["module_ms"])
    # an op no map knows lands in unscoped and counts as not matched
    events["devices"][0]["ops"][4][0] = "%fusion.9 = f32[4]{0} fusion(%d)"
    res = S.buckets(events, "jit_chipbench_decode", [dec])
    assert res["matched"] == pytest.approx(160 / 170)
    assert res["buckets"][S.UNSCOPED] == pytest.approx(25 / 3e6)
    assert S.buckets(events, "jit_chipbench_prefill", [dec]) is None


def test_instruction_key_and_scope_of():
    assert S.instruction_key(
        "  ROOT %fusion.143 = bf16[48,24576]{1,0:T(8,128)(2,1)} fusion("
        "%p.1), kind=kLoop") == ("fusion.143",
                                 "bf16[48,24576]{1,0:T(8,128)(2,1)}")
    assert S.instruction_key(
        "%copy-start = (bf16[8]{0:S(1)}, u32[]{:S(2)}) copy-start("
        "bf16[8]{0} %x.1)") == ("copy-start",
                                "(bf16[8]{0:S(1)}, u32[]{:S(2)})")
    assert S.instruction_key("HloModule jit_f") is None
    path = ("jit(chipbench_decode)/layers/while/body/closed_call/kv_write/"
            "scatter")
    assert S.scope_of(path, M.SCOPES) == "kv_write"
    assert S.scope_of("jit(f)/layers/while", M.SCOPES) == "layers"
    assert S.scope_of("jit(f)/head", M.SCOPES) is None
    assert S.scope_of("jit(f)/dynamic_slice", M.SCOPES) is None


@pytest.fixture
def served(serve_spec):
    """A tiny serving cell on the CPU after a window traced from its
    start (the trace itself is not taken)."""
    drv = harness.driver_module(serve_spec)
    st = drv.setup(serve_spec, 11, jax.devices()[:1], 0.5)
    drv.window(st, 0.5, at=(0.0, lambda: None))
    return st


def test_instruction_maps_of_a_tiny_serve_cell(served):
    maps, cost = S.program_maps(served, "decode", M.SCOPES)
    assert len(maps) == 1 and cost["programs"] == 1
    # every block scope of the dense model, the scan, and XLA's own work
    assert set(maps[0].values()) == {
        "embed", "layers", "attn_qkv", "kv_write", "attend", "attn_out",
        "mlp", "head", None}
    lengths = {p for _, p in served["admitted"][served["traced_from"][1]:]}
    pmaps, _ = S.program_maps(served, "prefill", M.SCOPES)
    assert len(pmaps) == len(lengths) >= 1
    assert all("attend" in m.values() for m in pmaps)
    # the decode program that ran is the one mapped: its lowering is the
    # run's own, so JAX's cache hands back the same executable
    fn = served["fns"]["decode"]
    ran = fn.lower(*S.program_args(served, "decode")[0]).compile().as_text()
    assert S.instruction_map(ran, M.SCOPES) == maps[0]


def test_nothing_is_read_where_the_model_has_no_scopes(served, monkeypatch):
    ctx = {"trace": {"events": {"devices": {}, "host": []}},
           "state": served}
    monkeypatch.delattr(M, "SCOPES")
    assert S.read(ctx, "decode") is None
    assert harness.read_metric("serve.decode_scan_cache_ms", ctx) is None


def test_nothing_is_read_from_a_program_without_scopes(served,
                                                       monkeypatch):
    # a decode run in the window whose compiled program carries no scope,
    # as one loaded from a cache entry of a program without them would
    op = "%fusion.1 = f32[4]{0} fusion(%a)"
    ctx = {"trace": {"events": {
        "devices": {0: {"ops": [[op, 10, 80]],
                        "modules": [["jit_chipbench_decode(1)", 0, 100]]}},
        "host": [["chipbench.window", 0, 200]]}}, "state": served}
    maps = [{("fusion.1", "f32[4]{0}"): None}]
    monkeypatch.setattr(S, "program_maps", lambda *a: (maps, {}))
    assert S.read(ctx, "decode") is None
    maps[0][("fusion.1", "f32[4]{0}")] = "mlp"
    ctx.pop("scopes")
    assert S.read(ctx, "decode")["buckets"] == {"mlp": 80e-6}


def _recorded():
    data = json.loads(DATA.read_text())
    ev = data["events"]
    ev["devices"] = {int(k): v for k, v in ev["devices"].items()}
    maps = {p: [{(n, s): v for n, s, v in m} for m in ms]
            for p, ms in data["maps"].items()}
    return ev, maps


def test_recorded_decode_buckets_sum_to_the_modules_time():
    ev, maps = _recorded()
    res = S.buckets(ev, "jit_chipbench_decode", maps["jit_chipbench_decode"])
    assert res["runs"] >= 3 and res["matched"] == 1.0
    parts = [sum(res["buckets"].get(n, 0.0) for n in names)
             for names in DECODE_BUCKETS]
    assert all(p > 0 for p in parts)
    assert sum(parts) == pytest.approx(res["module_ms"], rel=0.01)


def test_recorded_prefill_attention_lies_within_the_prefill():
    ev, maps = _recorded()
    res = S.buckets(ev, "jit_chipbench_prefill",
                    maps["jit_chipbench_prefill"])
    assert res["runs"] >= 1 and res["matched"] == 1.0
    assert 0 < res["buckets"]["attend"] < res["module_ms"]
    assert sum(res["buckets"].values()) == pytest.approx(res["module_ms"],
                                                         rel=0.01)
