"""The reduction from a profiler trace to the per-layer numbers, on a
small trace recorded on a TPU v5e (``data/trace_small.json``: three
rounds of a prefill and a decode program, each in a host span, inside
the window span) and on hand-made intervals."""
import json
import pathlib

import pytest

from chipbench import trace as T

DATA = pathlib.Path(__file__).parent / "data" / "trace_small.json"


def _toy():
    # window [0, 100) ns; ops overlap on [10, 30) and [25, 40); one op
    # half outside the window; prefill and decode program runs
    return {"devices": {0: {
        "ops": [["a", 10, 20], ["b", 25, 15], ["c", 60, 10],
                ["d", 95, 10]],
        "modules": [["jit_chipbench_prefill(1)", 10, 30],
                    ["jit_chipbench_decode(2)", 60, 10],
                    ["jit_chipbench_decode(2)", 120, 10]]}},
        "host": [["chipbench.window", 0, 100],
                 ["chipbench.serve.host", 40, 20],
                 ["chipbench.serve.decode", 70, 30]]}


def test_union_clip_and_busy_on_hand_made_intervals():
    ev = _toy()
    assert T.merge([(10, 30), (25, 40), (60, 70)]) == [[10, 40], [60, 70]]
    assert T.window_of(ev) == (0, 100)
    # busy: [10, 40) + [60, 70) + [95, 100) = 45 ns
    assert T.busy_s(ev) == pytest.approx(45e-9)
    red = T.reduce(ev)
    assert red["window_s"] == pytest.approx(100e-9)
    assert 1 - red["busy_s"] / red["window_s"] == pytest.approx(0.55)


def test_program_time_and_idle_attribution_on_hand_made_intervals():
    ev = _toy()
    assert T.module_stats(ev, "jit_chipbench_decode") == {
        "count": 1, "seconds": pytest.approx(10e-9)}
    assert T.module_ms({"events": ev}, "jit_chipbench_prefill") == \
        pytest.approx(30e-6)
    assert T.module_ms({"events": ev}, "jit_nothing") is None
    # gaps [0,10) none, [40,60) host, [70,95) decode span
    gaps = dict(T.idle_gaps(ev))
    assert gaps == pytest.approx({"chipbench.serve.decode": 25e-9,
                                  "chipbench.serve.host": 20e-9,
                                  "none": 10e-9})


def test_recorded_trace():
    ev = json.loads(DATA.read_text())["events"]
    ev["devices"] = {int(k): v for k, v in ev["devices"].items()}
    red = T.reduce(ev)
    assert red["devices"] == 1 and not red["truncated"]
    assert 0 < red["busy_s"] < red["window_s"]
    # the marks bound the window on the device's clock: it holds every
    # program run, though the device runs about 1 ms ahead of the host
    lo, hi = T.window_of(ev)
    assert all(lo <= s and s + d <= hi
               for _, s, d in ev["devices"][0]["modules"])
    pre = T.module_stats(ev, "jit_chipbench_prefill")
    dec = T.module_stats(ev, "jit_chipbench_decode")
    assert pre["count"] == 3 and dec["count"] == 3
    # the programs' device time lies within the busy time
    assert pre["seconds"] + dec["seconds"] <= red["busy_s"] * 1.001
    # every op of the window sums to at least the busy union
    lo, hi = T.window_of(ev)
    ops = ev["devices"][0]["ops"]
    inside = sum(d for _, s, d in ops if s >= lo and s + d <= hi)
    assert inside >= red["busy_s"] * 1e9 * 0.999
    gaps = dict(T.idle_gaps(ev))
    assert sum(gaps.values()) == pytest.approx(
        red["window_s"] - red["busy_s"], rel=1e-6)
