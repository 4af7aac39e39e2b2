"""BENCHMARK.json against the contract's rules of form, every cell's
files found by name, the cost functions against hand-computed counts,
and the harness's refusal to run without a TPU."""
import os
import re
import subprocess
import sys

import pytest

from chipbench import harness
from chipbench.costs import dense_lm

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
ONE_LINE = re.compile(r"^[^\n\t]{1,200}$")
BENCH = harness.benchmark()


def test_top_level_keys_and_paths():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= BENCH["run_seconds"] <= 51
    for p in BENCH["paths"]:
        assert re.fullmatch(r"[A-Za-z0-9_./-]{1,200}", p)
        assert not p.startswith("/") and ".." not in p
        assert (harness.ROOT / p).is_dir()
    assert all(ONE_LINE.match(w) for w in BENCH["command"])


def test_names_units_and_lines():
    seen = set()
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in BENCH[group]:
            assert NAME.match(e["name"]), e["name"]
            assert (group, e["name"]) not in seen
            seen.add((group, e["name"]))
            for k in ("why", "layer", "source"):
                if k in e:
                    assert ONE_LINE.match(e[k]), (e["name"], k)
            if "unit" in e:
                assert UNIT.match(e["unit"]), e["unit"]
                assert e["better"] in ("lower", "higher")
    for c in BENCH["configs"]:
        assert all(NAME.match(k) for k in c["reduced"])
    for w in BENCH["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4)


def test_end_to_end_bounds_and_sources():
    names = [m["name"] for m in BENCH["end_to_end"]]
    assert "setup_s" in names
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_every_cell_resolves_by_name(cell):
    spec = harness.resolve(cell, BENCH)
    assert spec["driver"].exists()
    reported = {m["name"] for m in spec["end_to_end"]}
    assert "setup_s" in reported and len(reported) >= 2
    assert spec["per_layer"]
    for m in spec["per_layer"]:
        # a per-layer metric moves an end-to-end metric its cells report
        assert m["moves"] in reported
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")


def test_every_config_is_used_and_its_file_is_under_paths():
    used = {w["config"] for w in BENCH["workloads"]}
    for c in BENCH["configs"]:
        assert c["name"] in used
        assert any(c["file"].startswith(p + "/") for p in BENCH["paths"])
        assert harness.load_json(harness.ROOT / c["file"])["name"] == \
            c["name"]


def test_peaks_are_keyed_by_device_kind():
    assert harness.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        harness.peaks("cpu")


# the nemotron-4-15b pipeline stage: 8 layers, d 6144, 48 q / 8 kv heads
# of 128, d_ff 24576, 64000 vocabulary rows
STAGE = {"num_layers": 8, "d_model": 6144, "num_heads": 48,
         "num_kv_heads": 8, "head_dim": 128, "d_ff": 24576,
         "vocab_size": 64000}


def test_costs_match_hand_counts():
    # q and o: 2 * 6144 * 48 * 128; k and v: 2 * 6144 * 8 * 128;
    # the MLP's two matrices: 2 * 6144 * 24576
    assert dense_lm.layer_params(STAGE) == \
        75_497_472 + 12_582_912 + 301_989_888
    # a 1024-token prefill: 2 * 8 layers * 390,070,272 * 1024 tokens,
    # attention 4 * 8 * 48 * 128 * (1024 * 1025 / 2) keys, one head row
    assert dense_lm.prefill_flops(STAGE, 1024) == \
        6_390_911_336_448 + 103_179_878_400 + 786_432_000
    # a decode step of 48 slots at position 999 each (1000 keys)
    assert dense_lm.decode_flops(STAGE, 48, 48_000) == \
        299_573_968_896 + 9_437_184_000 + 37_748_736_000
    # decode bytes of the same step: the layers and the head in bf16, 17
    # norm scales in f32, 48 embedding rows, the 48,000 keys' keys and
    # values read (2 * 8 layers * 1024 * 2 bytes each) and one token's
    # keys and values written per slot
    assert dense_lm.decode_bytes(STAGE, 48, 48_000) == \
        7_027_556_352 + 417_792 + 589_824 + 1_572_864_000 + 1_572_864


def test_run_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, str(harness.HERE / "run.py"), "--workload",
         "serve.chat-decode", "--seed", str(2**33 + 1), "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, env=env,
        cwd=harness.ROOT, timeout=300)
    assert r.returncode != 0
    assert not any(line.lstrip().startswith("{")
                   for line in r.stdout.splitlines())
