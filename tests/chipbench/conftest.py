"""A tiny serving cell, for runs on the CPU."""
import pathlib
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from chipbench import harness  # noqa: E402

# a limit for the tiny serving cell, between its sound readings (0 to
# 0.034 on the CPU, seeds 1 to 8) and its control's (0.174 to 0.513);
# every fault reads above 1
TINY_GAP_LIMIT = 0.12


def _spec(cell, config, traffic, limits, kind):
    return {"cell": {"name": cell, "chips": 1}, "config": config,
            "traffic": traffic, "limits": limits,
            "driver": harness.HERE / "drivers" / f"{kind}.py",
            "end_to_end": [], "per_layer": []}


@pytest.fixture
def gap_limit():
    return TINY_GAP_LIMIT


@pytest.fixture
def serve_spec():
    cfg = dict(harness.load_json(
        harness.HERE / "configs/nemotron-4-15b-pp4stage.json"),
        num_layers=2, d_model=64, num_heads=4, num_kv_heads=2,
        head_dim=16, d_ff=128, vocab_size=512)
    mix = dict(harness.load_json(harness.HERE / "traffic/chat-decode.json"),
               slots=4, cache_len=64, rate_per_s=16.0, warm_slots=4,
               sample={"requests": 8},
               prompt_len={"kind": "lognormal", "median": 12, "sigma": 0.3,
                           "round_to": 8, "min": 8, "max": 16},
               output_len={"kind": "lognormal", "median": 16, "sigma": 0.6,
                           "min": 4, "max": 40})
    return _spec("serve.tiny", cfg, mix, {"widest_gap": TINY_GAP_LIMIT},
                 "serve")
