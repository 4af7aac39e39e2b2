"""``chip_smoke.py`` off the chip: its serving check at a tiny width, and
its refusal to run without a TPU."""
import json
import os
import pathlib
import subprocess
import sys

import pytest

import chip_smoke
from repro.configs import reduced_config

ROOT = pathlib.Path(chip_smoke.__file__).resolve().parent


@pytest.mark.parametrize("dtype,bound", [("bfloat16", 5e-2),
                                         ("float32", 1e-4)])
def test_serving_check_separates_drift_from_faults(dtype, bound):
    """The prefill-vs-decode gap is rounding: it stays under the chip's
    bf16 limit, collapses in float32, and a changed token (the control)
    lands far outside either."""
    check = chip_smoke.Check()
    chip_smoke.phase_serving(check, cfg=reduced_config("gemma2-2b"),
                             batch=2, prompt_len=24, cache_len=40, steps=4,
                             dtype=dtype)
    assert not check.get("failed"), check
    assert max(check["logits_rel_l2_at_step"].values()) <= bound, check
    assert check["control_rel_l2"] > 0.2, check


def test_no_tpu_exits_nonzero_without_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")],
                         env=env, capture_output=True, text=True,
                         timeout=120)
    assert out.returncode != 0
    for line in out.stdout.splitlines():
        assert "device" not in json.loads(line)
