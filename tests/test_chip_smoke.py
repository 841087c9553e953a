"""The chip smoke script off the chip: it refuses to run on the CPU, and its
phases pass at tiny sizes (kernels interpreted, no chip needed)."""
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

import chip_smoke
from repro import configs
from repro.mcmc import nuts

ROOT = Path(__file__).resolve().parent.parent
TINY = nuts.NutsSettings(max_tree_depth=5, num_steps=2, steps_per_leaf=4)


def test_exits_nonzero_without_verdict_on_cpu():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    proc = subprocess.run(
        [sys.executable, str(ROOT / "chip_smoke.py")],
        env=env, capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    assert "default backend is TPU" in proc.stderr


def test_check_raises_on_failure():
    with pytest.raises(chip_smoke.SmokeFailure, match="nope"):
        chip_smoke.check(False, "nope")


def test_nuts_phase_tiny():
    out = chip_smoke.nuts_phase(
        0, chip_smoke.CompileClock(), chains=32, num_data=200, dim=5,
        settings=TINY, eps=0.05,
    )
    assert out["vm_steps"] > 0
    assert out["max_z"] <= chip_smoke.MEAN_TOL_SE


def test_sharded_phase_tiny():
    out = chip_smoke.sharded_phase(
        0, 4, chains=32, num_data=200, dim=10, settings=TINY, eps=0.05,
    )
    assert out["max_z"] <= chip_smoke.MEAN_TOL_SE


def test_serve_phase_smoke_width():
    out = chip_smoke.serve_phase(
        0, chip_smoke.CompileClock(),
        cfg=configs.get_smoke_config("smollm-135m"), lanes=2,
        max_context=16, max_prompt_len=4, max_new_tokens=3, num_requests=4,
        min_prompt_len=1,
    )
    assert out["tokens"] > 0
