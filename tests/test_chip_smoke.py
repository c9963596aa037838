"""chip_smoke.py's phases at smoke size on the CPU, and its refusal to
report a result without a TPU."""
import importlib.util
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.data.tasks import Tokenizer

SCRIPT = Path(__file__).resolve().parents[1] / "chip_smoke.py"


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def cfg():
    return get_smoke_config("qwen-distill-1.5b").replace(
        vocab=Tokenizer().vocab_size)


def test_serve_and_kernel_phases(chip_smoke, cfg):
    params, rollouts = chip_smoke.serve_phase(cfg, n_prompts=2, group=2,
                                              new_tokens=8)
    assert len(rollouts) == 4
    assert all(len(r.completion_ids) == 8 for r in rollouts)
    chip_smoke.kernel_phase(cfg, params, rollouts, on_chip=False,
                            n_prompts=2, group=2, new_tokens=8)


def test_train_phase(chip_smoke, cfg):
    history = chip_smoke.train_phase(cfg, steps=3)
    assert [m["step"] for m in history] == [1, 2, 3]


def test_teacher_forced_check_catches_a_shifted_position(chip_smoke, cfg):
    params, rollouts = chip_smoke.serve_phase(cfg, n_prompts=2, group=1,
                                              new_tokens=8)
    for r in rollouts:
        r.behavior_logp = np.roll(r.behavior_logp, -1)
    gap = chip_smoke.teacher_forced_gap(cfg, params, rollouts)
    with pytest.raises(chip_smoke.SmokeFailure):
        chip_smoke._require_close(gap, "shifted by one position")


def test_refuses_to_run_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, str(SCRIPT)], capture_output=True,
                         text=True, env=env, timeout=300)
    assert out.returncode != 0
    assert '"ok": true' not in out.stdout
