"""The chip smoke at smoke size on CPU, its refusal of a CPU backend, and
the train CLI's depth cut that it relies on."""
import math
import os
import subprocess
import sys

import pytest

from repro.configs import get_config
from repro.launch import train as train_mod

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
import chip_smoke  # noqa: E402


def test_phase_trains_smoke_variant_and_passes_its_checks(capsys):
    summary = chip_smoke.run_phase(variant="smoke", layers=None)
    out = capsys.readouterr().out
    assert "[train] done" in out
    assert summary["nodes"] == 4
    assert summary["layers"] == get_config("stablelm-1.6b", "smoke").n_layers
    assert summary["steps"] == chip_smoke.STEPS
    assert chip_smoke.check(summary) == []
    assert abs(summary["first_loss"] - math.log(summary["vocab"])) <= 1.0


def test_check_flags_bad_losses():
    good = {"steps": chip_smoke.STEPS, "vocab": 100352,
            "first_loss": math.log(100352), "last_loss": 9.0}
    assert chip_smoke.check(good) == []
    assert len(chip_smoke.check({**good, "first_loss": 3.0})) == 1
    assert len(chip_smoke.check({**good, "first_loss": float("nan")})) == 1
    assert len(chip_smoke.check({**good, "last_loss": float("inf")})) == 1
    assert len(chip_smoke.check({**good, "steps": 3})) == 1


def test_main_refuses_a_cpu_backend():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)  # the script finds src/ itself
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chip_smoke.py")],
        env=env, capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode != 0
    assert "needs a TPU" in proc.stderr
    assert '"ok"' not in proc.stdout


def test_layers_cuts_depth_and_keeps_published_widths():
    args = train_mod.parse_args(
        ["--arch", "stablelm-1.6b", "--variant", "full", "--layers", "1"])
    full = get_config("stablelm-1.6b", "full")
    cut = train_mod.model_config(args)
    assert cut.n_layers == 1 and full.n_layers == 24
    assert cut == full.replace(n_layers=1)  # nothing else moved
    assert (cut.d_model, cut.n_heads, cut.head_dim, cut.d_ff, cut.vocab,
            cut.dtype) == (2048, 32, 64, 5632, 100352, "bfloat16")
    no_cut = train_mod.parse_args(["--arch", "stablelm-1.6b", "--variant", "full"])
    assert train_mod.model_config(no_cut) == full


@pytest.mark.parametrize("argv", [
    ["--variant", "smoke", "--layers", "1"],
    ["--variant", "full", "--layers", "0"],
])
def test_layers_rejects_smoke_variant_and_empty_depth(argv):
    with pytest.raises(SystemExit):
        train_mod.parse_args(["--arch", "stablelm-1.6b"] + argv)
