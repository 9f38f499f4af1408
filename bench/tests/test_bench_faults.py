"""``correct`` must come out false when the timed path is broken
underneath (each fault a training cell can have), and for the control:
the reference computed in the configuration's lower precision in the
program's place.  At smoke widths, held to the smoke cell's limits."""
import importlib
import json
import time

import pytest

from bench import calibrate, check, harness
from conftest import smoke_suite

SEED = 2**31 + 23


def _unchanged(orig):
    def step(state, *args, **kwargs):
        return state, orig(state, *args, **kwargs)[1]
    return step


def _half_batch(orig):
    def loss(params, cfg, batch):
        rows = batch["tokens"].shape[0] // 2
        return orig(params, cfg, dict(batch, tokens=batch["tokens"][:rows]))
    return loss


def _no_exchange(orig):
    def average(key, params, *args, **kwargs):
        return params
    return average


FAULTS = {
    "state_unchanged": ("repro.core.pame", "pame_step", _unchanged),
    "half_batch": ("repro.launch.train", "train_loss", _half_batch),
    "no_exchange": ("repro.core.pme", "pme_average_pytree", _no_exchange),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_broken_timed_path_is_not_correct(fault, tmp_path, cpu_chip, capsys, monkeypatch):
    module, attr, wrap = FAULTS[fault]
    mod = importlib.import_module(module)
    monkeypatch.setattr(mod, attr, wrap(getattr(mod, attr)))
    suite = smoke_suite(str(tmp_path))
    rc = harness.main(["--workload", "smoke.dense_lm", "--seed", str(SEED), "--seconds", "1"],
                      t0=time.perf_counter(), suite=suite)
    result = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0
    assert result["correct"] is False
    assert any(row["value"] > row["limit"] for row in result["checks"].values())


@pytest.mark.parametrize("family", ["dense_lm", "mamba2"])
def test_the_control_and_planted_faults_fail_and_the_program_passes(family, tmp_path):
    suite = smoke_suite(str(tmp_path), family)
    workload = f"smoke.{family}"
    limits = suite.cell(workload).limits["limits"]
    lines = []
    calibrate.calibrate(suite, workload, [SEED], 1, emit=lines.append)
    verdicts = {row["kind"]: check.verdict(row["readings"], limits)[0]
                for row in map(json.loads, lines)}
    # the smoke widths run in float32, so the float32 witness is the reference
    assert verdicts == {"program": True, "control": False, "half_batch": False,
                        "no_exchange": False, "float32": True}
