"""Launcher regressions: seed-dependent batch stream, realized wire-bit
accounting across checkpoint resume, the tree-partitioned end-to-end
smoke on a >=1M-param registered model, and PaME rounds for the hybrid
and MoE/MLA families.
"""
import json
import os
import re
import zlib

import numpy as np
import pytest

from repro.launch import train as train_mod


def _build_args(extra):
    base = ["--arch", "stablelm-1.6b", "--variant", "smoke",
            "--batch", "2", "--seq", "16", "--nodes", "2", "--steps", "1"]
    return train_mod.make_parser().parse_args(base + extra)


def test_batch_stream_rng_depends_on_seed_and_step():
    a = train_mod.batch_stream_rng(0, 0).integers(0, 1 << 30, 8)
    b = train_mod.batch_stream_rng(1, 0).integers(0, 1 << 30, 8)
    c = train_mod.batch_stream_rng(0, 1).integers(0, 1 << 30, 8)
    a2 = train_mod.batch_stream_rng(0, 0).integers(0, 1 << 30, 8)
    assert not np.array_equal(a, b)  # the --seed used to be ignored here
    assert not np.array_equal(a, c)
    np.testing.assert_array_equal(a, a2)


def test_make_batch_windows_differ_across_seeds():
    _, _, _, mb0, _, _ = train_mod.build_everything(_build_args(["--seed", "0"]))
    _, _, _, mb0b, _, _ = train_mod.build_everything(_build_args(["--seed", "0"]))
    _, _, _, mb1, _, _ = train_mod.build_everything(_build_args(["--seed", "1"]))
    t0 = np.asarray(mb0(0)["tokens"])
    t0b = np.asarray(mb0b(0)["tokens"])
    t1 = np.asarray(mb1(0)["tokens"])
    np.testing.assert_array_equal(t0, t0b)   # same seed reproduces
    assert not np.array_equal(t0, t1)        # different seed, different windows


# ---------------------------------------------------------------------------
# resume accounting
# ---------------------------------------------------------------------------
TRAIN_ARGS = ["--arch", "stablelm-1.6b", "--variant", "smoke",
              "--batch", "2", "--seq", "32", "--nodes", "4",
              "--chunk", "2", "--log-every", "2"]


def _run_main(capsys, extra):
    train_mod.main(TRAIN_ARGS + extra)
    return capsys.readouterr().out


def _wire_gbits(out, step):
    m = re.search(rf"step={step} .*wire_gbits=([0-9.]+)", out)
    assert m, out
    return float(m.group(1))


def _manifest(ckpt_dir, step):
    step_dir = os.path.join(ckpt_dir, f"step_{step:09d}")
    with open(os.path.join(step_dir, "manifest.json")) as f:
        return step_dir, json.load(f)


def test_resume_restores_realized_cumulative_wire_bits(tmp_path, capsys):
    ckpt = str(tmp_path / "ckpt")
    out = _run_main(capsys, ["--steps", "4", "--edge-drop", "0.5",
                             "--ckpt-dir", ckpt, "--ckpt-every", "2"])
    static_per_step = float(
        re.search(r"wire_bits/step=([0-9.e+]+)", out).group(1))
    logged = _wire_gbits(out, 4)

    step_dir, manifest = _manifest(ckpt, 4)
    # payload {"cum_bits": ..., "state": ...} flattens with cum_bits first
    cum_meta = manifest["leaves"][0]
    assert "cum_bits" in cum_meta["file"]
    saved = float(np.load(os.path.join(step_dir, cum_meta["file"])))
    assert saved / 1e9 == pytest.approx(logged, abs=2e-4)
    # the pre-fix formula (static full-graph rate x steps) over-charges a
    # run whose edges were dropping half the time
    assert saved != pytest.approx(static_per_step * 4, rel=1e-3)

    # tamper the persisted counter with a sentinel (and fix the crc): a
    # resumed run must CONTINUE from it, proving the restore reads the leaf
    sentinel = np.asarray(2.0e9, np.float64)
    np.save(os.path.join(step_dir, cum_meta["file"]), sentinel)
    cum_meta["crc32"] = zlib.crc32(
        np.ascontiguousarray(sentinel).tobytes()) & 0xFFFFFFFF
    with open(os.path.join(step_dir, "manifest.json"), "w") as f:
        json.dump(manifest, f)
    out2 = _run_main(capsys, ["--steps", "6", "--edge-drop", "0.5",
                              "--ckpt-dir", ckpt, "--ckpt-every", "2"])
    assert "resumed from step 4" in out2
    resumed = _wire_gbits(out2, 6)
    assert 2.0 <= resumed <= 2.0 + 4 * static_per_step / 1e9


def test_resume_accepts_legacy_checkpoint_without_cum_bits(tmp_path, capsys):
    ckpt = str(tmp_path / "ckpt")
    _run_main(capsys, ["--steps", "4", "--edge-drop", "0.5",
                       "--ckpt-dir", ckpt, "--ckpt-every", "2"])
    for step in (2, 4):
        step_dir, manifest = _manifest(ckpt, step)
        cum_meta = manifest["leaves"].pop(0)
        assert "cum_bits" in cum_meta["file"]
        os.remove(os.path.join(step_dir, cum_meta["file"]))
        with open(os.path.join(step_dir, "manifest.json"), "w") as f:
            json.dump(manifest, f)
    out = _run_main(capsys, ["--steps", "6", "--edge-drop", "0.5",
                             "--ckpt-dir", ckpt, "--ckpt-every", "2"])
    # falls back to the static estimate instead of crashing
    assert "resumed from step 4" in out
    assert "[train] done" in out


# ---------------------------------------------------------------------------
# tree partition end-to-end on a real (>=1M-param) registered model
# ---------------------------------------------------------------------------
def test_partition_tree_trains_stablelm_smoke(capsys):
    out = _run_main(capsys, ["--steps", "8", "--chunk", "4",
                             "--partition", "tree", "--sigma0", "50"])
    assert "partition=tree" in out
    n_params = float(re.search(r"params=([0-9.]+)M", out).group(1))
    assert n_params >= 1.0
    losses = [float(x) for x in re.findall(r"loss=([0-9.]+)", out)]
    assert losses and all(np.isfinite(losses))
    assert losses[-1] < losses[0]
    assert "[train] done" in out


# ---------------------------------------------------------------------------
# a PaME round through the CLI for the hybrid and the MoE/MLA families
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("arch", ["zamba2-1.2b", "deepseek-v2-lite-16b"])
def test_pame_round_runs_for_arch_family(arch, capsys):
    out = train_mod.main(["--arch", arch, "--variant", "smoke",
                          "--steps", "2", "--chunk", "2", "--nodes", "4",
                          "--batch", "2", "--seq", "32", "--mixing", "dense"])
    log = capsys.readouterr().out
    assert "algo=pame mixing=dense" in log and "[train] done" in log
    assert out["loss"].shape == (2,)
    assert np.all(np.isfinite(out["loss"]))
