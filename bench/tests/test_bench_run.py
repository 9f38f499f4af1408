"""Whole runs of the harness at smoke widths on the CPU, through its own
functions with only the chip check answered by the test, and the runs
that must refuse: no TPU, and a directory holding only the benchmark."""
import json
import os
import shutil
import subprocess
import sys
import time

from bench import harness
from conftest import REPO, smoke_suite

SEED = 2**31 + 11


def run(suite, workload, capsys, trace=0):
    rc = harness.main(["--workload", workload, "--seed", str(SEED), "--seconds", "1",
                       "--trace", str(trace)], t0=time.perf_counter(), suite=suite)
    out = capsys.readouterr()
    return rc, json.loads(out.out.strip().splitlines()[-1]), out.err.strip().splitlines()


def test_a_sound_run_is_correct_and_reports_end_to_end(tmp_path, cpu_chip, capsys):
    rc, result, err = run(smoke_suite(str(tmp_path)), "smoke.dense_lm", capsys)
    assert rc == 0 and result["correct"] is True and result["failed"] == 0
    assert set(result["metrics"]) == {"tokens_per_s", "peak_hbm_gb", "setup_s"}
    assert result["metrics"]["tokens_per_s"]["value"] > 0
    assert result["attempted"] > 0 and result["attempted"] % 16 == 0
    assert list(result)[-1] == "checks"
    assert set(result["checks"]) == {"first_loss_gap", "loss_gap", "exchange_gap", "comm_mismatch"}
    assert all(line.startswith("[bench] check ") for line in err[-4:])


def test_a_traced_run_reports_the_layers_it_can_read(tmp_path, cpu_chip, capsys):
    rc, result, _ = run(smoke_suite(str(tmp_path), "mamba2"), "smoke.mamba2", capsys, trace=1)
    assert rc == 0 and result["correct"] is True
    metrics = result["metrics"]
    # the CPU has no device plane: the device readers find nothing to read
    assert set(metrics) == {"host_batch_ms", "compiles_in_window", "mfu"}
    assert metrics["compiles_in_window"]["value"] == 0
    assert result["device"]["window_s"] > 0 and "breakdown" in result


def bench_cmd(root):
    return [sys.executable, os.path.join(root, "bench", "run.py"), "--workload",
            "stablelm-1.6b.pame.tok1k", "--seed", "1", "--seconds", "1"]


def test_without_a_tpu_the_run_exits_nonzero_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(bench_cmd(REPO), capture_output=True, text=True, env=env,
                          timeout=300, cwd=REPO)
    assert proc.returncode == 1
    assert proc.stdout.strip() == ""
    assert "needs" in proc.stderr or "runs on a TPU" in proc.stderr


def test_the_benchmark_alone_exits_nonzero_and_prints_no_result(tmp_path):
    shutil.copytree(os.path.join(REPO, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path / "BENCHMARK.json")
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(bench_cmd(str(tmp_path)), capture_output=True, text=True,
                          env=env, timeout=300, cwd=tmp_path)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""

