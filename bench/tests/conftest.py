"""Fixtures of the benchmark's CPU tests: a copy of the benchmark's files
with a cell at smoke widths, and a stand-in for the chip check."""
import json
import os
import shutil
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
for path in (REPO, os.path.join(REPO, "src")):
    if path not in sys.path:
        sys.path.insert(0, path)

from bench import cells, harness  # noqa: E402

SMOKE_MODELS = {
    "dense_lm": {
        "config": "stablelm-1.6b_m4_l2", "traffic": "pame.b8x128",
        "model": {"d_model": 256, "n_heads": 4, "n_kv_heads": 4, "head_dim": 64,
                  "d_ff": 512, "n_layers": 2, "vocab": 512, "rope_theta": 10000.0,
                  "tie_embeddings": True, "dtype": "float32"},
    },
    "mamba2": {
        "config": "mamba2-1.3b_m4_l4", "traffic": "pame.b2x512",
        "model": {"d_model": 256, "n_layers": 2, "vocab": 512, "ssm_state": 32,
                  "ssm_expand": 2, "ssm_head_dim": 32, "ssm_groups": 1,
                  "ssm_chunk": 16, "d_conv": 4, "tie_embeddings": True,
                  "dtype": "float32"},
    },
}
SMOKE_TRAFFIC = {"batch": 2, "seq": 32}
# The smoke widths run in float32, as the reference computes: sound runs
# read under 1e-6 in loss on the CPU, so a thousandth leaves three orders
# of room and every planted fault and the control read above it on
# ``loss_gap`` (0.0026 and more).  At these widths the local steps move the parameters 0.16 of
# what the exchange does (``exchange_gap``); the exchange left out reads 1.
SMOKE_LIMITS = {"first_loss_gap": {"limit": 1e-3}, "loss_gap": {"limit": 1e-3},
                "exchange_gap": {"limit": 0.5}, "comm_mismatch": {"limit": 0}}
PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11, "hbm_bytes": 1e9}


def smoke_suite(root: str, family: str = "dense_lm") -> cells.Suite:
    """The benchmark's files copied under ``root`` with one more cell,
    ``smoke.<family>``: the repository's smoke-width model of that family
    (float32) under its full-size configuration's traffic at a smaller
    batch, with that configuration's control, held to ``SMOKE_LIMITS``."""
    spec_model = SMOKE_MODELS[family]
    bench = os.path.join(root, "bench")
    shutil.copytree(os.path.join(REPO, "bench"), bench,
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        spec = json.load(f)
    with open(os.path.join(bench, "configs", f"{spec_model['config']}.json")) as f:
        config = json.load(f)
    config.update(name=f"smoke-{family}", variant="smoke", model=spec_model["model"])
    with open(os.path.join(bench, "traffic", f"{spec_model['traffic']}.json")) as f:
        traffic = json.load(f)
    traffic.update(name="smoke", **SMOKE_TRAFFIC)
    name = f"smoke.{family}"
    for kind, data_name, data in (("configs", config["name"], config),
                                  ("traffic", f"smoke-{family}", traffic)):
        with open(os.path.join(bench, kind, f"{data_name}.json"), "w") as f:
            json.dump(data, f)
    with open(os.path.join(bench, "limits", f"{name}.json"), "w") as f:
        json.dump({"workload": name, "limits": SMOKE_LIMITS}, f)
    spec["workloads"].append({"name": name, "config": config["name"],
                              "traffic": f"smoke-{family}", "chips": 1, "why": "CPU test"})
    spec_path = os.path.join(root, "BENCHMARK.json")
    with open(spec_path, "w") as f:
        json.dump(spec, f)
    return cells.Suite(root=bench, spec_path=spec_path)


@pytest.fixture
def cpu_chip(monkeypatch):
    """The chip check answers with the CPU and made-up peaks."""
    import jax

    monkeypatch.setattr(harness, "require_devices",
                        lambda chips: (jax.devices()[:chips], dict(PEAKS)))
