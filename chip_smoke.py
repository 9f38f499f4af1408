"""Chip smoke: PaME training at stablelm-1.6b widths on one TPU chip.

    python chip_smoke.py

Drives the trainer's main path once through its own entry point,
`repro.launch.train.main` (registry `bind` -> scan engine -> PaME), at
stablelm-1.6b's published widths (d_model 2048, 32 heads x 64, d_ff 5632,
vocab 100352, bf16) with random weights from a seed.  The depth is cut
with `--layers` so that m full node replicas fit one chip; the setting
below was sized by compiling one scan chunk of the bound step for a v5e
and reading its `memory_analysis()`.

It checks that the loss of the first step is finite and within 1.0 of
ln(vocab), the loss of random weights, and that the last loss is finite.
The lines it prints before the last are smoke output, not benchmark
numbers: the wall time includes compilation.  The last line is one JSON
object naming the device.  Without a TPU it exits non-zero and prints no
result; there is no CPU fallback.
"""
from __future__ import annotations

import json
import math
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

ARCH = "stablelm-1.6b"
# m = 4 nodes at 2 layers with the dense node-axis contraction compiles to
# 10.8 GB on a v5e; the sparse contraction's f32 [m*deg, 100352, 2048] edge
# intermediate does not fit at any depth with m = 4.
NODES, LAYERS, MIXING = 4, 2, "dense"
STEPS, CHUNK = 8, 4
# the first loss of random weights sits near ln(vocab)
LOSS_SLACK = 1.0


def run_phase(variant: str = "full", layers: int | None = LAYERS) -> dict:
    """Train through `repro.launch.train.main` and summarize the run."""
    import numpy as np

    from repro.launch import train

    argv = ["--arch", ARCH, "--variant", variant, "--nodes", str(NODES),
            "--mixing", MIXING, "--algo", "pame",
            "--steps", str(STEPS), "--chunk", str(CHUNK)]
    if layers is not None:
        argv += ["--layers", str(layers)]
    cfg = train.model_config(train.parse_args(argv))
    t0 = time.perf_counter()
    out = train.main(argv)
    wall_s = time.perf_counter() - t0
    loss = np.asarray(out["loss"], np.float64)
    return {
        "nodes": NODES, "layers": cfg.n_layers, "vocab": cfg.vocab,
        "n_params": out["n_params"], "steps": int(loss.shape[0]),
        "first_loss": float(loss[0]), "last_loss": float(loss[-1]),
        "wall_s": wall_s,
    }


def check(summary: dict) -> list:
    """The smoke's correctness conditions; returns the failures."""
    failures = []
    if summary["steps"] != STEPS:
        failures.append(f"ran {summary['steps']} steps, expected {STEPS}")
    first, last = summary["first_loss"], summary["last_loss"]
    random_loss = math.log(summary["vocab"])
    if not (math.isfinite(first) and abs(first - random_loss) <= LOSS_SLACK):
        failures.append(
            f"first loss {first} is not within {LOSS_SLACK} of "
            f"ln(vocab) = {random_loss}"
        )
    if not math.isfinite(last):
        failures.append(f"last loss {last} is not finite")
    return failures


def main() -> int:
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip smoke needs a TPU; JAX found {devices[0].platform}",
              file=sys.stderr)
        return 1
    summary = run_phase()
    stats = devices[0].memory_stats() or {}
    print("[smoke] output of a smoke run, not benchmark numbers")
    print(f"[smoke] device_kind={devices[0].device_kind}")
    print(f"[smoke] nodes={summary['nodes']} layers={summary['layers']} "
          f"params_per_node={summary['n_params']}")
    # a program's temporaries are reserved apart from the buffers in use
    print(f"[smoke] peak_bytes_in_use={stats.get('peak_bytes_in_use')} "
          f"peak_bytes_reserved={stats.get('peak_bytes_reserved')}")
    print(f"[smoke] first_loss={summary['first_loss']!r} "
          f"last_loss={summary['last_loss']!r} steps={summary['steps']}")
    print(f"[smoke] wall_s={summary['wall_s']!r} (compilation included)")
    failures = check(summary)
    if failures:
        for f in failures:
            print(f"[smoke] FAIL {f}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
