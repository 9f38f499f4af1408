"""The yardstick's arithmetic against hand counts: model FLOPs per token,
the exchange's least bytes and the table of peaks."""
import os

import pytest

from bench import cells, peaks

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
suite = cells.Suite()

STABLELM = {"d_model": 2048, "n_heads": 32, "n_kv_heads": 32, "head_dim": 64,
            "d_ff": 5632, "n_layers": 2, "vocab": 100352}
MAMBA2 = {"d_model": 2048, "n_layers": 4, "vocab": 50280, "ssm_state": 128,
          "ssm_expand": 2, "ssm_head_dim": 64, "ssm_groups": 1, "ssm_chunk": 128,
          "d_conv": 4}


def test_dense_flops_per_token_at_stablelm_widths():
    flops = suite.module("flops", "dense_lm")
    # q, k, v, o: 4 x 2048^2; SwiGLU: 3 x 2048 x 5632; two layers; tied head 100352 x 2048
    weights = 2 * (4 * 2048 * 2048 + 3 * 2048 * 5632) + 100352 * 2048
    assert flops.matmul_weights(STABLELM) == weights == 308_281_344
    # forward: 2 per weight + scores and values over 128 x 128 for 32 heads of 64, two layers
    forward = 2 * weights + 2 * 4 * 128 * 32 * 64
    assert flops.flops_per_token(STABLELM, 128) == 3 * forward == 1_855_979_520


def test_ssd_flops_per_token_at_mamba2_widths():
    flops = suite.module("flops", "mamba2")
    proj = 2048 * (2 * 4096 + 2 * 128 + 64) + 4096 * 2048   # in_proj + out_proj
    conv = 4 * (4096 + 2 * 128)
    ssd = 128 * 128 + 128 * 64 * 64 + 2 * 64 * 64 * 128    # C.B, weighted values, state in and out
    forward = 4 * 2 * (proj + conv + ssd) + 2 * 50280 * 2048
    assert flops.flops_per_token(MAMBA2, 512) == 3 * forward == 1_276_108_800
    # short sequences shrink the chunk to the sequence
    short = dict(MAMBA2, n_layers=1, vocab=0)
    assert flops.flops_per_token(short, 64) == 3 * 2 * (
        proj + conv + 64 * 128 + 64 * 64 * 64 + 2 * 64 * 64 * 128)


def test_exchange_least_bytes():
    roofline = suite.module("layers", "exchange_roofline")
    replica = 308_291_584 * 2  # bf16 bytes of one stablelm node
    # read and write 4 replicas, plus one message of 20% of a replica
    assert roofline.least_bytes(replica, 4, 0.2, 1.0) == pytest.approx(
        8 * 616_583_168 + 0.2 * 616_583_168)
    assert roofline.least_bytes(replica, 4, 0.2, 0.0) == 4_932_665_344


def test_peaks_are_keyed_by_device_kind():
    v5e = peaks.peaks_for("TPU v5 lite")
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    assert "TPU v5e" in peaks.SOURCE
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peaks_for("TPU v4")
    with pytest.raises(KeyError):
        peaks.peaks_for("cpu")
