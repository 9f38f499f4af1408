"""Bytes-moved / FLOP roofline terms for the gossip contraction race.

Hardware: TPU v5e — 197 TFLOP/s bf16, 819 GB/s HBM.  `bench_gossip`
prices each `gather_terms` implementation with `gossip_roofline`.
"""
from __future__ import annotations

from typing import Optional

PEAK_FLOPS = 197e12      # bf16 per chip
HBM_BW = 819e9           # bytes/s per chip


def gossip_roofline(
    m: int,
    k: int,
    n: int,
    impl: str,
    *,
    n_terms: int = 1,
    itemsize: int = 4,
    block_m: int = 128,
    measured_us: Optional[float] = None,
) -> dict:
    """Bytes-moved / FLOP roofline terms for one `gather_terms` call.

    One call contracts `n_terms` ([m, k] weight, [m, n] operand) pairs
    over the padded neighbor table.  Per impl the HBM traffic models are:

      * slots  — k fused gather+fma passes: the operand is gathered once
        per slot (k·m·n reads), the accumulator lives in registers and is
        written once (m·n), plus the table+weights (m·k ids and floats).
      * segsum — gather to an [m·k, n] edge-value intermediate (k·m·n
        read + k·m·n write), then segment-sum reads it back and writes
        m·n.
      * pallas — the fused kernel: the operand streams through VMEM once
        per receiver-row tile (ceil(m/block_m)·m·n reads — 1 when
        m ≤ block_m), output written once; the scatter matrix never
        touches HBM.

    FLOPs: 2·k·m·n multiply-adds per term for slots/segsum; the kernel
    trades them for a dense-matrix build + MXU contraction,
    2·k·m²·(n/bn tiles) + 2·m²·n — more raw FLOPs, but on the matrix
    unit with minimal HBM traffic, which is the bet the race measures.
    """
    table_bytes = m * k * (4 + 4 * n_terms)  # int32 ids + f32 weights
    op = m * n * itemsize
    if impl == "slots":
        hbm = n_terms * (k * op + op) + table_bytes
        flops = n_terms * 2.0 * k * m * n
    elif impl == "segsum":
        hbm = n_terms * (3 * k * op + op) + table_bytes
        flops = n_terms * 2.0 * k * m * n
    elif impl == "pallas":
        row_tiles = -(-m // min(block_m, m))
        hbm = n_terms * (row_tiles * op + op) + table_bytes
        flops = 2.0 * k * m * m * row_tiles + n_terms * 2.0 * m * m * n
    else:
        raise ValueError(f"unknown gossip impl {impl!r}")
    row = {
        "impl": impl,
        "m": m, "k": k, "n": n, "n_terms": n_terms,
        "hbm_bytes": float(hbm),
        "flops": float(flops),
        "t_memory_s": hbm / HBM_BW,
        "t_compute_s": flops / PEAK_FLOPS,
        "intensity_flop_per_byte": flops / hbm,
    }
    if measured_us is not None:
        row["us"] = measured_us
        row["achieved_gbps"] = hbm / (measured_us * 1e-6) / 1e9
    return row
