"""Pallas TPU kernels for the compute hot spots.

  pme_average     — the paper's PME count-weighted masked average, fused
                    (mask-mul + two MXU matmuls + divide + self-fill), and
                    its bernoulli form, which draws the selected senders'
                    masks in the kernel;
  flash_attention — blockwise causal GQA attention (opt. sliding window);
  ssd_scan        — Mamba2 SSD intra-chunk contraction.

Each subpackage: `kernel.py` (pl.pallas_call + BlockSpec VMEM tiling),
`ops.py` (jit'd public wrapper; interpret=True on CPU), `ref.py` (pure-jnp
oracle used by the allclose test sweeps).
"""
