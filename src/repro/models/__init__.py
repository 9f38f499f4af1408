"""Model substrate: composable decoder stacks in pure JAX.

Families: dense GQA (opt. qk-norm / sliding window), MLA (DeepSeek-V2),
MoE (shared + routed top-k), Mamba2 SSD, hybrid (Mamba2 + shared attention),
VLM / audio backbones (frontends stubbed per spec).
"""
from repro.models.config import ModelConfig  # noqa: F401
from repro.models.model import (  # noqa: F401
    init_params,
    train_loss,
    train_loss_counted,
    prefill,
    decode_step,
    init_cache,
)
