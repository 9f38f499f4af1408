"""Architecture registry: the 10 assigned archs (+ the paper's own models)
and the chip shares of them that a deployment divides over chips.

Every module exposes FULL (exact assigned config) and SMOKE (reduced:
<=2 layers, d_model <= 512, <=4 experts) ModelConfigs.  `get_config(name,
variant)` is the single lookup used by the launcher, benchmarks and tests.
"""
from __future__ import annotations

import importlib
from typing import List

from repro.models.config import ModelConfig

ARCH_IDS: List[str] = [
    "mamba2_1p3b",
    "minitron_4b",
    "yi_34b",
    "deepseek_v2_236b",
    "zamba2_1p2b",
    "stablelm_1p6b",
    "internvl2_2b",
    "musicgen_large",
    "deepseek_v2_lite_16b",
    "qwen3_14b",
    "deepseek_v2_lite_16b_ep8",
]

# CLI aliases (the assignment's spelling) -> module names
ALIASES = {
    "mamba2-1.3b": "mamba2_1p3b",
    "minitron-4b": "minitron_4b",
    "yi-34b": "yi_34b",
    "deepseek-v2-236b": "deepseek_v2_236b",
    "zamba2-1.2b": "zamba2_1p2b",
    "stablelm-1.6b": "stablelm_1p6b",
    "internvl2-2b": "internvl2_2b",
    "musicgen-large": "musicgen_large",
    "deepseek-v2-lite-16b": "deepseek_v2_lite_16b",
    "qwen3-14b": "qwen3_14b",
    "deepseek-v2-lite-16b-ep8": "deepseek_v2_lite_16b_ep8",
}


def canonical(name: str) -> str:
    return ALIASES.get(name, name.replace("-", "_").replace(".", "p"))


def get_config(name: str, variant: str = "full") -> ModelConfig:
    mod = importlib.import_module(f"repro.configs.{canonical(name)}")
    cfg = {"full": mod.FULL, "smoke": mod.SMOKE}[variant]
    return cfg


def all_arch_names() -> List[str]:
    return list(ALIASES.keys())
