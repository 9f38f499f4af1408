"""deepseek-v2-lite-16b-ep8 — one chip's share of DeepSeek-V2-Lite when
each layer is divided over 8 chips by expert parallelism: experts 0-7 of
the 64 routed ones (the router keeps its 64 outputs and its top-6), 1/8
of the vocabulary (12800 rows of the embedding and of the untied head),
and MLA, the dense MLP and the shared experts whole, as every chip holds
them.  The experts held elsewhere add nothing here (see ``models.moe``).
"""
from repro.configs import deepseek_v2_lite_16b as lite

FULL = lite.FULL.replace(name="deepseek-v2-lite-16b-ep8", experts_held=8, vocab=12800)

# smoke widths have 4 experts: the share holds 2 of them
SMOKE = lite.SMOKE.replace(name="deepseek-lite-ep8-smoke", experts_held=2)
