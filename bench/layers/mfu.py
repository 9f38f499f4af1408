"""Whole round: model FLOPs per token (the configuration's ``flops/`` count:
matrix products, the tied head and the attention or SSD terms, nothing
recomputed) times the run's tokens per second, over the chips' bf16 peak."""


def read(ctx):
    flops_per_s = ctx.flops_per_token() * ctx.tokens_per_s
    return 100.0 * flops_per_s / (ctx.chips * ctx.peaks["bf16_flops_per_s"])
