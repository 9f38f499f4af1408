"""Model FLOPs per trained token of the Mamba-2 decoder (forward and
backward, 3x the forward; nothing recomputed).

Forward, per token and layer: 2 FLOPs per weight of the input and output
projections, 2 per tap of the depthwise convolution, and the SSD terms of
the chunked algorithm with chunks of l = min(ssm_chunk, S) positions:
C.B over the chunk per group (2 l G N), the weighted values (2 l H P), the
chunk state (2 H P N) and the state's output (2 H P N).  Plus 2 V d for the
tied head."""


def flops_per_token(c: dict, seq: int) -> float:
    d = c["d_model"]
    d_inner = c["ssm_expand"] * d
    hp, g, n = c["ssm_head_dim"], c["ssm_groups"], c["ssm_state"]
    heads = d_inner // hp
    chunk = min(c["ssm_chunk"], seq)
    proj = d * (2 * d_inner + 2 * g * n + heads) + d_inner * d
    conv = c["d_conv"] * (d_inner + 2 * g * n)
    ssd = chunk * g * n + chunk * heads * hp + 2 * heads * hp * n
    per_layer = 2 * (proj + conv + ssd)
    return 3.0 * (c["n_layers"] * per_layer + 2 * c["vocab"] * d)
