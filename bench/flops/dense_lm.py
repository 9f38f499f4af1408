"""Model FLOPs per trained token of the dense decoder (forward and backward,
3x the forward; nothing recomputed).

Forward, per token: 2 FLOPs per weight of every matrix product (q, k, v, o,
the three SwiGLU matrices, the tied head) and, per layer, 4 S h hd for the
scores and the weighted values over the full S x S square the program
computes."""


def matmul_weights(c: dict) -> int:
    d, h, kv, hd, ff = c["d_model"], c["n_heads"], c["n_kv_heads"], c["head_dim"], c["d_ff"]
    per_layer = d * (h + 2 * kv) * hd + h * hd * d + 3 * d * ff
    return c["n_layers"] * per_layer + c["vocab"] * d


def flops_per_token(c: dict, seq: int) -> float:
    attention = c["n_layers"] * 4 * seq * c["n_heads"] * c["head_dim"]
    return 3.0 * (2 * matmul_weights(c) + attention)
