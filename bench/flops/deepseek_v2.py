"""Model FLOPs per trained token of DeepSeek-V2's decoder, or of one chip's
share of it (forward and backward, 3x the forward; nothing recomputed).

Forward, per token: 2 FLOPs per weight of every matrix product a token
passes through: MLA's five projections, the dense layers' SwiGLU, the
router (all experts), the shared experts, on average k * H / E of the H
held experts (the pairs a share computes), and the head over the held
vocabulary; and, per layer, 2 S h (nope + rope) for the scores and
2 S h v for the weighted values over the full S x S square the program
computes."""


def mla_weights(c: dict) -> int:
    d, h, lora = c["d_model"], c["n_heads"], c["kv_lora"]
    nope, rope, vd = c["head_dim"], c["rope_head_dim"], c["v_head_dim"]
    return (d * h * (nope + rope) + d * (lora + rope) + lora * h * nope
            + lora * h * vd + h * vd * d)


def held_experts(c: dict) -> int:
    return c.get("experts_held") or c["n_experts"]


def expert_flops(c: dict, rows: float) -> float:
    """Forward and backward FLOPs of ``rows`` (token, choice) pairs through
    a held expert's SwiGLU (three d x d_ff_expert products)."""
    return 3.0 * 2 * 3 * c["d_model"] * c["d_ff_expert"] * rows


def expert_bytes(c: dict, nodes: int, rows: float) -> float:
    """The least HBM bytes the held experts' grouped matmuls move in a round
    of ``nodes`` nodes whose held experts computed ``rows`` (token, choice)
    pairs: every held expert's three matrices read forward and again
    backward and their gradients written, in each MoE layer of each node;
    and each pair's input row read and output row written forward, its
    output cotangent read and input cotangent written backward (the d_ff
    wide intermediates left on chip)."""
    width = {"bfloat16": 2, "float32": 4}[c["dtype"]]
    layers = c["n_layers"] - c["first_dense_layers"]
    weights = 3 * held_experts(c) * c["d_model"] * c["d_ff_expert"] * width
    return 3.0 * layers * nodes * weights + 4.0 * c["d_model"] * width * rows


def matmul_weights(c: dict) -> float:
    """Weights a token's forward multiplies, on average."""
    d, f = c["d_model"], c["d_ff_expert"]
    dense, moe = c["first_dense_layers"], c["n_layers"] - c["first_dense_layers"]
    routed = c["moe_top_k"] * held_experts(c) / c["n_experts"]
    per_moe = d * c["n_experts"] + (c["n_shared_experts"] + routed) * 3 * d * f
    return (c["n_layers"] * mla_weights(c) + dense * 3 * d * c["d_ff"]
            + moe * per_moe + c["vocab"] * d)


def flops_per_token(c: dict, seq: int) -> float:
    h = c["n_heads"]
    attention = c["n_layers"] * 2 * seq * h * (c["head_dim"] + c["rope_head_dim"] + c["v_head_dim"])
    return 3.0 * (2 * matmul_weights(c) + attention)
