"""Plain reference of DeepSeek-V2's decoder (arXiv:2405.04434; the
DeepSeek-V2-Lite ``config.json`` and modeling code), or of one chip's share
of it under expert parallelism, written from the published equations and
not imported from the program.

Per layer, with n the RMSNorm (eps 1e-6, learned scale):

- MLA without a query LoRA: q = Wq n(x), split per head into q_nope (128)
  and q_pe (64); [c_kv, k_pe] = Wdkv n(x), c_kv normed; k_nope = Wuk c_kv,
  v = Wuv c_kv per head; q_pe and the head-shared k_pe rotated with YaRN
  frequencies; scores [q_nope, q_pe] . [k_nope, k_pe] causal, scaled by
  192^-1/2 * (0.1 * mscale_all_dim * ln factor + 1)^2; x += Wo attn;
- the first ``first_dense_layers``: x += SwiGLU MLP (d_ff);
- the others: router softmax over every expert in float32, greedy top-k,
  the gate renormalised only under ``norm_topk_prob``; each held expert's
  SwiGLU computed on every token and weighted by the token's gate for it
  (0 where the token did not choose it), summed in float32; plus the
  shared experts' SwiGLU; and the sequence-wise balance loss
  alpha * mean_b sum_e (E / (S k)) count_be * mean_s p_bse.

A final RMSNorm feeds the untied head; the loss is the mean next-token
cross-entropy plus the layers' balance losses.  Departures from the
published model, which the configuration file lists: rope pairs split in
halves rather than interleaved (a fixed permutation of the q/k rope
columns), and, in a share, the experts held elsewhere are absent.

Precision follows the parameters' dtype, as the program's block does:
matrix products, the residual stream and the attention probabilities in
that dtype; norms, rope angles, the router, the softmaxes, the expert sum,
the logits and the loss in float32.  Given float32 parameters, everything
is float32.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from bench.reference.common import F32, cross_entropy, draw, rms_norm


def _held(c: dict) -> int:
    return c.get("experts_held") or c["n_experts"]


def weight_spec(c: dict) -> dict:
    """The parameter tree, leaf by leaf ``(shape, rule)``, in the layout the
    program trains: a scanned group of dense layers, then one of MoE
    layers, each (MLA, FFN)."""
    d, h, nope, rope, vd = (c["d_model"], c["n_heads"], c["head_dim"],
                            c["rope_head_dim"], c["v_head_dim"])
    lora, dense, vocab = c["kv_lora"], c["first_dense_layers"], c["vocab"]
    moe, f, sff = c["n_layers"] - dense, c["d_ff_expert"], c["n_shared_experts"] * c["d_ff_expert"]

    def mla(n):
        return {"ln": ((n, d), "ones"), "attn": {
            "w_uq": ((n, d, h * (nope + rope)), "fan_in"),
            "w_dkv": ((n, d, lora + rope), "fan_in"),
            "kv_norm": ((n, lora), "ones"),
            "w_uk": ((n, lora, h * nope), "fan_in"),
            "w_uv": ((n, lora, h * vd), "fan_in"),
            "wo": ((n, h * vd, d), "fan_in"),
        }}

    def swiglu(shape_in, shape_out):
        return {"w_gate": (shape_in, "fan_in"), "w_up": (shape_in, "fan_in"),
                "w_down": (shape_out, "fan_in")}

    groups = []
    if dense:
        groups.append({"0_mla": mla(dense), "1_mlp": {
            "ln": ((dense, d), "ones"),
            "mlp": swiglu((dense, d, c["d_ff"]), (dense, c["d_ff"], d))}})
    groups.append({"0_mla": mla(moe), "1_moe": {
        "ln": ((moe, d), "ones"),
        "moe": {"router": ((moe, d, c["n_experts"]), "fan_in"),
                **swiglu((moe, _held(c), d, f), (moe, _held(c), f, d)),
                "shared": swiglu((moe, d, sff), (moe, sff, d))}}})
    spec = {"embed": ((vocab, d), ("normal", 0.02)), "final_norm": ((d,), "ones"),
            "groups": groups}
    if not c["tie_embeddings"]:
        spec["lm_head"] = ((d, vocab), "fan_in")
    return spec


def init(key, c: dict) -> dict:
    return draw(key, weight_spec(c), jnp.dtype(c["dtype"]))


def yarn_inv_freq(c: dict) -> jax.Array:
    """DeepSeek-V2's YaRN inverse frequencies over the rope dimensions: the
    plain theta^(-2i/d) below the correction range, those divided by the
    factor above it, a linear ramp over it.  The range's ends are the
    dimensions whose wavelength turns beta_fast and beta_slow times over the
    original context."""
    dim, theta = c["rope_head_dim"], c["rope_theta"]
    plain = 1.0 / theta ** (jnp.arange(0, dim, 2, dtype=F32) / dim)
    if not c.get("yarn_factor"):
        return plain
    factor, context = c["yarn_factor"], c["yarn_original_max_position"]

    def dim_of(rotations):
        return dim * math.log(context / (rotations * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(dim_of(c["yarn_beta_fast"])), 0)
    high = min(math.ceil(dim_of(c["yarn_beta_slow"])), dim - 1)
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=F32) - low) / max(high - low, 1e-3), 0.0, 1.0)
    return (plain / factor) * ramp + plain * (1.0 - ramp)


def _mscale(factor: float, m: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * m * math.log(factor) + 1.0


def softmax_scale(c: dict) -> float:
    scale = (c["head_dim"] + c["rope_head_dim"]) ** -0.5
    if c.get("yarn_factor") and c.get("yarn_mscale_all_dim"):
        scale *= _mscale(c["yarn_factor"], c["yarn_mscale_all_dim"]) ** 2
    return scale


def _rope(x, c: dict):
    """x [B, S, H, r]: rotate the (first half, second half) pairs by the
    YaRN angles, magnitude mscale / mscale_all_dim."""
    s, r = x.shape[1], x.shape[-1]
    ang = jnp.arange(s, dtype=F32)[:, None] * yarn_inv_freq(c)  # [S, r/2]
    mag = 1.0
    if c.get("yarn_factor"):
        mag = (_mscale(c["yarn_factor"], c["yarn_mscale"])
               / _mscale(c["yarn_factor"], c["yarn_mscale_all_dim"]))
    cos, sin = (jnp.cos(ang) * mag)[:, None], (jnp.sin(ang) * mag)[:, None]
    x1, x2 = x[..., : r // 2].astype(F32), x[..., r // 2:].astype(F32)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1).astype(x.dtype)


def _layer(tree, i):
    return jax.tree_util.tree_map(lambda a: a[i], tree)


def _mla(p, c, x, mm, q):
    b, s, _ = x.shape
    h, nope, rope, vd = c["n_heads"], c["head_dim"], c["rope_head_dim"], c["v_head_dim"]
    lora = c["kv_lora"]
    query = mm(x, p["w_uq"]).reshape(b, s, h, nope + rope)
    kv = mm(x, p["w_dkv"])
    c_kv = rms_norm(kv[..., :lora], p["kv_norm"])
    k_pe = _rope(kv[..., lora:][:, :, None, :], c)                  # [B, S, 1, r]
    k_nope = mm(c_kv, p["w_uk"]).reshape(b, s, h, nope)
    v = mm(c_kv, p["w_uv"]).reshape(b, s, h, vd)
    query = jnp.concatenate([query[..., :nope], _rope(query[..., nope:], c)], -1)
    key = jnp.concatenate([k_nope, jnp.broadcast_to(k_pe, (b, s, h, rope))], -1)
    scores = jnp.einsum("bshd,bthd->bhst", q(query), q(key)).astype(F32) * softmax_scale(c)
    causal = jnp.tril(jnp.ones((s, s), bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1).astype(x.dtype)
    out = jnp.einsum("bhst,bthd->bshd", q(probs), q(v)).reshape(b, s, h * vd)
    return mm(out, p["wo"])


def _swiglu(p, x, mm):
    return mm(jax.nn.silu(mm(x, p["w_gate"])) * mm(x, p["w_up"]), p["w_down"])


def _moe(p, c, x, mm, q):
    """The held experts' and the shared experts' part, and the balance loss."""
    b, s, d = x.shape
    e, k = c["n_experts"], c["moe_top_k"]
    logits = jnp.einsum("bsd,de->bse", q(x).astype(F32), q(p["router"]).astype(F32))
    probs = jax.nn.softmax(logits, axis=-1)
    gate, choice = jax.lax.top_k(probs, k)                          # [B, S, k]
    if c["norm_topk_prob"]:
        gate = gate / jnp.sum(gate, axis=-1, keepdims=True)
    picked = jax.nn.one_hot(choice, e, dtype=F32)                   # [B, S, k, E]
    counts = jnp.sum(picked, axis=(1, 2))                           # [B, E]
    aux = c["router_aux_coef"] * jnp.mean(
        jnp.sum(counts * (e / (s * k)) * jnp.mean(probs, axis=1), axis=-1))
    weight = jnp.einsum("bske,bsk->bse", picked, gate)[..., :_held(c)]  # 0: not chosen
    y = jnp.zeros((b, s, d), F32)
    for j in range(_held(c)):
        expert = {name: p[name][j] for name in ("w_gate", "w_up", "w_down")}
        y = y + _swiglu(expert, x, mm).astype(F32) * weight[..., j:j + 1]
    return y.astype(x.dtype) + _swiglu(p["shared"], x, mm), aux


def loss(params: dict, c: dict, tokens, q) -> jax.Array:
    """Mean next-token cross-entropy plus the balance losses; ``q`` rounds
    every operand of a matrix product (identity for the reference)."""
    mm = lambda a, w: jnp.einsum("...d,df->...f", q(a), q(w))
    x = params["embed"][tokens]
    aux = jnp.zeros((), F32)
    for group in params["groups"]:
        ffn_kind = "1_mlp" if "1_mlp" in group else "1_moe"
        for i in range(group["0_mla"]["ln"].shape[0]):
            at, ff = _layer(group["0_mla"], i), _layer(group[ffn_kind], i)
            x = x + _mla(at["attn"], c, rms_norm(x, at["ln"]), mm, q)
            n = rms_norm(x, ff["ln"])
            if ffn_kind == "1_mlp":
                x = x + _swiglu(ff["mlp"], n, mm)
            else:
                y, a = _moe(ff["moe"], c, n, mm, q)
                x, aux = x + y, aux + a
    x = rms_norm(x, params["final_norm"])
    head = params["embed"].T if c["tie_embeddings"] else params["lm_head"]
    logits = mm(x, head).astype(F32)
    return cross_entropy(logits, tokens) + aux
