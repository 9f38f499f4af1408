"""Plain reference of the repository's dense decoder, written from its
equations (``repro.models``: ``model``, ``attention``, ``mlp``, ``layers``)
and not imported from it.

Per layer: x += Wo·attn(rope(Wq·n(x)), rope(Wk·n(x)), Wv·n(x)) and
x += Wd·(silu(Wg·n(x)) * Wu·n(x)), with n the RMSNorm (eps 1e-6, learned
scale), rotary embedding over the whole head (half-split pairs, theta from
the configuration), causal softmax attention scaled by head_dim^-1/2, and
a final RMSNorm into the tied embedding.  The loss is the mean next-token
cross-entropy.  Where this departs from the published StableLM-2 (LayerNorm,
partial rotary, qkv bias, untied head) the configuration file lists it.

Precision follows the parameters' dtype, as the repository's block does:
matrix products, the residual stream and the attention probabilities in
that dtype; norms, rotary angles, the softmax, the logits and the loss in
float32.  Given float32 parameters, everything is float32.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench.reference.common import F32, cross_entropy, draw, rms_norm


def weight_spec(c: dict) -> dict:
    """The parameter tree, leaf by leaf ``(shape, rule)``, in the layout
    the program trains (one scanned group of ``(attn, mlp)`` layers)."""
    d, h, kv, hd = c["d_model"], c["n_heads"], c["n_kv_heads"], c["head_dim"]
    ff, layers, vocab = c["d_ff"], c["n_layers"], c["vocab"]
    return {
        "embed": ((vocab, d), ("normal", 0.02)),
        "final_norm": ((d,), "ones"),
        "groups": [{
            "0_attn": {
                "ln": ((layers, d), "ones"),
                "attn": {
                    "wq": ((layers, d, h * hd), "fan_in"),
                    "wk": ((layers, d, kv * hd), "fan_in"),
                    "wv": ((layers, d, kv * hd), "fan_in"),
                    "wo": ((layers, h * hd, d), "fan_in"),
                },
            },
            "1_mlp": {
                "ln": ((layers, d), "ones"),
                "mlp": {
                    "w_gate": ((layers, d, ff), "fan_in"),
                    "w_up": ((layers, d, ff), "fan_in"),
                    "w_down": ((layers, ff, d), "fan_in"),
                },
            },
        }],
    }


def init(key, c: dict) -> dict:
    return draw(key, weight_spec(c), jnp.dtype(c["dtype"]))


def _rope(x, theta: float):
    """x [B, S, H, hd]: rotate the (first half, second half) pairs."""
    s, hd = x.shape[1], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd)
    ang = jnp.arange(s, dtype=F32)[:, None] * inv          # [S, hd/2]
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]  # [S, 1, hd/2]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1).astype(x.dtype)


def loss(params: dict, c: dict, tokens, q) -> jax.Array:
    """Mean next-token cross-entropy; ``q`` rounds every operand of a
    matrix product (identity for the reference)."""
    h, kv, hd = c["n_heads"], c["n_kv_heads"], c["head_dim"]
    act = params["embed"].dtype
    mm = lambda a, w: jnp.einsum("bsd,df->bsf", q(a), q(w))
    x = params["embed"][tokens]
    b, s, _ = x.shape
    causal = jnp.tril(jnp.ones((s, s), bool))
    group = params["groups"][0]
    for layer in range(c["n_layers"]):
        at = jax.tree_util.tree_map(lambda a: a[layer], group["0_attn"])
        ml = jax.tree_util.tree_map(lambda a: a[layer], group["1_mlp"])
        n = rms_norm(x, at["ln"])
        qh = _rope(mm(n, at["attn"]["wq"]).reshape(b, s, h, hd), c["rope_theta"])
        kh = _rope(mm(n, at["attn"]["wk"]).reshape(b, s, kv, hd), c["rope_theta"])
        vh = mm(n, at["attn"]["wv"]).reshape(b, s, kv, hd)
        kh = jnp.repeat(kh, h // kv, axis=2)
        vh = jnp.repeat(vh, h // kv, axis=2)
        scores = jnp.einsum("bshd,bthd->bhst", q(qh), q(kh)).astype(F32) * hd ** -0.5
        probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1).astype(act)
        att = jnp.einsum("bhst,bthd->bshd", q(probs), q(vh)).reshape(b, s, h * hd)
        x = x + mm(att, at["attn"]["wo"])
        n = rms_norm(x, ml["ln"])
        x = x + mm(jax.nn.silu(mm(n, ml["mlp"]["w_gate"])) * mm(n, ml["mlp"]["w_up"]),
                   ml["mlp"]["w_down"])
    x = rms_norm(x, params["final_norm"])
    logits = mm(x, params["embed"].T).astype(F32)
    return cross_entropy(logits, tokens)
