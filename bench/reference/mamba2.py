"""Plain float32 reference of the repository's Mamba-2 decoder, written from
its equations (``repro.models.ssm`` and ``model``) and not imported from it.

Per layer, on n = RMSNorm(x) (eps 1e-6): [z, xBC, dt] = n·W_in; xBC goes
through a causal depthwise convolution (width d_conv, bias) and SiLU and
splits into x [H, P], B and C [G, N]; dt = softplus(dt + dt_bias),
A = -exp(A_log).  The state-space output is computed in its quadratic
(dual) form over the whole sequence, with no chunks:

    y_t = sum_{s <= t} (C_t . B_s) exp(sum_{s < r <= t} dt_r A) dt_s x_s + D x_t

then y = RMSNorm(y * silu(z)) with a learned scale, x += y·W_out.  A final
RMSNorm feeds the tied embedding; the loss is the mean next-token
cross-entropy.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench.reference.common import F32, cross_entropy, draw, rms_norm


def _sizes(c: dict):
    d_inner = c["ssm_expand"] * c["d_model"]
    heads = d_inner // c["ssm_head_dim"]
    gn = c["ssm_groups"] * c["ssm_state"]
    return d_inner, heads, gn


def weight_spec(c: dict) -> dict:
    d, layers, vocab = c["d_model"], c["n_layers"], c["vocab"]
    d_inner, heads, gn = _sizes(c)
    conv_dim = d_inner + 2 * gn
    return {
        "embed": ((vocab, d), ("normal", 0.02)),
        "final_norm": ((d,), "ones"),
        "groups": [{
            "0_mamba": {
                "ln": ((layers, d), "ones"),
                "mamba": {
                    "A_log": ((layers, heads), ("zeros", "float32")),
                    "D": ((layers, heads), ("ones", "float32")),
                    "dt_bias": ((layers, heads), (("const", -2.0), "float32")),
                    "norm": ((layers, d_inner), "ones"),
                    "out_proj": ((layers, d_inner, d), "fan_in"),
                    "in_proj": ((layers, d, 2 * d_inner + 2 * gn + heads), "fan_in"),
                    "conv_w": ((layers, c["d_conv"], conv_dim), "fan_in"),
                    "conv_b": ((layers, conv_dim), "zeros"),
                },
            },
        }],
    }


def init(key, c: dict) -> dict:
    return draw(key, weight_spec(c), jnp.dtype(c["dtype"]))


def _mixer(p: dict, c: dict, n, q):
    b, s, _ = n.shape
    d_inner, heads, gn = _sizes(c)
    g, dn, hp = c["ssm_groups"], c["ssm_state"], c["ssm_head_dim"]
    proj = jnp.einsum("bsd,df->bsf", q(n), q(p["in_proj"]))
    z = proj[..., :d_inner]
    xbc = proj[..., d_inner: 2 * d_inner + 2 * gn]
    dt = proj[..., 2 * d_inner + 2 * gn:]
    k = c["d_conv"]
    padded = jnp.pad(xbc, ((0, 0), (k - 1, 0), (0, 0)))
    conv = sum(padded[:, i: i + s] * p["conv_w"][i] for i in range(k)) + p["conv_b"]
    conv = jax.nn.silu(conv)
    xs = conv[..., :d_inner].reshape(b, s, heads, hp)
    bm = conv[..., d_inner: d_inner + gn].reshape(b, s, g, dn)
    cm = conv[..., d_inner + gn:].reshape(b, s, g, dn)
    dt = jax.nn.softplus(dt + p["dt_bias"])                 # [B, S, H]
    cum = jnp.cumsum(dt * -jnp.exp(p["A_log"]), axis=1)     # [B, S, H]
    causal = jnp.tril(jnp.ones((s, s), bool))
    seg = cum[:, :, None, :] - cum[:, None, :, :]           # [B, t, s, H]
    decay = jnp.exp(jnp.where(causal[None, :, :, None], seg, -jnp.inf))
    cb = jnp.einsum("btgn,bsgn->btsg", q(cm), q(bm))         # [B, t, s, G]
    cb = jnp.repeat(cb, heads // g, axis=3)                  # [B, t, s, H]
    w = cb * decay * dt[:, None, :, :]
    y = jnp.einsum("btsh,bshp->bthp", q(w), q(xs)) + xs * p["D"][:, None]
    y = rms_norm(y.reshape(b, s, d_inner) * jax.nn.silu(z), p["norm"])
    return jnp.einsum("bsf,fd->bsd", q(y), q(p["out_proj"]))


def loss(params: dict, c: dict, tokens, q) -> jax.Array:
    x = params["embed"].astype(F32)[tokens]
    group = params["groups"][0]["0_mamba"]
    for layer in range(c["n_layers"]):
        lp = jax.tree_util.tree_map(lambda a: a[layer].astype(F32), group)
        x = x + _mixer(lp["mamba"], c, rms_norm(x, lp["ln"]), q)
    x = rms_norm(x, params["final_norm"].astype(F32))
    logits = jnp.einsum("bsd,vd->bsv", q(x), q(params["embed"].astype(F32)))
    return cross_entropy(logits, tokens)
