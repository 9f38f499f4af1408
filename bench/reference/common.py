"""Helpers shared by the model references: weights from a key, norms,
and the precision each matrix product is computed in."""
from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32


def identity(x):
    return x


def scaled_cast(dtype):
    """Round a matrix product's operand to ``dtype`` with one scale per
    tensor (largest magnitude to the format's largest finite value) and
    back to the operand's dtype; the backward pass rounds the operand's
    cotangent the same way, with a scale of its own.  Products computed
    with such operands, forward and backward, are what kernels in that
    format give; the parameters stay stored as they were."""
    dtype = jnp.dtype(dtype)
    top = float(jnp.finfo(dtype).max)

    def cast(x):
        xf = x.astype(F32)
        scale = jnp.maximum(jnp.max(jnp.abs(xf)), 1e-30) / top
        return ((xf / scale).astype(dtype).astype(F32) * scale).astype(x.dtype)

    @jax.custom_vjp
    def q(x):
        return cast(x)

    q.defvjp(lambda x: (cast(x), None), lambda _, ct: (cast(ct),))
    return q


def rms_norm(x, scale, eps: float = 1e-6):
    """Computed in float32, returned in ``x``'s dtype."""
    xf = x.astype(F32)
    out = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (out * scale.astype(F32)).astype(x.dtype)


def cross_entropy(logits, tokens):
    """Mean next-token cross-entropy of logits [B, S, V] on tokens [B, S]."""
    pred = logits[:, :-1]
    tgt = tokens[:, 1:]
    logz = jax.nn.logsumexp(pred, axis=-1)
    gold = jnp.take_along_axis(pred, tgt[..., None], axis=-1)[..., 0]
    return jnp.mean(logz - gold)


def draw(key, spec: dict, dtype) -> dict:
    """Weights for a nested dict whose leaves are ``(shape, rule)`` pairs:
    ``"ones"``, ``"zeros"``, ``("const", v)``, ``("normal", std)`` or
    ``"fan_in"`` (normal with std fan_in^-1/2, fan_in = shape[-2]).  A rule
    may be ``(rule, "float32")`` to keep that leaf in float32."""
    flat, treedef = jax.tree_util.tree_flatten(
        spec, is_leaf=lambda x: isinstance(x, tuple) and isinstance(x[0], tuple))
    keys = jax.random.split(key, len(flat))
    out = []
    for (shape, rule), k in zip(flat, keys):
        leaf_dtype = dtype
        if isinstance(rule, tuple) and rule[-1] == "float32":
            rule, leaf_dtype = rule[0], F32
        if rule == "ones":
            x = jnp.ones(shape, F32)
        elif rule == "zeros":
            x = jnp.zeros(shape, F32)
        elif rule == "fan_in":
            x = jax.random.normal(k, shape, F32) * shape[-2] ** -0.5
        elif rule[0] == "const":
            x = jnp.full(shape, rule[1], F32)
        elif rule[0] == "normal":
            x = jax.random.normal(k, shape, F32) * rule[1]
        else:
            raise ValueError(f"unknown weight rule {rule!r}")
        out.append(x.astype(leaf_dtype))
    return jax.tree_util.tree_unflatten(treedef, out)
