"""Shared primitives: norms, rope, initializers, projections."""
from __future__ import annotations

import math
from typing import Tuple

import jax
import jax.numpy as jnp

__all__ = [
    "rms_norm",
    "dense_init",
    "embed_init",
    "rope_freqs",
    "yarn_inv_freq",
    "yarn_mscale",
    "apply_rope",
    "linear",
]


def rms_norm(x: jax.Array, scale: jax.Array, eps: float = 1e-6) -> jax.Array:
    dt = x.dtype
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    out = xf * jax.lax.rsqrt(var + eps)
    return (out * scale.astype(jnp.float32)).astype(dt)


def dense_init(key: jax.Array, shape: Tuple[int, ...], dtype, fan_in: int = None):
    fan = fan_in if fan_in is not None else shape[0]
    std = fan ** -0.5
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def embed_init(key: jax.Array, vocab: int, d: int, dtype):
    return (jax.random.normal(key, (vocab, d), jnp.float32) * 0.02).astype(dtype)


def linear(x: jax.Array, w: jax.Array) -> jax.Array:
    return jnp.einsum("...d,df->...f", x, w)


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention temperature term, 0.1 * mscale * ln(factor) + 1."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(dim: int, theta: float, cfg) -> jax.Array:
    """YaRN inverse frequencies [dim/2] of a config's ``yarn_*`` fields: the
    plain ones below the band, those divided by ``yarn_factor`` above it,
    and a linear ramp between.  The band's ends are the dimensions that turn
    ``yarn_beta_fast`` and ``yarn_beta_slow`` times over
    ``yarn_original_max_position`` positions."""
    plain = 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))

    def turning_dim(rotations: float) -> float:
        return (dim * math.log(cfg.yarn_original_max_position / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(turning_dim(cfg.yarn_beta_fast)), 0)
    high = min(math.ceil(turning_dim(cfg.yarn_beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low) / (high - low), 0, 1)
    return plain / cfg.yarn_factor * ramp + plain * (1 - ramp)


def rope_freqs(
    positions: jax.Array, dim: int, theta: float, cfg=None
) -> Tuple[jax.Array, jax.Array]:
    """positions [...,] int -> (cos, sin) of shape [..., dim/2]; where
    ``cfg`` (a ``ModelConfig``) sets ``yarn_factor``, the YaRN frequencies
    and magnitude of its ``yarn_*`` fields."""
    if cfg is None or not cfg.yarn_factor:
        inv = 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
        mag = 1.0
    else:
        inv = yarn_inv_freq(dim, theta, cfg)
        mag = yarn_mscale(cfg.yarn_factor, cfg.yarn_mscale) / yarn_mscale(
            cfg.yarn_factor, cfg.yarn_mscale_all_dim)
    ang = positions.astype(jnp.float32)[..., None] * inv  # [..., dim/2]
    return jnp.cos(ang) * mag, jnp.sin(ang) * mag


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """x: [..., dim]; cos/sin broadcastable to [..., dim/2] (interleaved pairs)."""
    x1, x2 = jnp.split(x, 2, axis=-1)
    # broadcast cos/sin over the head axis if present
    while cos.ndim < x1.ndim:
        cos = cos[..., None, :]
        sin = sin[..., None, :]
    out1 = x1 * cos - x2 * sin
    out2 = x2 * cos + x1 * sin
    return jnp.concatenate([out1, out2], axis=-1).astype(x.dtype)
