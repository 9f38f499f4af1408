"""Attention blocks: GQA (opt. qk-norm, sliding window) and MLA.

Three execution modes share one code path each:
  * full-sequence (train / prefill)  — causal (optionally windowed) mask;
  * single-token decode              — ring-buffer KV cache of capacity C
                                       (C = seq_len for full attention,
                                        C = window for sliding window).

The cache stores an explicit `positions [C]` array (−1 = empty), so ring
wraparound and window masking fall out of one predicate instead of index
gymnastics.  MLA decodes in the *absorbed* form: the cache holds only the
compressed c_kv / k_rope streams and the per-head expansions are folded
into the query/output projections (DeepSeek-V2 Sec. 2.1).
"""
from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models.config import ModelConfig
from repro.models.layers import (
    apply_rope, dense_init, linear, rms_norm, rope_freqs, yarn_mscale,
)

__all__ = [
    "KVCache",
    "MLACache",
    "gqa_init",
    "gqa_apply",
    "gqa_decode",
    "mla_init",
    "mla_apply",
    "mla_decode",
    "init_kv_cache",
    "init_mla_cache",
]

NEG_INF = -1e30


class KVCache(NamedTuple):
    k: jax.Array          # [B, C, KV, hd]
    v: jax.Array          # [B, C, KV, hd]
    positions: jax.Array  # [C] int32, -1 = empty


class MLACache(NamedTuple):
    c_kv: jax.Array       # [B, C, kv_lora]
    k_rope: jax.Array     # [B, C, rope_hd]
    positions: jax.Array  # [C] int32


# ---------------------------------------------------------------------------
# GQA
# ---------------------------------------------------------------------------
def gqa_init(key: jax.Array, cfg: ModelConfig, dtype) -> dict:
    d, h, kv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], (d, h * hd), dtype),
        "wk": dense_init(ks[1], (d, kv * hd), dtype),
        "wv": dense_init(ks[2], (d, kv * hd), dtype),
        "wo": dense_init(ks[3], (h * hd, d), dtype),
    }
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((hd,), dtype)
        p["k_norm"] = jnp.ones((hd,), dtype)
    return p


def _qkv(params: dict, cfg: ModelConfig, x: jax.Array, positions: jax.Array):
    b, s, _ = x.shape
    h, kv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = linear(x, params["wq"]).reshape(b, s, h, hd)
    k = linear(x, params["wk"]).reshape(b, s, kv, hd)
    v = linear(x, params["wv"]).reshape(b, s, kv, hd)
    if cfg.qk_norm:
        q = rms_norm(q, params["q_norm"])
        k = rms_norm(k, params["k_norm"])
    cos, sin = rope_freqs(positions, hd, cfg.rope_theta)  # [s, hd/2]
    q = apply_rope(q, cos[None], sin[None])
    k = apply_rope(k, cos[None], sin[None])
    return q, k, v


def _grouped_attention(
    q: jax.Array,  # [B, S, H, hd]
    k: jax.Array,  # [B, T, KV, hd]
    v: jax.Array,  # [B, T, KV, hd]
    mask: jax.Array,  # [S, T] or [B, S, T] bool (True = attend)
    scale: float,
) -> jax.Array:
    b, s, h, hd = q.shape
    kv = k.shape[2]
    g = h // kv
    qg = q.reshape(b, s, kv, g, hd)
    scores = jnp.einsum("bskgh,btkh->bkgst", qg, k).astype(jnp.float32) * scale
    if mask.ndim == 2:
        mask_b = mask[None, None, None]
    else:
        mask_b = mask[:, None, None]
    scores = jnp.where(mask_b, scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    out = jnp.einsum("bkgst,btkh->bskgh", probs, v)
    return out.reshape(b, s, h, hd)


def _causal_mask(s: int, window: Optional[int]) -> jax.Array:
    i = jnp.arange(s)[:, None]
    j = jnp.arange(s)[None, :]
    mask = j <= i
    if window is not None:
        mask &= (i - j) < window
    return mask


def gqa_apply(
    params: dict,
    cfg: ModelConfig,
    x: jax.Array,  # [B, S, d]
    positions: jax.Array,  # [S]
    return_cache: bool = False,
    cache_capacity: Optional[int] = None,
) -> Tuple[jax.Array, Optional[KVCache]]:
    """Full-sequence attention (train / prefill)."""
    b, s, _ = x.shape
    q, k, v = _qkv(params, cfg, x, positions)
    if cfg.use_flash:  # the kernel applies the causal + window mask itself
        from repro.kernels.flash_attention import ops as flash_ops

        out = flash_ops.flash_attention(q, k, v, window=cfg.window)
    else:
        mask = _causal_mask(s, cfg.window)
        out = _grouped_attention(q, k, v, mask, cfg.head_dim ** -0.5)
    y = linear(out.reshape(b, s, -1), params["wo"])
    cache = None
    if return_cache:
        cap = cache_capacity or s
        take = min(s, cap)
        pos_arr = jnp.full((cap,), -1, jnp.int32)
        cache = KVCache(
            k=jnp.zeros((b, cap) + k.shape[2:], k.dtype).at[:, :take].set(k[:, -take:]),
            v=jnp.zeros((b, cap) + v.shape[2:], v.dtype).at[:, :take].set(v[:, -take:]),
            positions=pos_arr.at[:take].set(positions[-take:].astype(jnp.int32)),
        )
    return y, cache


def gqa_decode(
    params: dict,
    cfg: ModelConfig,
    x: jax.Array,      # [B, 1, d]
    pos: jax.Array,    # scalar int32 — position of the new token
    cache: KVCache,
) -> Tuple[jax.Array, KVCache]:
    b = x.shape[0]
    cap = cache.k.shape[1]
    q, k, v = _qkv(params, cfg, x, pos[None])
    slot = (pos % cap).astype(jnp.int32)
    new_k = jax.lax.dynamic_update_slice_in_dim(cache.k, k, slot, axis=1)
    new_v = jax.lax.dynamic_update_slice_in_dim(cache.v, v, slot, axis=1)
    new_positions = cache.positions.at[slot].set(pos.astype(jnp.int32))
    valid = (new_positions >= 0) & (new_positions <= pos)
    if cfg.window is not None:
        valid &= (pos - new_positions) < cfg.window
    out = _grouped_attention(
        q, new_k, new_v, valid[None, None, :].repeat(b, 0), cfg.head_dim ** -0.5
    )
    y = linear(out.reshape(b, 1, -1), params["wo"])
    return y, KVCache(new_k, new_v, new_positions)


def init_kv_cache(cfg: ModelConfig, batch: int, capacity: int, dtype) -> KVCache:
    kv, hd = cfg.n_kv_heads, cfg.head_dim
    return KVCache(
        k=jnp.zeros((batch, capacity, kv, hd), dtype),
        v=jnp.zeros((batch, capacity, kv, hd), dtype),
        positions=jnp.full((capacity,), -1, jnp.int32),
    )


# ---------------------------------------------------------------------------
# MLA (DeepSeek-V2 multi-head latent attention)
# ---------------------------------------------------------------------------
def mla_init(key: jax.Array, cfg: ModelConfig, dtype) -> dict:
    d, h = cfg.d_model, cfg.n_heads
    nope, rope_hd, v_hd = cfg.head_dim, cfg.rope_head_dim, cfg.v_head_dim
    ks = jax.random.split(key, 6)
    q_in = cfg.q_lora if cfg.q_lora else d
    p = {
        "w_uq": dense_init(ks[1], (q_in, h * (nope + rope_hd)), dtype),
        "w_dkv": dense_init(ks[2], (d, cfg.kv_lora + rope_hd), dtype),
        "kv_norm": jnp.ones((cfg.kv_lora,), dtype),
        "w_uk": dense_init(ks[3], (cfg.kv_lora, h * nope), dtype),
        "w_uv": dense_init(ks[4], (cfg.kv_lora, h * v_hd), dtype),
        "wo": dense_init(ks[5], (h * v_hd, d), dtype),
    }
    if cfg.q_lora:
        p["w_dq"] = dense_init(ks[0], (d, cfg.q_lora), dtype)
        p["q_norm"] = jnp.ones((cfg.q_lora,), dtype)
    return p


def _mla_q(params: dict, cfg: ModelConfig, x: jax.Array, positions: jax.Array):
    b, s, _ = x.shape
    h, nope, rope_hd = cfg.n_heads, cfg.head_dim, cfg.rope_head_dim
    if cfg.q_lora:
        cq = rms_norm(linear(x, params["w_dq"]), params["q_norm"])
    else:
        cq = x
    q = linear(cq, params["w_uq"]).reshape(b, s, h, nope + rope_hd)
    q_nope, q_rope = q[..., :nope], q[..., nope:]
    cos, sin = rope_freqs(positions, rope_hd, cfg.rope_theta, cfg)
    q_rope = apply_rope(q_rope, cos[None], sin[None])
    return q_nope, q_rope


def _mla_ckv(params: dict, cfg: ModelConfig, x: jax.Array, positions: jax.Array):
    ckv_full = linear(x, params["w_dkv"])
    c_kv = rms_norm(ckv_full[..., : cfg.kv_lora], params["kv_norm"])
    k_rope = ckv_full[..., cfg.kv_lora :]
    cos, sin = rope_freqs(positions, cfg.rope_head_dim, cfg.rope_theta, cfg)
    k_rope = apply_rope(k_rope, cos[None], sin[None])
    return c_kv, k_rope


def _mla_scale(cfg: ModelConfig) -> float:
    """Softmax scale: (nope + rope)^-1/2, times YaRN's temperature term
    squared where the configuration scales the rope (DeepSeek-V2)."""
    scale = (cfg.head_dim + cfg.rope_head_dim) ** -0.5
    if cfg.yarn_factor and cfg.yarn_mscale_all_dim:
        scale *= yarn_mscale(cfg.yarn_factor, cfg.yarn_mscale_all_dim) ** 2
    return scale


def mla_apply(
    params: dict,
    cfg: ModelConfig,
    x: jax.Array,
    positions: jax.Array,
    return_cache: bool = False,
    cache_capacity: Optional[int] = None,
) -> Tuple[jax.Array, Optional[MLACache]]:
    """Full-sequence MLA with per-head expansion (train / prefill), named
    ``mla.attend`` in a device trace."""
    with jax.named_scope("mla.attend"):
        return _mla_full(params, cfg, x, positions, return_cache, cache_capacity)


def _mla_full(params, cfg, x, positions, return_cache, cache_capacity):
    b, s, _ = x.shape
    h, nope, v_hd = cfg.n_heads, cfg.head_dim, cfg.v_head_dim
    q_nope, q_rope = _mla_q(params, cfg, x, positions)
    c_kv, k_rope = _mla_ckv(params, cfg, x, positions)
    k_nope = linear(c_kv, params["w_uk"]).reshape(b, s, h, nope)
    v = linear(c_kv, params["w_uv"]).reshape(b, s, h, v_hd)
    scale = _mla_scale(cfg)
    rows = jnp.arange(s)
    sc = (
        jnp.einsum("bshn,bthn->bhst", q_nope, k_nope)
        + jnp.einsum("bshr,btr->bhst", q_rope, k_rope)
    ).astype(jnp.float32) * scale
    j = jnp.arange(s)
    mask = j[None, :] <= rows[:, None]
    if cfg.window is not None:
        mask &= (rows[:, None] - j[None, :]) < cfg.window
    sc = jnp.where(mask[None, None], sc, NEG_INF)
    probs = jax.nn.softmax(sc, axis=-1).astype(v.dtype)
    out = jnp.einsum("bhst,bthv->bshv", probs, v).reshape(b, s, -1)
    y = linear(out, params["wo"])
    cache = None
    if return_cache:
        cap = cache_capacity or s
        take = min(s, cap)
        pos_arr = jnp.full((cap,), -1, jnp.int32)
        cache = MLACache(
            c_kv=jnp.zeros((b, cap, cfg.kv_lora), c_kv.dtype)
            .at[:, :take]
            .set(c_kv[:, -take:]),
            k_rope=jnp.zeros((b, cap, cfg.rope_head_dim), k_rope.dtype)
            .at[:, :take]
            .set(k_rope[:, -take:]),
            positions=pos_arr.at[:take].set(positions[-take:].astype(jnp.int32)),
        )
    return y, cache


def mla_decode(
    params: dict,
    cfg: ModelConfig,
    x: jax.Array,    # [B, 1, d]
    pos: jax.Array,  # scalar
    cache: MLACache,
) -> Tuple[jax.Array, MLACache]:
    """Absorbed-form decode: scores against the compressed cache."""
    b = x.shape[0]
    h, nope, v_hd = cfg.n_heads, cfg.head_dim, cfg.v_head_dim
    cap = cache.c_kv.shape[1]
    q_nope, q_rope = _mla_q(params, cfg, x, pos[None])  # [B,1,H,*]
    c_new, kr_new = _mla_ckv(params, cfg, x, pos[None])
    slot = (pos % cap).astype(jnp.int32)
    c_kv = jax.lax.dynamic_update_slice_in_dim(cache.c_kv, c_new, slot, axis=1)
    k_rope = jax.lax.dynamic_update_slice_in_dim(cache.k_rope, kr_new, slot, axis=1)
    positions = cache.positions.at[slot].set(pos.astype(jnp.int32))
    valid = (positions >= 0) & (positions <= pos)
    if cfg.window is not None:
        valid &= (pos - positions) < cfg.window
    # absorb W_uk into the query:  q_eff[b,h,c] = q_nope . W_uk[:, h, :]
    w_uk = params["w_uk"].reshape(cfg.kv_lora, h, nope)
    q_eff = jnp.einsum("bshn,chn->bshc", q_nope, w_uk)[:, 0]  # [B,H,kv_lora]
    scale = _mla_scale(cfg)
    scores = (
        jnp.einsum("bhc,btc->bht", q_eff, c_kv)
        + jnp.einsum("bshr,btr->bht", q_rope, k_rope)
    ).astype(jnp.float32) * scale
    scores = jnp.where(valid[None, None], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(c_kv.dtype)
    ctx = jnp.einsum("bht,btc->bhc", probs, c_kv)  # compressed context
    w_uv = params["w_uv"].reshape(cfg.kv_lora, h, v_hd)
    out = jnp.einsum("bhc,chv->bhv", ctx, w_uv).reshape(b, 1, h * v_hd)
    y = linear(out, params["wo"])
    return y, MLACache(c_kv, k_rope, positions)


def init_mla_cache(cfg: ModelConfig, batch: int, capacity: int, dtype) -> MLACache:
    return MLACache(
        c_kv=jnp.zeros((batch, capacity, cfg.kv_lora), dtype),
        k_rope=jnp.zeros((batch, capacity, cfg.rope_head_dim), dtype),
        positions=jnp.full((capacity,), -1, jnp.int32),
    )
