"""Mamba2 block — state-space duality (SSD), arXiv:2405.21060.

Full-sequence path uses the chunked SSD algorithm:
  intra-chunk:  quadratic attention-like form with decay mask
                L[i,j] = exp(cumA_i - cumA_j) (causal within a chunk);
  inter-chunk:  per-chunk states combined by an associative scan over the
                chunk axis (h_k = decay_k * h_{k-1} + s_k).

Decode path is the O(1) recurrence  h <- h*exp(dtA) + dt * B (x) outer,
y = C.h + D*x.  The intra-chunk contraction is the compute hot spot and has
a Pallas kernel (`repro.kernels.ssd_scan`) selected by cfg.use_ssd_kernel.
"""
from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models.config import ModelConfig
from repro.models.layers import dense_init, linear, rms_norm

__all__ = ["SSMCache", "mamba_init", "mamba_apply", "mamba_decode", "init_ssm_cache"]


class SSMCache(NamedTuple):
    conv: jax.Array   # [B, d_conv-1, conv_dim] — rolling pre-conv inputs
    state: jax.Array  # [B, H, P, N] — SSD recurrent state


def mamba_init(key: jax.Array, cfg: ModelConfig, dtype) -> dict:
    d, di, g, n, h = (
        cfg.d_model,
        cfg.d_inner,
        cfg.ssm_groups,
        cfg.ssm_state,
        cfg.ssm_heads,
    )
    ks = jax.random.split(key, 8)
    common = {
        "A_log": jnp.zeros((h,), jnp.float32),   # A = -exp(A_log) = -1
        "D": jnp.ones((h,), jnp.float32),
        "dt_bias": jnp.full((h,), -2.0, jnp.float32),  # softplus ~= 0.12
        "norm": jnp.ones((di,), dtype),
        "out_proj": dense_init(ks[2], (di, d), dtype),
    }
    if cfg.ssm_split_proj:
        # per-stream projections (z, x, B, C, dt) with per-stream convs
        return {
            **common,
            "in_z": dense_init(ks[0], (d, di), dtype),
            "in_x": dense_init(ks[3], (d, di), dtype),
            "in_B": dense_init(ks[4], (d, g * n), dtype),
            "in_C": dense_init(ks[5], (d, g * n), dtype),
            "in_dt": dense_init(ks[6], (d, h), dtype),
            "conv_x_w": dense_init(ks[1], (cfg.d_conv, di), dtype, fan_in=cfg.d_conv),
            "conv_x_b": jnp.zeros((di,), dtype),
            "conv_B_w": dense_init(ks[7], (cfg.d_conv, g * n), dtype, fan_in=cfg.d_conv),
            "conv_B_b": jnp.zeros((g * n,), dtype),
            "conv_C_w": dense_init(
                jax.random.fold_in(ks[7], 1), (cfg.d_conv, g * n), dtype,
                fan_in=cfg.d_conv,
            ),
            "conv_C_b": jnp.zeros((g * n,), dtype),
        }
    proj_out = 2 * di + 2 * g * n + h
    return {
        **common,
        "in_proj": dense_init(ks[0], (d, proj_out), dtype),
        "conv_w": dense_init(ks[1], (cfg.d_conv, cfg.conv_dim), dtype, fan_in=cfg.d_conv),
        "conv_b": jnp.zeros((cfg.conv_dim,), dtype),
    }


def _split_proj(cfg: ModelConfig, proj: jax.Array):
    di, g, n, h = cfg.d_inner, cfg.ssm_groups, cfg.ssm_state, cfg.ssm_heads
    z = proj[..., :di]
    xbc = proj[..., di : di + cfg.conv_dim]
    dt = proj[..., di + cfg.conv_dim :]
    assert dt.shape[-1] == h
    return z, xbc, dt


def _causal_conv(cfg: ModelConfig, xbc: jax.Array, w: jax.Array, b: jax.Array):
    """Depthwise causal conv over time; xbc [B,S,C]."""
    k = cfg.d_conv
    pad = jnp.pad(xbc, ((0, 0), (k - 1, 0), (0, 0)))
    out = sum(pad[:, i : i + xbc.shape[1], :] * w[i][None, None] for i in range(k))
    return jax.nn.silu(out + b[None, None])


def _split_xbc(cfg: ModelConfig, xbc: jax.Array):
    di, g, n = cfg.d_inner, cfg.ssm_groups, cfg.ssm_state
    x = xbc[..., :di]
    b_ = xbc[..., di : di + g * n]
    c_ = xbc[..., di + g * n :]
    shp = xbc.shape[:-1]
    return (
        x.reshape(shp + (cfg.ssm_heads, cfg.ssm_head_dim)),
        b_.reshape(shp + (g, n)),
        c_.reshape(shp + (g, n)),
    )


def _ssd_chunked(
    cfg: ModelConfig,
    x: jax.Array,    # [B, S, H, P]
    dt: jax.Array,   # [B, S, H] (post-softplus)
    a: jax.Array,    # [H] negative
    b_: jax.Array,   # [B, S, G, N]
    c_: jax.Array,   # [B, S, G, N]
    h0: Optional[jax.Array] = None,  # [B, H, P, N]
) -> Tuple[jax.Array, jax.Array]:
    """Returns (y [B,S,H,P], final_state [B,H,P,N])."""
    bsz, s, h, p = x.shape
    g, n = b_.shape[2], b_.shape[3]
    l = min(cfg.ssm_chunk, s)
    pad = (-s) % l
    if pad:
        zf = lambda u: jnp.pad(u, ((0, 0), (0, pad)) + ((0, 0),) * (u.ndim - 2))
        x, dt, b_, c_ = zf(x), zf(dt), zf(b_), zf(c_)
    sp = s + pad
    nc = sp // l
    xc = x.reshape(bsz, nc, l, h, p)
    dtc = dt.reshape(bsz, nc, l, h)
    bc = b_.reshape(bsz, nc, l, g, n)
    cc = c_.reshape(bsz, nc, l, g, n)

    rep = h // g  # heads per group
    da = dtc * a[None, None, None]                        # [B,Nc,L,H]
    cum = jnp.cumsum(da, axis=2)                          # within-chunk
    if cfg.use_ssd_kernel:
        from repro.kernels.ssd_scan import ops as ssd_ops

        y_intra, chunk_state = ssd_ops.ssd_intra_chunk(xc, dtc, cum, bc, cc, rep)
    else:
        # decay mask L[i,j] = exp(cum_i - cum_j), i >= j
        seg = cum[:, :, :, None, :] - cum[:, :, None, :, :]   # [B,Nc,L(i),L(j),H]
        causal = jnp.tril(jnp.ones((l, l), bool))
        lmat = jnp.where(causal[None, None, :, :, None], jnp.exp(seg), 0.0)
        bh = jnp.repeat(bc, rep, axis=3)                      # [B,Nc,L,H,N]
        ch = jnp.repeat(cc, rep, axis=3)
        scores = jnp.einsum("bnlhs,bnmhs->bnlmh", ch, bh)     # C_i . B_j
        w = scores * lmat * dtc[:, :, None, :, :]             # * dt_j
        y_intra = jnp.einsum("bnlmh,bnmhp->bnlhp", w.astype(xc.dtype), xc)
        # chunk state: sum_j exp(cum_last - cum_j) dt_j B_j (x) x_j
        decay_to_end = jnp.exp(cum[:, :, -1:, :] - cum)       # [B,Nc,L,H]
        wstate = (decay_to_end * dtc)[..., None] * bh          # [B,Nc,L,H,N]
        chunk_state = jnp.einsum(
            "bnlhs,bnlhp->bnhps", wstate.astype(xc.dtype), xc
        )                                                      # [B,Nc,H,P,N]

    # inter-chunk recurrence over Nc: h_k = exp(sum chunk dA)_k h_{k-1} + s_k
    # (recurrent state kept in f32 regardless of activation dtype)
    chunk_state = chunk_state.astype(jnp.float32)
    chunk_decay = jnp.exp(cum[:, :, -1, :])                   # [B,Nc,H] f32

    def combine(lhs, rhs):
        d1, s1 = lhs
        d2, s2 = rhs
        return d1 * d2, s1 * d2[..., None, None] + s2

    if h0 is None:
        h0 = jnp.zeros((bsz, h, p, n), chunk_state.dtype)
    # prepend h0 as a pseudo-chunk with decay 1
    decays = jnp.concatenate(
        [jnp.ones((bsz, 1, h), chunk_decay.dtype), chunk_decay], axis=1
    )
    states = jnp.concatenate([h0[:, None], chunk_state], axis=1)
    _, run = jax.lax.associative_scan(combine, (decays, states), axis=1)
    prev_states = run[:, :-1]                                  # state BEFORE chunk k
    final_state = run[:, -1]

    # inter-chunk contribution: y_i += C_i . (exp(cum_i) * h_prev)
    ch = jnp.repeat(cc, rep, axis=3)
    inner = jnp.einsum("bnlhs,bnhps->bnlhp", ch.astype(prev_states.dtype), prev_states)
    y_inter = inner * jnp.exp(cum)[..., None]
    y = (y_intra + y_inter.astype(y_intra.dtype)).reshape(bsz, sp, h, p)
    if pad:
        y = y[:, :s]
    return y, final_state


def _project(params: dict, cfg: ModelConfig, x: jax.Array):
    """Returns (z, xs [B,S,H,P], b_ [B,S,G,N], c_, dt_raw, xbc_preconv)."""
    bsz, s, _ = x.shape
    g, n = cfg.ssm_groups, cfg.ssm_state
    if cfg.ssm_split_proj:
        z = linear(x, params["in_z"])
        xs_raw = linear(x, params["in_x"])
        b_raw = linear(x, params["in_B"])
        c_raw = linear(x, params["in_C"])
        dt_raw = linear(x, params["in_dt"])
        xs_c = _causal_conv(cfg, xs_raw, params["conv_x_w"], params["conv_x_b"])
        b_c = _causal_conv(cfg, b_raw, params["conv_B_w"], params["conv_B_b"])
        c_c = _causal_conv(cfg, c_raw, params["conv_C_w"], params["conv_C_b"])
        xs = xs_c.reshape(bsz, s, cfg.ssm_heads, cfg.ssm_head_dim)
        b_ = b_c.reshape(bsz, s, g, n)
        c_ = c_c.reshape(bsz, s, g, n)
        xbc = jnp.concatenate([xs_raw, b_raw, c_raw], axis=-1)  # cache layout
        return z, xs, b_, c_, dt_raw, xbc
    proj = linear(x, params["in_proj"])
    z, xbc, dt_raw = _split_proj(cfg, proj)
    xbc_conv = _causal_conv(cfg, xbc, params["conv_w"], params["conv_b"])
    xs, b_, c_ = _split_xbc(cfg, xbc_conv)
    return z, xs, b_, c_, dt_raw, xbc


def mamba_apply(
    params: dict,
    cfg: ModelConfig,
    x: jax.Array,  # [B, S, d]
    return_cache: bool = False,
) -> Tuple[jax.Array, Optional[SSMCache]]:
    bsz, s, _ = x.shape
    z, xs, b_, c_, dt_raw, xbc = _project(params, cfg, x)
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + params["dt_bias"][None, None])
    a = -jnp.exp(params["A_log"])
    y, final_state = _ssd_chunked(cfg, xs, dt, a, b_, c_)
    y = y + xs * params["D"][None, None, :, None].astype(xs.dtype)
    y = y.reshape(bsz, s, cfg.d_inner)
    y = rms_norm(y * jax.nn.silu(z), params["norm"])
    out = linear(y, params["out_proj"])
    cache = None
    if return_cache:
        tail = cfg.d_conv - 1
        conv_tail = jnp.pad(xbc, ((0, 0), (tail, 0), (0, 0)))[:, -tail:]
        cache = SSMCache(conv=conv_tail, state=final_state)
    return out, cache


def mamba_decode(
    params: dict,
    cfg: ModelConfig,
    x: jax.Array,  # [B, 1, d]
    cache: SSMCache,
) -> Tuple[jax.Array, SSMCache]:
    bsz = x.shape[0]
    if cfg.ssm_split_proj:
        di, g, n = cfg.d_inner, cfg.ssm_groups, cfg.ssm_state
        z = linear(x, params["in_z"])
        xbc = jnp.concatenate(
            [linear(x, params["in_x"]), linear(x, params["in_B"]),
             linear(x, params["in_C"])], axis=-1,
        )
        dt_raw = linear(x, params["in_dt"])
        window = jnp.concatenate([cache.conv, xbc], axis=1)  # [B, d_conv, C]
        outs = []
        for lo, hi, w_key, b_key in (
            (0, di, "conv_x_w", "conv_x_b"),
            (di, di + g * n, "conv_B_w", "conv_B_b"),
            (di + g * n, di + 2 * g * n, "conv_C_w", "conv_C_b"),
        ):
            seg = window[:, :, lo:hi]
            outs.append(
                jnp.einsum("bkc,kc->bc", seg, params[w_key]) + params[b_key]
            )
        conv_out = jax.nn.silu(jnp.concatenate(outs, axis=-1))[:, None]
    else:
        proj = linear(x, params["in_proj"])
        z, xbc, dt_raw = _split_proj(cfg, proj)
        # rolling conv state
        window = jnp.concatenate([cache.conv, xbc], axis=1)  # [B, d_conv, C]
        conv_out = jnp.einsum("bkc,kc->bc", window, params["conv_w"]) + params["conv_b"]
        conv_out = jax.nn.silu(conv_out)[:, None]            # [B,1,C]
    xs, b_, c_ = _split_xbc(cfg, conv_out)
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + params["dt_bias"][None, None])
    a = -jnp.exp(params["A_log"])
    da = jnp.exp(dt[:, 0] * a[None])                     # [B,H]
    rep = cfg.ssm_heads // cfg.ssm_groups
    bh = jnp.repeat(b_[:, 0], rep, axis=1)               # [B,H,N]
    chh = jnp.repeat(c_[:, 0], rep, axis=1)
    contrib = (dt[:, 0][..., None, None] * xs[:, 0][..., None]) * bh[:, :, None, :]
    new_state = cache.state * da[..., None, None] + contrib.astype(cache.state.dtype)
    y = jnp.einsum("bhpn,bhn->bhp", new_state, chh.astype(new_state.dtype))
    y = y.astype(xs.dtype) + xs[:, 0] * params["D"][None, :, None].astype(xs.dtype)
    y = y.reshape(bsz, 1, cfg.d_inner)
    y = rms_norm(y * jax.nn.silu(z), params["norm"])
    out = linear(y, params["out_proj"])
    return out, SSMCache(conv=window[:, 1:], state=new_state)


def init_ssm_cache(cfg: ModelConfig, batch: int, dtype) -> SSMCache:
    return SSMCache(
        conv=jnp.zeros((batch, cfg.d_conv - 1, cfg.conv_dim), dtype),
        state=jnp.zeros(
            (batch, cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state), jnp.float32
        ),
    )
