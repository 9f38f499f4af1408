"""Mamba2 SSD intra-chunk contraction — Pallas TPU kernel.

Per (batch, chunk, head) the kernel computes, for a chunk of length L:

    scores[i,j] = (C_i . B_j) * exp(cum_i - cum_j) * dt_j   (i >= j)
    y[i]        = sum_j scores[i,j] * x_j                   [L, P]
    state       = sum_j exp(cum_L - cum_j) * dt_j * (x_j (x) B_j)  [P, N]

i.e. two MXU matmuls ([L,N]x[N,L] and [L,L]x[L,P]) plus one for the chunk
state, all on VMEM-resident tiles — L = 128, P = 64, N = 64/128 keeps the
working set ~0.5 MB.  The inter-chunk recurrence (associative scan over
chunks) stays in XLA where the compiler already pipelines it.

The wrapper lays the operands out head-major ([B, Nc, heads, L, ...]) so
that every block ends in whole array dims, which the TPU compiler requires;
the head grid axis maps to the group axis of B/C via h // (H // G).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(x_ref, dt_ref, cumc_ref, cumr_ref, b_ref, c_ref, y_ref, st_ref):
    # every operand is a 2-D tile whose two dims are whole array dims, as
    # Mosaic requires: x/B/C/y are [L, P|N], dt and cum come as a [1, L]
    # row, and cum once more as an [L, 1] column.
    x = x_ref[0, 0, 0].astype(jnp.float32)        # [L, P]
    dt = dt_ref[0, 0, 0].astype(jnp.float32)      # [1, L]
    cum_c = cumc_ref[0, 0, 0].astype(jnp.float32)  # [L, 1]
    cum_r = cumr_ref[0, 0, 0].astype(jnp.float32)  # [1, L]
    bmat = b_ref[0, 0, 0].astype(jnp.float32)     # [L, N]
    cmat = c_ref[0, 0, 0].astype(jnp.float32)     # [L, N]
    l = x.shape[0]

    seg = cum_c - cum_r                            # [L(i), L(j)]
    rows = jax.lax.broadcasted_iota(jnp.int32, (l, l), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (l, l), 1)
    causal = cols <= rows
    lmat = jnp.where(causal, jnp.exp(seg), 0.0)

    scores = jnp.dot(cmat, bmat.T, preferred_element_type=jnp.float32)
    w = scores * lmat * dt
    y = jnp.dot(w, x, preferred_element_type=jnp.float32)  # [L, P]

    decay_to_end = jnp.exp(cum_r[:, l - 1:] - cum_r) * dt  # [1, L]
    state = jnp.dot(
        x.T * decay_to_end, bmat, preferred_element_type=jnp.float32
    )                                                       # [P, N]

    y_ref[0, 0, 0] = y.astype(y_ref.dtype)
    st_ref[0, 0, 0] = state.astype(st_ref.dtype)


@functools.partial(jax.jit, static_argnames=("rep", "interpret"))
def ssd_intra_chunk_pallas(
    xc: jax.Array,    # [B, Nc, L, H, P]
    dtc: jax.Array,   # [B, Nc, L, H]
    cum: jax.Array,   # [B, Nc, L, H]  (within-chunk cumsum of dt*A)
    bc: jax.Array,    # [B, Nc, L, G, N]
    cc: jax.Array,    # [B, Nc, L, G, N]
    rep: int,         # heads per group, H = G * rep
    interpret: bool = False,
):
    b, nc, l, h, p = xc.shape
    n = bc.shape[-1]
    grid = (b, nc, h)
    # operands laid out head-major, [B, Nc, heads, L, ...], so each block's
    # last two dims are whole dims of its array
    xt = xc.transpose(0, 1, 3, 2, 4)                # [B, Nc, H, L, P]
    dt_row = dtc.transpose(0, 1, 3, 2)[:, :, :, None, :]   # [B, Nc, H, 1, L]
    cum_row = cum.transpose(0, 1, 3, 2)[:, :, :, None, :]  # [B, Nc, H, 1, L]
    cum_col = cum.transpose(0, 1, 3, 2)[..., None]         # [B, Nc, H, L, 1]
    bt = bc.transpose(0, 1, 3, 2, 4)                # [B, Nc, G, L, N]
    ct = cc.transpose(0, 1, 3, 2, 4)

    def head(shape):
        return pl.BlockSpec(shape, lambda bi, ci, hi: (bi, ci, hi, 0, 0))

    def group(shape):
        return pl.BlockSpec(
            shape, lambda bi, ci, hi, r=rep: (bi, ci, hi // r, 0, 0)
        )

    y, state = pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            head((1, 1, 1, l, p)),
            head((1, 1, 1, 1, l)),
            head((1, 1, 1, l, 1)),
            head((1, 1, 1, 1, l)),
            group((1, 1, 1, l, n)),
            group((1, 1, 1, l, n)),
        ],
        out_specs=[head((1, 1, 1, l, p)), head((1, 1, 1, p, n))],
        out_shape=[
            jax.ShapeDtypeStruct((b, nc, h, l, p), xc.dtype),
            jax.ShapeDtypeStruct((b, nc, h, p, n), jnp.float32),
        ],
        interpret=interpret,
    )(xt, dt_row, cum_col, cum_row, bt, ct)
    return y.transpose(0, 1, 3, 2, 4), state
