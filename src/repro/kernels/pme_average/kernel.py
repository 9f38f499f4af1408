"""Fused PME count-weighted average — Pallas TPU kernel.

For a (receiver, coordinate) tile of shape [BM, BN]:
    agg[i, l] = sum_j A[j, i] * M[j, l] * W[j, l]     (MXU matmul)
    cnt[i, l] = sum_j A[j, i] * M[j, l]               (MXU matmul)
    out[i, l] = cnt > 0 ? agg / cnt : W[i, l]         (VPU select)

The grid covers both the coordinate axis (tiles of BN) and the receiver
node axis (tiles of BM), so neither m nor n has to fit a single tile: W/M
tiles stream HBM->VMEM along the coordinate axis with the full sender axis
resident for the contraction, while each grid row only holds its [BM, m]
slice of the selection matrix A^T and the matching [BM, BN] self-fallback
tile of W.  The fusion avoids materialising the masked copy of W and the
count tensor in HBM — on a v5e this takes the op from 4 HBM round trips of
the [m, n] operand down to 1 read + 1 write.

`pme_bernoulli_average_pallas` (below) is the bernoulli-mode form: it takes
no masks but draws them itself, for the selected senders only.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK_N = 512
DEFAULT_BLOCK_M = 128


def _kernel(at_ref, w_ref, m_ref, wself_ref, out_ref):
    # f32 compute: exact counts, and the CPU interpreter lacks bf16 dots;
    # on TPU the converts fuse into the MXU matmul.
    a_t = at_ref[...].astype(jnp.float32)       # [BM, m]  A^T rows, receiver-major
    w = w_ref[...]                              # [m, BN]  full sender axis
    mask = m_ref[...].astype(jnp.float32)       # [m, BN] (0/1)
    w_self = wself_ref[...].astype(jnp.float32)  # [BM, BN] receivers' own coords
    wm = w.astype(jnp.float32) * mask
    agg = jnp.dot(a_t, wm, preferred_element_type=jnp.float32)
    cnt = jnp.dot(a_t, mask, preferred_element_type=jnp.float32)
    out = jnp.where(cnt > 0, agg / jnp.maximum(cnt, 1.0), w_self)
    out_ref[...] = out.astype(out_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("block_n", "block_m", "interpret")
)
def pme_average_pallas(
    w: jax.Array,      # [m, n]
    masks: jax.Array,  # [m, n] same dtype as w (0/1)
    a: jax.Array,      # [m, m] selection, A[j, i] = j in N_i^k
    block_n: int = DEFAULT_BLOCK_N,
    block_m: int = DEFAULT_BLOCK_M,
    interpret: bool = False,
) -> jax.Array:
    m, n = w.shape
    bn = min(block_n, n)
    bm = min(block_m, m)
    pad_n = (-n) % bn
    pad_m = (-m) % bm
    if pad_n:
        w = jnp.pad(w, ((0, 0), (0, pad_n)))
        masks = jnp.pad(masks, ((0, 0), (0, pad_n)))
    a_t = a.T.astype(w.dtype)  # [receiver, sender]
    w_self = w
    if pad_m:
        # pad receiver rows only; the sender (contraction) axis stays m, so
        # padded rows see cnt == 0 and fall back to their (zero) w_self.
        a_t = jnp.pad(a_t, ((0, pad_m), (0, 0)))
        w_self = jnp.pad(w_self, ((0, pad_m), (0, 0)))
    grid = ((m + pad_m) // bm, (n + pad_n) // bn)
    out = pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, m), lambda i, j: (i, 0)),   # A^T receiver rows
            pl.BlockSpec((m, bn), lambda i, j: (0, j)),   # W sender tile
            pl.BlockSpec((m, bn), lambda i, j: (0, j)),   # mask sender tile
            pl.BlockSpec((bm, bn), lambda i, j: (i, j)),  # W self-fallback
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m + pad_m, n + pad_n), w.dtype),
        interpret=interpret,
    )(a_t, w, masks, w_self)
    return out[:m, :n]


# ---------------------------------------------------------------------------
# Bernoulli masks drawn in the kernel
#
# In bernoulli mode sender j keeps coordinate l of a leaf with probability p,
# by `jax.random.bernoulli(leaf_key, p, leaf.shape)`.  With the partitionable
# threefry PRNG each coordinate's bits depend on its own flat index alone:
# bits = x0 ^ x1 of threefry2x32(key, (hi, lo) of the index), and the draw
# keeps the coordinate iff `uniform`'s float in [0, 1), (bits >> 9) * 2^-23,
# is below float32(p), i.e. iff bits >> 9 < ceil(float32(p) * 2^23).  So a
# tile can draw its own masks, bit for bit those of the plain draw, next to
# the average that uses them: the masks never reach HBM, and a sender no
# receiver selected (a zero row of A) is never drawn.  The hash runs on
# int32 vectors (sums and products wrap alike) with the key as scalars.
#
# A leaf [m, L, R, C] (nodes, the axes between, rows, columns) is passed as
# [L, m, R, C], the order in which a model that scans over its stacked
# layers reads it, so the view is a bitcast of the layout the step keeps.
# One grid step holds every sender's [BR, BC] tile of one l, so every
# receiver's fallback is resident as well.  Masks are drawn a strip of
# STRIP_ROWS rows at a time, which keeps a strip's threefry state in vector
# registers.
# ---------------------------------------------------------------------------

BERNOULLI_BLOCK_C = 512
STRIP_ROWS = 16  # one bf16 (16, 128) tile row; 8 f32 vregs at 512 lanes
BERNOULLI_VMEM_BYTES = 12 << 20  # under the 16 MiB a v5e kernel may use by default
MANTISSA_BITS = 23  # float32 bits `jax.random.uniform` fills


def bernoulli_threshold(p) -> jax.Array:
    """int32 t with (bits >> 9) < t  iff  uniform(bits) < float32(p)."""
    scaled = jnp.ceil(jnp.asarray(p, jnp.float32) * float(1 << MANTISSA_BITS))
    return jnp.clip(scaled, 0, 1 << MANTISSA_BITS).astype(jnp.int32)


def _wrap_i32(x: int) -> int:
    """An unsigned 32-bit count as the int32 with the same bits."""
    return (x + (1 << 31)) % (1 << 32) - (1 << 31)


_ROTATIONS = ((13, 15, 26, 6), (17, 29, 16, 24))


def _threefry_bits(k1, k2, counter):
    """x0 ^ x1 of threefry2x32((k1, k2), (0, counter)), the 32 random bits
    the partitionable PRNG gives the coordinate of flat index `counter`
    (< 2^32), on int32 words: jax's 20-round schedule, with the key
    injections summed as scalars first."""
    srl = jax.lax.shift_right_logical
    ks = (k1, k2, k1 ^ k2 ^ 0x1BD11BDA)
    x0, x1 = ks[0], counter + ks[1]
    for i in range(5):
        for r in _ROTATIONS[i % 2]:
            x0 = x0 + x1
            x1 = x0 ^ ((x1 << r) | srl(x1, 32 - r))
        x0 = x0 + ks[(i + 1) % 3]
        x1 = x1 + (ks[(i + 2) % 3] + (i + 1))
    return x0 ^ x1


def bernoulli_blocks(m: int, rows: int, cols: int, itemsize: int):
    """(BR, BC, strip) of the fused bernoulli kernel for m nodes' [rows,
    cols] tiles, or None where even one strip of every node does not fit the
    VMEM budget: double-buffered input and output tiles plus the f32 sums
    and counts of every receiver."""
    bc = min(BERNOULLI_BLOCK_C, cols)
    strip = min(STRIP_ROWS, rows)
    fit = BERNOULLI_VMEM_BYTES // (m * bc * (4 * itemsize + 8))
    if fit < strip:
        return None
    if rows <= strip:
        return rows, bc, rows
    return min(fit, rows) // strip * strip, bc, strip


def _strips(rows: int, strip: int):
    """Run the decorated body on the first row of each strip of a block of
    ``rows``: in a loop, or at the static offset 0 where the block is one
    strip (a leaf of fewer rows than a strip, whose dynamic offset could not
    be proved aligned)."""
    def run(body):
        if rows == strip:
            body(0)
        else:
            pl.loop(0, rows // strip)(lambda s: body(pl.multiple_of(s * strip, strip)))
    return run


def _bernoulli_kernel(key_ref, thr_ref, a_ref, send_ref, recv_ref,
                      w_ref, out_ref, agg_ref, cnt_ref, *, layers, rows, cols, strip):
    m, br, bc = w_ref.shape
    shape = (strip, bc)
    k1, k2 = key_ref[0], key_ref[1]
    # a node's flat index of the strip's coordinates is node_base + the
    # block's first coordinate + r0 * cols + offsets
    offsets = (jax.lax.broadcasted_iota(jnp.int32, shape, 0) * _wrap_i32(cols)
               + jax.lax.broadcasted_iota(jnp.int32, shape, 1))
    first = (pl.program_id(0) * _wrap_i32(rows * cols)
             + pl.program_id(1) * _wrap_i32(br * cols) + pl.program_id(2) * bc)
    thr = thr_ref[0]

    @pl.loop(0, m)
    def _(i):
        @pl.when(recv_ref[i] != 0)
        def _():
            agg_ref[i] = jnp.zeros((br, bc), jnp.float32)
            cnt_ref[i] = jnp.zeros((br, bc), jnp.float32)

    @pl.loop(0, m)
    def _(j):
        @pl.when(send_ref[j] != 0)
        def _():
            base = j * _wrap_i32(layers * rows * cols) + first

            @_strips(br, strip)
            def _(r0):
                counter = base + r0 * _wrap_i32(cols) + offsets
                bits = _threefry_bits(k1, k2, counter)
                keep = jax.lax.shift_right_logical(bits, 32 - MANTISSA_BITS) < thr
                part = pl.ds(r0, strip)
                sent = jnp.where(keep, w_ref[j, part, :].astype(jnp.float32), 0.0)
                kept = jnp.where(keep, 1.0, 0.0)

                @pl.loop(0, m)
                def _(i):
                    a = a_ref[j, i]

                    @pl.when(a != 0)
                    def _():
                        agg_ref[i, part, :] += a * sent
                        cnt_ref[i, part, :] += a * kept

    @pl.loop(0, m)
    def _(i):
        @pl.when(recv_ref[i] != 0)
        def _():
            @_strips(br, strip)
            def _(r0):
                part = pl.ds(r0, strip)
                cnt = cnt_ref[i, part, :]
                avg = (agg_ref[i, part, :] / jnp.maximum(cnt, 1.0)).astype(out_ref.dtype)
                out_ref[i, part, :] = jnp.where(cnt > 0, avg, w_ref[i, part, :])

        @pl.when(recv_ref[i] == 0)
        def _():
            out_ref[i] = w_ref[i]


@functools.partial(jax.jit, static_argnames=("interpret",))
def pme_bernoulli_average_pallas(
    w: jax.Array,          # [L, m, R, C]: leaf [m, L, R, C], node axis second
    key_words: jax.Array,  # uint32[2]: the leaf's threefry key
    a: jax.Array,          # [m, m] selection, A[j, i] = j in N_i^k
    p,                     # keep probability
    interpret: bool = False,
) -> jax.Array:
    """PME average of one leaf with bernoulli(p) masks drawn in the kernel:
    equal to `core.pme`'s einsum over `jax.random.bernoulli(key, p, (m, L,
    R, C))` (requires m L R C < 2^32 and the partitionable threefry PRNG)."""
    layers, m, rows, cols = w.shape
    br, bc, strip = bernoulli_blocks(m, rows, cols, w.dtype.itemsize)
    # A in the leaf's dtype, as the einsum path multiplies it
    a_f = a.astype(w.dtype).astype(jnp.float32)
    nz = a_f != 0
    scalars = (
        jax.lax.bitcast_convert_type(key_words.astype(jnp.uint32), jnp.int32),
        bernoulli_threshold(p).reshape(1),
        a_f,
        jnp.any(nz, axis=1).astype(jnp.int32),  # sender j is drawn
        jnp.any(nz, axis=0).astype(jnp.int32),  # receiver i averages
    )
    tile = pl.BlockSpec((None, m, br, bc), lambda l, r, c, *_: (l, 0, r, c))
    return pl.pallas_call(
        functools.partial(_bernoulli_kernel, layers=layers, rows=rows, cols=cols,
                          strip=strip),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(scalars),
            grid=(layers, pl.cdiv(rows, br), pl.cdiv(cols, bc)),
            in_specs=[tile],
            out_specs=tile,
            scratch_shapes=[pltpu.VMEM((m, br, bc), jnp.float32)] * 2,
        ),
        out_shape=jax.ShapeDtypeStruct(w.shape, w.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel")),
        # one read and one write of the leaf; the sums and counts over m
        # senders of every coordinate (the threefry hash is integer work)
        cost_estimate=pl.CostEstimate(
            flops=4 * m * w.size, transcendentals=0, bytes_accessed=2 * w.nbytes),
        interpret=interpret,
    )(*scalars, w)
