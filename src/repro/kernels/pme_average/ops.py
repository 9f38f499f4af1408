"""Public wrappers: pick interpret mode on CPU, the kernel on TPU."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.kernels.pme_average.kernel import (
    DEFAULT_BLOCK_M,
    DEFAULT_BLOCK_N,
    bernoulli_blocks,
    pme_average_pallas,
    pme_bernoulli_average_pallas,
)


def _on_cpu() -> bool:
    return jax.default_backend() == "cpu"


def pme_average(
    w: jax.Array,
    masks: jax.Array,
    a: jax.Array,
    block_n: int = DEFAULT_BLOCK_N,
    block_m: int = DEFAULT_BLOCK_M,
) -> jax.Array:
    """Count-weighted PME average; masks may be bool or numeric."""
    masks = masks.astype(w.dtype)
    return pme_average_pallas(
        w, masks, a, block_n=block_n, block_m=block_m, interpret=_on_cpu()
    )


def _view(shape) -> tuple:
    """[m, L, R, C] of a node-stacked leaf: its last two axes as R and C,
    the axes between the node axis and them merged into L."""
    m, *rest = shape
    rest = [1, 1, *rest]
    return m, math.prod(rest[:-2]), rest[-2], rest[-1]


def bernoulli_fits(leaf: jax.Array) -> bool:
    """Whether one strip of every node of `leaf` fits the fused bernoulli
    kernel's VMEM budget (false only for very many nodes)."""
    m, _, rows, cols = _view(leaf.shape)
    return bernoulli_blocks(m, rows, cols, leaf.dtype.itemsize) is not None


def pme_bernoulli_average(
    leaf: jax.Array,  # [m, ...] node-stacked leaf
    key: jax.Array,   # the leaf's threefry key, raw (uint32[2]) or typed
    a: jax.Array,     # [m, m] selection, A[j, i] = j in N_i^k
    p,
) -> jax.Array:
    """PME average of one leaf under masks `jax.random.bernoulli(key, p,
    leaf.shape)`, drawn inside the kernel: only for selected senders, and
    never written to memory."""
    if jax.dtypes.issubdtype(key.dtype, jax.dtypes.prng_key):
        key = jax.random.key_data(key)
    view = leaf.reshape(_view(leaf.shape)).swapaxes(0, 1)
    out = pme_bernoulli_average_pallas(view, key, a, p, interpret=_on_cpu())
    return out.swapaxes(0, 1).reshape(leaf.shape)
