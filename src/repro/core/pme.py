"""Partial Message Exchange (PME) — Algorithm 2 of the PaME paper.

Every selected neighbor j of node i transmits only s_j randomly chosen
coordinates of w_j; node i averages coordinate l over the lambda_{i,l}
neighbors that sent it and fills missing coordinates from its own w_i.

Two mask samplers are provided:
  * "exact"     — s coordinates chosen uniformly *without replacement*
                  (the paper's scheme; Theorem 1 applies verbatim);
  * "bernoulli" — each coordinate kept i.i.d. with prob p = s/n
                  (same mean traffic, used for very large parameter leaves
                  where an argsort over n is wasteful).

The aggregation itself is written as dense masked matmuls over the node
axis, with every node's leaves on one device.  On an accelerator, large
leaves route through the fused Pallas kernels of
`repro.kernels.pme_average`; under bernoulli masks the kernel draws each
selected sender's mask in the same pass as the average.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

__all__ = [
    "sample_coordinate_masks",
    "sample_neighbor_selection",
    "sample_neighbor_selection_padded",
    "pme_average",
    "pme_average_pytree",
    "pme_average_pytree_padded",
    "naive_average",
    "message_bits",
    "leaf_rates",
    "tree_message_bits",
]


# leaves at least this large route through the fused Pallas kernels
# (kernels.pme_average) on an accelerator; smaller ones stay on the einsum.
_KERNEL_MIN_ELEMS = 1 << 17

# jax.monitoring event recorded when a traced exchange draws bernoulli masks
# inside the fused kernel, with the leaves and coordinates it routes there
FUSED_MASK_EVENT = "/repro/pme/fused_mask"


def sample_coordinate_masks(
    key: jax.Array,
    m: int,
    n: int,
    s: int,
    mode: str = "exact",
) -> jax.Array:
    """Per-sender coordinate masks M: [m, n] bool, |M_j| = s (exact mode).

    Node j draws T_j^k subset of [n] with |T_j^k| = s, uniformly without
    replacement, independently across nodes (Setup 1.3).
    """
    if mode == "exact":
        if s >= n:  # dense exchange (s = n): every coordinate is sent
            return jnp.ones((m, n), bool)
        u = jax.random.uniform(key, (m, n))
        # keep the s smallest entries per row: one O(n log s) top_k pass on
        # -u instead of two full argsorts (selects the same set of
        # coordinates as the rank-based formulation for any draw of u).
        _, idx = jax.lax.top_k(-u, s)
        rows = jnp.arange(m)[:, None]
        return jnp.zeros((m, n), bool).at[rows, idx].set(True)
    elif mode == "bernoulli":
        p = s / n
        return jax.random.bernoulli(key, p, (m, n))
    raise ValueError(f"unknown mask mode {mode!r}")


def sample_neighbor_selection_padded(
    key: jax.Array,
    nbrs: jax.Array,  # [m, d] padded neighbor ids
    valid: jax.Array,  # [m, d] bool
    t: jax.Array,  # [m] int — t_i = floor(nu_i * |N_i|), >= 1
    comm_mask: jax.Array,  # [m] bool — k in K_i?
    survivors: Optional[jax.Array] = None,  # [m, d] bool — realized edges
) -> jax.Array:
    """Random neighbor selection N_i^k (Alg. 1 line 5) in padded form.

    Returns sel: [m, d] bool where sel[i, slot] marks nbrs[i, slot] as a
    selected neighbor of receiver i this round.  Rows of non-communicating
    receivers are all-zero — the "local parameter tracking" branch (Alg. 1
    line 9) with no per-node cond.  Same PRNG draws as the dense variant,
    which is just this selection scattered into an [m, m] matrix.

    Under a dynamic-network scenario, `survivors` restricts selection to
    the step's realized edge set (`Realization.edge_alive`): dropped links
    and offline neighbors can never be picked, and a receiver with fewer
    than t_i surviving neighbors simply pulls from all of them.
    """
    if survivors is not None:
        valid = valid & survivors
    m, d = nbrs.shape
    u = jax.random.uniform(key, (m, d))
    u = jnp.where(valid, u, jnp.inf)  # never pick padding
    # receiver i keeps its t_i smallest draws: a single top_k pass over the
    # (small) padded-degree axis, then scatter "position < t_i" back through
    # the sort order — picks the same neighbors as the double-argsort rank
    # formulation without materialising two full sorts.
    _, order = jax.lax.top_k(-u, d)  # ascending u per row
    take = jnp.arange(d)[None, :] < t[:, None]
    sel = jnp.zeros((m, d), bool).at[jnp.arange(m)[:, None], order].set(take)
    sel = sel & valid  # [m, d] — receiver i picks these
    return sel & comm_mask[:, None]


def sample_neighbor_selection(
    key: jax.Array,
    nbrs: jax.Array,  # [m, d] padded neighbor ids
    valid: jax.Array,  # [m, d] bool
    t: jax.Array,  # [m] int — t_i = floor(nu_i * |N_i|), >= 1
    comm_mask: jax.Array,  # [m] bool — k in K_i?
    survivors: Optional[jax.Array] = None,  # [m, d] bool — realized edges
) -> jax.Array:
    """Random neighbor selection N_i^k (Alg. 1 line 5) as a matrix A.

    Returns A: [m, m] float where A[j, i] = 1 iff node j is a selected
    neighbor of receiver i this round (column i describes N_i^k).  Columns
    of non-communicating receivers are all-zero, which makes every
    coordinate count lambda_{i,l} = 0 and PME fall back to w_i — exactly
    the "local parameter tracking" branch (Alg. 1 line 9).  `survivors`
    restricts selection to a scenario's realized edge set.
    """
    m, d = nbrs.shape
    sel = sample_neighbor_selection_padded(
        key, nbrs, valid, t, comm_mask, survivors=survivors
    )
    # edge-list scatter into dense A[sender, receiver]: m·d scalar adds
    # instead of the old [m, d, m] one-hot einsum, whose O(m²·d) operand
    # dominated memory at large m.  Padding slots scatter sel=False (0.0)
    # onto A[i, i], an additive no-op (a node is never its own neighbor,
    # so the true diagonal is 0).
    rows = jnp.broadcast_to(jnp.arange(m, dtype=nbrs.dtype)[:, None], (m, d))
    return (
        jnp.zeros((m, m), jnp.float32)
        .at[nbrs, rows]
        .add(sel.astype(jnp.float32))
    )


def pme_average(
    w: jax.Array,  # [m, n] node-stacked parameters
    masks: jax.Array,  # [m, n] bool per-sender coordinate masks
    a: jax.Array,  # [m, m] selection matrix, A[j, i] = j in N_i^k
    own: Optional[jax.Array] = None,  # [m, n] receiver's own view (default w)
) -> jax.Array:
    """Count-weighted PME average — Alg. 2 line 6, Eq. (6)/(7).

    v_bar[i, l] = sum_{j in N_i^k, l in T_j} w[j, l] / lambda_{i,l}
    with fallback own[i, l] (= w[i, l] unless overridden) when
    lambda_{i,l} = 0.
    """
    wm = jnp.where(masks, w, 0.0)
    agg = jnp.einsum("jn,ji->in", wm, a)  # sum of received coords
    cnt = jnp.einsum("jn,ji->in", masks.astype(w.dtype), a)  # lambda_{i,l}
    return jnp.where(cnt > 0, agg / jnp.maximum(cnt, 1.0), w if own is None else own)


def naive_average(
    w: jax.Array,
    masks: jax.Array,
    a: jax.Array,
) -> jax.Array:
    """The *biased* strawman of Theorem 1: divide by |N_i^k| instead of
    lambda_{i,l}.  Expectation is (s/n) * mean — kept for tests/benchmarks."""
    wm = jnp.where(masks, w, 0.0)
    agg = jnp.einsum("jn,ji->in", wm, a)
    t = jnp.maximum(a.sum(axis=0), 1.0)  # |N_i^k| per receiver
    return agg / t[:, None]


def pme_average_pytree(
    key: jax.Array,
    params: object,  # pytree with [m, ...] leaves
    a: jax.Array,
    p,  # float, or per-leaf rate sequence (tree partition — see leaf_rates)
    mode: str = "bernoulli",
    self_params: Optional[object] = None,
) -> object:
    """Apply PME leaf-wise to a node-stacked parameter pytree.

    Each leaf is treated as its own message segment with the same keep
    fraction p = s/n; the coordinate mask of sender j is regenerated from
    `key` fold_in'd with the leaf index, mirroring the seed-based wire
    format (only values + a seed move between nodes).  Passing a sequence
    of rates instead of a scalar gives each leaf its own keep fraction
    (the tree-partitioned exchange; order = tree_flatten leaf order).

    `self_params` overrides the receiver's *own* view: the lambda=0
    fallback reads from it instead of `params`.  The bounded-staleness
    path passes the delayed sender stack as `params` (what the network
    transports) and the fresh parameters as `self_params` (a node always
    knows its own current point) — delay then hits only communication,
    never the local fill.  None keeps the classic single-stack semantics.
    """
    leaves, treedef = jax.tree_util.tree_flatten(params)
    self_leaves = (
        [None] * len(leaves) if self_params is None
        else jax.tree_util.tree_flatten(self_params)[0]
    )
    m = leaves[0].shape[0]
    per_leaf = isinstance(p, (tuple, list))
    out = []
    fused = []  # coordinates of the leaves whose masks the kernel draws
    for idx, leaf in enumerate(leaves):
        own = self_leaves[idx]
        p_i = p[idx] if per_leaf else p
        with jax.named_scope("pme.mask"):
            lkey = jax.random.fold_in(key, idx)
            if mode == "exact":
                flat = leaf.reshape(m, -1)
                n = flat.shape[1]
                s = max(1, int(round(p_i * n)))
                masks = sample_coordinate_masks(lkey, m, n, s, mode="exact")
            elif mode == "bernoulli" and _draws_in_kernel(leaf, lkey, own):
                masks = None
            else:
                masks = jax.random.bernoulli(lkey, p_i, leaf.shape)
        with jax.named_scope("pme.average"):
            if masks is None:
                from repro.kernels.pme_average.ops import pme_bernoulli_average

                fused.append(leaf.size)
                out.append(pme_bernoulli_average(leaf, lkey, a, p_i))
            else:
                out.append(_average_leaf(leaf, masks, a, own, mode))
    if fused:
        jax.monitoring.record_event(
            FUSED_MASK_EVENT, leaves=len(fused), coordinates=sum(fused)
        )
    return jax.tree_util.tree_unflatten(treedef, out)


def _accelerator() -> bool:
    """Whether the kernels run compiled: on the CPU they exist only in the
    (much slower) interpret mode, so the einsum paths are taken there."""
    return jax.default_backend() != "cpu"


def _draws_in_kernel(leaf, key, own) -> bool:
    """Whether a bernoulli leaf's masks are drawn inside the fused kernel,
    bit for bit `jax.random.bernoulli(key, p, leaf.shape)`, and never
    materialised: on an accelerator, for a leaf of at least
    `_KERNEL_MIN_ELEMS` and fewer than 2^32 coordinates whose every node
    fits the kernel's block, with the fallback read from the leaf itself,
    under the partitionable threefry PRNG (the one whose draw of a
    coordinate depends on that coordinate's index alone)."""
    if own is not None or not _accelerator():
        return False
    if not _KERNEL_MIN_ELEMS <= leaf.size < 1 << 32:
        return False
    if jax.dtypes.issubdtype(key.dtype, jax.dtypes.prng_key):
        impl = str(jax.random.key_impl(key))
    else:
        impl = jax.config.jax_default_prng_impl
    if impl != "threefry2x32" or not jax.config.jax_threefry_partitionable:
        return False
    from repro.kernels.pme_average.ops import bernoulli_fits

    return bernoulli_fits(leaf)


def _average_leaf(leaf, masks, a, own, mode: str):
    """One leaf of `pme_average_pytree`: the count-weighted average of the
    masked senders, with the receiver's `own` view (the leaf itself when
    None) where no sender covers a coordinate."""
    m = leaf.shape[0]
    if mode == "exact":
        flat = leaf.reshape(m, -1)
        from repro.core.mixing import default_impl

        if own is None and (
            default_impl() == "pallas"
            or (flat.size >= _KERNEL_MIN_ELEMS and _accelerator())
        ):
            # hot path: fused Pallas kernel (1 HBM read + 1 write of the
            # [m, n] operand).  By size/backend gate, tiny leaves stay on
            # the einsum path — kernel launch overhead dominates — and so
            # does CPU, where the kernel only exists in (much slower)
            # interpret mode.  REPRO_GOSSIP_IMPL="pallas" overrides both
            # gates so the whole dense-exchange path runs through the
            # kernel (interpret on CPU) alongside the fused gossip
            # contraction.  (The kernel computes the fallback from `w`
            # internally, so a self-view override routes through the
            # einsum instead.)
            from repro.kernels.pme_average.ops import (
                pme_average as pme_average_fused,
            )

            avg = pme_average_fused(flat, masks, a)
        elif own is None:
            # positional-only call: drop-in average variants (e.g. the
            # naive_average ablation) need not know about `own`
            avg = pme_average(flat, masks, a)
        else:
            avg = pme_average(flat, masks, a, own=own.reshape(m, -1))
        return avg.reshape(leaf.shape)
    # No reshape: keep the leaf's trailing structure intact; only the
    # node axis is contracted.
    # Operands stay in the leaf dtype (bf16 at model scale) with f32
    # accumulation — counts <= m are exactly representable.
    mask_t = masks.astype(leaf.dtype)
    a_t = a.astype(leaf.dtype)
    agg = jnp.einsum(
        "j...,ji->i...", leaf * mask_t, a_t,
        preferred_element_type=jnp.float32,
    )
    cnt = jnp.einsum(
        "j...,ji->i...", mask_t, a_t, preferred_element_type=jnp.float32
    )
    return jnp.where(
        cnt > 0, (agg / jnp.maximum(cnt, 1.0)).astype(leaf.dtype),
        leaf if own is None else own,
    )


def pme_average_pytree_padded(
    key: jax.Array,
    params: object,  # pytree with [m, ...] leaves
    nbrs: jax.Array,  # [m, d] padded neighbor ids
    sel: jax.Array,   # [m, d] bool — sample_neighbor_selection_padded output
    p,  # float, or per-leaf rate sequence (tree partition)
    mode: str = "bernoulli",
    pad: Optional[jax.Array] = None,  # [m, d] bool — structural padding
    impl: Optional[str] = None,       # gossip contraction (see core.mixing)
    self_params: Optional[object] = None,
) -> object:
    """PME applied leaf-wise through the padded neighbor-exchange form.

    Same estimator as `pme_average_pytree` with a dense selection matrix —
    v_bar[i, l] = sum over selected neighbors of masked w[j, l] / count,
    falling back to w[i, l] where the count is zero — but the node-axis
    contraction runs through the shared `repro.core.mixing.gather_terms`
    core over the d = max_degree slots: O(m·deg·n) instead of the
    O(m²·n) einsum, with the payload sum and the lambda_{i,l} coordinate
    counts aggregated in one slot walk (two gathers per slot).
    Coordinate masks are drawn exactly as in the dense path (fold_in per
    leaf), so the two agree to fp tolerance for the same key.
    `self_params` overrides the receiver's lambda=0 fallback view exactly
    as in `pme_average_pytree` (delay hits only communication).
    """
    from repro.core.mixing import gather_terms

    leaves, treedef = jax.tree_util.tree_flatten(params)
    self_leaves = (
        leaves if self_params is None
        else jax.tree_util.tree_flatten(self_params)[0]
    )
    m, d = nbrs.shape
    sel_f = sel.astype(jnp.float32)
    per_leaf = isinstance(p, (tuple, list))
    out = []
    for idx, leaf in enumerate(leaves):
        own = self_leaves[idx]
        shape = leaf.shape
        p_i = p[idx] if per_leaf else p
        with jax.named_scope("pme.mask"):
            lkey = jax.random.fold_in(key, idx)
            if mode == "exact":
                flat = leaf.reshape(m, -1)
                n = flat.shape[1]
                s = max(1, int(round(p_i * n)))
                masks = sample_coordinate_masks(lkey, m, n, s, mode="exact")
            else:
                flat = leaf
                masks = jax.random.bernoulli(lkey, p_i, shape)
        with jax.named_scope("pme.average"):
            if mode == "exact":
                payload = jnp.where(masks, flat, 0.0)
            else:
                payload = flat * masks.astype(flat.dtype)
            mask_f = masks.astype(jnp.float32)
            agg, cnt = gather_terms(
                nbrs,
                [(sel_f, payload.astype(jnp.float32)), (sel_f, mask_f)],
                pad=pad, impl=impl,
            )
            fallback = flat if self_params is None else own.reshape(flat.shape)
            avg = jnp.where(
                cnt > 0, (agg / jnp.maximum(cnt, 1.0)).astype(flat.dtype), fallback
            )
            out.append(avg.reshape(shape))
    return jax.tree_util.tree_unflatten(treedef, out)


def message_bits(s: int, n: int, value_bits: int = 64) -> int:
    """Eq. (8): transmitting a sparse vector costs (value_bits-1)*s + n bits
    (s payload values + an n-bit occupancy pattern); 64-bit gives 63s + n.

    value_bits=8 prices an int8 wire: full 8-bit payload values (no
    sign-bit folding), the n-bit occupancy pattern, plus one f32 absmax
    scale per message for dequantisation.
    """
    if value_bits == 8:
        return 8 * s + n + 32
    return (value_bits - 1) * s + n


def leaf_rates(num_leaves: int, p: float, p_leaf=None) -> Tuple[float, ...]:
    """Resolve the per-leaf transmission rates of a tree-partitioned message.

    ``p_leaf=None`` broadcasts the global rate p to every leaf; otherwise
    ``p_leaf`` must list one rate in (0, 1] per pytree leaf, in
    ``tree_flatten`` leaf order.
    """
    if p_leaf is None:
        rates = (float(p),) * num_leaves
    else:
        rates = tuple(float(r) for r in p_leaf)
        if len(rates) != num_leaves:
            raise ValueError(
                f"p_leaf has {len(rates)} rates but the model pytree has "
                f"{num_leaves} leaves"
            )
    for r in rates:
        if not 0.0 < r <= 1.0:
            raise ValueError(f"per-leaf transmission rate {r} outside (0, 1]")
    return rates


def tree_message_bits(sizes, rates, value_bits: int = 64) -> int:
    """Eq. (8) cost of one tree-partitioned message.

    Each pytree leaf is its own message segment: leaf of n_leaf coordinates
    at rate r carries s_leaf = max(1, round(r·n_leaf)) payload values plus
    its own n_leaf-bit occupancy pattern, so the total is
    sum_leaf message_bits(s_leaf, n_leaf).  This is what actually moves on
    the wire for a multi-leaf model — the flat formula
    message_bits(round(p·n_total), n_total) prices a single occupancy
    pattern over the concatenated vector, which no leaf-wise sampler emits.
    """
    if isinstance(rates, float):
        rates = (rates,) * len(sizes)
    if len(rates) != len(sizes):
        raise ValueError(
            f"got {len(rates)} rates for {len(sizes)} leaf sizes"
        )
    return sum(
        message_bits(max(1, int(round(r * n))), int(n), value_bits)
        for r, n in zip(rates, sizes)
    )
