"""Mixture-of-Experts block (DeepSeek-V2: shared experts + routed top-k),
dropless, computing the part of the result that the experts held here give.

A layer's router spans all ``n_experts``; the layer holds ``held_experts``
of them (experts ``first_expert`` on; expert parallelism puts the others on
other chips) and computes their part for every token routed to them.  No
capacity, no token dropped:

  1. ``moe.route``: router logits in float32, softmax, greedy top-k (the
     gate renormalised only under ``norm_topk_prob``), the balance loss;
     the (token, choice) pairs sorted by held expert and each held pair's
     token row gathered into its expert's group of the row buffer.  A
     group starts on a tile of ``ROW_TILE`` rows and takes whole tiles
     (its last tile zero-filled), so a grouped matmul visits one tile a
     group however the router splits the tokens, until a group outgrows a
     tile.  The buffer has the worst case's rows: all T*k pairs held, and
     each group's last tile nearly empty;
  2. ``moe.experts``: each held expert's SwiGLU over its own group, as
     grouped matmuls (the Pallas megablox kernels ``gmm``/``tgmm``, in
     interpret mode on the CPU) whose tiles stop at the last group.  Under
     ``vmap`` (one program for all DFL nodes) the nodes' groups merge into
     one call (``_merged``): the kernels take no batch axis;
  3. ``moe.combine``: each token's rows back in choice order, weighted by
     the gate and summed in float32;
  4. ``moe.shared``: the shared experts on every token.

What the experts held elsewhere would add is absent: on one chip the layer
runs without the exchange that expert parallelism adds.

Aux loss: DeepSeek-V2's sequence-wise balance loss over every expert,
alpha * mean_b sum_e f_be P_be, with f_be the share of sequence b's choices
that went to e times E / k and P_be the mean router probability of e over
b; the router is whole on every chip, so it is exact in a share.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental.pallas.ops.tpu.megablox.ops import backend as megablox

from repro.models.config import ModelConfig
from repro.models.layers import dense_init, linear
from repro.models.mlp import mlp_apply

__all__ = ["moe_init", "moe_apply", "grouped_swiglu", "DISPATCH_EVENT"]

# jax.monitoring event recorded when a MoE layer is traced: the experts it
# holds, the router's width, the choices a token makes and the row bound
DISPATCH_EVENT = "/repro/moe/dispatch"

F32 = jnp.float32


def moe_init(key: jax.Array, cfg: ModelConfig, dtype) -> dict:
    d, e, held, ffe = cfg.d_model, cfg.n_experts, cfg.held_experts, cfg.d_ff_expert
    ks = jax.random.split(key, 5)
    params = {
        "router": dense_init(ks[0], (d, e), dtype, fan_in=d),
        "w_gate": dense_init(ks[1], (held, d, ffe), dtype, fan_in=d),
        "w_up": dense_init(ks[2], (held, d, ffe), dtype, fan_in=d),
        "w_down": dense_init(ks[3], (held, ffe, d), dtype, fan_in=ffe),
    }
    if cfg.n_shared_experts:
        sff = cfg.n_shared_experts * ffe
        sk = jax.random.split(ks[4], 3)
        params["shared"] = {
            "w_gate": dense_init(sk[0], (d, sff), dtype),
            "w_up": dense_init(sk[1], (d, sff), dtype),
            "w_down": dense_init(sk[2], (sff, d), dtype),
        }
    return params


# ---------------------------------------------------------------------------
# grouped matmuls over row-ragged buffers (Pallas: megablox gmm / tgmm)
# ---------------------------------------------------------------------------
ROW_TILE = 256  # rows of a grouped-matmul tile; a group takes whole tiles
VMEM_BYTES = 12 * 2**20  # a kernel's blocks (double-buffered) and accumulator


def _widths(n: int) -> list:
    """Tile widths for a dimension of n, widest first: n itself, then those
    of 1024, 512, 256, 128 dividing it."""
    return [n] + [t for t in (1024, 512, 256, 128) if t < n and n % t == 0]


def _tiling(k: int, n: int, itemsize: int, outer: bool = False) -> tuple:
    """(rows, k, n) tile of a grouped matmul [R, k] x [k, n] (``outer``:
    [k, R] x [R, n] per group, accumulating [k, n]) with the most k x n
    whose blocks fit ``VMEM_BYTES``: fewer grid steps, each weight block
    read once a row tile."""
    def fits(tk, tn):
        blocks = ROW_TILE * tk + ROW_TILE * tn + tk * tn
        acc = tk * tn if outer else ROW_TILE * tn
        out = tk * tn if outer else 0
        return 2 * blocks * itemsize + 4 * acc + 2 * out * itemsize <= VMEM_BYTES

    best = max(((tk, tn) for tk in _widths(k) for tn in _widths(n) if fits(tk, tn)),
               key=lambda t: (t[0] * t[1], t[1]), default=(min(128, k), min(128, n)))
    return (ROW_TILE,) + best


def _interpret() -> bool:
    return jax.default_backend() == "cpu"


def _rows_dot(x, w, sizes, transpose: bool = False):
    """x [R, a] by group: the rows of group g times w[g] ([G, a, b], or
    [G, b, a] with ``transpose``); rows past the last group are zero."""
    n = w.shape[1] if transpose else w.shape[2]
    out = megablox.gmm(x, w, sizes, preferred_element_type=x.dtype,
                       tiling=_tiling(x.shape[1], n, x.dtype.itemsize),
                       transpose_rhs=transpose, interpret=_interpret())
    live = jnp.arange(x.shape[0]) < jnp.sum(sizes)
    return jnp.where(live[:, None], out, jnp.zeros((), out.dtype))


def _group_outer(x, y, sizes):
    """[G, a, b]: per group, x's rows [R, a] against y's rows [R, b]."""
    return megablox.tgmm(x.T, y, sizes, preferred_element_type=x.dtype,
                         tiling=_tiling(x.shape[1], y.shape[1], x.dtype.itemsize,
                                        outer=True),
                         interpret=_interpret())


def _merged(fn):
    """``fn(sizes [G], rows, groups) -> (rows, groups)``, with ``rows`` a
    tuple of row buffers [R, ...] whose rows past sum(sizes) are unused and
    ``groups`` a tuple of per-group arrays [G, ...].  Under ``vmap`` over M
    nodes the call is one call over M*G groups: each node's used rows are
    moved to the front, node after node, and the row results moved back."""

    @jax.custom_batching.custom_vmap
    def call(sizes, rows, groups):
        return fn(sizes, rows, groups)

    @call.def_vmap
    def _batched(axis_size, in_batched, sizes, rows, groups):
        def full(x, batched):
            return x if batched else jnp.broadcast_to(x, (axis_size,) + x.shape)

        sizes = full(sizes, in_batched[0])
        rows = tuple(full(x, b) for x, b in zip(rows, in_batched[1]))
        groups = tuple(full(x, b) for x, b in zip(groups, in_batched[2]))
        m, g = sizes.shape
        r = rows[0].shape[1]
        used = jnp.arange(r)[None, :] < jnp.sum(sizes, axis=1)[:, None]
        order = jnp.argsort(~used.reshape(-1), stable=True)
        back = jnp.argsort(order)

        def flat(x):
            return x.reshape((x.shape[0] * x.shape[1],) + x.shape[2:])

        rows_out, groups_out = call(
            sizes.reshape(m * g), tuple(flat(x)[order] for x in rows),
            tuple(flat(x) for x in groups))
        out = (tuple(y[back].reshape((m, r) + y.shape[1:]) for y in rows_out),
               tuple(w.reshape((m, g) + w.shape[1:]) for w in groups_out))
        return out, jax.tree_util.tree_map(lambda _: True, out)

    return call


def _silu_parts(hg):
    sig = jax.nn.sigmoid(hg.astype(F32))
    return hg.astype(F32) * sig, sig


@_merged
def _swiglu_rows(sizes, rows, groups):
    (x,), (wg, wu, wd) = rows, groups
    hg, hu = _rows_dot(x, wg, sizes), _rows_dot(x, wu, sizes)
    act = (_silu_parts(hg)[0] * hu.astype(F32)).astype(x.dtype)
    return (_rows_dot(act, wd, sizes),), ()


@_merged
def _swiglu_rows_bwd(sizes, rows, groups):
    """The gradients, the gate and up projections computed again rather
    than kept from the forward pass (two row buffers a layer less)."""
    (x, dy), (wg, wu, wd) = rows, groups
    hg, hu = _rows_dot(x, wg, sizes), _rows_dot(x, wu, sizes)
    silu, sig = _silu_parts(hg)
    act = (silu * hu.astype(F32)).astype(x.dtype)
    da = _rows_dot(dy, wd, sizes, transpose=True).astype(F32)
    dhg = (da * hu.astype(F32) * sig * (1 + hg.astype(F32) * (1 - sig))).astype(x.dtype)
    dhu = (da * silu).astype(x.dtype)
    dx = (_rows_dot(dhg, wg, sizes, transpose=True).astype(F32)
          + _rows_dot(dhu, wu, sizes, transpose=True).astype(F32)).astype(x.dtype)
    return (dx,), (_group_outer(x, dhg, sizes), _group_outer(x, dhu, sizes),
                   _group_outer(act, dy, sizes))


@jax.custom_vjp
def grouped_swiglu(x, w_gate, w_up, w_down, sizes):
    """Row r of ``x`` [R, d] through the SwiGLU of its group's expert
    (groups of ``sizes`` consecutive rows; rows past them give zeros)."""
    (y,), _ = _swiglu_rows(sizes, (x,), (w_gate, w_up, w_down))
    return y


def _grouped_swiglu_fwd(x, w_gate, w_up, w_down, sizes):
    return grouped_swiglu(x, w_gate, w_up, w_down, sizes), (x, w_gate, w_up, w_down, sizes)


def _grouped_swiglu_bwd(res, dy):
    x, w_gate, w_up, w_down, sizes = res
    (dx,), (dwg, dwu, dwd) = _swiglu_rows_bwd(
        sizes, (x, dy), (w_gate, w_up, w_down))
    return dx, dwg, dwu, dwd, None


grouped_swiglu.defvjp(_grouped_swiglu_fwd, _grouped_swiglu_bwd)


def _take(x, index):
    """Rows ``index`` of x, an index of len(x) giving a zero row."""
    return jnp.concatenate([x, jnp.zeros((1,) + x.shape[1:], x.dtype)])[index]


@jax.custom_vjp
def _move(x, src, dst):
    """Row r of the result is row ``src[r]`` of x (len(x): a zero row);
    ``dst`` is the inverse map (row i of x went to ``dst[i]``, or nowhere
    where it is out of range).  The gradient gathers the cotangent by
    ``dst``, where autodiff would scatter-add."""
    return _take(x, src)


def _move_fwd(x, src, dst):
    return _take(x, src), (src, dst)


def _move_bwd(res, ct):
    src, dst = res
    return _take(ct, dst), None, None


_move.defvjp(_move_fwd, _move_bwd)


def _group_rows(group, held: int):
    """The row buffer's layout for pairs whose held expert is ``group``
    ([P]; ``held`` for pairs of experts held elsewhere): (sizes [held] of
    whole tiles, src [R] the pair of each buffer row (P: none), dst [P] the
    buffer row of each pair (R: none)), each group starting on a tile."""
    pairs = group.shape[0]
    rows = -(-(pairs + held * (ROW_TILE - 1)) // ROW_TILE) * ROW_TILE
    order = jnp.argsort(group, stable=True)
    counts = jnp.bincount(group, length=held + 1)[:held]
    tiles = -(-counts // ROW_TILE)
    first = jnp.cumsum(counts) - counts                    # in sorted order
    start = (jnp.cumsum(tiles) - tiles) * ROW_TILE         # in the buffer
    sorted_group = group[order]
    g = jnp.minimum(sorted_group, held - 1)
    slot = jnp.where(sorted_group < held,
                     start[g] + jnp.arange(pairs) - first[g], rows)
    src = jnp.full((rows,), pairs, jnp.int32).at[slot].set(
        order.astype(jnp.int32), mode="drop")
    dst = jnp.zeros((pairs,), jnp.int32).at[order].set(slot.astype(jnp.int32))
    return (tiles * ROW_TILE).astype(jnp.int32), src, dst


# ---------------------------------------------------------------------------
# the layer
# ---------------------------------------------------------------------------
def moe_apply(
    params: dict, cfg: ModelConfig, x: jax.Array, first_expert: int = 0
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """x: [B, S, d] -> (y, aux_loss, rows): the held experts' part of the
    routed result plus the shared experts', the balance loss, and the
    number of (token, choice) pairs the held experts computed."""
    b, s, d = x.shape
    t = b * s
    e, k, held = cfg.n_experts, cfg.moe_top_k, cfg.held_experts
    xf = x.reshape(t, d)
    jax.monitoring.record_event(
        DISPATCH_EVENT, held=held, router=e, top_k=k, row_bound=t * k)

    with jax.named_scope("moe.route"):
        logits = linear(xf.astype(F32), params["router"].astype(F32))  # [T, E]
        probs = jax.nn.softmax(logits, axis=-1)
        gate, choice = jax.lax.top_k(probs, k)  # [T, K]
        if cfg.norm_topk_prob:
            gate = gate / jnp.sum(gate, axis=-1, keepdims=True)
        chosen = jnp.sum(jax.nn.one_hot(choice.reshape(b, s * k), e, dtype=F32), axis=1)
        balance = chosen * (e / (s * k)) * jnp.mean(probs.reshape(b, s, e), axis=1)
        aux = cfg.router_aux_coef * jnp.mean(jnp.sum(balance, axis=-1))

        local = choice.reshape(t * k) - first_expert
        group = jnp.where((local >= 0) & (local < held), local, held)
        sizes, src, dst = _group_rows(group, held)
        # pair p = token * k + choice: the token rows repeated k times
        rows = _move(jnp.repeat(xf, k, axis=0), src, dst)

    with jax.named_scope("moe.experts"):
        out = grouped_swiglu(rows, params["w_gate"], params["w_up"],
                             params["w_down"], sizes)

    with jax.named_scope("moe.combine"):
        per_choice = _move(out, dst, src).reshape(t, k, d)
        y = jnp.einsum("tkd,tk->td", per_choice.astype(F32), gate).astype(x.dtype)

    if cfg.n_shared_experts:
        with jax.named_scope("moe.shared"):
            y = y + mlp_apply(params["shared"], xf)
    return y.reshape(b, s, d), aux, jnp.sum(group < held)
