"""PaME — Algorithm 1 of the paper, as a functional JAX step.

All m nodes are simulated inside one SPMD program: every state leaf carries
a leading node axis [m, ...].  Per-node randomness (neighbor selection,
coordinate masks, sub-batches) is counter-based via fold_in(step), so nodes
behave independently without a coordinator — the paper's "partially
synchronized" regime.

Update rule (lines 4–14):
    k in K_i:  v_i = PME(w_i, {w_j : j in N_i^k}),  N_i^k ~ U(N_i, t_i)
    else:      v_i = w_i
    w_i^{k+1}  = v_i - grad f_i(v_i; B_i^k) / (sigma_i^k * t_i)
    sigma_i^{k+1} = gamma_i * sigma_i^k

The non-communicating branch is realised by zeroing the receiver's column
of the selection matrix A, which drives every coordinate count to zero and
makes PME return w_i exactly — one fused code path, no per-node cond.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import engine, pme
from repro.core.topology import Topology

__all__ = [
    "PaMEConfig", "PaMEState", "TopologyArrays",
    "pame_init", "pame_step", "make_pame_runner", "run_pame",
]

# grad_fn(params_i, batch_i, key) -> (loss_i, grads_i); a counted one
# (pame_step's ``counted``) -> ((loss_i, {name: count}), grads_i)
GradFn = Callable[[object, object, jax.Array], Tuple[jax.Array, object]]


@dataclasses.dataclass(frozen=True)
class PaMEConfig:
    """Hyper-parameters of Algorithm 1 (paper Table II defaults)."""

    nu: float = 0.2          # participation rate nu_i
    p: float = 0.2           # transmission rate s/n
    gamma: float = 1.005     # penalty growth gamma_i > 1
    sigma0: float = 1.0      # initial penalty sigma_i^0
    kappa_lo: int = 3        # communication period interval [lo, hi]
    kappa_hi: int = 7
    mask_mode: str = "exact"  # "exact" (paper) | "bernoulli" (huge leaves)
    homogeneous_kappa: Optional[int] = None  # set to force kappa_i = k0
    mixing: str = "dense"    # node-axis contraction of the exchange:
                             # "dense" ([m, m] selection-matrix einsum) |
                             # "sparse" (padded neighbor gather, O(m·deg·n))
    partition: str = "flat"  # message format over a multi-leaf model:
                             # "flat" prices one concatenated vector (the
                             # paper's single-vector Eq. (8)); "tree" makes
                             # each pytree leaf its own message segment —
                             # per-leaf rates (p_leaf) and per-leaf Eq.-(8)
                             # accounting (sum over leaf occupancy patterns)
    p_leaf: Optional[Tuple[float, ...]] = None  # per-leaf transmission
                             # rates in tree_flatten order (tree partition
                             # only); None broadcasts the global p

    def __post_init__(self):
        if self.partition not in ("flat", "tree"):
            raise ValueError(
                f"unknown partition {self.partition!r}; pick 'flat' or 'tree'"
            )
        if self.p_leaf is not None:
            if self.partition != "tree":
                raise ValueError("p_leaf requires partition='tree'")
            # normalize to a hashable tuple: p_leaf sits in the registry's
            # static_hp_fields, which compares configs for equality
            object.__setattr__(
                self, "p_leaf", tuple(float(r) for r in self.p_leaf)
            )


class TopologyArrays(NamedTuple):
    """Device-side view of a Topology for use inside jit."""

    nbrs: jax.Array   # [m, d] padded neighbor ids
    valid: jax.Array  # [m, d] bool
    t: jax.Array      # [m] t_i = max(1, floor(nu_i |N_i|))
    kappa: jax.Array  # [m] per-node communication periods


class PaMEState(NamedTuple):
    params: object     # pytree, leaves [m, ...]
    sigma: jax.Array   # [m]
    step: jax.Array    # int32 scalar
    key: jax.Array     # PRNG key


def make_topology_arrays(
    topo: Topology, cfg: PaMEConfig, seed: int = 0
) -> TopologyArrays:
    nbrs, valid = topo.neighbor_matrix_padded()
    deg = topo.degrees
    t = np.maximum(1, np.floor(cfg.nu * deg)).astype(np.int32)
    rng = np.random.default_rng(seed)
    if cfg.homogeneous_kappa is not None:
        kappa = np.full(topo.m, cfg.homogeneous_kappa, dtype=np.int32)
    else:
        kappa = rng.integers(cfg.kappa_lo, cfg.kappa_hi + 1, topo.m).astype(np.int32)
    return TopologyArrays(
        nbrs=jnp.asarray(nbrs),
        valid=jnp.asarray(valid),
        t=jnp.asarray(t),
        kappa=jnp.asarray(kappa),
    )


def pame_init(key: jax.Array, params_stacked: object, m: int, cfg: PaMEConfig) -> PaMEState:
    """W^0 = 0 per Setup 1 is the caller's choice; any stacked init works
    as long as it lies in N(delta) (Lemma 3)."""
    del m
    leaves = jax.tree_util.tree_leaves(params_stacked)
    m_ = leaves[0].shape[0]
    return PaMEState(
        params=params_stacked,
        sigma=jnp.full((m_,), cfg.sigma0, dtype=jnp.float32),
        step=jnp.zeros((), jnp.int32),
        key=key,
    )


def _tree_scale_sub(base, grads, scale):
    """base - grads * scale[node] broadcast over trailing dims."""

    def one(b, g):
        s = scale.reshape((-1,) + (1,) * (b.ndim - 1))
        return b - g * s.astype(b.dtype)

    return jax.tree_util.tree_map(one, base, grads)


def pame_step(
    state: PaMEState,
    batch: object,  # pytree, leaves [m, ...] (per-node sub-batches B_i^k)
    grad_fn: GradFn,
    topo: TopologyArrays,
    cfg: PaMEConfig,
    realization: Optional[object] = None,  # scenarios.Realization — dynamic
    # network state for this step; restricts PME to surviving neighbors and
    # adds realized wire-bit metrics.  None keeps the static program as-is.
    self_params: Optional[object] = None,  # fresh self-view for the lambda=0
    # fill under bounded staleness: state.params then carries the delayed
    # sender stack (what the wire transports) while each node's own fill
    # reads its true current parameters.  None = classic single stack.
    delivered: Optional[jax.Array] = None,  # [m, d] bool — message-level
    # delivery mask (repro.core.faults).  A selected message is *sent* (and
    # charged) regardless; only delivered ones enter the average.  PME's
    # count normalization keeps the realized averaging row-stochastic under
    # arbitrary asymmetric loss, with the lambda=0 fill as the limit case.
    counted: bool = False,  # grad_fn is counted: its counts, summed over
    # the nodes, join the round's metrics (a MoE model's ``expert_rows``)
) -> Tuple[PaMEState, dict]:
    m = topo.nbrs.shape[0]
    sparse = cfg.mixing == "sparse"
    if cfg.partition == "tree":
        # tree-partitioned exchange: each leaf is its own message segment
        # with its own rate; a float keeps the flat code path bit-identical
        num_leaves = len(jax.tree_util.tree_leaves(state.params))
        rate = pme.leaf_rates(num_leaves, cfg.p, cfg.p_leaf)
    else:
        rate = cfg.p
    if not sparse and delivered is not None:
        raise NotImplementedError(
            "message-level delivery masks need mixing='sparse' "
            "(padded selection); the dense selection matrix has no "
            "per-slot delivery channel"
        )

    # The round's work is named by scope (pame.select, pame.exchange,
    # pame.local_step, pame.update, pame.metrics) so that a device trace can
    # be split by layer; scopes are metadata and leave the program as it is.
    with jax.named_scope("pame.select"):
        k_sel, k_mask, k_data = (
            jax.random.fold_in(state.key, state.step * 3 + i) for i in range(3)
        )
        comm_mask = (state.step % topo.kappa) == 0  # k in K_i
        survivors = None
        if realization is not None:
            # offline / straggling receivers skip the exchange entirely; the
            # sender side is filtered through the realized edge set below.
            comm_mask = comm_mask & realization.participating
            survivors = realization.edge_alive
        if sparse:
            # padded neighbor-exchange: never materialise the [m, m] selection
            # matrix; gather over max_degree slots instead (same PRNG draws).
            sel = pme.sample_neighbor_selection_padded(
                k_sel, topo.nbrs, topo.valid, topo.t, comm_mask, survivors=survivors
            )
            n_messages = jnp.sum(sel.astype(jnp.int32))
            sel_recv = sel if delivered is None else sel & delivered
        else:
            a = pme.sample_neighbor_selection(
                k_sel, topo.nbrs, topo.valid, topo.t, comm_mask, survivors=survivors
            )
            n_messages = jnp.sum(a).astype(jnp.int32)

    with jax.named_scope("pame.exchange"):
        if sparse:
            v_bar = pme.pme_average_pytree_padded(
                k_mask, state.params, topo.nbrs, sel_recv, rate,
                mode=cfg.mask_mode, pad=~topo.valid, self_params=self_params,
            )
        else:
            v_bar = pme.pme_average_pytree(
                k_mask, state.params, a, rate, mode=cfg.mask_mode,
                self_params=self_params,
            )

    with jax.named_scope("pame.local_step"):
        node_keys = jax.random.split(k_data, m)
        losses, grads = jax.vmap(grad_fn)(v_bar, batch, node_keys)
        counts = {}
        if counted:
            losses, counts = losses

    with jax.named_scope("pame.update"):
        stepsize = 1.0 / (state.sigma * topo.t.astype(jnp.float32))
        new_params = _tree_scale_sub(v_bar, grads, stepsize)
        new_state = PaMEState(
            params=new_params,
            sigma=state.sigma * cfg.gamma,
            step=state.step + 1,
            key=state.key,
        )

    with jax.named_scope("pame.metrics"):
        # consensus error ||W - Pi||_F^2 (metric of Lemma 6)
        def _cons(leaf):
            mean = leaf.mean(axis=0, keepdims=True)
            return jnp.sum((leaf - mean) ** 2)

        consensus = sum(jax.tree_util.tree_leaves(
            jax.tree_util.tree_map(_cons, new_params)
        ))
        metrics = {
            "loss_mean": jnp.mean(losses),
            "consensus": consensus,
            "comm_nodes": jnp.sum(comm_mask.astype(jnp.int32)),
            "sigma_mean": jnp.mean(new_state.sigma),
            **{name: jnp.sum(c) for name, c in counts.items()},
        }
        if not sparse:
            # senders some receiver selected: the only ones whose masks the
            # fused bernoulli kernel draws (a zero row of A is never sent)
            metrics["senders_drawn"] = jnp.sum(jnp.any(a != 0, axis=1).astype(jnp.int32))
        if realization is not None:
            # realized Eq.-(8) accounting: each selected surviving neighbor
            # transmits one sparse message of 64-bit values.  Flat partition
            # prices one concatenated vector of s = round(p·n_total)
            # coordinates; tree partition sums the per-leaf segments (their
            # own s_leaf + occupancy pattern each).
            sizes = [
                int(np.prod(leaf.shape[1:]))
                for leaf in jax.tree_util.tree_leaves(state.params)
            ]
            if cfg.partition == "tree":
                bits = pme.tree_message_bits(sizes, rate)
            else:
                n_total = sum(sizes)
                s = max(1, int(round(cfg.p * n_total)))
                bits = pme.message_bits(s, n_total)
            metrics["wire_bits"] = n_messages.astype(jnp.float32) * float(bits)
    return new_state, metrics


def _stack_params(params0: object, m: int) -> object:
    return jax.tree_util.tree_map(
        lambda x: jnp.broadcast_to(x[None], (m,) + x.shape), params0
    )


def make_pame_runner(
    grad_fn: GradFn,
    topo: Topology,
    cfg: PaMEConfig,
    *,
    objective_fn: Optional[Callable[[object], jax.Array]] = None,
    tol_std: float = 1e-3,
    chunk_size: int = engine.DEFAULT_CHUNK_SIZE,
    seed: int = 0,
) -> Callable:
    """Build a reusable scan-fused PaME driver (see `repro.core.engine`).

    Returns ``run(key, params0, m, batch_fn, num_steps) -> (state, history)``.
    The compiled chunk executables persist on the runner, so a warm-up call
    followed by a timed call measures steady-state step cost.
    """
    topo_arrays = make_topology_arrays(topo, cfg, seed=seed)

    def step_fn(state, batch):
        return pame_step(state, batch, grad_fn, topo_arrays, cfg)

    runner = engine.make_scan_runner(
        step_fn,
        objective_fn=objective_fn,
        tol_std=tol_std,
        chunk_size=chunk_size,
    )

    def run(key, params0, m, batch_fn, num_steps):
        state = pame_init(key, _stack_params(params0, m), m, cfg)
        state, metrics, info = runner(state, batch_fn, num_steps)
        history = engine.history_from(metrics, info, {
            "loss": "loss_mean",
            "objective": "objective",
            "consensus": "consensus",
        })
        return state, history

    return run


def run_pame(
    key: jax.Array,
    params0: object,  # single-node pytree; will be stacked m times
    m: int,
    grad_fn: GradFn,
    batch_fn: Callable[[int], object],  # step -> per-node batch pytree [m,...]
    topo: Topology,
    cfg: PaMEConfig,
    num_steps: int = 200,
    objective_fn: Optional[Callable[[object], jax.Array]] = None,
    tol_std: float = 1e-3,
    seed: int = 0,
    driver: str = "scan",
    chunk_size: int = engine.DEFAULT_CHUNK_SIZE,
) -> Tuple[PaMEState, dict]:
    """Run PaME with the paper's termination rule:
    stop when std{f(w^{k-2}), f(w^{k-1}), f(w^k)} < tol_std.

    driver="scan" (default) runs `chunk_size` steps per dispatch through the
    fused `lax.scan` engine with donated state and device-side metric
    buffers; driver="host" is the original one-step-per-dispatch reference
    loop, kept for equivalence testing.
    """
    if driver == "scan":
        run = make_pame_runner(
            grad_fn, topo, cfg, objective_fn=objective_fn, tol_std=tol_std,
            chunk_size=chunk_size, seed=seed,
        )
        return run(key, params0, m, batch_fn, num_steps)
    if driver != "host":
        raise ValueError(f"unknown driver {driver!r}")

    topo_arrays = make_topology_arrays(topo, cfg, seed=seed)
    state = pame_init(key, _stack_params(params0, m), m, cfg)
    step = jax.jit(
        lambda s, b: pame_step(s, b, grad_fn, topo_arrays, cfg)
    )
    history = {"loss": [], "objective": [], "consensus": []}
    f_window: list = []
    for k in range(num_steps):
        batch = batch_fn(k)
        state, metrics = step(state, batch)
        history["loss"].append(float(metrics["loss_mean"]))
        history["consensus"].append(float(metrics["consensus"]))
        if objective_fn is not None:
            mean_params = jax.tree_util.tree_map(
                lambda x: x.mean(axis=0), state.params
            )
            fval = float(objective_fn(mean_params))
            history["objective"].append(fval)
            f_window.append(fval)
            if len(f_window) >= 3 and float(np.std(f_window[-3:])) < tol_std:
                break
    history["steps_run"] = len(history["loss"])
    # one schema across drivers: the host loop dispatches exactly the steps
    # it runs (no chunk rounding past an early termination).
    history["steps_dispatched"] = history["steps_run"]
    return state, history
