"""Plain reference of PaME rounds (Algorithm 1, with the PME average of
Algorithm 2), written from the paper and the traffic file's settings.

The deployment's graph and communication periods follow from the traffic
file's ``deployment_seed``: an Erdos-Renyi G(m, p) drawn with numpy's
``default_rng(seed)`` until connected, t_i = max(1, floor(nu * deg_i)),
kappa_i drawn by a fresh ``default_rng(seed)`` from [kappa_lo, kappa_hi].
Every node draws its own initial weights (``initial``).  Round k, from the
run's state key:

- receivers with k mod kappa_i = 0 communicate; each picks the t_i of its
  neighbours with the smallest uniform draw (key fold_in(key, 3k), one
  draw per slot of the neighbour list padded to the largest degree);
- every sender keeps each coordinate of each parameter leaf with
  probability p (key fold_in(fold_in(key, 3k + 1), leaf index), one draw
  per coordinate of the node-stacked leaf);
- v_i = the count-weighted mean of the coordinates received, w_i where
  none was; then w_i = v_i - grad f_i(v_i) / (sigma_i t_i) and
  sigma_i *= gamma.

The parameters are held in the dtype the configuration states for each
leaf; the exchange and the update are computed in float32 and end rounded
to it, as stored parameters are.  The loss is computed in the parameters'
dtype as the model's reference does (``dtype="float32"`` computes it in
float32 at the highest matmul precision instead: a witness, not the
reference).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference.common import F32, identity


@dataclasses.dataclass(frozen=True)
class Deployment:
    adjacency: np.ndarray  # [m, m] int
    nbrs: np.ndarray       # [m, d] neighbour ids, padded with the node's own id
    valid: np.ndarray      # [m, d] bool
    t: np.ndarray          # [m] int
    kappa: np.ndarray      # [m] int

    @property
    def m(self) -> int:
        return self.adjacency.shape[0]

    def communicating(self, k: int) -> np.ndarray:
        return (k % self.kappa) == 0


def _connected(a: np.ndarray) -> bool:
    seen = {0}
    todo = [0]
    while todo:
        for v in np.nonzero(a[todo.pop()])[0]:
            if int(v) not in seen:
                seen.add(int(v))
                todo.append(int(v))
    return len(seen) == a.shape[0]


def deployment(traffic: dict, m: int) -> Deployment:
    if traffic["topology"] != "erdos_renyi":
        raise ValueError(f"reference has no topology {traffic['topology']!r}")
    seed = traffic["deployment_seed"]
    rng = np.random.default_rng(seed)
    for _ in range(1000):
        upper = rng.random((m, m)) < traffic["edge_p"]
        a = np.triu(upper, k=1).astype(np.int64)
        a = a + a.T
        if _connected(a):
            break
    else:
        raise RuntimeError("no connected graph drawn")
    deg = a.sum(axis=1)
    d = int(deg.max())
    nbrs = np.tile(np.arange(m)[:, None], (1, d))
    valid = np.zeros((m, d), bool)
    for i in range(m):
        ns = np.nonzero(a[i])[0]
        nbrs[i, : len(ns)] = ns
        valid[i, : len(ns)] = True
    t = np.maximum(1, np.floor(traffic["nu"] * deg)).astype(np.int64)
    kappa = np.random.default_rng(seed).integers(
        traffic["kappa_lo"], traffic["kappa_hi"] + 1, m)
    return Deployment(a, nbrs, valid, t, kappa)


def selection(key, dep: Deployment, k: int) -> np.ndarray:
    """A[j, i] = 1 where receiver i pulls from sender j in round k."""
    u = np.asarray(jax.random.uniform(jax.random.fold_in(key, 3 * k), dep.nbrs.shape))
    u = np.where(dep.valid, u, np.inf)
    comm = dep.communicating(k)
    a = np.zeros((dep.m, dep.m), np.float32)
    for i in range(dep.m):
        if comm[i]:
            for slot in np.argsort(u[i])[: dep.t[i]]:
                if dep.valid[i, slot]:
                    a[dep.nbrs[i, slot], i] = 1.0
    return a


@jax.jit
def _mask(key, w, p):
    return jax.random.bernoulli(key, p, w.shape)


@functools.partial(jax.jit, donate_argnums=0)
def _receive(out, w, mask, senders, i):
    """Row i of the PME average: the count-weighted mean over the senders
    of receiver i (``senders`` = column i of A) of the coordinates each
    kept, w_i where none was."""
    to = senders.reshape((-1,) + (1,) * (w.ndim - 1))
    agg = jnp.sum(to * jnp.where(mask, w, 0.0), axis=0)
    cnt = jnp.sum(to * mask, axis=0)
    return out.at[i].set(
        jnp.where(cnt > 0, agg / jnp.maximum(cnt, 1.0), w[i]).astype(out.dtype))


def _exchange(key, w, a, p):
    """PME average of one node-stacked leaf, one receiver at a time so that
    no more than the leaf, its mask and the result are held."""
    mask = _mask(key, w, p)
    out = jnp.zeros_like(w)
    for i in range(w.shape[0]):
        out = _receive(out, w, mask, a[:, i], i)
    return out


@functools.partial(jax.jit, static_argnums=(2, 3))
def _mask_at(key, index, shape, p):
    """The sender masks of a node-stacked leaf of ``shape``, at the flat
    coordinates ``index`` of each node: [m, len(index)]."""
    return jax.random.bernoulli(key, p, shape).reshape(shape[0], -1)[:, index]


def initializer(family, sizes: dict, m: int):
    """weight key -> the m nodes' initial weights, in one jitted call: node
    i draws its own from key i of ``split(key, m)``."""
    return jax.jit(lambda key: jax.vmap(lambda k: family.init(k, sizes))(
        jax.random.split(key, m)))


def sample_index(seed: int, sizes: dict, count: int) -> dict:
    """For each leaf (path -> coordinates a node holds), ``count`` flat
    coordinates drawn from ``seed`` (all of them where the leaf is
    smaller)."""
    rng = np.random.default_rng(seed)
    return {path: (np.arange(n) if n <= count else np.sort(rng.integers(0, n, count)))
            .astype(np.int32) for path, n in sorted(sizes.items())}


class Rounds:
    """PaME rounds of one configuration under one traffic mix.

    ``compute`` rounds every matrix product's operands (identity: the
    reference; a scaled cast: the lower-precision control).  ``fault``
    plants one of the faults the check must catch: ``"half_batch"`` (the
    loss over the first half of each node's rows) or ``"no_exchange"``
    (v_i = w_i).  ``dtype`` is the dtype the loss is computed in (None:
    each leaf's own)."""

    def __init__(self, family, sizes: dict, traffic: dict, m: int,
                 compute: Callable = identity, fault: Optional[str] = None,
                 dtype: Optional[str] = None):
        self.family, self.sizes, self.traffic = family, sizes, traffic
        self.dep = deployment(traffic, m)
        self.q, self.fault = compute, fault
        self._initial = initializer(family, sizes, m)
        precision = "highest" if dtype == "float32" else None

        def node_loss(params, tokens):
            with jax.default_matmul_precision(precision):
                return family.loss(params, sizes, tokens, compute)

        def update(leaf, i, g, step):
            """Node i's row of a stacked leaf, updated in place, computed in
            the dtype the loss is."""
            dt = leaf.dtype if dtype is None else jnp.dtype(dtype)
            new = leaf[i].astype(dt) - g.astype(dt) * step.astype(dt)
            return leaf.at[i].set(new.astype(leaf.dtype))

        self._grad = jax.jit(jax.value_and_grad(node_loss))
        self._cast = (lambda tree: tree) if dtype is None else jax.jit(
            lambda tree: jax.tree_util.tree_map(lambda x: x.astype(dtype), tree))
        self._update = jax.jit(update, donate_argnums=0)

    def initial(self, weight_key):
        return self._initial(jnp.asarray(weight_key, jnp.uint32))

    def schedule(self, rounds: int) -> list:
        """The number of communicating nodes in each round."""
        return [int(self.dep.communicating(k).sum()) for k in range(rounds)]

    def run(self, state_key, weight_key, tokens, rounds: int,
            index: Optional[dict] = None) -> dict:
        """Per-round node-mean loss and, given ``index`` (path -> flat
        coordinates), the nodes' parameters there after the rounds."""
        tr, dep, m = self.traffic, self.dep, self.dep.m
        key = jnp.asarray(state_key, jnp.uint32)
        named, treedef = jax.tree_util.tree_flatten_with_path(self.initial(weight_key))
        paths = [jax.tree_util.keystr(p) for p, _ in named]
        w = [leaf for _, leaf in named]
        del named
        sigma = np.full(m, tr["sigma0"], np.float32)
        out = {"loss": [], "comm_nodes": self.schedule(rounds)}
        for k in range(rounds):
            comm = dep.communicating(k)
            if self.fault != "no_exchange" and comm.any():
                a = jnp.asarray(selection(key, dep, k))
                mkey = jax.random.fold_in(key, 3 * k + 1)
                for j in range(len(w)):  # leaf by leaf, the old one freed
                    w[j] = _exchange(jax.random.fold_in(mkey, j), w[j], a, tr["p"])
            losses = []
            for i in range(m):
                v = self._cast(jax.tree_util.tree_unflatten(treedef, [leaf[i] for leaf in w]))
                toks = jnp.asarray(tokens[k][i])
                if self.fault == "half_batch":
                    toks = toks[: toks.shape[0] // 2]
                loss, g = self._grad(v, toks)
                del v
                losses.append(float(loss))
                step = jnp.float32(1.0 / (sigma[i] * np.float32(dep.t[i])))
                for j, gl in enumerate(jax.tree_util.tree_leaves(g)):
                    w[j] = self._update(w[j], i, gl, step)
                del g
            out["loss"].append(float(np.mean(losses)))
            sigma = sigma * np.float32(tr["gamma"])
        if index is not None:
            out["sample"] = {p: np.asarray(leaf.reshape(m, -1)[:, index[p]], np.float32)
                             for p, leaf in zip(paths, w)}
        return out

    def exchange_alone(self, state_key, weight_key, rounds: int, index: dict) -> dict:
        """The nodes' parameters at the coordinates ``index`` (path -> flat
        coordinates) where they start and after ``rounds`` rounds of the
        exchange alone, with no local step: path -> (start, end), each
        float32 [m, len(index[path])]."""
        tr, dep, m = self.traffic, self.dep, self.dep.m
        key = jnp.asarray(state_key, jnp.uint32)
        leaves = jax.tree_util.tree_flatten_with_path(self.initial(weight_key))[0]
        paths = [jax.tree_util.keystr(p) for p, _ in leaves]
        kinds = [(x.shape, x.dtype) for _, x in leaves]
        start = [np.asarray(x.reshape(m, -1)[:, index[p]], np.float32)
                 for p, (_, x) in zip(paths, leaves)]
        del leaves
        now = list(start)
        for k in range(rounds):
            if not dep.communicating(k).any():
                continue
            a = selection(key, dep, k)
            mkey = jax.random.fold_in(key, 3 * k + 1)
            for j, (path, (shape, dtype)) in enumerate(zip(paths, kinds)):
                mask = np.asarray(_mask_at(jax.random.fold_in(mkey, j),
                                           jnp.asarray(index[path]), shape, tr["p"]))
                cnt = a.T @ mask.astype(np.float32)
                agg = a.T @ np.where(mask, now[j], 0.0)
                avg = np.where(cnt > 0, agg / np.maximum(cnt, 1.0), now[j])
                now[j] = avg.astype(dtype).astype(np.float32)
        return {p: (s, e) for p, s, e in zip(paths, start, now)}
