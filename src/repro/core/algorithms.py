"""Unified algorithm registry for decentralized FL.

The paper's headline claims are comparative (PaME vs D-PSGD / DFedSAM /
CHOCO-SGD / BEER / (AN)Q-NIDS, Figs. 8–10), yet each implementation used a
bespoke ``*_init``/``*_step`` signature that every harness hand-wired with
lambdas.  This module gives all six one contract:

  * :class:`Algorithm` — a named spec with per-algorithm hyperparameter
    dataclasses, ``init``/``step`` glue, per-step :func:`wire_bits`
    accounting (expected bits on the wire per step, network-wide), and
    ``params_of`` for reading the node-stacked parameters out of any state.
  * :func:`register` / :func:`get_algorithm` / :func:`list_algorithms` —
    the registry the launcher (``--algo``) and the benchmark race iterate.
  * :meth:`Algorithm.bind` — closes a spec over (grad_fn, topology, hps,
    mixing mode) and returns a :class:`BoundAlgorithm` whose ``step`` is
    engine-ready: run it through ``repro.core.engine`` scan chunks or the
    host loop via :meth:`BoundAlgorithm.run`.

Gossip in every bound baseline routes through ``repro.core.mixing``:
``mixing="sparse"`` (default) contracts the node axis in padded
neighbor-exchange form, O(m·deg·n); ``mixing="dense"`` is the
bit-identical full-connectivity escape hatch; ``mixing="matrix"`` keeps
the legacy dense einsum.

Extending::

    @dataclasses.dataclass(frozen=True)
    class MyHp:
        lr: float = 0.1

    register(Algorithm(
        name="mine", hp_cls=MyHp,
        init=lambda key, stacked, ctx, batch0: my_init(key, stacked),
        step=lambda state, batch, ctx: my_step(
            state, batch, ctx.grad_fn, ctx.mixer, ctx.hps.lr),
        wire_bits=lambda topo, hps, n: float(topo.degrees.sum()) * 64 * n,
    ))
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import baselines as B
from repro.core import engine
from repro.core import faults as flt_mod
from repro.core import pame as pame_mod
from repro.core import scenarios as scen_mod
from repro.core import temporal as temp_mod
from repro.core.compression import qsgd, rand_k
from repro.core.mixing import Mixer, make_mixer, ring_gather
from repro.core.pme import leaf_rates as pme_leaf_rates
from repro.core.pme import message_bits, tree_message_bits
from repro.core.topology import Topology
from repro.serve.events import PacedCarry, ServePacing

AnyScenario = Union[scen_mod.Scenario, temp_mod.TemporalScenario]

__all__ = [
    "Algorithm", "BoundAlgorithm", "BatchedAlgorithm", "AlgoContext",
    "register", "get_algorithm", "list_algorithms", "lane_finals",
    "PaMEHp", "DPSGDHp", "DFedSAMHp", "ChocoHp", "BeerHp", "AnqNidsHp",
]


# ---------------------------------------------------------------------------
# Per-algorithm hyperparameters.  PaME reuses its paper-Table-II config.
# ---------------------------------------------------------------------------
PaMEHp = pame_mod.PaMEConfig


@dataclasses.dataclass(frozen=True)
class DPSGDHp:
    lr: float = 0.1


@dataclasses.dataclass(frozen=True)
class DFedSAMHp:
    lr: float = 0.1
    rho: float = 0.05       # SAM ascent radius
    local_steps: int = 1


@dataclasses.dataclass(frozen=True)
class ChocoHp:
    lr: float = 0.05
    gossip_gamma: float = 0.3
    comp_frac: float = 0.3  # contractive rand-k keep fraction
    value_bits: int = 64


@dataclasses.dataclass(frozen=True)
class BeerHp:
    lr: float = 0.05
    gossip_gamma: float = 0.4
    comp_frac: float = 0.2
    value_bits: int = 64


@dataclasses.dataclass(frozen=True)
class AnqNidsHp:
    lr: float = 0.1
    qsgd_levels: int = 16


@dataclasses.dataclass(frozen=True)
class AlgoContext:
    """Everything a registered step needs beyond (state, batch)."""

    grad_fn: Callable
    topo: Topology
    hps: object
    mixer: Mixer
    extras: dict
    # grad_fn returns ((loss, {name: count}), grads) (``Algorithm.counts``)
    grad_counts: bool = False


@dataclasses.dataclass(frozen=True)
class Algorithm:
    """A registered DFL algorithm.

    ``init(key, params_stacked, ctx, batch0) -> state`` (``batch0`` is only
    consulted when ``needs_batch0``), ``step(state, batch, ctx) -> (state,
    metrics)`` with a ``loss_mean`` metric, ``wire_bits(topo, hps, n) ->
    float`` expected bits transmitted network-wide per *step* for an
    n-coordinate model, and ``params_of(state)`` the node-stacked pytree.
    """

    name: str
    hp_cls: type
    init: Callable
    step: Callable
    wire_bits: Callable
    params_of: Callable = staticmethod(lambda s: s.params)
    needs_batch0: bool = False
    # optional (topo, hps, sizes) -> float: per-leaf Eq.-(8) accounting for
    # algorithms whose wire format partitions over the model pytree;
    # ``sizes`` is the per-leaf coordinate count of the (unstacked) model
    # in tree_flatten order.  None falls back to wire_bits(topo, hps,
    # sum(sizes)) wherever the leaf structure is known.
    wire_bits_sizes: Optional[Callable] = None
    # optional (topo, hps, mixing, seed) -> dict merged into ctx.extras
    setup: Optional[Callable] = None
    # optional (hps, n) -> bits per realized *directed* edge per step; used
    # by dynamic-network scenario runs to charge only surviving links.
    # Algorithms whose step emits its own "wire_bits" metric (PaME) or that
    # send nothing leave this None.
    edge_bits: Optional[Callable] = None
    # hyperparameter fields that shape the traced program (payload sizes,
    # python loop counts, wire formats): bind_batched refuses configs that
    # differ in these — they cannot share one compiled sweep.
    static_hp_fields: Tuple[str, ...] = ()
    # fields realized into device arrays by `setup` (e.g. PaME's nu /
    # kappa_* -> TopologyArrays): configs may differ in them without the
    # scalar itself entering the trace — the stacked per-config extras
    # carry the difference.
    setup_hp_fields: Tuple[str, ...] = ()
    # the step consumes the delayed-delivery extras itself
    # (``fresh_params`` fresh self-view + ``delivered`` message masks)
    # instead of the wrapper's post-hoc innovation re-add — PaME's
    # memoryless exchange needs no mean bookkeeping.
    handles_delay: bool = False
    # optional replicated variants for fault-injected binds (surrogate-
    # memory algorithms): ``rep_init(key, stacked, ctx, batch0, arrays)``
    # and ``rep_step(state, batch, ctx)`` reading the FaultRealization
    # from ``ctx.extras["fault"]`` (see ``repro.core.faults``).
    rep_init: Optional[Callable] = None
    rep_step: Optional[Callable] = None
    # the step takes a counted grad_fn (``bind(grad_counts=True)``) and sums
    # its counts over the nodes into the round's metrics
    counts: bool = False

    def _check_counts(self, grad_counts: bool):
        if grad_counts and not self.counts:
            raise ValueError(f"{self.name} does not take a counted grad_fn")

    def bind(
        self,
        grad_fn: Callable,
        topo: Topology,
        hps: Optional[object] = None,
        *,
        mixing: str = "sparse",
        seed: int = 0,
        scenario: Optional[AnyScenario] = None,
        faults: Optional[flt_mod.FaultModel] = None,
        pacing: Optional[ServePacing] = None,
        grad_counts: bool = False,
    ) -> "BoundAlgorithm":
        """Close the spec over (grad_fn, topology, hps, mixing, scenario).

        ``scenario=None`` or a static scenario keeps the existing
        fixed-``Topology`` program exactly (bit-identical); a dynamic
        scenario wraps the step so each global step k realizes its own
        doubly-stochastic mixing matrix on device (see
        ``repro.core.scenarios``), freezes dropped nodes' state, and logs
        realized per-step ``wire_bits``.  A ``TemporalScenario``
        (``repro.core.temporal``) additionally threads Markov link/node
        state and the bounded-staleness snapshot ring through the
        engine's auxiliary carry slot; its step signature grows to
        ``step(state, batch, k, aux) -> (state, metrics, aux)``.

        A non-static ``faults`` model (``repro.core.faults``) layers
        message-level failures over the (possibly static) base scenario:
        per-direction loss, lossy-link bursts, delayed delivery and
        transient crashes, with per-receiver renormalized weights and —
        for algorithms registered with replicated variants — per-receiver
        surrogate replicas with wire-charged ack/repair resync.  The step
        signature is the temporal one (aux carries the ``FaultCarry``).
        A zero-rate ``FaultModel`` binds the plain fault-free program,
        bit-identical to ``faults=None``.

        ``pacing`` (``repro.serve.events.ServePacing``) layers the
        serve-while-train event clock over the (possibly static) base
        scenario: per-round request arrivals queue against each node,
        and a node whose backlog exceeds the defer threshold *defers its
        gossip exchange* that round exactly like a scenario straggler
        (local update still applied, self-loop in B^k — mean-preserving
        by construction).  The event clock threads through the engine's
        auxiliary carry slot (``PacedCarry``), composing with a bound
        ``FaultModel`` whose carry rides in the ``inner`` slot.  A
        zero-rate pacing binds the plain unpaced program, bit-identical
        to ``pacing=None``.
        """
        self._check_counts(grad_counts)
        hps = self.hp_cls() if hps is None else hps
        if not isinstance(hps, self.hp_cls):
            raise TypeError(
                f"{self.name} expects {self.hp_cls.__name__}, got {type(hps).__name__}"
            )
        extras = dict(self.setup(topo, hps, mixing, seed)) if self.setup else {}
        if "hps" in extras:  # setup may rewrite hps (e.g. PaME's mixing field)
            hps = extras.pop("hps")
        mixer = make_mixer(topo, "matrix" if mixing == "matrix" else mixing)
        ctx = AlgoContext(grad_fn=grad_fn, topo=topo, hps=hps, mixer=mixer,
                          extras=extras, grad_counts=grad_counts)
        if faults is not None and faults.is_static:
            faults = None  # zero-rate model == the fault-free program
        if pacing is not None and pacing.is_static:
            pacing = None  # zero-rate process == the unpaced program
        if faults is not None or pacing is not None:
            if isinstance(scenario, temp_mod.TemporalScenario):
                what = "faults" if faults is not None else "pacing"
                raise NotImplementedError(
                    f"{what} cannot stack on a TemporalScenario: fold the "
                    "staleness into FaultModel(delay=..., max_delay=...) "
                    "and the link/node dynamics into a base Scenario"
                )
            base = scenario if scenario is not None else scen_mod.Scenario(
                name="static")
            return BoundAlgorithm(
                self, ctx, scenario=base,
                scen_arrays=scen_mod.make_scenario_arrays(topo, base),
                mixing_mode=mixing, faults=faults, pacing=pacing,
            )
        if scenario is not None and not scenario.is_static:
            return BoundAlgorithm(
                self, ctx, scenario=scenario,
                scen_arrays=scen_mod.make_scenario_arrays(topo, scenario),
                mixing_mode=mixing,
            )
        return BoundAlgorithm(self, ctx)

    def bind_batched(
        self,
        grad_fn: Callable,
        topo: Topology,
        hps_list: Optional[Sequence[object]] = None,
        *,
        seeds: Sequence[int] = (0,),
        mixing: str = "sparse",
        seed: int = 0,
        scenario: Optional[AnyScenario] = None,
        faults: Optional[flt_mod.FaultModel] = None,
        pacing: Optional[ServePacing] = None,
        grad_counts: bool = False,
    ) -> "BatchedAlgorithm":
        """Close the spec over S seeds × C configs as ONE lane-batched step.

        The returned :class:`BatchedAlgorithm` runs every (seed, config)
        cell of the grid as one lane of a single jitted scan
        (``engine.make_scan_runner(lanes=L)``): per-lane PRNG streams
        enter through per-lane state keys (lane (s, c) reproduces the
        unbatched ``bind(hps_c)`` run under ``PRNGKey(s)`` to fp
        tolerance), per-config hyperparameters enter either as traced
        per-lane scalars (float fields: lr, gamma, sigma0, ...) or
        through per-config device arrays stacked out of ``setup`` (PaME's
        nu / kappa draws via ``TopologyArrays``), and the whole grid
        compiles once instead of once per cell.

        Fields named in ``static_hp_fields`` shape the traced program
        (payload sizes, loop counts) and must therefore be equal across
        ``hps_list`` — differing values raise.  Lane order is
        config-major: ``lane = c * len(seeds) + s``.

        A dynamic ``scenario`` is supported: each lane folds its seed
        into the scenario key, so different seeds draw independent
        network sample paths (and the same seed under different configs
        sees the same path — paired comparisons).  A non-static
        ``faults`` model likewise folds each lane's seed into the fault
        key — independent fault sample paths per seed, shared across
        configs; a non-static ``pacing`` folds each lane's seed into the
        arrival-process key the same way — independent request traces
        per seed, shared across configs.
        """
        self._check_counts(grad_counts)
        hps_list = [self.hp_cls() if h is None else h
                    for h in (hps_list or [None])]
        for h in hps_list:
            if not isinstance(h, self.hp_cls):
                raise TypeError(
                    f"{self.name} expects {self.hp_cls.__name__}, "
                    f"got {type(h).__name__}"
                )
        seeds = [int(s) for s in seeds]
        if not seeds:
            raise ValueError("bind_batched needs at least one seed")

        # per-config setup -> (effective hps, extras)
        extras_list, eff_hps = [], []
        for h in hps_list:
            extras = dict(self.setup(topo, h, mixing, seed)) if self.setup else {}
            if "hps" in extras:
                h = extras.pop("hps")
            extras_list.append(extras)
            eff_hps.append(h)
        hps0 = eff_hps[0]

        # classify differing hp fields: static -> refuse, setup-realized ->
        # carried by the stacked extras, float -> traced per-lane scalar
        swept: dict = {}
        for field in dataclasses.fields(self.hp_cls):
            vals = [getattr(h, field.name) for h in eff_hps]
            if all(v == vals[0] for v in vals[1:]):
                continue
            if field.name in self.static_hp_fields:
                raise ValueError(
                    f"{self.name}: hp field {field.name!r} shapes the traced "
                    f"program and must be equal across batched configs "
                    f"(got {vals})"
                )
            if field.name in self.setup_hp_fields:
                continue  # realized via the stacked setup extras
            if isinstance(vals[0], float) and not isinstance(vals[0], bool):
                swept[field.name] = np.asarray(vals, np.float32)
                continue
            raise ValueError(
                f"{self.name}: cannot batch over non-float hp field "
                f"{field.name!r} (got {vals}); sweep it across separate "
                "binds instead"
            )

        # split extras into per-config array stacks vs shared objects
        shared_extras: dict = {}
        stacked_extras: dict = {}
        for key in extras_list[0]:
            values = [ex[key] for ex in extras_list]
            leaves = jax.tree_util.tree_leaves(values[0])
            if leaves and all(
                isinstance(leaf, (jax.Array, np.ndarray))
                for v in values for leaf in jax.tree_util.tree_leaves(v)
            ):
                stacked_extras[key] = jax.tree_util.tree_map(
                    lambda *xs: jnp.stack([jnp.asarray(x) for x in xs]),
                    *values,
                )
            else:
                shared_extras[key] = values[0]

        mixer = make_mixer(topo, "matrix" if mixing == "matrix" else mixing)
        ctx0 = AlgoContext(grad_fn=grad_fn, topo=topo, hps=hps0, mixer=mixer,
                           extras=shared_extras, grad_counts=grad_counts)
        if faults is not None and faults.is_static:
            faults = None  # zero-rate model == the fault-free program
        if pacing is not None and pacing.is_static:
            pacing = None  # zero-rate process == the unpaced program
        scen_arrays = None
        if faults is not None or pacing is not None:
            if isinstance(scenario, temp_mod.TemporalScenario):
                what = "faults" if faults is not None else "pacing"
                raise NotImplementedError(
                    f"{what} cannot stack on a TemporalScenario: fold the "
                    "staleness into FaultModel(delay=..., max_delay=...) "
                    "and the link/node dynamics into a base Scenario"
                )
            if scenario is None:
                scenario = scen_mod.Scenario(name="static")
            scen_arrays = scen_mod.make_scenario_arrays(topo, scenario)
        elif scenario is not None and not scenario.is_static:
            scen_arrays = scen_mod.make_scenario_arrays(topo, scenario)
        elif scenario is not None:
            scenario = None  # static scenario == the fixed-Topology path
        return BatchedAlgorithm(
            self, ctx0, eff_hps, seeds, swept, stacked_extras,
            mixing_mode=mixing, scenario=scenario, scen_arrays=scen_arrays,
            faults=faults, pacing=pacing,
        )


class BoundAlgorithm:
    """An Algorithm closed over (grad_fn, topology, hps, mixer).

    ``step`` is a plain ``(state, batch) -> (state, metrics)`` closure,
    directly consumable by ``engine.make_scan_runner`` or ``jax.jit``.
    When a dynamic scenario is bound, ``step`` instead takes ``(state,
    batch, k)`` — the global step index realizes the step's network — and
    the engine must be built with ``step_takes_index=True`` (``run`` /
    ``make_runner`` do this automatically).  A ``TemporalScenario`` bind
    further extends the signature to ``step(state, batch, k, aux) ->
    (state, metrics, aux)``, where ``aux`` is the ``TemporalCarry``
    (Markov chain state + staleness ring) built by :meth:`aux_init` and
    threaded through the engine's auxiliary carry slot
    (``carries_aux=True``).
    """

    def __init__(
        self,
        spec: Algorithm,
        ctx: AlgoContext,
        scenario: Optional[AnyScenario] = None,
        scen_arrays: Optional[scen_mod.ScenarioArrays] = None,
        mixing_mode: str = "sparse",
        faults: Optional[flt_mod.FaultModel] = None,
        fault_key: Optional[jax.Array] = None,
        pacing: Optional[ServePacing] = None,
        pace_key: Optional[jax.Array] = None,
    ):
        self.spec = spec
        self.ctx = ctx
        self.scenario = scenario
        self.scen_arrays = scen_arrays
        self._mixing_mode = mixing_mode
        self.faults = faults
        if faults is not None and fault_key is None:
            fault_key = jax.random.PRNGKey(faults.seed)
        self.fault_key = fault_key
        self.pacing = pacing
        if pacing is not None and pace_key is None:
            pace_key = jax.random.PRNGKey(pacing.process.seed)
        self.pace_key = pace_key

    @property
    def name(self) -> str:
        return self.spec.name

    @property
    def hps(self) -> object:
        return self.ctx.hps

    @property
    def dynamic(self) -> bool:
        """True when a non-static scenario is bound (step takes k)."""
        return self.scenario is not None

    @property
    def temporal(self) -> bool:
        """True when the bound scenario is a TemporalScenario (step
        threads the auxiliary carry — run/make_runner pass it to the
        engine as ``carries_aux``)."""
        return isinstance(self.scenario, temp_mod.TemporalScenario)

    @property
    def faulty(self) -> bool:
        """True when a non-static FaultModel is bound (step threads the
        FaultCarry through the engine's auxiliary carry slot)."""
        return self.faults is not None

    @property
    def paced(self) -> bool:
        """True when a non-static ServePacing is bound (step threads the
        serve-event clock through the engine's auxiliary carry slot)."""
        return self.pacing is not None

    @property
    def carries_aux(self) -> bool:
        return self.temporal or self.faulty or self.paced

    @property
    def params_of(self) -> Callable:
        return self.spec.params_of

    def init(self, key: jax.Array, params_stacked: object,
             batch0: Optional[object] = None) -> object:
        if self.spec.needs_batch0 and batch0 is None:
            raise ValueError(f"{self.name} needs batch0 at init")
        if self.faulty and self.spec.rep_init is not None:
            return self.spec.rep_init(key, params_stacked, self.ctx, batch0,
                                      self.scen_arrays)
        return self.spec.init(key, params_stacked, self.ctx, batch0)

    def aux_init(self, state: object):
        """Initial auxiliary carry: the FaultCarry of a fault-injected
        bind, the TemporalCarry of a temporal bind (stationary Markov
        draws + the staleness ring seeded with the initial parameters),
        or — for a paced bind — a PacedCarry wrapping the fresh serve
        event clock around the inner FaultCarry (None when no faults)."""
        inner = None
        if self.faulty:
            inner = flt_mod.fault_carry_init(
                self.faults, self.scen_arrays, self.spec.params_of(state),
                self.fault_key,
            )
        if self.paced:
            return PacedCarry(
                events=self.pacing.init(self.scen_arrays.m, self.pace_key),
                inner=inner,
            )
        if inner is not None:
            return inner
        if not self.temporal:
            raise TypeError(f"{self.name} is not bound to a TemporalScenario")
        return temp_mod.temporal_carry_init(
            self.scenario, self.scen_arrays, self.spec.params_of(state)
        )

    def step(self, state: object, batch: object,
             k: Optional[jax.Array] = None,
             aux: Optional[object] = None):
        if not self.dynamic:
            return self.spec.step(state, batch, self.ctx)
        if k is None:
            raise TypeError(
                f"{self.name} is bound to scenario {self.scenario.name!r}: "
                "step(state, batch, k) needs the global step index"
            )
        if self.paced:
            if aux is None:
                raise TypeError(
                    f"{self.name} is bound to pacing "
                    f"{self.pacing.process.name!r}: step(state, batch, k, "
                    "aux) needs the PacedCarry (see aux_init)"
                )
            k = jnp.asarray(k, jnp.int32)
            new_ev, busy, ev_metrics = self.pacing.advance(aux.events, k)
            if self.faulty:
                new_state, metrics, new_inner = self._fault_step(
                    state, batch, k, aux.inner, extra_straggler=busy
                )
            else:
                new_state, metrics = self._dynamic_step(
                    state, batch, k, extra_straggler=busy
                )
                new_inner = None
            metrics.update(ev_metrics)
            return new_state, metrics, PacedCarry(new_ev, new_inner)
        if self.faulty:
            if aux is None:
                raise TypeError(
                    f"{self.name} is bound to fault model "
                    f"{self.faults.name!r}: step(state, batch, k, aux) "
                    "needs the FaultCarry (see aux_init)"
                )
            return self._fault_step(state, batch,
                                    jnp.asarray(k, jnp.int32), aux)
        if self.temporal:
            if aux is None:
                raise TypeError(
                    f"{self.name} is bound to temporal scenario "
                    f"{self.scenario.name!r}: step(state, batch, k, aux) "
                    "needs the TemporalCarry (see aux_init)"
                )
            return self._temporal_step(state, batch,
                                       jnp.asarray(k, jnp.int32), aux)
        return self._dynamic_step(state, batch, jnp.asarray(k, jnp.int32))

    def _realized_metrics(self, r: scen_mod.Realization, state: object,
                          metrics: dict) -> dict:
        """Realized wire accounting shared by the i.i.d. and temporal paths:
        algorithms without their own per-message metric are charged
        edge_bits on every realized directed edge."""
        if "wire_bits" not in metrics:
            n = sum(
                int(np.prod(leaf.shape[1:]))
                for leaf in jax.tree_util.tree_leaves(self.spec.params_of(state))
            )
            eb = self.spec.edge_bits(self.ctx.hps, n) if self.spec.edge_bits else 0.0
            metrics["wire_bits"] = (
                r.directed_edges.astype(jnp.float32) * float(eb)
            )
        metrics["alive_nodes"] = jnp.sum(r.alive.astype(jnp.int32))
        return metrics

    def _partition_metrics(self, k: jax.Array, new_state: object,
                           metrics: dict) -> dict:
        """Per-component consensus / mean-drift scalars when the bound
        scenario schedules partition windows: within-component
        disagreement (``comp_consensus``) and the between-component mean
        gap (``comp_mean_gap``) whose post-heal decay is the recovery
        headline.  A partition-free scenario adds nothing — the traced
        program is unchanged."""
        scen = self.scenario
        if not getattr(scen, "partitions", ()):
            return metrics
        comp = scen_mod.active_components(self.scen_arrays, k)
        x = jnp.concatenate([
            jnp.reshape(leaf, (leaf.shape[0], -1)).astype(jnp.float32)
            for leaf in jax.tree_util.tree_leaves(
                self.spec.params_of(new_state))
        ], axis=1)
        cc, gap = scen_mod.component_stats(comp, x, scen.max_parts)
        metrics["comp_consensus"] = cc
        metrics["comp_mean_gap"] = gap
        return metrics

    def _dynamic_step(self, state: object, batch: object, k: jax.Array,
                      extra_straggler: Optional[jax.Array] = None,
                      ) -> Tuple[object, dict]:
        """One step under the bound scenario (fully traceable).

        Realizes step k's graph from the folded scenario key, swaps the
        per-step mixer into the context, reverts dropped nodes' state
        bitwise, and charges only realized edges on the wire.
        ``extra_straggler`` (the pacing layer's busy mask) ORs into the
        scenario's straggler draw before the weights are built — same
        sample_masks PRNG discipline, so a no-op mask realizes the same
        matrix as the plain scenario path.
        """
        if extra_straggler is None:
            r = scen_mod.realize(self.scenario, self.scen_arrays, k)
        else:
            edge_up, alive, straggler = scen_mod.sample_masks(
                self.scenario, self.scen_arrays, k
            )
            r = scen_mod.realization_from_masks(
                self.scen_arrays, edge_up, alive,
                straggler | extra_straggler,
            )
        mixer = scen_mod.scenario_mixer(self.scen_arrays, r, self._mixing_mode)
        ctx_t = dataclasses.replace(
            self.ctx, mixer=mixer,
            extras={**self.ctx.extras, "realization": r},
        )
        new_state, metrics = self.spec.step(state, batch, ctx_t)
        new_state = scen_mod.freeze_dropped(r.alive, state, new_state)
        metrics = self._realized_metrics(r, state, metrics)
        return new_state, self._partition_metrics(k, new_state, metrics)

    def _temporal_step(self, state: object, batch: object, k: jax.Array,
                       aux: temp_mod.TemporalCarry):
        """One step under the bound TemporalScenario (fully traceable).

        Advances the Markov chains from the carried state, realizes the
        step's doubly-stochastic matrix with delayed stragglers still
        participating, and substitutes their ring-gathered t-delayed
        parameters into the exchange — message-only delay: receivers see
        the stale values, but a delayed node's *local compute* never
        waits.  Gradients are steered back to the fresh iterate via the
        ``grad_shift`` extra (fresh − delayed, zero rows for punctual
        nodes), and after the step each delayed node's private innovation
        (fresh − delayed) is re-added to its own row.  On the substituted
        stack ``mixed_j = B_jj·eff_j + Σ off-terms``, so the re-add makes
        the self-view ``B_jj·fresh_j + (1−B_jj)·(fresh_j − eff_j)`` on
        top of the off-diagonal terms: exactly the fresh self-view plus
        the (1−B_jj)-scaled innovation correction that restores the
        global parameter sum for every realized matrix.  Algorithms
        registered with ``handles_delay`` (PaME) instead consume the
        fresh stack directly (``fresh_params`` extra → the lambda=0 /
        uncovered-coordinate fallback) and skip the re-add —
        their exchange is memoryless, so there is no surrogate mean to
        rebalance.  Requires the algorithm state to carry its
        node-stacked parameters in a ``params`` field (all built-in
        registrations do).
        """
        new_ts, r, delayed, tau = temp_mod.advance(
            self.scenario, self.scen_arrays, aux.ts, k
        )
        mixer = scen_mod.scenario_mixer(self.scen_arrays, r, self._mixing_mode)
        extras = {**self.ctx.extras, "realization": r}
        d_max = self.scenario.staleness
        ring = aux.ring
        if d_max > 0:
            fresh = self.spec.params_of(state)
            slot = jnp.mod(k - tau, d_max)
            eff = ring_gather(ring, fresh, slot, delayed)
            state_in = state._replace(params=eff)
            if self.spec.handles_delay:
                extras["fresh_params"] = fresh
            else:
                # zero rows for punctual nodes: every gradient call point
                # becomes the undelayed iterate, no masking needed
                extras["grad_shift"] = jax.tree_util.tree_map(
                    lambda f, e: f - e, fresh, eff
                )
        else:
            state_in = state
        ctx_t = dataclasses.replace(self.ctx, mixer=mixer, extras=extras)
        new_state, metrics = self.spec.step(state_in, batch, ctx_t)
        if d_max > 0:
            if not self.spec.handles_delay:
                def _readd(p, f, e):
                    keep = delayed.reshape((-1,) + (1,) * (p.ndim - 1))
                    return p + jnp.where(keep, f - e, jnp.zeros_like(p))

                new_params = jax.tree_util.tree_map(
                    _readd, self.spec.params_of(new_state), fresh, eff
                )
                new_state = new_state._replace(params=new_params)
            ring = temp_mod.ring_push(ring, fresh, k, d_max)
            tgrid = jnp.arange(d_max + 1, dtype=jnp.int32)
            metrics["stale_hist"] = jnp.sum(
                (tau[:, None] == tgrid[None, :]) & r.participating[:, None],
                axis=0,
            ).astype(jnp.float32)
            metrics["stale_nodes"] = jnp.sum(delayed.astype(jnp.int32))
        new_state = scen_mod.freeze_dropped(r.alive, state, new_state)
        metrics = self._realized_metrics(r, state, metrics)
        return new_state, metrics, temp_mod.TemporalCarry(new_ts, ring)

    def _fault_step(self, state: object, batch: object, k: jax.Array,
                    aux: flt_mod.FaultCarry,
                    extra_straggler: Optional[jax.Array] = None):
        """One step under the bound FaultModel (fully traceable).

        Samples the base scenario masks, advances the fault Markov state
        (lossy-link bursts, crashes, delivery delays), draws the
        per-direction message losses, and realizes the *per-receiver
        renormalized* row-stochastic weights (``repro.core.faults``).
        Direct parameter mixers (D-PSGD / DFedSAM) gossip under those
        renormalized weights; algorithms registered with replicated
        variants run their ``rep_step`` — per-receiver surrogate replicas
        that desync on lost messages and resync through wire-charged
        repair traffic — and PaME consumes the delivery masks natively
        (``delivered`` extra: sent messages are charged, only delivered
        ones enter the count-normalized average).  Delayed delivery
        reuses the temporal snapshot ring with the same fresh-self-view
        semantics as :meth:`_temporal_step`; crashed nodes' state freezes
        bitwise (the local checkpoint they rejoin from).
        """
        fm = self.faults
        edge_up, alive, straggler = scen_mod.sample_masks(
            self.scenario, self.scen_arrays, k
        )
        if extra_straggler is not None:
            # the pacing layer's busy mask: a backlogged node defers its
            # exchange exactly like a scenario straggler
            straggler = straggler | extra_straggler
        new_fs, fr = flt_mod.advance_faults(
            fm, self.scen_arrays, aux.fs, self.fault_key, k,
            edge_up, alive, straggler,
        )
        r = fr.base
        use_rep = self.spec.rep_step is not None
        # the renormalized weights keep direct parameter mixing
        # row-stochastic under asymmetric loss; replicated steps and PaME
        # read the symmetric base weights / delivery masks from `fr`
        mixer = scen_mod.scenario_mixer(
            self.scen_arrays, r._replace(weights=fr.weights),
            self._mixing_mode,
        )
        extras = {**self.ctx.extras, "realization": r, "fault": fr,
                  "fault_arrays": self.scen_arrays,
                  "delivered": fr.recv_ok, "repair": fm.repair}
        d_max = fm.max_delay
        ring = aux.ring
        if d_max > 0:
            fresh = self.spec.params_of(state)
            slot = jnp.mod(k - fr.tau, d_max)
            eff = ring_gather(ring, fresh, slot, fr.delayed)
            state_in = state._replace(params=eff)
            if self.spec.handles_delay:
                extras["fresh_params"] = fresh
            else:
                extras["grad_shift"] = jax.tree_util.tree_map(
                    lambda f, e: f - e, fresh, eff
                )
        else:
            state_in = state
        if use_rep:
            n = sum(
                int(np.prod(leaf.shape[1:]))
                for leaf in jax.tree_util.tree_leaves(
                    self.spec.params_of(state))
            )
            extras["innov_bits"] = float(self.spec.edge_bits(self.ctx.hps, n))
        ctx_t = dataclasses.replace(self.ctx, mixer=mixer, extras=extras)
        step_fn = self.spec.rep_step if use_rep else self.spec.step
        new_state, metrics = step_fn(state_in, batch, ctx_t)
        if d_max > 0:
            if not self.spec.handles_delay:
                def _readd(p, f, e):
                    keep = fr.delayed.reshape((-1,) + (1,) * (p.ndim - 1))
                    return p + jnp.where(keep, f - e, jnp.zeros_like(p))

                new_params = jax.tree_util.tree_map(
                    _readd, self.spec.params_of(new_state), fresh, eff
                )
                new_state = new_state._replace(params=new_params)
            ring = temp_mod.ring_push(ring, fresh, k, d_max)
            metrics["stale_nodes"] = jnp.sum(fr.delayed.astype(jnp.int32))
        new_state = scen_mod.freeze_dropped(r.alive, state, new_state)
        metrics = self._realized_metrics(r, state, metrics)
        metrics = self._partition_metrics(k, new_state, metrics)
        metrics["col_defect"] = fr.col_defect
        metrics["mean_drift"] = new_fs.drift
        metrics["dropped_msgs"] = fr.dropped.astype(jnp.float32)
        metrics["crashed_nodes"] = jnp.sum(new_fs.crashed.astype(jnp.int32))
        return new_state, metrics, flt_mod.FaultCarry(new_fs, ring)

    def wire_bits(self, n: int) -> float:
        """Expected bits on the wire per step, summed over the network."""
        return float(self.spec.wire_bits(self.ctx.topo, self.ctx.hps, n))

    def wire_bits_for(self, params0: object) -> float:
        """Expected bits/step for a concrete model pytree: routes through
        the per-leaf ``wire_bits_sizes`` accounting when the algorithm
        registers one (tree-partitioned formats), else the flat formula."""
        sizes = tuple(
            int(np.prod(l.shape)) for l in jax.tree_util.tree_leaves(params0)
        )
        if self.spec.wire_bits_sizes is not None:
            return float(
                self.spec.wire_bits_sizes(self.ctx.topo, self.ctx.hps, sizes)
            )
        return self.wire_bits(sum(sizes))

    def make_runner(
        self,
        *,
        objective_fn: Optional[Callable] = None,
        tol_std: float = 1e-3,
        chunk_size: int = engine.DEFAULT_CHUNK_SIZE,
    ) -> Callable:
        """Persistent scan runner (compiled chunks cached across calls):
        ``run(key, params0, m, batch_fn, num_steps) -> (state, history)``."""
        runner = engine.make_scan_runner(
            self.step, objective_fn=objective_fn, params_of=self.spec.params_of,
            tol_std=tol_std, chunk_size=chunk_size,
            step_takes_index=self.dynamic, carries_aux=self.carries_aux,
        )

        def run(key, params0, m, batch_fn, num_steps):
            stacked = B.stack_params(params0, m)
            batch0 = batch_fn(0) if self.spec.needs_batch0 else None
            state = self.init(key, stacked, batch0)
            aux = self.aux_init(state) if self.carries_aux else None
            state, metrics, info = runner(state, batch_fn, num_steps, aux=aux)
            info = dict(info)
            info.pop("aux", None)
            history = {
                key_: [float(v) for v in vals]
                for key_, vals in metrics.items()
                if key_ != "stale_hist"
            }
            if "stale_hist" in metrics:
                history["staleness_hist"] = engine.staleness_hist(
                    metrics["stale_hist"]
                )
            history["loss"] = history.pop("loss_mean", [])
            history.update(info)
            self._account_wire(history, params0)
            return state, history

        return run

    def run(
        self,
        key: jax.Array,
        params0: object,
        m: int,
        batch_fn: Callable[[int], object],
        num_steps: int,
        *,
        objective_fn: Optional[Callable] = None,
        tol_std: float = 1e-3,
        driver: str = "scan",
        chunk_size: int = engine.DEFAULT_CHUNK_SIZE,
    ) -> Tuple[object, dict]:
        """One-shot race driver (scan or host), with wire accounting."""
        stacked = B.stack_params(params0, m)
        batch0 = batch_fn(0) if self.spec.needs_batch0 else None
        state = self.init(key, stacked, batch0)
        aux = self.aux_init(state) if self.carries_aux else None
        state, history = B.run_algorithm(
            self.step, state, batch_fn, num_steps,
            objective_fn=objective_fn, params_of=self.spec.params_of,
            tol_std=tol_std, driver=driver, chunk_size=chunk_size,
            step_takes_index=self.dynamic,
            carries_aux=self.carries_aux, aux=aux,
        )
        self._account_wire(history, params0)
        return state, history

    def _account_wire(self, history: dict, params0: object) -> None:
        per_step = history.get("wire_bits")
        if per_step:
            # dynamic scenario: only realized (surviving) edges were charged
            history["wire_bits_total"] = float(np.sum(per_step))
            history["wire_bits_per_step"] = (
                history["wire_bits_total"] / max(len(per_step), 1)
            )
            return
        history.pop("wire_bits", None)  # static runs keep the legacy schema
        history["wire_bits_per_step"] = self.wire_bits_for(params0)
        history["wire_bits_total"] = (
            history["wire_bits_per_step"] * history["steps_run"]
        )


class BatchedAlgorithm:
    """S seeds × C configs of one Algorithm as a single lane-batched step.

    Built by :meth:`Algorithm.bind_batched`.  ``step`` has the exact
    signature the engine expects of a lane-batched step — ``(state,
    batch[, k][, aux]) -> (state, metrics[, aux])`` with state leaves
    ``[L, m, ...]`` and per-step metric values ``[L]`` — implemented as a
    single ``jax.vmap`` over (state, per-lane hp scalars, per-config
    extras stacks[, per-lane scenario key, aux]); the batch and global
    step index broadcast.  ``run``/``make_runner`` drive it through
    ``engine.make_scan_runner(lanes=L)``: one compile for the whole
    grid, per-lane termination, per-lane metric buffers and wire-bit
    accounting.

    Lane order is config-major: ``lane = c * S + s`` — ``lane_config``
    / ``lane_seed`` in the returned history map lanes back to grid
    cells, and :func:`lane_finals` reduces a per-lane metric buffer at
    each lane's own stopping step.
    """

    def __init__(
        self,
        spec: Algorithm,
        ctx0: AlgoContext,
        hps_list: Sequence[object],
        seeds: Sequence[int],
        swept: dict,            # field -> [C] np.float32 of per-config values
        stacked_extras: dict,   # extras key -> pytree with leading [C] axis
        mixing_mode: str = "sparse",
        scenario: Optional[AnyScenario] = None,
        scen_arrays: Optional[scen_mod.ScenarioArrays] = None,
        faults: Optional[flt_mod.FaultModel] = None,
        pacing: Optional[ServePacing] = None,
    ):
        self.spec = spec
        self.ctx0 = ctx0
        self.hps_list = list(hps_list)
        self.seeds = list(seeds)
        self.scenario = scenario
        self.scen_arrays = scen_arrays
        self._mixing_mode = mixing_mode
        self.faults = faults
        self.pacing = pacing
        c, s = len(self.hps_list), len(self.seeds)
        self.lane_config = np.repeat(np.arange(c), s)       # [L]
        self.lane_seed = np.asarray(self.seeds * c)         # [L]
        # per-lane traced hp scalars (configs expanded over seeds)
        self._lane_hp = {
            f: jnp.asarray(vals[self.lane_config])
            for f, vals in swept.items()
        }
        # per-lane setup extras ([C, ...] stacks expanded over seeds)
        self._lane_extras = jax.tree_util.tree_map(
            lambda x: jnp.take(x, jnp.asarray(self.lane_config), axis=0),
            stacked_extras,
        )
        # per-lane PRNG: lane (s, c) starts from PRNGKey(s), exactly the
        # key an unbatched run for that seed would get
        self._lane_keys = jnp.stack(
            [jax.random.PRNGKey(int(s)) for s in self.lane_seed]
        )
        self._scen_keys = None
        if scen_arrays is not None:
            # per-seed network sample paths (shared across configs)
            self._scen_keys = jax.vmap(
                lambda s: jax.random.fold_in(scen_arrays.key, s)
            )(jnp.asarray(self.lane_seed, jnp.uint32))
        self._fault_keys = None
        if faults is not None:
            # per-seed fault sample paths (shared across configs)
            fk = jax.random.PRNGKey(faults.seed)
            self._fault_keys = jax.vmap(
                lambda s: jax.random.fold_in(fk, s)
            )(jnp.asarray(self.lane_seed, jnp.uint32))
        self._pace_keys = None
        if pacing is not None:
            # per-seed request traces (shared across configs)
            pk = jax.random.PRNGKey(pacing.process.seed)
            self._pace_keys = jax.vmap(
                lambda s: jax.random.fold_in(pk, s)
            )(jnp.asarray(self.lane_seed, jnp.uint32))

    # -- grid geometry ------------------------------------------------------
    @property
    def name(self) -> str:
        return self.spec.name

    @property
    def lanes(self) -> int:
        return len(self.hps_list) * len(self.seeds)

    @property
    def dynamic(self) -> bool:
        return self.scenario is not None

    @property
    def temporal(self) -> bool:
        return isinstance(self.scenario, temp_mod.TemporalScenario)

    @property
    def faulty(self) -> bool:
        return self.faults is not None

    @property
    def paced(self) -> bool:
        return self.pacing is not None

    @property
    def carries_aux(self) -> bool:
        return self.temporal or self.faulty or self.paced

    @property
    def params_of(self) -> Callable:
        return self.spec.params_of

    # -- lane plumbing ------------------------------------------------------
    def _lane_bound(self, hp_vals: dict, ex_arrays: dict,
                    scen_key: Optional[jax.Array],
                    fault_key: Optional[jax.Array] = None) -> BoundAlgorithm:
        """Rebuild the single-lane BoundAlgorithm inside the vmapped body:
        traced hp scalars replace the dataclass fields, the lane's slice
        of the stacked setup extras joins the shared ones.  The pacing
        spec is shared across lanes — each lane's event stream diverges
        through the per-lane key carried in its EventState."""
        hps = (dataclasses.replace(self.ctx0.hps, **hp_vals)
               if hp_vals else self.ctx0.hps)
        ctx = dataclasses.replace(
            self.ctx0, hps=hps, extras={**self.ctx0.extras, **ex_arrays}
        )
        scen_arrays = self.scen_arrays
        if scen_key is not None and scen_arrays is not None:
            scen_arrays = scen_arrays._replace(key=scen_key)
        return BoundAlgorithm(
            self.spec, ctx, scenario=self.scenario,
            scen_arrays=scen_arrays, mixing_mode=self._mixing_mode,
            faults=self.faults, fault_key=fault_key, pacing=self.pacing,
        )

    def init(self, params0: object, m: int,
             batch0: Optional[object] = None) -> object:
        """Lane-stacked initial state ([L, m, ...] leaves)."""
        stacked = B.stack_params(params0, m)

        def lane(key, hp_vals, ex_arrays):
            return self._lane_bound(hp_vals, ex_arrays, None).init(
                key, stacked, batch0
            )

        return jax.vmap(lane)(self._lane_keys, self._lane_hp,
                              self._lane_extras)

    def aux_init(self, state: object) -> object:
        """Lane-stacked auxiliary carry (FaultCarry, TemporalCarry, or a
        PacedCarry wrapping per-lane event clocks)."""
        if self.paced:
            m = self.scen_arrays.m

            def lane(st, scen_key, fkey, pkey):
                inner = None
                if self.faulty:
                    inner = flt_mod.fault_carry_init(
                        self.faults, self.scen_arrays._replace(key=scen_key),
                        self.spec.params_of(st), fkey,
                    )
                return PacedCarry(self.pacing.init(m, pkey), inner)

            return jax.vmap(lane)(state, self._scen_keys, self._fault_keys,
                                  self._pace_keys)
        if self.faulty:
            def lane(st, scen_key, fkey):
                return flt_mod.fault_carry_init(
                    self.faults, self.scen_arrays._replace(key=scen_key),
                    self.spec.params_of(st), fkey,
                )

            return jax.vmap(lane)(state, self._scen_keys, self._fault_keys)
        if not self.temporal:
            raise TypeError(f"{self.name} is not bound to a TemporalScenario")

        def lane(st, scen_key):
            return temp_mod.temporal_carry_init(
                self.scenario, self.scen_arrays._replace(key=scen_key),
                self.spec.params_of(st),
            )

        return jax.vmap(lane)(state, self._scen_keys)

    def step(self, state: object, batch: object,
             k: Optional[jax.Array] = None, aux: Optional[object] = None):
        """Lane-batched step — one vmap over the lane axis; the batch and
        the global step index broadcast to every lane."""

        def lane(st, hp_vals, ex_arrays, scen_key, fkey, ax):
            ba = self._lane_bound(hp_vals, ex_arrays, scen_key, fkey)
            if self.carries_aux:
                return ba.step(st, batch, k, ax)
            if self.dynamic:
                return ba.step(st, batch, k)
            return ba.step(st, batch)

        return jax.vmap(lane)(
            state, self._lane_hp, self._lane_extras, self._scen_keys,
            self._fault_keys, aux,
        )

    def wire_bits(self, n: int) -> float:
        """Expected bits/step (network-wide) of config 0 — the scalar the
        training log prints; per-lane accounting lives in the history."""
        return float(self.spec.wire_bits(self.ctx0.topo, self.hps_list[0], n))

    def wire_bits_for(self, params0: object) -> float:
        """Config-0 expected bits/step for a concrete model pytree (see
        :meth:`BoundAlgorithm.wire_bits_for`)."""
        sizes = tuple(
            int(np.prod(l.shape)) for l in jax.tree_util.tree_leaves(params0)
        )
        if self.spec.wire_bits_sizes is not None:
            return float(self.spec.wire_bits_sizes(
                self.ctx0.topo, self.hps_list[0], sizes
            ))
        return self.wire_bits(sum(sizes))

    # -- drivers ------------------------------------------------------------
    def make_runner(
        self,
        *,
        objective_fn: Optional[Callable] = None,
        tol_std: float = 1e-3,
        chunk_size: int = engine.DEFAULT_CHUNK_SIZE,
    ) -> Callable:
        """Persistent lane-batched scan runner:
        ``run(params0, m, batch_fn, num_steps) -> (state, history)`` with
        per-lane ``[steps, L]`` metric buffers in the history."""
        runner = engine.make_scan_runner(
            self.step, objective_fn=objective_fn,
            params_of=self.spec.params_of, tol_std=tol_std,
            chunk_size=chunk_size, step_takes_index=self.dynamic,
            carries_aux=self.carries_aux, lanes=self.lanes,
        )

        def run(params0, m, batch_fn, num_steps):
            batch0 = batch_fn(0) if self.spec.needs_batch0 else None
            state = self.init(params0, m, batch0)
            aux = self.aux_init(state) if self.carries_aux else None
            state, metrics, info = runner(state, batch_fn, num_steps,
                                          aux=aux)
            return state, self._assemble_history(metrics, info, params0)

        return run

    def run(
        self,
        params0: object,
        m: int,
        batch_fn: Callable[[int], object],
        num_steps: int,
        *,
        objective_fn: Optional[Callable] = None,
        tol_std: float = 1e-3,
        chunk_size: int = engine.DEFAULT_CHUNK_SIZE,
    ) -> Tuple[object, dict]:
        """One-shot batched grid run (see `make_runner`)."""
        return self.make_runner(
            objective_fn=objective_fn, tol_std=tol_std,
            chunk_size=chunk_size,
        )(params0, m, batch_fn, num_steps)

    def _assemble_history(self, metrics: dict, info: dict,
                          params0: object) -> dict:
        history = {k: np.asarray(v) for k, v in metrics.items()
                   if k != "stale_hist"}
        steps_run = np.asarray(info["steps_run"])
        if "stale_hist" in metrics:
            # [steps, L, D+1] -> per-lane run-level histogram [L, D+1],
            # each lane truncated at its own stopping step (a frozen lane
            # keeps emitting rows until the last dispatched chunk)
            rows = np.asarray(metrics["stale_hist"])
            history["staleness_hist"] = np.stack([
                rows[: steps_run[l], l].sum(axis=0)
                for l in range(self.lanes)
            ])
        if "loss_mean" in history:
            history["loss"] = history.pop("loss_mean")
        history["steps_run"] = steps_run
        history["steps_dispatched"] = info["steps_dispatched"]
        history["lane_config"] = self.lane_config
        history["lane_seed"] = self.lane_seed
        sizes = tuple(
            int(np.prod(leaf.shape))
            for leaf in jax.tree_util.tree_leaves(params0)
        )
        if "wire_bits" in history:
            # dynamic: per-step realized bits [steps, L], truncated per lane
            per = history["wire_bits"]
            total = np.array([
                per[: steps_run[l], l].sum() for l in range(self.lanes)
            ])
            history["wire_bits_total"] = total
            history["wire_bits_per_step"] = total / np.maximum(steps_run, 1)
        else:
            per_cfg = np.array([
                float(self.spec.wire_bits_sizes(self.ctx0.topo, h, sizes))
                if self.spec.wire_bits_sizes is not None
                else float(self.spec.wire_bits(self.ctx0.topo, h, sum(sizes)))
                for h in self.hps_list
            ])
            history["wire_bits_per_step"] = per_cfg[self.lane_config]
            history["wire_bits_total"] = (
                history["wire_bits_per_step"] * steps_run
            )
        return history


def lane_finals(history: dict, key: str = "objective") -> np.ndarray:
    """Per-lane final value of a batched metric buffer: entry l is
    ``history[key][steps_run[l] - 1, l]`` — each lane read at its own
    stopping step (the buffers run to the last dispatched chunk)."""
    buf = np.asarray(history[key])
    steps_run = np.asarray(history["steps_run"])
    lanes = buf.shape[1]
    return np.array([
        buf[max(int(steps_run[l]) - 1, 0), l] for l in range(lanes)
    ])


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------
_REGISTRY: dict = {}


def register(alg: Algorithm) -> Algorithm:
    if alg.name in _REGISTRY:
        raise ValueError(f"algorithm {alg.name!r} already registered")
    _REGISTRY[alg.name] = alg
    return alg


def get_algorithm(name: str) -> Algorithm:
    if name not in _REGISTRY:
        raise ValueError(
            f"unknown algorithm {name!r}; pick from {list(_REGISTRY)}"
        )
    return _REGISTRY[name]


def list_algorithms() -> Tuple[str, ...]:
    return tuple(_REGISTRY)


# ---------------------------------------------------------------------------
# Wire accounting helpers (Eq. (8) + per-algorithm message formats)
# ---------------------------------------------------------------------------
def _dense_edges_bits(topo: Topology, n: int, bits_per_msg: float) -> float:
    """Every node sends one message to every neighbor each step."""
    return float(topo.degrees.sum()) * bits_per_msg


# bits per *directed* edge per step for the gossip baselines; the static
# wire_bits formulas below are (base directed edge count) × these, and the
# dynamic scenario path charges (realized directed edge count) × these.
def _full_msg_bits(hps, n: int) -> float:
    return float(message_bits(n, n))


def _choco_edge_bits(hps, n: int) -> float:
    return float(rand_k(hps.comp_frac, hps.value_bits, rescale=False).bits(n))


def _beer_edge_bits(hps, n: int) -> float:
    # two compressed streams per edge per step (x and gradient surrogates)
    return 2.0 * _choco_edge_bits(hps, n)


def _anq_edge_bits(hps, n: int) -> float:
    return float(qsgd(hps.qsgd_levels).bits(n))


def _pame_msgs_per_step(topo: Topology, hps: PaMEHp) -> float:
    """Expected sparse messages on the wire per step: receiver i pulls t_i
    messages in the 1/kappa_i fraction of steps it communicates."""
    t = np.maximum(1, np.floor(hps.nu * topo.degrees))
    if hps.homogeneous_kappa is not None:
        inv_kappa = 1.0 / float(hps.homogeneous_kappa)
    else:
        ks = np.arange(hps.kappa_lo, hps.kappa_hi + 1, dtype=np.float64)
        inv_kappa = float(np.mean(1.0 / ks))
    return float(t.sum()) * inv_kappa


def _pame_wire_bits(topo: Topology, hps: PaMEHp, n: int) -> float:
    """Expected bits/step pricing one flat n-coordinate message of
    message_bits(s, n) per transmission.  The flat-partition formula;
    multi-leaf models route through _pame_wire_bits_sizes wherever the
    leaf structure is known."""
    s = max(1, int(round(hps.p * n)))
    return _pame_msgs_per_step(topo, hps) * message_bits(s, n)


def _pame_wire_bits_sizes(topo: Topology, hps: PaMEHp, sizes) -> float:
    """Expected bits/step for a concrete model pytree: flat partition keeps
    the single-vector formula exactly (bit-compatible history schema); tree
    partition sums the per-leaf Eq.-(8) segments at their p_leaf rates."""
    if hps.partition != "tree":
        return _pame_wire_bits(topo, hps, sum(sizes))
    rates = pme_leaf_rates(len(sizes), hps.p, hps.p_leaf)
    return _pame_msgs_per_step(topo, hps) * tree_message_bits(sizes, rates)


# ---------------------------------------------------------------------------
# Registrations — PaME + the five baselines of Figs. 8–10
# ---------------------------------------------------------------------------
def _pame_setup(topo, hps, mixing, seed):
    # the bind-level mixing mode governs the node-axis contraction
    mode = "sparse" if mixing == "sparse" else "dense"
    hps = dataclasses.replace(hps, mixing=mode)
    return {
        "hps": hps,
        "topo_arrays": pame_mod.make_topology_arrays(topo, hps, seed=seed),
    }


register(Algorithm(
    name="pame",
    hp_cls=PaMEHp,
    init=lambda key, stacked, ctx, batch0: pame_mod.pame_init(
        key, stacked, ctx.topo.m, ctx.hps),
    step=lambda state, batch, ctx: pame_mod.pame_step(
        state, batch, ctx.grad_fn, ctx.extras["topo_arrays"], ctx.hps,
        realization=ctx.extras.get("realization"),
        self_params=ctx.extras.get("fresh_params"),
        delivered=ctx.extras.get("delivered"), counted=ctx.grad_counts),
    wire_bits=_pame_wire_bits,
    wire_bits_sizes=_pame_wire_bits_sizes,
    setup=_pame_setup,
    counts=True,
    # PaME consumes message-only delay natively: senders transmit the
    # ring-delayed stack while the lambda=0 / uncovered-coordinate
    # fallback reads the fresh self-view — no innovation re-add (the
    # count-normalized average is memoryless).
    handles_delay=True,
    # PaME's step emits its own realized "wire_bits" (per-message Eq. (8)
    # on the selected surviving neighbors), so no per-edge rate here.
    # p fixes the message payload size s = round(p·n) (shape-static);
    # nu / kappa_* are realized into TopologyArrays by setup, so batched
    # configs may sweep them without the scalars entering the trace.
    static_hp_fields=("p", "mask_mode", "mixing", "partition", "p_leaf"),
    setup_hp_fields=("nu", "kappa_lo", "kappa_hi", "homogeneous_kappa"),
))

register(Algorithm(
    name="dpsgd",
    hp_cls=DPSGDHp,
    init=lambda key, stacked, ctx, batch0: B.dpsgd_init(key, stacked),
    step=lambda state, batch, ctx: B.dpsgd_step(
        state, batch, ctx.grad_fn, ctx.mixer, ctx.hps.lr,
        grad_shift=ctx.extras.get("grad_shift")),
    wire_bits=lambda topo, hps, n: _dense_edges_bits(
        topo, n, _full_msg_bits(hps, n)),
    edge_bits=_full_msg_bits,
    # lr is a traced per-lane scalar under bind_batched
))

register(Algorithm(
    name="dfedsam",
    hp_cls=DFedSAMHp,
    init=lambda key, stacked, ctx, batch0: B.dfedsam_init(key, stacked),
    step=lambda state, batch, ctx: B.dfedsam_step(
        state, batch, ctx.grad_fn, ctx.mixer, ctx.hps.lr,
        rho=ctx.hps.rho, local_steps=ctx.hps.local_steps,
        grad_shift=ctx.extras.get("grad_shift")),
    wire_bits=lambda topo, hps, n: _dense_edges_bits(
        topo, n, _full_msg_bits(hps, n)),
    edge_bits=_full_msg_bits,
    static_hp_fields=("local_steps",),  # python loop count in the step
))


def _choco_setup(topo, hps, mixing, seed):
    return {"comp": rand_k(hps.comp_frac, hps.value_bits, rescale=False)}


register(Algorithm(
    name="choco",
    hp_cls=ChocoHp,
    init=lambda key, stacked, ctx, batch0: B.choco_init(key, stacked),
    step=lambda state, batch, ctx: B.choco_step(
        state, batch, ctx.grad_fn, ctx.mixer, ctx.hps.lr,
        ctx.extras["comp"], ctx.hps.gossip_gamma,
        grad_shift=ctx.extras.get("grad_shift")),
    wire_bits=lambda topo, hps, n: _dense_edges_bits(
        topo, n, _choco_edge_bits(hps, n)),
    edge_bits=_choco_edge_bits,
    setup=_choco_setup,
    # the rand-k sparsifier's keep count round(frac·n) is shape-static
    static_hp_fields=("comp_frac", "value_bits"),
    rep_init=lambda key, stacked, ctx, batch0, arrays:
        flt_mod.rep_choco_init(key, stacked, arrays),
    rep_step=lambda state, batch, ctx: flt_mod.rep_choco_step(
        state, batch, ctx.grad_fn, ctx.hps.lr, ctx.extras["comp"],
        ctx.hps.gossip_gamma, ctx.extras["fault"],
        ctx.extras["fault_arrays"], ctx.extras["innov_bits"],
        ctx.extras["repair"], grad_shift=ctx.extras.get("grad_shift")),
))

register(Algorithm(
    name="beer",
    hp_cls=BeerHp,
    init=lambda key, stacked, ctx, batch0: B.beer_init(
        key, stacked, batch0, ctx.grad_fn),
    step=lambda state, batch, ctx: B.beer_step(
        state, batch, ctx.grad_fn, ctx.mixer, ctx.hps.lr,
        ctx.extras["comp"], ctx.hps.gossip_gamma,
        grad_shift=ctx.extras.get("grad_shift")),
    wire_bits=lambda topo, hps, n: _dense_edges_bits(
        topo, n, _beer_edge_bits(hps, n)),
    edge_bits=_beer_edge_bits,
    needs_batch0=True,
    setup=_choco_setup,
    static_hp_fields=("comp_frac", "value_bits"),
    rep_init=lambda key, stacked, ctx, batch0, arrays:
        flt_mod.rep_beer_init(key, stacked, batch0, ctx.grad_fn, arrays),
    rep_step=lambda state, batch, ctx: flt_mod.rep_beer_step(
        state, batch, ctx.grad_fn, ctx.hps.lr, ctx.extras["comp"],
        ctx.hps.gossip_gamma, ctx.extras["fault"],
        ctx.extras["fault_arrays"], ctx.extras["innov_bits"],
        ctx.extras["repair"], grad_shift=ctx.extras.get("grad_shift")),
))

register(Algorithm(
    name="anq_nids",
    hp_cls=AnqNidsHp,
    init=lambda key, stacked, ctx, batch0: B.nids_init(
        key, stacked, batch0, ctx.grad_fn, ctx.hps.lr),
    step=lambda state, batch, ctx: B.nids_step(
        state, batch, ctx.grad_fn, ctx.mixer, ctx.hps.lr, ctx.extras["q"],
        grad_shift=ctx.extras.get("grad_shift")),
    wire_bits=lambda topo, hps, n: _dense_edges_bits(
        topo, n, _anq_edge_bits(hps, n)),
    edge_bits=_anq_edge_bits,
    needs_batch0=True,
    setup=lambda topo, hps, mixing, seed: {"q": qsgd(hps.qsgd_levels)},
    static_hp_fields=("qsgd_levels",),  # quantizer wire format
    rep_init=lambda key, stacked, ctx, batch0, arrays:
        flt_mod.rep_nids_init(key, stacked, arrays),
    rep_step=lambda state, batch, ctx: flt_mod.rep_nids_step(
        state, batch, ctx.grad_fn, ctx.hps.lr, ctx.extras["q"],
        ctx.extras["fault"], ctx.extras["fault_arrays"],
        ctx.extras["innov_bits"], ctx.extras["repair"],
        grad_shift=ctx.extras.get("grad_shift")),
))
