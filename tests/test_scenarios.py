"""Dynamic-network scenario engine: paper-invariant conformance suite.

Pins the properties the scenario subsystem must guarantee for Assumption 1
(and the paper's accounting) to keep holding pointwise on time-varying
graphs:

  * every realized per-step matrix is doubly stochastic, symmetric, and
    nonnegative — across seeds, churn rates, and graph families;
  * non-participating nodes self-loop with weight exactly 1, and dropped
    nodes' parameters are bitwise untouched for the dropped step;
  * realized `wire_bits` equals the Eq.-(8) hand count on the realized
    edge set (gossip baselines and PaME's message-level accounting);
  * static-scenario runs are bit-identical to the fixed-`Topology` path
    (same program on both sides — per the FMA caveat, never compared
    across differently-lowered programs);
  * the spectral gap zeta of the *expected* dynamic matrix predicts the
    measured consensus-error contraction slope;
  * sparse and dense scenario mixing agree on time-varying graphs,
    including the m=2, isolated-node, and fully-dropped-step edge cases.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import algorithms as ALG
from repro.core import pme
from repro.core.pame import make_topology_arrays
from repro.core.scenarios import (
    Scenario,
    expected_matrix,
    get_scenario,
    list_scenarios,
    make_scenario_arrays,
    realization_from_masks,
    realization_matrix,
    realize,
    scenario_mixer,
)
from repro.core.topology import build_topology, spectral_gap_zeta

GRAPHS = [
    ("ring", {}),
    ("erdos_renyi", dict(p=0.5, seed=0)),
    ("regular", dict(degree=4, seed=0)),
]
DYNAMICS = [
    dict(edge_drop=0.3),
    dict(churn=0.3),
    dict(straggler=0.4),
    dict(edge_drop=0.25, churn=0.2, straggler=0.2),
]


def _linreg(m, n, spn=32, seed=0):
    rng = np.random.default_rng(seed)
    w_star = rng.standard_normal(n)
    a = rng.standard_normal((m, spn, n))
    y = a @ w_star + 0.1 * rng.standard_normal((m, spn))
    batch = (jnp.asarray(a, jnp.float32), jnp.asarray(y, jnp.float32))

    def grad_fn(w, b, key):
        aa, yy = b
        r = aa @ w - yy
        return 0.5 * jnp.mean(r**2), aa.T @ r / aa.shape[0]

    return batch, grad_fn


def test_scenario_validation_and_presets():
    with pytest.raises(ValueError, match="probability"):
        Scenario(churn=1.5)
    with pytest.raises(ValueError, match="probability"):
        Scenario(edge_drop=-0.1)
    with pytest.raises(ValueError, match="unknown scenario"):
        get_scenario("nope")
    assert get_scenario("static").is_static
    for name in list_scenarios():
        assert get_scenario(name).name == name
    assert not Scenario(churn=0.1).is_static


@pytest.mark.parametrize("kind,kwargs", GRAPHS)
@pytest.mark.parametrize("dyn", DYNAMICS)
@pytest.mark.parametrize("seed", [0, 7])
def test_realized_matrix_doubly_stochastic(kind, kwargs, dyn, seed):
    """Assumption 1 pointwise: every realized B^k is symmetric, doubly
    stochastic, and nonnegative, for every graph family x dynamics x seed."""
    m = 12
    topo = build_topology(kind, m, **kwargs)
    scen = Scenario(name="t", seed=seed, **dyn)
    arrays = make_scenario_arrays(topo, scen)
    for k in range(5):
        r = realize(scen, arrays, k)
        b = np.asarray(realization_matrix(arrays, r), np.float64)
        np.testing.assert_allclose(b.sum(axis=1), 1.0, atol=1e-5)
        np.testing.assert_allclose(b.sum(axis=0), 1.0, atol=1e-5)
        assert b.min() >= 0.0
        np.testing.assert_allclose(b, b.T, atol=1e-7)


def test_non_participants_self_loop_weight_one():
    """Dropped and straggling nodes get B_ii = 1 exactly (no traffic in or
    out), so the realized matrix stays doubly stochastic over survivors."""
    m = 12
    topo = build_topology("erdos_renyi", m, p=0.5, seed=1)
    scen = Scenario(name="t", churn=0.5, straggler=0.3, seed=2)
    arrays = make_scenario_arrays(topo, scen)
    saw_out = 0
    for k in range(6):
        r = realize(scen, arrays, k)
        b = np.asarray(realization_matrix(arrays, r))
        out = ~np.asarray(r.participating)
        saw_out += int(out.sum())
        for i in np.nonzero(out)[0]:
            assert b[i, i] == 1.0
            assert np.all(b[i, np.arange(m) != i] == 0.0)
            assert np.all(b[np.arange(m) != i, i] == 0.0)
    assert saw_out > 0  # churn=0.5 over 6 steps: certain in practice


def test_zero_probability_realization_matches_base_topology():
    """With all probabilities 0 the realized weights reproduce the static
    Metropolis matrix (fp tolerance) and the full base edge set."""
    for kind, kwargs in GRAPHS:
        topo = build_topology(kind, 12, **kwargs)
        scen = Scenario(name="static")
        arrays = make_scenario_arrays(topo, scen)
        r = realize(scen, arrays, 0)
        assert bool(jnp.all(r.edge_alive == arrays.valid))
        assert int(r.directed_edges) == int(topo.degrees.sum())
        b = np.asarray(realization_matrix(arrays, r), np.float64)
        np.testing.assert_allclose(b, topo.mixing, atol=1e-6)


def test_fully_dropped_step_is_identity_and_frozen():
    """churn=1.0: B^k = I exactly, zero realized edges, zero wire bits, and
    every node's parameters are bitwise untouched across the run."""
    m, n = 8, 20
    topo = build_topology("erdos_renyi", m, p=0.6, seed=0)
    scen = Scenario(name="dead", churn=1.0, seed=0)
    arrays = make_scenario_arrays(topo, scen)
    r = realize(scen, arrays, 0)
    assert int(r.directed_edges) == 0
    np.testing.assert_array_equal(
        np.asarray(realization_matrix(arrays, r)), np.eye(m, dtype=np.float32)
    )
    batch, grad_fn = _linreg(m, n)
    bound = ALG.get_algorithm("dpsgd").bind(
        grad_fn, topo, ALG.DPSGDHp(lr=0.1), scenario=scen
    )
    state, hist = bound.run(
        jax.random.PRNGKey(0), jnp.zeros(n), m, lambda k: batch, 4,
        tol_std=0.0, chunk_size=2,
    )
    np.testing.assert_array_equal(np.asarray(state.params), np.zeros((m, n)))
    assert hist["wire_bits"] == [0.0] * 4
    assert hist["wire_bits_total"] == 0.0


def test_edge_cases_m2_and_isolated_nodes():
    """m=2 single-link graph under link failure, and a star whose hub drops
    (isolating every leaf): each realization stays doubly stochastic and
    the isolated-node matrix is exactly the identity."""
    # m = 2: the one edge is either up (B = [[.5,.5],[.5,.5]]) or down (I)
    topo2 = build_topology("ring", 2)
    scen = Scenario(name="t", edge_drop=0.5, seed=0)
    arrays2 = make_scenario_arrays(topo2, scen)
    seen = set()
    for k in range(12):
        b = np.asarray(realization_matrix(arrays2, realize(scen, arrays2, k)))
        np.testing.assert_allclose(b.sum(axis=1), 1.0, atol=1e-6)
        np.testing.assert_allclose(b, b.T, atol=1e-7)
        up = bool(b[0, 1] > 0)
        seen.add(up)
        expected = np.full((2, 2), 0.5) if up else np.eye(2)
        np.testing.assert_allclose(b, expected, atol=1e-6)
    assert seen == {True, False}  # both outcomes realized over 12 draws

    # star with the hub dropped: every leaf is isolated -> B = I exactly
    topo_s = build_topology("star", 7)
    arrays_s = make_scenario_arrays(topo_s, Scenario(name="s"))
    m, d = arrays_s.nbrs.shape
    alive = jnp.ones((m,), bool).at[0].set(False)
    r = realization_from_masks(
        arrays_s, jnp.ones((m, d), bool), alive, jnp.zeros((m,), bool)
    )
    np.testing.assert_array_equal(
        np.asarray(realization_matrix(arrays_s, r)), np.eye(m, dtype=np.float32)
    )
    assert int(r.directed_edges) == 0


@pytest.mark.parametrize("kind,kwargs", GRAPHS)
def test_scenario_mixer_sparse_matches_dense_timevarying(kind, kwargs):
    """Sparse (padded gather) and dense/matrix scenario mixers agree to fp
    tolerance on every realized graph, for every gossip operator variant."""
    m = 10
    topo = build_topology(kind, m, **kwargs)
    scen = Scenario(name="t", edge_drop=0.3, churn=0.2, seed=4)
    arrays = make_scenario_arrays(topo, scen)
    rng = np.random.default_rng(1)
    tree = {
        "w": jnp.asarray(rng.standard_normal((m, 5, 3)), jnp.float32),
        "b": jnp.asarray(rng.standard_normal((m,)), jnp.float32),
    }
    for k in range(3):
        r = realize(scen, arrays, k)
        mx_s = scenario_mixer(arrays, r, "sparse")
        mx_m = scenario_mixer(arrays, r, "matrix")
        for fn in ("mix", "mix_lazy", "mix_half"):
            out_s = getattr(mx_s, fn)(tree)
            out_m = getattr(mx_m, fn)(tree)
            for key in tree:
                np.testing.assert_allclose(
                    np.asarray(out_s[key]), np.asarray(out_m[key]),
                    rtol=1e-5, atol=1e-6, err_msg=f"{fn} step {k}",
                )
        hats = jax.tree_util.tree_map(lambda x: 0.5 * x, tree)
        out_s = mx_s.mix_nids_quantized(hats, tree)
        out_m = mx_m.mix_nids_quantized(hats, tree)
        for key in tree:
            np.testing.assert_allclose(
                np.asarray(out_s[key]), np.asarray(out_m[key]),
                rtol=1e-5, atol=1e-6,
            )


@pytest.mark.parametrize("name", ["dpsgd", "pame"])
def test_static_scenario_bit_identical_to_fixed_topology(name):
    """bind(scenario=static) is the existing fixed-Topology program: same
    jitted scan, bit-identical curves and final parameters."""
    m, n = 8, 20
    topo = build_topology("erdos_renyi", m, p=0.6, seed=1)
    batch, grad_fn = _linreg(m, n)
    hps = {
        "dpsgd": ALG.DPSGDHp(lr=0.1),
        "pame": ALG.PaMEHp(nu=0.3, p=0.3, gamma=1.01, sigma0=8.0),
    }[name]
    runs = {}
    for scen in (None, get_scenario("static")):
        bound = ALG.get_algorithm(name).bind(grad_fn, topo, hps, scenario=scen)
        assert not bound.dynamic
        state, hist = bound.run(
            jax.random.PRNGKey(0), jnp.zeros(n), m, lambda k: batch, 8,
            tol_std=0.0, chunk_size=4,
        )
        runs[scen is None] = (np.asarray(bound.params_of(state)), hist)
    assert runs[True][1]["loss"] == runs[False][1]["loss"]
    np.testing.assert_array_equal(runs[True][0], runs[False][0])
    assert "wire_bits" not in runs[True][1] and "wire_bits" not in runs[False][1]


def test_dropped_params_untouched_stragglers_update_locally():
    """Per step: dropped nodes' parameters are bitwise frozen; stragglers
    skip the exchange (self-loop) but still apply their local gradient."""
    m, n = 12, 16
    topo = build_topology("erdos_renyi", m, p=0.5, seed=2)
    scen = Scenario(name="t", churn=0.4, straggler=0.3, seed=5)
    lr = 0.1
    batch, grad_fn = _linreg(m, n, seed=3)
    bound = ALG.get_algorithm("dpsgd").bind(
        grad_fn, topo, ALG.DPSGDHp(lr=lr), scenario=scen
    )
    rng = np.random.default_rng(0)
    stacked = jnp.asarray(rng.standard_normal((m, n)), jnp.float32)
    state = bound.init(jax.random.PRNGKey(0), stacked)
    saw_drop = saw_strag = 0
    for k in range(3):
        r = realize(scen, bound.scen_arrays, k)
        old = np.asarray(state.params)
        # reproduce the per-node gradients the step draws
        key = jax.random.fold_in(state.key, state.step)
        keys = jax.random.split(key, m)
        _, grads = jax.vmap(grad_fn)(state.params, batch, keys)
        new_state, metrics = bound.step(state, batch, k)
        new = np.asarray(new_state.params)
        alive = np.asarray(r.alive)
        participating = np.asarray(r.participating)
        for i in range(m):
            if not alive[i]:
                saw_drop += 1
                np.testing.assert_array_equal(new[i], old[i])
            elif not participating[i]:  # straggler: local SGD, no exchange
                saw_strag += 1
                np.testing.assert_array_equal(
                    new[i], np.asarray(-lr * grads[i] + state.params[i])
                )
        state = new_state
    assert saw_drop > 0 and saw_strag > 0


def test_realized_wire_bits_match_hand_count_gossip():
    """For a gossip baseline, per-step wire_bits == (realized directed
    edges) x message_bits(n, n), recomputed independently per step."""
    m, n = 10, 24
    topo = build_topology("erdos_renyi", m, p=0.5, seed=1)
    scen = Scenario(name="t", edge_drop=0.3, churn=0.2, seed=6)
    batch, grad_fn = _linreg(m, n)
    bound = ALG.get_algorithm("dpsgd").bind(
        grad_fn, topo, ALG.DPSGDHp(lr=0.1), scenario=scen
    )
    steps = 6
    _, hist = bound.run(
        jax.random.PRNGKey(0), jnp.zeros(n), m, lambda k: batch, steps,
        tol_std=0.0, chunk_size=3,
    )
    per_msg = pme.message_bits(n, n)
    expected = [
        float(int(realize(scen, bound.scen_arrays, k).directed_edges) * per_msg)
        for k in range(steps)
    ]
    assert hist["wire_bits"] == expected
    assert hist["wire_bits_total"] == sum(expected)


@pytest.mark.parametrize("mixing", ["sparse", "dense"])
def test_realized_wire_bits_match_hand_count_pame(mixing):
    """PaME's realized accounting: per-step wire_bits == (number of
    selected surviving sender->receiver messages) x message_bits(s, n),
    with the selection reproduced from the same PRNG stream — through the
    padded selection (sparse mixing) or the [m, m] selection matrix
    (dense mixing)."""
    m, n = 10, 30
    seed = 0
    topo = build_topology("erdos_renyi", m, p=0.5, seed=2)
    scen = Scenario(name="t", edge_drop=0.25, churn=0.25, seed=7)
    cfg = ALG.PaMEHp(nu=0.5, p=0.3, gamma=1.01, sigma0=8.0)
    batch, grad_fn = _linreg(m, n)
    bound = ALG.get_algorithm("pame").bind(
        grad_fn, topo, cfg, mixing=mixing, seed=seed, scenario=scen
    )
    steps = 8
    key = jax.random.PRNGKey(0)
    _, hist = bound.run(
        key, jnp.zeros(n), m, lambda k: batch, steps,
        tol_std=0.0, chunk_size=4,
    )
    arrs = make_topology_arrays(topo, cfg, seed=seed)
    s = max(1, int(round(cfg.p * n)))
    per_msg = pme.message_bits(s, n)
    sample = (pme.sample_neighbor_selection_padded if mixing == "sparse"
              else pme.sample_neighbor_selection)
    expected = []
    for k in range(steps):
        r = realize(scen, bound.scen_arrays, k)
        k_sel = jax.random.fold_in(key, k * 3)
        comm = ((jnp.asarray(k) % arrs.kappa) == 0) & r.participating
        sel = sample(
            k_sel, arrs.nbrs, arrs.valid, arrs.t, comm, survivors=r.edge_alive
        )
        expected.append(float(int(sel.sum()) * per_msg))
    assert hist["wire_bits"] == expected
    # sanity: the dynamics actually bit — some steps communicated
    assert sum(expected) > 0


def test_dynamic_run_host_equals_scan():
    """The scenario-wrapped step gives the same curves through the host
    loop and the scan engine (the realization rides the step index)."""
    m, n = 8, 16
    topo = build_topology("ring", m)
    scen = Scenario(name="t", edge_drop=0.3, churn=0.2, straggler=0.2, seed=1)
    batch, grad_fn = _linreg(m, n)
    bound = ALG.get_algorithm("choco").bind(
        grad_fn, topo, ALG.ChocoHp(lr=0.05), scenario=scen
    )
    outs = {}
    for driver in ("scan", "host"):
        _, hist = bound.run(
            jax.random.PRNGKey(0), jnp.zeros(n), m, lambda k: batch, 6,
            tol_std=0.0, driver=driver, chunk_size=3,
        )
        outs[driver] = hist
    np.testing.assert_allclose(
        outs["scan"]["loss"], outs["host"]["loss"], rtol=1e-5, atol=1e-7
    )
    assert outs["scan"]["wire_bits"] == outs["host"]["wire_bits"]
    # the README-documented schema holds on both drivers
    assert outs["scan"]["alive_nodes"] == outs["host"]["alive_nodes"]
    assert len(outs["scan"]["alive_nodes"]) == 6
    assert all(0 <= a <= m for a in outs["scan"]["alive_nodes"])


@pytest.mark.parametrize(
    "kind,kwargs,dyn",
    [
        ("erdos_renyi", dict(p=0.5, seed=1), dict(churn=0.2, edge_drop=0.2)),
        ("ring", {}, dict(edge_drop=0.3)),
    ],
)
def test_zeta_of_expected_matrix_predicts_contraction(kind, kwargs, dyn):
    """Spectral conformance: the consensus error of the pure-mixing dynamic
    process contracts at the rate predicted by the expected matrix —
    measured log-slope within tolerance of 2·log zeta(E[B]), and no faster
    than the E[B^T B] bound allows."""
    m = 16
    topo = build_topology(kind, m, **kwargs)
    scen = Scenario(name="z", seed=3, **dyn)
    arrays = make_scenario_arrays(topo, scen)
    eb = expected_matrix(topo, scen, num_samples=400)
    np.testing.assert_allclose(eb.sum(axis=1), 1.0, atol=1e-6)
    zeta = spectral_gap_zeta(eb)
    assert 0.0 < zeta < 1.0
    predicted = 2.0 * np.log(zeta)
    # E[B^T B] restricted to the mean-orthogonal subspace upper-bounds the
    # per-step expected contraction; zeta(E[B])^2 lower-bounds it (Jensen).
    mats = np.stack([
        np.asarray(
            realization_matrix(arrays, realize(scen, arrays, k)), np.float64
        )
        for k in range(400)
    ])
    ebtb = np.einsum("kij,kil->kjl", mats, mats).mean(axis=0)
    rho2 = np.sort(np.linalg.eigvalsh(ebtb))[::-1][1]
    assert zeta**2 <= rho2 + 1e-9
    # measure the actual dynamic process on fresh realizations (f64 host
    # math: no fp32 noise floor over 120 steps)
    mats2 = np.stack([
        np.asarray(
            realization_matrix(arrays, realize(scen, arrays, 1000 + k)),
            np.float64,
        )
        for k in range(120)
    ])
    rng = np.random.default_rng(0)
    x = rng.standard_normal((m, 64))
    # remove the consensus component up front: it is preserved by every
    # B^k, and its O(1) magnitude would otherwise put an fp64-roundoff
    # floor under the exponentially decaying deviation we are measuring
    x -= x.mean(axis=0, keepdims=True)
    errs = []
    for b in mats2:
        errs.append(np.sum((x - x.mean(axis=0, keepdims=True)) ** 2))
        x = b @ x
    errs.append(np.sum((x - x.mean(axis=0, keepdims=True)) ** 2))
    slope = (np.log(errs[110]) - np.log(errs[10])) / 100.0
    tol = max(0.15 * abs(predicted), 0.02)
    assert abs(slope - predicted) < tol, (slope, predicted)
    assert slope <= np.log(rho2) + 0.05
