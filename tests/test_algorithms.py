"""The unified algorithm registry: contract, drivers, wire accounting."""
import dataclasses
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import algorithms as ALG
from repro.core.topology import build_topology

EXPECTED = ("pame", "dpsgd", "dfedsam", "choco", "beer", "anq_nids")
CHUNK = 4


def _make_problem():
    m, n, spn = 8, 24, 32
    topo = build_topology("erdos_renyi", m, p=0.6, seed=1)
    rng = np.random.default_rng(0)
    w_star = rng.standard_normal(n)
    a = rng.standard_normal((m, spn, n))
    y = a @ w_star + 0.1 * rng.standard_normal((m, spn))
    a_j, y_j = jnp.asarray(a, jnp.float32), jnp.asarray(y, jnp.float32)

    def grad_fn(w, batch, key):
        aa, yy = batch
        r = aa @ w - yy
        return 0.5 * jnp.mean(r**2), aa.T @ r / aa.shape[0]

    return topo, grad_fn, (a_j, y_j), m, n


@pytest.fixture(scope="module")
def problem():
    return _make_problem()


# well-behaved small-problem hyperparameters per algorithm
def _hps(name):
    return {
        "pame": ALG.PaMEHp(nu=0.3, p=0.3, gamma=1.01, sigma0=8.0),
        "dpsgd": ALG.DPSGDHp(lr=0.05),
        "dfedsam": ALG.DFedSAMHp(lr=0.05, rho=0.01),
        "choco": ALG.ChocoHp(lr=0.05, gossip_gamma=0.3, comp_frac=0.3),
        "beer": ALG.BeerHp(lr=0.02, gossip_gamma=0.3, comp_frac=0.3),
        "anq_nids": ALG.AnqNidsHp(lr=0.05, qsgd_levels=64),
    }[name]


def test_all_expected_algorithms_registered():
    names = ALG.list_algorithms()
    for name in EXPECTED:
        assert name in names
    with pytest.raises(ValueError, match="unknown algorithm"):
        ALG.get_algorithm("nope")


def _scan_and_host(name, problem):
    """Both drivers' histories (lists) and final params, same seed."""
    topo, grad_fn, batch, m, n = problem
    bound = ALG.get_algorithm(name).bind(grad_fn, topo, _hps(name))
    outs = {}
    for driver in ("scan", "host"):
        state, hist = bound.run(
            jax.random.PRNGKey(0), jnp.zeros(n), m, lambda k: batch,
            2 * CHUNK, tol_std=0.0, driver=driver, chunk_size=CHUNK,
        )
        outs[driver] = {
            key: np.asarray(hist[key]).tolist()
            for key in ("loss", "steps_run", "steps_dispatched",
                        "wire_bits_per_step", "wire_bits_total")
        }
        outs[driver]["params"] = np.asarray(bound.params_of(state)).tolist()
    return outs


# XLA:CPU below FMA3 emits no fused multiply-add (see the docstring of
# test_registry_contract_scan_host_same_curves)
_NO_FMA = "--xla_cpu_max_isa=AVX"
_ROUNDING_SENSITIVE = ("anq_nids",)


def _scan_and_host_without_fma(name):
    here = os.path.dirname(os.path.abspath(__file__))
    src = os.path.join(os.path.dirname(here), "src")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"{os.environ.get('XLA_FLAGS', '')} {_NO_FMA}".strip(),
               PYTHONPATH=os.pathsep.join([src, here]))
    code = ("import json, test_algorithms as t; "
            f"print(json.dumps(t._scan_and_host({name!r}, "
            "t._make_problem())))")
    out = subprocess.run([sys.executable, "-c", code], env=env, check=True,
                         capture_output=True, text=True, timeout=300)
    return json.loads(out.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("name", EXPECTED)
def test_registry_contract_scan_host_same_curves(name, problem):
    """Every registered algorithm runs 2x chunk steps under driver="scan"
    and driver="host" from the same seed with identical loss curves, and
    its wire_bits is finite and positive.

    XLA:CPU's LLVM backend contracts a*b + c into one FMA where a fused
    loop allows it, and the host step and the scan body fuse differently,
    so the two drivers differ in the last bit of some values (capping the
    ISA below FMA3 makes them bitwise equal).  QSGD's stochastic rounding
    turns such a 1-ulp difference into a whole quantization level whenever
    the uniform draw lies between the two rounding probabilities (pinned by
    test_qsgd_one_ulp_flip_at_rounding_boundary).  For ANQ-NIDS on this
    problem the drivers agree to 2.4e-7 through step 5; at step 6 one
    coordinate of the surrogate hat_z jumps by 2.6e-3, one level
    (norm / 64).  Rounding-sensitive algorithms are therefore compared in
    a child whose XLA:CPU emits no FMA (`_NO_FMA`), so both drivers round
    alike."""
    topo, grad_fn, _, _, n = problem
    if name in _ROUNDING_SENSITIVE:
        outs = _scan_and_host_without_fma(name)
    else:
        outs = _scan_and_host(name, problem)
    h_s, h_h = outs["scan"], outs["host"]
    assert h_s["steps_run"] == h_h["steps_run"] == 2 * CHUNK
    assert h_s["steps_dispatched"] == h_h["steps_dispatched"] == 2 * CHUNK
    np.testing.assert_allclose(h_s["loss"], h_h["loss"], rtol=1e-5, atol=1e-7)
    np.testing.assert_allclose(
        np.asarray(h_s["params"]), np.asarray(h_h["params"]),
        rtol=1e-5, atol=1e-6,
    )
    wb = ALG.get_algorithm(name).bind(grad_fn, topo, _hps(name)).wire_bits(n)
    assert np.isfinite(wb) and wb > 0
    assert h_s["wire_bits_per_step"] == wb
    assert h_s["wire_bits_total"] == pytest.approx(wb * h_s["steps_run"])


def test_qsgd_one_ulp_flip_at_rounding_boundary():
    """Two f32 inputs one ulp apart, same key, quantize one level apart:
    the stochastic rounding compares a uniform draw with the fractional
    level, so it is discontinuous at every draw."""
    from repro.core.compression import qsgd

    levels, key = 64, jax.random.PRNGKey(3)
    quant = jax.jit(qsgd(levels).apply)
    rest = np.random.default_rng(0).standard_normal(15).astype(np.float32)

    def level(x0_bits):
        x = np.concatenate([np.array([x0_bits], np.int32).view(np.float32), rest])
        out = np.asarray(quant(key, jnp.asarray(x)))
        return out[0] / np.linalg.norm(x) * levels, x

    # bisect the f32 bit patterns of x[0] between two inputs whose levels
    # differ until they are adjacent floats
    lo = np.array([0.30], np.float32).view(np.int32)[0]
    hi = np.array([0.60], np.float32).view(np.int32)[0]
    assert round(level(hi)[0]) != round(level(lo)[0])
    while hi - lo > 1:
        mid = (lo + hi) // 2
        if round(level(mid)[0]) == round(level(lo)[0]):
            lo = mid
        else:
            hi = mid
    lev_lo, x_lo = level(lo)
    lev_hi, x_hi = level(hi)
    assert np.nextafter(x_lo[0], np.float32(1.0)) == x_hi[0]  # one ulp apart
    assert round(lev_hi) - round(lev_lo) == 1                # one level apart


@pytest.mark.parametrize("name", EXPECTED)
def test_registry_default_hps_construct(name):
    alg = ALG.get_algorithm(name)
    hps = alg.hp_cls()
    assert dataclasses.is_dataclass(hps)


def test_bind_rejects_wrong_hp_type(problem):
    topo, grad_fn, _, _, _ = problem
    with pytest.raises(TypeError, match="dpsgd expects DPSGDHp"):
        ALG.get_algorithm("dpsgd").bind(grad_fn, topo, ALG.BeerHp())


def test_needs_batch0_enforced(problem):
    topo, grad_fn, batch, m, n = problem
    bound = ALG.get_algorithm("beer").bind(grad_fn, topo, _hps("beer"))
    stacked = jnp.zeros((m, n))
    with pytest.raises(ValueError, match="batch0"):
        bound.init(jax.random.PRNGKey(0), stacked)


def test_make_runner_persistent_and_consistent(problem):
    """The persistent runner matches the one-shot driver and can be
    re-invoked without re-init side effects."""
    topo, grad_fn, batch, m, n = problem
    bound = ALG.get_algorithm("dpsgd").bind(grad_fn, topo, _hps("dpsgd"))
    runner = bound.make_runner(chunk_size=CHUNK)
    _, h1 = runner(jax.random.PRNGKey(0), jnp.zeros(n), m, lambda k: batch, 2 * CHUNK)
    _, h2 = runner(jax.random.PRNGKey(0), jnp.zeros(n), m, lambda k: batch, 2 * CHUNK)
    assert h1["loss"] == h2["loss"]
    _, h3 = bound.run(
        jax.random.PRNGKey(0), jnp.zeros(n), m, lambda k: batch, 2 * CHUNK,
        tol_std=0.0, chunk_size=CHUNK,
    )
    np.testing.assert_allclose(h1["loss"], h3["loss"], rtol=1e-6)


def test_register_rejects_duplicates():
    with pytest.raises(ValueError, match="already registered"):
        ALG.register(ALG.get_algorithm("dpsgd"))


def test_custom_registration_roundtrip(problem):
    """The README extension example: a custom algorithm registers, binds,
    and runs through the same drivers."""
    topo, grad_fn, batch, m, n = problem

    @dataclasses.dataclass(frozen=True)
    class GDHp:
        lr: float = 0.1

    name = "_test_local_gd"
    if name not in ALG.list_algorithms():
        from collections import namedtuple

        S = namedtuple("S", "params step key")

        def _init(key, stacked, ctx, batch0):
            return S(stacked, jnp.zeros((), jnp.int32), key)

        def _step(state, batch, ctx):
            key = jax.random.fold_in(state.key, state.step)
            keys = jax.random.split(key, ctx.topo.m)
            losses, grads = jax.vmap(ctx.grad_fn)(state.params, batch, keys)
            new = jax.tree_util.tree_map(
                lambda p, g: p - ctx.hps.lr * g, state.params, grads
            )
            return state._replace(params=new, step=state.step + 1), {
                "loss_mean": jnp.mean(losses)
            }

        ALG.register(ALG.Algorithm(
            name=name, hp_cls=GDHp, init=_init, step=_step,
            wire_bits=lambda topo_, hps_, n_: 1.0,  # local-only: no traffic
        ))
    bound = ALG.get_algorithm(name).bind(grad_fn, topo, GDHp(lr=0.05))
    _, hist = bound.run(
        jax.random.PRNGKey(0), jnp.zeros(n), m, lambda k: batch, 8,
        tol_std=0.0, chunk_size=CHUNK,
    )
    assert hist["loss"][-1] < hist["loss"][0]


def test_pame_history_schema_aligned_across_drivers(problem):
    """Satellite: run_pame host/scan drivers share one schema — both carry
    steps_dispatched and neither carries the dead "bits" list."""
    from repro.core import PaMEConfig, run_pame

    topo, grad_fn, batch, m, n = problem
    cfg = PaMEConfig(nu=0.3, p=0.3, gamma=1.01, sigma0=8.0)
    for driver in ("host", "scan"):
        _, hist = run_pame(
            jax.random.PRNGKey(0), jnp.zeros(n), m, grad_fn, lambda k: batch,
            topo, cfg, num_steps=6, tol_std=0.0, driver=driver, chunk_size=3,
        )
        assert "bits" not in hist, driver
        assert hist["steps_dispatched"] == 6, driver
        assert hist["steps_run"] == 6, driver
        assert len(hist["loss"]) == 6, driver
