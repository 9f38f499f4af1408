"""Scan-fused execution engine: same-seed equivalence with the host loop."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import PaMEConfig, build_topology, run_pame
from repro.core import baselines as B
from repro.core.engine import run_scan_loop


def _linreg(m=10, n=32, spn=48, seed=0, noise=0.5):
    rng = np.random.default_rng(seed)
    w_star = rng.standard_normal(n)
    a = rng.standard_normal((m, spn, n))
    b = a @ w_star + noise * rng.standard_normal((m, spn))
    a_j, b_j = jnp.asarray(a, jnp.float32), jnp.asarray(b, jnp.float32)

    def grad_fn(w, batch, key):
        aa, yy = batch
        r = aa @ w - yy
        return 0.5 * jnp.mean(r**2), aa.T @ r / aa.shape[0]

    def objective(w):
        r = jnp.einsum("mbn,n->mb", a_j, w) - b_j
        return jnp.sum(0.5 * jnp.mean(r**2, axis=1))

    return (a_j, b_j), grad_fn, objective


@pytest.mark.parametrize("chunk_size", [7, 32])
def test_scan_driver_matches_host_loop(chunk_size):
    """Same seed, same trajectory: params bit-compatible, metrics <= 1e-5
    relative error, across chunk sizes that do and don't divide num_steps."""
    m, n = 10, 32
    batch, grad_fn, objective = _linreg(m=m, n=n)
    topo = build_topology("erdos_renyi", m, p=0.5, seed=1)
    cfg = PaMEConfig(nu=0.3, p=0.2, gamma=1.01, sigma0=8.0)
    kwargs = dict(
        num_steps=60, objective_fn=objective, tol_std=0.0,
    )
    st_h, h_h = run_pame(
        jax.random.PRNGKey(0), jnp.zeros(n), m, grad_fn, lambda k: batch,
        topo, cfg, driver="host", **kwargs,
    )
    st_s, h_s = run_pame(
        jax.random.PRNGKey(0), jnp.zeros(n), m, grad_fn, lambda k: batch,
        topo, cfg, driver="scan", chunk_size=chunk_size, **kwargs,
    )
    assert h_h["steps_run"] == h_s["steps_run"] == 60
    np.testing.assert_allclose(
        np.asarray(st_s.params), np.asarray(st_h.params), rtol=1e-6, atol=1e-6
    )
    for key in ("loss", "objective", "consensus"):
        a_ = np.asarray(h_h[key])
        b_ = np.asarray(h_s[key])
        np.testing.assert_allclose(b_, a_, rtol=1e-5, atol=1e-6)


def test_scan_driver_early_termination_matches_host():
    """The std-based rule fires at the same step and the returned state is
    the state *at* the triggering step (frozen inside the scan)."""
    m, n = 8, 24
    batch, grad_fn, objective = _linreg(m=m, n=n, seed=3)
    topo = build_topology("complete", m)
    cfg = PaMEConfig(nu=0.5, p=0.5, gamma=1.05, sigma0=8.0)
    runs = {}
    for driver in ("host", "scan"):
        runs[driver] = run_pame(
            jax.random.PRNGKey(0), jnp.zeros(n), m, grad_fn, lambda k: batch,
            topo, cfg, num_steps=1000, objective_fn=objective, tol_std=1e-3,
            driver=driver,
        )
    st_h, h_h = runs["host"]
    st_s, h_s = runs["scan"]
    assert h_h["steps_run"] < 1000  # the rule actually fired
    assert h_s["steps_run"] == h_h["steps_run"]
    np.testing.assert_allclose(
        np.asarray(st_s.params), np.asarray(st_h.params), rtol=1e-6, atol=1e-6
    )
    assert len(h_s["objective"]) == h_s["steps_run"]


def test_scan_driver_varying_batches():
    """batch_fn returning a fresh pytree per step exercises the stacked-xs
    path; trajectories must still match the host loop."""
    m, n = 6, 16
    rng = np.random.default_rng(0)
    data = [
        (jnp.asarray(rng.standard_normal((m, 8, n)), jnp.float32),
         jnp.asarray(rng.standard_normal((m, 8)), jnp.float32))
        for _ in range(30)
    ]

    def grad_fn(w, batch, key):
        aa, yy = batch
        r = aa @ w - yy
        return 0.5 * jnp.mean(r**2), aa.T @ r / aa.shape[0]

    topo = build_topology("ring", m)
    cfg = PaMEConfig(nu=0.5, p=0.3, gamma=1.01, sigma0=8.0)
    outs = {}
    for driver in ("host", "scan"):
        outs[driver] = run_pame(
            jax.random.PRNGKey(1), jnp.zeros(n), m, grad_fn,
            lambda k: data[k], topo, cfg, num_steps=30, tol_std=0.0,
            driver=driver,
        )
    np.testing.assert_allclose(
        np.asarray(outs["scan"][0].params),
        np.asarray(outs["host"][0].params),
        rtol=1e-6, atol=1e-6,
    )
    np.testing.assert_allclose(
        outs["scan"][1]["loss"], outs["host"][1]["loss"], rtol=1e-5, atol=1e-7
    )


def test_run_algorithm_scan_matches_host():
    m, n = 8, 20
    batch, grad_fn, objective = _linreg(m=m, n=n, seed=5)
    topo = build_topology("erdos_renyi", m, p=0.5, seed=0)
    bmat = jnp.asarray(topo.mixing)
    w0 = B.stack_params(jnp.zeros(n), m)
    key = jax.random.PRNGKey(0)
    outs = {}
    for driver in ("host", "scan"):
        outs[driver] = B.run_algorithm(
            lambda s_, b_: B.dpsgd_step(s_, b_, grad_fn, bmat, 0.1),
            B.dpsgd_init(key, w0), lambda k: batch, 50,
            objective_fn=objective, tol_std=0.0, driver=driver,
        )
    np.testing.assert_allclose(
        np.asarray(outs["scan"][0].params),
        np.asarray(outs["host"][0].params),
        rtol=1e-6, atol=1e-6,
    )
    np.testing.assert_allclose(
        outs["scan"][1]["objective"], outs["host"][1]["objective"],
        rtol=1e-5, atol=1e-6,
    )
    # donation must not invalidate the caller's shared initial stack
    assert np.isfinite(np.asarray(w0)).all()


def test_exact_pytree_kernel_route_matches_einsum():
    """The fused Pallas kernel must agree with the einsum path on a leaf
    above the routing threshold (the accelerator hot path; on CPU the
    pytree route itself stays on einsum and the kernel runs interpreted
    here just to pin the equivalence)."""
    from repro.core import pme
    from repro.kernels.pme_average.ops import pme_average as pme_average_fused

    m, d1, d2 = 8, 512, 40  # flat size 8*20480 > _KERNEL_MIN_ELEMS
    rng = np.random.default_rng(0)
    tree = {"w": jnp.asarray(rng.standard_normal((m, d1, d2)), jnp.float32)}
    a = jnp.asarray(
        ((rng.random((m, m)) < 0.5) & ~np.eye(m, dtype=bool)).astype(np.float32)
    )
    key = jax.random.PRNGKey(0)
    flat = tree["w"].reshape(m, -1)
    assert flat.size >= pme._KERNEL_MIN_ELEMS
    n = flat.shape[1]
    s = max(1, int(round(0.2 * n)))
    masks = pme.sample_coordinate_masks(
        jax.random.fold_in(key, 0), m, n, s, mode="exact"
    )
    ref = pme.pme_average(flat, masks, a).reshape(tree["w"].shape)
    fused = pme_average_fused(flat, masks, a).reshape(tree["w"].shape)
    np.testing.assert_allclose(np.asarray(fused), np.asarray(ref), atol=1e-5)
    # and the pytree entry point (whichever route it picks on this backend)
    out = pme.pme_average_pytree(key, tree, a, p=0.2, mode="exact")
    np.testing.assert_allclose(np.asarray(out["w"]), np.asarray(ref), atol=1e-5)


def test_engine_preserves_initial_state_buffers():
    """run_scan_loop donates its carry; the caller's state must survive."""
    m, n = 6, 12
    batch, grad_fn, _ = _linreg(m=m, n=n, seed=7)
    topo = build_topology("complete", m)
    bmat = jnp.asarray(topo.mixing)
    w0 = B.stack_params(jnp.ones(n), m)
    state0 = B.dpsgd_init(jax.random.PRNGKey(0), w0)
    run_scan_loop(
        lambda s_, b_: B.dpsgd_step(s_, b_, grad_fn, bmat, 0.1),
        state0, lambda k: batch, 10, tol_std=0.0,
    )
    # reusing the same state object for a second run must not raise
    _, metrics, info = run_scan_loop(
        lambda s_, b_: B.dpsgd_step(s_, b_, grad_fn, bmat, 0.1),
        state0, lambda k: batch, 10, tol_std=0.0,
    )
    assert info["steps_run"] == info["steps_dispatched"] == 10
    assert np.isfinite(metrics["loss_mean"]).all()


def test_engine_const_batch_detected_through_fresh_containers():
    """batch_fn rebuilding the tuple around the same arrays every step must
    hit the constant-batch fast path (no chunk_size-fold stacking) and still
    match the host loop."""
    m, n = 6, 16
    batch, grad_fn, _ = _linreg(m=m, n=n, seed=11)
    topo = build_topology("ring", m)
    cfg = PaMEConfig(nu=0.5, p=0.3, gamma=1.01, sigma0=8.0)
    outs = {}
    for driver in ("host", "scan"):
        outs[driver] = run_pame(
            jax.random.PRNGKey(2), jnp.zeros(n), m, grad_fn,
            lambda k: (batch[0], batch[1]),  # fresh tuple, same arrays
            topo, cfg, num_steps=20, tol_std=0.0, driver=driver,
        )
    np.testing.assert_allclose(
        np.asarray(outs["scan"][0].params),
        np.asarray(outs["host"][0].params),
        rtol=1e-6, atol=1e-6,
    )


def test_k_start_offsets_step_index_and_termination_window():
    """run(..., k_start=) hands the global index to 3-arg steps, and the
    std-termination guard counts steps into *this run*: a resumed run with
    a tiny objective must still fill its 3-value window (3 steps), never
    fire on the zero-padded warm-up after 1."""
    from repro.core.engine import make_scan_runner

    seen = []

    def step_fn(state, batch, k):
        seen.append(None)  # trace count, not per-step
        return state + 0.0, {"loss_mean": jnp.zeros(()), "k": k}

    runner = make_scan_runner(
        step_fn,
        objective_fn=lambda p: jnp.asarray(1e-3),  # constant, << 2.1*tol
        params_of=lambda s: s,
        tol_std=1e-2,
        chunk_size=4,
        donate=False,
        step_takes_index=True,
    )
    state = jnp.zeros((4, 2))
    _, metrics, info = runner(state, lambda k: None, 8, k_start=100)
    # window fills at the 3rd step of the run and fires immediately (the
    # objective is constant); firing after 1 step would mean the guard
    # leaked the global index
    assert info["steps_run"] == 3
    np.testing.assert_array_equal(
        np.asarray(metrics["k"]), np.arange(100, 103)
    )
    # fresh runner, no offset: same rule, same step count
    _, metrics0, info0 = runner(state, lambda k: None, 8)
    assert info0["steps_run"] == 3
    np.testing.assert_array_equal(np.asarray(metrics0["k"]), np.arange(3))


def test_setup_compilation_cache(tmp_path, monkeypatch):
    """The cache resolver: JAX_COMPILATION_CACHE_DIR wins, else the fixed
    <repo>/.jax_cache; a named subdirectory nests inside the resolved
    directory; and the configured dir receives cache entries on compile."""
    import os

    import jax

    from repro.core import engine

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    prior = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        assert engine.compilation_cache_dir() == os.path.join(repo, ".jax_cache")

        env_dir = tmp_path / "env_cache"
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(env_dir))
        assert engine.compilation_cache_dir() == str(env_dir)
        assert engine.setup_compilation_cache() == str(env_dir)
        assert jax.config.jax_compilation_cache_dir == str(env_dir)

        sub = engine.setup_compilation_cache("race")
        assert sub == str(env_dir / "race")
        assert jax.config.jax_compilation_cache_dir == sub

        # a fresh jit closure compiled now must land an entry on disk
        fn = jax.jit(lambda x: x * 2.0 + 1.0)
        jax.block_until_ready(fn(jnp.arange(8.0)))
        entries = [f for f in os.listdir(sub) if not f.endswith("-atime")]
        assert entries, "persistent cache wrote no entries"
    finally:
        jax.config.update("jax_compilation_cache_dir", prior)
        engine.reset_cache()


def _noisy_step(state, batch, aux=None):
    """A nonlinear step with a PRNG draw per step; with ``aux`` it also
    reads and rewrites a carried copy of the last parameters."""
    key, sub = jax.random.split(state["key"])
    w = state["w"]
    g = jnp.tanh(w @ batch) @ batch.T + 0.1 * jax.random.normal(sub, w.shape)
    if aux is not None:
        g = g + 0.5 * (w - aux["prev"])
    new = {"w": w - 0.05 * g, "key": key}
    metrics = {"loss_mean": jnp.mean(g**2), "norm": jnp.sum(w**2)}
    if aux is None:
        return new, metrics
    return new, metrics, {"prev": w, "t": aux["t"] + 1}


@pytest.mark.parametrize("carries_aux", [False, True], ids=["state", "aux"])
@pytest.mark.parametrize("lanes", [None, 2], ids=["single", "lanes2"])
def test_no_stop_rule_matches_a_rule_that_never_fires(lanes, carries_aux):
    """Without an objective the chunk has no freeze select; what it returns
    is bit for bit what a runner with a rule that cannot fire (tol_std=0)
    returns: final state, aux, every metric and info."""
    from repro.core.engine import make_scan_runner

    m, n = 4, 6
    rng = np.random.default_rng(0)
    w = jnp.asarray(rng.standard_normal((m, n)), jnp.float32)
    state = {"w": w, "key": jax.random.PRNGKey(3)}
    step = _noisy_step if carries_aux else (lambda s, b: _noisy_step(s, b))
    if lanes is not None:
        state = {"w": jnp.stack([w, -w]),
                 "key": jax.random.split(jax.random.PRNGKey(3), lanes)}
        step = jax.vmap(step, in_axes=(0, None) + ((0,) if carries_aux else ()))
    aux = None
    if carries_aux:
        aux = {"prev": jnp.zeros_like(state["w"]),
               "t": jnp.zeros(() if lanes is None else (lanes,), jnp.int32)}
    batch = jnp.asarray(rng.standard_normal((n, 5)), jnp.float32)
    outs = []
    for objective_fn in (None, lambda p: jnp.sum(p**2)):
        runner = make_scan_runner(
            step, objective_fn=objective_fn, params_of=lambda s: s["w"],
            tol_std=0.0, chunk_size=4, carries_aux=carries_aux, lanes=lanes,
        )
        outs.append(runner(state, lambda k: batch, 10, aux=aux))
    (st0, met0, info0), (st1, met1, info1) = outs
    leaves = lambda t: [np.asarray(x) for x in jax.tree_util.tree_leaves(t)]
    for a, b in zip(leaves((st0, info0["aux"])), leaves((st1, info1["aux"]))):
        np.testing.assert_array_equal(a, b)
    assert set(met1) == set(met0) | {"objective"}
    for key, val in met0.items():
        np.testing.assert_array_equal(val, met1[key])
    np.testing.assert_array_equal(info0["steps_run"], info1["steps_run"])
    np.testing.assert_array_equal(info0["steps_run"], 10)
    assert info0["steps_dispatched"] == info1["steps_dispatched"] == 10
