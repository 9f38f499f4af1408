"""The sharded PaME train step (compressed exchange, (node, fsdp, model)
mesh) is numerically identical to the single-device step — run in a
subprocess with 8 fake devices."""
import os
import subprocess
import sys
import textwrap

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

CODE = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import AxisType, Mesh
    from repro.configs import get_config
    from repro.core.pame import PaMEConfig, pame_init, pame_step, make_topology_arrays
    from repro.core.topology import build_topology
    from repro.models.model import init_params, train_loss
    from repro import sharding as shd

    cfg = get_config("stablelm-1.6b", "smoke")
    m = 4
    pcfg = PaMEConfig(nu=0.5, p=0.25, gamma=1.01, sigma0=20.0,
                      mask_mode="bernoulli", homogeneous_kappa=2,
                      exchange="EXCHANGE")
    topo = build_topology("ring", m)
    arrs = make_topology_arrays(topo, pcfg)
    params0 = init_params(jax.random.PRNGKey(0), cfg)
    stacked = jax.tree_util.tree_map(
        lambda x: jnp.broadcast_to(x[None], (m,) + x.shape), params0)
    state = pame_init(jax.random.PRNGKey(1), stacked, m, pcfg)
    tok = jnp.asarray(
        np.random.default_rng(0).integers(0, cfg.vocab, (m, 2, 32)), jnp.int32)
    batch = {"tokens": tok}

    def grad_fn(p, b, k):
        return jax.value_and_grad(lambda pp: train_loss(pp, cfg, b))(p)

    ref_state, ref_m = jax.jit(
        lambda s, b: pame_step(s, b, grad_fn, arrs, pcfg))(state, batch)

    devs = np.array(jax.devices()[:8]).reshape(4, 1, 2)
    mesh = Mesh(devs, ("node", "fsdp", "model"), axis_types=(AxisType.Auto,) * 3)
    state_specs = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), state)
    state_sh = shd.state_shardings(state_specs, mesh)
    batch_sh = shd.batch_shardings(
        {"tokens": jax.ShapeDtypeStruct(tok.shape, tok.dtype)}, mesh, True)
    with mesh:
        fn = jax.jit(
            lambda s, b: pame_step(s, b, grad_fn, arrs, pcfg,
                                   param_shardings=state_sh.params),
            in_shardings=(state_sh, batch_sh))
        sh_state, sh_m = fn(jax.device_put(state, state_sh),
                            jax.device_put(batch, batch_sh))

    err = max(
        float(jnp.max(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32))))
        for a, b in zip(jax.tree_util.tree_leaves(ref_state.params),
                        jax.tree_util.tree_leaves(sh_state.params)))
    assert err < 1e-5, err
    assert abs(float(ref_m["loss_mean"]) - float(sh_m["loss_mean"])) < 1e-5
    print("OK err", err)
    """
)


def _run(exchange: str):
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    env.pop("XLA_FLAGS", None)
    res = subprocess.run(
        [sys.executable, "-c", CODE.replace("EXCHANGE", exchange)],
        capture_output=True, text=True, env=env, timeout=900,
    )
    assert res.returncode == 0, res.stdout[-2000:] + res.stderr[-2000:]
    assert "OK err" in res.stdout


def test_sharded_step_matches_single_device_compressed():
    _run("compressed")


def test_sharded_step_matches_single_device_dense():
    _run("dense")
