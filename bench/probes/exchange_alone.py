"""The cell's PaME step with a quadratic local loss (0.5 ||w||^2, grad = w)
in the model's place, bound through the registry with the cell's
hyperparameters, topology and mixing, run alone as the engine's scan chunk:
one chunk to compile and warm up, then one traced chunk."""
import jax
import jax.numpy as jnp


def _quadratic(params, batch, key):
    del batch, key
    leaves = jax.tree_util.tree_leaves(params)
    loss = 0.5 * sum(jnp.sum(jnp.square(x.astype(jnp.float32))) for x in leaves)
    return loss, params


def measure(ctx):
    from repro.core import engine
    from repro.core.algorithms import get_algorithm

    prog = ctx.program
    bound = get_algorithm(prog.args.algo).bind(
        _quadratic, prog.bound.ctx.topo, prog.bound.hps, mixing=prog.args.mixing,
        seed=prog.args.seed, scenario=prog.bound.scenario)
    runner = engine.make_scan_runner(
        bound.step, chunk_size=prog.chunk, step_takes_index=bound.dynamic,
        carries_aux=bound.carries_aux)
    weights = prog.weights(ctx.seeds.weight_key)
    replica_bytes = sum(x.size // prog.m * x.dtype.itemsize
                        for x in jax.tree_util.tree_leaves(weights))
    state = bound.init(jnp.asarray(ctx.seeds.state_key, jnp.uint32), weights, None)
    held = {"state": state, "aux": bound.aux_init(state) if bound.carries_aux else None}
    del weights, state
    batch = jnp.zeros((prog.m,), jnp.float32)

    def chunk(k):
        state, _, info = runner(held["state"], lambda _: batch, prog.chunk,
                                copy_state=False, k_start=k, aux=held["aux"])
        jax.block_until_ready(state)
        held["state"], held["aux"] = state, info["aux"]

    chunk(0)
    seconds = ctx.device_seconds(lambda: chunk(prog.chunk), "jit_chunk(")
    held.clear()
    return {
        "seconds_per_round": None if seconds is None else seconds / prog.chunk,
        "first_round": prog.chunk,
        "rounds": prog.chunk,
        "replica_bytes": float(replica_bytes),
    }
