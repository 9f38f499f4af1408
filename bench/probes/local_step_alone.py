"""The model's forward and backward alone: ``jax.vmap(jax.value_and_grad(
train_loss))`` over the m replicas and one round's batch, compiled and
warmed up, then three traced calls."""
import jax


def measure(ctx):
    from repro.models.model import train_loss

    prog = ctx.program
    cfg = prog.cfg

    def local_step(params, batch):
        return jax.vmap(jax.value_and_grad(lambda p, b: train_loss(p, cfg, b)))(
            params, batch)

    step = jax.jit(local_step)
    params = prog.weights(ctx.seeds.weight_key)
    batch = prog.make_batch(ctx.seeds.data_offset)
    jax.block_until_ready(step(params, batch))
    seconds = ctx.device_seconds(
        lambda: jax.block_until_ready(step(params, batch)), "jit_local_step(", calls=3)
    return {"seconds_per_call": seconds}
