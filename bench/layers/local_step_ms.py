"""Model (``repro.models`` forward and backward): device milliseconds of
``jax.vmap(jax.value_and_grad(train_loss))`` over the m replicas and one
round's batch, run alone."""


def read(ctx):
    seconds = ctx.probe("local_step_alone")["seconds_per_call"]
    return None if seconds is None else 1000.0 * seconds
