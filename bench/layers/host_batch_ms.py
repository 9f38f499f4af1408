"""Train loop (``repro.launch.train``'s ``make_batch``): host milliseconds
per round spent building the round's batch, from the benchmark's own span
around each call of the batch function it hands the runner."""


def read(ctx):
    return 1000.0 * ctx.feed_seconds / ctx.rounds
