"""Model, MoE layers: the held experts' grouped-matmul kernels (Pallas
``gmm``/``tgmm``) against the chip's roofline.  The least time is the
larger of the FLOP bound and the HBM bound of the traced rounds: FLOPs are
the ``expert_rows`` round metric (the (token, choice) pairs the held
experts computed, summed over nodes) times a pair's forward and backward
SwiGLU FLOPs (``flops/<config>.py`` ``expert_flops``); bytes are the held
experts' weights read forward and backward and their gradients written,
and the pairs' rows (``expert_bytes``).  At about 96 pairs an expert per
node the bytes bound the kernels.  The time is the kernels' own device
self time (``probes/moe_scopes.py`` ``kernel_ms``), not the scope's.

Per-layer metrics carry no ``workloads`` key in ``BENCHMARK.json``
(``bench/tests/test_bench_spec.py`` admits none), and a metric without one
is reported in every cell that reports ``tokens_per_s``: a cell whose
model has no MoE layer reads 0."""


def read(ctx):
    probe = ctx.suite.module("probes", "moe_scopes").probe(ctx)
    if probe is None or probe["ms"] is None:
        return None
    seconds = probe["kernel_ms"] / 1e3
    if probe["expert_rows"] is None or not seconds:
        return 0.0
    count = ctx.suite.module("flops", ctx.cell.config["flops"])
    model, rows = ctx.cell.config["model"], probe["expert_rows"]
    least_s = max(count.expert_flops(model, rows) / ctx.peaks["bf16_flops_per_s"],
                  count.expert_bytes(model, ctx.program.m, rows) / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least_s / seconds
