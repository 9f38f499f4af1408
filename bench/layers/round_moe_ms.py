"""Model, MoE layers: device milliseconds per round of the program's own
chunk under the ``moe.route``, ``moe.experts``, ``moe.shared`` and
``moe.combine`` scopes (routing and dispatch, the held experts' grouped
matmuls, the shared experts, the combine; forward and backward), by self
time (``probes/moe_scopes.py``); 0 in a cell whose model
has no MoE layer, since per-layer metrics carry no ``workloads`` key in
``BENCHMARK.json`` (``bench/tests/test_bench_spec.py`` admits none) and a
metric without one is reported in every cell that reports
``tokens_per_s``."""


def read(ctx):
    probe = ctx.suite.module("probes", "moe_scopes").probe(ctx)
    if probe is None or probe["ms"] is None:
        return None
    return sum(ms for name, ms in probe["ms"].items() if name.startswith("moe."))
