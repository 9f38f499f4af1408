"""Model, MoE layers: device milliseconds per round of the program's own
chunk under the ``moe.experts`` scope: the held experts' grouped matmuls
(Pallas ``gmm``/``tgmm``), forward and backward, with the merge of the
nodes' rows around them (``probes/moe_scopes.py``); 0 in a cell whose model
has no MoE layer, since per-layer metrics carry no ``workloads`` key in
``BENCHMARK.json`` (``bench/tests/test_bench_spec.py`` admits none) and a
metric without one is reported in every cell that reports
``tokens_per_s``."""


def read(ctx):
    probe = ctx.suite.module("probes", "moe_scopes").probe(ctx)
    if probe is None or probe["ms"] is None:
        return None
    return probe["ms"]["moe.experts"]
