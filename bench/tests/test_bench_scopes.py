"""The reduction of a traced chunk to the program's named scopes and host
spans (``bench/scopes.py``), on hand-made HLO and events, on the recorded
TPU trace, and the round probe and its readers at smoke widths on the CPU."""
import os
import types

import pytest

from bench import harness, scopes, xplane
from conftest import smoke_suite

FIXTURE = os.path.join(os.path.dirname(__file__), "data", "small_tpu.xplane.pb")
CHUNK_OP = 'op_name="jit(chunk)/while/body/closed_call/'

HLO = f"""HloModule jit_chunk, is_scheduled=true

%fused_computation.1 (param_0: f32[8]) -> f32[8] {{
  %param_0 = f32[8]{{0}} parameter(0)
  %a.1 = f32[8]{{0}} multiply(%param_0, %param_0), metadata={{{CHUNK_OP}pame.exchange/pme.average/mul"}}
  %a.2 = f32[8]{{0}} add(%a.1, %a.1), metadata={{{CHUNK_OP}pame.exchange/pme.average/add"}}
  ROOT %a.3 = f32[8]{{0}} negate(%a.2), metadata={{op_name="jit(chunk)/while/body/squeeze"}}
}}

%fused_computation.2 (param_0.1: f32[8]) -> f32[8] {{
  %param_0.1 = f32[8]{{0}} parameter(0)
  %b.1 = f32[8]{{0}} multiply(%param_0.1, %param_0.1), metadata={{{CHUNK_OP}pame.update/mul"}}
  ROOT %b.2 = f32[8]{{0}} select(%b.1, %b.1, %b.1), metadata={{{CHUNK_OP}engine.carry/jit(_where)/select_n"}}
}}

%fused_computation.3 (param_0.2: f32[8]) -> f32[8] {{
  %param_0.2 = f32[8]{{0}} parameter(0)
  %c.1 = f32[8]{{0}} fusion(%param_0.2), kind=kLoop, calls=%fused_computation.4
  %c.2 = f32[8]{{0}} add(%c.1, %c.1), metadata={{{CHUNK_OP}pame.update/add"}}
  ROOT %c.3 = f32[8]{{0}} add(%c.2, %c.1)
}}

%fused_computation.4 (param_0.3: f32[8]) -> f32[8] {{
  %param_0.3 = f32[8]{{0}} parameter(0)
  %d.1 = f32[8]{{0}} sine(%param_0.3), metadata={{{CHUNK_OP}pame.local_step/vmap(transpose(jvp()))/sin"}}
  %d.2 = f32[8]{{0}} cosine(%d.1), metadata={{{CHUNK_OP}pame.local_step/vmap(jvp())/cos"}}
  ROOT %d.3 = f32[8]{{0}} add(%d.1, %d.2), metadata={{{CHUNK_OP}pame.local_step/vmap(jvp())/add"}}
}}

ENTRY %main (x: f32[8]) -> (f32[8], f32[8]) {{
  %x = f32[8]{{0}} parameter(0)
  %fusion.1 = f32[8]{{0}} fusion(%x), kind=kLoop, calls=%fused_computation.1, metadata={{op_name="jit(chunk)/while/body/squeeze"}}
  %fusion.2 = f32[8]{{0}} fusion(%fusion.1), kind=kLoop, calls=%fused_computation.2, metadata={{{CHUNK_OP}engine.carry/jit(_where)/select_n"}}
  %fusion.3 = f32[8]{{0}} fusion(%fusion.2), kind=kLoop, calls=%fused_computation.3
  %custom-call.1 = f32[8]{{0}} custom-call(%fusion.3), custom_call_target="tpu_custom_call", metadata={{{CHUNK_OP}pame.exchange/pme.average/pme_average"}}
  ROOT %tuple = (f32[8]{{0}}, f32[8]{{0}}) tuple(%custom-call.1, %x)
}}
"""


def test_scope_paths_are_path_parts_also_inside_transforms():
    assert scopes.path_of(
        "jit(chunk)/while/body/closed_call/pame.exchange/pme.mask/jit(_bernoulli)/lt"
    ) == ("pame.exchange", "pme.mask")
    assert scopes.path_of("jit(f)/transpose(jvp(pame.local_step))/dot_general") == (
        "pame.local_step",)
    assert scopes.path_of("jit(f)/vmap(jvp(pame.local_step))/pame.local_step/x") == (
        "pame.local_step",)
    assert scopes.path_of("jit(chunk)/while/body/squeeze") is None
    assert scopes.path_of("jit(f)/xpame.update/pame.updates/mul") is None


def test_opcode_reads_past_array_and_tuple_shapes():
    assert scopes.opcode("f32[8]{0:T(8,128)(2,1)} fusion(%x), kind=kLoop") == "fusion"
    assert scopes.opcode("(f32[8]{0}, s32[]) custom-call(%a), custom_call_target=\"x\"") == (
        "custom-call")


def test_a_fusion_takes_the_scope_most_of_its_computation_carries():
    mapping = scopes.scope_map(HLO)
    # its own op_name (its root's) names no scope: 2 of 3 inside say exchange
    assert mapping["fusion.1"] == ("pame.exchange", "pme.average")
    # a tie (update against the carry's select) goes to the root's scope
    assert mapping["fusion.2"] == ("engine.carry",)
    # a nested fusion's instructions count: 3 local-step against 1 update
    assert mapping["fusion.3"] == ("pame.local_step",)
    assert mapping["custom-call.1"] == ("pame.exchange", "pme.average")
    assert mapping["x"] is None and mapping["tuple"] is None


def _trace(ops, modules, host=()):
    return xplane.Trace(ops={"/device:TPU:0": ops}, modules={"/device:TPU:0": modules},
                        host=list(host))


def test_self_time_by_scope_inside_the_chunk_program_only():
    tr = _trace(
        ops=[(0, 100, "%while = (f32[8]) while(%x)"),
             (10, 30, "%fusion.1 = f32[8]{0} fusion(%x)"),
             (30, 60, "%fusion.3 = f32[8]{0} fusion(%fusion.2)"),
             (60, 70, "%copy.9 = f32[8]{0} copy(%y)"),            # not in the map
             (110, 150, "%custom-call.1 = f32[8]{0} custom-call(%fusion.3)"),
             (200, 260, "%fusion.1 = f32[8]{0} fusion(%x)")],     # another program
        modules=[(0, 150, "jit_chunk(1)"), (200, 260, "jit_stack(2)")])
    ops_ns = scopes.program_ops_ns(tr, "jit_chunk(")
    assert sum(ops_ns.values()) == 140
    times = scopes.scope_ns(ops_ns, scopes.scope_map(HLO))
    assert times == {("pame.exchange", "pme.average"): 20 + 40,
                     ("pame.local_step",): 30, None: 40 + 10}
    assert scopes.under(times, "pame.select", "pame.exchange") == 60
    assert scopes.under(times, "pme.average") == 60
    assert scopes.under(times, "engine.carry") == 0
    assert scopes.program_ops_ns(tr, "jit_other(") is None


def test_idle_gaps_are_named_by_the_innermost_program_span():
    tr = _trace(ops=[(0, 100, "%a = f32[] add()"), (150, 300, "%b = f32[] add()"),
                     (320, 400, "%c = f32[] add()"), (460, 500, "%d = f32[] add()")],
                modules=[])
    spans = [(90, 410, "engine.chunk", 16), (100, 140, "engine.batches", None),
             (140, 151, "engine.dispatch", None), (300, 330, "engine.readback", None)]
    assert scopes.named_gaps(tr, spans, 0, 500) == [
        ["untraced host", pytest.approx(60e-9)],
        ["engine.batches", pytest.approx(50e-9)],
        ["engine.readback", pytest.approx(20e-9)],
    ]


def test_gaps_are_read_inside_the_chunks_only():
    tr = _trace(ops=[(0, 100, "%a = f32[] add()"), (160, 300, "%b = f32[] add()")],
                modules=[])
    spans = [(0, 120, "engine.chunk", 0), (100, 120, "engine.readback", None),
             (140, 300, "engine.chunk", 16), (140, 160, "engine.batches", None)]
    # the 60 ns gap spans the junction (120, 140) between two runner calls
    assert scopes.named_gaps(tr, spans, 0, 300) == [["untraced host", pytest.approx(60e-9)]]
    assert scopes.chunk_gaps(tr, spans) == [["engine.readback", pytest.approx(20e-9)],
                                            ["engine.batches", pytest.approx(20e-9)]]


def test_the_recorded_trace_has_no_scope_and_no_program_span():
    recorded = xplane.load(FIXTURE)
    ops_ns = scopes.program_ops_ns(recorded, "jit_small(")
    modules = recorded.modules["/device:TPU:0"]
    busy = sum(xplane.busy_s(recorded, s, e) for s, e, _ in modules)
    assert sum(ops_ns.values()) / 1e9 == pytest.approx(busy, rel=1e-6)
    times = scopes.scope_ns(ops_ns, scopes.scope_map(HLO))
    assert list(times) == [None]
    assert scopes.host_spans(FIXTURE) == []


def test_host_spans_read_the_engines_chunk_and_its_step(tmp_path):
    import jax
    import jax.numpy as jnp

    from repro.core import engine

    run = engine.make_scan_runner(lambda s, b: (s + b, {"s": s.sum()}), chunk_size=2,
                                  params_of=lambda s: s)
    run(jnp.zeros(3), lambda k: jnp.ones(3) * k, 2)
    with harness.profiled(str(tmp_path)):
        run(jnp.zeros(3), lambda k: jnp.ones(3) * k, 4, k_start=6)
    spans = scopes.host_spans(xplane.find(str(tmp_path)))
    assert [(n, step) for _, _, n, step in spans if n == "engine.chunk"] == [
        ("engine.chunk", 6), ("engine.chunk", 8)]
    assert {n for _, _, n, _ in spans} == {"engine.chunk", *scopes.ENGINE_SPANS}


@pytest.fixture(scope="module")
def smoke_program(tmp_path_factory):
    suite = smoke_suite(str(tmp_path_factory.mktemp("suite")))
    return suite, harness.Program(suite, suite.cell("smoke.dense_lm"))


def _ctx(suite, program, trace):
    ctx = types.SimpleNamespace(program=program, seeds=harness.Seeds.of(2**31 + 5),
                                trace=trace, window=(0, 10), _probes={})
    ctx.probe = lambda name: ctx._probes.setdefault(
        name, suite.module("probes", name).measure(ctx))
    return ctx


def test_the_round_probe_runs_the_programs_chunks_on_the_cpu(smoke_program):
    suite, program = smoke_program
    probe = suite.module("probes", "round_scopes").measure(
        _ctx(suite, program, trace=None))
    # two traced chunks, each an engine.chunk span; the CPU has no device plane
    assert probe["chunks"] == 2
    assert probe["scope_ns"] is None and probe["round_ns"] is None
    assert probe["gaps"] is None


def test_readers_read_nothing_without_a_device_or_from_an_older_program(smoke_program):
    suite, program = smoke_program
    names = ("round_exchange_ms", "round_mask_ms", "round_local_step_ms",
             "round_carry_ms", "round_unscoped_pct", "engine_idle_ms")
    no_device = xplane.Trace(ops={}, modules={}, host=[])
    ctx = _ctx(suite, program, no_device)
    for name in names:
        assert suite.module("layers", name).read(ctx) is None
    assert ctx._probes == {}  # not even run

    # a device trace, but a program whose runner has no HLO accessor
    older = types.SimpleNamespace(runner=lambda *a, **k: None)
    ctx = _ctx(suite, older, _trace(ops=[(0, 5, "%a = f32[] add()")], modules=[]))
    for name in names:
        assert suite.module("layers", name).read(ctx) is None
    assert ctx._probes["round_scopes"]["chunks"] == 0


def test_readers_scale_the_probe_per_round_and_per_chunk(smoke_program):
    suite, program = smoke_program
    ctx = _ctx(suite, program, _trace(ops=[(0, 5, "%a = f32[] add()")], modules=[]))
    ctx._probes["round_scopes"] = {
        "scope_ns": {("pame.select",): 1e6, ("pame.exchange", "pme.mask"): 4e6,
                     ("pame.exchange", "pme.average"): 5e6, ("pame.local_step",): 6e6,
                     ("engine.carry",): 0.5e6, ("pame.update",): 2e6, None: 0.5e6},
        "round_ns": 19e6, "chunks": 2,
        "gaps": [["engine.batches", 0.008], ["engine.dispatch", 0.002],
                 ["untraced host", 0.5], ["engine.readback", 0.001]]}
    read = lambda name: suite.module("layers", name).read(ctx)
    assert read("round_exchange_ms") == pytest.approx(10.0)
    assert read("round_mask_ms") == pytest.approx(4.0)
    assert read("round_local_step_ms") == pytest.approx(6.0)
    assert read("round_carry_ms") == pytest.approx(0.5)
    assert read("round_unscoped_pct") == pytest.approx(100 * 0.5 / 19)
    assert read("engine_idle_ms") == pytest.approx(1000 * 0.011 / 2)
