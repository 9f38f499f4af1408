"""Trace reduction, checked on a small trace recorded on a TPU v5e (three
runs of a jitted 8-step scan of a 512x512 bf16 matmul) and on hand-built
traces; and the compile counter."""
import os

import numpy as np
import pytest

from bench import monitor, xplane

FIXTURE = os.path.join(os.path.dirname(__file__), "data", "small_tpu.xplane.pb")


@pytest.fixture(scope="module")
def recorded():
    return xplane.load(FIXTURE)


def test_recorded_trace_has_one_tpu_with_ops_and_programs(recorded):
    assert recorded.devices == ["/device:TPU:0"]
    assert len(recorded.ops["/device:TPU:0"]) == 63
    assert [n.split("(")[0] for _, _, n in recorded.modules["/device:TPU:0"]] == ["jit_small"] * 3


def test_program_device_time_is_the_sum_of_its_module_events(recorded):
    # the three runs of jit_small last 43.912 us in all (read off the trace)
    assert xplane.module_s(recorded, "jit_small(") == pytest.approx(43.912e-6, rel=1e-6)
    assert xplane.module_s(recorded, "jit_other(") is None


def test_busy_time_is_the_union_of_op_intervals(recorded):
    events = recorded.ops["/device:TPU:0"]
    lo = min(s for s, _, _ in events)
    hi = max(e for _, e, _ in events)
    slot = 0.125  # ns; the trace's times are multiples of it
    timeline = np.zeros(int(round((hi - lo) / slot)), bool)
    for s, e, _ in events:
        timeline[int(round((s - lo) / slot)):int(round((e - lo) / slot))] = True
    busy = timeline.sum() * slot / 1e9
    assert xplane.busy_s(recorded, lo, hi) == pytest.approx(busy, rel=1e-9)
    # the nested ops (a while over its body) tile the busy time by self time
    self_total = sum(xplane.self_times(events, lo, hi).values())
    assert self_total / 1e9 == pytest.approx(busy, rel=1e-6)


def test_top_ops_rank_self_time(recorded):
    events = recorded.ops["/device:TPU:0"]
    lo, hi = min(s for s, _, _ in events), max(e for _, e, _ in events)
    top = xplane.top_ops(recorded, lo, hi, n=3)
    assert len(top) == 3
    assert top[0][1] >= top[1][1] >= top[2][1] > 0
    assert all(" = " not in name and not name.startswith("%") for name, _ in top)


def hand_trace():
    ops = [(0, 100, "%while = (...) while(...)"), (10, 40, "%fusion.1 = f32[8]{0} fusion(x)"),
           (50, 90, "%fusion.2 = f32[8]{0} fusion(y)"), (200, 260, "%fusion.1 = f32[8]{0} fusion(x)"),
           (400, 500, "%copy = f32[8]{0} copy(z)")]
    host = [(0, 600, "bench.window"), (100, 200, "bench.chunk"), (120, 190, "bench.batch"),
            (260, 600, "bench.chunk")]
    return xplane.Trace(ops={"/device:TPU:0": ops},
                        modules={"/device:TPU:0": [(0, 100, "jit_chunk(1)"), (200, 260, "jit_chunk(1)")]},
                        host=host)


def test_hand_trace_busy_gaps_and_self_times():
    tr = hand_trace()
    assert xplane.span(tr, "bench.window") == (0, 600)
    assert xplane.busy_s(tr, 0, 600) == pytest.approx(260e-9)
    assert xplane.busy_s(tr, 50, 250) == pytest.approx(100e-9)
    assert xplane.idle_gaps(tr, 0, 600) == [
        ["bench.chunk", pytest.approx(140e-9)],
        ["bench.batch", pytest.approx(100e-9)],
        ["bench.chunk", pytest.approx(100e-9)],
    ]
    times = xplane.self_times(tr.ops["/device:TPU:0"], 0, 600)
    assert times == {"while": 30, "fusion.1 f32[8]": 90, "fusion.2 f32[8]": 40, "copy f32[8]": 100}
    assert xplane.module_s(tr, "jit_chunk(") == pytest.approx(160e-9)


def test_merge_and_clip():
    assert xplane.merge([(5, 7), (0, 2), (1, 3), (3, 4)]) == [(0, 4), (5, 7)]
    assert xplane.clip([(0, 4), (5, 7)], 3, 6) == [(3, 4), (5, 6)]


def test_a_trace_without_a_device_reads_nothing():
    tr = xplane.Trace(ops={}, modules={}, host=[(0, 10, "bench.window")])
    assert xplane.busy_s(tr, 0, 10) is None
    assert xplane.idle_gaps(tr, 0, 10) == []
    assert xplane.module_s(tr, "jit_chunk(") is None


def test_compile_counter_sees_new_programs_only():
    import jax
    import jax.numpy as jnp

    counter = monitor.CompileCounter()
    f = jax.jit(lambda x: x * 3.0 + 1.0)
    x = jnp.ones((7,), jnp.float32)
    counter.start()
    f(x).block_until_ready()
    assert counter.stop() == 1
    counter.start()
    f(x).block_until_ready()
    assert counter.stop() == 0
    f(jnp.ones((9,), jnp.float32)).block_until_ready()  # stopped: not counted
    assert counter.count == 0
