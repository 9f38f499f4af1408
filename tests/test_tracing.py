"""What the program tells a profiler and an operator: the engine's host
spans, its chunk-build event and HLO accessor, the PaME step's named
scopes in the compiled chunk, and the train CLI's per-chunk log."""
import glob
import re

import jax
import jax.numpy as jnp
import pytest

from repro.core import engine
from repro.launch import train

SCOPES = ("pame.select", "pame.exchange", "pame.local_step", "pame.update",
          "pame.metrics", "pme.mask", "pme.average")
STOP_RULE_SCOPE = "engine.carry"  # only in chunks built with an objective
ENGINE_SPANS = ("engine.batches", "engine.stack", "engine.dispatch", "engine.readback")


def _counting_runner(chunk_size=4, objective_fn=None):
    def step(state, batch):
        return state + batch, {"s": jnp.sum(state)}

    return engine.make_scan_runner(step, chunk_size=chunk_size, params_of=lambda s: s,
                                   objective_fn=objective_fn, tol_std=0.0)


class Builds:
    """The engine's chunk-build events while the block runs."""

    def __enter__(self):
        self.events = []
        jax.monitoring.register_event_listener(self._on_event)
        return self.events

    def __exit__(self, *exc):
        jax.monitoring.unregister_event_listener(self._on_event)

    def _on_event(self, event, **kwargs):
        if event == engine.CHUNK_BUILD_EVENT:
            self.events.append((kwargs["length"], kwargs["const_batch"]))


def test_chunk_build_fires_once_per_new_program_and_for_a_shorter_tail():
    run = _counting_runner(chunk_size=4)
    ones = jnp.ones((3,))
    with Builds() as builds:
        run(jnp.zeros((3,)), lambda k: ones, 8)       # two chunks of 4, one program
        assert builds == [(4, 1)]
        run(jnp.zeros((3,)), lambda k: ones, 4)       # reuse: no build
        assert builds == [(4, 1)]
        run(jnp.zeros((3,)), lambda k: ones, 10)      # tail of 2: a new program
        assert builds == [(4, 1), (2, 1)]
        run(jnp.zeros((3,)), lambda k: ones * k, 4)   # batches differ: stacked
        assert builds == [(4, 1), (2, 1), (4, 0)]


def test_optimized_hlo_is_keyed_like_the_cache_and_compiles_nothing_new():
    run = _counting_runner(chunk_size=4)
    ones = jnp.ones((3,))
    run(jnp.zeros((3,)), lambda k: ones, 6)
    assert set(run.chunk_programs()) == {(4, True), (2, True)}
    compiles = []
    listener = lambda event, secs, **kw: compiles.append(event)
    jax.monitoring.register_event_duration_secs_listener(listener)
    try:
        texts = run.optimized_hlo()
    finally:
        jax.monitoring.unregister_event_duration_listener(listener)
    assert "/jax/core/compile/backend_compile_duration" not in compiles
    assert set(texts) == {(4, True), (2, True)}
    assert all(t.startswith("HloModule jit_chunk") for t in texts.values())
    assert STOP_RULE_SCOPE not in texts[(4, True)]


def test_chunk_build_says_whether_it_compiles_the_stop_rule():
    rules = []
    listener = (lambda event, **kw: rules.append(kw["stop_rule"])
                if event == engine.CHUNK_BUILD_EVENT else None)
    jax.monitoring.register_event_listener(listener)
    try:
        _counting_runner()(jnp.zeros((3,)), lambda k: jnp.ones((3,)), 4)
        _counting_runner(objective_fn=jnp.sum)(jnp.zeros((3,)), lambda k: jnp.ones((3,)), 4)
    finally:
        jax.monitoring.unregister_event_listener(listener)
    assert rules == [0, 1]


@pytest.mark.parametrize("objective_fn", [None, jnp.sum], ids=["no_rule", "rule"])
def test_only_a_chunk_with_a_stop_rule_has_engine_carry(objective_fn):
    """The window and freeze selects are compiled, under their scope, only
    where an objective can stop the run (here one that never fires)."""
    run = _counting_runner(objective_fn=objective_fn)
    run(jnp.zeros((3,)), lambda k: jnp.ones((3,)), 4)
    (text,) = run.optimized_hlo().values()
    names = set(re.findall(r'op_name="([^"]*)"', text))
    assert any(STOP_RULE_SCOPE in n for n in names) == (objective_fn is not None)


def test_engine_spans_nest_under_their_chunk_with_its_first_step(tmp_path):
    from jax.profiler import ProfileData

    run = _counting_runner(chunk_size=4)
    ones = jnp.ones((3,))
    run(jnp.zeros((3,)), lambda k: ones * k, 4)  # compiled outside the trace
    jax.profiler.start_trace(str(tmp_path))
    try:
        run(jnp.zeros((3,)), lambda k: ones * k, 8, k_start=100)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    spans = sorted(
        (e.start_ns, e.start_ns + e.duration_ns, e.name, dict(e.stats).get("step_num"))
        for plane in ProfileData.from_file(path).planes
        for line in plane.lines for e in line.events if e.name.startswith("engine."))
    chunks = [s for s in spans if s[2] == "engine.chunk"]
    assert [int(s[3]) for s in chunks] == [100, 104]
    inner = [s for s in spans if s[2] != "engine.chunk"]
    for name in ENGINE_SPANS:
        assert sum(s[2] == name for s in inner) >= 1, name
    for start, end, name, _ in inner:
        around = [c for c in chunks if c[0] <= start and end <= c[1]]
        assert len(around) == 1, name
    # the bulk readback of the metrics lies in the last chunk
    readback = [s for s in inner if s[2] == "engine.readback"]
    assert readback[-1][0] >= chunks[-1][0]


def _smoke_args(*extra):
    return train.parse_args([
        "--arch", "stablelm-1.6b", "--variant", "smoke", "--nodes", "4",
        "--mixing", "dense", "--batch", "2", "--seq", "32", "--chunk", "4",
        *extra])


def test_pame_chunk_names_every_scope_of_the_round():
    """On the CPU too: the optimized chunk of the bound PaME step carries
    each scope of the set in its instructions' op_name metadata, and no
    stop rule's, since the run has no objective."""
    cfg, bound, state, make_batch, _, _ = train.build_everything(_smoke_args())
    run = engine.make_scan_runner(bound.step, chunk_size=4)
    run(state, make_batch, 4, copy_state=False)
    (text,) = run.optimized_hlo().values()
    names = set(re.findall(r'op_name="([^"]*)"', text))
    for scope in SCOPES:
        assert any(re.search(r"(?<![\w.])" + re.escape(scope) + r"(?![\w.])", n)
                   for n in names), scope
    assert not any(STOP_RULE_SCOPE in n for n in names)


def test_train_log_gives_the_chunk_time_and_names_each_build(capsys):
    train.main(["--arch", "stablelm-1.6b", "--variant", "smoke", "--nodes", "4",
                "--batch", "2", "--seq", "32", "--chunk", "4", "--steps", "10"])
    lines = [l for l in capsys.readouterr().out.splitlines()
             if l.startswith("[train] step=")]
    assert len(lines) == 3
    assert re.search(r"\([0-9.]+s/step, compiling\) \(built chunk program: 4 rounds\)$",
                     lines[0])
    assert re.search(r"\([0-9.]+s/step\)$", lines[1])
    assert re.search(r"\([0-9.]+s/step\) \(built chunk program: 2 rounds\)$", lines[2])


def test_train_spans_reach_the_trace(tmp_path):
    from jax.profiler import ProfileData

    log_dir = tmp_path / "trace"
    jax.profiler.start_trace(str(log_dir))
    try:
        train.main(["--arch", "stablelm-1.6b", "--variant", "smoke", "--nodes", "4",
                    "--batch", "2", "--seq", "32", "--chunk", "4", "--steps", "4",
                    "--ckpt-dir", str(tmp_path / "ckpt"), "--ckpt-every", "4"])
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(log_dir / "**" / "*.xplane.pb"), recursive=True)
    names = {e.name for plane in ProfileData.from_file(path).planes
             for line in plane.lines for e in line.events}
    assert {"train.log", "train.checkpoint", "engine.chunk"} <= names
