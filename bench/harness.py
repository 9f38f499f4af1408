"""One run of one cell: set-up, the measured window, the per-layer readings
and the check that decides ``correct``.

The system under test is built with the train CLI's own objects
(``repro.launch.train.parse_args`` and ``build_everything``) from an argv
made of the cell's configuration and traffic files, and driven one chunk
at a time through ``repro.core.engine.make_scan_runner`` with
``copy_state=False``, as ``train.main`` drives it.  The run's seed gives:

- the weights, drawn on the device in one jitted call by the
  configuration's reference module (the distributions the repository's
  ``init_params`` uses), in the dtype they are trained in; every node
  draws its own, so the exchange moves coordinates between nodes that
  start apart;
- the state's PRNG key (neighbour selection and coordinate masks);
- which windows of the deployment's corpus each round reads;
- the coordinates at which the check compares the parameters.

The graph, the communication periods and the corpus are the deployment's
(the traffic file's ``deployment_seed``), so every seed runs the same
compiled programs.

Set-up runs the first chunk (the window's own compiled program, so
nothing compiles inside the window) and keeps what it produced for the
check; the window then runs whole chunks, each ended by a device sync,
until ``--seconds`` have passed.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import shutil
import sys
import tempfile
import time

import numpy as np

from bench import cells, check, monitor, xplane
from bench import peaks as peaks_mod
from bench.reference import pame


class NoChip(RuntimeError):
    pass


def require_devices(chips: int):
    """The devices the cell runs on and their peaks; NoChip without a TPU."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"the benchmark runs on a TPU; JAX found {devices[0].platform}")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chips; JAX found {len(devices)}")
    return devices[:chips], peaks_mod.peaks_for(devices[0].device_kind)


@dataclasses.dataclass(frozen=True)
class Seeds:
    state_key: np.ndarray   # uint32[2]
    weight_key: np.ndarray  # uint32[2]
    data_offset: int        # round k reads the corpus windows of step offset + k
    sample_seed: int        # the coordinates the check compares

    @classmethod
    def of(cls, seed: int) -> "Seeds":
        words = np.random.SeedSequence(int(seed) % 2**64).generate_state(6, np.uint32)
        return cls(words[0:2].copy(), words[2:4].copy(), int(words[4]) + 1, int(words[5]))


def program_argv(config: dict, traffic: dict) -> list:
    argv = ["--arch", config["arch"], "--variant", config["variant"],
            "--nodes", str(config["num_nodes"]), "--mixing", config["mixing"]]
    if config["variant"] == "full":
        argv += ["--layers", str(config["model"]["n_layers"])]
    for flag, key in (("--algo", "algo"), ("--topology", "topology"),
                      ("--scenario", "scenario"), ("--batch", "batch"),
                      ("--seq", "seq"), ("--chunk", "chunk"), ("--p", "p"),
                      ("--nu", "nu"), ("--gamma", "gamma"), ("--sigma0", "sigma0"),
                      ("--kappa-lo", "kappa_lo"), ("--kappa-hi", "kappa_hi"),
                      ("--seed", "deployment_seed")):
        argv += [flag, str(traffic[key])]
    return argv


class Feed:
    """The batch function handed to the runner: the program's own
    ``make_batch``, timed, with the first ``keep`` rounds' tokens kept."""

    def __init__(self, make_batch, offset: int, keep: int):
        self.make_batch, self.offset, self.keep = make_batch, offset, keep
        self.seconds = 0.0
        self.kept = {}

    def __call__(self, k: int):
        import jax

        start = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.batch"):
            batch = self.make_batch(self.offset + k)
        self.seconds += time.perf_counter() - start
        if k < self.keep:
            self.kept[k] = np.asarray(batch["tokens"])
        return batch


class Program:
    """The system under test, built through the train CLI's own objects."""

    def __init__(self, suite: cells.Suite, cell: cells.Cell):
        import jax
        import jax.numpy as jnp

        from repro.core import engine
        from repro.launch import train

        config, traffic = cell.config, cell.traffic
        self.args = train.parse_args(program_argv(config, traffic))
        cfg, bound, state, make_batch, _, params0 = train.build_everything(self.args)

        for key, value in config["model"].items():
            if getattr(cfg, key) != value:
                raise ValueError(f"{config['name']}: the program runs {key}="
                                 f"{getattr(cfg, key)!r}, the file says {value!r}")
        if bound.hps.mask_mode != traffic["mask_mode"] or not np.array_equal(
                bound.ctx.topo.adjacency,
                pame.deployment(traffic, self.args.nodes).adjacency):
            raise ValueError(f"{traffic['name']}: the program's masks or graph "
                             "differ from the traffic file's")
        template = jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), state.params)
        del state, params0
        self.cfg, self.bound, self.make_batch = cfg, bound, make_batch
        self.m, self.chunk = self.args.nodes, self.args.chunk
        self.tokens_per_round = self.m * self.args.batch * self.args.seq
        self.family = suite.module("reference", config["reference"])
        self._initial = pame.initializer(self.family, config["model"], self.m)
        made = jax.eval_shape(self._initial, jax.ShapeDtypeStruct((2,), jnp.uint32))
        if made != template:
            raise ValueError(f"{config['reference']} weights {made} do not match "
                             f"the program's parameters {template}")
        named = jax.tree_util.tree_flatten_with_path(template)[0]
        self.leaf_sizes = {jax.tree_util.keystr(p): x.size // self.m for p, x in named}
        self._gather = jax.jit(lambda params, index: [
            leaf.reshape(leaf.shape[0], -1)[:, ix]
            for leaf, ix in zip(jax.tree_util.tree_leaves(params), index)])
        self.runner = engine.make_scan_runner(
            bound.step, chunk_size=self.chunk, step_takes_index=bound.dynamic,
            carries_aux=bound.carries_aux)

    def weights(self, weight_key):
        """The m nodes' weights from the seed (one jitted call)."""
        import jax.numpy as jnp

        return self._initial(jnp.asarray(weight_key, jnp.uint32))

    def sample_index(self, seeds: Seeds) -> dict:
        """path -> the flat coordinates at which the check compares."""
        return pame.sample_index(seeds.sample_seed, self.leaf_sizes, check.SAMPLE)

    def start(self, seeds: Seeds):
        import jax.numpy as jnp

        state = self.bound.init(jnp.asarray(seeds.state_key, jnp.uint32),
                                self.weights(seeds.weight_key), None)
        aux = self.bound.aux_init(state) if self.bound.carries_aux else None
        return state, aux

    def chunk_from(self, state, aux, feed, k: int):
        import jax

        state, metrics, info = self.runner(
            state, feed, self.chunk, copy_state=False, k_start=k, aux=aux)
        jax.block_until_ready(state)
        return state, info["aux"], metrics

    def first_chunk(self, seeds: Seeds, feed: Feed):
        """Set-up's chunk: rounds 0..chunk-1, what they produced for the check:
        the per-round loss and communicating count, and the parameters after
        the chunk at the sampled coordinates."""
        state, aux = self.start(seeds)
        state, aux, metrics = self.chunk_from(state, aux, feed, 0)
        index = self.sample_index(seeds)
        sample = self._gather(state.params, [index[p] for p in self.leaf_sizes])
        observed = {
            "loss": np.asarray(metrics["loss_mean"], np.float64).tolist(),
            "comm_nodes": np.asarray(metrics["comm_nodes"]).astype(int).tolist(),
            "sample": {p: np.asarray(x, np.float32) for p, x in zip(self.leaf_sizes, sample)},
        }
        return state, aux, observed

    def window(self, state, aux, feed: Feed, seconds: float):
        """Whole chunks from round ``chunk`` on until ``seconds`` have passed."""
        import jax

        k = self.chunk
        feed.seconds = 0.0
        start = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.window"):
            while True:
                with jax.profiler.TraceAnnotation("bench.chunk"):
                    state, aux, _ = self.chunk_from(state, aux, feed, k)
                k += self.chunk
                if time.perf_counter() - start >= seconds:
                    break
        return state, aux, k - self.chunk, time.perf_counter() - start


def reference_check(cell: cells.Cell, program: Program, seeds: Seeds,
                    observed: dict, kept: dict):
    """Readings of what the program produced against the reference (and
    that reference's own output): its first ``check.REFERENCE_ROUNDS``
    rounds whole, and the chunk's exchanges alone at the sample."""
    from bench.reference import pame

    rounds = pame.Rounds(program.family, cell.config["model"], cell.traffic, program.m)
    tokens = [kept[k] for k in range(check.REFERENCE_ROUNDS)]
    ref = rounds.run(seeds.state_key, seeds.weight_key, tokens, check.REFERENCE_ROUNDS)
    ref["comm_nodes"] = rounds.schedule(program.chunk)
    ref["exchanged"] = rounds.exchange_alone(seeds.state_key, seeds.weight_key,
                                             program.chunk, program.sample_index(seeds))
    return check.readings(observed, ref), ref


class Context:
    """What a per-layer reader (``layers/<metric>.py``) may read."""

    def __init__(self, suite, cell, program, seeds, trace, window, rounds,
                 feed_seconds, compiles, tokens_per_s, peaks, chips):
        self.suite, self.cell, self.program, self.seeds = suite, cell, program, seeds
        self.trace, self.window = trace, window
        self.rounds, self.feed_seconds, self.compiles = rounds, feed_seconds, compiles
        self.tokens_per_s, self.peaks, self.chips = tokens_per_s, peaks, chips
        self._probes = {}

    def probe(self, name: str) -> dict:
        """``probes/<name>.py``'s measurement, made once per run."""
        if name not in self._probes:
            self._probes[name] = self.suite.module("probes", name).measure(self)
        return self._probes[name]

    def flops_per_token(self) -> float:
        flops = self.suite.module("flops", self.cell.config["flops"])
        return flops.flops_per_token(self.cell.config["model"], self.cell.traffic["seq"])

    def device_seconds(self, fn, prefix: str, calls: int = 1):
        """Device seconds per call of the programs named ``prefix`` when
        ``fn()`` runs ``calls`` times in a profiler session of its own;
        None where the trace has no device."""
        log_dir = tempfile.mkdtemp(prefix="bench-probe-")
        try:
            with profiled(log_dir):
                for _ in range(calls):
                    fn()
            trace = xplane.load(xplane.find(log_dir))
        finally:
            shutil.rmtree(log_dir, ignore_errors=True)
        seconds = xplane.module_s(trace, prefix)
        return None if seconds is None else seconds / calls


@contextlib.contextmanager
def profiled(log_dir: str):
    """A profiler session without the Python tracer, which would slow the
    host by a quarter."""
    import jax

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(log_dir, profiler_options=options)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def run_cell(suite: cells.Suite, cell: cells.Cell, seed: int, seconds: float,
             trace: bool, devices, peaks: dict, t0: float) -> dict:
    cache = monitor.CacheCounter()
    program = Program(suite, cell)
    build_s = time.perf_counter() - t0
    seeds = Seeds.of(seed)
    feed = Feed(program.make_batch, seeds.data_offset, keep=program.chunk)
    state, aux, observed = program.first_chunk(seeds, feed)

    counter = monitor.CompileCounter()
    log_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    counter.start()
    with profiled(log_dir) if trace else contextlib.nullcontext():
        setup_s = time.perf_counter() - t0
        state, aux, rounds, window_s = program.window(state, aux, feed, seconds)
    compiles = counter.stop()
    peak_bytes = max(
        (s.get("peak_bytes_in_use", 0) + s.get("peak_bytes_reserved", 0))
        for s in ((d.memory_stats() or {}) for d in devices))
    del state, aux
    tokens_per_s = rounds * program.tokens_per_round / window_s

    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": peak_bytes}
    metrics, breakdown = {}, None
    if not trace:
        values = {"tokens_per_s": tokens_per_s, "setup_s": setup_s,
                  "peak_hbm_gb": peak_bytes / 1e9}
        for entry in cell.end_to_end:
            metrics[entry["name"]] = {"value": values[entry["name"]], "unit": entry["unit"]}
    else:
        try:
            tr = xplane.load(xplane.find(log_dir))
        finally:
            shutil.rmtree(log_dir, ignore_errors=True)
        window = xplane.span(tr, "bench.window")
        busy = xplane.busy_s(tr, *window)
        device["busy_s"] = busy
        device["window_s"] = (window[1] - window[0]) / 1e9
        breakdown = {"device_ops": xplane.top_ops(tr, *window),
                     "idle_gaps": xplane.idle_gaps(tr, *window)}
        ctx = Context(suite, cell, program, seeds, tr, window, rounds,
                      feed.seconds, compiles, tokens_per_s, peaks, len(devices))
        for entry in cell.per_layer:
            value = suite.module("layers", entry["name"]).read(ctx)
            if value is not None:
                metrics[entry["name"]] = {"value": value, "unit": entry["unit"]}

    start = time.perf_counter()
    values, _ = reference_check(cell, program, seeds, observed, feed.kept)
    reference_s = time.perf_counter() - start
    correct, rows = check.verdict(values, cell.limits["limits"])
    result = {
        "correct": correct,
        "attempted": rounds,
        "failed": 0 if correct else program.chunk,
        "metrics": metrics,
        "device": device,
    }
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["run"] = {"rounds": rounds, "window_s": window_s, "setup_s": setup_s,
                     "build_s": build_s, "reference_s": reference_s,
                     "cache_hits": cache.hits, "cache_misses": cache.misses}
    result["checks"] = {r["name"]: {"value": r["value"], "limit": r["limit"]} for r in rows}
    return result


def parse(argv):
    ap = argparse.ArgumentParser(description="one run of one benchmark cell")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv, t0: float, suite: cells.Suite = None) -> int:
    opts = parse(argv)
    suite = suite or cells.Suite()
    cell = suite.cell(opts.workload)
    try:
        devices, peaks = require_devices(cell.chips)
    except NoChip as e:
        print(f"[bench] {e}", file=sys.stderr)
        return 1
    result = run_cell(suite, cell, opts.seed, opts.seconds, bool(opts.trace),
                      devices, peaks, t0)
    sys.stdout.flush()
    for name, row in result["checks"].items():
        print(f"[bench] check {name} = {row['value']!r} (limit {row['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0
