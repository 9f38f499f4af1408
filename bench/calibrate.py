"""Readings from which the limits of ``correct`` are set, for one cell.

    python3 bench/calibrate.py --workload NAME --seeds 12 --control-seeds 3

In one process (so the programs compile once), for each of ``--seeds``
seeds from ``--first-seed`` on: the program's first chunk, as a benchmark
run's set-up makes it, against the reference (the lower readings).  For
the first ``--control-seeds`` of them, also, each over the whole chunk in
the program's place and against the same reference: the control (the
reference with every matrix product's operands, forward and backward,
rounded to the configuration's ``control`` format, one scale per tensor;
parameters stored as the configuration states), the reference with each
planted fault (``half_batch``, ``no_exchange``), and the reference
computed in float32 (a witness of how far the configuration's precision
lies from float32).  A state left unchanged reads 1 on ``exchange_gap``
and needs no run.

Prints one JSON line per reading, with the per-round losses; the
benchmark's own runs never run this.
"""
import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
# no eviction: it reads a stamp file per entry, and one missing stamp makes
# every later write to the cache fail
os.environ["JAX_COMPILATION_CACHE_MAX_SIZE"] = "-1"


def calibrate(suite, workload: str, seeds: list, control_seeds: int, emit=print):
    from bench import check, harness
    from bench.reference import common, pame

    cell = suite.cell(workload)
    program = harness.Program(suite, cell)
    variants = {
        "control": {"compute": common.scaled_cast(cell.config["control"])},
        "half_batch": {"fault": "half_batch"},
        "no_exchange": {"fault": "no_exchange"},
        "float32": {"dtype": "float32"},
    }
    rounds = {name: pame.Rounds(program.family, cell.config["model"], cell.traffic,
                                program.m, **kw) for name, kw in variants.items()}
    for index, seed in enumerate(seeds):
        run_seeds = harness.Seeds.of(seed)
        feed = harness.Feed(program.make_batch, run_seeds.data_offset, keep=program.chunk)
        start = time.perf_counter()
        state, aux, observed = program.first_chunk(run_seeds, feed)
        del state, aux
        chunk_s = time.perf_counter() - start
        start = time.perf_counter()
        values, ref = harness.reference_check(cell, program, run_seeds, observed, feed.kept)
        emit(json.dumps({"kind": "program", "seed": seed, "readings": values,
                         "loss": observed["loss"], "reference_loss": ref["loss"],
                         "chunk_s": chunk_s, "reference_s": time.perf_counter() - start}))
        if index >= control_seeds:
            continue
        tokens = [feed.kept[k] for k in range(program.chunk)]
        sample_at = program.sample_index(run_seeds)
        for name, variant in rounds.items():
            start = time.perf_counter()
            out = variant.run(run_seeds.state_key, run_seeds.weight_key, tokens,
                              program.chunk, sample_at)
            emit(json.dumps({"kind": name, "seed": seed,
                             "readings": check.readings(out, ref), "loss": out["loss"],
                             "seconds": time.perf_counter() - start}))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=2**31 - 100)
    opts = ap.parse_args(argv)

    from bench import cells, harness
    from repro.core import engine

    engine.setup_compilation_cache()
    suite = cells.Suite()
    try:
        harness.require_devices(suite.cell(opts.workload).chips)
    except harness.NoChip as e:
        print(f"[calibrate] {e}", file=sys.stderr)
        return 1
    seeds = list(range(opts.first_seed, opts.first_seed + opts.seeds))
    calibrate(suite, opts.workload, seeds, opts.control_seeds,
              emit=lambda line: print(line, flush=True))
    return 0


if __name__ == "__main__":
    sys.exit(main())
