"""Cold-vs-warm persistent compilation cache smoke — the CI gate.

    PYTHONPATH=src python -m benchmarks.cache_smoke

Runs a small `bind_batched` grid dispatch in a child process twice
against one fixed subdirectory of the resolved compilation cache
(`engine.compilation_cache_dir()`), emptied first.  The children find it
through `JAX_COMPILATION_CACHE_DIR`, so the environment path is exercised
too.  The check is deterministic, not a timing assertion: a warm run
that actually skips compilation reads every executable from the cache
and writes NO new entries, so any new `jit_*` file in the cache dir
after the second run means a program was recompiled — that fails the
smoke.  Wall-clock for both runs is printed for the log but not
asserted (CI machines are too noisy for a ratio gate; the ≥30% saving
claim lives in `bench_sweep`'s compile-cache race, measured on a quiet
host).
"""
from __future__ import annotations

import os
import shutil
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)


def _workload() -> None:
    """One bind_batched grid dispatch — trace + compile + run."""
    import jax
    import jax.numpy as jnp

    from benchmarks.common import linreg_problem
    from repro.core import algorithms as ALG
    from repro.core import build_topology
    from repro.core.engine import setup_compilation_cache

    setup_compilation_cache()  # JAX_COMPILATION_CACHE_DIR, set by the parent
    m, n = 16, 60
    topo = build_topology("ring", m)
    batch, grad_fn, objective = linreg_problem(m, n, spn=16, seed=0)
    ba = ALG.get_algorithm("dpsgd").bind_batched(
        grad_fn, topo,
        [ALG.DPSGDHp(lr=0.1), ALG.DPSGDHp(lr=0.05)], seeds=[0, 1],
    )
    _, hist = ba.run(
        jnp.zeros(n), m, lambda k: batch, 16,
        objective_fn=objective, tol_std=0.0, chunk_size=8,
    )
    jax.block_until_ready(hist["objective"])


def _entries(cache_dir: str) -> list:
    """Cache executables only — `-atime` stamps are touched on reads."""
    return sorted(
        f for f in os.listdir(cache_dir) if not f.endswith("-atime")
    )


def main() -> None:
    if len(sys.argv) > 1 and sys.argv[1] == "child":
        _workload()
        return
    from repro.core.engine import compilation_cache_dir

    cache_dir = os.path.join(compilation_cache_dir(), "cache_smoke")
    shutil.rmtree(cache_dir, ignore_errors=True)
    env = dict(os.environ)
    env["JAX_COMPILATION_CACHE_DIR"] = cache_dir
    env["PYTHONPATH"] = os.path.join(REPO, "src") + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else ""
    )

    def run_child() -> float:
        t0 = time.perf_counter()
        subprocess.run(
            [sys.executable, "-m", "benchmarks.cache_smoke", "child"],
            env=env, cwd=REPO, check=True,
        )
        return time.perf_counter() - t0

    cold_s = run_child()
    cold = _entries(cache_dir)
    warm_s = run_child()
    warm = _entries(cache_dir)
    if not cold:
        sys.exit(
            "cache smoke FAIL: cold run wrote no cache entries — "
            "persistent cache not active"
        )
    new = sorted(set(warm) - set(cold))
    if new:
        sys.exit(
            f"cache smoke FAIL: warm run recompiled {len(new)} program(s) "
            f"(new cache entries: {new[:5]})"
        )
    print(
        f"cache smoke OK: {len(cold)} cached programs; "
        f"cold {cold_s:.2f}s, warm {warm_s:.2f}s "
        f"({(1.0 - warm_s / cold_s) * 100.0:.0f}% saved)"
    )


if __name__ == "__main__":
    main()
