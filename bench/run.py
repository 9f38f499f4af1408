"""One run of one benchmark cell on the chip.

    python3 bench/run.py --workload NAME --seed N --seconds S --trace 0|1

Prints one JSON object as the last line of standard output (``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1`` a
``breakdown``, and the compared numbers under ``checks``), and the
compared numbers beside their limits as the last lines of standard error.
Without a TPU, or with fewer chips than the cell asks for, it exits 1 and
prints no result.  JAX's persistent compilation cache is kept in
``.jax_cache`` at the root of the checkout.
"""
import os
import sys
import time

T0 = time.perf_counter()
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
# no eviction: it reads a stamp file per entry, and one missing stamp makes
# every later write to the cache fail
os.environ["JAX_COMPILATION_CACHE_MAX_SIZE"] = "-1"

if __name__ == "__main__":
    from bench import harness
    from repro.core import engine

    engine.setup_compilation_cache()
    sys.exit(harness.main(sys.argv[1:], t0=T0))
