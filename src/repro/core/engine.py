"""Scan-fused execution engine for iterative DFL algorithms.

The host-loop drivers (`run_pame`, `run_algorithm`) used to dispatch one
jitted step per Python iteration and block on several `float()` device
syncs every step — on small problems the wall time was dispatch overhead,
not algorithm math.  This engine instead runs `chunk_size` steps per
dispatch inside a single `jax.lax.scan`:

  * the algorithm state is the scan carry and is **donated** back to the
    runtime (`donate_argnums=0`), so multi-MB parameter stacks are updated
    in place across chunks;
  * per-step metrics (loss / consensus / objective / ...) accumulate in
    device-side stacked buffers; the host reads them back with a single
    `jax.device_get` after the run;
  * the paper's std-based termination rule (stop when
    std{f(w^{k-2}), f(w^{k-1}), f(w^k)} < tol) is evaluated *inside* the
    scan on a rolling 3-value window.  Once it fires, the carried state is
    frozen (`jnp.where` select per leaf), so the returned state is exactly
    the state at the triggering step even though the chunk runs to its
    static length.  The host only inspects a single boolean per chunk
    boundary to decide whether to dispatch the next chunk.  Without an
    objective there is no rule, and the chunk has neither window nor
    selects: each step writes the carried state once.

`make_scan_runner` returns a closure with a *persistent* jit cache: build
the runner once per (step_fn, objective_fn, chunk_size) combination, warm
it up, and every subsequent run with the same chunk length reuses the
compiled executable — this is what lets benchmarks measure steady-state
`us_per_call` instead of compile time.

Batches are prefetched per chunk on the host (`batch_fn(k)` for each step
of the chunk).  When `batch_fn` returns the *same object* every step (the
common full-batch case) the chunk is compiled with the batch closed over
as a single non-scanned operand instead of stacking `chunk_size` copies.

What a chunk does is named for the profiler: on the host, each chunk is an
``engine.chunk`` step span (``step_num`` = its first step) holding
``engine.batches``, ``engine.stack``, ``engine.dispatch`` and
``engine.readback``; on the device, the stop rule's objective, window and
freeze selects run under the ``engine.carry`` scope, which a chunk without
a stop rule does not have.  Each new chunk program records the
``CHUNK_BUILD_EVENT`` monitoring event, and the runner's ``optimized_hlo()``
gives the compiled text of the chunk programs it has run, whose
instruction names a device trace uses.
"""
from __future__ import annotations

import os
from typing import Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental.compilation_cache.compilation_cache import reset_cache

__all__ = [
    "make_scan_runner", "run_scan_loop", "run_batched", "history_from",
    "staleness_hist", "setup_compilation_cache", "compilation_cache_dir",
]

DEFAULT_CHUNK_SIZE = 32

# jax.monitoring event recorded each time a runner builds a new chunk
# program (a new (length, const_batch) pair), with both as keyword values
# and ``stop_rule`` (1 when the chunk compiles the freeze selects, else 0)
CHUNK_BUILD_EVENT = "/repro/engine/chunk_build"


# Where the persistent compilation cache lives when JAX_COMPILATION_CACHE_DIR
# is unset: a fixed path, because the path is part of what makes a later
# process find an entry again.
REPO_CACHE_DIR = os.path.normpath(os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "..", "..", "..", ".jax_cache"
))


def compilation_cache_dir() -> str:
    """The one place the persistent compilation cache is resolved:
    ``JAX_COMPILATION_CACHE_DIR`` when it is set (JAX reads it into
    ``jax_compilation_cache_dir`` itself), else ``<repo>/.jax_cache``."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    return env if env else REPO_CACHE_DIR


def setup_compilation_cache(subdir: Optional[str] = None) -> str:
    """Turn XLA's persistent compilation cache on at the resolved directory.

    Compile time is the dominant fixed cost of every `bind_batched` grid
    dispatch: a fresh process (or a fresh runner closure) re-traces AND
    re-compiles the whole scan even though the program is byte-identical
    to the last run.  With a persistent cache, tracing still happens but
    the XLA compile is replaced by a disk read keyed on the serialized
    HLO + compile options.

    The directory is `compilation_cache_dir()`.  ``subdir`` names a fixed
    subdirectory of it, for cold-versus-warm checks that must start from
    an empty cache of their own.  The two min-threshold knobs are zeroed
    so even sub-second programs are cached — this repo's workloads are
    many small scans, not one big XLA program.  The directory fills with
    `jit_<name>-<fingerprint>` entries (plus `-atime` stamps jax uses for
    LRU eviction); it is safe to delete wholesale at any time.

    Returns the directory configured (for logging).
    """
    cache_dir = compilation_cache_dir()
    if subdir is not None:
        cache_dir = os.path.join(cache_dir, subdir)
    os.makedirs(cache_dir, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    # jax initializes its cache object once per process and ignores a later
    # directory change until the object is reset
    reset_cache()
    return cache_dir


def history_from(metrics: dict, info: dict, keys: dict) -> dict:
    """Assemble a driver `history` dict from a runner's (metrics, info).

    `keys` maps history names to metric names (e.g. {"loss": "loss_mean"});
    values become plain float lists to keep the host-loop schema.
    """
    history = {
        out: [float(v) for v in metrics.get(src, ())]
        for out, src in keys.items()
    }
    history["steps_run"] = info["steps_run"]
    history["steps_dispatched"] = info["steps_dispatched"]
    return history


def staleness_hist(rows) -> list:
    """Collapse per-step ``stale_hist`` rows ([steps, D+1] or an iterable
    of [D+1] rows) into the run-level staleness histogram — the one
    schema every driver (scan, host, training CLI) logs."""
    return [float(v) for v in np.sum(np.asarray(rows), axis=0)]


class _Carry(NamedTuple):
    state: object      # algorithm state pytree (donated across chunks)
    done: jax.Array    # bool scalar — termination rule has fired
    win: jax.Array     # [3] f32 rolling window of objective values
    aux: object = None  # auxiliary user carry (e.g. temporal-process state
    #                     + staleness ring) — threads through the scan with
    #                     the state, frozen by the same termination select


def _sel(pred: jax.Array, t: jax.Array, f: jax.Array) -> jax.Array:
    """jnp.where with `pred` broadcast from the *left*: a scalar pred
    selects whole trees (single-lane runs), a [L] pred selects per lane
    over [L, ...] leaves (batched runs)."""
    p = pred.reshape(pred.shape + (1,) * (t.ndim - pred.ndim))
    return jnp.where(p, t, f)


def _tree_select(pred: jax.Array, on_true: object, on_false: object) -> object:
    return jax.tree_util.tree_map(
        lambda t, f: _sel(pred, t, f), on_true, on_false
    )


def _abstract(x) -> jax.ShapeDtypeStruct:
    """What jit sees of one argument: shape, dtype, weak type and, for a
    device array committed to its devices, its placement (an uncommitted
    one is placed by jit, and a placement given here would change the
    lowered program)."""
    aval = jax.typeof(x)
    committed = isinstance(x, jax.Array) and x.committed
    return jax.ShapeDtypeStruct(
        aval.shape, aval.dtype, weak_type=aval.weak_type,
        sharding=x.sharding if committed else None,
    )


def make_scan_runner(
    step_fn: Callable,  # (state, batch) -> (state, metrics dict of scalars)
    *,
    objective_fn: Optional[Callable[[object], jax.Array]] = None,
    params_of: Callable = lambda s: s.params,
    tol_std: float = 1e-3,
    chunk_size: int = DEFAULT_CHUNK_SIZE,
    donate: bool = True,
    step_takes_index: bool = False,
    carries_aux: bool = False,
    lanes: Optional[int] = None,
) -> Callable[..., Tuple[object, dict, dict]]:
    """Build a reusable chunked-scan driver.

    Returns ``run(state, batch_fn, num_steps) -> (state, metrics, info)``
    where ``metrics`` maps each key of the step's metric dict (plus
    ``"objective"`` when ``objective_fn`` is given) to a host ``np.ndarray``
    of length ``info["steps_run"]``, and ``info["steps_dispatched"]`` counts
    the steps actually executed on device (chunk-rounded past an early
    termination — the right denominator for wall-clock-per-step).  Compiled
    chunk executables are cached on the runner, so repeat runs with the
    same shapes skip compilation.

    The stop rule, and with it the per-leaf freeze select over state and
    aux, is compiled only when ``objective_fn`` is given.  Without one the
    chunk carries the step's output as it is, so the step's update writes
    the state once a round instead of feeding a select that always picks
    the new state; the results are the same bit for bit.

    ``lanes=L`` turns the runner into the vmap-over-lanes batched engine:
    ``step_fn`` is expected to be lane-batched (state leaves ``[L, m,
    ...]``, per-step metric values of shape ``[L]`` — see
    ``Algorithm.bind_batched``), ``objective_fn`` stays per-lane (it is
    vmapped here over the lane axis of the node-mean parameters), the
    std-termination rule runs per lane with the frozen-state select
    applied lane-wise (a finished lane's state/aux stop moving while the
    other lanes run on), and the chunk loop stops only when *every* lane
    has fired.  ``metrics`` values then come back as ``[steps, L]``
    arrays (untruncated — per-lane lengths live in ``info["steps_run"]``,
    an ``[L]`` int array).  One traced program, one compile, S·C lanes.

    ``step_takes_index=True`` calls ``step_fn(state, batch, k)`` with the
    global step index as a traced i32 scalar — dynamic-network scenario
    steps fold it into their PRNG key to realize the step's graph inside
    the scan (the scenario's counter rides the scan carry alongside the
    algorithm state).  ``run(..., k_start=)`` offsets the index for
    callers that drive chunks manually (e.g. the training CLI), so
    realizations stay aligned with the global step across runner calls.
    The default (False) leaves the traced program unchanged.

    ``carries_aux=True`` adds an auxiliary user-carry slot: ``run(...,
    aux=aux0)`` seeds it, the step is called as ``step_fn(state, batch,
    [k,] aux)`` and must return ``(new_state, metrics, new_aux)``, and the
    final aux comes back in ``info["aux"]``.  The aux pytree lives in the
    scan carry next to the algorithm state — temporal-process Markov state
    and the bounded-staleness parameter ring ride it across steps with no
    host round-trips — and is frozen by the same termination select as the
    state.

    The returned ``run`` also carries two read-only accessors over the
    chunk programs it has run, keyed like its cache by ``(length,
    const_batch)``: ``run.chunk_programs()`` gives each jitted chunk with
    the abstract arguments (shapes, dtypes, placement) of its first call,
    and ``run.optimized_hlo()`` their optimized HLO text.
    """

    def _scan_body(carry: _Carry, k: jax.Array, k_rel: jax.Array, batch: object):
        step_args = (carry.state, batch)
        if step_takes_index:
            step_args += (k,)
        if carries_aux:
            new_state, metrics, new_aux = step_fn(*step_args, carry.aux)
        else:
            new_state, metrics = step_fn(*step_args)
            new_aux = carry.aux
        ys = dict(metrics)
        if objective_fn is None:
            # nothing can stop the run, so nothing is frozen: the carry takes
            # the step's output as it is, and `done` stays false
            ys["_stopped"] = carry.done
            return carry._replace(state=new_state, aux=new_aux), ys
        with jax.named_scope("engine.carry"):
            # node axis is 0 for single runs, 1 behind the lane axis
            mean_params = jax.tree_util.tree_map(
                lambda x: x.mean(axis=0 if lanes is None else 1),
                params_of(new_state),
            )
            obj_fn = objective_fn if lanes is None else jax.vmap(objective_fn)
            obj = obj_fn(mean_params).astype(jnp.float32)  # [] or [L]
            win = jnp.concatenate([carry.win[..., 1:], obj[..., None]], -1)
            # guard on steps into *this run* (k_rel), not the global index:
            # each run() starts a fresh zero window, and a k_start > 0 run
            # must still fill all three slots before the rule can fire.
            trigger = (k_rel >= 2) & (jnp.std(win, axis=-1) < tol_std)
            # A step that runs *after* the rule fired is a no-op: keep the frozen
            # state so the returned state is exactly the triggering step's (per
            # lane, when batched).
            frozen = carry.done
            out_state = _tree_select(frozen, carry.state, new_state)
            out_aux = _tree_select(frozen, carry.aux, new_aux)
            out_win = _sel(frozen, carry.win, win)
            done = carry.done | trigger
            ys["objective"] = obj
            ys["_stopped"] = done
            return _Carry(out_state, done, out_win, out_aux), ys

    compiled: dict = {}  # (length, const_batch) -> jitted chunk fn
    abstract: dict = {}  # (length, const_batch) -> its first call's arguments

    def _chunk_fn(length: int, const_batch: bool):
        key = (length, const_batch)
        if key not in compiled:
            jax.monitoring.record_event(
                CHUNK_BUILD_EVENT, length=length, const_batch=int(const_batch),
                stop_rule=int(objective_fn is not None),
            )

            def chunk(carry, batch, k0, r0):
                ks = k0 + jnp.arange(length)
                rs = r0 + jnp.arange(length)
                if const_batch:
                    body = lambda c, kr: _scan_body(c, kr[0], kr[1], batch)
                    return jax.lax.scan(body, carry, (ks, rs))
                body = lambda c, krb: _scan_body(c, krb[0], krb[1], krb[2])
                return jax.lax.scan(body, carry, (ks, rs, batch))

            compiled[key] = jax.jit(
                chunk, donate_argnums=(0,) if donate else ()
            )
        return compiled[key]

    def run(
        state: object,
        batch_fn: Callable[[int], object],
        num_steps: int,
        *,
        copy_state: bool = True,
        k_start: int = 0,
        aux: object = None,
    ) -> Tuple[object, dict, dict]:
        if carries_aux and aux is None:
            raise ValueError("carries_aux runner needs run(..., aux=aux0)")
        if donate and copy_state:
            # The first chunk donates the carry's buffers; copy so the
            # caller's initial state (often shared across runs) survives.
            # Callers that hand over ownership (e.g. a training loop that
            # immediately rebinds to the returned state) pass
            # copy_state=False and skip the deep copy.
            state, aux = jax.tree_util.tree_map(
                lambda x: x.copy() if isinstance(x, jax.Array) else x,
                (state, aux),
            )
        carry = _Carry(
            state=state,
            done=jnp.zeros((() if lanes is None else (lanes,)), bool),
            win=jnp.zeros(
                ((3,) if lanes is None else (lanes, 3)), jnp.float32
            ),
            aux=aux,
        )
        leaves0, treedef0 = None, None

        def _same_batch(b, first):
            # identity on the *leaves*, not the container: batch_fn often
            # rebuilds the tuple/dict around the same arrays each step, and
            # stacking chunk_size aliases of a big batch would be an
            # accidental chunk_size-fold device allocation.
            if b is first:
                return True
            lv, td = jax.tree_util.tree_flatten(b)
            return (
                td == treedef0
                and len(lv) == len(leaves0)
                and all(x is y for x, y in zip(lv, leaves0))
            )

        ys_chunks = []
        k0 = k_start
        end = k_start + num_steps
        last = num_steps <= 0
        # Host spans on the profiler's clock: every span of one chunk lies
        # inside its engine.chunk span, whose step_num is the chunk's first
        # step.  They are cheap while no profiler session is open.
        while not last:
            length = min(chunk_size, end - k0)
            with jax.profiler.StepTraceAnnotation("engine.chunk", step_num=k0):
                with jax.profiler.TraceAnnotation("engine.batches"):
                    batches = [batch_fn(k) for k in range(k0, k0 + length)]
                with jax.profiler.TraceAnnotation("engine.stack"):
                    leaves0, treedef0 = jax.tree_util.tree_flatten(batches[0])
                    const = all(_same_batch(b, batches[0]) for b in batches[1:])
                    if const:
                        batch = batches[0]
                    else:
                        batch = jax.tree_util.tree_map(
                            lambda *xs: jnp.stack(xs), *batches
                        )
                with jax.profiler.TraceAnnotation("engine.dispatch"):
                    args = (
                        carry, batch, jnp.asarray(k0, jnp.int32),
                        jnp.asarray(k0 - k_start, jnp.int32),
                    )
                    fn = _chunk_fn(length, const)
                    if (length, const) not in abstract:
                        abstract[(length, const)] = jax.tree_util.tree_map(
                            _abstract, args
                        )
                    carry, ys = fn(*args)
                ys_chunks.append(ys)
                k0 += length
                last = k0 >= end
                if objective_fn is not None or last:
                    with jax.profiler.TraceAnnotation("engine.readback"):
                        # one scalar sync per chunk boundary — the only
                        # mid-run readback (batched runs stop once *every*
                        # lane's rule has fired)
                        if objective_fn is not None:
                            last |= bool(jax.device_get(carry.done.all()))
                        if last:  # single bulk readback of all metrics
                            host = jax.device_get(jax.tree_util.tree_map(
                                lambda *xs: jnp.concatenate(xs), *ys_chunks
                            ))
        if not ys_chunks:
            zero_steps = 0 if lanes is None else np.zeros(lanes, np.int64)
            return carry.state, {}, {
                "steps_run": zero_steps, "steps_dispatched": 0,
                "aux": carry.aux,
            }
        stopped = host.pop("_stopped")  # [steps] or [steps, L]
        if lanes is None:
            steps_run = (
                int(np.argmax(stopped)) + 1 if stopped.any()
                else int(len(stopped))
            )
            metrics = {key: val[:steps_run] for key, val in host.items()}
        else:
            fired = stopped.any(axis=0)  # [L]
            steps_run = np.where(
                fired, np.argmax(stopped, axis=0) + 1, len(stopped)
            ).astype(np.int64)
            # per-lane lengths differ; hand back the full [steps, L] buffers
            metrics = dict(host)
        return carry.state, metrics, {
            "steps_run": steps_run,
            "steps_dispatched": k0 - k_start,
            "aux": carry.aux,
        }

    def chunk_programs() -> dict:
        """(length, const_batch) -> (jitted chunk, the abstract arguments of
        its first call) for every chunk program this runner has run."""
        return {key: (compiled[key], args) for key, args in abstract.items()}

    def optimized_hlo() -> dict:
        """(length, const_batch) -> the optimized HLO text of each chunk
        program this runner has run.  Lowers and compiles on the call (an
        executable the persistent compilation cache holds is read back)."""
        return {
            key: fn.lower(*args).compile().as_text()
            for key, (fn, args) in chunk_programs().items()
        }

    run.chunk_programs = chunk_programs
    run.optimized_hlo = optimized_hlo
    return run


def run_scan_loop(
    step_fn: Callable,
    state: object,
    batch_fn: Callable[[int], object],
    num_steps: int,
    *,
    objective_fn: Optional[Callable] = None,
    params_of: Callable = lambda s: s.params,
    tol_std: float = 1e-3,
    chunk_size: int = DEFAULT_CHUNK_SIZE,
    donate: bool = True,
    step_takes_index: bool = False,
    carries_aux: bool = False,
    aux: object = None,
) -> Tuple[object, dict, dict]:
    """One-shot convenience wrapper over `make_scan_runner`."""
    runner = make_scan_runner(
        step_fn,
        objective_fn=objective_fn,
        params_of=params_of,
        tol_std=tol_std,
        chunk_size=chunk_size,
        donate=donate,
        step_takes_index=step_takes_index,
        carries_aux=carries_aux,
    )
    return runner(state, batch_fn, num_steps, aux=aux)


def run_batched(
    step_fn: Callable,   # lane-batched: state leaves [L, m, ...], metrics [L]
    state: object,
    batch_fn: Callable[[int], object],
    num_steps: int,
    *,
    lanes: int,
    objective_fn: Optional[Callable] = None,
    params_of: Callable = lambda s: s.params,
    tol_std: float = 1e-3,
    chunk_size: int = DEFAULT_CHUNK_SIZE,
    donate: bool = True,
    step_takes_index: bool = False,
    carries_aux: bool = False,
    aux: object = None,
) -> Tuple[object, dict, dict]:
    """One-shot batched (vmap-over-lanes) scan run.

    The lane axis — S seeds × C hyperparameter configs, flattened — is
    threaded through the scan carry (state, aux, per-lane termination
    window) so the whole sweep is ONE jitted program: one compile, one
    dispatch stream, per-lane metric buffers coming back as ``[steps,
    L]`` arrays with per-lane ``info["steps_run"]``.  ``step_fn`` must
    already be lane-batched; ``Algorithm.bind_batched`` builds one from
    any registered algorithm (per-lane PRNG folds via per-lane state
    keys, per-lane hyperparameters as traced scalars).
    ``objective_fn`` remains the per-run callable — it is vmapped over
    the lane axis of the node-mean parameters here.
    """
    runner = make_scan_runner(
        step_fn,
        objective_fn=objective_fn,
        params_of=params_of,
        tol_std=tol_std,
        chunk_size=chunk_size,
        donate=donate,
        step_takes_index=step_takes_index,
        carries_aux=carries_aux,
        lanes=lanes,
    )
    return runner(state, batch_fn, num_steps, aux=aux)
