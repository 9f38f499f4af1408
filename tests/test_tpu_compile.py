"""Compile the Pallas kernels for a TPU v5e at real widths, without a chip.

The TPU compiler is installed with JAX and compiles for a described chip,
so these tests catch what interpret mode cannot: block shapes the TPU
lowering refuses, scratch the kernel may not use, programs that do not
fit.  Nothing runs; each test checks that the kernel lowered to a TPU
custom call.  The topology is described in a fixture, never at import, so
that under several test workers only the one running this file loads the
TPU library.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.experimental.compilation_cache.compilation_cache import reset_cache


@pytest.fixture(scope="module")
def one_chip():
    """One v5e chip of a described 2x2 slice; the persistent compilation
    cache is off meanwhile (a TPU entry written here cannot be read back
    without a chip)."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    prior = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prior)
    reset_cache()


def _compile(fn, *shapes):
    compiled = jax.jit(fn).lower(*shapes).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def test_pme_average_compiles_for_v5e(one_chip):
    """m = 4 nodes over one stablelm-1.6b MLP matrix (2048 x 5632), bf16:
    the exact-mode leaves PaME routes through this kernel on a TPU."""
    from repro.kernels.pme_average.kernel import pme_average_pallas

    m, n = 4, 2048 * 5632
    w = jax.ShapeDtypeStruct((m, n), jnp.bfloat16, sharding=one_chip)
    a = jax.ShapeDtypeStruct((m, m), jnp.bfloat16, sharding=one_chip)
    _compile(pme_average_pallas, w, w, a)


def test_pme_bernoulli_average_compiles_for_v5e(one_chip):
    """The embedding leaf of the stablelm-1.6b cell (m = 4, bf16 [100352,
    2048]) with its bernoulli masks drawn in the kernel: no mask and no f32
    sums reach HBM (the einsum path's temporaries are 7.40 GB)."""
    from repro.kernels.pme_average.kernel import pme_bernoulli_average_pallas

    w = jax.ShapeDtypeStruct((1, 4, 100352, 2048), jnp.bfloat16, sharding=one_chip)
    key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one_chip)
    a = jax.ShapeDtypeStruct((4, 4), jnp.float32, sharding=one_chip)
    compiled = _compile(lambda w, k, a: pme_bernoulli_average_pallas(w, k, a, 0.2), w, key, a)
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 30


def test_gossip_compiles_for_v5e(one_chip):
    """m = 32 nodes at degree 8, PME's two terms (payload and counts) on
    one shared weight table, over one stablelm-1.6b MLP matrix in f32."""
    from repro.kernels.gossip.kernel import gossip_gather_pallas

    m, k, n = 32, 8, 2048 * 5632
    nbrs = jax.ShapeDtypeStruct((m, k), jnp.int32, sharding=one_chip)
    w = jax.ShapeDtypeStruct((m, k), jnp.float32, sharding=one_chip)
    x = jax.ShapeDtypeStruct((m, n), jnp.float32, sharding=one_chip)
    _compile(lambda nb, ww, a, b: gossip_gather_pallas(nb, (ww,), (a, b), (0, 0)),
             nbrs, w, x, x)


def test_flash_attention_compiles_for_v5e(one_chip):
    """stablelm-1.6b attention: 32 heads of 64 at seq 2048, bf16."""
    from repro.kernels.flash_attention.kernel import flash_attention_pallas

    q = jax.ShapeDtypeStruct((1, 2048, 32, 64), jnp.bfloat16, sharding=one_chip)
    _compile(flash_attention_pallas, q, q, q)


def test_ssd_scan_compiles_for_v5e(one_chip):
    """mamba2-1.3b's SSD: 64 heads of 64, one B/C group of state 128,
    chunk 128, seq 2048."""
    from repro.kernels.ssd_scan.kernel import ssd_intra_chunk_pallas

    b, nc, l, h, p, g, n = 1, 16, 128, 64, 64, 1, 128

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    _compile(
        lambda x, dt, cum, bb, cc: ssd_intra_chunk_pallas(x, dt, cum, bb, cc, h // g),
        sds((b, nc, l, h, p), jnp.bfloat16), sds((b, nc, l, h), jnp.float32),
        sds((b, nc, l, h), jnp.float32), sds((b, nc, l, g, n), jnp.bfloat16),
        sds((b, nc, l, g, n), jnp.bfloat16),
    )


# Custom calls the TPU compiler adds for its own buffer bookkeeping; they
# do no work of the program's and carry no op_name.
BOOKKEEPING_CALLS = ("AllocateBuffer", "ConcatBitcast")


def _smoke_chunk_for_v5e(one_chip, fused: bool = False, arch: str = "stablelm-1.6b",
                         nodes: int = 4, min_fused: int = None, objective_fn=None):
    """The bound PaME step's scan chunk at smoke widths (dense mixing,
    bernoulli masks, the CLI's hyperparameters) compiled for a v5e;
    ``fused`` takes the gate of the fused bernoulli kernel as on a TPU (for
    leaves of ``min_fused`` coordinates or more where given), and the MoE
    kernels compiled rather than interpreted; ``objective_fn`` gives the
    runner a stop rule that never fires (``tol_std`` 0).  The chunk runs on
    the CPU first, for its arguments."""
    from repro.core import engine, pme
    from repro.kernels.pme_average import ops
    from repro.launch import train
    from repro.models import moe

    args = train.parse_args([
        "--arch", arch, "--variant", "smoke", "--nodes", str(nodes),
        "--mixing", "dense", "--batch", "2", "--seq", "32", "--chunk", "4"])
    _, bound, state, make_batch, _, _ = train.build_everything(args)
    run = engine.make_scan_runner(bound.step, chunk_size=4,
                                  objective_fn=objective_fn, tol_std=0.0)
    run(state, make_batch, 4, copy_state=False)  # on the CPU, for the arguments
    (chunk, arguments), = run.chunk_programs().values()
    on_chip = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip), arguments)
    with pytest.MonkeyPatch.context() as mp:
        if fused:
            mp.setattr(pme, "_accelerator", lambda: True)
            mp.setattr(ops, "_on_cpu", lambda: False)
            mp.setattr(moe, "_interpret", lambda: False)
            if min_fused is not None:
                mp.setattr(pme, "_KERNEL_MIN_ELEMS", min_fused)
            jax.clear_caches()  # trace the chunk again, through the gate
        return chunk.lower(*on_chip).compile()


def _work(text: str) -> list:
    """The fusions and custom calls of a module outside fused computations,
    but the compiler's own bookkeeping calls."""
    import re

    from bench import scopes

    computations = scopes.parse(text)
    fused = {callee for insts in computations.values()
             for _, _, op, _, calls in insts if op == "fusion" for callee in calls}
    bookkeeping = set(re.findall(
        r"%([\w.\-]+) = [^\n]*custom_call_target=\"(?:"
        + "|".join(BOOKKEEPING_CALLS) + r")\"", text))
    return [inst for name, insts in computations.items() if name not in fused
            for inst, _, op, _, _ in insts
            if op in ("fusion", "custom-call") and inst not in bookkeeping]


@pytest.fixture
def bench_importable():
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if repo not in sys.path:
        sys.path.insert(0, repo)


def test_pame_chunk_is_named_by_scope_for_v5e(one_chip, bench_importable):
    """Every scope of the round appears in the compiled chunk, but the stop
    rule's (the run has no objective), and at least 95% of the fusions and
    custom calls outside fused computations map to one."""
    from bench import scopes

    text = _smoke_chunk_for_v5e(one_chip).as_text()
    mapping = scopes.scope_map(text)
    paths = {p for p in mapping.values() if p is not None}
    assert set(scopes.SCOPES) - {"engine.carry"} == {name for p in paths for name in p}
    work = _work(text)
    mapped = sum(mapping[inst] is not None for inst in work)
    assert len(work) > 100 and mapped >= 0.95 * len(work), (mapped, len(work))


def test_pame_chunk_with_fused_masks_is_named_by_scope_for_v5e(one_chip, bench_importable):
    """With the fused bernoulli kernel (every smoke leaf of 2^17 or more
    coordinates), each kernel call is a TPU custom call under
    ``pame.exchange``/``pme.average``, the norm scales' draws still under
    ``pme.mask``, and the round stays as fully named."""
    import re

    from bench import scopes
    from repro.core import pme

    events = []
    listener = lambda event, **kw: events.append(kw) if event == pme.FUSED_MASK_EVENT else None
    jax.monitoring.register_event_listener(listener)
    try:
        text = _smoke_chunk_for_v5e(one_chip, fused=True).as_text()
    finally:
        jax.monitoring.unregister_event_listener(listener)
    assert {e["leaves"] for e in events} == {8}
    mapping = scopes.scope_map(text)
    kernels = re.findall(r"%([\w.\-]+) = [^\n]*custom_call_target=\"tpu_custom_call\"", text)
    assert len(kernels) == 8
    assert {mapping[k] for k in kernels} == {("pame.exchange", "pme.average")}
    paths = {p for p in mapping.values() if p is not None}
    assert set(scopes.SCOPES) - {"engine.carry"} == {name for p in paths for name in p}
    work = _work(text)
    mapped = sum(mapping[inst] is not None for inst in work)
    assert mapped >= 0.95 * len(work), (mapped, len(work))


def test_stop_rule_chunk_keeps_its_selects_and_their_scratch_for_v5e(
        one_chip, bench_importable):
    """Under an objective (one that never fires) the chunk compiles the
    freeze selects under ``engine.carry``, and they cost scratch that the
    chunk without a stop rule does not need."""
    from bench import scopes

    plain = _smoke_chunk_for_v5e(one_chip)
    ruled = _smoke_chunk_for_v5e(
        one_chip, objective_fn=lambda params: jnp.zeros((), jnp.float32))
    mapping = scopes.scope_map(ruled.as_text())
    assert "engine.carry" in {name for p in mapping.values() if p for name in p}
    assert (plain.memory_analysis().temp_size_in_bytes
            < ruled.memory_analysis().temp_size_in_bytes)


MOE_SCOPES = ("moe.route", "moe.experts", "moe.shared", "moe.combine", "mla.attend")


def test_moe_share_chunk_is_named_by_scope_for_v5e(one_chip, bench_importable):
    """The DeepSeek-V2-Lite ep8 share (MLA, a dense layer, then routed
    experts of which the share holds some, and shared ones) over 3 nodes:
    its grouped matmuls compile to Pallas kernels, every round scope
    and the model's ``moe.*``/``mla.attend`` scopes appear, at least 95% of
    the work maps to a round scope, and the expert leaves [m, L, E, d, f]
    go through the fused bernoulli kernel (here every leaf of 2^14
    coordinates or more, as every expert leaf at published widths)."""
    import re

    from bench import scopes
    from repro.core import pme
    from repro.models import moe

    events = []

    def listener(event, **kw):
        events.append((event, kw))

    jax.monitoring.register_event_listener(listener)
    try:
        text = _smoke_chunk_for_v5e(one_chip, fused=True, arch="deepseek-v2-lite-16b-ep8",
                                    nodes=3, min_fused=1 << 14).as_text()
    finally:
        jax.monitoring.unregister_event_listener(listener)
    dispatch = [kw for event, kw in events if event == moe.DISPATCH_EVENT]
    assert dispatch and {kw["held"] for kw in dispatch} == {2}
    assert {(kw["router"], kw["top_k"], kw["row_bound"]) for kw in dispatch} == {(4, 2, 128)}
    fused = [kw for event, kw in events if event == pme.FUSED_MASK_EVENT]
    expert = 3 * 3 * 1 * 2 * 128 * 64  # w_gate, w_up, w_down of 3 nodes
    assert fused and all(kw["coordinates"] >= expert for kw in fused)
    kernels = re.findall(r"%([\w.\-]+) = [^\n]*custom_call_target=\"tpu_custom_call\"", text)
    assert {k.split(".")[0] for k in kernels} >= {"gmm", "tgmm"}
    mapping = scopes.scope_map(text)
    paths = {p for p in mapping.values() if p is not None}
    assert set(scopes.SCOPES) - {"engine.carry"} == {name for p in paths for name in p}
    for name in MOE_SCOPES:
        assert re.search(r'op_name="[^"]*pame\.local_step[^"]*' + re.escape(name), text), name
    work = _work(text)
    mapped = sum(mapping[inst] is not None for inst in work)
    assert mapped >= 0.95 * len(work), (mapped, len(work))
    # the benchmark's MoE probe names the kernels by the model's scopes
    from bench import cells

    probe = cells.Suite().module("probes", "moe_scopes")
    moe_map = probe.scope_map(text)
    assert set(probe.NAMES) <= set(moe_map.values())
    assert {moe_map[k] for k in kernels if k.startswith(("gmm", "tgmm"))} == {"moe.experts"}
