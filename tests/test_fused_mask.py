"""The fused bernoulli PME kernel (`kernels.pme_average`, masks drawn in the
kernel) in interpret mode: its masks are `jax.random.bernoulli`'s bit for
bit, its average is `core.pme`'s einsum path, it reads no sender that no
receiver selected; and where `pme_average_pytree` takes it, which it
reports by a `jax.monitoring` event, and the round's `senders_drawn`."""
import contextlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import pme
from repro.core.pame import PaMEConfig, make_topology_arrays, pame_init, pame_step
from repro.core.topology import build_topology
from repro.kernels.pme_average.kernel import bernoulli_blocks, bernoulli_threshold
from repro.kernels.pme_average.ops import pme_bernoulli_average

# per-node leaf shapes whose rows and columns are not multiples of a block
# (512 columns, strips of 16 rows), a 1-D one, and one with a layer axis
SHAPES = [(37, 600), (2, 19, 130), (5000,)]
KEYS = [0, 2**31 + 11]


def _key(seed, leaf=3):
    return jax.random.fold_in(jax.random.PRNGKey(seed), leaf)


def _bcast(x, shape):
    return jnp.reshape(x, (-1,) + (1,) * len(shape))


@contextlib.contextmanager
def fused_masks_fire():
    """The fused-mask events recorded while the block runs."""
    events = []

    def on_event(event, **kwargs):
        if event == pme.FUSED_MASK_EVENT:
            events.append(kwargs)

    jax.monitoring.register_event_listener(on_event)
    try:
        yield events
    finally:
        jax.monitoring.unregister_event_listener(on_event)


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("p", [0.05, 0.2, 1.0])
@pytest.mark.parametrize("m", [2, 3, 4])
def test_kernel_masks_are_bernoullis_bit_for_bit(m, p, shape):
    """Receiver i pulls from sender i+1 alone; sender j holds the constant
    j+1 everywhere, so receiver i reads sender i+1's value exactly where
    that sender's mask keeps the coordinate and its own value elsewhere."""
    src = (np.arange(m) + 1) % m
    a = jnp.zeros((m, m)).at[src, np.arange(m)].set(1.0)
    w = _bcast(jnp.arange(1, m + 1, dtype=jnp.float32), shape) * jnp.ones((m,) + shape)
    for seed in KEYS:
        key = _key(seed)
        out = pme_bernoulli_average(w.astype(jnp.bfloat16), key, a, p)
        masks = jax.random.bernoulli(key, p, (m,) + shape)
        drawn = out == _bcast(jnp.asarray(src + 1, jnp.bfloat16), shape)
        np.testing.assert_array_equal(np.asarray(drawn), np.asarray(masks[src]))


def _einsum(leaf, masks, a):
    """`core.pme`'s einsum path on the leaf.  A bf16 leaf is averaged in
    float32 and rounded once, as the bf16 einsum (exact products, float32
    sums) does; XLA's CPU backend has no bf16 x bf16 -> f32 dot."""
    f = jax.jit(lambda x: pme._average_leaf(x, masks, a, None, "bernoulli"))
    return f(leaf.astype(jnp.float32)).astype(leaf.dtype)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("p", [0.05, 0.2, 1.0])
@pytest.mark.parametrize("m", [2, 3, 4])
def test_kernel_average_is_the_einsum_paths(m, p, shape, dtype):
    """Bitwise where a coordinate's count is 0, 1, 2 or 4 (a power of two
    divides exactly); within one ulp of the dtype where it is 3.  The
    values are multiples of 1/8, so every sum is exact in either order."""
    rng = np.random.default_rng(m * 100 + int(p * 20))
    a = jnp.asarray((rng.random((m, m)) < 0.7) & ~np.eye(m, dtype=bool), jnp.float32)
    a = a.at[(np.arange(m) + 1) % m, np.arange(m)].set(1.0)  # every receiver pulls
    leaf = jnp.asarray(rng.integers(-64, 64, (m,) + shape) / 8, dtype)
    key = _key(KEYS[1], leaf=m)
    masks = jax.random.bernoulli(key, p, leaf.shape)
    out = np.asarray(pme_bernoulli_average(leaf, key, a, p), np.float32)
    ref = np.asarray(_einsum(leaf, masks, a), np.float32)
    cnt = np.asarray(jnp.einsum("j...,ji->i...", masks.astype(jnp.float32), a))
    power = np.isin(cnt, (0, 1, 2, 4))
    np.testing.assert_array_equal(out[power], ref[power])
    ulp = np.spacing(np.abs(ref[~power]).astype(dtype)).astype(np.float32)
    assert np.all(np.abs(out[~power] - ref[~power]) <= ulp)


@pytest.mark.parametrize("m", [2, 3, 4])
def test_kernel_leaves_a_receiver_that_pulls_nothing_as_it_was(m):
    rng = np.random.default_rng(m)
    leaf = jnp.asarray(rng.standard_normal((m, 2, 19, 130)), jnp.bfloat16)
    a = jnp.ones((m, m)) - jnp.eye(m)
    a = a.at[:, 0].set(0.0)  # receiver 0 does not communicate
    out = pme_bernoulli_average(leaf, _key(1), a, 0.5)
    np.testing.assert_array_equal(np.asarray(out[0]), np.asarray(leaf[0]))
    assert not np.array_equal(np.asarray(out[1]), np.asarray(leaf[1]))


@pytest.mark.parametrize("m", [3, 4])
def test_kernel_reads_no_sender_that_no_receiver_selected(m):
    """Sender 0 is in no receiver's selection: filling it with NaN moves
    no other receiver's output by a bit."""
    rng = np.random.default_rng(10 + m)
    leaf = jnp.asarray(rng.standard_normal((m, 37, 600)), jnp.float32)
    a = (jnp.ones((m, m)) - jnp.eye(m)).at[0, :].set(0.0)
    key = _key(2)
    out = pme_bernoulli_average(leaf, key, a, 0.3)
    poisoned = pme_bernoulli_average(leaf.at[0].set(jnp.nan), key, a, 0.3)
    np.testing.assert_array_equal(np.asarray(out[1:]), np.asarray(poisoned[1:]))


@pytest.mark.parametrize("p", [1e-7, 0.05, 0.1, 0.2, 1 / 3, 0.5, 0.9, 1.0, 1.5])
def test_threshold_splits_the_uniform_floats_where_bernoulli_does(p):
    """bits >> 9 = u keeps a coordinate iff u < t, and `uniform`'s float
    u * 2^-23 is below float32(p) exactly for the u under t."""
    t = int(bernoulli_threshold(p))
    below = np.float32(p)
    for u in (t - 1, t):
        if 0 <= u < 1 << 23:
            assert (np.float32(u) * np.float32(2.0**-23) < below) == (u < t)


def test_blocks_fit_the_budget_and_refuse_too_many_nodes():
    br, bc, strip = bernoulli_blocks(4, 100352, 2048, 2)
    assert (bc, strip) == (512, 16) and br % strip == 0
    assert 4 * br * bc * (4 * 2 + 8) <= 12 << 20
    assert bernoulli_blocks(4, 5, 300, 4) == (5, 300, 5)
    assert bernoulli_blocks(4096, 100352, 2048, 2) is None


# ---------------------------------------------------------------------------
# where pme_average_pytree takes the kernel
# ---------------------------------------------------------------------------
M = 4


def _tree():
    rng = np.random.default_rng(0)
    return {
        "big": jnp.asarray(rng.standard_normal((M, 2, 128, 300)), jnp.float32),
        "wide": jnp.asarray(rng.standard_normal((M, 40000)), jnp.float32),
        "scale": jnp.ones((M, 300), jnp.float32),  # under _KERNEL_MIN_ELEMS
    }


def _selection():
    return jnp.zeros((M, M)).at[1, 0].set(1.0).at[2, 0].set(1.0).at[0, 3].set(1.0)


def _exchange(tree, key=None, **kwargs):
    key = jax.random.PRNGKey(7) if key is None else key
    fn = jax.jit(lambda k, t, a: pme.pme_average_pytree(k, t, a, 0.3, **kwargs))
    return fn(key, tree, _selection())


@pytest.fixture
def accelerator(monkeypatch):
    """The gate as on an accelerator; the kernel still runs interpreted."""
    monkeypatch.setattr(pme, "_accelerator", lambda: True)


def test_cpu_keeps_the_einsum_and_fires_no_event():
    with fused_masks_fire() as events:
        out = _exchange(_tree())
    assert events == []
    masks = jax.random.bernoulli(jax.random.fold_in(jax.random.PRNGKey(7), 0),
                                 0.3, _tree()["big"].shape)
    ref = pme._average_leaf(_tree()["big"], masks, _selection(), None, "bernoulli")
    np.testing.assert_array_equal(np.asarray(out["big"]), np.asarray(ref))


def test_gate_routes_large_leaves_and_reports_them(accelerator):
    tree = _tree()
    with fused_masks_fire() as events:
        out = _exchange(tree)
    assert events == [{"leaves": 2, "coordinates": M * 2 * 128 * 300 + M * 40000}]
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(pme, "_accelerator", lambda: False)
        ref = _exchange(tree)
    for name in tree:
        np.testing.assert_array_equal(np.asarray(out[name]), np.asarray(ref[name]))


@pytest.mark.parametrize("case", ["exact", "self_params", "rbg_key", "not_partitionable"])
def test_gate_keeps_the_einsum_elsewhere(accelerator, case):
    """Exact masks come from a global top_k, a self view replaces the
    fallback, and another PRNG (or threefry drawn whole) gives other bits:
    none of these reaches the kernel."""
    tree = _tree()
    with contextlib.ExitStack() as stack:
        kwargs, key = {}, None
        if case == "exact":
            kwargs["mode"] = "exact"
        elif case == "self_params":
            kwargs["self_params"] = tree
        elif case == "rbg_key":
            key = jax.random.key(7, impl="rbg")
        else:
            stack.enter_context(jax.threefry_partitionable(False))
        with fused_masks_fire() as events:
            _exchange(tree, key=key, **kwargs)
    assert events == []


def test_padded_mixing_never_takes_the_kernel(accelerator):
    topo = build_topology("complete", M)
    arrs = make_topology_arrays(topo, PaMEConfig(nu=0.5))
    sel = pme.sample_neighbor_selection_padded(
        jax.random.PRNGKey(1), arrs.nbrs, arrs.valid, arrs.t, jnp.ones((M,), bool))
    with fused_masks_fire() as events:
        pme.pme_average_pytree_padded(jax.random.PRNGKey(2), _tree(), arrs.nbrs, sel,
                                      0.3, mode="bernoulli")
    assert events == []


# ---------------------------------------------------------------------------
# senders_drawn
# ---------------------------------------------------------------------------
def test_senders_drawn_counts_the_distinct_selected_senders():
    m = 6
    topo = build_topology("erdos_renyi", m, p=0.5, seed=3)
    cfg = PaMEConfig(nu=0.5, p=0.3, mask_mode="bernoulli", homogeneous_kappa=2)
    arrs = make_topology_arrays(topo, cfg)
    params = {"w": jnp.zeros((m, 8))}

    def grad_fn(p, b, k):
        return jnp.sum(p["w"] ** 2), p

    state = pame_init(jax.random.PRNGKey(4), params, m, cfg)
    for k in range(4):
        key = jax.random.fold_in(state.key, state.step * 3)
        comm = (state.step % arrs.kappa) == 0
        a = pme.sample_neighbor_selection(key, arrs.nbrs, arrs.valid, arrs.t, comm)
        state, metrics = pame_step(state, params, grad_fn, arrs, cfg)
        drawn = int(metrics["senders_drawn"])
        if k % 2:  # kappa 2: nobody communicates in odd rounds
            assert drawn == 0
        else:
            assert drawn == int(np.sum(np.asarray(a).any(axis=1))) > 0
