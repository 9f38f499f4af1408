"""PME mechanism: Theorem 1 unbiasedness, boundedness, mask invariants."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import pme


def test_coordinate_masks_exact_cardinality():
    key = jax.random.PRNGKey(0)
    masks = pme.sample_coordinate_masks(key, m=6, n=50, s=7, mode="exact")
    assert masks.shape == (6, 50)
    assert np.all(np.asarray(masks.sum(axis=1)) == 7)


def test_paper_worked_example():
    """The exact worked example from Sec. III-B of the paper."""
    w_i = jnp.array([2.0, 8.0, 3.0, 6.0])
    w = jnp.stack(
        [
            w_i,                                  # receiver i = node 0
            jnp.array([2.0, 8.0, 1.0, 4.0]),      # node 2 in the paper
            jnp.array([4.0, 7.0, 2.0, 5.0]),      # node 4
            jnp.array([3.0, 6.0, 0.0, 6.0]),      # node 5 (note the real 0!)
        ]
    )
    masks = jnp.array(
        [
            [False, False, False, False],
            [True, False, False, True],   # T_2 = {1, 4}
            [False, False, True, True],   # T_4 = {3, 4}
            [False, False, True, True],   # T_5 = {3, 4}
        ]
    )
    a = jnp.zeros((4, 4)).at[1, 0].set(1.0).at[2, 0].set(1.0).at[3, 0].set(1.0)
    out = pme.pme_average(w, masks, a)
    # paper: v_bar = [2, 8, 1, 5]  ('*' = transmitted true zero participates)
    np.testing.assert_allclose(np.asarray(out[0]), [2.0, 8.0, 1.0, 5.0], atol=1e-6)


def test_theorem1_unbiased_montecarlo():
    """E[v_bar | lambda>0] = mean(w);  E[v_tilde] = (s/n) mean(w)."""
    q, n, s = 5, 8, 3
    rng = np.random.default_rng(0)
    w = jnp.asarray(rng.standard_normal((q, n)), jnp.float32)
    a = jnp.ones((q, q)) - jnp.eye(q)
    a = a.at[:, 1:].set(0.0)  # single receiver 0, neighbors = everyone else
    a = jnp.zeros((q, q)).at[1:, 0].set(1.0)
    target = np.asarray(w[1:]).mean(axis=0)

    trials = 4000
    acc = np.zeros(n)
    cnt_pos = np.zeros(n)
    acc_naive = np.zeros(n)
    for t in range(trials):
        key = jax.random.PRNGKey(t)
        masks = pme.sample_coordinate_masks(key, q, n, s, mode="exact")
        masks = masks.at[0].set(False)  # receiver transmits nothing
        vbar = np.asarray(pme.pme_average(w, masks, a)[0])
        lam = np.asarray(masks[1:].sum(axis=0))
        sel = lam > 0
        acc[sel] += vbar[sel]
        cnt_pos[sel] += 1
        vnaive = np.asarray(pme.naive_average(w, masks, a)[0])
        acc_naive += vnaive
    est = acc / np.maximum(cnt_pos, 1)
    np.testing.assert_allclose(est, target, atol=0.12)  # unbiased
    est_naive = acc_naive / trials
    np.testing.assert_allclose(est_naive, (s / n) * target, atol=0.12)  # biased by s/n


@settings(max_examples=15, deadline=None)
@given(
    m=st.integers(3, 8),
    n=st.integers(4, 32),
    seed=st.integers(0, 10_000),
)
def test_pme_output_bounded_by_inputs(m, n, seed):
    """Lemma 3 ingredient: every PME output coord is a convex combination of
    input coords => ||v_bar||_inf <= ||W||_inf."""
    rng = np.random.default_rng(seed)
    w = jnp.asarray(rng.standard_normal((m, n)), jnp.float32)
    masks = jnp.asarray(rng.random((m, n)) < rng.uniform(0.05, 0.9))
    a = jnp.asarray(
        ((rng.random((m, m)) < 0.5) & ~np.eye(m, dtype=bool)).astype(np.float32)
    )
    out = pme.pme_average(w, masks, a)
    assert float(jnp.max(jnp.abs(out))) <= float(jnp.max(jnp.abs(w))) + 1e-5


def test_pme_no_communication_returns_self():
    rng = np.random.default_rng(1)
    w = jnp.asarray(rng.standard_normal((4, 10)), jnp.float32)
    masks = jnp.ones((4, 10), bool)
    a = jnp.zeros((4, 4))  # nobody selected (k not in K_i)
    out = pme.pme_average(w, masks, a)
    np.testing.assert_allclose(np.asarray(out), np.asarray(w))


def test_pme_pytree_modes_agree_in_expectation():
    rng = np.random.default_rng(2)
    m, n = 6, 40
    tree = {"a": jnp.asarray(rng.standard_normal((m, 5, 8)), jnp.float32)}
    a = jnp.asarray(
        ((rng.random((m, m)) < 0.6) & ~np.eye(m, dtype=bool)).astype(np.float32)
    )
    outs = {}
    for mode in ("exact", "bernoulli"):
        acc = np.zeros((m, 5, 8))
        for t in range(300):
            out = pme.pme_average_pytree(
                jax.random.PRNGKey(t), tree, a, p=0.4, mode=mode
            )
            acc += np.asarray(out["a"])
        outs[mode] = acc / 300
    np.testing.assert_allclose(outs["exact"], outs["bernoulli"], atol=0.15)


def test_message_bits_eq8():
    # paper: 63 s + n bits, and the example 1.63e4 << 6.4e5 for n = 100 s = 1e4
    assert pme.message_bits(100, 10_000) == 63 * 100 + 10_000
    assert pme.message_bits(100, 10_000) < 64 * 10_000 / 30


def test_neighbor_selection_counts_and_validity():
    from repro.core.topology import build_topology
    from repro.core.pame import PaMEConfig, make_topology_arrays

    topo = build_topology("erdos_renyi", 10, p=0.6, seed=0)
    cfg = PaMEConfig(nu=0.4)
    arrs = make_topology_arrays(topo, cfg)
    comm = jnp.ones((10,), bool)
    a = pme.sample_neighbor_selection(
        jax.random.PRNGKey(0), arrs.nbrs, arrs.valid, arrs.t, comm
    )
    a_np = np.asarray(a)
    # column i has exactly t_i senders, all true neighbors of i
    for i in range(10):
        assert a_np[:, i].sum() == int(arrs.t[i])
        senders = np.nonzero(a_np[:, i])[0]
        for j in senders:
            assert topo.adjacency[j, i] == 1
    # non-communicating receiver -> empty column
    comm2 = comm.at[3].set(False)
    a2 = np.asarray(
        pme.sample_neighbor_selection(
            jax.random.PRNGKey(0), arrs.nbrs, arrs.valid, arrs.t, comm2
        )
    )
    assert a2[:, 3].sum() == 0


@pytest.mark.parametrize("self_view", [False, True], ids=["no_self", "self"])
@pytest.mark.parametrize("mask_mode", ["exact", "bernoulli"])
@pytest.mark.parametrize("form", ["dense", "padded"])
def test_exchange_forms_keep_algorithm2_invariants(form, mask_mode, self_view):
    """Both pytree exchange forms keep Algorithm 2's invariants on a
    two-leaf model: a receiver that selects no one keeps its own view bit
    for bit (the self view where one is given), and every output
    coordinate lies within the range of the selected senders' values and
    the receiver's own."""
    from repro.core.pame import PaMEConfig, make_topology_arrays
    from repro.core.topology import build_topology

    m = 6
    topo = build_topology("erdos_renyi", m, p=0.6, seed=0)
    arrs = make_topology_arrays(topo, PaMEConfig(nu=0.5))
    rng = np.random.default_rng(5)
    tree = {"a": jnp.asarray(rng.standard_normal((m, 3, 4)), jnp.float32),
            "b": jnp.asarray(rng.standard_normal((m, 7)), jnp.float32)}
    own = (jax.tree_util.tree_map(lambda x: x + 3.0, tree) if self_view
           else tree)
    comm = jnp.ones((m,), bool).at[1].set(False).at[4].set(False)
    k_sel, k_mask = jax.random.split(jax.random.PRNGKey(7))
    sel = np.asarray(pme.sample_neighbor_selection_padded(
        k_sel, arrs.nbrs, arrs.valid, arrs.t, comm))
    if form == "dense":
        a = pme.sample_neighbor_selection(
            k_sel, arrs.nbrs, arrs.valid, arrs.t, comm)
        out = pme.pme_average_pytree(
            k_mask, tree, a, 0.3, mode=mask_mode,
            self_params=own if self_view else None)
    else:
        out = pme.pme_average_pytree_padded(
            k_mask, tree, arrs.nbrs, jnp.asarray(sel), 0.3, mode=mask_mode,
            pad=~arrs.valid, self_params=own if self_view else None)
    nbrs = np.asarray(arrs.nbrs)
    for name in tree:
        w, v, o = (np.asarray(t[name]) for t in (tree, own, out))
        for i in range(m):
            senders = nbrs[i][sel[i]]
            if not senders.size:
                np.testing.assert_array_equal(o[i], v[i])
                continue
            seen = np.concatenate([w[senders], v[i][None]])
            assert np.all(o[i] >= seen.min(axis=0) - 1e-5)
            assert np.all(o[i] <= seen.max(axis=0) + 1e-5)
            assert not np.array_equal(o[i], v[i])  # the exchange moved it
    assert not sel[1].any() and not sel[4].any() and sel.any()
