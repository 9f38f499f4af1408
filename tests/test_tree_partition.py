"""Pytree-partitioned partial exchange (PaMEConfig.partition="tree").

Covers the three contract pieces of the partitioned format:

  * the flat path is BITWISE-identical to the pre-partition code — the
    pinned loss/consensus curves below were captured before the feature
    landed and must reproduce exactly;
  * per-leaf Eq.-(8) accounting matches a hand-computed total, both in
    the static registry estimate (`wire_bits_for`) and in the realized
    per-step metric under a dynamic scenario;
  * config validation fails loudly (bad partition, p_leaf misuse, rate
    bounds, leaf-count mismatch).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import PaMEConfig, build_topology
from repro.core.algorithms import get_algorithm
from repro.core.pme import leaf_rates, message_bits, tree_message_bits

M = 8


def _problem():
    """Quadratic toward fixed targets over a 2-leaf pytree (sizes 55+37)."""
    rng = np.random.default_rng(0)
    tgt = {"w": jnp.asarray(rng.standard_normal(37), jnp.float32),
           "v": jnp.asarray(rng.standard_normal((5, 11)), jnp.float32)}
    params0 = {"w": jnp.zeros((37,), jnp.float32),
               "v": jnp.zeros((5, 11), jnp.float32)}

    def grad_fn(p, b, k):
        loss = sum(jnp.sum((p[n] - tgt[n]) ** 2) for n in sorted(p))
        g = {n: 2.0 * (p[n] - tgt[n]) for n in p}
        return loss, g

    return params0, grad_fn


def _consensus(params):
    tot = 0.0
    for leaf in jax.tree_util.tree_leaves(params):
        mu = leaf.mean(axis=0, keepdims=True)
        tot = tot + jnp.sum((leaf - mu) ** 2)
    return tot


# Captured at the commit BEFORE partition="tree" existed (same problem,
# same seeds), then re-pinned under jax 0.9.0 with the code unchanged: that
# release turned `jax_threefry_partitionable` on by default, which changes
# every random stream (masks, neighbor selection), so the old curves moved
# from step 3 on (loss 14.00 -> 14.95).  The flat path itself did not change.
# Sampled at steps [0, 3, 7, 11] of a 12-step run.
FLAT_PINS = {
    ("bernoulli", "sparse"): (
        [87.24075317382812, 14.945980072021484, 1.6438064575195312,
         0.20970168709754944],
        [6.774549961090088, 37.67254638671875, 60.93923568725586,
         69.51057434082031], 120640),
    ("bernoulli", "dense"): (
        [87.24075317382812, 14.945980072021484, 1.6438064575195312,
         0.20970168709754944],
        [6.774549961090088, 37.672542572021484, 60.939231872558594,
         69.51057434082031], 120640),
    ("exact", "sparse"): (
        [87.24075317382812, 14.430669784545898, 1.5629053115844727,
         0.16956445574760437],
        [6.774549961090088, 38.13227081298828, 61.316650390625,
         70.04749298095703], 120640),
    ("exact", "dense"): (
        [87.24075317382812, 14.430669784545898, 1.5629053115844727,
         0.16956445574760437],
        [6.774549961090088, 38.132266998291016, 61.316650390625,
         70.04749298095703], 120640),
}


@pytest.mark.parametrize("mask_mode,mixing", sorted(FLAT_PINS))
def test_flat_path_bitwise_identical_to_pre_partition_pins(mask_mode, mixing):
    params0, grad_fn = _problem()
    topo = build_topology("erdos_renyi", M, p=0.5, seed=3)
    cfg = PaMEConfig(nu=0.5, p=0.3, gamma=1.01, sigma0=4.0,
                     kappa_lo=2, kappa_hi=4, mask_mode=mask_mode)
    ba = get_algorithm("pame").bind(grad_fn, topo, cfg, mixing=mixing, seed=0)
    state, hist = ba.run(jax.random.PRNGKey(1), params0, M, lambda k: None,
                         12, objective_fn=_consensus, tol_std=0.0)
    pin_loss, pin_obj, pin_wire = FLAT_PINS[(mask_mode, mixing)]
    loss = [float(x) for x in np.asarray(hist["loss"])[[0, 3, 7, 11]]]
    obj = [float(x) for x in np.asarray(hist["objective"])[[0, 3, 7, 11]]]
    assert loss == pin_loss          # bitwise: exact float equality
    assert obj == pin_obj
    assert int(hist["wire_bits_total"]) == pin_wire


# ---------------------------------------------------------------------------
# Eq. (8) per-leaf accounting
# ---------------------------------------------------------------------------
def test_tree_message_bits_matches_hand_computed_total():
    # dict pytrees flatten in sorted key order: "v" (5*11=55), "w" (37)
    sizes = (55, 37)
    # uniform p=0.3:  s_v = round(16.5) = 16 (banker's), s_w = round(11.1) = 11
    hand = (63 * 16 + 55) + (63 * 11 + 37)
    assert tree_message_bits(sizes, 0.3) == hand
    assert tree_message_bits(sizes, (0.3, 0.3)) == hand
    # per-leaf rates (mirror the implementation's round() exactly)
    s_v = max(1, int(round(0.1 * 55)))
    s_w = max(1, int(round(0.8 * 37)))
    hand2 = (63 * s_v + 55) + (63 * s_w + 37)
    assert tree_message_bits(sizes, (0.1, 0.8)) == hand2
    # int8 payload variant: 8s + n + one f32 absmax scale per segment
    assert tree_message_bits(sizes, 0.3, value_bits=8) == \
        (8 * 16 + 55 + 32) + (8 * 11 + 37 + 32)
    with pytest.raises(ValueError, match="rates"):
        tree_message_bits(sizes, (0.3,))


def test_leaf_rates_validation():
    assert leaf_rates(3, 0.2) == (0.2, 0.2, 0.2)
    assert leaf_rates(2, 0.2, (0.1, 0.9)) == (0.1, 0.9)
    with pytest.raises(ValueError, match="leaves"):
        leaf_rates(3, 0.2, (0.1, 0.9))
    with pytest.raises(ValueError, match="rate"):
        leaf_rates(2, 0.2, (0.1, 1.5))
    with pytest.raises(ValueError, match="rate"):
        leaf_rates(2, 0.2, (0.0, 0.5))


def test_static_wire_accounting_is_per_leaf_for_tree():
    params0, grad_fn = _problem()
    topo = build_topology("erdos_renyi", M, p=0.5, seed=3)
    kw = dict(nu=0.5, p=0.3, gamma=1.01, sigma0=4.0, kappa_lo=2, kappa_hi=4,
              mask_mode="exact")
    flat = get_algorithm("pame").bind(grad_fn, topo, PaMEConfig(**kw),
                                      mixing="dense", seed=0)
    tree = get_algorithm("pame").bind(
        grad_fn, topo, PaMEConfig(partition="tree", **kw),
        mixing="dense", seed=0)
    n = 92
    msgs = flat.wire_bits_for(params0) / message_bits(
        max(1, int(round(0.3 * n))), n)
    # same expected message count, different per-message price
    assert tree.wire_bits_for(params0) == pytest.approx(
        msgs * tree_message_bits((55, 37), 0.3))
    assert flat.wire_bits_for(params0) != tree.wire_bits_for(params0)
    # the flat sizes-aware path must agree with the legacy n_total formula
    assert flat.wire_bits_for(params0) == pytest.approx(flat.wire_bits(n))


def test_realized_dynamic_accounting_scales_by_per_leaf_price():
    """Under edge drops both partitions realize the SAME message count per
    step (comm decisions don't depend on the payload format), so the
    realized totals must differ exactly by the per-message Eq.-(8) ratio."""
    params0, grad_fn = _problem()
    topo = build_topology("erdos_renyi", M, p=0.5, seed=3)
    from repro.core.scenarios import get_scenario
    import dataclasses
    scen = dataclasses.replace(get_scenario("flaky_links"), seed=7)
    kw = dict(nu=0.5, p=0.3, gamma=1.01, sigma0=4.0, kappa_lo=2, kappa_hi=4,
              mask_mode="exact")
    totals = {}
    for name, cfg in [("flat", PaMEConfig(**kw)),
                      ("tree", PaMEConfig(partition="tree", **kw))]:
        ba = get_algorithm("pame").bind(grad_fn, topo, cfg, mixing="dense",
                                        seed=0, scenario=scen)
        _, hist = ba.run(jax.random.PRNGKey(1), params0, M, lambda k: None,
                         12, tol_std=0.0)
        totals[name] = float(hist["wire_bits_total"])
    n = 92
    flat_price = message_bits(max(1, int(round(0.3 * n))), n)
    tree_price = tree_message_bits((55, 37), 0.3)
    assert totals["flat"] > 0
    assert totals["tree"] == pytest.approx(
        totals["flat"] * tree_price / flat_price, rel=1e-6)


def test_tree_partition_trains_and_batched_lanes_account_per_leaf():
    params0, grad_fn = _problem()
    topo = build_topology("erdos_renyi", M, p=0.5, seed=3)
    cfg = PaMEConfig(nu=0.5, p=0.3, gamma=1.01, sigma0=4.0, kappa_lo=2,
                     kappa_hi=4, mask_mode="exact", partition="tree",
                     p_leaf=(0.1, 0.8))
    ba = get_algorithm("pame").bind_batched(
        grad_fn, topo, [cfg], seeds=[0, 1, 2], mixing="dense", seed=0)
    state, hist = ba.run(params0, M, lambda k: None, 12, tol_std=0.0)
    loss = np.asarray(hist["loss"])
    assert loss.shape[-1] == 3  # three seed lanes
    assert float(loss[-1].mean()) < float(loss[0].mean())
    # static estimate: per-leaf prices with the per-leaf rates
    s_v = max(1, int(round(0.1 * 55)))
    s_w = max(1, int(round(0.8 * 37)))
    price = (63 * s_v + 55) + (63 * s_w + 37)
    flat_cfg = PaMEConfig(nu=0.5, p=0.3, gamma=1.01, sigma0=4.0, kappa_lo=2,
                          kappa_hi=4, mask_mode="exact")
    flat = get_algorithm("pame").bind(grad_fn, topo, flat_cfg, mixing="dense",
                                      seed=0)
    msgs = flat.wire_bits_for(params0) / message_bits(
        max(1, int(round(0.3 * 92))), 92)
    wps = np.asarray(hist["wire_bits_per_step"])  # per-lane [L]
    np.testing.assert_allclose(wps, np.full(wps.shape, msgs * price),
                               rtol=1e-6)


def test_config_validation():
    with pytest.raises(ValueError, match="partition"):
        PaMEConfig(partition="columns")
    with pytest.raises(ValueError, match="p_leaf"):
        PaMEConfig(p_leaf=(0.5, 0.5))  # flat partition
    params0, grad_fn = _problem()
    topo = build_topology("erdos_renyi", 4, p=0.9, seed=0)
    cfg = PaMEConfig(partition="tree", p_leaf=(0.5, 0.5, 0.5))  # 3 != 2 leaves
    ba = get_algorithm("pame").bind(grad_fn, topo, cfg, mixing="dense", seed=0)
    with pytest.raises(ValueError, match="leaves"):
        ba.run(jax.random.PRNGKey(1), params0, 4, lambda k: None, 2,
               tol_std=0.0)
