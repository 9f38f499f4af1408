"""The dropless expert layer that holds a share of the experts
(``repro.models.moe``), DeepSeek-V2's gate, and YaRN rope, on the CPU at
small widths in float32."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import ModelConfig, init_params, train_loss_counted
from repro.models import attention, layers, moe
from repro.models.mlp import mlp_apply

CFG = ModelConfig(
    "share", "moe", n_layers=2, d_model=64, vocab=64, n_heads=4, n_kv_heads=4,
    head_dim=16, use_mla=True, kv_lora=32, rope_head_dim=8, v_head_dim=16,
    d_ff=128, n_experts=16, n_shared_experts=1, moe_top_k=3, d_ff_expert=32,
    first_dense_layers=1, norm_topk_prob=False, router_aux_coef=0.001,
)
B, S = 2, 64  # T * k = 384 rows, three row tiles


def _x(seed=0, shape=(B, S, CFG.d_model)):
    return jax.random.normal(jax.random.PRNGKey(seed), shape, jnp.float32)


def _expert(params, j, x):
    p = {name: params[name][j] for name in ("w_gate", "w_up", "w_down")}
    return mlp_apply(p, x)


def _dense_layer(params, cfg, x):
    """Every expert on every token, weighted by the token's gate for it
    (0 where not chosen): the layer written out with no dispatch."""
    probs = jax.nn.softmax(x @ params["router"], axis=-1)
    gate, choice = jax.lax.top_k(probs, cfg.moe_top_k)
    if cfg.norm_topk_prob:
        gate = gate / gate.sum(-1, keepdims=True)
    weight = jnp.einsum("bske,bsk->bse", jax.nn.one_hot(choice, cfg.n_experts), gate)
    y = sum(_expert(params, j, x) * weight[..., j:j + 1] for j in range(cfg.n_experts))
    return y + mlp_apply(params["shared"], x)


def test_eight_shares_add_up_to_the_uncut_layer():
    """8 shares of 2 of 16 experts: their routed parts, with the shared
    expert counted once, add up to the whole layer; each share's balance
    loss is the whole layer's (the router is whole everywhere), and the
    shares' rows are every (token, choice) pair once."""
    params = moe.moe_init(jax.random.PRNGKey(1), CFG, jnp.float32)
    x = _x()
    with jax.default_matmul_precision("highest"):
        whole, aux, rows = moe.moe_apply(params, CFG, x)
        shared = mlp_apply(params["shared"], x.reshape(-1, CFG.d_model)).reshape(x.shape)
        share_cfg = CFG.replace(experts_held=2)
        total, total_rows = shared, 0
        for s in range(8):
            part = dict(params, **{n: params[n][2 * s:2 * s + 2]
                                   for n in ("w_gate", "w_up", "w_down")})
            y, a, r = moe.moe_apply(part, share_cfg, x, first_expert=2 * s)
            total = total + (y - shared)
            total_rows += int(r)
            assert float(a) == pytest.approx(float(aux), rel=1e-6)
        dense = _dense_layer(params, CFG, x)
    assert total_rows == int(rows) == B * S * CFG.moe_top_k
    np.testing.assert_allclose(total, whole, atol=2e-5)
    np.testing.assert_allclose(whole, dense, atol=2e-5)


def test_dropless_when_every_token_picks_the_same_experts():
    """All B*S tokens route to experts 0-5 (of 16; 8 held): every pair is
    computed, none dropped (a capacity of 1.25 T k / E would keep 36 of
    each expert's 128 rows)."""
    cfg = CFG.replace(moe_top_k=6, experts_held=8)
    params = moe.moe_init(jax.random.PRNGKey(2), cfg, jnp.float32)
    router = np.zeros((cfg.d_model, cfg.n_experts), np.float32)
    router[:, :6] = np.linspace(3.0, 1.0, 6)
    params["router"] = jnp.asarray(router)
    x = jnp.abs(_x(3)) + 0.1  # positive rows: every token's top 6 are 0-5
    with jax.default_matmul_precision("highest"):
        y, _, rows = moe.moe_apply(params, cfg, x)
        full = dict(params, **{n: jnp.concatenate(
            [params[n], jnp.zeros((8,) + params[n].shape[1:])]) for n in ("w_gate", "w_up", "w_down")})
        dense = _dense_layer(full, cfg, x)
    assert int(rows) == B * S * 6
    np.testing.assert_allclose(y, dense, atol=2e-5)


@pytest.mark.parametrize("counts", [(3, 0, 300, 1), (0, 0, 0, 0), (700, 0, 0, 0)])
def test_each_group_starts_on_a_tile_and_takes_whole_tiles(counts):
    """The row buffer's layout: a group of n pairs takes ceil(n / ROW_TILE)
    whole tiles from a tile boundary, so a grouped matmul's tiles do not
    depend on how the pairs split inside a tile; every held pair has its
    own row, and src and dst invert each other; the buffer holds the
    worst case."""
    held, tile = len(counts), moe.ROW_TILE
    elsewhere = 700 - sum(counts)
    group = np.concatenate([np.full(n, g) for g, n in enumerate(counts)]
                           + [np.full(elsewhere, held)])
    group = jnp.asarray(np.random.default_rng(0).permutation(group), jnp.int32)
    sizes, src, dst = moe._group_rows(group, held)
    rows = src.shape[0]
    assert rows % tile == 0 and rows >= 700 + held * (tile - 1)
    np.testing.assert_array_equal(sizes, [-(-n // tile) * tile for n in counts])
    starts = np.cumsum(sizes) - sizes
    for p, g in enumerate(np.asarray(group)):
        if g == held:
            assert dst[p] == rows
        else:
            assert starts[g] <= dst[p] < starts[g] + counts[g] and src[dst[p]] == p
    assert int(jnp.sum(src < 700)) == sum(counts)


@pytest.mark.parametrize("renormalise", [False, True])
def test_the_gate_is_not_renormalised(renormalise):
    """With every expert alike, the routed part is the expert's output times
    the sum of the top-k probabilities (under 1), or times 1 with
    ``norm_topk_prob``."""
    cfg = CFG.replace(norm_topk_prob=renormalise, n_shared_experts=0)
    params = moe.moe_init(jax.random.PRNGKey(4), cfg, jnp.float32)
    for name in ("w_gate", "w_up", "w_down"):
        params[name] = jnp.broadcast_to(params[name][:1], params[name].shape)
    x = _x(5)
    with jax.default_matmul_precision("highest"):
        y, _, _ = moe.moe_apply(params, cfg, x)
        one = _expert(params, 0, x)
        top = jax.lax.top_k(jax.nn.softmax(x @ params["router"], -1), cfg.moe_top_k)[0].sum(-1)
    expected = one if renormalise else one * top[..., None]
    assert renormalise or float(top.max()) < 0.9
    np.testing.assert_allclose(y, expected, atol=2e-5)


def test_nodes_merged_under_vmap_match_each_node_alone():
    """``vmap`` over nodes (PaME's local step) merges the nodes' groups into
    one grouped matmul; loss, gradients and rows match each node alone."""
    cfg = get_config("deepseek-v2-lite-16b-ep8", "smoke")
    m = 3
    params = jax.vmap(lambda k: init_params(k, cfg))(jax.random.split(jax.random.PRNGKey(0), m))
    toks = jnp.asarray(np.random.default_rng(0).integers(0, cfg.vocab, (m, 2, 32)), jnp.int32)

    def step(p, t):
        return jax.value_and_grad(
            lambda q: train_loss_counted(q, cfg, {"tokens": t}), has_aux=True)(p)

    (loss, stats), grads = jax.vmap(step)(params, toks)
    for i in range(m):
        (l_i, s_i), g_i = step(jax.tree_util.tree_map(lambda a: a[i], params), toks[i])
        assert float(loss[i]) == pytest.approx(float(l_i), rel=1e-6)
        assert int(stats["expert_rows"][i]) == int(s_i["expert_rows"]) > 0
        for a, b in zip(jax.tree_util.tree_leaves(grads), jax.tree_util.tree_leaves(g_i)):
            np.testing.assert_allclose(a[i], b, atol=1e-6)


def test_yarn_frequencies_and_scale_follow_the_formula():
    """DeepSeek-V2-Lite's rope: dims 64, theta 1e4, factor 40 over 4096
    positions, beta 32/1: the correction range is dims 10-23 (floor and
    ceil of 64 ln(4096 / (beta 2 pi)) / (2 ln 1e4) = 10.47, 22.51); the
    softmax scale 192^-1/2 (0.1 * 0.707 ln 40 + 1)^2."""
    cfg = get_config("deepseek-v2-lite-16b", "full")
    inv = np.asarray(layers.yarn_inv_freq(64, 1e4, cfg))
    plain = 1e4 ** (-np.arange(0, 64, 2) / 64)
    ramp = np.clip((np.arange(32) - 10) / 13, 0, 1)
    np.testing.assert_allclose(inv, plain / 40 * ramp + plain * (1 - ramp), rtol=1e-6)
    assert inv[5] == pytest.approx(plain[5]) and inv[30] == pytest.approx(plain[30] / 40)
    cos, sin = layers.rope_freqs(jnp.arange(8), 64, 1e4, cfg)
    np.testing.assert_allclose(cos ** 2 + sin ** 2, 1.0, rtol=1e-6)  # mscale / mscale_all_dim = 1
    scale = 192 ** -0.5 * (0.1 * 0.707 * math.log(40) + 1) ** 2
    assert attention._mla_scale(cfg) == pytest.approx(scale) == pytest.approx(0.114722, rel=1e-5)
    assert attention._mla_scale(cfg.replace(yarn_factor=0.0)) == pytest.approx(192 ** -0.5)


def test_the_share_counts_its_parameters():
    """``param_count`` of the ep8 share at 5 layers: the held experts, the
    64-wide router and the untied head, to the parameter."""
    cfg = get_config("deepseek-v2-lite-16b-ep8", "full").replace(n_layers=5)
    shapes = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    assert cfg.param_count() == sum(x.size for x in jax.tree_util.tree_leaves(shapes)) == 535_060_992
    assert shapes["groups"][1]["1_moe"]["moe"]["router"].shape == (4, 2048, 64)
    assert shapes["groups"][1]["1_moe"]["moe"]["w_gate"].shape == (4, 8, 2048, 1408)
    # active: shared + 6 * 8 / 64 of the 8 held experts a token
    idle = 4 * (8 - 0.75) * 3 * 2048 * 1408
    assert cfg.active_param_count() == 535_060_992 - idle
