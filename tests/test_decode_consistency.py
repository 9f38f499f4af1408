"""Serving correctness: prefill + token-by-token decode must reproduce the
full-sequence forward logits for every cached family (incl. absorbed MLA,
SSD state handoff, sliding-window ring buffer, hybrid shared-attn caches)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import ModelConfig, init_params, prefill, decode_step
from repro.models.model import _embed_inputs, _logits, _run_trunk_full

CONFIGS = {
    "dense": ModelConfig(
        "dense", "dense", n_layers=2, d_model=64, vocab=64,
        n_heads=4, n_kv_heads=2, head_dim=16, d_ff=128, qk_norm=True,
    ),
    "window": ModelConfig(
        "window", "dense", n_layers=2, d_model=64, vocab=64,
        n_heads=4, n_kv_heads=2, head_dim=16, d_ff=128, window=8,
    ),
    "mla_moe": ModelConfig(
        "mla", "moe", n_layers=3, d_model=64, vocab=64,
        n_heads=4, n_kv_heads=4, head_dim=16, use_mla=True, kv_lora=32,
        q_lora=24, rope_head_dim=8, v_head_dim=16, d_ff=128, n_experts=4,
        n_shared_experts=1, moe_top_k=2, d_ff_expert=32, first_dense_layers=1,
    ),
    "ssm": ModelConfig(
        "ssm", "ssm", n_layers=2, d_model=64, vocab=64,
        ssm_state=16, ssm_head_dim=16, ssm_chunk=8,
    ),
    "hybrid": ModelConfig(
        "hybrid", "hybrid", n_layers=5, d_model=64, vocab=64,
        n_heads=4, n_kv_heads=2, head_dim=16, d_ff=128,
        ssm_state=16, ssm_head_dim=16, ssm_chunk=8, attn_every=2,
    ),
}


@pytest.mark.parametrize("name", list(CONFIGS))
def test_decode_matches_full_forward(name):
    cfg = CONFIGS[name]
    b, s = 2, 16
    params = init_params(jax.random.PRNGKey(1), cfg)
    tok = jnp.asarray(
        np.random.default_rng(1).integers(0, cfg.vocab, (b, s)), jnp.int32
    )
    batch = {"tokens": tok}
    x = _embed_inputs(params, cfg, batch)
    xf, _, _ = _run_trunk_full(params, cfg, x, jnp.arange(s), False, s)
    full_logits = _logits(params, cfg, xf)
    half = s // 2
    lg, caches = prefill(params, cfg, {"tokens": tok[:, :half]}, s)
    errs = [float(jnp.max(jnp.abs(lg - full_logits[:, half - 1])))]
    for t in range(half, s):
        lg, caches = decode_step(params, cfg, tok[:, t], jnp.int32(t), caches)
        errs.append(float(jnp.max(jnp.abs(lg - full_logits[:, t]))))
    assert max(errs) < 3e-4, (name, errs)


def test_ring_buffer_wraparound_matches_windowed_attention():
    """Decode past the cache capacity with a window: ring buffer must agree
    with a full-capacity run restricted to the same window."""
    cfg = CONFIGS["window"]  # window=8
    b, s, cap = 1, 24, 8  # capacity == window
    params = init_params(jax.random.PRNGKey(0), cfg)
    tok = jnp.asarray(np.random.default_rng(0).integers(0, cfg.vocab, (b, s)), jnp.int32)
    # ground truth: full forward with window mask
    batch = {"tokens": tok}
    x = _embed_inputs(params, cfg, batch)
    xf, _, _ = _run_trunk_full(params, cfg, x, jnp.arange(s), False, s)
    full_logits = _logits(params, cfg, xf)
    # ring-buffer decode with capacity = window only
    lg, caches = prefill(params, cfg, {"tokens": tok[:, :4]}, cap)
    errs = []
    for t in range(4, s):
        lg, caches = decode_step(params, cfg, tok[:, t], jnp.int32(t), caches)
        errs.append(float(jnp.max(jnp.abs(lg - full_logits[:, t]))))
    assert max(errs) < 3e-4, errs


def test_unrolled_matches_scanned():
    cfg = CONFIGS["dense"]
    params = init_params(jax.random.PRNGKey(2), cfg)
    tok = jnp.asarray(np.random.default_rng(2).integers(0, 64, (2, 12)), jnp.int32)
    from repro.models.model import train_loss

    l_scan = train_loss(params, cfg, {"tokens": tok})
    l_unroll = train_loss(params, cfg.replace(unroll=True), {"tokens": tok})
    np.testing.assert_allclose(float(l_scan), float(l_unroll), rtol=1e-5)
