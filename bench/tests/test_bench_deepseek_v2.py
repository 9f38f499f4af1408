"""DeepSeek-V2-Lite's share against its plain reference
(``bench/reference/deepseek_v2.py``) at smoke widths on the CPU in float32,
the parameter layout at published widths, the FLOP count, and a whole run
of the harness on a smoke cell of the family."""
import json
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import conftest
from bench import cells, harness
from repro.configs import get_config
from repro.models import init_params, moe, train_loss

suite = cells.Suite()
ref = suite.module("reference", "deepseek_v2")
flops = suite.module("flops", "deepseek_v2")
SEED = 2**31 + 57

# the ep8 share's smoke variant: 2 of 4 experts held, untied head, YaRN
SMOKE = {"d_model": 128, "n_heads": 4, "n_kv_heads": 4, "head_dim": 32, "use_mla": True,
         "kv_lora": 64, "q_lora": 0, "rope_head_dim": 16, "v_head_dim": 32,
         "rope_theta": 10000.0, "yarn_factor": 40.0, "yarn_original_max_position": 4096,
         "yarn_beta_fast": 32.0, "yarn_beta_slow": 1.0, "yarn_mscale": 0.707,
         "yarn_mscale_all_dim": 0.707, "d_ff": 256, "n_experts": 4, "experts_held": 2,
         "n_shared_experts": 1, "moe_top_k": 2, "norm_topk_prob": False, "d_ff_expert": 64,
         "first_dense_layers": 1, "router_aux_coef": 0.001, "n_layers": 2, "vocab": 512,
         "tie_embeddings": False, "dtype": "float32"}


def _full_sizes():
    with open(f"{conftest.REPO}/bench/configs/deepseek-v2-lite_m3_l5.json") as f:
        return json.load(f)["model"]


@pytest.mark.parametrize("variant", ["smoke", "full"])
def test_weight_spec_is_the_program_parameter_tree(variant):
    """The reference's weights have the program's tree, shapes and dtypes
    (published widths: abstract shapes only)."""
    cfg = get_config("deepseek-v2-lite-16b-ep8", variant)
    sizes = SMOKE
    if variant == "full":
        sizes = _full_sizes()
        cfg = cfg.replace(n_layers=sizes["n_layers"])
    program = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    reference = jax.eval_shape(lambda: ref.init(jax.random.PRNGKey(0), sizes))
    assert jax.tree_util.tree_structure(program) == jax.tree_util.tree_structure(reference)
    assert [(x.shape, x.dtype) for x in jax.tree_util.tree_leaves(program)] == \
        [(x.shape, x.dtype) for x in jax.tree_util.tree_leaves(reference)]


@pytest.mark.parametrize("arch", ["deepseek-v2-lite-16b-ep8", "deepseek-v2-lite-16b"])
def test_program_matches_the_reference_in_float32(arch):
    """Loss and every gradient leaf, the share and the uncut layer alike, on
    the reference's weights.  Both compute in float32 at the highest matmul
    precision; they differ in summation order only (grouped rows against
    every held expert on every token, one fused score product against two),
    which moves float32 results by under 1e-6 relative: loss to 1e-5
    relative, a gradient leaf to 1e-4 of its largest entry."""
    cfg = get_config(arch, "smoke")
    sizes = dict(SMOKE, experts_held=cfg.experts_held)
    params = ref.init(jax.random.PRNGKey(3), sizes)
    tokens = jnp.asarray(np.random.default_rng(3).integers(0, cfg.vocab, (2, 64)), jnp.int32)
    with jax.default_matmul_precision("highest"):
        loss_p, grad_p = jax.value_and_grad(
            lambda p: train_loss(p, cfg, {"tokens": tokens}))(params)
        loss_r, grad_r = jax.value_and_grad(
            lambda p: ref.loss(p, sizes, tokens, lambda a: a))(params)
    assert float(loss_p) == pytest.approx(float(loss_r), rel=1e-5)
    for path, gp in jax.tree_util.tree_flatten_with_path(grad_p)[0]:
        gr = grad_r
        for key in path:
            gr = gr[key.key if hasattr(key, "key") else key.idx]
        scale = float(jnp.max(jnp.abs(gr)))
        assert scale > 0, jax.tree_util.keystr(path)
        np.testing.assert_allclose(gp, gr, atol=1e-4 * scale, err_msg=jax.tree_util.keystr(path))


def test_shares_add_up_to_the_uncut_reference_layer():
    """4 shares of 2 of 8 experts through the program's layer: their routed
    parts, with the shared expert counted once, add up to the reference's
    uncut layer, and share 0 is the reference's own share of experts 0-1."""
    c = dict(SMOKE, n_experts=8, experts_held=0)
    layer = jax.tree_util.tree_map(
        lambda a: a[0], ref.init(jax.random.PRNGKey(5), c)["groups"][1]["1_moe"]["moe"])
    x = jax.random.normal(jax.random.PRNGKey(6), (2, 64, 128), jnp.float32)
    mm = lambda a, w: jnp.einsum("...d,df->...f", a, w)
    share = get_config("deepseek-v2-lite-16b", "smoke").replace(n_experts=8, experts_held=2)

    def part(s):
        return dict(layer, **{n: layer[n][2 * s:2 * s + 2] for n in ("w_gate", "w_up", "w_down")})

    with jax.default_matmul_precision("highest"):
        whole, _ = ref._moe(layer, c, x, mm, lambda a: a)
        first, _ = ref._moe(part(0), dict(c, experts_held=2), x, mm, lambda a: a)
        shared = ref._swiglu(layer["shared"], x, mm)
        ys = [moe.moe_apply(part(s), share, x, first_expert=2 * s)[0] for s in range(4)]
    np.testing.assert_allclose(ys[0], first, atol=2e-5)
    np.testing.assert_allclose(shared + sum(y - shared for y in ys), whole, atol=2e-5)


def test_flops_per_token_at_published_widths():
    c = _full_sizes()
    mla = 2048 * 16 * 192 + 2048 * 576 + 512 * 16 * 128 * 2 + 16 * 128 * 2048
    assert flops.mla_weights(c) == mla == 13_762_560
    routed = 6 * 8 / 64  # of the 8 held experts, a token's average
    weights = 5 * mla + 3 * 2048 * 10944 + 4 * (2048 * 64 + (2 + routed) * 3 * 2048 * 1408) \
        + 12800 * 2048
    assert flops.matmul_weights(c) == pytest.approx(weights)
    attention = 5 * 2 * 128 * 16 * (128 + 64 + 128)
    assert flops.flops_per_token(c, 128) == pytest.approx(3 * (2 * weights + attention))
    assert 1.5e9 < flops.flops_per_token(c, 128) < 1.6e9
    assert flops.expert_flops(c, 10) == 3 * 2 * 3 * 2048 * 1408 * 10
    # 4 MoE layers x 3 nodes x 8 held experts x 3 matrices, read forward and
    # backward and their gradients written, in bf16; 4 rows of d a pair
    weights = 4 * 3 * 8 * 3 * 2048 * 1408 * 2
    assert flops.expert_bytes(c, 3, 10) == 3 * weights + 4 * 2048 * 2 * 10


def test_a_run_of_the_family_is_correct_and_traced_readers_stay_silent(
        tmp_path, cpu_chip, capsys, monkeypatch):
    monkeypatch.setitem(conftest.SMOKE_MODELS, "deepseek_v2", {
        "config": "deepseek-v2-lite_m3_l5", "traffic": "pame.b8x128", "model": SMOKE})
    # 3 nodes at smoke widths: the local steps move the parameters 0.64 of
    # what the chunk's exchanges do (exchange_gap); the exchange left out
    # reads 1
    monkeypatch.setitem(conftest.SMOKE_LIMITS, "exchange_gap", {"limit": 0.9})
    suite_ = conftest.smoke_suite(str(tmp_path), "deepseek_v2")
    rc = harness.main(["--workload", "smoke.deepseek_v2", "--seed", str(SEED), "--seconds", "1",
                       "--trace", "1"], t0=time.perf_counter(), suite=suite_)
    result = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0 and result["correct"] is True, result["checks"]
    assert result["checks"]["loss_gap"]["value"] < 1e-5
    # the CPU has no device plane: the MoE readers find nothing to read
    assert set(result["metrics"]) == {"host_batch_ms", "compiles_in_window", "mfu"}


def test_the_moe_probe_reads_zero_where_the_chunk_has_no_moe_scope(tmp_path, monkeypatch):
    """A cell whose model has no MoE or MLA layer (stablelm's): the probe
    finds none of its scopes in the chunk's HLO and reads 0 ms for each,
    tracing nothing; the readers then read 0 and the roofline 0."""
    import types

    suite_ = conftest.smoke_suite(str(tmp_path))
    program = harness.Program(suite_, suite_.cell("smoke.dense_lm"))
    seeds = harness.Seeds.of(SEED)
    program.first_chunk(seeds, harness.Feed(program.make_batch, seeds.data_offset, keep=0))
    probe = suite_.module("probes", "moe_scopes")
    measured = probe.measure(types.SimpleNamespace(program=program, seeds=seeds))
    assert measured == {"ms": {name: 0.0 for name in probe.NAMES}, "kernel_ms": 0.0,
                        "expert_rows": None}
    ctx = types.SimpleNamespace(suite=suite_, probe=lambda name: measured)
    # the chip's answer, without a device plane
    monkeypatch.setattr(probe, "probe", lambda c: c.probe("moe_scopes"))
    assert suite_.module("layers", "round_moe_ms").read(ctx) == 0.0
    assert suite_.module("layers", "round_moe_experts_ms").read(ctx) == 0.0
    assert suite_.module("layers", "moe_experts_roofline").read(ctx) == 0.0


def test_the_experts_roofline_is_the_kernels_time_against_the_larger_bound():
    """At published widths the held experts' weights, read twice and their
    gradients written, outweigh their FLOPs: 9216 pairs a round (96 an
    expert, 8 experts, 4 layers, 3 nodes) bound the kernels at the HBM's
    4.98 GB of weights and 0.15 GB of rows, 6.27 ms, against 2.43 ms at the
    FLOP peak."""
    import types

    c = _full_sizes()
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    measured = {"ms": {"moe.experts": 40.0}, "kernel_ms": 30.0, "expert_rows": 9216.0}
    ctx = types.SimpleNamespace(
        suite=suite, peaks=peaks, program=types.SimpleNamespace(m=3),
        cell=types.SimpleNamespace(config={"flops": "deepseek_v2", "model": c}))
    probe = suite.module("probes", "moe_scopes")
    read = suite.module("layers", "moe_experts_roofline").read
    original = probe.probe
    probe.probe = lambda _: measured
    try:
        share = read(ctx)
    finally:
        probe.probe = original
    least_s = flops.expert_bytes(c, 3, 9216) / 819e9
    assert least_s > flops.expert_flops(c, 9216) / 197e12
    assert least_s == pytest.approx(6.27e-3, rel=1e-3)
    assert share == pytest.approx(100 * least_s / 30e-3)
