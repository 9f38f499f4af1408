"""Decoder assembly: groups of scanned blocks, three execution paths.

A model is a sequence of *groups*; each group scans `repeat` copies of a
block `pattern` (list of block kinds).  Params and caches are stacked along
the scan axis, so HLO size is independent of depth:

  dense/vlm/audio : [("attn", "mlp")] * L              (one group)
  moe             : dense first layers, then (mla|attn, moe)
  ssm             : [("mamba",)] * L
  hybrid (zamba2) : super-blocks [shared_block, mamba*attn_every] — the
                    transformer block's *weights* are shared across all
                    applications (Zamba2), its KV cache is per-site.

Paths:
  train_loss  — full sequence, next-token CE (+ MoE aux), optional remat;
  train_loss_counted — the same loss and the pass's counters
                (``expert_rows``: the (token, choice) pairs the held
                experts computed);
  prefill     — full sequence, returns logits of last position + caches;
  decode_step — one token against ring-buffer caches (serve_step).
"""
from __future__ import annotations

import dataclasses
from typing import Any, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import attention as attn
from repro.models import moe as moe_mod
from repro.models import ssm as ssm_mod
from repro.models.config import ModelConfig
from repro.models.layers import dense_init, embed_init, linear, rms_norm
from repro.models.mlp import mlp_apply, mlp_init

__all__ = [
    "LayerGroup",
    "layer_groups",
    "init_params",
    "train_loss",
    "train_loss_counted",
    "prefill",
    "decode_step",
    "init_cache",
]


def _no_aux():
    """A block's aux: (MoE balance loss, expert rows computed)."""
    return jnp.zeros((), jnp.float32), jnp.zeros((), jnp.int32)


@dataclasses.dataclass(frozen=True)
class LayerGroup:
    repeat: int
    pattern: Tuple[str, ...]  # block kinds, e.g. ("attn", "mlp")


def layer_groups(cfg: ModelConfig) -> List[LayerGroup]:
    at = cfg.arch_type
    if at in ("dense", "vlm", "audio"):
        kind = "mla" if cfg.use_mla else "attn"
        return [LayerGroup(cfg.n_layers, (kind, "mlp"))]
    if at == "moe":
        kind = "mla" if cfg.use_mla else "attn"
        groups = []
        if cfg.first_dense_layers:
            groups.append(LayerGroup(cfg.first_dense_layers, (kind, "mlp")))
        groups.append(
            LayerGroup(cfg.n_layers - cfg.first_dense_layers, (kind, "moe"))
        )
        return [g for g in groups if g.repeat > 0]
    if at == "ssm":
        return [LayerGroup(cfg.n_layers, ("mamba",))]
    if at == "hybrid":
        every = cfg.attn_every
        n_full = cfg.n_layers // every
        rem = cfg.n_layers - n_full * every
        groups = []
        if n_full:
            groups.append(LayerGroup(n_full, ("shared_block",) + ("mamba",) * every))
        if rem:
            groups.append(LayerGroup(1, ("shared_block",) + ("mamba",) * rem))
        return groups
    raise ValueError(f"unknown arch_type {at!r}")


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------
def _block_init(key: jax.Array, kind: str, cfg: ModelConfig, dtype) -> dict:
    d = cfg.d_model
    if kind == "attn":
        return {"ln": jnp.ones((d,), dtype), "attn": attn.gqa_init(key, cfg, dtype)}
    if kind == "mla":
        return {"ln": jnp.ones((d,), dtype), "attn": attn.mla_init(key, cfg, dtype)}
    if kind == "mlp":
        return {"ln": jnp.ones((d,), dtype), "mlp": mlp_init(key, d, cfg.d_ff, dtype)}
    if kind == "moe":
        return {"ln": jnp.ones((d,), dtype), "moe": moe_mod.moe_init(key, cfg, dtype)}
    if kind == "mamba":
        return {"ln": jnp.ones((d,), dtype), "mamba": ssm_mod.mamba_init(key, cfg, dtype)}
    raise ValueError(kind)


def _shared_block_init(key: jax.Array, cfg: ModelConfig, dtype) -> dict:
    k1, k2 = jax.random.split(key)
    return {
        "ln1": jnp.ones((cfg.d_model,), dtype),
        "attn": attn.gqa_init(k1, cfg, dtype),
        "ln2": jnp.ones((cfg.d_model,), dtype),
        "mlp": mlp_init(k2, cfg.d_model, cfg.d_ff, dtype),
    }


def init_params(key: jax.Array, cfg: ModelConfig) -> dict:
    dtype = jnp.dtype(cfg.dtype)
    groups = layer_groups(cfg)
    keys = jax.random.split(key, len(groups) + 4)
    params: dict = {
        "embed": embed_init(keys[0], cfg.vocab, cfg.d_model, dtype),
        "final_norm": jnp.ones((cfg.d_model,), dtype),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = dense_init(keys[1], (cfg.d_model, cfg.vocab), dtype)
    if cfg.arch_type == "vlm":
        params["vision_proj"] = dense_init(
            keys[2], (cfg.vision_dim, cfg.d_model), dtype
        )
    if cfg.arch_type == "hybrid":
        params["shared_block"] = _shared_block_init(keys[3], cfg, dtype)

    gparams = []
    for gi, grp in enumerate(groups):
        gkey = keys[4 + gi]

        def one_layer(k, _grp=grp):
            bkeys = jax.random.split(k, len(_grp.pattern))
            return {
                f"{i}_{kind}": _block_init(bk, kind, cfg, dtype)
                for i, (kind, bk) in enumerate(zip(_grp.pattern, bkeys))
                if kind != "shared_block"
            }

        lkeys = jax.random.split(gkey, grp.repeat)
        gparams.append(jax.vmap(one_layer)(lkeys))
    params["groups"] = gparams
    return params


# ---------------------------------------------------------------------------
# caches
# ---------------------------------------------------------------------------
def _block_cache(kind: str, cfg: ModelConfig, batch: int, capacity: int, dtype):
    if kind in ("attn", "shared_block"):
        return attn.init_kv_cache(cfg, batch, capacity, dtype)
    if kind == "mla":
        return attn.init_mla_cache(cfg, batch, capacity, dtype)
    if kind == "mamba":
        return ssm_mod.init_ssm_cache(cfg, batch, dtype)
    return None  # mlp / moe carry no cache


def init_cache(cfg: ModelConfig, batch: int, capacity: int) -> list:
    """Abstract-friendly cache pytree mirroring the group structure."""
    dtype = jnp.dtype(cfg.dtype)
    caches = []
    for grp in layer_groups(cfg):
        entry = {}
        for i, kind in enumerate(grp.pattern):
            c = _block_cache(kind, cfg, batch, capacity, dtype)
            if c is not None:
                entry[f"{i}_{kind}"] = jax.tree_util.tree_map(
                    lambda x: jnp.broadcast_to(
                        x[None], (grp.repeat,) + x.shape
                    ).copy(),
                    c,
                )
        caches.append(entry)
    return caches


# ---------------------------------------------------------------------------
# block application
# ---------------------------------------------------------------------------
def _apply_block_full(
    kind: str,
    bparams: dict,
    shared: Optional[dict],
    cfg: ModelConfig,
    x: jax.Array,
    positions: jax.Array,
    want_cache: bool,
    capacity: int,
):
    """Full-sequence (train/prefill). Returns (x, cache_or_None, aux), aux
    being (balance loss, expert rows)."""
    aux = _no_aux()
    if kind == "attn":
        h, cache = attn.gqa_apply(
            bparams["attn"], cfg, rms_norm(x, bparams["ln"]), positions,
            return_cache=want_cache, cache_capacity=capacity,
        )
        return x + h, cache, aux
    if kind == "mla":
        h, cache = attn.mla_apply(
            bparams["attn"], cfg, rms_norm(x, bparams["ln"]), positions,
            return_cache=want_cache, cache_capacity=capacity,
        )
        return x + h, cache, aux
    if kind == "mlp":
        return x + mlp_apply(bparams["mlp"], rms_norm(x, bparams["ln"])), None, aux
    if kind == "moe":
        h, loss, rows = moe_mod.moe_apply(bparams["moe"], cfg, rms_norm(x, bparams["ln"]))
        return x + h, None, (loss, rows)
    if kind == "mamba":
        h, cache = ssm_mod.mamba_apply(
            bparams["mamba"], cfg, rms_norm(x, bparams["ln"]), return_cache=want_cache
        )
        return x + h, cache, aux
    if kind == "shared_block":
        sb = shared
        h, cache = attn.gqa_apply(
            sb["attn"], cfg, rms_norm(x, sb["ln1"]), positions,
            return_cache=want_cache, cache_capacity=capacity,
        )
        x = x + h
        x = x + mlp_apply(sb["mlp"], rms_norm(x, sb["ln2"]))
        return x, cache, aux
    raise ValueError(kind)


def _apply_block_decode(
    kind: str,
    bparams: dict,
    shared: Optional[dict],
    cfg: ModelConfig,
    x: jax.Array,
    pos: jax.Array,
    cache,
):
    if kind == "attn":
        h, c = attn.gqa_decode(bparams["attn"], cfg, rms_norm(x, bparams["ln"]), pos, cache)
        return x + h, c
    if kind == "mla":
        h, c = attn.mla_decode(bparams["attn"], cfg, rms_norm(x, bparams["ln"]), pos, cache)
        return x + h, c
    if kind == "mlp":
        return x + mlp_apply(bparams["mlp"], rms_norm(x, bparams["ln"])), None
    if kind == "moe":
        h, _, _ = moe_mod.moe_apply(bparams["moe"], cfg, rms_norm(x, bparams["ln"]))
        return x + h, None
    if kind == "mamba":
        h, c = ssm_mod.mamba_decode(bparams["mamba"], cfg, rms_norm(x, bparams["ln"]), cache)
        return x + h, c
    if kind == "shared_block":
        sb = shared
        h, c = attn.gqa_decode(sb["attn"], cfg, rms_norm(x, sb["ln1"]), pos, cache)
        x = x + h
        x = x + mlp_apply(sb["mlp"], rms_norm(x, sb["ln2"]))
        return x, c
    raise ValueError(kind)


# ---------------------------------------------------------------------------
# trunk runners
# ---------------------------------------------------------------------------
def _run_trunk_full(
    params: dict,
    cfg: ModelConfig,
    x: jax.Array,
    positions: jax.Array,
    want_cache: bool,
    capacity: int,
):
    shared = params.get("shared_block")
    groups = layer_groups(cfg)
    caches_out = []
    aux_total = _no_aux()
    for grp, gparams in zip(groups, params["groups"]):

        def body(carry, layer_params):
            h, aux_acc = carry
            cache_entries = {}
            for i, kind in enumerate(grp.pattern):
                bp = layer_params.get(f"{i}_{kind}")
                h, cache, aux = _apply_block_full(
                    kind, bp, shared, cfg, h, positions, want_cache, capacity
                )
                aux_acc = (aux_acc[0] + aux[0], aux_acc[1] + aux[1])
                if cache is not None:
                    cache_entries[f"{i}_{kind}"] = cache
            return (h, aux_acc), cache_entries

        if cfg.remat:
            body = jax.checkpoint(body)
        if cfg.unroll:
            ys = []
            carry = (x, aux_total)
            for li in range(grp.repeat):
                lp = jax.tree_util.tree_map(lambda t, _li=li: t[_li], gparams)
                carry, y = body(carry, lp)
                ys.append(y)
            (x, aux_total) = carry
            gcache = (
                jax.tree_util.tree_map(lambda *zs: jnp.stack(zs), *ys)
                if ys and ys[0]
                else {}
            )
        else:
            (x, aux_total), gcache = jax.lax.scan(body, (x, aux_total), gparams)
        caches_out.append(gcache)
    return x, caches_out, aux_total


def _run_trunk_decode(
    params: dict,
    cfg: ModelConfig,
    x: jax.Array,
    pos: jax.Array,
    caches: list,
):
    shared = params.get("shared_block")
    groups = layer_groups(cfg)
    new_caches = []
    for grp, gparams, gcache in zip(groups, params["groups"], caches):

        def body(h, xs):
            layer_params, layer_cache = xs
            out_entries = {}
            for i, kind in enumerate(grp.pattern):
                bp = layer_params.get(f"{i}_{kind}")
                ck = f"{i}_{kind}"
                h, c = _apply_block_decode(
                    kind, bp, shared, cfg, h, pos, layer_cache.get(ck)
                )
                if c is not None:
                    out_entries[ck] = c
            return h, out_entries

        if cfg.unroll:
            ys = []
            for li in range(grp.repeat):
                sl = lambda t, _li=li: t[_li]
                x, y = body(
                    x,
                    (
                        jax.tree_util.tree_map(sl, gparams),
                        jax.tree_util.tree_map(sl, gcache),
                    ),
                )
                ys.append(y)
            gcache_new = jax.tree_util.tree_map(lambda *zs: jnp.stack(zs), *ys)
        else:
            x, gcache_new = jax.lax.scan(body, x, (gparams, gcache))
        new_caches.append(gcache_new)
    return x, new_caches


# ---------------------------------------------------------------------------
# embeddings & heads
# ---------------------------------------------------------------------------
def _embed_inputs(params: dict, cfg: ModelConfig, batch: dict) -> jax.Array:
    tok = batch["tokens"]
    x = params["embed"][tok]
    if cfg.arch_type == "vlm":
        patches = batch["patch_embeds"]  # [B, n_patches, vision_dim]
        vis = linear(patches.astype(x.dtype), params["vision_proj"])
        x = jnp.concatenate([vis, x], axis=1)
    return x


def _logits(params: dict, cfg: ModelConfig, x: jax.Array) -> jax.Array:
    x = rms_norm(x, params["final_norm"])
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    return jnp.einsum("...d,dv->...v", x, head).astype(jnp.float32)


# ---------------------------------------------------------------------------
# public paths
# ---------------------------------------------------------------------------
def train_loss_counted(params: dict, cfg: ModelConfig, batch: dict):
    """(loss, {"expert_rows": int32}): ``train_loss`` and the (token,
    choice) pairs the held experts of every MoE layer computed (0 in a
    model without MoE layers)."""
    x = _embed_inputs(params, cfg, batch)
    s_total = x.shape[1]
    positions = jnp.arange(s_total)
    x, _, (aux, rows) = _run_trunk_full(params, cfg, x, positions, False, s_total)
    logits = _logits(params, cfg, x)
    tok = batch["tokens"]
    if cfg.arch_type == "vlm":
        logits = logits[:, cfg.n_patches :]
    pred = logits[:, :-1]
    tgt = tok[:, 1:]
    logz = jax.nn.logsumexp(pred, axis=-1)
    gold = jnp.take_along_axis(pred, tgt[..., None], axis=-1)[..., 0]
    ce = jnp.mean(logz - gold)
    return ce + aux, {"expert_rows": rows}


def train_loss(params: dict, cfg: ModelConfig, batch: dict) -> jax.Array:
    """Next-token cross-entropy (+ MoE aux).  batch: tokens [B,S]
    (+ patch_embeds for vlm); loss over text positions only."""
    return train_loss_counted(params, cfg, batch)[0]


def prefill(params: dict, cfg: ModelConfig, batch: dict, capacity: int):
    """Returns (last-position logits [B, vocab], caches)."""
    x = _embed_inputs(params, cfg, batch)
    positions = jnp.arange(x.shape[1])
    x, caches, _ = _run_trunk_full(params, cfg, x, positions, True, capacity)
    logits = _logits(params, cfg, x[:, -1:])[:, 0]
    return logits, caches


def decode_step(params: dict, cfg: ModelConfig, token: jax.Array, pos: jax.Array, caches: list):
    """token [B] int32, pos scalar int32 -> (logits [B, vocab], caches)."""
    x = params["embed"][token][:, None]  # [B,1,d]
    x, new_caches = _run_trunk_decode(params, cfg, x, pos, caches)
    logits = _logits(params, cfg, x)[:, 0]
    return logits, new_caches
