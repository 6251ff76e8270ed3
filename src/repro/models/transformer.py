"""Unified decoder-only transformer covering the dense / moe / vlm /
audio families. Layers are uniform and scanned (``lax.scan`` over
stacked per-layer parameters) so HLO size and compile time are flat in
depth; DeepSeek's leading dense layer runs outside the scan.

Device scopes: latent attention runs under ``jax.named_scope("ddal.mla")``
and the expert layer under ``"ddal.moe"`` (its held experts' products
under ``"ddal.experts"``, ``repro.models.moe``), so a profiler trace
of a train step splits them out of ``ddal.grad``.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.common.sharding import shard
from repro.models import attention as attn
from repro.models.common import (cross_entropy, dense_init, embed_init,
                                 rms_norm, sinusoidal_positions)
from repro.models.mlp import gelu_mlp, init_gelu_mlp, init_swiglu, swiglu
from repro.models.moe import init_moe, moe_apply


# ----------------------------------------------------------------------
# init
# ----------------------------------------------------------------------
def _init_layer(cfg, key, *, dense_ff: Optional[int] = None):
    """One decoder layer. dense_ff overrides MoE with a dense FF."""
    ka, kc, kf = jax.random.split(key, 3)
    dt = cfg.dtype("param")
    p = {"ln1": jnp.ones((cfg.d_model,), dt),
         "ln2": jnp.ones((cfg.d_model,), dt)}
    if cfg.mla is not None:
        p["attn"] = attn.init_mla(cfg, ka)
    else:
        p["attn"] = attn.init_self_attention(cfg, ka)
    if cfg.cross_attention:
        p["ln_x"] = jnp.ones((cfg.d_model,), dt)
        p["xattn"] = attn.init_cross_attention(cfg, kc)
    if dense_ff is not None:
        p["mlp"] = (init_gelu_mlp(kf, cfg.d_model, dense_ff, dt)
                    if cfg.family == "audio"
                    else init_swiglu(kf, cfg.d_model, dense_ff, dt))
    else:
        p["moe"] = init_moe(cfg, kf)
    return p


def init_transformer(cfg, key):
    k_embed, k_layers, k_head, k_l0 = jax.random.split(key, 4)
    dt = cfg.dtype("param")
    V, E = cfg.vocab_size, cfg.d_model
    params = {}
    if cfg.family == "audio":
        params["embed"] = embed_init(k_embed, (cfg.n_codebooks, V, E), dt)
        params["lm_head"] = dense_init(k_head, (cfg.n_codebooks, E, V), dt)
    else:
        params["embed"] = embed_init(k_embed, (V, E), dt)
        if not cfg.tie_embeddings:
            params["lm_head"] = dense_init(k_head, (E, V), dt)
    params["final_norm"] = jnp.ones((E,), dt)

    dense_ff = cfg.d_ff if cfg.moe is None else None
    n_scan = cfg.n_layers - cfg.first_k_dense
    keys = jax.random.split(k_layers, n_scan)
    params["layers"] = jax.vmap(
        lambda k: _init_layer(cfg, k, dense_ff=dense_ff))(keys)
    if cfg.first_k_dense:
        params["layer0"] = _init_layer(cfg, k_l0, dense_ff=cfg.dense_ff)
    return params


# ----------------------------------------------------------------------
# forward
# ----------------------------------------------------------------------
def _layer_apply(cfg, p, x, positions, cond, layer_cache, *,
                 dense_ff: bool):
    cdt = cfg.dtype("compute")
    h = rms_norm(x, p["ln1"], cfg.norm_eps)
    if cfg.mla is not None:
        with jax.named_scope("ddal.mla"):
            a, new_cache = attn.mla_attention(cfg, p["attn"], h, positions,
                                              layer_cache and
                                              layer_cache.get("kv"))
    else:
        a, new_cache = attn.self_attention(cfg, p["attn"], h, positions,
                                           layer_cache=layer_cache and
                                           layer_cache.get("kv"))
    x = x + a
    new_xcache = None
    if cfg.cross_attention:
        hx = rms_norm(x, p["ln_x"], cfg.norm_eps)
        cx, new_xcache = attn.cross_attention(
            cfg, p["xattn"], hx, cond,
            layer_cache and layer_cache.get("xkv"))
        x = x + cx
    h2 = rms_norm(x, p["ln2"], cfg.norm_eps)
    aux = jnp.float32(0.0)
    stats = {}
    if dense_ff:
        f = (gelu_mlp(p["mlp"], h2, cdt) if cfg.family == "audio"
             else swiglu(p["mlp"], h2, cdt))
    else:
        with jax.named_scope("ddal.moe"):
            f, aux, stats = moe_apply(cfg, p["moe"], h2)
    x = x + f
    out_cache = None
    if layer_cache is not None:
        out_cache = {}
        if new_cache is not None:
            out_cache["kv"] = new_cache
        if new_xcache is not None:
            out_cache["xkv"] = new_xcache
    return x, aux, out_cache, stats


def _embed(cfg, params, tokens, positions, vision=None):
    cdt = cfg.dtype("compute")
    emb = params["embed"].astype(cdt)
    if cfg.family == "audio":
        # tokens: (B, n_codebooks, S) — summed codebook embeddings
        x = sum(emb[i][tokens[:, i]] for i in range(cfg.n_codebooks))
        flat_pos = positions
        x = x + sinusoidal_positions(flat_pos, cfg.d_model).astype(cdt)
        return x
    x = emb[tokens]
    if cfg.family == "vlm" and vision is not None:
        # pre-projected patch embeddings prepended to the text tokens
        x = jnp.concatenate([vision.astype(cdt), x], axis=1)
    return x


def transformer_forward(cfg, params, batch, cache=None):
    """Full-sequence pass (train / prefill).

    batch: tokens, positions [, labels, vision, cond].
    Returns (logits, aux_loss, new_cache, stats): stats are the scanned
    layers' counters from ``moe_apply``, each stacked over the layers.
    """
    cdt = cfg.dtype("compute")
    cond = batch.get("cond")
    if cond is not None:
        cond = cond.astype(cdt)
    x = _embed(cfg, params, batch["tokens"], batch["positions"],
               batch.get("vision"))
    x = shard(x, "batch", None, None)
    positions = batch["positions"]
    dense_ff = cfg.moe is None

    l0_cache = None
    if cfg.first_k_dense:
        lc = None if cache is None else jax.tree.map(
            lambda c: c[0], cache["layer0"])
        x, _, l0_cache, _ = _layer_apply(cfg, params["layer0"], x,
                                         positions, cond, lc,
                                         dense_ff=True)
        if l0_cache is not None:
            l0_cache = jax.tree.map(lambda c: c[None], l0_cache)

    def body(carry, per_layer):
        xc, aux_sum = carry
        lp, lcache = per_layer
        xo, aux, new_cache, stats = _layer_apply(
            cfg, lp, xc, positions, cond, lcache, dense_ff=dense_ff)
        return (xo, aux_sum + aux), (new_cache, stats)

    body_fn = body
    if cfg.remat and cache is None:
        body_fn = jax.checkpoint(
            body, policy=jax.checkpoint_policies.nothing_saveable)

    scan_cache = None if cache is None else cache["layers"]
    n_scan = cfg.n_layers - cfg.first_k_dense
    unroll = cfg.unroll_layers
    if scan_cache is None:
        # scan still needs a per-layer xs structure: params only
        (x, aux_sum), (_, stats) = jax.lax.scan(
            lambda c, lp: body_fn(c, (lp, None)),
            (x, jnp.float32(0.0)), params["layers"], unroll=unroll)
        new_cache = None
    else:
        (x, aux_sum), (new_layer_caches, stats) = jax.lax.scan(
            body_fn, (x, jnp.float32(0.0)),
            (params["layers"], scan_cache), unroll=unroll)
        new_cache = {"layers": new_layer_caches}
        if l0_cache is not None:
            new_cache["layer0"] = l0_cache

    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = _lm_head(cfg, params, x)
    return logits, aux_sum, new_cache, stats


def _lm_head(cfg, params, x):
    cdt = cfg.dtype("compute")
    if cfg.family == "audio":
        heads = params["lm_head"].astype(cdt)         # (4, E, V)
        logits = jnp.einsum("bsd,kdv->bksv", x, heads)
        return shard(logits, "batch", None, None, "vocab")
    w = (params["embed"].T if cfg.tie_embeddings
         else params["lm_head"]).astype(cdt)
    return shard(x @ w, "batch", None, "vocab")


def transformer_decode(cfg, params, batch, cache):
    """One-token decode. batch: tokens (B,1) or (B,K,1) for audio,
    positions (B,1) / (B,3,1); cache from make_cache/prefill."""
    logits, _, new_cache, _ = transformer_forward(cfg, params, batch,
                                                  cache=cache)
    return logits, new_cache


def transformer_loss(cfg, params, batch):
    return transformer_loss_stats(cfg, params, batch)[0]


def transformer_loss_stats(cfg, params, batch):
    """(loss, stats). Where the expert layers hold a share of the
    experts, stats counts this pass's token-expert pairs routed to
    them: ``held_pairs``, the sum over layers and held experts, and
    ``held_pairs_max``, the most any held expert got in one layer.
    Empty otherwise."""
    logits, aux, _, layer_stats = transformer_forward(cfg, params, batch)
    # labels cover the full (vision_prefix + text) sequence for vlm;
    # the data pipeline marks vision positions with -100.
    loss = cross_entropy(logits, batch["labels"]) + aux
    stats = {}
    if "held_pairs" in layer_stats:
        pairs = layer_stats["held_pairs"]                # (L, n_held)
        stats = {"held_pairs": jnp.sum(pairs),
                 "held_pairs_max": jnp.max(pairs)}
    return loss, stats


def make_transformer_cache(cfg, batch: int, max_len: int):
    n_scan = cfg.n_layers - cfg.first_k_dense
    def one(n):
        entry = {}
        if cfg.mla is not None:
            entry["kv"] = attn.make_mla_cache(cfg, batch, max_len, n)
        else:
            entry["kv"] = attn.make_kv_cache(cfg, batch, max_len, n)
        if cfg.cross_attention:
            H, D = cfg.n_heads, cfg.head_dim
            entry["xkv"] = {
                "ck": jnp.zeros((n, batch, cfg.cond_len, H, D),
                                cfg.dtype("compute")),
                "cv": jnp.zeros((n, batch, cfg.cond_len, H, D),
                                cfg.dtype("compute")),
            }
        return entry
    cache = {"layers": one(n_scan)}
    if cfg.first_k_dense:
        # layer0 is dense FF but same attention type
        cache["layer0"] = one(1)
    return cache
