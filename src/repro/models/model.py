"""Public model API: one namespace per architecture family.

    model = get_model(cfg)
    params = model.init(cfg, key)
    loss   = model.loss(cfg, params, batch)            # train
    logits, cache = model.prefill(cfg, params, batch)  # prefill
    logits, cache = model.decode(cfg, params, batch, cache)

``input_specs(cfg, shape)`` builds ShapeDtypeStruct stand-ins for every
model input of the given assigned input shape (weak-type-correct, no
device allocation) — the multi-pod dry-run lowers against these.
``param_logical_axes`` gives the logical sharding of every parameter.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig, ShapeConfig
from repro.models import hybrid as hy
from repro.models import ssm_model as ssm
from repro.models import transformer as tf


@dataclasses.dataclass(frozen=True)
class Model:
    init: Callable
    loss: Callable
    forward: Callable            # full-seq: (cfg, params, batch, cache)
    decode: Callable             # (cfg, params, batch, cache)
    make_cache: Callable         # (cfg, batch_size, max_len)
    # (cfg, params, batch) -> (loss, stats): counters of the pass the
    # train step reports beside the loss; None = no counters
    loss_stats: Optional[Callable] = None


def _tf_prefill(cfg, params, batch, cache):
    logits, _, new_cache, _ = tf.transformer_forward(cfg, params, batch,
                                                     cache=cache)
    return logits, new_cache


def _ssm_prefill(cfg, params, batch, cache):
    logits, _, new_cache = ssm.ssm_forward(cfg, params, batch, cache=cache)
    return logits, new_cache


def _hy_prefill(cfg, params, batch, cache):
    logits, _, new_cache = hy.hybrid_forward(cfg, params, batch, cache=cache)
    return logits, new_cache


_FAMILIES: Dict[str, Model] = {
    "transformer": Model(
        init=tf.init_transformer,
        loss=tf.transformer_loss,
        forward=_tf_prefill,
        decode=tf.transformer_decode,
        make_cache=tf.make_transformer_cache,
        loss_stats=tf.transformer_loss_stats,
    ),
    "ssm": Model(
        init=ssm.init_ssm_model,
        loss=ssm.ssm_loss,
        forward=_ssm_prefill,
        decode=ssm.ssm_decode,
        make_cache=lambda cfg, b, m: ssm.make_ssm_cache(cfg, b, m),
    ),
    "hybrid": Model(
        init=hy.init_hybrid,
        loss=hy.hybrid_loss,
        forward=_hy_prefill,
        decode=hy.hybrid_decode,
        make_cache=hy.make_hybrid_cache,
    ),
}


def get_model(cfg: ArchConfig) -> Model:
    if cfg.family in ("dense", "moe", "vlm", "audio"):
        return _FAMILIES["transformer"]
    return _FAMILIES[cfg.family]


# ----------------------------------------------------------------------
# input specs (dry-run stand-ins and data-pipeline shape contracts)
# ----------------------------------------------------------------------
def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


def input_specs(cfg: ArchConfig, shape: ShapeConfig) -> Dict[str, Any]:
    """ShapeDtypeStructs for the batch of ``shape.kind``. For decode the
    batch is a single new token; the cache spec comes separately from
    ``cache_specs``."""
    B, S = shape.global_batch, shape.seq_len
    i32 = jnp.int32
    cdt = cfg.dtype("compute")
    E = cfg.d_model

    if shape.kind in ("train", "prefill"):
        specs = {"positions": _sds((B, S), i32)}
        if cfg.family == "audio":
            specs["tokens"] = _sds((B, cfg.n_codebooks, S), i32)
            specs["cond"] = _sds((B, cfg.cond_len, E), cdt)
            if shape.kind == "train":
                specs["labels"] = _sds((B, cfg.n_codebooks, S), i32)
        elif cfg.family == "vlm":
            vp = cfg.vision_prefix
            specs["tokens"] = _sds((B, S - vp), i32)
            specs["vision"] = _sds((B, vp, E), cdt)
            specs["positions"] = _sds((B, 3, S), i32)
            if shape.kind == "train":
                specs["labels"] = _sds((B, S), i32)
        else:
            specs["tokens"] = _sds((B, S), i32)
            if shape.kind == "train":
                specs["labels"] = _sds((B, S), i32)
        return specs

    # decode: ONE new token at position S-1, cache holds the prefix
    if cfg.family == "audio":
        tok = {"tokens": _sds((B, cfg.n_codebooks, 1), i32),
               "positions": _sds((B, 1), i32)}
    elif cfg.family == "vlm":
        tok = {"tokens": _sds((B, 1), i32),
               "positions": _sds((B, 3, 1), i32)}
    else:
        tok = {"tokens": _sds((B, 1), i32),
               "positions": _sds((B, 1), i32)}
    return tok


def cache_specs(cfg: ArchConfig, shape: ShapeConfig) -> Any:
    """ShapeDtypeStructs for the decode cache of ``shape``."""
    model = get_model(cfg)
    cache = jax.eval_shape(
        lambda: model.make_cache(cfg, shape.global_batch, shape.seq_len))
    return cache


def param_specs(cfg: ArchConfig) -> Any:
    """ShapeDtypeStructs of the parameter pytree (no allocation)."""
    model = get_model(cfg)
    key = jax.random.PRNGKey(0)
    return jax.eval_shape(lambda k: model.init(cfg, k), key)


def make_batch(cfg: ArchConfig, shape: ShapeConfig, key) -> Dict[str, Any]:
    """Concrete random batch matching ``input_specs`` (for smoke tests
    and CPU examples; never used by the dry-run)."""
    specs = input_specs(cfg, shape)
    out = {}
    for name, s in specs.items():
        key, k = jax.random.split(key)
        if s.dtype == jnp.int32 and name in ("tokens", "labels"):
            out[name] = jax.random.randint(k, s.shape, 0, cfg.vocab_size,
                                           jnp.int32)
        elif name == "positions":
            if cfg.family == "vlm" and s.shape[1] == 3:
                pos = jnp.arange(s.shape[-1], dtype=jnp.int32)
                out[name] = jnp.broadcast_to(pos, s.shape)
            else:
                pos = jnp.arange(s.shape[-1], dtype=jnp.int32)
                out[name] = jnp.broadcast_to(pos, s.shape)
        else:
            out[name] = jax.random.normal(k, s.shape, jnp.float32
                                          ).astype(s.dtype) * 0.02
    if cfg.family == "vlm" and "labels" in out:
        # vision prefix carries no LM loss
        vp = cfg.vision_prefix
        out["labels"] = out["labels"].at[:, :vp].set(-100)
    return out


# ----------------------------------------------------------------------
# parameter sharding rules (logical axes; see repro.common.sharding)
# ----------------------------------------------------------------------
_COLUMN = {"wq", "wk", "wv", "w_gate", "w_up", "w1", "w_uk", "w_uv",
           "w_z", "w_x"}
_ROW = {"wo", "w_down", "w2", "out_proj"}
_COLUMN_BIAS = {"bq", "bk", "bv", "b1"}
_VEC_SHARDED = {"norm_w", "conv_x"}


def param_logical_axes(cfg: ArchConfig, params_shape) -> Any:
    """Pytree (matching params) of logical PartitionSpec name tuples."""
    def rule(path, leaf):
        names = [getattr(p, "key", getattr(p, "name", None)) or
                 str(getattr(p, "idx", "")) for p in path]
        last = names[-1]
        parent = names[-2] if len(names) > 1 else ""
        ndim = len(leaf.shape)
        lead = ndim - 2  # stacked-layer / expert leading axes

        def spec(*tail):
            return tuple([None] * (ndim - len(tail)) + list(tail))

        if parent == "experts":
            # (Ne, E, F) / (Ne, F, E): expert-parallel on axis -3
            return tuple([None] * (ndim - 3) + ["experts", None, None])
        if last == "embed":
            if cfg.family == "audio":
                return spec("vocab", None)
            return spec("vocab", None)
        if last == "lm_head":
            return spec(None, "vocab")
        if last in _COLUMN:
            return spec(None, "ff")
        if last in _ROW:
            return spec("ff", None)
        if last in _COLUMN_BIAS:
            return spec("ff")
        if last == "norm_w":
            return spec("ssm_inner")
        if parent == "conv_x" and last == "w":
            return spec(None, "ssm_inner")
        if parent == "conv_x" and last == "b":
            return spec("ssm_inner")
        if parent in ("a", "b") or last in ("a", "b"):
            # LoRA factors: a (din, r) row-ish, b (r, dout) column-ish —
            # both small; replicate.
            return spec(None, None) if ndim >= 2 else spec(None)
        return tuple([None] * ndim)

    return jax.tree_util.tree_map_with_path(rule, params_shape)
