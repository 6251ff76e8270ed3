"""Attention layers: GQA/MHA self-attention (RoPE / M-RoPE / none,
optional sliding window, optional QKV bias), cross-attention
(MusicGen conditioning) and Multi-head Latent Attention (DeepSeek-V2).

All functions are pure; decode-time KV caches are functional values
threaded through ``lax.scan`` over layers. Cache slots carry their
absolute position (``pos``, -1 = empty) which uniformly expresses both
full caches and sliding-window ring buffers.
"""
from __future__ import annotations


import jax
import jax.numpy as jnp

from repro.common.sharding import shard
from repro.models import rope as rope_lib
from repro.models.common import causal_mask_bias, dense_init, softmax_attention


# ----------------------------------------------------------------------
# parameter init
# ----------------------------------------------------------------------
def init_self_attention(cfg, key):
    ks = jax.random.split(key, 4)
    E, H, K, D = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    dt = cfg.dtype("param")
    p = {
        "wq": dense_init(ks[0], (E, H * D), dt),
        "wk": dense_init(ks[1], (E, K * D), dt),
        "wv": dense_init(ks[2], (E, K * D), dt),
        "wo": dense_init(ks[3], (H * D, E), dt),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((H * D,), dt)
        p["bk"] = jnp.zeros((K * D,), dt)
        p["bv"] = jnp.zeros((K * D,), dt)
    return p


def init_cross_attention(cfg, key):
    ks = jax.random.split(key, 4)
    E, H, D = cfg.d_model, cfg.n_heads, cfg.head_dim
    dt = cfg.dtype("param")
    return {
        "wq": dense_init(ks[0], (E, H * D), dt),
        "wk": dense_init(ks[1], (E, H * D), dt),
        "wv": dense_init(ks[2], (E, H * D), dt),
        "wo": dense_init(ks[3], (H * D, E), dt),
    }


def init_mla(cfg, key):
    m = cfg.mla
    ks = jax.random.split(key, 5)
    E, H = cfg.d_model, cfg.n_heads
    dt = cfg.dtype("param")
    qdim = H * (m.qk_nope_dim + m.qk_rope_dim)
    return {
        "wq": dense_init(ks[0], (E, qdim), dt),
        "w_dkv": dense_init(ks[1], (E, m.kv_lora_rank + m.qk_rope_dim), dt),
        "ln_ckv": jnp.ones((m.kv_lora_rank,), dt),
        "w_uk": dense_init(ks[2], (m.kv_lora_rank, H * m.qk_nope_dim), dt),
        "w_uv": dense_init(ks[3], (m.kv_lora_rank, H * m.v_dim), dt),
        "wo": dense_init(ks[4], (H * m.v_dim, E), dt),
    }


# ----------------------------------------------------------------------
# cache construction / update
# ----------------------------------------------------------------------
def make_kv_cache(cfg, batch: int, max_len: int, n_layers: int,
                  dtype=None):
    """Stacked-over-layers KV cache. For sliding-window configs the
    cache is a ring buffer of ``window`` slots."""
    dt = dtype or cfg.dtype("compute")
    slots = min(max_len, cfg.sliding_window) if cfg.sliding_window else max_len
    K, D = cfg.n_kv_heads, cfg.head_dim
    return {
        "k": jnp.zeros((n_layers, batch, slots, K, D), dt),
        "v": jnp.zeros((n_layers, batch, slots, K, D), dt),
        "pos": jnp.full((n_layers, batch, slots), -1, jnp.int32),
    }


def make_mla_cache(cfg, batch: int, max_len: int, n_layers: int,
                   dtype=None):
    dt = dtype or cfg.dtype("compute")
    m = cfg.mla
    slots = min(max_len, cfg.sliding_window) if cfg.sliding_window else max_len
    return {
        "ckv": jnp.zeros((n_layers, batch, slots, m.kv_lora_rank), dt),
        "k_rope": jnp.zeros((n_layers, batch, slots, m.qk_rope_dim), dt),
        "pos": jnp.full((n_layers, batch, slots), -1, jnp.int32),
    }


def _write_slots(buf, new, slot_idx):
    """Scatter per-batch rows into cache slots.

    buf: (B, Smax, ...); new: (B, T, ...); slot_idx: (B, T) int32.
    """
    B = buf.shape[0]
    bidx = jnp.arange(B)[:, None] * jnp.ones_like(slot_idx)
    return buf.at[bidx, slot_idx].set(new.astype(buf.dtype))


def _slots_for(cfg, positions):
    """Map absolute positions → cache slots (ring for sliding window)."""
    if cfg.sliding_window:
        return positions % cfg.sliding_window
    return positions


# ----------------------------------------------------------------------
# apply
# ----------------------------------------------------------------------
def _maybe_pallas(cfg, q, k, v, positions, window):
    """Use the Pallas flash kernel for full-sequence (no-cache) passes."""
    if cfg.attention_impl == "xla":
        return None
    from repro.kernels.flash_attention import ops as fa_ops
    interpret = cfg.attention_impl == "pallas_interpret"
    return fa_ops.flash_attention(
        q, k, v, causal=True, window=window,
        scale=1.0 / (q.shape[-1] ** 0.5), interpret=interpret)


def self_attention(cfg, p, x, positions, cache=None, layer_cache=None):
    """GQA self-attention.

    x: (B, S, E); positions: (B, S) or (B, 3, S) for M-RoPE.
    layer_cache: this layer's slice of the KV cache (decode/prefill) or
    None (training). Returns (out, new_layer_cache).
    """
    B, S, E = x.shape
    H, K, D = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    cdt = cfg.dtype("compute")
    xq = x @ p["wq"].astype(cdt)
    xk = x @ p["wk"].astype(cdt)
    xv = x @ p["wv"].astype(cdt)
    if cfg.qkv_bias:
        xq = xq + p["bq"].astype(cdt)
        xk = xk + p["bk"].astype(cdt)
        xv = xv + p["bv"].astype(cdt)
    q = xq.reshape(B, S, H, D)
    k = xk.reshape(B, S, K, D)
    v = xv.reshape(B, S, K, D)
    q = shard(q, "batch", None, "heads", None)
    k = shard(k, "batch", None, "kv_heads", None)
    v = shard(v, "batch", None, "kv_heads", None)
    q = rope_lib.apply_rope(cfg, q, positions)
    k = rope_lib.apply_rope(cfg, k, positions)
    flat_pos = positions[:, -1, :] if positions.ndim == 3 else positions

    scale = 1.0 / (D ** 0.5)
    new_cache = layer_cache
    if layer_cache is None:
        out = _maybe_pallas(cfg, q, k, v, flat_pos, cfg.sliding_window)
        if out is None:
            bias = causal_mask_bias(flat_pos, flat_pos, cfg.sliding_window)
            out = softmax_attention(q, k, v, bias, scale,
                                    cfg.attention_scores_dtype)
    else:
        slots = _slots_for(cfg, flat_pos)
        kc = _write_slots(layer_cache["k"], k, slots)
        vc = _write_slots(layer_cache["v"], v, slots)
        pc = _write_slots(layer_cache["pos"], flat_pos, slots)
        # flash-decoding layout: cache SLOTS shard over "model"; the
        # softmax/contraction over the sharded slot dim reduces to
        # tiny (B,H,1)-scalar combines that GSPMD inserts (§Perf it.5)
        kc = shard(kc, "batch", "kv_slots", None, None)
        vc = shard(vc, "batch", "kv_slots", None, None)
        pc = shard(pc, "batch", "kv_slots")
        new_cache = {"k": kc, "v": vc, "pos": pc}
        k_valid = pc >= 0
        bias = causal_mask_bias(flat_pos, pc, cfg.sliding_window, k_valid)
        out = softmax_attention(q, kc, vc, bias, scale,
                                cfg.attention_scores_dtype)
    out = out.reshape(B, S, H * D)
    return out @ p["wo"].astype(cdt), new_cache


def cross_attention(cfg, p, x, cond, layer_cache=None):
    """MHA cross-attention to a (B, Lc, E) conditioning sequence.
    K/V are position-independent; at decode time they are precomputed
    once (layer_cache = {"ck", "cv"}) and reused every step."""
    B, S, E = x.shape
    H, D = cfg.n_heads, cfg.head_dim
    cdt = cfg.dtype("compute")
    q = (x @ p["wq"].astype(cdt)).reshape(B, S, H, D)
    if layer_cache is not None and "ck" in layer_cache:
        k, v = layer_cache["ck"], layer_cache["cv"]
    else:
        Lc = cond.shape[1]
        k = (cond @ p["wk"].astype(cdt)).reshape(B, Lc, H, D)
        v = (cond @ p["wv"].astype(cdt)).reshape(B, Lc, H, D)
    bias = jnp.zeros((B, 1, S, k.shape[1]), jnp.float32)
    out = softmax_attention(q, k, v, bias, 1.0 / (D ** 0.5))
    out = out.reshape(B, S, H * D) @ p["wo"].astype(cdt)
    return out, {"ck": k, "cv": v}


def mla_attention(cfg, p, x, positions, layer_cache=None):
    """DeepSeek-V2 Multi-head Latent Attention.

    Caches only the rank-r latent ``ckv`` plus the shared rotary key
    (kv_lora_rank + qk_rope_dim floats per token) — the paper's KV-cache
    compression. Per-head K/V are re-expanded from the latent.
    """
    m = cfg.mla
    B, S, E = x.shape
    H = cfg.n_heads
    cdt = cfg.dtype("compute")
    from repro.models.common import rms_norm

    q = (x @ p["wq"].astype(cdt)).reshape(B, S, H, m.qk_nope_dim + m.qk_rope_dim)
    q_nope, q_rope = q[..., :m.qk_nope_dim], q[..., m.qk_nope_dim:]
    dkv = x @ p["w_dkv"].astype(cdt)
    ckv = rms_norm(dkv[..., :m.kv_lora_rank], p["ln_ckv"], cfg.norm_eps)
    k_rope = dkv[..., m.kv_lora_rank:][:, :, None, :]       # 1 shared head
    if m.rope_interleave:
        q_rope = rope_lib.deinterleave(q_rope)
        k_rope = rope_lib.deinterleave(k_rope)
    q_rope = rope_lib.rope(q_rope, positions, cfg.rope_theta)
    k_rope = rope_lib.rope(k_rope, positions, cfg.rope_theta)[:, :, 0, :]

    new_cache = layer_cache
    if layer_cache is not None:
        slots = _slots_for(cfg, positions)
        ckv_c = _write_slots(layer_cache["ckv"], ckv, slots)
        kr_c = _write_slots(layer_cache["k_rope"], k_rope, slots)
        pc = _write_slots(layer_cache["pos"], positions, slots)
        ckv_c = shard(ckv_c, "batch", "kv_slots", None)
        kr_c = shard(kr_c, "batch", "kv_slots", None)
        pc = shard(pc, "batch", "kv_slots")
        new_cache = {"ckv": ckv_c, "k_rope": kr_c, "pos": pc}
        ckv_all, k_rope_all, k_pos = ckv_c, kr_c, pc
        k_valid = pc >= 0
    else:
        ckv_all, k_rope_all, k_pos = ckv, k_rope, positions
        k_valid = None

    T = ckv_all.shape[1]
    bias = causal_mask_bias(positions, k_pos, cfg.sliding_window, k_valid)
    scale = 1.0 / ((m.qk_nope_dim + m.qk_rope_dim) ** 0.5)

    if cfg.mla_absorb and layer_cache is not None and S < T:
        # DeepSeek-V2 weight absorption (decode): score the query
        # against the rank-r latent DIRECTLY instead of re-expanding
        # per-head K/V from the whole cache every step —
        #   scores = (q_nope W_ukᵀ) · ckv  +  q_rope · k_rope
        #   out    = (probs · ckv) W_uv
        # Cost per layer drops from O(T·r·H·(dn+dv)) expansion matmuls
        # to O(T·H·r) score/context terms — a (dn=128)× cut at 32k+
        # context (EXPERIMENTS.md §Perf it.6). Exact same math
        # (associativity); the non-absorbed path stays for prefill
        # (S = T) where expansion amortises over the whole sequence.
        f32 = jnp.float32
        wuk = p["w_uk"].astype(cdt).reshape(m.kv_lora_rank, H,
                                            m.qk_nope_dim)
        q_lat = jnp.einsum("bqhd,rhd->bqhr", q_nope, wuk)  # (B,S,H,r)
        s_nope = jnp.einsum("bqhr,btr->bhqt", q_lat.astype(f32),
                            ckv_all.astype(f32))
        s_rope = jnp.einsum("bqhd,btd->bhqt", q_rope.astype(f32),
                            k_rope_all.astype(f32))
        scores = (s_nope + s_rope) * scale + bias
        probs = jax.nn.softmax(scores, axis=-1)
        ctx = jnp.einsum("bhqt,btr->bqhr", probs,
                         ckv_all.astype(f32))              # (B,S,H,r)
        wuv = p["w_uv"].astype(cdt).reshape(m.kv_lora_rank, H, m.v_dim)
        out = jnp.einsum("bqhr,rhv->bqhv", ctx.astype(cdt), wuv)
    else:
        k_nope = (ckv_all @ p["w_uk"].astype(cdt)
                  ).reshape(B, T, H, m.qk_nope_dim)
        vv = (ckv_all @ p["w_uv"].astype(cdt)).reshape(B, T, H, m.v_dim)
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_rope_all[:, :, None, :],
                                      (B, T, H, m.qk_rope_dim))],
            axis=-1)
        qfull = jnp.concatenate([q_nope, q_rope], axis=-1)
        out = softmax_attention(qfull, k, vv, bias, scale,
                                cfg.attention_scores_dtype)
    out = out.reshape(B, S, H * m.v_dim) @ p["wo"].astype(cdt)
    return out, new_cache
