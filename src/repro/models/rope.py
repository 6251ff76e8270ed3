"""Rotary position embeddings: standard RoPE and Qwen2-VL M-RoPE.

M-RoPE (arXiv:2409.12191): the head_dim/2 rotary frequencies are split
into three contiguous sections (t, h, w); each section takes its angle
from the corresponding component of a (3,)-vector position. For pure
text all three components are equal and M-RoPE degenerates to RoPE.
"""
from __future__ import annotations

from typing import Tuple

import jax.numpy as jnp


def _angles(positions, dim: int, theta: float):
    """positions (..., S) → (..., S, dim/2) angles."""
    half = dim // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    return positions[..., None].astype(jnp.float32) * freq


def _apply_rotary(x, cos, sin):
    """x (..., D) with rotate-half pairing (x1, x2 = split halves)."""
    d = x.shape[-1] // 2
    x1, x2 = x[..., :d], x[..., d:]
    return jnp.concatenate([x1 * cos - x2 * sin,
                            x2 * cos + x1 * sin], axis=-1).astype(x.dtype)


def deinterleave(x):
    """Channel pairs (2i, 2i+1) of the last axis moved to (i, i + D/2):
    rotate-half RoPE on the result is RoPE on interleaved pairs
    (DeepSeek-V3's ``rope_interleave``), up to the same reordering of
    every query and key, which leaves their dot products unchanged."""
    *lead, d = x.shape
    return jnp.swapaxes(x.reshape(*lead, d // 2, 2), -1, -2).reshape(x.shape)


def rope(x, positions, theta: float):
    """Standard RoPE. x: (B, S, H, D); positions: (B, S)."""
    ang = _angles(positions, x.shape[-1], theta)      # (B,S,D/2)
    cos = jnp.cos(ang)[:, :, None, :]
    sin = jnp.sin(ang)[:, :, None, :]
    return _apply_rotary(x, cos, sin)


def mrope(x, positions3, theta: float, sections: Tuple[int, int, int]):
    """M-RoPE. x: (B, S, H, D); positions3: (B, 3, S); sections sum to D/2."""
    assert sum(sections) == x.shape[-1] // 2, (sections, x.shape)
    ang_parts = []
    off = 0
    for i, sec in enumerate(sections):
        half = x.shape[-1] // 2
        freq = theta ** (-(jnp.arange(off, off + sec, dtype=jnp.float32))
                         / half)
        ang_parts.append(positions3[:, i, :, None].astype(jnp.float32)
                         * freq)
        off += sec
    ang = jnp.concatenate(ang_parts, axis=-1)         # (B,S,D/2)
    cos = jnp.cos(ang)[:, :, None, :]
    sin = jnp.sin(ang)[:, :, None, :]
    return _apply_rotary(x, cos, sin)


def apply_rope(cfg, x, positions):
    """Dispatch on cfg.rope_mode; positions is (B,S) or (B,3,S)."""
    if cfg.rope_mode == "none":
        return x
    if cfg.rope_mode == "mrope":
        return mrope(x, positions, cfg.rope_theta, cfg.mrope_sections)
    return rope(x, positions, cfg.rope_theta)
