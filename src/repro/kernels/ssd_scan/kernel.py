"""Pallas-TPU kernel for the Mamba2 SSD intra-chunk dual form.

TPU adaptation (DESIGN.md §3): the SSD "quadratic dual" inside a chunk
is exactly two MXU-shaped matmuls — (l, n)·(n, l) scores and
(l, l)·(l, p) outputs — sandwiching an elementwise decay mask
L[i,j] = exp(cs_i − cs_j)·dt_j on j ≤ i. The original CUDA kernel
(Triton in the paper's repo) tiles over SMs; here one grid step owns a
whole (chunk × head) block in VMEM — chunk=256, n=128, p=64 gives
l·n + l·l + l·p ≈ 208 KiB fp32, comfortably VMEM-resident, and both
matmuls are 128-aligned for the MXU.

Grid: (b·nc, h). The inter-chunk recurrence stays OUTSIDE the kernel
as a `lax.associative_scan` (log-depth, bandwidth-trivial) — splitting
at the chunk boundary is the TPU-native factorisation of SSD.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _ssd_kernel(x_ref, dt_ref, cs_ref, b_ref, c_ref, o_ref):
    """Blocks: x (1,1,l,p); dt, cs (1,1,1,l) rows; B, C (1,1,l,n);
    o (1,1,l,p)."""
    x = x_ref[0, 0].astype(jnp.float32)          # (l, p)
    dt = dt_ref[0, 0].astype(jnp.float32)        # (1, l)
    cs = cs_ref[0, 0].astype(jnp.float32)        # (1, l)
    Bm = b_ref[0, 0].astype(jnp.float32)         # (l, n)
    Cm = c_ref[0, 0].astype(jnp.float32)         # (l, n)
    l = x.shape[0]

    scores = jax.lax.dot_general(
        Cm, Bm, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)      # (l, l) = C·Bᵀ
    ii = jax.lax.broadcasted_iota(jnp.int32, (l, l), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (l, l), 1)
    cs_j = jnp.broadcast_to(cs, (l, l))          # cs_j along lanes
    decay = jnp.exp(cs_j.T - cs_j)               # exp(cs_i − cs_j)
    Lmask = jnp.where(jj <= ii, decay, 0.0)
    scores = scores * Lmask * dt
    o_ref[0, 0] = jax.lax.dot_general(
        scores, x, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("interpret",))
def ssd_intra_chunk_bchl(x, dt, cs, B, C, *,
                         interpret: bool = False) -> jnp.ndarray:
    """x: (bn, h, l, p); dt, cs: (bn, h, l); B, C: (bn, h, l, n).
    Returns (bn, h, l, p) fp32. dt and cs travel as (bn, h, 1, l)
    rows so that each block's last two dims are (1, l): full, and
    lane-dense."""
    bn, h, l, p = x.shape
    n = B.shape[-1]
    dt = dt.reshape(bn, h, 1, l)
    cs = cs.reshape(bn, h, 1, l)
    out = pl.pallas_call(
        _ssd_kernel,
        grid=(bn, h),
        in_specs=[
            pl.BlockSpec((1, 1, l, p), lambda i, j: (i, j, 0, 0)),
            pl.BlockSpec((1, 1, 1, l), lambda i, j: (i, j, 0, 0)),
            pl.BlockSpec((1, 1, 1, l), lambda i, j: (i, j, 0, 0)),
            pl.BlockSpec((1, 1, l, n), lambda i, j: (i, j, 0, 0)),
            pl.BlockSpec((1, 1, l, n), lambda i, j: (i, j, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, l, p), lambda i, j: (i, j, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((bn, h, l, p), jnp.float32),
        interpret=interpret,
    )(x, dt, cs, B, C)
    return out
