"""Mamba2 SSD (state-space duality) sequence mixing — pure-jnp version.

Chunked algorithm from arXiv:2405.21060 §6: within a chunk the SSM is
computed in its "quadratic attention" dual form (MXU-friendly block
matmuls); across chunks a first-order recurrence on the (H, P, N)
states is evaluated with ``lax.associative_scan``. All decay factors
are exp of non-positive numbers (A < 0, dt > 0) so the math is
overflow-free by construction.

The Pallas kernel in ``repro.kernels.ssd_scan`` implements the
intra-chunk dual form; this module is also its ``ref`` oracle.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp


def _segsum_mask(dA_cs):
    """L[i, j] = exp(cs[i] - cs[j]) for j <= i else 0.

    dA_cs: (..., L) inclusive cumsum of dt·A over the chunk.
    Returns (..., L, L).
    """
    L = dA_cs.shape[-1]
    diff = dA_cs[..., :, None] - dA_cs[..., None, :]
    causal = jnp.tril(jnp.ones((L, L), bool))
    # mask before the exp: above the diagonal diff is positive and, over
    # a 256-step chunk, overflows to inf, whose gradient through a
    # where-after-exp is 0 · inf = NaN
    return jnp.exp(jnp.where(causal, diff, -jnp.inf))


def ssd_chunked(x, dt, A, B, C, chunk: int,
                initial_state: Optional[jnp.ndarray] = None,
                impl: str = "xla") -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Full-sequence SSD.

    x:  (b, s, h, p)   per-head inputs
    dt: (b, s, h)      positive step sizes (already softplus'd)
    A:  (h,)           negative decay rates
    B:  (b, s, g, n)   input projections (g groups broadcast onto heads)
    C:  (b, s, g, n)   output projections
    Returns (y (b,s,h,p), final_state (b,h,p,n)).
    """
    b, s, h, p = x.shape
    g, n = B.shape[2], B.shape[3]
    if s % chunk:
        # pad to a chunk multiple with dt = 0 steps: exp(0·A) = 1 and
        # the state update dt·x·B = 0, so padding is an exact no-op on
        # the recurrence (outputs at padded positions are discarded).
        pad = chunk - s % chunk
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        B = jnp.pad(B, ((0, 0), (0, pad), (0, 0), (0, 0)))
        C = jnp.pad(C, ((0, 0), (0, pad), (0, 0), (0, 0)))
        y, fs = ssd_chunked(x, dt, A, B, C, chunk,
                            initial_state=initial_state, impl=impl)
        return y[:, :s], fs
    nc = s // chunk
    rep = h // g
    Bh = jnp.repeat(B, rep, axis=2)                     # (b,s,h,n)
    Ch = jnp.repeat(C, rep, axis=2)

    f32 = jnp.float32
    xc = x.reshape(b, nc, chunk, h, p)
    dtc = dt.reshape(b, nc, chunk, h).astype(f32)
    Bc = Bh.reshape(b, nc, chunk, h, n)
    Cc = Ch.reshape(b, nc, chunk, h, n)

    dA = dtc * A.astype(f32)                            # (b,nc,l,h) ≤ 0
    cs = jnp.cumsum(dA, axis=2)                         # inclusive

    # ---- intra-chunk (dual quadratic form) ---------------------------
    if impl == "pallas_interpret":
        from repro.kernels.ssd_scan import ops as ssd_ops
        y_diag = ssd_ops.ssd_intra_chunk(xc, dtc, cs, Bc, Cc,
                                         interpret=True)
    else:
        Lmask = _segsum_mask(jnp.moveaxis(cs, 3, 2))    # (b,nc,h,l,l)
        scores = jnp.einsum("bcihn,bcjhn->bchij",
                            Cc.astype(f32), Bc.astype(f32))
        scores = scores * Lmask * jnp.moveaxis(dtc, 3, 2)[..., None, :]
        y_diag = jnp.einsum("bchij,bcjhp->bcihp", scores,
                            xc.astype(f32))

    # ---- chunk states -------------------------------------------------
    decay_to_end = jnp.exp(cs[:, :, -1:, :] - cs)       # (b,nc,l,h)
    states = jnp.einsum("bclhn,bclh,bclhp->bchpn",
                        Bc.astype(f32), decay_to_end * dtc,
                        xc.astype(f32))                 # (b,nc,h,p,n)
    chunk_decay = jnp.exp(cs[:, :, -1, :])              # (b,nc,h)

    # ---- inter-chunk associative scan ---------------------------------
    if initial_state is not None:
        s0 = initial_state.astype(f32)[:, None]         # (b,1,h,p,n)
        d0 = jnp.ones((b, 1, h), f32)
        states = jnp.concatenate([s0, states], axis=1)
        chunk_decay = jnp.concatenate([d0, chunk_decay], axis=1)

    def combine(a, bb):
        d1, s1 = a
        d2, s2 = bb
        return d1 * d2, s1 * d2[..., None, None] + s2

    decays, states_cum = jax.lax.associative_scan(
        combine, (chunk_decay, states), axis=1)
    final_state = states_cum[:, -1]                     # (b,h,p,n)
    # state *entering* each (original) chunk:
    if initial_state is not None:
        states_in = states_cum[:, :nc]
    else:
        zeros = jnp.zeros_like(states_cum[:, :1])
        states_in = jnp.concatenate([zeros, states_cum[:, :-1]], axis=1)

    # ---- inter-chunk output contribution ------------------------------
    y_off = jnp.einsum("bclhn,bchpn,bclh->bclhp",
                       Cc.astype(f32), states_in, jnp.exp(cs))
    y = (y_diag + y_off).reshape(b, s, h, p)
    return y.astype(x.dtype), final_state


def ssd_decode_step(state, x, dt, A, B, C):
    """Single-token recurrent update.

    state: (b, h, p, n); x: (b, h, p); dt: (b, h); B, C: (b, g, n).
    Returns (y (b,h,p), new_state).
    """
    f32 = jnp.float32
    g = B.shape[1]
    h = x.shape[1]
    rep = h // g
    Bh = jnp.repeat(B, rep, axis=1).astype(f32)         # (b,h,n)
    Ch = jnp.repeat(C, rep, axis=1).astype(f32)
    dtf = dt.astype(f32)
    dA = jnp.exp(dtf * A.astype(f32))                   # (b,h)
    upd = (dtf[..., None] * x.astype(f32))[..., None] * Bh[:, :, None, :]
    new_state = state * dA[..., None, None] + upd       # (b,h,p,n)
    y = jnp.einsum("bhpn,bhn->bhp", new_state, Ch)
    return y.astype(x.dtype), new_state
