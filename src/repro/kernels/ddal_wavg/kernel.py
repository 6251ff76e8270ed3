"""Pallas-TPU kernel for DDAL's eq. 4 contraction: ḡ = Σ_j w_j·G[j].

The op is a streaming m-way weighted reduction over the full gradient
vector — at LLM scale it is HBM-bandwidth-bound (arithmetic intensity
≈ 0.5 FLOP/byte). XLA typically emits m separate scaled adds (reading
the fp32 accumulator m times); this kernel streams each (m, TILE) slab
through VMEM once and keeps one fp32 accumulator tile, so HBM traffic
is exactly one pass over G plus one write of ḡ — the roofline floor.

Tiling: the flat parameter vector is viewed as (tiles, ROWS, 128)
— 128 lanes, ROWS sublane-multiples — and the grid walks tiles. The
m-loop is unrolled inside the block (the paper's store holds ≤ tens of
pieces). Weights ride along as a tiny VMEM block replicated per tile.

Beyond the plain contraction (``wavg_flat``, weights precomputed on
the host side of the launch), the *fused* exchange kernels fold the
whole eq. 4 share step into the block loop:

* ``fused_wavg_flat`` — reads the raw (T, R, valid) metadata as tiny
  (m, 1) VMEM blocks, regenerates the eq. 4 weights *inside* the
  kernel (the way ``grad_sketch`` regenerates its signs in VMEM —
  nothing weight-shaped ever reaches HBM) and emits (ḡ, Σw) directly:
  one HBM pass over G, one write of ḡ, one (1, 1) write of Σw.
* ``fused_wavg_q_flat`` — the same pass over **int8 block-quantized**
  knowledge planes: per-block fp32 scales ride along as a small
  second operand and the dequantisation happens inside the block
  loop, so HBM reads ~N bytes of int8 instead of 4N of fp32 — the
  ~4× delay-line/cross-pod traffic saving at a pinned accuracy bound.

Quantization blocks are ``q_block`` consecutive elements of the flat
vector with ``q_block % 128 == 0`` and ``tile % q_block == 0``, i.e. a
block is a whole group of sublane rows — the in-kernel dequant is a
broadcast multiply over row groups, no lane-crossing reshuffle.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LANES = 128
DEFAULT_ROWS = 64                  # tile = 64·128 = 8192 elements
EQ4_EPS = 1e-12                    # eq4_weights' normalisation clamp


def _wavg_kernel(w_ref, g_ref, o_ref):
    """w_ref: (m, 1); g_ref: (m, 1, ROWS, LANES); o_ref: (1, ROWS, LANES)."""
    m = g_ref.shape[0]
    acc = jnp.zeros(o_ref.shape, jnp.float32)
    for j in range(m):                       # m is static & small
        acc = acc + w_ref[j, 0] * g_ref[j].astype(jnp.float32)
    o_ref[...] = acc


@functools.partial(jax.jit, static_argnames=("rows", "interpret"))
def wavg_flat(G: jnp.ndarray, w: jnp.ndarray,
              rows: int = DEFAULT_ROWS,
              interpret: bool = False) -> jnp.ndarray:
    """G: (m, N) float, w: (m,) → (N,) fp32 = Σ_j w[j]·G[j]."""
    m, n = G.shape
    tile = rows * LANES
    n_pad = max(tile, ((n + tile - 1) // tile) * tile)
    if n_pad != n:
        G = jnp.pad(G, ((0, 0), (0, n_pad - n)))
    tiles = n_pad // tile
    G4 = G.reshape(m, tiles, rows, LANES)
    w2 = w.astype(jnp.float32).reshape(m, 1)

    out = pl.pallas_call(
        _wavg_kernel,
        grid=(tiles,),
        in_specs=[
            pl.BlockSpec((m, 1), lambda i: (0, 0)),
            pl.BlockSpec((m, 1, rows, LANES), lambda i: (0, i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, rows, LANES), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((tiles, rows, LANES),
                                       jnp.float32),
        interpret=interpret,
    )(w2, G4)
    return out.reshape(n_pad)[:n]


# ---------------------------------------------------------------------
# fused eq. 4 share step: weights computed in VMEM, (ḡ, Σw) emitted
# ---------------------------------------------------------------------
def _eq4_weights_block(T, R, V, eps):
    """eq. 4 weights on (m, 1) VMEM blocks — the *same float ops in
    the same order* as ``repro.core.weighting.eq4_weights`` (mask,
    sum, clamp, normalise, average), so the in-kernel weights match
    the multi-op path's bit for bit."""
    Tm = T * V
    Rm = R * V
    t_hat = Tm / jnp.maximum(jnp.sum(Tm), eps)
    r_hat = Rm / jnp.maximum(jnp.sum(Rm), eps)
    return 0.5 * (t_hat + r_hat)                         # (m, 1)


def _fused_wavg_kernel(T_ref, R_ref, V_ref, g_ref, o_ref, ws_ref, *,
                       eps):
    """T/R/V_ref: (m, 1); g_ref: (m, 1, ROWS, LANES);
    o_ref: (1, ROWS, LANES); ws_ref: (1, 1)."""
    m = g_ref.shape[0]
    w = _eq4_weights_block(T_ref[...], R_ref[...], V_ref[...], eps)

    @pl.when(pl.program_id(0) == 0)
    def _():                       # Σw once — revisited blocks alias
        ws_ref[...] = jnp.sum(w).reshape(1, 1)

    acc = jnp.zeros(o_ref.shape, jnp.float32)
    for j in range(m):                       # m is static & small
        acc = acc + w[j, 0] * g_ref[j].astype(jnp.float32)
    o_ref[...] = acc


def _fused_wavg_q_kernel(T_ref, R_ref, V_ref, q_ref, s_ref, o_ref,
                         ws_ref, *, eps, q_rows):
    """Quantized planes: q_ref (m, 1, ROWS, LANES) int8, s_ref
    (1, m, ROWS // q_rows) fp32 per-block scales — dequantised inside
    the block loop (one int8 HBM pass, never an fp32 copy of G). Each
    scale block is ``q_rows`` whole sublane rows, so the dequant is a
    static row slice times a broadcast scalar."""
    m, _, rows, lanes = q_ref.shape
    nb = rows // q_rows
    w = _eq4_weights_block(T_ref[...], R_ref[...], V_ref[...], eps)
    sc = s_ref[0]                                        # (m, nb)

    @pl.when(pl.program_id(0) == 0)
    def _():
        ws_ref[...] = jnp.sum(w).reshape(1, 1)

    acc = [jnp.zeros((q_rows, lanes), jnp.float32) for _ in range(nb)]
    for j in range(m):
        qf = q_ref[j, 0].astype(jnp.float32)             # (rows, lanes)
        for b in range(nb):
            blk = qf[b * q_rows:(b + 1) * q_rows]
            acc[b] = acc[b] + w[j, 0] * (blk * sc[j, b])
    for b in range(nb):
        o_ref[0, b * q_rows:(b + 1) * q_rows, :] = acc[b]


def _fused_call(kernel, extra_in, extra_specs, T, R, valid, tiles,
                rows, m, interpret):
    """Shared pallas_call plumbing for both fused variants."""
    meta = [jnp.asarray(x, jnp.float32).reshape(m, 1)
            for x in (T, R, valid)]
    meta_specs = [pl.BlockSpec((m, 1), lambda i: (0, 0))] * 3
    out, wsum = pl.pallas_call(
        kernel,
        grid=(tiles,),
        in_specs=meta_specs + extra_specs,
        out_specs=[
            pl.BlockSpec((1, rows, LANES), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((tiles, rows, LANES), jnp.float32),
            jax.ShapeDtypeStruct((1, 1), jnp.float32),
        ],
        interpret=interpret,
    )(*meta, *extra_in)
    return out, wsum[0, 0]


@functools.partial(jax.jit, static_argnames=("rows", "interpret",
                                             "eps"))
def fused_wavg_flat(G, T, R, valid, rows: int = DEFAULT_ROWS,
                    interpret: bool = False, eps: float = EQ4_EPS):
    """G: (m, N) float; T, R: (m,); valid: (m,) bool →
    (ḡ: (N,) fp32, Σw: () fp32) — eq. 4 in one HBM pass."""
    m, n = G.shape
    tile = rows * LANES
    n_pad = max(tile, ((n + tile - 1) // tile) * tile)
    if n_pad != n:
        G = jnp.pad(G, ((0, 0), (0, n_pad - n)))
    tiles = n_pad // tile
    G4 = G.reshape(m, tiles, rows, LANES)
    out, wsum = _fused_call(
        functools.partial(_fused_wavg_kernel, eps=eps),
        [G4],
        [pl.BlockSpec((m, 1, rows, LANES), lambda i: (0, i, 0, 0))],
        T, R, valid, tiles, rows, m, interpret)
    return out.reshape(n_pad)[:n], wsum


@functools.partial(jax.jit, static_argnames=("q_block", "rows",
                                             "interpret", "eps"))
def fused_wavg_q_flat(Q, scale, T, R, valid, q_block: int,
                      rows: int = DEFAULT_ROWS,
                      interpret: bool = False, eps: float = EQ4_EPS):
    """Q: (m, N) int8 block-quantized planes; scale: (m, ⌈N/q_block⌉)
    fp32 per-block scales → (ḡ, Σw) with dequant fused into the block
    loop. ``q_block`` must be a multiple of ``LANES`` dividing the
    tile (``rows * LANES``)."""
    if q_block % LANES or (rows * LANES) % q_block:
        raise ValueError(
            f"q_block must be a multiple of {LANES} dividing the "
            f"{rows * LANES}-element tile, got {q_block}")
    m, n = Q.shape
    tile = rows * LANES
    n_pad = max(tile, ((n + tile - 1) // tile) * tile)
    nb_pad = n_pad // q_block
    if n_pad != n:
        Q = jnp.pad(Q, ((0, 0), (0, n_pad - n)))
    if scale.shape[1] != nb_pad:
        scale = jnp.pad(scale, ((0, 0), (0, nb_pad - scale.shape[1])))
    tiles = n_pad // tile
    q_rows = q_block // LANES
    nb_tile = rows // q_rows
    Q4 = Q.reshape(m, tiles, rows, LANES)
    # tile-major scales: each grid step's (m, nb_tile) block spans the
    # array's last two dims whole, as the TPU block tiling requires
    S3 = scale.reshape(m, tiles, nb_tile).transpose(1, 0, 2)
    out, wsum = _fused_call(
        functools.partial(_fused_wavg_q_kernel, eps=eps,
                          q_rows=q_rows),
        [Q4, S3],
        [pl.BlockSpec((m, 1, rows, LANES), lambda i: (0, i, 0, 0)),
         pl.BlockSpec((1, m, nb_tile), lambda i: (i, 0, 0))],
        T, R, valid, tiles, rows, m, interpret)
    return out.reshape(n_pad)[:n], wsum
