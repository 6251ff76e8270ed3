"""Pallas-TPU kernel for the streaming gradient-sketch projection.

The op projects a stacked per-agent gradient matrix G: (n, P) through
a seeded random ±1 (Rademacher / sign-JL) matrix S: (P, d) into a
small sketch G·S: (n, d). The kernel walks (n, ROWS·128) slabs of G
through VMEM, *regenerates* the matching (tile, d) sign block from a
counter-based hash — the sign matrix is never stored anywhere, in HBM
or elsewhere — and accumulates the (n, d) sketch tile in place across
the sequential grid. HBM traffic is one pass over G plus one (n, d)
write.

That traffic is not what sets the pace. With few agents (n = 2) the
kernel reads G at a few percent of a TPU v5e's HBM bandwidth at most;
most of its time goes to the vector unit, which hashes P × d signs, d
per position read. So the kernel hashes as little as keeps
the stream bit for bit: the hash's input ``seed + (start + p)·P1 +
j·P2`` splits (uint32 addition wraps) into a (tile, d) base ``p·P1 +
j·P2``, built once per call in VMEM scratch (``_hash_base``), plus one
scalar per grid step (``_hash_offset``); only the mix that reaches bit
31 runs, and bit 31 becomes ±1.0 by two bit operations on 1.0's bit
pattern (``_signs_from_hash``). That is 9 vector operations per sign,
2 of them integer multiplies, against about 20 and 4 for
``sign_block``.

Signs are a pure function of ``(seed, global position, sketch dim)``
(``sign_block``, which the kernel's ``hoisted_sign_block`` equals bit
for bit), so the sketch is independent of tiling, identical between
this kernel, the tiled XLA fallback and the jnp oracle (``ref.py``),
and — because the projection is linear and the signs depend only on
position — sketches of gradient *sums* equal sums of sketches, which
is what lets the streaming trainer carry an (n, d) window sketch
instead of re-deriving it from the accumulators.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
DEFAULT_ROWS = 8               # tile = 8·128 = 1024 positions per step

# xxhash/murmur-style 32-bit mixing constants (wrap-around uint32
# arithmetic; every path, the kernel's shortened generator included,
# derives the same sign stream from them). Single source of
# truth — ``repro.core.relevance.fold_seed`` mixes round indices with
# the same constants.
MIX_CONSTANTS = (0x9E3779B1, 0x85EBCA77, 0xC2B2AE3D)
_P1, _P2, _P3 = MIX_CONSTANTS


def _sign_bits(seed, start, count: int, dim: int) -> jnp.ndarray:
    """The raw sign bits (uint32 ∈ {0, 1}) behind ``sign_block``:
    hash ``(seed, global position, sketch dim)`` and keep the top bit.
    Shared by every width the sign stream is materialised at, so all
    of them agree bit for bit."""
    pos = jax.lax.broadcasted_iota(jnp.int32, (count, dim), 0)
    dimi = jax.lax.broadcasted_iota(jnp.int32, (count, dim), 1)
    s = jnp.asarray(seed).astype(jnp.uint32)
    x = (s
         + (jnp.asarray(start).astype(jnp.uint32)
            + pos.astype(jnp.uint32)) * jnp.uint32(_P1)
         + dimi.astype(jnp.uint32) * jnp.uint32(_P2))
    x = (x ^ (x >> 15)) * jnp.uint32(_P2)
    x = (x ^ (x >> 13)) * jnp.uint32(_P3)
    x = x ^ (x >> 16)
    return x >> 31


def sign_block(seed, start, count: int, dim: int) -> jnp.ndarray:
    """Deterministic ±1 fp32 block ``S[p - start, j]`` for global
    positions p ∈ [start, start + count) and sketch dims j < dim.

    Pure function of ``(seed, p, j)`` — independent of how callers
    tile the position axis — built from 2D iotas (TPU-legal) and a
    xorshift-multiply integer hash. ``seed``/``start`` may be traced
    scalars; ``count``/``dim`` are static.
    """
    # through int32: the TPU compiler has no uint32 -> float32 cast
    bits = _sign_bits(seed, start, count, dim).astype(jnp.int32)
    return 1.0 - 2.0 * bits.astype(jnp.float32)


def sign_block_i8(seed, start, count: int, dim: int) -> jnp.ndarray:
    """``sign_block`` bit-packed to int8: the same ±1 stream at one
    byte per sign instead of four (ROADMAP "sign-generation
    bandwidth"). The off-TPU tiled path materialises one (block, d)
    sign block per chunk — int8 cuts that block's memory traffic 4×,
    and the cast back to fp32 fuses into the projection dot (±1 is
    exact in both dtypes, so the sketch is bitwise unchanged; pinned
    against the jnp oracle in ``tests/test_exchange.py``). The Pallas
    kernel keeps fp32: it regenerates signs in VMEM where the MXU
    wants fp32 operands and no sign block ever reaches HBM."""
    bits = _sign_bits(seed, start, count, dim)
    return (jnp.int8(1) - jnp.int8(2) * bits.astype(jnp.int8))


def _hash_base(count: int, dim: int) -> jnp.ndarray:
    """The shape-only part of the hash's input: ``p·P1 + j·P2`` for
    block row p < count and sketch dim j < dim (uint32, wrapping).
    Independent of seed, start and leaf, so the kernel builds it once
    per call."""
    pos = jax.lax.broadcasted_iota(jnp.int32, (count, dim), 0)
    dimi = jax.lax.broadcasted_iota(jnp.int32, (count, dim), 1)
    return (pos.astype(jnp.uint32) * jnp.uint32(_P1)
            + dimi.astype(jnp.uint32) * jnp.uint32(_P2))


def _hash_offset(seed, start) -> jnp.ndarray:
    """The block's scalar part of the hash's input: ``seed + start·P1``
    (uint32, wrapping). Wrapping addition splits ``_sign_bits``' input
    as ``_hash_offset(seed, start) + _hash_base(...)`` exactly."""
    return (jnp.asarray(seed).astype(jnp.uint32)
            + jnp.asarray(start).astype(jnp.uint32) * jnp.uint32(_P1))


def _signs_from_hash(x: jnp.ndarray) -> jnp.ndarray:
    """±1.0 fp32 from the hash input x: only the mix that reaches bit
    31 (``_sign_bits``' final ``x ^ (x >> 16)`` never changes it), and
    bit 31 placed as the sign of 1.0's bit pattern, so a set bit gives
    −1.0 exactly as ``1 − 2·bit`` does."""
    x = (x ^ (x >> 15)) * jnp.uint32(_P2)
    x = (x ^ (x >> 13)) * jnp.uint32(_P3)
    bits = (x & jnp.uint32(0x80000000)) | jnp.uint32(0x3F800000)
    return jax.lax.bitcast_convert_type(bits, jnp.float32)


def hoisted_sign_block(seed, start, count: int, dim: int) -> jnp.ndarray:
    """``sign_block`` as the kernel computes it: the hash's shape-only
    base plus one scalar, then the shortened mix. Bit for bit
    ``sign_block(seed, start, count, dim)``."""
    return _signs_from_hash(_hash_base(count, dim)
                            + _hash_offset(seed, start))


def _sketch_kernel(seed_ref, g_ref, o_ref, base_ref, *, offset, tile,
                   dim, total):
    """seed_ref: (1, 1) in SMEM; g_ref: (n, TILE); o_ref: (n, d);
    base_ref: (TILE, d) uint32 VMEM scratch.

    The output block is revisited by every grid step (TPU grids run
    sequentially): step 0 zeroes it and fills ``base_ref`` with the
    hash's shape-only part, every step adds its scalar offset to that
    base, mixes, and accumulates its slab's contribution G_tile @
    S_tile. When ``total`` is not a tile multiple the final block's
    overhang (whose contents Pallas leaves undefined) is masked to
    zero in-register — G is never padded or copied in HBM.
    """
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)
        base_ref[...] = _hash_base(tile, dim)

    start = i * tile
    signs = _signs_from_hash(
        base_ref[...] + _hash_offset(seed_ref[0, 0], offset + start))
    g = g_ref[...].astype(jnp.float32)                   # (n, tile)
    if total % tile:
        pos = jax.lax.broadcasted_iota(jnp.int32, g.shape, 1) + start
        g = jnp.where(pos < total, g, 0.0)
    o_ref[...] += jnp.dot(g, signs,
                          preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("dim", "offset", "rows",
                                             "interpret"))
def sketch_flat(G: jnp.ndarray, seed, dim: int, offset: int = 0,
                rows: int = DEFAULT_ROWS,
                interpret: bool = False) -> jnp.ndarray:
    """G: (n, P) float, seed: () int → (n, d) fp32 = G @ S where
    ``S[p, j] = sign_block(seed, offset + p, ...)``. The grid walks
    ceil(P / tile) blocks of the position axis directly on the
    unpadded G — the ragged final block is masked inside the kernel,
    so the only HBM traffic is one read of G plus the (n, d) write."""
    n, p = G.shape
    tile = rows * LANES
    tiles = (p + tile - 1) // tile
    seed2 = jnp.reshape(jnp.asarray(seed, jnp.int32), (1, 1))

    return pl.pallas_call(
        functools.partial(_sketch_kernel, offset=offset, tile=tile,
                          dim=dim, total=p),
        grid=(tiles,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((n, tile), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((n, dim), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((n, dim), jnp.float32),
        scratch_shapes=[pltpu.VMEM((tile, dim), jnp.uint32)],
        interpret=interpret,
    )(seed2, G)
