"""jit'd wrappers for the gradient-sketch projection.

``sketch_pytree`` is the production entry point: it streams a stacked
gradient pytree (leaves (n, *param)) leaf-by-leaf into one (n, d)
sketch, with offsets advancing by true leaf size so the result equals
projecting the flat concatenation — which is never materialised.

Implementation selection (``impl``):

* ``"auto"``    — Pallas kernel where the program is lowered for a
  TPU (one HBM pass, signs regenerated in VMEM), tiled XLA elsewhere
  (``jax.lax.platform_dependent``). The CPU/GPU tiled path
  is the same algorithm at XLA level: per-leaf chunks of
  ``block`` positions, one (block, d) sign block live at a time.
* ``"pallas"`` / ``"pallas_interpret"`` — force the kernel
  (interpret mode runs it off-TPU; the kernel-vs-oracle tests use
  this).
* ``"xla"``     — force the tiled XLA path.

Small leaves (< one kernel tile) always take the jnp reference — the
launch overhead would dominate and XLA fuses them anyway. Leaves and
sketch dims that don't meet the kernel's lane alignment (d % 128)
fall back to the tiled XLA path rather than failing.

Under ``shard_map`` the rows of each device are its own (its agents'),
and every device would hash the same signs: they depend on position,
not on row. So where the caller's ``shard_map`` spans D > 1 devices,
``sketch_pytree`` divides each kernel-sized leaf's positions instead.
An ``all_to_all`` hands device q the q-th contiguous share of every
device's rows, the kernel sketches those L·D rows over that share
with the share's start folded into the seed scalar (the hash's input
is ``seed + position·P1 + j·P2``, wrapping, so the signs are the same
bits), and one ``psum_scatter`` returns each device the sum of its own
rows' shares. Each device hashes 1/D of the signs; the result is the
per-device sketch up to float32 summation order.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.kernels.grad_sketch import ref
from repro.kernels.grad_sketch.kernel import (
    DEFAULT_ROWS,
    LANES,
    _hash_offset,
    sign_block_i8,
    sketch_flat,
)

_MIN_KERNEL_SIZE = DEFAULT_ROWS * LANES
# XLA-path chunk: one (block, d) int8 sign block is the only
# projection intermediate ever live — block·d bytes (1 MB at
# d = 256; was 4 MB fp32 before the bit-pack).
DEFAULT_BLOCK = 4096
# beyond this many chunks per leaf, roll the walk into a fori_loop —
# unrolled static slices fuse (and run) better, but jaxpr size must
# stay bounded for LLM-scale leaves
_MAX_UNROLL = 64

IMPLS = ("auto", "pallas", "pallas_interpret", "xla")


def _xla_sketch_flat(G: jnp.ndarray, seed, dim: int, offset: int = 0,
                     block: int = DEFAULT_BLOCK) -> jnp.ndarray:
    """Tiled XLA projection: walk ``block``-position chunks of G so
    only one (block, d) sign block exists at a time — generated as an
    **int8** ±1 matrix (``sign_block_i8``), 1 B/sign instead of 4,
    with the fp32 cast fused into the dot; ±1 is exact either way, so
    the sketch is bitwise the fp32-sign oracle's. Few-tile leaves
    unroll (static slices fuse best); beyond ``_MAX_UNROLL`` tiles
    the loop rolls into a ``fori_loop`` so program size stays O(1)
    however large the leaf (a 4e8-position embedding would otherwise
    unroll ~1e5 dot equations into the jaxpr). The short tail chunk
    is one static trailing step: the sign stream is positional, so no
    padding copy of G is ever made."""
    n, p = G.shape
    tiles, tail = divmod(p, block)
    acc = jnp.zeros((n, dim), jnp.float32)

    def chunk(a, start, width):
        g = jax.lax.slice_in_dim(G, start, start + width, axis=1)
        s = sign_block_i8(seed, offset + start, width, dim)
        return a + jnp.dot(g.astype(jnp.float32),
                           s.astype(jnp.float32),
                           preferred_element_type=jnp.float32)

    if tiles <= _MAX_UNROLL:
        for t in range(tiles):
            acc = chunk(acc, t * block, block)
    else:
        def body(i, a):
            g = jax.lax.dynamic_slice_in_dim(G, i * block, block,
                                             axis=1)
            s = sign_block_i8(seed, offset + i * block, block, dim)
            return a + jnp.dot(g.astype(jnp.float32),
                               s.astype(jnp.float32),
                               preferred_element_type=jnp.float32)
        acc = jax.lax.fori_loop(0, tiles, body, acc)
    if tail:
        acc = chunk(acc, tiles * block, tail)
    return acc


def sketch_leaf(x: jnp.ndarray, seed, dim: int, offset: int = 0, *,
                impl: str = "auto") -> jnp.ndarray:
    """One leaf (n, *param) → its (n, d) sketch contribution."""
    if impl not in IMPLS:
        raise ValueError(f"unknown sketch impl {impl!r}; expected one "
                         f"of {IMPLS}")
    n = x.shape[0]
    p = int(x.size) // n
    G = jnp.reshape(x, (n, p))
    if p < _MIN_KERNEL_SIZE:
        return ref.sketch_flat(G, seed, dim, offset=offset)

    def xla(G):
        return _xla_sketch_flat(G, seed, dim, offset=offset)

    def kernel(G):
        return sketch_flat(G, seed, dim, offset=offset,
                           interpret=impl == "pallas_interpret")

    if impl == "xla" or dim % LANES:
        return xla(G)
    if impl == "auto":
        return jax.lax.platform_dependent(G, tpu=kernel, default=xla)
    return kernel(G)


def _manual_axes():
    """The mesh axes the caller's ``shard_map`` runs manually over, in
    mesh order, and the number of devices they span (1 outside one)."""
    mesh = jax.sharding.get_abstract_mesh()
    axes = tuple(mesh.manual_axes)
    return axes, math.prod(mesh.shape[a] for a in axes)


def _sketch_share(x, seed, dim: int, offset: int, axes, devices: int,
                  impl: str) -> jnp.ndarray:
    """This device's (n·D, d) partial sketch of one leaf (n, *param)
    under ``shard_map`` over ``axes``: the all-to-all gives it the
    q-th of D contiguous shares of the positions of every device's n
    rows, device by device, and it sketches them with the share's
    start folded into the seed. The split runs along the leading
    parameter axis where that divides by D (no copy of the leaf);
    otherwise the flat leaf is padded with zero positions, which add
    nothing to G·S."""
    n = x.shape[0]
    if x.ndim < 2 or x.shape[1] % devices:
        p = int(x.size) // n
        x = jnp.pad(jnp.reshape(x, (n, p)), ((0, 0), (0, -p % devices)))
    part = jax.lax.all_to_all(x, axes, 1, 0, tiled=True)
    share = int(part.size) // (n * devices)
    start = (jnp.uint32(offset % 2**32)
             + jax.lax.axis_index(axes).astype(jnp.uint32)
             * jnp.uint32(share))
    seed_q = jax.lax.bitcast_convert_type(_hash_offset(seed, start),
                                          jnp.int32)
    return sketch_leaf(part, seed_q, dim, impl=impl)


def sketch_pytree(grads, seed, dim: int, *,
                  impl: str = "auto") -> jnp.ndarray:
    """Stream a stacked gradient pytree into its (n, d) sketch in one
    pass — the (n, P) concat is never built. ``seed`` may be traced;
    the sketch is a deterministic pure function of (seed, grads).
    Under a ``shard_map`` over D > 1 devices each device gets its own
    rows' sketch, and a leaf whose D-th share fills a kernel tile is
    hashed a share a device (module docstring)."""
    leaves = jax.tree.leaves(grads)
    if not leaves:
        raise ValueError("sketch_pytree needs at least one leaf")
    n = leaves[0].shape[0]
    axes, devices = _manual_axes()
    acc = jnp.zeros((n, dim), jnp.float32)
    shared = None
    offset = 0
    for x in leaves:
        p = int(x.size) // n
        if devices > 1 and -(-p // devices) >= _MIN_KERNEL_SIZE:
            part = _sketch_share(x, seed, dim, offset, axes, devices,
                                 impl)
            shared = part if shared is None else shared + part
        else:
            acc = acc + sketch_leaf(x, seed, dim, offset, impl=impl)
        offset += p
    if shared is not None:
        acc = acc + jax.lax.psum_scatter(shared, axes, tiled=True)
    return acc
