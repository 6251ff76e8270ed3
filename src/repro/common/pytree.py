"""Pytree arithmetic helpers (no optax available — we build our own).

All helpers are jit-friendly pure functions over arbitrary pytrees of
jnp arrays. They are used by the optimiser, the DDAL weighted-average
(paper eq. 4) and the knowledge stores.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def tree_map(f, *trees):
    return jax.tree.map(f, *trees)


def tree_add(a, b):
    return jax.tree.map(jnp.add, a, b)


def tree_sub(a, b):
    return jax.tree.map(jnp.subtract, a, b)


def tree_scale(a, s):
    """Scale every leaf of ``a`` by scalar (or 0-d array) ``s``."""
    return jax.tree.map(lambda x: x * s, a)


def tree_zeros_like(a):
    return jax.tree.map(jnp.zeros_like, a)


def tree_ones_like(a):
    return jax.tree.map(jnp.ones_like, a)


def tree_add_scaled(a, b, s):
    """a + s * b, leafwise."""
    return jax.tree.map(lambda x, y: x + s * y, a, b)


def tree_lerp(a, b, t):
    """(1 - t) * a + t * b, leafwise."""
    return jax.tree.map(lambda x, y: (1.0 - t) * x + t * y, a, b)


def tree_dot(a, b):
    """Inner product of two pytrees."""
    leaves = jax.tree.map(lambda x, y: jnp.vdot(x, y), a, b)
    return jax.tree.reduce(jnp.add, leaves, jnp.float32(0.0))


def tree_sq_norm(a):
    # a sum of squares, not ``jnp.vdot``: XLA:CPU lowers a leaf's vdot
    # to one flat dot that accumulates in float32 term by term, 7% short
    # on a 33M-element leaf; the reduction sums pairwise
    leaves = jax.tree.map(lambda x: jnp.sum(jnp.square(x)), a)
    return jax.tree.reduce(jnp.add, leaves, jnp.float32(0.0))


def tree_norm(a):
    return jnp.sqrt(tree_sq_norm(a))


def tree_count(a) -> int:
    """Total number of scalar parameters (static)."""
    return sum(int(x.size) for x in jax.tree.leaves(a))


def tree_weighted_sum(trees_stacked, weights):
    """Weighted sum over the leading axis of every leaf.

    ``trees_stacked`` is a pytree whose leaves have a leading axis of
    size m (m stacked gradient pieces); ``weights`` is an (m,) vector.
    Returns the pytree with the leading axis contracted:
    ``out = sum_j weights[j] * leaf[j]`` — exactly the contraction in
    DDAL's eq. 4 once the weights have been normalised.
    """
    def wsum(leaf):
        w = weights.astype(leaf.dtype)
        return jnp.tensordot(w, leaf, axes=(0, 0))
    return jax.tree.map(wsum, trees_stacked)


def tree_stack(trees):
    """Stack a python list of congruent pytrees along a new axis 0."""
    return jax.tree.map(lambda *xs: jnp.stack(xs, axis=0), *trees)


def tree_unstack(tree, n):
    """Inverse of tree_stack for a static n."""
    return [jax.tree.map(lambda x, i=i: x[i], tree) for i in range(n)]


def tree_cast(a, dtype):
    return jax.tree.map(lambda x: x.astype(dtype), a)


def global_norm_clip(grads, max_norm):
    """Classic global-norm gradient clipping; returns (clipped, norm)."""
    norm = tree_norm(grads)
    scale = jnp.minimum(1.0, max_norm / (norm + 1e-6))
    return tree_scale(grads, scale), norm
