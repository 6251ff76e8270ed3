"""jit'd wrappers for the eq. 4 weighted-average kernels.

``tree_wavg`` applies the kernel leaf-wise over a stacked gradient
pytree (leaves (m, *param_shape)) — the exact contraction DDAL's
knowledge stores perform at every share step. Small leaves (< one
tile) fall back to the jnp oracle: kernel launch overhead would
dominate and XLA already fuses them — that fallback path compiles on
any backend with no interpreter involved.

``interpret=None`` auto-selects: compiled Pallas on TPU, interpreter
mode elsewhere (Pallas-TPU kernels cannot compile on CPU/GPU). An
explicit bool overrides — tests force ``interpret=True`` off-TPU.

The *fused* entry points (``fused_wavg`` / ``tree_fused_wavg`` and
their ``_q`` quantized twins) take the raw (T, R, valid) metadata and
emit (ḡ, Σw) in one pass. They carry a grad_sketch-style ``impl``
knob:

* ``"auto"``   — Pallas where the program is lowered for a TPU, tiled
  XLA elsewhere (``jax.lax.platform_dependent``: the choice follows
  the device the step is compiled for, not the process's default
  backend, so a CPU reference run beside a chip takes the XLA form);
* ``"pallas"`` — the fused kernel (``interpret`` then auto-resolves
  via :func:`resolve_interpret` unless forced);
* ``"xla"``    — portable path. At quantization-off this is literally
  ``eq4_weights`` + the ``tree_weighted_sum`` tensordot, so it is
  **bitwise-equal** to the historical multi-op share step; quantized,
  it dequantises in lane-sized chunks under ``lax.scan`` so no fp32
  copy of the full plane stack ever materialises.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.weighting import eq4_weights
from repro.kernels.ddal_wavg import ref
from repro.kernels.ddal_wavg.kernel import (DEFAULT_ROWS, EQ4_EPS, LANES,
                                            fused_wavg_flat,
                                            fused_wavg_q_flat, wavg_flat)

_MIN_KERNEL_SIZE = DEFAULT_ROWS * LANES
_XLA_Q_CHUNK = 8192        # target elements per scan step (≥ q_block)

IMPLS = ("auto", "pallas", "xla")


def resolve_interpret(interpret: Optional[bool]) -> bool:
    """None → interpret off-TPU, compiled on TPU; bool → itself."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return interpret


def resolve_impl(impl: Optional[str]) -> str:
    """Validate an ``impl`` name; None means ``auto``."""
    if impl is None:
        impl = "auto"
    if impl not in IMPLS:
        raise ValueError(f"impl must be one of {IMPLS}, got {impl!r}")
    return impl


def _pick(kind: str, pallas, xla):
    """The function ``kind`` names; ``auto`` defers the choice to
    lowering, where the target platform is known."""
    if kind == "auto":
        return lambda *a: jax.lax.platform_dependent(*a, tpu=pallas,
                                                     default=xla)
    return pallas if kind == "pallas" else xla


def wavg(G: jnp.ndarray, w: jnp.ndarray, *,
         interpret: Optional[bool] = None) -> jnp.ndarray:
    """Σ_j w_j·G[j] for G: (m, N) → (N,) fp32."""
    return wavg_flat(G, w, interpret=resolve_interpret(interpret))


def tree_wavg(grads_stacked, w, *, interpret: Optional[bool] = None):
    """Kernel-backed version of pytree eq. 4 contraction."""
    interp = resolve_interpret(interpret)

    def leaf(x):
        m = x.shape[0]
        size = int(x.size) // m
        if size < _MIN_KERNEL_SIZE:
            return ref.wavg(x.reshape(m, -1), w).reshape(x.shape[1:])
        flat = x.reshape(m, size)
        return wavg_flat(flat, w, interpret=interp
                         ).reshape(x.shape[1:])
    return jax.tree.map(leaf, grads_stacked)


# ---------------------------------------------------------------------
# fused share step: (T, R, valid) in, (ḡ, Σw) out
# ---------------------------------------------------------------------
def fused_wavg(G, T, R, valid, *, impl: str = "auto",
               interpret: Optional[bool] = None, eps: float = EQ4_EPS):
    """Fused eq. 4 on a flat plane stack G: (m, N) → (ḡ: (N,), Σw)."""
    kind = resolve_impl(impl)
    interp = kind == "pallas" and resolve_interpret(interpret)
    return _pick(
        kind,
        lambda *a: fused_wavg_flat(*a, interpret=interp, eps=eps),
        lambda *a: ref.fused_wavg(*a, eps=eps))(G, T, R, valid)


def _xla_fused_wavg_q_flat(Q, scale, w, q_block: int):
    """Streaming-dequant contraction: scan over element chunks so the
    live fp32 intermediate is (m, chunk), never the full (m, N) plane
    stack — the XLA analogue of in-kernel dequantisation."""
    m, n = Q.shape
    chunk = max(q_block, (_XLA_Q_CHUNK // q_block) * q_block)
    n_pad = -(-n // chunk) * chunk
    nb_pad = n_pad // q_block
    if n_pad != n:
        Q = jnp.pad(Q, ((0, 0), (0, n_pad - n)))
    if scale.shape[1] != nb_pad:
        scale = jnp.pad(scale, ((0, 0), (0, nb_pad - scale.shape[1])))
    steps = n_pad // chunk
    nbc = chunk // q_block
    Qc = Q.reshape(m, steps, chunk).transpose(1, 0, 2)
    Sc = scale.reshape(m, steps, nbc).transpose(1, 0, 2)
    wf = w.astype(jnp.float32)

    def step(carry, qs):
        q, s = qs                                # (m, chunk), (m, nbc)
        g = ref.dequantize_flat(q, s, q_block)
        return carry, jnp.tensordot(wf, g, axes=(0, 0))

    _, out = jax.lax.scan(step, 0, (Qc, Sc))
    return out.reshape(n_pad)[:n]


def fused_wavg_q(Q, scale, T, R, valid, q_block: int, *,
                 impl: str = "auto", interpret: Optional[bool] = None,
                 eps: float = EQ4_EPS):
    """Fused eq. 4 over int8 block-quantized planes → (ḡ, Σw)."""
    kind = resolve_impl(impl)
    interp = kind == "pallas" and resolve_interpret(interpret)

    def xla(Q, scale, T, R, valid):
        w = eq4_weights(T, R, valid, eps=eps)
        return _xla_fused_wavg_q_flat(Q, scale, w, q_block), jnp.sum(w)

    return _pick(
        kind,
        lambda *a: fused_wavg_q_flat(*a, q_block, interpret=interp,
                                     eps=eps),
        xla)(Q, scale, T, R, valid)


def tree_fused_wavg(stacked, T, R, valid, *, impl: str = "auto",
                    interpret: Optional[bool] = None,
                    eps: float = EQ4_EPS):
    """Fused eq. 4 over a stacked pytree (leaves (m, *param)) →
    (ḡ tree, Σw). The ``xla`` path reproduces the multi-op share step
    op-for-op — ``eq4_weights`` then the exact ``tree_weighted_sum``
    contraction on the *unreshaped* leaf — so it is bitwise-equal to
    the historical path; ``pallas`` streams big leaves through the
    fused kernel and keeps small leaves on the oracle contraction."""
    kind = resolve_impl(impl)
    w = eq4_weights(T, R, valid, eps=eps)
    interp = kind == "pallas" and resolve_interpret(interpret)

    def xla(x):
        return jnp.tensordot(w.astype(x.dtype), x, axes=(0, 0))

    def kernel(x):
        m = x.shape[0]
        g, _ = fused_wavg_flat(x.reshape(m, -1), T, R, valid,
                               interpret=interp, eps=eps)
        return g.reshape(x.shape[1:])

    def leaf(x):
        if int(x.size) // x.shape[0] < _MIN_KERNEL_SIZE:
            return xla(x)
        return _pick(kind, kernel, xla)(x)
    return jax.tree.map(leaf, stacked), jnp.sum(w)


def tree_fused_wavg_q(qtree, stree, T, R, valid, q_block: int, *,
                      impl: str = "auto",
                      interpret: Optional[bool] = None,
                      eps: float = EQ4_EPS):
    """Fused eq. 4 over an int8-quantized stacked pytree → (ḡ, Σw)."""
    kind = resolve_impl(impl)
    w = eq4_weights(T, R, valid, eps=eps)
    interp = kind == "pallas" and resolve_interpret(interpret)

    def kernel(qf, sf):
        return fused_wavg_q_flat(qf, sf, T, R, valid, q_block,
                                 interpret=interp, eps=eps)[0]

    def leaf(q, s):
        m = q.shape[0]
        size = int(q.size) // m
        qf = q.reshape(m, size)
        sf = s.reshape(m, -1)
        if size < _MIN_KERNEL_SIZE:
            g = jnp.tensordot(w.astype(jnp.float32),
                              ref.dequantize_flat(qf, sf, q_block),
                              axes=(0, 0))
        else:
            g = _pick(kind, kernel,
                      lambda qf, sf: _xla_fused_wavg_q_flat(
                          qf, sf, w, q_block))(qf, sf)
        return g.reshape(q.shape[1:])
    return jax.tree.map(leaf, qtree, stree), jnp.sum(w)


# ---------------------------------------------------------------------
# int8 block quantization over pytrees (knowledge-plane storage)
# ---------------------------------------------------------------------
def quantize_tree(tree, q_block: int, lead: int = 1):
    """Quantize every leaf's trailing (param) axes into int8 blocks.

    Leaves are viewed as (*lead_shape, P) with ``lead`` leading axes
    kept verbatim (m for stores, (n, k, D+2) for delay lines). Returns
    (qtree, stree): qtree mirrors the input shapes in int8; stree's
    leaves are (*lead_shape, ⌈P/q_block⌉) fp32 scales."""
    leaves, treedef = jax.tree.flatten(tree)
    pairs = [ref.quantize_flat(x.reshape(x.shape[:lead] + (-1,)),
                               q_block) for x in leaves]
    qtree = jax.tree.unflatten(
        treedef, [p[0].reshape(x.shape) for p, x in zip(pairs, leaves)])
    stree = jax.tree.unflatten(treedef, [p[1] for p in pairs])
    return qtree, stree


def dequantize_tree(qtree, stree, q_block: int):
    """Inverse of :func:`quantize_tree` → fp32 tree of qtree's shapes.
    The lead-axis split is recovered from each scale leaf's rank."""
    def leaf(q, s):
        lead = s.ndim - 1
        flat = q.reshape(q.shape[:lead] + (-1,))
        return ref.dequantize_flat(flat, s, q_block).reshape(q.shape)
    return jax.tree.map(leaf, qtree, stree)
