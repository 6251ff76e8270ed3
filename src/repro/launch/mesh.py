"""Production mesh definitions (TPU v5e target).

Defined as FUNCTIONS so importing this module never touches jax device
state (the dry-run sets XLA_FLAGS before any jax initialisation).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes, devices=None):
    """``jax.make_mesh`` with every axis ``Auto``: the model code
    places arrays with ``with_sharding_constraint`` and lets GSPMD
    propagate the rest, which ``Explicit`` axes (jax's default) turn
    into hard asserts. ``devices`` defaults to every device."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: 16×16 = 256 chips over ("data", "model").
    Multi-pod: 2×16×16 = 512 chips over ("pod", "data", "model") —
    one GARL agent per pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_pod_mesh(n_pods: int, devices_per_pod: int = None,
                  pod_axis: str = "pod", devices=None):
    """Two-level ``(pod_axis, "agent")`` mesh for hierarchical DDAL
    dispatch: the ``"agent"`` axis is the fast intra-pod interconnect
    (ICI on a TPU pod), ``pod_axis`` the slow cross-pod one (DCN).
    Only pod leaders' knowledge planes ever cross ``pod_axis``
    (``repro.core.pod_dispatch``).

    On a single-host simulation rig the devices come from
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` — the same
    mesh the multi-device test lane uses. ``devices`` (default: every
    device) are laid out pod-major: with one agent per device, agent
    i of the DDAL group runs on ``devices[i]``."""
    n_dev = len(devices) if devices is not None else jax.device_count()
    if devices_per_pod is None:
        if n_pods < 1 or n_dev % n_pods:
            raise ValueError(
                f"{n_dev} devices do not split into {n_pods} pods — "
                f"pass devices_per_pod explicitly")
        devices_per_pod = n_dev // n_pods
    return make_mesh((n_pods, devices_per_pod), (pod_axis, "agent"),
                     devices=devices)


def train_rules(mesh, pod_axis: str = "pod") -> dict:
    """Logical→physical sharding rules for training on ``mesh``.
    ``pod_axis`` must name the cross-pod axis when the mesh was built
    with a non-default name (``make_pod_mesh(..., pod_axis=...)``)."""
    has_pod = pod_axis in mesh.axis_names
    # two-level DDAL mesh: the agent axis spreads over pods × the
    # intra-pod agent axis (repro.core.pod_dispatch)
    if has_pod and "agent" in mesh.axis_names:
        agent = (pod_axis, "agent")
    else:
        agent = pod_axis if has_pod else None
    return {
        "agent": agent,
        "batch": "data",
        "vocab": "model",
        "heads": "model",
        "kv_heads": "model",
        "qkv_fused": "model",
        "ff": "model",
        "experts": "model",
        "ssm_inner": "model",
        "kv_slots": None,        # training: no decode cache
    }


def serve_rules(mesh, global_batch: int) -> dict:
    """Serving has no agent axis; the batch spreads over every
    non-model axis when divisible (pod×data on the multi-pod mesh)."""
    has_pod = "pod" in mesh.axis_names
    batch_axes = ("pod", "data") if has_pod else ("data",)
    n = 1
    for a in batch_axes:
        n *= mesh.shape[a]
    batch = batch_axes if global_batch % n == 0 else None
    if batch is not None and len(batch) == 1:
        batch = batch[0]
    return {
        "agent": None,
        "batch": batch,
        "vocab": "model",
        "heads": "model",
        "kv_heads": "model",
        "qkv_fused": "model",
        "ff": "model",
        "experts": "model",
        "ssm_inner": "model",
        # decode caches shard their SLOT dim over "model" (32768 and
        # the 8192 sliding window both divide 16) — flash-decoding
        # style distributed KV sweep; kv-head counts (8, 4) don't
        # divide 16, so head-sharding would replicate (§Perf it.5)
        "kv_slots": "model",
    }
