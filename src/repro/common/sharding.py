"""Logical-axis sharding helpers.

Model code annotates tensors with *logical* axis names ("batch",
"seq", "model_in", "experts", ...). A rule table, installed by the
launcher (or left empty for single-device smoke tests), maps logical
names to physical mesh axes. When no rules are installed every
annotation is the identity, so the same model code runs on one CPU
device and on the 512-chip production mesh.
"""
from __future__ import annotations

import contextlib
import threading
from typing import Optional

import jax
from jax.sharding import PartitionSpec as P

_state = threading.local()

# Default logical→physical rules for the production ("data", "model")
# mesh (the "pod" axis is handled separately: it only ever shards the
# leading agent axis, see repro.core.sharded_ddal).
DEFAULT_RULES = {
    "batch": "data",
    "agent": "pod",
    "vocab": "model",
    "heads": "model",
    "kv_heads": "model",
    "qkv_fused": "model",
    "ff": "model",
    "experts": "model",
    "ssm_inner": "model",
    "embed": None,
    "seq": None,
}


def get_rules() -> Optional[dict]:
    return getattr(_state, "rules", None)


@contextlib.contextmanager
def axis_rules(rules: Optional[dict]):
    """Install logical→physical sharding rules for the enclosed scope."""
    prev = get_rules()
    _state.rules = rules
    try:
        yield
    finally:
        _state.rules = prev


def logical_spec(*names: Optional[str]) -> P:
    """Resolve logical axis names to a PartitionSpec under current rules."""
    rules = get_rules()
    if rules is None:
        return P()
    return P(*[rules.get(n) if n is not None else None for n in names])


def shard(x, *names: Optional[str]):
    """Apply a logical sharding constraint (identity w/o rules)."""
    rules = get_rules()
    if rules is None:
        return x
    if all(rules.get(n) is None for n in names if n is not None):
        return x
    return jax.lax.with_sharding_constraint(x, logical_spec(*names))


def named_sharding(mesh, *names: Optional[str]):
    """A NamedSharding for jit in_/out_shardings from logical names."""
    return jax.sharding.NamedSharding(mesh, logical_spec(*names))

