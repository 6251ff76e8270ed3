"""Device time of the held experts' grouped products in a traced run.

The TPU compiler rewrites each ``jax.lax.ragged_dot`` into a custom
call named ``ragged-dot-*`` (with its ``ragged-dot-metadata``) whose
``op_name`` is that name alone: the model's ``ddal.experts`` scope is
gone, so ``bench/scopes.py`` gives the call the scope of the loop
around it (``ddal.grad``). The held experts are the program's only
ragged dots, so their calls are found by name and added back here.
"""
from __future__ import annotations

import re

import scopes

RAGGED = re.compile(r"^ragged-dot")
EXPERT_SCOPES = ("ddal.moe", "ddal.experts")


def stray_ragged_s(ctx) -> float:
    """Seconds of the ragged-dot calls that no expert scope holds."""
    if "scope_of" not in ctx:
        ctx["scope_of"] = scopes.trace_scopes(ctx["chips"])
    scope_of = ctx["scope_of"]
    return sum(s for name, s in ctx["trace"].op_s.items()
               if RAGGED.match(name)
               and scope_of.get(name) not in EXPERT_SCOPES)


def scope_s(ctx, names) -> float:
    """Seconds in the scopes ``names``, with the stray ragged-dot calls;
    None where the trace has none of the scopes."""
    times = scopes.read(ctx)
    if not times or not any(n in times for n in names):
        return None
    return sum(times.get(n, 0.0) for n in names) + stray_ragged_s(ctx)
