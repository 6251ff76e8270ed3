"""The streaming train step names its pieces for the device trace.

``make_group_train_step`` wraps each piece of the step in
``jax.named_scope``. The names reach the lowered program's op names,
which become the compiled ops' ``op_name`` metadata: the path a
profiler trace splits device time by (``bench/devtrace.py``). The step
is lowered for a TPU, where the sketch branch holds the Pallas
kernel's custom call; nothing is compiled and no chip is needed.
"""
from __future__ import annotations

import functools
import re

import jax
import pytest

SCOPES = ("ddal.grad", "ddal.window", "ddal.sketch", "ddal.exchange",
          "ddal.combine", "ddal.optimizer")


@pytest.fixture(scope="module")
def lowered_text():
    from repro import optim
    from repro.configs import get_arch_config
    from repro.configs.base import GroupSpec, ShapeConfig
    from repro.core import make_group_train_step
    from repro.core.sharded_ddal import train_state_specs
    from repro.data import StreamSpec, make_group_batch

    cfg = get_arch_config("mamba2-780m").reduced()
    spec = GroupSpec(n_agents=2, threshold=1, minibatch=2,
                     knowledge_mode="streaming",
                     exchange_estimator="grad_cos+sketch",
                     relevance_sketch_dim=128)
    opt = optim.adamw(1e-3)
    state = train_state_specs(cfg, spec, opt)
    batch = make_group_batch(cfg, ShapeConfig("t", 32, 2, "train"),
                             StreamSpec(), 2, 0)
    step = jax.jit(make_group_train_step(cfg, spec, opt))
    lowered = step.trace(state, batch).lower(lowering_platforms=("tpu",))
    return lowered.as_text(debug_info=True)


def op_names(text: str, pattern: str):
    """The full op name (name stack) of every op whose line matches
    ``pattern``. An op inside a private function (a nested jit) is
    named by the call that reaches it, prefixed to its own name, as
    the compiler names it once the call is inlined."""
    alias = dict(re.findall(r"^(#loc\d+) = loc\((.*)\)$", text, re.M))

    def name(line):
        m = re.search(r"loc\((#loc\d+)\)\s*$", line)
        v = alias.get(m.group(1), "") if m else ""
        m = re.match(r'"([^"]*)"', v)
        return m.group(1) if m else ""

    owner, body = None, {}
    for line in text.splitlines():
        m = re.match(r"\s*func\.func (?:public |private )?@([\w.$-]+)",
                     line)
        if m:
            owner = m.group(1)
        elif owner is not None:
            body.setdefault(owner, []).append(line)
    calls = {}                               # callee -> calling lines
    for fn, lines in body.items():
        for line in lines:
            for callee in re.findall(r"call @([\w.$-]+)\(", line):
                calls.setdefault(callee, []).append((fn, line))

    @functools.lru_cache(maxsize=None)
    def prefixes(fn):
        if fn == "main":
            return ("",)
        return tuple(p + name(line) + "/" for caller, line in
                     calls.get(fn, ()) for p in prefixes(caller))

    return [p + name(line) for fn, lines in body.items() for line in lines
            if re.search(pattern, line) for p in prefixes(fn)]


def test_every_scope_reaches_the_lowered_step(lowered_text):
    names = op_names(lowered_text, r"loc\(#loc\d+\)\s*$")
    for scope in SCOPES:
        assert any(f"/{scope}/" in n for n in names), scope
    # the backward pass carries the scope of its forward
    assert any("/ddal.grad/" in n and "transpose" in n for n in names)


def test_sketch_kernel_sits_under_the_sketch_scope(lowered_text):
    calls = op_names(lowered_text, r"custom_call @tpu_custom_call")
    assert calls, "the TPU lowering holds no Pallas custom call"
    assert all("/ddal.sketch/" in n for n in calls), calls


def test_combine_ops_sit_under_the_combine_scope(lowered_text):
    """Every op of the share branch of the exchange cond is eq. 4's or
    relevance's, inside ``ddal.combine``; the local branch (zeros) is
    the exchange's own."""
    names = op_names(lowered_text, r"loc\(#loc\d+\)\s*$")
    share = [n for n in names if "/ddal.exchange/cond/branch_1_fun/" in n]
    assert share
    assert all("/branch_1_fun/ddal.combine/" in n for n in share), share
    combine = [n.rsplit("/", 1)[1] for n in share]
    assert "div" in combine and "dot_general" in combine
