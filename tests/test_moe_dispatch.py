"""MoE dispatch-engine equivalence: the expert-parallel shard_map path
(gather dispatch + fp32 psum combine — §Perf iteration 1) must be
numerically identical to the dense scatter reference, for losses AND
gradients, including under the vmapped agent axis."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.common.sharding import axis_rules
from repro.configs import get_arch_config
from repro.configs.base import ShapeConfig
from repro.launch.mesh import make_mesh, train_rules
from repro.models import get_model, make_batch
from repro.models.moe import _dispatch_indices


@pytest.mark.parametrize(
    "arch",
    ["qwen3-moe-30b-a3b",
     # the deepseek cell is the slowest single test in the fast lane
     # (~16s) and exercises the same dispatch path with shared-expert
     # routing on top; the qwen cell keeps the dense-parity oracle in
     # tier-1, deepseek rides the slow lane
     pytest.param("deepseek-v2-lite-16b", marks=pytest.mark.slow)])
def test_expert_parallel_equals_dense(arch):
    cfg = get_arch_config(arch).reduced()
    model = get_model(cfg)
    key = jax.random.PRNGKey(0)
    params = model.init(cfg, key)
    batch = make_batch(cfg, ShapeConfig("t", 64, 2, "train"), key)

    l_dense = model.loss(cfg.with_(moe_dispatch="dense"), params, batch)
    g_dense = jax.grad(lambda p: model.loss(
        cfg.with_(moe_dispatch="dense"), p, batch))(params)

    mesh = make_mesh((1, 1), ("data", "model"))
    with jax.set_mesh(mesh), axis_rules(train_rules(mesh)):
        l_ep = jax.jit(lambda p, b: model.loss(cfg, p, b))(params, batch)
        g_ep = jax.jit(jax.grad(
            lambda p: model.loss(cfg, p, batch)))(params)
    np.testing.assert_allclose(float(l_dense), float(l_ep), rtol=1e-5)
    jax.tree.map(lambda a, b: np.testing.assert_allclose(
        np.asarray(a), np.asarray(b), rtol=3e-4, atol=3e-5),
        g_dense, g_ep)


def test_expert_parallel_under_vmap():
    """The DDAL train step vmaps over agents — shard_map must batch."""
    cfg = get_arch_config("qwen3-moe-30b-a3b").reduced()
    model = get_model(cfg)
    key = jax.random.PRNGKey(0)
    params = model.init(cfg, key)
    batch = make_batch(cfg, ShapeConfig("t", 64, 2, "train"), key)
    mesh = make_mesh((1, 1), ("data", "model"))
    with jax.set_mesh(mesh), axis_rules(train_rules(mesh)):
        vg = jax.jit(jax.vmap(jax.value_and_grad(
            lambda p, b: model.loss(cfg, p, b))))
        pp = jax.tree.map(lambda x: jnp.stack([x, x]), params)
        bb = jax.tree.map(lambda x: jnp.stack([x, x]), batch)
        losses, grads = vg(pp, bb)
    l_ref = model.loss(cfg.with_(moe_dispatch="dense"), params, batch)
    np.testing.assert_allclose(np.asarray(losses),
                               np.full(2, float(l_ref)), rtol=1e-5)


def test_dispatch_indices_match_cumsum_semantics():
    """Sort-based slots == cumsum-scatter slots (same drops)."""
    key = jax.random.PRNGKey(3)
    B, S, k, Ne, C = 3, 16, 2, 4, 5
    T = S * k
    e_flat = jax.random.randint(key, (B, T), 0, Ne)
    gate_flat = jax.random.uniform(jax.random.fold_in(key, 1), (B, T),
                                   minval=0.1)
    token_idx, w, src, valid = _dispatch_indices(e_flat, gate_flat,
                                                 Ne, C, k)
    # reference: cumsum position per token
    onehot = jax.nn.one_hot(e_flat, Ne, dtype=jnp.int32)
    pos = jnp.take_along_axis(jnp.cumsum(onehot, axis=1) - 1,
                              e_flat[..., None], axis=2)[..., 0]
    keep = np.asarray(pos < C)
    for b in range(B):
        got = set()
        for e in range(Ne):
            for c in range(C):
                if bool(valid[b, e, c]):
                    t = int(token_idx[b, e, c])
                    assert int(e_flat[b, t]) == e
                    got.add(t)
        want = {t for t in range(T) if keep[b, t]}
        assert got == want
