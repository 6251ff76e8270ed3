"""The window sketch with its positions split over the agents' devices.

Under a ``shard_map`` over D > 1 devices, ``sketch_pytree`` hands each
device one D-th of every kernel-sized leaf's positions for every
device's agents, sketches those rows, and scatters the sums back
(``repro.kernels.grad_sketch.ops``). Each device must get exactly the
sketch of its own rows that one device computes alone, up to float32
summation order; the train step on an agent mesh must hold the
split's all-to-all inside ``ddal.sketch``, and a one-device step none.
"""
from __future__ import annotations

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.kernels.grad_sketch import ops as SK
from repro.kernels.grad_sketch.kernel import MIX_CONSTANTS

DIM = 128
# per-agent shapes, in pytree order: below the kernel's size (the
# device's own path); a leading axis that divides by D (split without
# a copy); a size that divides by no D (padded with zero positions); a
# leading axis of 3 (the padded flat leaf); a leaf whose share fills a
# kernel tile on 2 devices but not on 4
LEAVES = {"a_small": (37,), "b_rows": (8, 1100), "c_ragged": (4099,),
          "d_odd": (3, 2000), "e_norm": (2048,)}


def _grads(n, seed=0):
    rng = np.random.default_rng(seed)
    return {k: jnp.asarray(rng.normal(size=(n,) + s), jnp.float32)
            for k, s in LEAVES.items()}


def _worst_row_gap(got, want):
    got, want = np.asarray(got), np.asarray(want)
    return float(np.max(np.linalg.norm(got - want, axis=1)
                        / np.linalg.norm(want, axis=1)))


def _mesh(kind, devices):
    from repro.launch.mesh import make_mesh, make_pod_mesh
    if kind == "pods":
        return make_pod_mesh(2, devices=devices[:4])
    return make_mesh((2,), ("pod",), devices=devices[:2])


@pytest.mark.multi_device
@pytest.mark.parametrize("impl", ["xla", "pallas_interpret"])
@pytest.mark.parametrize("kind,local", [("pods", 1), ("pods", 2),
                                        ("flat", 1)])
def test_split_sketch_is_each_devices_own(multi_device, kind, local,
                                          impl):
    """(pod 2, agent 2) with one and two agents a device, and a flat
    2-device agent axis: each device's rows equal the one-device
    sketch of those rows. The seed is negative (its uint32 is past
    2³¹), and every split leaf's share starts past 2³²/P1, so the
    folded start wraps."""
    mesh = _mesh(kind, multi_device)
    axes = mesh.axis_names
    devices = mesh.devices.size
    n = local * devices
    grads = _grads(n)
    seed = jnp.int32(-123_457)
    offset, starts = 0, []
    for s in LEAVES.values():
        p = int(np.prod(s))
        if -(-p // devices) >= SK._MIN_KERNEL_SIZE:
            starts.append(offset + -(-p // devices))
        offset += p
    assert starts and min(starts) * MIX_CONSTANTS[0] >= 2**32

    want = SK.sketch_pytree(grads, seed, DIM, impl=impl)
    split = jax.jit(jax.shard_map(
        lambda g, s: SK.sketch_pytree(g, s, DIM, impl=impl), mesh=mesh,
        in_specs=(P(axes), P()), out_specs=P(axes), check_vma=False))
    got = split(grads, seed)
    assert _worst_row_gap(got, want) <= 1e-5
    hlo = split.lower(grads, seed).compile().as_text()
    assert "all-to-all" in hlo and "reduce-scatter" in hlo


def _toy_step(mesh, n):
    """A streaming step whose gradients are the agents' rows of
    ``_grads`` (the loss is linear in the weights), elastic, with the
    sketched estimator: the window sketch after a step is the sketch
    of those rows."""
    from repro import optim
    from repro.configs.base import GroupSpec
    from repro.core import relevance as REL
    from repro.core.sharded_ddal import (TrainState, init_knowledge,
                                         make_group_train_step)

    spec = GroupSpec(n_agents=n, threshold=1, minibatch=2,
                     knowledge_mode="streaming",
                     exchange_estimator="grad_cos+sketch",
                     relevance_sketch_dim=DIM, elastic=True)

    def loss_fn(params, batch):
        return sum(jnp.sum(params[k] * batch[k]) for k in params)

    opt = optim.sgd(0.0)
    params = jax.tree.map(jnp.zeros_like, _grads(n))
    state = TrainState(
        params=params, opt_state=jax.vmap(opt.init)(params),
        know=init_knowledge(params, rel=REL.init_relevance(n),
                            sketch_dim=DIM, alive=jnp.ones((n,), bool)),
        step=jnp.ones((), jnp.int32))   # past warm-up, not a share step
    step = jax.jit(make_group_train_step(None, spec, opt,
                                         loss_fn=loss_fn, mesh=mesh))
    return spec, state, step


@pytest.mark.multi_device
def test_dead_agent_row_stays_zero_on_the_mesh(multi_device):
    """Elastic membership masks the rows after the scatter: a dead
    agent's window sketch stays zero, and each live agent's is the
    one-device sketch of its gradient."""
    from repro.core import relevance as REL
    from repro.core.sharded_ddal import kill_agents

    mesh = _mesh("pods", multi_device)
    n = 4
    spec, state, step = _toy_step(mesh, n)
    state = kill_agents(state, jnp.arange(n) == 2)
    grads = _grads(n)
    out, _ = step(state, grads)
    sk = np.asarray(out.know.sk)
    np.testing.assert_array_equal(sk[2], 0.0)
    want = np.asarray(SK.sketch_pytree(
        grads, REL.fold_seed(spec.topology_seed, 1), DIM))
    live = [0, 1, 3]
    assert _worst_row_gap(sk[live], want[live]) <= 1e-5


def _all_to_all_scopes(hlo: str):
    """The ``op_name`` of every all-to-all of a compiled program."""
    return [m.group(1) for line in hlo.splitlines()
            if re.search(r"\ball-to-all(-start)?\(", line)
            for m in [re.search(r'op_name="([^"]*)"', line)] if m]


@pytest.mark.multi_device
def test_pods_step_splits_the_sketch(multi_device):
    """The small pods cell's train step on 4 devices holds the split's
    all-to-all, inside the sketch's scope."""
    from kanana_pods_small import driver, small

    cell, conf = small()
    parts = driver().build(conf, cell["traffic"], multi_device[:4])
    lo = hi = jnp.uint32(0)
    with jax.set_mesh(parts["mesh"]):
        state = parts["make_state"].eval_shape(lo, hi)
        batch = parts["batch"].eval_shape(lo, hi, jnp.int32(0))
        hlo = parts["step"].lower(state, batch).compile().as_text()
    scopes = _all_to_all_scopes(hlo)
    assert any("ddal.sketch" in s for s in scopes), scopes


def test_one_device_step_has_no_all_to_all():
    """Two agents on one device under ``vmap``, as on share2: the
    agents' axis spans no devices, so nothing is split."""
    mesh = None
    spec, state, step = _toy_step(mesh, 2)
    hlo = step.lower(state, _grads(2)).compile().as_text()
    assert not _all_to_all_scopes(hlo) and "all-to-all" not in hlo
