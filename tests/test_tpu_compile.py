"""Compile every Pallas entry point for a described TPU v5e.

Interpret mode runs a kernel's logic on the CPU but never shows it to
the TPU compiler, which refuses what the interpreter accepts: block
shapes off the (8, 128) tiling, casts Mosaic lacks, too much VMEM.
These tests hand each kernel to that compiler at the widths the main
path uses (mamba2-780m's published widths for the model kernels) and
check that the executable holds the kernel (``tpu_custom_call``).
Nothing runs: a described chip has no memory to run on.

The topology is described inside a fixture, never at import: only one
process may load the TPU library at a time, and every pytest worker
imports every test file.
"""
from __future__ import annotations

import math
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.ddal_wavg.kernel import (fused_wavg_flat,
                                            fused_wavg_q_flat, wavg_flat)
from repro.kernels.flash_attention.kernel import flash_attention_bhsd
from repro.kernels.grad_sketch.kernel import sketch_flat
from repro.kernels.ssd_scan.kernel import ssd_intra_chunk_bchl

M, N = 4, 1 << 22            # eq. 4 pieces × flat plane elements
Q_BLOCK = 1024               # int8 scale block


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                          # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_persistent_cache():
    """A compile for a described chip is written to the persistent
    cache but cannot be read back without one (a warning, which the
    suite treats as an error)."""
    from jax.experimental.compilation_cache import compilation_cache
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


def _compile(one_chip, fn, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
            for s, d in shapes]
    hlo = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in hlo


META = [((M,), jnp.float32), ((M,), jnp.float32), ((M,), jnp.bool_)]


def test_wavg_flat(one_chip):
    _compile(one_chip, lambda G, w: wavg_flat(G, w, interpret=False),
             ((M, N), jnp.float32), ((M,), jnp.float32))


def test_fused_wavg_flat(one_chip):
    _compile(one_chip,
             lambda G, *m: fused_wavg_flat(G, *m, interpret=False),
             ((M, N), jnp.float32), *META)


def test_fused_wavg_q_flat(one_chip):
    _compile(one_chip,
             lambda Q, S, *m: fused_wavg_q_flat(Q, S, *m, Q_BLOCK,
                                                interpret=False),
             ((M, N), jnp.int8), ((M, N // Q_BLOCK), jnp.float32),
             *META)


# mamba2-780m's embedding leaf: 50,288 × 1,536 positions per agent
@pytest.mark.parametrize("p", [N, 50_288 * 1_536])
def test_sketch_flat(one_chip, p):
    _compile(one_chip,
             lambda G, seed: sketch_flat(G, seed, 256, interpret=False),
             ((2, p), jnp.float32), ((), jnp.int32))


# kanana-2-30b-a3b's expert leaf (4 layers × 8 held experts × 2,048 ×
# 768) split over a 2x2 host: each chip sketches a quarter of it for
# all four agents
EXPERT_LEAF = (4, 8, 2_048, 768)


def test_sketch_flat_every_agents_quarter(one_chip):
    _compile(one_chip,
             lambda G, seed: sketch_flat(G, seed, 256, interpret=False),
             ((4, math.prod(EXPERT_LEAF) // 4), jnp.float32),
             ((), jnp.int32))


def test_split_sketch_on_a_2x2_mesh(topo):
    """The window sketch of one agent a chip under ``shard_map`` on the
    described (pod 2, agent 2) mesh, over the embedding and an expert
    leaf: the all-to-all, the kernel on every agent's share of the
    positions, and the reduce-scatter that returns each chip its
    agent's row (at 4 KiB the compiler makes it an all-reduce and a
    slice)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.kernels.grad_sketch.ops import sketch_pytree
    from repro.launch.mesh import make_pod_mesh

    mesh = make_pod_mesh(2, devices=topo.devices)
    axes = mesh.axis_names
    grads = {k: jax.ShapeDtypeStruct((4,) + s, jnp.float32,
                                     sharding=NamedSharding(mesh, P(axes)))
             for k, s in (("embed", (16_032, 2_048)),
                          ("experts", EXPERT_LEAF))}
    seed = jax.ShapeDtypeStruct((), jnp.int32,
                                sharding=NamedSharding(mesh, P()))
    sketch = jax.shard_map(lambda g, s: sketch_pytree(g, s, 256),
                           mesh=mesh, in_specs=(P(axes), P()),
                           out_specs=P(axes), check_vma=False)
    hlo = jax.jit(sketch).lower(grads, seed).compile().as_text()
    assert "all-to-all" in hlo and "tpu_custom_call" in hlo
    assert "reduce-scatter" in hlo or "all-reduce" in hlo


def test_flash_attention_bhsd(one_chip):
    qkv = ((1, 32, 4096, 128), jnp.bfloat16)
    _compile(one_chip,
             lambda q, k, v: flash_attention_bhsd(q, k, v,
                                                  interpret=False),
             qkv, qkv, qkv)


def test_ssd_intra_chunk_bchl(one_chip):
    # mamba2-780m: 48 heads of 64, d_state 128, chunk 256
    bn, h, l, p, n = 4, 48, 256, 64, 128
    _compile(one_chip,
             lambda *a: ssd_intra_chunk_bchl(*a, interpret=False),
             ((bn, h, l, p), jnp.float32), ((bn, h, l), jnp.float32),
             ((bn, h, l), jnp.float32), ((bn, h, l, n), jnp.float32),
             ((bn, h, l, n), jnp.float32))


def _held_experts_grad(one_chip, agents=None):
    """kanana-2-30b-a3b's expert layer, forward and backward, at its
    published widths with 8 of 128 experts held, lowered for the
    described chip; under ``vmap`` over ``agents`` agents if given."""
    import dataclasses

    from repro.configs import get_arch_config
    from repro.models.moe import moe_apply

    cfg = get_arch_config("kanana-2-30b-a3b")
    cfg = cfg.with_(moe=dataclasses.replace(cfg.moe, n_held=8))
    E, F, Ne = cfg.d_model, cfg.moe.expert_ff, cfg.moe.n_experts
    lead = () if agents is None else (agents,)
    shapes = {"router": ((E, Ne), jnp.float32),
              "router_bias": ((Ne,), jnp.float32),
              "experts": {k: ((8,) + s, jnp.float32) for k, s in (
                  ("w_gate", (E, F)), ("w_up", (E, F)),
                  ("w_down", (F, E)))},
              "shared": {k: (s, jnp.float32) for k, s in (
                  ("w_gate", (E, 2 * F)), ("w_up", (E, 2 * F)),
                  ("w_down", (2 * F, E)))}}
    params = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(lead + s[0], s[1],
                                       sharding=one_chip), shapes,
        is_leaf=lambda s: isinstance(s, tuple))
    x = jax.ShapeDtypeStruct(lead + (1, 2048, E), jnp.bfloat16,
                             sharding=one_chip)

    def loss(p, x):
        out, _, stats = moe_apply(cfg, p, x)
        return jnp.sum(out.astype(jnp.float32) ** 2), stats
    grad = jax.grad(loss, has_aux=True)
    return jax.jit(grad if agents is None else jax.vmap(grad)).lower(
        params, x)


def test_held_experts_layer(one_chip):
    """The held experts' dropless grouped products are the TPU's
    ragged-dot kernel."""
    hlo = _held_experts_grad(one_chip).compile().as_text()
    assert "tpu_custom_call" in hlo


def test_held_experts_take_no_agent_axis(one_chip):
    """The TPU compiler takes a ragged dot without batch dimensions
    only, so the layer under ``vmap``, even over one agent, does not
    compile: the train step runs a device's one agent unbatched
    (``core/sharded_ddal._per_agent_map``)."""
    with pytest.raises(Exception, match="number of batch dimensions"):
        _held_experts_grad(one_chip, agents=1).compile()
