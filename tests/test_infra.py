"""Infrastructure tests: roofline HLO parser, checkpointing, data
pipeline determinism, serving engine, optimisers, sharding helpers."""
from __future__ import annotations

import os
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

# ----------------------------------------------------------------------
# roofline: HLO collective parsing
# ----------------------------------------------------------------------
from repro.launch.mesh import make_mesh
from repro.roofline.hlo import collective_bytes, count_ops

_FAKE_HLO = """
HloModule jit_step

fused_computation {
  %p0 = f32[128,256]{1,0} parameter(0)
  ROOT %t = f32[128,256]{1,0} tanh(%p0)
}

ENTRY %main {
  %x = f32[128,256]{1,0} parameter(0)
  %y = bf16[64]{0} parameter(1)
  %ar = f32[128,256]{1,0} all-reduce(%x), replica_groups={}, to_apply=%add
  %ag = bf16[1024]{0} all-gather(%y), dimensions={0}
  %cp = f32[128,256]{1,0} collective-permute(%ar), source_target_pairs={{0,1}}
  %ags = (bf16[64]{0}, bf16[1024]{0}) all-gather-start(%y), dimensions={0}
  %agd = bf16[1024]{0} all-gather-done(%ags)
  ROOT %out = f32[128,256]{1,0} add(%cp, %x)
}
"""


def test_collective_bytes_parsing():
    out = collective_bytes(_FAKE_HLO)
    f32_mat = 128 * 256 * 4
    assert out["all-reduce"] == f32_mat          # operand %x
    assert out["all-gather"] == 64 * 2 * 2       # two ops, operand %y
    assert out["collective-permute"] == f32_mat  # operand %ar
    assert out["total"] == 2 * f32_mat + 2 * 128
    assert count_ops(_FAKE_HLO, "all-gather") >= 2


def test_collective_bytes_tuple_form():
    """XLA's all-reduce combiner emits TUPLE all-reduces whose result
    types contain /*index=N*/ comments — parser-v2 regression test
    (these were silently skipped before, undercounting gradient ARs)."""
    hlo = """
ENTRY %m {
  %a = f32[64]{0} parameter(0)
  %b = f32[8,2]{1,0} parameter(1)
  %c = f32[4]{0} parameter(2)
  %d = f32[4]{0} parameter(3)
  %e = f32[4]{0} parameter(4)
  %f = f32[4]{0} parameter(5)
  %ar = (f32[64]{0}, f32[8,2]{1,0}, f32[4]{0}, f32[4]{0}, f32[4]{0}, /*index=5*/f32[4]{0}) all-reduce(%a, %b, %c, %d, %e, %f), replica_groups={}
  ROOT %t = f32[64]{0} get-tuple-element(%ar), index=0
}
"""
    out = collective_bytes(hlo)
    want = (64 + 16 + 4 * 4) * 4
    assert out["all-reduce"] == want, out


def test_collective_bytes_real_lowering():
    """Parse a genuinely compiled module with a known all-reduce."""
    mesh = make_mesh((1,), ("m",))
    x = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    f = jax.jit(lambda a: a.sum(), in_shardings=(
        jax.sharding.NamedSharding(mesh,
                                   jax.sharding.PartitionSpec("m")),))
    txt = f.lower(x).compile().as_text()
    out = collective_bytes(txt)      # 1-device: no collectives expected
    assert out["total"] >= 0


# ----------------------------------------------------------------------
# roofline: model FLOPs / param counting
# ----------------------------------------------------------------------
def test_active_params_moe_smaller_than_total():
    from repro.configs import get_arch_config
    from repro.roofline import active_param_count, param_count
    cfg = get_arch_config("qwen3-moe-30b-a3b").reduced()
    assert active_param_count(cfg) < param_count(cfg)

    dense = get_arch_config("llama3.2-3b").reduced()
    assert active_param_count(dense) == param_count(dense)


def test_roofline_terms():
    from repro.configs.base import ShapeConfig
    from repro.roofline import analyze
    shape = ShapeConfig("t", 128, 4, "train")
    r = analyze("a", shape, "2x2", 4,
                {"flops": 4e12, "bytes accessed": 8e9},
                {"all-reduce": 1e9, "total": 1e9}, mflops=2e12)
    assert r.t_compute == 4e12 / (4 * 197e12)
    assert r.t_memory == 8e9 / (4 * 819e9)
    assert r.t_collective == 1e9 / (4 * 50e9)
    assert r.dominant == "compute"
    assert 0 < r.useful_ratio < 1


# ----------------------------------------------------------------------
# checkpoint
# ----------------------------------------------------------------------
def test_checkpoint_roundtrip_nested():
    from repro.checkpoint import save, restore
    from repro.checkpoint.npz import restore_step
    tree = {"a": jnp.arange(6, dtype=jnp.int32).reshape(2, 3),
            "b": [jnp.ones(4, jnp.bfloat16), {"c": jnp.zeros(())}]}
    path = os.path.join(tempfile.mkdtemp(), "ck.npz")
    save(path, tree, step=42)
    back = restore(path, jax.eval_shape(lambda: tree))
    jax.tree.map(lambda x, y: np.testing.assert_array_equal(
        np.asarray(x, np.float32), np.asarray(y, np.float32)),
        tree, back)
    assert restore_step(path) == 42


def test_checkpoint_shape_mismatch_raises():
    from repro.checkpoint import save, restore
    path = os.path.join(tempfile.mkdtemp(), "ck.npz")
    save(path, {"a": jnp.ones((2, 2))})
    with pytest.raises(ValueError):
        restore(path, {"a": jax.ShapeDtypeStruct((3, 2), jnp.float32)})


# ----------------------------------------------------------------------
# data pipeline
# ----------------------------------------------------------------------
def test_stream_determinism_and_agent_identity():
    from repro.configs import get_arch_config
    from repro.configs.base import ShapeConfig
    from repro.data import StreamSpec, make_agent_batch
    cfg = get_arch_config("llama3.2-3b").reduced()
    sh = ShapeConfig("t", 64, 2, "train")
    spec = StreamSpec(seed=7)
    a = make_agent_batch(cfg, sh, spec, 0, 3)
    b = make_agent_batch(cfg, sh, spec, 0, 3)
    np.testing.assert_array_equal(np.asarray(a["tokens"]),
                                  np.asarray(b["tokens"]))
    c = make_agent_batch(cfg, sh, spec, 1, 3)
    assert not np.array_equal(np.asarray(a["tokens"]),
                              np.asarray(c["tokens"]))
    d = make_agent_batch(cfg, sh, spec, 0, 4)
    assert not np.array_equal(np.asarray(a["tokens"]),
                              np.asarray(d["tokens"]))


def test_stream_matches_input_specs():
    from repro.configs import ARCH_IDS, get_arch_config
    from repro.configs.base import ShapeConfig
    from repro.data import StreamSpec, make_agent_batch
    from repro.models import input_specs
    sh = ShapeConfig("t", 32, 2, "train")
    for aid in ARCH_IDS:
        cfg = get_arch_config(aid).reduced()
        specs = input_specs(cfg, sh)
        batch = make_agent_batch(cfg, sh, StreamSpec(), 0, 0)
        assert set(batch) == set(specs), aid
        for k, v in specs.items():
            assert batch[k].shape == v.shape, (aid, k)
            assert batch[k].dtype == v.dtype, (aid, k)


def test_musicgen_delay_pattern():
    """Audio stream applies the MusicGen delay pattern: codebook c is
    right-shifted by c frames; pad positions carry no loss."""
    from repro.configs import get_arch_config
    from repro.configs.base import ShapeConfig
    from repro.data import StreamSpec, make_agent_batch
    cfg = get_arch_config("musicgen-medium").reduced()
    b = make_agent_batch(cfg, ShapeConfig("t", 32, 2, "train"),
                         StreamSpec(), 0, 0)
    t = np.asarray(b["tokens"])
    l = np.asarray(b["labels"])
    for c in range(cfg.n_codebooks):
        assert (t[:, c, :c] == 0).all()
        assert (l[:, c, :c] == -100).all()
        assert (l[:, c, c:] == t[:, c, c:]).all()


def test_markov_stream_is_learnable():
    """A tiny model on the markov stream beats the uniform floor."""
    from repro.data.synthetic import StreamSpec, _markov_tokens
    spec = StreamSpec(seed=0, n_states=16, branch=2)
    toks = _markov_tokens(spec, 64, 0, 0, 4, 256)
    # bigram entropy of a branch-2 chain ≤ log(2) < log(16)
    joint = {}
    t = np.asarray(toks)
    for row in t:
        for x, y in zip(row[:-1], row[1:]):
            joint[(int(x), int(y))] = joint.get((int(x), int(y)), 0) + 1
    # every state has at most `branch` successors
    succ = {}
    for (x, y) in joint:
        succ.setdefault(x, set()).add(y)
    assert max(len(s) for s in succ.values()) <= spec.branch


# ----------------------------------------------------------------------
# serving
# ----------------------------------------------------------------------
def test_serve_batches_packing():
    from repro.serving import serve_batches
    reqs = [[1, 2, 3], [4], [5, 6], [7, 8, 9, 10], [11]]
    batches = serve_batches(reqs, 2)
    assert len(batches) == 3
    toks, lens = batches[0]
    assert toks.shape[0] == 2 and int(lens[0]) == 3 and int(lens[1]) == 1
    # tail batch padded with a dummy request
    toks, lens = batches[-1]
    assert toks.shape[0] == 2


def test_serve_engine_greedy_deterministic():
    from repro.configs import get_arch_config
    from repro.models import get_model
    from repro.serving import ServeConfig, ServeEngine
    cfg = get_arch_config("granite-3-8b").reduced()
    model = get_model(cfg)
    params = model.init(cfg, jax.random.PRNGKey(0))
    eng = ServeEngine(cfg, params, ServeConfig(max_len=32,
                                               max_new_tokens=6))
    toks = jnp.asarray([[1, 2, 3, 4]], jnp.int32)
    lens = jnp.asarray([4], jnp.int32)
    o1 = eng.generate(toks, lens)
    o2 = eng.generate(toks, lens)
    np.testing.assert_array_equal(np.asarray(o1), np.asarray(o2))


# ----------------------------------------------------------------------
# optimisers
# ----------------------------------------------------------------------
def test_adamw_converges_on_quadratic():
    from repro.optim import adamw
    opt = adamw(0.1)
    params = {"w": jnp.asarray([5.0, -3.0])}
    state = opt.init(params)
    for i in range(200):
        g = {"w": params["w"]}          # ∇ of ½‖w‖²
        params, state = opt.update(g, state, params, i)
    assert float(jnp.abs(params["w"]).max()) < 1e-2


def test_global_norm_clip():
    from repro.common.pytree import global_norm_clip, tree_norm
    g = {"a": jnp.full((4,), 10.0)}
    clipped, norm = global_norm_clip(g, 1.0)
    np.testing.assert_allclose(float(norm), 20.0)
    np.testing.assert_allclose(float(tree_norm(clipped)), 1.0,
                               rtol=1e-4)


def test_tree_sq_norm_of_a_large_leaf():
    """An embedding-sized leaf's squared norm to float32 rounding, under
    vmap over agents as the train step's optimizer takes it (a flat
    float32 dot read 2% short here)."""
    from repro.common.pytree import tree_sq_norm
    x = np.random.default_rng(0).standard_normal((2, 1 << 23)).astype(
        np.float32)
    want = (x.astype(np.float64) ** 2).sum(axis=1)
    got = jax.jit(jax.vmap(lambda v: tree_sq_norm({"w": v})))(x)
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5)


# ----------------------------------------------------------------------
# sharding helpers
# ----------------------------------------------------------------------
def test_sanitize_partition_specs():
    from jax.sharding import PartitionSpec as P
    from repro.launch.dryrun_lib import _sanitize
    mesh = make_mesh((1,), ("model",))

    class FakeMesh:
        shape = {"model": 16, "data": 4}
    spec = _sanitize(FakeMesh, P(None, "model"), (10, 8))
    assert spec == P(None, None)          # 8 % 16 != 0 → dropped
    spec = _sanitize(FakeMesh, P("data", "model"), (8, 32))
    assert spec == P("data", "model")
    spec = _sanitize(FakeMesh, P(("data", "model"),), (64, 3))
    assert spec == P(("data", "model"), None)


def test_cache_partition_specs_cover_all_archs():
    from jax.sharding import PartitionSpec as P
    from repro.configs import ARCH_IDS, get_arch_config
    from repro.configs.base import ShapeConfig
    from repro.launch.shardings import cache_partition_specs
    from repro.models import cache_specs
    sh = ShapeConfig("d", 64, 2, "decode")
    for aid in ARCH_IDS:
        cfg = get_arch_config(aid).reduced()
        specs = cache_partition_specs(cfg, sh, "data")
        shapes = cache_specs(cfg, sh)
        flat_specs = jax.tree.leaves(
            specs, is_leaf=lambda x: isinstance(x, P))
        flat_shapes = jax.tree.leaves(shapes)
        assert len(flat_specs) == len(flat_shapes), aid


def test_axis_rules_scoping():
    from repro.common.sharding import axis_rules, get_rules, logical_spec
    from jax.sharding import PartitionSpec as P
    assert get_rules() is None
    with axis_rules({"batch": "data"}):
        assert logical_spec("batch", None) == P("data", None)
    assert get_rules() is None
