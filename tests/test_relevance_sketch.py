"""Sketched streaming relevance (ISSUE 4): the grad_sketch kernel vs
its jnp oracle, the streaming pytree pass vs the dense flatten
projection, (seed, round) determinism, the d → error contraction
property, the exact-path (sketch_dim = 0) equivalence oracle, and the
wavg-kernel interpret auto-selection regression."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given
from hypothesis import strategies as st

from repro.configs.base import GroupSpec
from repro.core import DDAL, relevance as REL
from repro.kernels.grad_sketch import ops as SK
from repro.kernels.grad_sketch import ref as SKref
from repro.kernels.grad_sketch.kernel import (hoisted_sign_block,
                                             sign_block, sketch_flat)


def _tree(n, seed=0, sizes=(37, 3200, 5000)):
    rng = np.random.default_rng(seed)
    return {f"l{i}": jnp.asarray(rng.normal(size=(n, p)), jnp.float32)
            for i, p in enumerate(sizes)}


# ----------------------------------------------------------------------
# kernel vs oracle
# ----------------------------------------------------------------------
@pytest.mark.parametrize("n,p,d,offset", [
    (8, 1024, 128, 11), (3, 4097, 256, 11), (8, 1000, 128, 11),
    (16, 2048, 384, 11),
    # late leaves: large offsets, ragged last tile
    (2, 5000, 256, 2_000_000_000), (4, 3000, 128, 123_456_789)])
def test_sketch_kernel_matches_ref(n, p, d, offset):
    """Pallas kernel (interpret) ≡ one-shot jnp projection: same sign
    stream, only tile-accumulation order differs."""
    G = jnp.asarray(np.random.default_rng(n * p).normal(size=(n, p)),
                    jnp.float32)
    got = sketch_flat(G, jnp.int32(7), d, offset=offset, interpret=True)
    want = SKref.sketch_flat(G, jnp.int32(7), d, offset=offset)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-3)


def test_sketch_xla_path_matches_ref():
    """Tiled XLA fallback walks blocks of the position axis but
    reproduces the one-shot projection (same positional signs)."""
    G = jnp.asarray(np.random.default_rng(0).normal(size=(4, 9000)),
                    jnp.float32)
    got = SK._xla_sketch_flat(G, jnp.int32(3), 192, offset=5,
                              block=1024)
    want = SKref.sketch_flat(G, jnp.int32(3), 192, offset=5)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-3)


@pytest.mark.parametrize("d", [128, 256])
@pytest.mark.parametrize("start", [0, 123_456_789, np.uint32(2**31 + 5)])
@pytest.mark.parametrize("seed", [0, 7, 0x7FFFFFFF, -1])  # -1: 0xFFFFFFFF
def test_hoisted_sign_block_is_sign_block(seed, start, d):
    """The kernel's generator (shape-only base + one scalar, shortened
    mix, sign bit placed into 1.0) gives ``sign_block``'s signs bit for
    bit, also where seed and position products wrap in uint32."""
    want = np.asarray(sign_block(np.int32(seed), start, 1024, d))
    got = np.asarray(hoisted_sign_block(np.int32(seed), start, 1024, d))
    assert got.dtype == want.dtype == np.float32
    np.testing.assert_array_equal(got.view(np.uint32),
                                  want.view(np.uint32))


def test_sign_block_positional_and_balanced():
    """Signs are a pure function of (seed, position, dim): tiling the
    position axis changes nothing, and the stream is ±1-balanced."""
    whole = np.asarray(sign_block(jnp.int32(5), 0, 4096, 64))
    lo = np.asarray(sign_block(jnp.int32(5), 0, 1000, 64))
    hi = np.asarray(sign_block(jnp.int32(5), 1000, 3096, 64))
    np.testing.assert_array_equal(whole, np.concatenate([lo, hi]))
    assert set(np.unique(whole).tolist()) == {-1.0, 1.0}
    assert abs(whole.mean()) < 0.02


@pytest.mark.parametrize("impl", ["xla", "pallas_interpret"])
def test_sketch_pytree_equals_flatten_projection(impl):
    """The streaming leaf-by-leaf pass ≡ projecting the (n, P) concat
    (which it exists to avoid): offsets advance by true leaf size."""
    tree = _tree(6)
    got = SK.sketch_pytree(tree, jnp.int32(1), 256, impl=impl)
    want = SKref.sketch_oracle(tree, jnp.int32(1), 256)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-3)


def test_sketch_linear_in_gradients():
    """sketch(a + b) == sketch(a) + sketch(b) for a shared seed — the
    property that lets the streaming trainer carry a window sketch
    instead of re-projecting its accumulators."""
    a, b = _tree(4, seed=1), _tree(4, seed=2)
    seed = jnp.int32(9)
    s_sum = SK.sketch_pytree(jax.tree.map(jnp.add, a, b), seed, 128)
    s_ab = (SK.sketch_pytree(a, seed, 128)
            + SK.sketch_pytree(b, seed, 128))
    np.testing.assert_allclose(np.asarray(s_sum), np.asarray(s_ab),
                               rtol=1e-4, atol=1e-3)


# ----------------------------------------------------------------------
# determinism + error contraction
# ----------------------------------------------------------------------
def test_sketch_deterministic_in_seed_and_round():
    tree = _tree(5)
    s1 = REL.sketch_cosine(tree, 128, REL.fold_seed(3, 7))
    s2 = REL.sketch_cosine(tree, 128, REL.fold_seed(3, 7))
    s3 = REL.sketch_cosine(tree, 128, REL.fold_seed(3, 8))
    s4 = REL.sketch_cosine(tree, 128, REL.fold_seed(4, 7))
    np.testing.assert_array_equal(np.asarray(s1), np.asarray(s2))
    assert (np.asarray(s1) != np.asarray(s3)).any()
    assert (np.asarray(s1) != np.asarray(s4)).any()


@given(st.integers(0, 2 ** 31 - 1))
def test_sketch_error_shrinks_with_dim(seed):
    """Mean |sketched − exact| cosine error contracts ~1/√d: a 64×
    dim gap leaves an 8× expected-error gap, far beyond fluctuation."""
    tree = _tree(8, seed=seed % 1000, sizes=(600, 900))
    exact = np.asarray(REL.grad_cosine(tree))
    off = ~np.eye(8, dtype=bool)

    def mean_err(d):
        sk = np.asarray(REL.sketch_cosine(
            tree, d, REL.fold_seed(seed, 0)))
        return np.abs(sk - exact)[off].mean()

    assert mean_err(512) < mean_err(8)


def test_sketch_cosine_contract():
    """Same contract as grad_cosine: unit diagonal, [-1, 1], and a
    zero gradient row reads as cosine 0 against everyone."""
    tree = {"w": jnp.asarray(
        np.concatenate([np.random.default_rng(0).normal(size=(3, 4096)),
                        np.zeros((1, 4096))]), jnp.float32)}
    c = np.asarray(REL.sketch_cosine(tree, 256, jnp.int32(0)))
    np.testing.assert_allclose(np.diag(c), 1.0)
    assert (c >= -1.0).all() and (c <= 1.0).all()
    np.testing.assert_allclose(c[3, :3], 0.0, atol=1e-6)


# ----------------------------------------------------------------------
# exact path (sketch_dim = 0) equivalence oracle
# ----------------------------------------------------------------------
# the seed's exact estimator — (n, P) flatten concat + one normalised
# Gram, the memory spike the per-leaf path fixes; single shared
# definition with the benchmark's bitwise gate
_pre_pr_grad_cosine = REL.flatten_cosine


def test_exact_path_bitwise_on_single_leaf():
    """Single-leaf pytrees run the identical contraction as the
    pre-PR flatten estimator — bitwise, including through the
    update_relevance dispatch with sketch_dim=0."""
    tree = {"w": jnp.asarray(
        np.random.default_rng(3).normal(size=(6, 20000)), jnp.float32)}
    np.testing.assert_array_equal(
        np.asarray(REL.grad_cosine(tree)),
        np.asarray(_pre_pr_grad_cosine(tree)))
    rel0 = REL.init_relevance(6)
    got = REL.update_relevance(rel0, tree, "grad_cos", 0.7,
                               sketch_dim=0)
    want = REL.ema_update(
        rel0, REL.to_relevance(_pre_pr_grad_cosine(tree)), 0.7)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_exact_path_matches_flatten_oracle_multi_leaf():
    """Multi-leaf trees only reassociate the Σ over leaves — the
    per-leaf streaming Gram stays within ulps of the flatten oracle
    and never builds the (n, P) concat (pinned by the benchmark's
    jaxpr peak-intermediate gate)."""
    tree = _tree(7, seed=11)
    np.testing.assert_allclose(
        np.asarray(REL.grad_cosine(tree)),
        np.asarray(_pre_pr_grad_cosine(tree)), rtol=1e-6, atol=1e-6)


def test_update_relevance_sketch_dispatch():
    """sketch_dim > 0 routes through the sketched estimator (close to
    but distinct from the exact path); uniform stays the identity."""
    tree = _tree(4, seed=5)
    rel0 = REL.init_relevance(4)
    exact = REL.update_relevance(rel0, tree, "grad_cos", 0.0)
    sk = REL.update_relevance(rel0, tree, "grad_cos", 0.0,
                              sketch_dim=1024, seed=1, rnd=2)
    assert (np.asarray(sk) != np.asarray(exact)).any()
    np.testing.assert_allclose(np.asarray(sk), np.asarray(exact),
                               atol=0.2)
    out = REL.update_relevance(rel0, tree, "uniform", 0.5,
                               sketch_dim=64)
    assert out is rel0


def test_relevance_exchange_bytes_accounting():
    """Sketched relevance moves (A, d) rows across the mesh; the
    exact Gram moves the (A, P) accumulator rows — flat in |params|
    only for the sketch."""
    from repro.core.pod_dispatch import relevance_exchange_bytes
    assert relevance_exchange_bytes(8, 10**6, 0) == 8 * 10**6 * 4
    assert relevance_exchange_bytes(8, 10**6, 256) == 8 * 256 * 4
    assert (relevance_exchange_bytes(8, 10**6, 256)
            == relevance_exchange_bytes(8, 10**9, 256))


def test_group_spec_sketch_validation():
    with pytest.raises(ValueError, match="relevance_sketch_dim"):
        GroupSpec(n_agents=4, relevance_mode="grad_cos",
                  relevance_sketch_dim=-1)
    with pytest.raises(ValueError, match="grad_cos"):
        GroupSpec(n_agents=4, relevance_mode="uniform",
                  relevance_sketch_dim=64)
    spec = GroupSpec(n_agents=4, relevance_mode="grad_cos",
                     relevance_sketch_dim=256)
    assert spec.relevance_sketch_dim == 256


# ----------------------------------------------------------------------
# integration: sketched relevance reaches eq. 4 in both trainers
# ----------------------------------------------------------------------
def test_ddal_sketch_separates_aligned_from_opposed():
    """The ring-buffer DDAL loop with sketched relevance learns the
    same aligned ≫ opposed split as the exact estimator (the sketch
    dim is large enough that the decision survives the noise)."""
    n = 4
    spec = GroupSpec(n_agents=n, threshold=0, minibatch=1_000,
                     m_pieces=8, relevance_mode="grad_cos",
                     relevance_ema=0.5, relevance_sketch_dim=512)

    def gen(state, key):
        del key
        return ({"w": state["sign"] * jnp.ones_like(state["w"])},
                {}, state)

    ddal = DDAL(spec, gen, lambda s, g: s, lambda s: {"w": s["w"]})
    gs = ddal.init({"w": jnp.zeros((n, 4096)),
                    "sign": jnp.asarray([1.0, 1.0, -1.0, -1.0]
                                        )[:, None]})
    step = jax.jit(ddal.epoch_step)
    for e in range(6):
        gs, _ = step(gs, jax.random.split(jax.random.PRNGKey(e), n))
    rel = np.asarray(gs.relevance)
    assert rel[0, 1] > 0.8
    assert rel[0, 2] < 0.3


def test_streaming_sketch_carry_and_reset():
    """The streaming trainer carries the (A, d) window sketch: it is
    the sketch of the rg accumulator at share time (linearity, fp32
    knowledge dtype), it resets with the window, and the learned rel
    moves off the prior."""
    from repro import optim
    from repro.core.sharded_ddal import (
        TrainState,
        init_knowledge,
        make_group_train_step,
    )

    n, d, mb = 4, 128, 3
    spec = GroupSpec(n_agents=n, threshold=0, minibatch=mb,
                     relevance_mode="grad_cos", relevance_ema=0.5,
                     relevance_sketch_dim=d,
                     knowledge_mode="streaming")

    def loss_fn(params, batch):
        return jnp.sum((params["w"] - batch["t"]) ** 2)

    opt = optim.sgd(0.05)
    key = jax.random.PRNGKey(0)
    params = {"w": jax.random.normal(key, (n, 64))}
    # build the state by hand (toy loss needs no ArchConfig)
    state = TrainState(
        params=params,
        opt_state=jax.vmap(opt.init)(params),
        know=init_knowledge(params, rel=REL.init_relevance(n),
                            sketch_dim=d),
        step=jnp.zeros((), jnp.int32))
    assert state.know.sk.shape == (n, d)
    np.testing.assert_array_equal(np.asarray(state.know.sk), 0.0)

    step_fn = jax.jit(make_group_train_step(None, spec, opt,
                                            loss_fn=loss_fn))
    batch = {"t": jnp.asarray(np.random.default_rng(0).normal(
        size=(n, 64)), jnp.float32)}
    # step 0 shares immediately (threshold 0, 0 % mb == 0) and resets;
    # steps 1..mb-1 then accumulate — sk must equal sketch(rg)
    st = state
    for _ in range(mb):
        st, m = step_fn(st, batch)
    seed_r = REL.fold_seed(spec.topology_seed,
                           (st.step - 1 + mb) // mb)
    want = SK.sketch_pytree(st.know.rg, seed_r, d)
    np.testing.assert_allclose(np.asarray(st.know.sk),
                               np.asarray(want), rtol=1e-4, atol=1e-3)
    assert float(jnp.abs(st.know.sk).max()) > 0
    # the share step consumes the sketch and resets the window
    st2, m = step_fn(st, batch)
    assert int(m["shared"]) == 1
    np.testing.assert_array_equal(np.asarray(st2.know.sk), 0.0)
    rel = np.asarray(st2.know.rel)
    assert not np.allclose(rel, 1.0)
    assert (rel > 0).all() and (rel <= 1.0 + 1e-6).all()


# ----------------------------------------------------------------------
# satellite: wavg kernel interpret auto-selection
# ----------------------------------------------------------------------
def test_weighted_average_kernel_auto_interpret():
    """use_kernel=True must run on CPU rigs without hardcoding
    interpret=True at the call site: the wrapper auto-selects
    interpret off-TPU, and the result matches the jnp path."""
    from repro.core import knowledge as K
    from repro.kernels.ddal_wavg.ops import resolve_interpret

    assert resolve_interpret(None) == (jax.default_backend() != "tpu")
    assert resolve_interpret(True) is True
    assert resolve_interpret(False) is False

    params = {"w": jnp.zeros((9000,), jnp.float32)}
    store = K.make_store(params, m=4)
    for j in range(4):
        piece = {"w": jnp.full((9000,), float(j + 1))}
        store = K.append(store, piece, T=float(j + 1), R=1.0)
    g_kernel, w_kernel = jax.jit(
        lambda s: K.weighted_average(s, use_kernel=True))(store)
    g_ref, w_ref = K.weighted_average(store, use_kernel=False)
    np.testing.assert_allclose(np.asarray(g_kernel["w"]),
                               np.asarray(g_ref["w"]),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(float(w_kernel), float(w_ref))


def test_tree_wavg_small_leaf_fallback_compiles_uninterpreted():
    """Leaves below one kernel tile take the jnp fallback, which must
    compile on CPU even with interpret=False (no Pallas involved) —
    the regression the hardcoded interpret=True was masking."""
    from repro.kernels.ddal_wavg import ops as wavg_ops
    from repro.kernels.ddal_wavg import ref as wavg_ref

    tree = {"a": jnp.ones((3, 17, 4)), "b": jnp.ones((3, 100))}
    w = jnp.asarray([0.2, 0.3, 0.5])
    got = jax.jit(
        lambda t, ww: wavg_ops.tree_wavg(t, ww, interpret=False))(
        tree, w)
    want = wavg_ref.tree_wavg(tree, w)
    jax.tree.map(lambda a, b: np.testing.assert_allclose(
        np.asarray(a), np.asarray(b), rtol=1e-6), got, want)
