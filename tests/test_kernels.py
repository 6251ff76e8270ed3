"""Pallas kernel validation: shape/dtype sweeps against the pure-jnp
oracles, executed in interpret mode on CPU (deliverable c)."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

# ----------------------------------------------------------------------
# ddal_wavg — the paper's eq. 4 contraction
# ----------------------------------------------------------------------
from repro.kernels.ddal_wavg import ops as wavg_ops
from repro.kernels.ddal_wavg import ref as wavg_ref


@pytest.mark.parametrize("m,n", [(1, 128), (3, 100), (8, 8192),
                                 (5, 20_000), (16, 4_097)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_wavg_flat(m, n, dtype):
    key = jax.random.PRNGKey(m * 1000 + n)
    G = jax.random.normal(key, (m, n), jnp.float32).astype(dtype)
    w = jax.random.uniform(jax.random.fold_in(key, 1), (m,))
    got = wavg_ops.wavg(G, w, interpret=True)
    want = wavg_ref.wavg(G, w)
    tol = 1e-6 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=tol, atol=tol)


def test_wavg_tree():
    key = jax.random.PRNGKey(0)
    tree = {"a": jax.random.normal(key, (4, 17, 33)),
            "b": jax.random.normal(key, (4, 12_000)),
            "c": {"d": jax.random.normal(key, (4, 8))}}
    w = jax.random.uniform(key, (4,))
    got = wavg_ops.tree_wavg(tree, w, interpret=True)
    want = wavg_ref.tree_wavg(tree, w)
    jax.tree.map(lambda a, b: np.testing.assert_allclose(
        np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-5), got, want)


def test_wavg_zero_weights():
    G = jnp.ones((3, 256))
    w = jnp.zeros((3,))
    got = wavg_ops.wavg(G, w, interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.zeros(256))


# ----------------------------------------------------------------------
# ddal_wavg — fused eq. 4 share step (+ int8-quantized planes)
# ----------------------------------------------------------------------
from repro.core.weighting import eq4_weights
from repro.common.pytree import tree_weighted_sum


def _share_meta(m, seed=0):
    kT, kR = jax.random.split(jax.random.PRNGKey(seed))
    T = jnp.abs(jax.random.normal(kT, (m,))) + 0.1
    R = jnp.abs(jax.random.normal(kR, (m,))) + 0.1
    valid = (jnp.arange(m) != 1) if m > 1 else jnp.ones((m,), bool)
    return T, R, valid


def _legacy_share(G, T, R, valid):
    w = eq4_weights(T, R, valid)
    return tree_weighted_sum(G, w), jnp.sum(w)


def _count_pallas_calls(fn, *args):
    hits = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if "pallas" in eqn.primitive.name:
                hits.append(eqn)
            for p in eqn.params.values():
                sub = getattr(p, "jaxpr", p if hasattr(p, "eqns")
                              else None)
                if sub is not None:
                    walk(sub)

    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return len(hits)


@pytest.mark.parametrize("m,n", [(1, 256), (4, 8192), (6, 100_000),
                                 (8, 262_144), (3, 8_193)])
def test_fused_wavg_xla_bitwise_vs_multi_op(m, n):
    """The fused XLA entry — what CPU/GPU trainers compile — must be
    bit-identical to the historical eq4_weights + tree_weighted_sum
    path at quantization-off."""
    G = jax.random.normal(jax.random.PRNGKey(n), (m, n), jnp.float32)
    T, R, valid = _share_meta(m, seed=n)
    want_g, want_w = _legacy_share(G, T, R, valid)
    got_g, got_w = wavg_ops.fused_wavg(G, T, R, valid, impl="xla")
    np.testing.assert_array_equal(np.asarray(got_g),
                                  np.asarray(want_g))
    assert float(got_w) == float(want_w)


def test_tree_fused_wavg_xla_bitwise_vs_multi_op():
    """Tree-wise: mixed small/large leaves, arbitrary ranks — still
    bitwise, including the (ḡ, Σw) pair the store combiner returns."""
    key = jax.random.PRNGKey(7)
    tree = {"emb": jax.random.normal(key, (5, 300, 65)),
            "head": {"w": jax.random.normal(key, (5, 33)),
                     "b": jax.random.normal(key, (5,))}}
    T, R, valid = _share_meta(5)
    want_g, want_w = _legacy_share(tree, T, R, valid)
    got_g, got_w = wavg_ops.tree_fused_wavg(tree, T, R, valid,
                                            impl="xla")
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(
        np.asarray(a), np.asarray(b)), got_g, want_g)
    assert float(got_w) == float(want_w)


@pytest.mark.parametrize("m,n", [(4, 8192), (6, 100_000)])
def test_fused_wavg_pallas_interpret_matches_oracle(m, n):
    G = jax.random.normal(jax.random.PRNGKey(m), (m, n), jnp.float32)
    T, R, valid = _share_meta(m)
    want_g, want_w = _legacy_share(G, T, R, valid)
    got_g, got_w = wavg_ops.fused_wavg(G, T, R, valid, impl="pallas",
                                       interpret=True)
    np.testing.assert_allclose(np.asarray(got_g), np.asarray(want_g),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(float(got_w), float(want_w), rtol=1e-6)


@pytest.mark.parametrize("qb", [128, 512, 2048, 8192])
def test_quantize_roundtrip_error_bound(qb):
    """int8 block quantization: the roundtrip error of every element
    is ≤ half its block's scale (the analytic bound the eq. 4
    accuracy gate builds on), and the wire dtypes/shapes hold."""
    n = 20_000
    G = jax.random.normal(jax.random.PRNGKey(qb), (3, n), jnp.float32)
    G = G * jnp.exp(jax.random.normal(jax.random.PRNGKey(1),
                                      (3, n)))     # mixed magnitudes
    Q, S = wavg_ref.quantize_flat(G, qb)
    assert Q.dtype == jnp.int8 and Q.shape == G.shape
    assert S.shape == (3, -(-n // qb))
    back = wavg_ref.dequantize_flat(Q, S, qb)
    err = jnp.abs(back - G)
    bound = jnp.repeat(S / 2.0, qb, axis=-1)[:, :n] + 1e-9
    assert bool(jnp.all(err <= bound)), (
        f"max excess {float(jnp.max(err - bound))}"
    )


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_fused_wavg_q_matches_dequantized_oracle(impl):
    """Both quantized entries compute eq. 4 over the *dequantized*
    planes — bitwise for XLA, kernel tolerance for Pallas."""
    m, n, qb = 5, 100_000, 512
    G = jax.random.normal(jax.random.PRNGKey(3), (m, n), jnp.float32)
    T, R, valid = _share_meta(m, seed=3)
    Q, S = wavg_ref.quantize_flat(G, qb)
    want_g, want_w = wavg_ref.fused_wavg_q(Q, S, T, R, valid, qb)
    got_g, got_w = wavg_ops.fused_wavg_q(Q, S, T, R, valid, qb,
                                         impl=impl, interpret=True)
    if impl == "xla":
        np.testing.assert_array_equal(np.asarray(got_g),
                                      np.asarray(want_g))
    else:
        np.testing.assert_allclose(np.asarray(got_g),
                                   np.asarray(want_g),
                                   rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(float(got_w), float(want_w), rtol=1e-6)


def test_fused_wavg_q_rejects_bad_block():
    Q = jnp.zeros((2, 256), jnp.int8)
    S = jnp.zeros((2, 2), jnp.float32)
    T, R, valid = _share_meta(2)
    with pytest.raises(ValueError, match="q_block"):
        from repro.kernels.ddal_wavg.kernel import fused_wavg_q_flat
        fused_wavg_q_flat(Q, S, T, R, valid, q_block=100,
                          interpret=True)


def test_small_leaf_oracle_fallback():
    """Leaves under one tile never pay a kernel launch: the pallas
    tree entry routes them through the jnp contraction (zero
    pallas_call eqns), while a tile-sized leaf gets exactly one."""
    T, R, valid = _share_meta(3)
    small = {"b": jnp.ones((3, 64)), "w": jnp.ones((3, 10, 12))}
    big = {"emb": jnp.ones((3, 16_384))}

    def run(tree):
        return lambda: wavg_ops.tree_fused_wavg(
            tree, T, R, valid, impl="pallas", interpret=True)

    assert _count_pallas_calls(run(small)) == 0
    assert _count_pallas_calls(run(big)) == 1
    got_g, got_w = run(small)()
    want_g, want_w = _legacy_share(small, T, R, valid)
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(
        np.asarray(a), np.asarray(b)), got_g, want_g)


def test_resolve_impl_auto_selection():
    """`auto` is decided where the program is lowered: compiled for
    the CPU it is the XLA form, bit for bit and with no kernel call in
    the executable. Explicit choices pass through, and unknown names
    fail loudly on every new entry point."""
    assert wavg_ops.resolve_impl("auto") == "auto"
    assert wavg_ops.resolve_impl(None) == "auto"
    assert wavg_ops.resolve_impl("pallas") == "pallas"
    assert wavg_ops.resolve_impl("xla") == "xla"
    with pytest.raises(ValueError, match="impl"):
        wavg_ops.resolve_impl("cuda")
    G = jax.random.normal(jax.random.PRNGKey(0), (4, 3 * 8192))
    T, R, valid = _share_meta(4)

    def run(impl):
        return jax.jit(lambda *a: wavg_ops.fused_wavg(*a, impl=impl))
    if jax.default_backend() == "cpu":
        hlo = run("auto").lower(G, T, R, valid).compile().as_text()
        assert "tpu_custom_call" not in hlo
        for a, b in zip(run("auto")(G, T, R, valid),
                        run("xla")(G, T, R, valid)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    with pytest.raises(ValueError, match="impl"):
        wavg_ops.fused_wavg(G, T, R, valid, impl="nope")


def test_store_weighted_average_fused_is_bitwise():
    """The store combiner's new default (`fused=True`) reproduces the
    legacy multi-op weighted_average bit for bit on a populated ring,
    and a quantized store stays within the analytic eq. 4 bound."""
    from repro.core import knowledge as K
    params_like = {"w": jnp.zeros((24, 7)), "b": jnp.zeros((13,))}
    key = jax.random.PRNGKey(0)

    def fill(store, qb=0):
        for i in range(5):
            piece = jax.tree.map(
                lambda x: jax.random.normal(
                    jax.random.fold_in(key, i), x.shape), params_like)
            scale = None
            if qb:
                piece, scale = wavg_ops.quantize_tree(piece, qb,
                                                      lead=0)
            store = K.append(store, piece, T=float(i + 1),
                             R=0.5 + 0.1 * i, scale=scale)
        return store

    st = fill(K.make_store(params_like, m=8))
    legacy_g, legacy_w = K.weighted_average(st)
    fused_g, fused_w = K.weighted_average(st, fused=True)
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(
        np.asarray(a), np.asarray(b)), fused_g, legacy_g)
    assert float(fused_w) == float(legacy_w)

    qb = 128
    stq = fill(K.make_store(params_like, m=8, quant_block=qb), qb=qb)
    quant_g, quant_w = K.weighted_average(stq, quant_block=qb)
    max_scale = max(float(jnp.max(s))
                    for s in jax.tree.leaves(stq.scale))
    err = max(float(jnp.max(jnp.abs(a - b))) for a, b in
              zip(jax.tree.leaves(quant_g), jax.tree.leaves(legacy_g)))
    assert err <= max_scale / 2 + 1e-7
    np.testing.assert_allclose(float(quant_w), float(legacy_w),
                               rtol=1e-6)


def test_flat_pod_quant_gate_is_identity_at_zero():
    """flat/pod combiners push window planes through
    quantize_knowledge_roundtrip before aggregation; quant-off must be
    the *same object* (no tracer-level perturbation), and quantized
    planes must respect the per-block bound."""
    from repro.core.sharded_ddal import (Knowledge,
                                         quantize_knowledge_roundtrip)
    key = jax.random.PRNGKey(4)
    tg = {"w": jax.random.normal(key, (4, 1000))}
    know = Knowledge(tg=tg,
                     tsum=jnp.ones((4,)),
                     rg=jax.tree.map(lambda x: 0.5 * x, tg),
                     rsum=jnp.ones((4,)))
    assert quantize_knowledge_roundtrip(know, 0) is know
    rt = quantize_knowledge_roundtrip(know, 128)
    _, S = wavg_ref.quantize_flat(tg["w"].reshape(4, -1), 128)
    err = jnp.abs(rt.tg["w"] - know.tg["w"]).reshape(4, -1)
    bound = jnp.repeat(S / 2.0, 128, axis=-1)[:, :1000] + 1e-9
    assert bool(jnp.all(err <= bound))
    np.testing.assert_array_equal(np.asarray(rt.tsum),
                                  np.asarray(know.tsum))


# ----------------------------------------------------------------------
# flash_attention
# ----------------------------------------------------------------------
from repro.kernels.flash_attention import ops as fa_ops
from repro.kernels.flash_attention import ref as fa_ref


@pytest.mark.parametrize(
    "B,S,H,K,D,win,blk",
    [(2, 128, 4, 2, 32, None, 64),
     (1, 256, 4, 4, 64, None, 128),
     (2, 96, 8, 2, 32, None, 32),
     (1, 256, 4, 2, 32, 64, 64),
     (1, 64, 2, 1, 16, 16, 32),     # MQA + window
     (2, 80, 4, 4, 32, None, 32)])  # padded seq (80 % 32 != 0)
def test_flash_attention(B, S, H, K, D, win, blk):
    key = jax.random.PRNGKey(0)
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (B, S, H, D), jnp.float32)
    k = jax.random.normal(kk, (B, S, K, D), jnp.float32)
    v = jax.random.normal(kv, (B, S, K, D), jnp.float32)
    got = fa_ops.flash_attention(q, k, v, window=win, block_q=blk,
                                 block_k=blk, interpret=True)
    want = fa_ref.attention(q, k, v, window=win)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_flash_attention_bf16():
    key = jax.random.PRNGKey(1)
    q = jax.random.normal(key, (1, 128, 4, 32)).astype(jnp.bfloat16)
    k = jax.random.normal(key, (1, 128, 2, 32)).astype(jnp.bfloat16)
    v = jax.random.normal(key, (1, 128, 2, 32)).astype(jnp.bfloat16)
    got = fa_ops.flash_attention(q, k, v, interpret=True)
    want = fa_ref.attention(q, k, v)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        rtol=3e-2, atol=3e-2)


# ----------------------------------------------------------------------
# ssd_scan — Mamba2 intra-chunk dual form
# ----------------------------------------------------------------------
from repro.kernels.ssd_scan import ops as ssd_ops
from repro.kernels.ssd_scan import ref as ssd_ref


def _ssd_inputs(key, b, nc, l, h, n, p):
    ks = jax.random.split(key, 5)
    xc = jax.random.normal(ks[0], (b, nc, l, h, p), jnp.float32)
    dtc = jax.nn.softplus(jax.random.normal(ks[1], (b, nc, l, h)))
    A = -jnp.exp(jax.random.normal(ks[2], (h,)))
    cs = jnp.cumsum(dtc * A, axis=2)
    Bc = jax.random.normal(ks[3], (b, nc, l, h, n), jnp.float32)
    Cc = jax.random.normal(ks[4], (b, nc, l, h, n), jnp.float32)
    return xc, dtc, cs, Bc, Cc


@pytest.mark.parametrize("b,nc,l,h,p,n",
                         [(2, 2, 32, 3, 16, 16),
                          (1, 4, 64, 2, 32, 64),
                          (2, 1, 128, 4, 64, 128)])
def test_ssd_intra_chunk(b, nc, l, h, p, n):
    xc, dtc, cs, Bc, Cc = _ssd_inputs(jax.random.PRNGKey(0),
                                      b, nc, l, h, n, p)
    got = ssd_ops.ssd_intra_chunk(xc, dtc, cs, Bc, Cc, interpret=True)
    want = ssd_ref.ssd_intra_chunk(xc, dtc, cs, Bc, Cc)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_ssd_chunked_end_to_end():
    """Full ssd_chunked with the Pallas intra-chunk path == XLA path."""
    from repro.models.ssd import ssd_chunked
    key = jax.random.PRNGKey(0)
    b, s, h, p, n, chunk = 1, 128, 2, 16, 32, 32
    ks = jax.random.split(key, 5)
    x = jax.random.normal(ks[0], (b, s, h, p))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, h)))
    A = -jnp.exp(jax.random.normal(ks[2], (h,)))
    B = jax.random.normal(ks[3], (b, s, 1, n))
    C = jax.random.normal(ks[4], (b, s, 1, n))
    y1, s1 = ssd_chunked(x, dt, A, B, C, chunk, impl="xla")
    y2, s2 = ssd_chunked(x, dt, A, B, C, chunk,
                         impl="pallas_interpret")
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.slow
def test_model_level_kernel_equivalence():
    """attention_impl / ssd_impl flags do not change model outputs.
    Slow lane: two full reduced-model losses per arch under interpret
    mode; the per-kernel parity sweeps above give tier-1 the same
    oracle coverage at a fraction of the wall time."""
    from repro.configs import get_arch_config
    from repro.configs.base import ShapeConfig
    from repro.models import get_model, make_batch
    for arch, flag in [("llama3.2-3b", "attention_impl"),
                       ("mamba2-780m", "ssd_impl")]:
        cfg = get_arch_config(arch).reduced()
        model = get_model(cfg)
        key = jax.random.PRNGKey(0)
        params = model.init(cfg, key)
        batch = make_batch(cfg, ShapeConfig("t", 64, 2, "train"), key)
        l1 = model.loss(cfg, params, batch)
        l2 = model.loss(cfg.with_(**{flag: "pallas_interpret"}),
                        params, batch)
        np.testing.assert_allclose(float(l1), float(l2), rtol=1e-4)


def test_ssd_chunked_gradients_finite_at_published_chunk():
    """mamba2's published 256-step chunk drives exp(cs_i − cs_j) past
    the fp32 range above the diagonal; the masked entries must not
    leak 0 · inf = NaN into the gradients."""
    from repro.models.ssd import ssd_chunked
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    b, s, h, p, n, chunk = 1, 512, 4, 8, 16, 256
    x = jax.random.normal(ks[0], (b, s, h, p))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, h)))
    A = -jnp.linspace(1.0, 16.0, h)          # mamba2's A_log init
    B = jax.random.normal(ks[3], (b, s, 1, n))
    C = jax.random.normal(ks[4], (b, s, 1, n))
    grads = jax.grad(
        lambda *a: jnp.sum(ssd_chunked(*a, chunk)[0]),
        argnums=(0, 1, 2, 3, 4))(x, dt, A, B, C)
    for g in grads:
        assert bool(jnp.all(jnp.isfinite(g)))
