"""kanana-2-30b-a3b's layers in the program against the benchmark's
plain reference (``bench/kanana_ref.py``), at a small size on the CPU
with seeded random weights: the forward logits, the loss and every
gradient; dispatch that drops no token when every token picks one
held expert; and the chip shares of the expert layer and of latent
attention adding up to the whole layer."""
from __future__ import annotations

import dataclasses
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch_config
from repro.configs.base import MoEConfig
from repro.models import get_model
from repro.models.attention import mla_attention
from repro.models.moe import moe_apply

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "bench")
sys.path.insert(0, BENCH)

import kanana_ref as K  # noqa: E402

SEQ = 24


def small_conf(**kw) -> dict:
    with open(os.path.join(BENCH, "configs", "kanana-2-30b-a3b.json")) as f:
        conf = json.load(f)
    conf.update(hidden_size=64, qk_nope_head_dim=16, qk_rope_head_dim=8,
                v_head_dim=16, kv_lora_rank=32, moe_intermediate_size=32,
                intermediate_size=96, router_outputs=16,
                n_routed_experts=4, first_held_expert=4, vocab_size=97,
                num_hidden_layers=3, compute_dtype="float32",
                rope_theta=10000.0)
    conf.update(kw)
    return conf


def program_cfg(conf: dict):
    """The program's configuration for ``conf``, as the benchmark's
    pods driver builds it."""
    cfg = get_arch_config("kanana-2-30b-a3b")
    return cfg.with_(
        n_layers=conf["num_hidden_layers"],
        d_model=conf["hidden_size"], n_heads=conf["num_attention_heads"],
        n_kv_heads=conf["num_key_value_heads"],
        vocab_size=conf["vocab_size"], dense_ff=conf["intermediate_size"],
        norm_eps=conf["rms_norm_eps"], rope_theta=conf["rope_theta"],
        compute_dtype=conf["compute_dtype"],
        moe=dataclasses.replace(
            cfg.moe, n_experts=conf["router_outputs"],
            top_k=conf["num_experts_per_tok"],
            expert_ff=conf["moe_intermediate_size"],
            n_held=conf["n_routed_experts"],
            first_held=conf["first_held_expert"]),
        mla=dataclasses.replace(
            cfg.mla, kv_lora_rank=conf["kv_lora_rank"],
            qk_nope_dim=conf["qk_nope_head_dim"],
            qk_rope_dim=conf["qk_rope_head_dim"],
            v_dim=conf["v_head_dim"]))


def weights(conf: dict, seed: int = 3, bias_scale: float = 0.3):
    """Seeded weights with a non-zero correction bias."""
    p = K.init_agent(conf, seed, 0, 0)
    bias = p["layers"]["moe"]["router_bias"]
    p["layers"]["moe"]["router_bias"] = bias_scale * jax.random.normal(
        jax.random.PRNGKey(seed), bias.shape)
    return p


def tokens(conf: dict, batch: int = 2):
    t = jax.random.randint(jax.random.PRNGKey(11), (batch, SEQ + 1), 0,
                           conf["vocab_size"])
    return t[:, :-1], t[:, 1:]


def program_batch(t, lab):
    pos = jnp.broadcast_to(jnp.arange(t.shape[1]), t.shape)
    return {"tokens": t, "labels": lab, "positions": pos}


def test_moe_config_defaults_are_todays_routing():
    moe = MoEConfig(n_experts=64, top_k=6, expert_ff=1408, n_shared=2)
    assert (moe.scoring, moe.router_bias, moe.routed_scaling,
            moe.norm_topk, moe.n_held, moe.first_held) == (
                "softmax", False, 1.0, True, 0, 0)
    for arch in ("deepseek-v2-lite-16b", "qwen3-moe-30b-a3b"):
        cfg = get_arch_config(arch).reduced()
        shapes = jax.eval_shape(
            lambda k: get_model(cfg).init(cfg, k), jax.random.PRNGKey(0))
        moe_p = shapes["layers"]["moe"]
        assert "router_bias" not in moe_p
        assert moe_p["experts"]["w_gate"].shape[1] == cfg.moe.n_experts
    with pytest.raises(ValueError, match="held experts"):
        MoEConfig(n_experts=8, top_k=2, expert_ff=4, n_held=4,
                  first_held=6)


def test_forward_loss_and_grads_match_reference():
    conf = small_conf()
    cfg = program_cfg(conf)
    model = get_model(cfg)
    p = weights(conf)
    t, lab = tokens(conf)
    assert jax.tree.structure(p) == jax.tree.structure(jax.eval_shape(
        lambda k: model.init(cfg, k), jax.random.PRNGKey(0)))
    with jax.default_matmul_precision("highest"):
        got_logits, _ = model.forward(cfg, p, program_batch(t, lab), None)
        ref_logits = K.logits(conf, p, t)
        (got_l, stats), got_g = jax.value_and_grad(
            lambda q: model.loss_stats(cfg, q, program_batch(t, lab)),
            has_aux=True)(p)
        ref_l, ref_g = jax.value_and_grad(
            lambda q: K.loss(conf, q, t, lab))(p)
    np.testing.assert_allclose(got_logits, ref_logits, rtol=2e-4,
                               atol=2e-4)
    np.testing.assert_allclose(got_l, ref_l, rtol=1e-5)
    flat_got = jax.tree_util.tree_leaves_with_path(got_g)
    flat_ref = jax.tree.leaves(ref_g)
    for (path, g), r in zip(flat_got, flat_ref):
        scale = max(float(jnp.max(jnp.abs(r))), 1e-6)
        np.testing.assert_allclose(g / scale, r / scale, atol=2e-4,
                                   err_msg=jax.tree_util.keystr(path))
    # the correction bias selects experts and carries no gradient
    assert not np.any(np.asarray(got_g["layers"]["moe"]["router_bias"]))
    # pairs routed to the 4 held experts of 16, top 6, over 2 layers
    T = t.size
    assert 0 < int(stats["held_pairs"]) <= 2 * T * 4
    assert int(stats["held_pairs_max"]) <= T


def test_dispatch_drops_no_token_when_every_token_picks_one_expert():
    conf = small_conf()
    cfg = program_cfg(conf)
    model = get_model(cfg)
    p = weights(conf, bias_scale=0.0)
    # the bias makes every token choose held expert first_held + 1
    bias = p["layers"]["moe"]["router_bias"]
    p["layers"]["moe"]["router_bias"] = bias.at[:, conf[
        "first_held_expert"] + 1].set(100.0)
    t, lab = tokens(conf)
    T = t.size
    x = jax.random.normal(jax.random.PRNGKey(5), (2, SEQ,
                                                  conf["hidden_size"]))
    lp = jax.tree.map(lambda w: w[0], p["layers"]["moe"])
    with jax.default_matmul_precision("highest"):
        out, _, stats = moe_apply(cfg, lp, x)
        want, counts = K.held_experts(conf, lp, x.reshape(T, -1), K.dot)
        want = want + K.swiglu(lp["shared"], x.reshape(T, -1), K.dot)
        (_, s), _ = jax.value_and_grad(
            lambda q: model.loss_stats(cfg, q, program_batch(t, lab)),
            has_aux=True)(p)
    np.testing.assert_allclose(out.reshape(T, -1), want, rtol=2e-4,
                               atol=2e-5)
    assert int(stats["held_pairs"][1]) == T == int(counts[1])
    np.testing.assert_array_equal(stats["held_pairs"], counts)
    assert int(s["held_pairs_max"]) == T


def _unwritten_ragged_dot():
    """``jax.lax.ragged_dot`` as the TPU's kernel leaves it: the rows
    of its result outside every group, of its input's gradient, and an
    empty group's weight gradient hold whatever the memory held (NaN
    here)."""
    exact = jax.lax.ragged_dot

    def poison(out, sizes):
        covered = jnp.arange(out.shape[0]) < jnp.sum(sizes)
        return jnp.where(covered[:, None], out, jnp.nan)

    @jax.custom_vjp
    def ragged_dot(a, w, sizes):
        return poison(exact(a, w, sizes), sizes)

    def fwd(a, w, sizes):
        return ragged_dot(a, w, sizes), (a, w, sizes)

    def bwd(res, ct):
        a, w, sizes = res
        _, vjp = jax.vjp(lambda a_, w_: exact(a_, w_, sizes), a, w)
        da, dw = vjp(ct)
        empty = (sizes == 0)[:, None, None]
        return poison(da, sizes), jnp.where(empty, jnp.nan, dw), None

    ragged_dot.defvjp(fwd, bwd)
    return ragged_dot


@pytest.mark.parametrize("empty", [False, True])
def test_unwritten_rows_of_the_grouped_product_stay_out(monkeypatch,
                                                        empty):
    """On the TPU the grouped product writes only what its groups
    cover; the layer's output and gradients must not see the rest,
    also where held experts get no token."""
    conf = small_conf()
    cfg = program_cfg(conf)
    model = get_model(cfg)
    p = weights(conf)
    if empty:
        # no token chooses the held experts first_held .. first_held + 2
        first = conf["first_held_expert"]
        bias = p["layers"]["moe"]["router_bias"]
        p["layers"]["moe"]["router_bias"] = bias.at[
            :, first:first + 3].set(-100.0)
    t, lab = tokens(conf)
    monkeypatch.setattr(jax.lax, "ragged_dot", _unwritten_ragged_dot())
    with jax.default_matmul_precision("highest"):
        got_l, got_g = jax.value_and_grad(
            lambda q: model.loss(cfg, q, program_batch(t, lab)))(p)
        ref_l, ref_g = jax.value_and_grad(
            lambda q: K.loss(conf, q, t, lab))(p)
    np.testing.assert_allclose(got_l, ref_l, rtol=1e-5)
    for g, r in zip(jax.tree.leaves(got_g), jax.tree.leaves(ref_g)):
        scale = max(float(jnp.max(jnp.abs(r))), 1e-6)
        np.testing.assert_allclose(g / scale, r / scale, atol=2e-4)


def test_expert_shares_add_up_to_the_whole_layer():
    """16 chips each hold 1 of 16 experts: their parts, with the shared
    experts that each computes alike counted once, are the whole
    layer of the uncut reference."""
    shares = 16
    whole = small_conf(n_routed_experts=16, first_held_expert=0)
    p = jax.tree.map(lambda w: w[0], weights(whole)["layers"]["moe"])
    x = jax.random.normal(jax.random.PRNGKey(2), (2, SEQ, 64))
    T = x.shape[0] * x.shape[1]
    with jax.default_matmul_precision("highest"):
        shared = K.swiglu(p["shared"], x.reshape(T, -1), K.dot)
        total = shared
        for j in range(shares):
            conf = small_conf(n_routed_experts=1, first_held_expert=j)
            part = dict(p, experts=jax.tree.map(lambda w: w[j:j + 1],
                                                p["experts"]))
            out, _, _ = moe_apply(program_cfg(conf), part, x)
            total = total + out.reshape(T, -1) - shared
        want = K.moe(whole, p, x, K.dot).reshape(T, -1)
    np.testing.assert_allclose(total, want, rtol=1e-4, atol=1e-4)


def test_head_shares_add_up_to_the_whole_attention():
    """16 chips each hold 2 of 32 heads: the sum of their output
    projections is the uncut reference's attention."""
    whole = small_conf(num_attention_heads=32, num_key_value_heads=32)
    p = jax.tree.map(lambda w: w[0],
                     K.init_agent(whole, 4, 0, 0)["layers"]["attn"])
    x = jax.random.normal(jax.random.PRNGKey(8), (2, SEQ, 64))
    pos = jnp.broadcast_to(jnp.arange(SEQ), (2, SEQ))
    d = K.dims(whole)
    cfg = program_cfg(small_conf(num_attention_heads=2,
                                 num_key_value_heads=2))
    total = 0.0
    with jax.default_matmul_precision("highest"):
        for j in range(16):
            def cols(w, width):
                return w[:, j * 2 * width:(j + 1) * 2 * width]
            part = dict(p, wq=cols(p["wq"], d["DN"] + d["DR"]),
                        w_uk=cols(p["w_uk"], d["DN"]),
                        w_uv=cols(p["w_uv"], d["DV"]),
                        wo=p["wo"][j * 2 * d["DV"]:(j + 1) * 2 * d["DV"]])
            out, _ = mla_attention(cfg, part, x, pos)
            total = total + out
        want = K.attention(whole, p, x, pos, K.dot)
    np.testing.assert_allclose(total, want, rtol=1e-4, atol=1e-4)


def test_interleaved_rope_is_the_published_pair_rotation():
    from repro.models import rope as rope_lib
    x = jax.random.normal(jax.random.PRNGKey(1), (2, SEQ, 3, 8))
    pos = jnp.broadcast_to(jnp.arange(SEQ), (2, SEQ))
    got = rope_lib.rope(rope_lib.deinterleave(x), pos, 1e4)
    want = rope_lib.deinterleave(K.rotate_pairs(x, pos, 1e4))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_chip_share_has_the_configured_parameters():
    """The benchmark's cut, as its pods driver builds it for the
    program, holds the weights the configuration file counts, in the
    reference's layout: 8 of 128 experts, 2 of 32 heads, 16,032 rows."""
    import harness
    with open(os.path.join(BENCH, "configs", "kanana-2-30b-a3b.json")) as f:
        conf = json.load(f)
    drv = harness.load_module(os.path.join(BENCH, "drivers",
                                           "train_pods.py"),
                              "bench_driver_train_pods")
    cfg = drv.arch_config(conf)
    shapes = jax.eval_shape(lambda k: get_model(cfg).init(cfg, k),
                            jax.random.PRNGKey(0))
    got = {jax.tree_util.keystr(k): v.shape for k, v in
           jax.tree_util.tree_leaves_with_path(shapes)}
    want = {"".join(f"['{p}']" for p in path.split("/")): shape
            for path, shape in K.leaf_shapes(conf).items()}
    assert got == want
    assert sum(int(np.prod(s)) for s in got.values()) == (
        conf["parameters_per_agent"])
    assert cfg.moe.n_experts == 128 and cfg.moe.n_held == 8


def test_expert_readers_take_back_the_unscoped_ragged_dots():
    """On the TPU the grouped products' ragged-dot calls lose the
    ``ddal.experts`` scope; the pods cell's readers count them by name,
    once, in the expert layer's time and in the experts' roofline."""
    import harness
    import held_experts

    class Trace:
        op_s = {"ragged-dot-none": 0.3, "ragged-dot-metadata": 0.01,
                "ragged-dot-none.2": 0.2, "fusion.1": 0.1,
                "fusion.2": 0.5, "fusion.3": 1.0}
    ctx = {"trace": Trace(), "chips": 4, "peaks": {
               "bf16_flops_s": 197e12, "hbm_bytes_s": 819e9},
           "counters": {"steps": 10, "expert_work": {
               "flops": 197e12 * 0.25, "bytes": 0.0}},
           "scope_s": {"ddal.experts": 0.1, "ddal.moe": 0.5,
                       "ddal.grad": 1.51},
           "scope_of": {"fusion.1": "ddal.experts", "fusion.2": "ddal.moe",
                        "fusion.3": "ddal.grad",
                        "ragged-dot-none": "ddal.grad",
                        "ragged-dot-metadata": None,
                        "ragged-dot-none.2": "ddal.experts"}}
    # fusion.1 and ragged-dot-none.2 already sit in ddal.experts
    assert held_experts.stray_ragged_s(ctx) == pytest.approx(0.31)
    read = {m: harness.load_module(os.path.join(
        BENCH, "metrics", m + ".py"), "metric_" + m.replace(".", "_"))
        for m in ("moe_ms.train", "expert_roofline.train")}
    assert read["moe_ms.train"].read(ctx) == pytest.approx(91.0)
    assert read["expert_roofline.train"].read(ctx) == pytest.approx(
        100.0 * 0.25 / 0.41)
