"""Group training with one agent per chip: DDAL's streaming train step
(``repro.core``) over a group of language-model agents on a two-level
(pod, agent) mesh (``repro.launch.mesh.make_pod_mesh``), driven as
``drivers/train.py`` drives one chip's group.

Each agent's weights, optimizer state and window live on its own
chip, and its forward and backward pass and window sketch run there;
the share step's eq. 4 gathers a pod's windows over the agent axis and
passes the pod leaders' windows over the pod axis
(``repro.core.pod_dispatch``). Set-up makes the state on the chips
from the seed, compiles the step, and drives it through the first
three steps, reading what the check needs; the window then runs the
same step. After it the state is freed and the plain reference
(``group_ref`` over ``kanana_ref``) follows the same three steps, one
agent per chip.

End to end: ``train_tok_s``, every agent's tokens of the steps that
finished in the window over the window.
"""
from __future__ import annotations

import os
import time

import numpy as np

import flops
import group_ref
import kanana_flops
import kanana_ref as K
import mamba2_ref
import train_ref
from harness import BENCH_DIR, Outcome, RunError, load_module, seed_words

_train = load_module(os.path.join(BENCH_DIR, "drivers", "train.py"),
                     "bench_driver_train")
free_device_memory, optimizer = _train.free_device_memory, _train.optimizer


def arch_config(conf: dict):
    """The program's configuration, with every size taken from the
    configuration file: the chip's share of the published model."""
    import dataclasses
    from repro.configs import get_arch_config
    cfg = get_arch_config(conf["program_arch"])
    return cfg.with_(
        n_layers=conf["num_hidden_layers"],
        first_k_dense=conf["first_k_dense_replace"],
        d_model=conf["hidden_size"], n_heads=conf["num_attention_heads"],
        n_kv_heads=conf["num_key_value_heads"],
        vocab_size=conf["vocab_size"], dense_ff=conf["intermediate_size"],
        norm_eps=conf["rms_norm_eps"], rope_theta=conf["rope_theta"],
        tie_embeddings=conf["tie_word_embeddings"],
        param_dtype=conf["param_dtype"],
        compute_dtype=conf["compute_dtype"],
        moe=dataclasses.replace(
            cfg.moe, n_experts=conf["router_outputs"],
            top_k=conf["num_experts_per_tok"],
            expert_ff=conf["moe_intermediate_size"],
            n_shared=conf["n_shared_experts"],
            scoring=conf["scoring_func"],
            routed_scaling=conf["routed_scaling_factor"],
            norm_topk=conf["norm_topk_prob"],
            n_held=conf["n_routed_experts"],
            first_held=conf["first_held_expert"]),
        mla=dataclasses.replace(
            cfg.mla, kv_lora_rank=conf["kv_lora_rank"],
            qk_nope_dim=conf["qk_nope_head_dim"],
            qk_rope_dim=conf["qk_rope_head_dim"],
            v_dim=conf["v_head_dim"],
            rope_interleave=conf["rope_interleave"]))


def group_spec(traffic: dict):
    from repro.configs.base import GroupSpec
    return GroupSpec(n_agents=traffic["agents"],
                     threshold=traffic["threshold"],
                     minibatch=traffic["minibatch"],
                     knowledge_mode="streaming", **traffic["exchange"])


def build(conf: dict, traffic: dict, devices, fault=None):
    """The program's pieces on a mesh of ``devices`` and the jitted
    functions the run drives. ``fault`` plants ``no_exchange``, a
    share step that combines each agent's own window alone, or
    ``sketch_half``, a window sketch that leaves out every second
    leaf."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.core import make_group_train_step
    from repro.core.exchange import build_exchange
    from repro.core.sharded_ddal import TrainState, init_knowledge
    from repro.launch.mesh import make_pod_mesh
    from repro.launch.shardings import ddal_agent_axis

    cfg = arch_config(conf)
    spec = group_spec(traffic)
    opt = optimizer(traffic)
    mesh = make_pod_mesh(spec.pods, pod_axis=spec.pod_axis,
                         devices=devices)
    exchange = build_exchange(spec, mesh, kind="streaming")
    if fault == "sketch_half":
        est = exchange.estimator
        whole = est.sketch_step

        def half(grads, rnd):
            leaves, tree = jax.tree.flatten(grads)
            return whole(jax.tree.unflatten(tree, [
                x if i % 2 == 0 else jnp.zeros_like(x)
                for i, x in enumerate(leaves)]), rnd)
        est.sketch_step = half
    if fault == "no_exchange":
        def alone(know, rel, step, alive=None):
            def own(tg, rg):
                ex = (-1,) + (1,) * (tg.ndim - 1)
                return 0.5 * (tg / jnp.reshape(know.tsum, ex)
                              + rg / jnp.reshape(know.rsum, ex))
            return jax.tree.map(own, know.tg, know.rg)
        exchange.combiner = alone
    A = traffic["agents"]
    b1 = traffic["adamw"]["b1"]
    axis = ddal_agent_axis(mesh, spec.pod_axis)

    def placed(tree):
        return jax.tree.map(lambda x: NamedSharding(
            mesh, P(axis) if x.ndim else P()), tree)

    def make_state(lo, hi):
        params = K.init_params(conf, lo, hi, A)
        return TrainState(
            params=params, opt_state=jax.vmap(opt.init)(params),
            know=init_knowledge(params, jnp.dtype(spec.knowledge_dtype),
                                rel=exchange.streaming_rel_init(),
                                sketch_dim=exchange.sketch_dim),
            step=jnp.zeros((), jnp.int32))

    def first_grad(state):
        # Adam's first moment after one step is (1 - b1) g
        return train_ref.leaf_norms(jax.tree.map(
            lambda m: m / (1.0 - b1), state.opt_state["m"]))

    def delta(state, lo, hi):
        p0 = K.init_params(conf, lo, hi, A)
        return train_ref.leaf_norms(jax.tree.map(
            lambda p, q: p - q, state.params, p0))

    def batch(lo, hi, s):
        return train_ref.make_batch(traffic, conf["vocab_size"], lo, hi, s)

    words = (jnp.uint32(0), jnp.uint32(0))
    state_shape = jax.eval_shape(make_state, *words)
    batch_shape = jax.eval_shape(batch, *words, jnp.int32(0))
    return {
        "cfg": cfg, "spec": spec, "mesh": mesh,
        "make_state": jax.jit(make_state,
                              out_shardings=placed(state_shape)),
        "step": jax.jit(make_group_train_step(cfg, spec, opt,
                                              exchange=exchange,
                                              mesh=mesh),
                        donate_argnums=0),
        "batch": jax.jit(batch, out_shardings=placed(batch_shape)),
        "first_grad": jax.jit(first_grad),
        "delta": jax.jit(delta),
    }


def run(bench, fault=None) -> Outcome:
    """``fault`` names a planted fault (``unchanged``, ``half_batch``,
    ``no_exchange``, ``sketch_half``) for the harness's own tests; a
    benchmark run passes none."""
    import jax
    import jax.numpy as jnp

    conf, traffic = bench.config, bench.cell["traffic"]
    if traffic["threshold"] != 1 or traffic["minibatch"] != 2:
        raise RunError("the check follows threshold 1, minibatch 2")
    lo, hi = seed_words(bench.seed)
    lo_a, hi_a = jnp.uint32(lo), jnp.uint32(hi)
    A, B, S = traffic["agents"], traffic["batch"], traffic["seq"]
    parts = build(conf, traffic, bench.devices, fault)
    with jax.set_mesh(parts["mesh"]):
        steps, window_s, share_s, local_s, failed, pairs, got = _drive(
            bench, parts, fault, lo_a, hi_a)
    free_device_memory()

    t0 = time.perf_counter()
    ref = group_ref.reference(K, conf, traffic, lo, hi, bench.devices)
    bench.log(f"bench: reference took {time.perf_counter() - t0:.1f}s")
    gaps = train_ref.gaps(got, ref)
    limits = bench.cell["check"]["limits"]
    bench.log(f"bench: losses program {got['loss'].tolist()} reference "
              f"{ref['loss'].tolist()}")
    bench.log(f"bench: every gap read {gaps}")

    chips = len(bench.devices)
    tokens = steps * A * B * S
    sizes = [int(np.prod(s)) for s in K.leaf_shapes(conf).values()]
    passes = steps * A * K.dims(conf)["L"]
    bench.log(f"bench: held-expert pairs {pairs['sum']} over {passes} "
              f"expert-layer passes, {pairs['sum'] / max(tokens, 1):.4f} "
              f"a token; most on one expert in one pass "
              f"{pairs['max']}")
    return Outcome(
        attempted=steps, failed=failed,
        end_to_end={"train_tok_s": tokens / window_s},
        compared={k: (gaps[k], float(limits[k])) for k in sorted(limits)},
        counters={
            "steps": steps, "share_s": share_s, "local_s": local_s,
            "tokens": tokens,
            "train_flops_per_token": kanana_flops.train_flops_per_token(
                conf, S, pairs["sum"] / max(tokens, 1)),
            # after warm-up every step streams its gradients into the
            # window sketch, each chip its own agent's
            "sketch_calls": steps,
            "sketch_work": flops.sketch_work(
                sizes, A // chips,
                traffic["exchange"]["relevance_sketch_dim"]),
            # one chip's share of the held experts' work in the window
            "expert_work": kanana_flops.expert_work(
                conf, pairs["sum"] / chips, passes // chips),
        })


def _drive(bench, parts, fault, lo_a, hi_a):
    """Set-up and the window: returns the steps, the window's length,
    the share and local step times, the failed steps, the window's
    held-expert pairs (sum, and the most on one expert in one pass)
    and the readings of the first three steps."""
    import jax
    import jax.numpy as jnp

    traffic = bench.cell["traffic"]
    A, S = traffic["agents"], traffic["seq"]

    state_c = bench.compile("make_state", parts["make_state"], lo_a, hi_a)
    state = state_c(lo_a, hi_a)
    batch_c = bench.compile("batch", parts["batch"], lo_a, hi_a,
                            jnp.int32(0))
    step_c = bench.compile("train_step", parts["step"], state,
                           batch_c(lo_a, hi_a, jnp.int32(0)))
    grad_c = bench.compile("first_grad", parts["first_grad"], state)
    delta_c = bench.compile("delta", parts["delta"], state, lo_a, hi_a)
    bench.phase("warmup")

    def one_step(state, i):
        batch = batch_c(lo_a, hi_a, jnp.int32(i))
        if fault == "half_batch":
            # the loss reads the first half of each agent's tokens
            # alone; causal attention keeps that half's logits its own
            batch = dict(batch, labels=batch["labels"].at[
                ..., S // 2:].set(-100))
        if fault == "unchanged":
            _, m = step_c(jax.tree.map(jnp.copy, state), batch)
            return state, m
        return step_c(state, batch)

    # the first three steps, read for the check
    got_loss = np.zeros((3, A))
    for i in range(3):
        state, m = one_step(state, i)
        got_loss[i] = np.asarray(jax.device_get(m["loss"]))
        if i == 0:
            got_grad = train_ref.per_leaf(jax.device_get(grad_c(state)))
        if i == 1:
            got_sketch = np.asarray(jax.device_get(state.know.sk),
                                    np.float64)
    ema = traffic["exchange"].get("relevance_ema", 0.9)
    got_rel = (np.asarray(jax.device_get(state.know.rel), np.float64)
               - ema) / (1 - ema)
    got_delta = train_ref.per_leaf(jax.device_get(
        delta_c(state, lo_a, hi_a)))
    jax.block_until_ready(state)

    # the window
    share_s, local_s = [], []
    failed = 0
    pairs = {"sum": 0, "max": 0}
    i = 3
    bench.start_window()
    while bench.in_window():
        t0 = time.perf_counter()
        with bench.span("bench.batch"):
            batch = batch_c(lo_a, hi_a, jnp.int32(i))
        with bench.span("bench.step"):
            state, m = step_c(state, batch)
        with bench.span("bench.fetch"):
            m = jax.device_get(m)
        dt = time.perf_counter() - t0
        (share_s if m["shared"] else local_s).append(dt)
        failed += int(not np.all(np.isfinite(m["loss"])))
        pairs["sum"] += int(np.sum(m["held_pairs"]))
        pairs["max"] = max(pairs["max"], int(np.max(m["held_pairs_max"])))
        i += 1
    jax.block_until_ready(state)
    window_s = bench.end_window()
    steps = len(share_s) + len(local_s)
    bench.read_memory([step_c])
    del state, m
    got = {"loss": got_loss, "grad": got_grad, "delta": got_delta,
           "sketch": got_sketch, "rel": got_rel}
    return steps, window_s, share_s, local_s, failed, pairs, got


def control(cell: dict, conf: dict, seed: int, devices=None) -> dict:
    """Readings that set the check's upper ends, at the cell's size:
    the reference with float8 projections and a float8 sketch in the
    program's place (the control), and the reference with each planted
    fault (half of each agent's tokens, no exchange between agents,
    half of the leaves sketched), each against the float32
    reference."""
    import jax
    traffic = cell["traffic"]
    lo, hi = seed_words(seed)
    devices = devices or jax.devices()[:cell["chips"]]

    def ref(**kw):
        return group_ref.reference(K, conf, traffic, lo, hi, devices, **kw)
    base = ref()
    runs = {"control_fp8": ref(mm=mamba2_ref.fp8_dot,
                               sketch_cast=mamba2_ref.f8_round),
            "fault_half_batch": ref(half=True),
            "fault_no_exchange": ref(exchange=False),
            "fault_sketch_half": ref(sketch_keep=lambda i: i % 2 == 0)}
    return {k: train_ref.gaps(v, base) for k, v in runs.items()}
