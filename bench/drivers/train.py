"""Group training: DDAL's streaming train step (``repro.core``) over a
group of language-model agents, driven as ``repro.launch.train``'s loop
drives it: the batch, the compiled step, then its metrics fetched.

Set-up makes the weights and the whole train state on the device in
one jitted call from the seed, compiles the step, and drives that same
state through the first three steps: a local step, then a share
window of two that ends in eq. 4. It reads what the check needs on the
way: each step's losses, the first applied gradient (from Adam's first
moment after one step), the window's gradient sketch after step 1, the
relevance that the share step observed, and the weights' change after
the three. The window then runs the same compiled step on the same
state. After it, the state is freed and the plain reference
(``train_ref``) follows the same three steps.

End to end: ``train_tok_s``, every agent's tokens of the steps that
finished in the window over the window.
"""
from __future__ import annotations

import gc
import time

import numpy as np

import flops
import mamba2_ref as M
import train_ref
from harness import Outcome, RunError, seed_words


def arch_config(conf: dict):
    """The program's configuration, with every size taken from the
    configuration file."""
    from repro.configs import get_arch_config
    from repro.configs.base import SSMConfig
    s = conf["ssm_cfg"]
    return get_arch_config(conf["program_arch"]).with_(
        n_layers=conf["n_layer"], d_model=conf["d_model"],
        vocab_size=M.vocab_rows(conf),
        tie_embeddings=conf["tie_embeddings"],
        norm_eps=conf["norm_epsilon"], param_dtype=conf["param_dtype"],
        compute_dtype=conf["compute_dtype"],
        ssm=SSMConfig(d_state=s["d_state"], expand=s["expand"],
                      head_dim=s["headdim"], n_groups=s["ngroups"],
                      chunk=s["chunk_size"], d_conv=s["d_conv"]))


def group_spec(traffic: dict):
    from repro.configs.base import GroupSpec
    return GroupSpec(n_agents=traffic["agents"],
                     threshold=traffic["threshold"],
                     minibatch=traffic["minibatch"],
                     knowledge_mode="streaming", **traffic["exchange"])


def optimizer(traffic: dict):
    from repro import optim
    o = traffic["adamw"]
    return optim.adamw(o["lr"], b1=o["b1"], b2=o["b2"], eps=o["eps"],
                       weight_decay=o["weight_decay"], clip=o["clip"])


def build(conf: dict, traffic: dict, fault=None):
    """The program's pieces and the jitted functions the run drives.
    ``fault`` plants ``no_exchange``, a share step that combines each
    agent's own window alone, or ``sketch_half``, a window sketch that
    leaves out every second leaf."""
    import jax
    import jax.numpy as jnp
    from repro.core import make_group_train_step
    from repro.core.exchange import build_exchange
    from repro.core.sharded_ddal import TrainState, init_knowledge

    cfg = arch_config(conf)
    spec = group_spec(traffic)
    opt = optimizer(traffic)
    exchange = build_exchange(spec, kind="streaming")
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

    def make_state(lo, hi):
        params = M.init_params(conf, lo, hi, A)
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
        p0 = M.init_params(conf, lo, hi, A)
        return train_ref.leaf_norms(jax.tree.map(
            lambda p, q: p - q, state.params, p0))

    def batch(lo, hi, s):
        return train_ref.make_batch(traffic, conf["vocab_size"], lo, hi, s)

    return {
        "cfg": cfg, "spec": spec,
        "make_state": jax.jit(make_state),
        "step": jax.jit(make_group_train_step(cfg, spec, opt,
                                              exchange=exchange),
                        donate_argnums=0),
        "batch": jax.jit(batch),
        "first_grad": jax.jit(first_grad),
        "delta": jax.jit(delta),
    }


def free_device_memory() -> None:
    import jax
    for x in jax.live_arrays():
        x.delete()
    gc.collect()


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
    state, m, steps, window_s, share_s, local_s, failed, got = _drive(
        bench, build(conf, traffic, fault), fault, lo_a, hi_a)
    del state, m
    free_device_memory()

    t0 = time.perf_counter()
    ref = train_ref.reference(conf, traffic, lo, hi)
    bench.log(f"bench: reference took {time.perf_counter() - t0:.1f}s")
    gaps = train_ref.gaps(got, ref)
    limits = bench.cell["check"]["limits"]
    bench.log(f"bench: losses program {got['loss'].tolist()} reference "
              f"{ref['loss'].tolist()}")
    bench.log(f"bench: every gap read {gaps}")

    shapes = M.leaf_shapes(conf)
    sizes = [int(np.prod(s)) for s in shapes.values()]
    return Outcome(
        attempted=steps, failed=failed,
        end_to_end={"train_tok_s": steps * A * B * S / window_s},
        compared={k: (gaps[k], float(limits[k])) for k in sorted(limits)},
        counters={
            "steps": steps, "share_s": share_s, "local_s": local_s,
            "tokens": steps * A * B * S,
            "train_flops_per_token": flops.mamba2_train_flops_per_token(
                conf, S),
            # after warm-up every step streams its gradients into the
            # window sketch
            "sketch_calls": steps if traffic["exchange"].get(
                "relevance_sketch_dim") else 0,
            "sketch_work": flops.sketch_work(
                sizes, A, traffic["exchange"].get("relevance_sketch_dim",
                                                  0)),
        })


def _drive(bench, parts, fault, lo_a, hi_a):
    """Set-up and the window: returns the live state, the last metrics,
    the steps, the window's length, the share and local step times,
    the failed steps and the readings of the first three steps."""
    import jax
    import jax.numpy as jnp

    traffic = bench.cell["traffic"]
    A, B = traffic["agents"], traffic["batch"]

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
            # the step sees half of each agent's rows twice over: the
            # mean is over the first half alone
            batch = jax.tree.map(
                lambda x: jnp.concatenate([x[:, :B // 2]] * 2, axis=1),
                batch)
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
    # the share step's relevance observation, out of the EMA from the
    # uniform prior
    ema = traffic["exchange"].get("relevance_ema", 0.9)
    got_rel = (np.asarray(jax.device_get(state.know.rel), np.float64)
               - ema) / (1 - ema)
    got_delta = train_ref.per_leaf(jax.device_get(
        delta_c(state, lo_a, hi_a)))
    jax.block_until_ready(state)

    # the window
    share_s, local_s = [], []
    failed = 0
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
        i += 1
    jax.block_until_ready(state)
    window_s = bench.end_window()
    steps = len(share_s) + len(local_s)
    bench.read_memory([step_c])
    got = {"loss": got_loss, "grad": got_grad, "delta": got_delta,
           "sketch": got_sketch, "rel": got_rel}
    return state, m, steps, window_s, share_s, local_s, failed, got


def control(cell: dict, conf: dict, seed: int) -> dict:
    """Readings that set the check's upper ends, at the cell's size:
    the reference with float8 projections and a float8 sketch in the
    program's place (the control), and the reference with each planted
    fault (half of each batch, no exchange between agents, half of the
    leaves sketched), each against the float32 reference."""
    traffic = cell["traffic"]
    lo, hi = seed_words(seed)

    def ref(**kw):
        return train_ref.reference(conf, traffic, lo, hi, **kw)
    base = ref()
    runs = {"control_fp8": ref(mm=M.fp8_dot, sketch_cast=M.f8_round),
            "fault_half_batch": ref(rows=traffic["batch"] // 2),
            "fault_no_exchange": ref(exchange=False),
            "fault_sketch_half": ref(sketch_keep=lambda i: i % 2 == 0)}
    return {k: train_ref.gaps(v, base) for k, v in runs.items()}
