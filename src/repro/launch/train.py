"""Training launcher: DDAL group-agent training of any model-zoo arch.

Without ``--full`` this runs the REDUCED smoke config end-to-end (real
data → real gradients → eq. 4 knowledge exchange → optimiser). With
``--full`` it runs the published widths; ``--layers N`` keeps the
first N whole layers, which is how a group of agents at published
widths fits on one chip. ``--mesh prod`` / ``prod-multipod`` place the
full config on the 256- and 512-chip production meshes.

    PYTHONPATH=src python -m repro.launch.train --arch llama3.2-3b \
        --agents 2 --steps 30 --batch 4 --seq 128 --threshold 5 \
        --minibatch 5 [--full [--layers N]] [--ckpt out.npz]

``main(argv)`` returns a :class:`TrainRun`: one metrics dict per step
and the compiled step, so a caller in the same process can check them.
"""
from __future__ import annotations

import argparse
import time
import warnings
from typing import Any, List, NamedTuple


class TrainRun(NamedTuple):
    steps: List[dict]     # per step: loss (A,) array, shared, seconds
    compile_s: float      # lowering + compiling the step
    compiled: Any         # the compiled step (``as_text()``, memory)
    params_per_agent: int


# Legacy named flags are kept as thin shims over the --exchange
# vocabulary (each still works, but explicit use now emits a
# DeprecationWarning pointing at the docs/exchange.md migration
# table; new strategies never add flags here — they arrive through
# the registry automatically).
_DEPRECATION = " [deprecated spelling of --exchange {key}=N]"

# legacy flag → (GroupSpec field, default applied when unset). Flags
# parse with a None sentinel so only *explicit* use warns.
_LEGACY_FLAGS = {
    "topology": ("topology", "full"),
    "degree": ("degree", 4),
    "topology-seed": ("topology_seed", 0),
    "pods": ("pods", 0),
    "pod-axis": ("pod_axis", "pod"),
    "resample-every": ("resample_every", 0),
    "relevance-mode": ("relevance_mode", "uniform"),
    "relevance-ema": ("relevance_ema", 0.9),
    "relevance-sketch-dim": ("relevance_sketch_dim", 0),
}


def _legacy_spec_kw(args) -> dict:
    """Fold the legacy named flags into GroupSpec kwargs, warning on
    each explicit (non-None) use with its --exchange spelling."""
    kw = {}
    for flag, (field, default) in _LEGACY_FLAGS.items():
        value = getattr(args, field)
        if value is None:
            kw[field] = default
        else:
            warnings.warn(
                f"--{flag} is deprecated: spell it --exchange "
                f"{field}={value} (see docs/exchange.md, 'Migration: "
                f"old GroupSpec flags -> strategies')",
                DeprecationWarning, stacklevel=2)
            kw[field] = value
    return kw


def _exchange_kv(text: str):
    """Parse one ``--exchange key=value`` item against the registry
    vocabulary (``repro.core.exchange.cli_options``): the key names
    either a strategy selector (schedule/estimator/delay/combiner) or
    any registered strategy's declared parameter, and the value is
    coerced to that parameter's type."""
    from repro.core.exchange import cli_options
    opts = cli_options()
    key, sep, value = text.partition("=")
    if not sep:
        raise argparse.ArgumentTypeError(
            f"--exchange wants key=value, got {text!r}")
    if key not in opts:
        raise argparse.ArgumentTypeError(
            f"unknown exchange option {key!r}; valid keys: "
            f"{', '.join(sorted(opts))}")
    field, typ = opts[key]
    try:
        return field, typ(value)
    except ValueError:
        raise argparse.ArgumentTypeError(
            f"--exchange {key} wants a {typ.__name__}, got {value!r}")


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--arch", default="llama3.2-3b")
    p.add_argument("--agents", type=int, default=2)
    p.add_argument("--steps", type=int, default=30)
    p.add_argument("--batch", type=int, default=4)
    p.add_argument("--seq", type=int, default=128)
    p.add_argument("--threshold", type=int, default=5)
    p.add_argument("--minibatch", type=int, default=5)
    p.add_argument("--lr", type=float, default=1e-3)
    p.add_argument("--exchange", action="append", default=[],
                   type=_exchange_kv, metavar="KEY=VALUE",
                   help="exchange-protocol configuration "
                        "(repro.core.exchange): KEY is a strategy "
                        "selector (schedule= estimator= delay= "
                        "combiner=) or any registered strategy's "
                        "parameter (e.g. resample_every= "
                        "relevance_ema= explore_eps= pods=). "
                        "Repeatable; keys and types come from the "
                        "strategy registry, so newly registered "
                        "strategies need no new flags. Examples: "
                        "--exchange schedule=relevance_topk "
                        "--exchange explore_eps=0.2; faulty-network "
                        "training: --exchange transport=faulty "
                        "--exchange loss=0.2 --exchange corrupt=0.05 "
                        "(repro.core.transport)")
    p.add_argument("--topology", default=None,
                   choices=["full", "ring", "torus2d", "star",
                            "random_k", "hierarchical"],
                   help="communication graph"
                        + _DEPRECATION.format(key="topology"))
    p.add_argument("--degree", type=int, default=None,
                   help="k for random_k; pod size for hierarchical"
                        + _DEPRECATION.format(key="degree"))
    p.add_argument("--topology-seed", type=int, default=None,
                   help="gossip sampling seed"
                        + _DEPRECATION.format(key="topology_seed"))
    p.add_argument("--pods", type=int, default=None,
                   help="multi-host dispatch: map hierarchical pods "
                        "onto a two-level (pod, agent) mesh — "
                        "intra-pod exchange stays on the fast agent "
                        "axis, only pod leaders' planes cross the pod "
                        "axis (requires --topology hierarchical and "
                        "agents == pods * degree; 0 = flat combine)"
                        + _DEPRECATION.format(key="pods"))
    p.add_argument("--pod-axis", default=None,
                   help="mesh axis name the leader-level exchange "
                        "crosses (--pods only)"
                        + _DEPRECATION.format(key="pod_axis"))
    p.add_argument("--resample-every", type=int, default=None,
                   help="dynamic gossip: resample the random_k "
                        "neighbor table every N steps inside the "
                        "jitted loop (0 = static wiring; requires "
                        "--topology random_k)"
                        + _DEPRECATION.format(key="resample_every"))
    p.add_argument("--relevance-mode", default=None,
                   choices=["uniform", "grad_cos"],
                   help="eq. 4 per-edge relevance R: 'uniform' "
                        "(paper §6 static prior) or 'grad_cos' "
                        "(learned online from the cosine similarity "
                        "of the agents' share-window gradients) "
                        "[deprecated spelling of --exchange "
                        "estimator=...]")
    p.add_argument("--relevance-ema", type=float, default=None,
                   help="EMA decay of the learned relevance estimate "
                        "across share steps (grad_cos only)"
                        + _DEPRECATION.format(key="relevance_ema"))
    p.add_argument("--relevance-sketch-dim", type=int, default=None,
                   help="sketched streaming relevance (grad_cos "
                        "only): project each agent's gradients "
                        "through a seeded ±1 random projection into "
                        "an (agents, d) sketch and estimate cosines "
                        "on sketches — O(agents·|params|) streaming "
                        "+ O(agents²·d) comparisons instead of "
                        "O(agents²·|params|); 0 = exact pairwise "
                        "cosines (d ≈ 256 keeps worst-case cosine "
                        "error ≈ 0.06 before EMA averaging)"
                        + _DEPRECATION.format(
                            key="relevance_sketch_dim"))
    p.add_argument("--full", action="store_true",
                   help="published widths and depth of --arch instead "
                        "of the reduced smoke config")
    p.add_argument("--layers", type=int, default=None,
                   help="with --full: keep the first N whole layers "
                        "(widths stay published)")
    p.add_argument("--mesh", default="single",
                   choices=["single", "prod", "prod-multipod", "pods"],
                   help="'single' runs on the default device; 'pods' "
                        "builds the two-level (pod, agent) mesh over "
                        "the visible devices (simulate with XLA_FLAGS="
                        "--xla_force_host_platform_device_count=N) and "
                        "runs the pod-dispatched combine collectives; "
                        "'single' with --pods runs the same "
                        "decomposition without collectives")
    p.add_argument("--elastic", action="store_true",
                   help="elastic group membership: carry a per-agent "
                        "alive mask through the exchange so agents "
                        "can be killed/revived between steps without "
                        "perturbing survivors (see docs/exchange.md, "
                        "'Membership semantics')")
    p.add_argument("--ckpt", default=None,
                   help="save final params to this .npz")
    p.add_argument("--ckpt-full", default=None,
                   help="save the FULL TrainState — params, optimiser "
                        "state, and the exchange window (Knowledge "
                        "incl. sketches and learned relevance) — so a "
                        "preempted run rejoins mid-stream via "
                        "--restore instead of resetting the group")
    p.add_argument("--restore", default=None,
                   help="restore a --ckpt-full TrainState before "
                        "training (leaves missing from older "
                        "checkpoints, e.g. the elastic alive mask, "
                        "keep their freshly initialised values)")
    p.add_argument("--seed", type=int, default=0)
    args = p.parse_args(argv)
    if args.layers is not None and not args.full:
        p.error("--layers cuts the depth of the --full config")

    import jax
    import numpy as np

    from repro import optim
    from repro.checkpoint import save
    from repro.configs import get_arch_config
    from repro.configs.base import GroupSpec, ShapeConfig
    from repro.core import init_train_state, make_group_train_step
    from repro.data import StreamSpec, make_group_batch
    from repro.launch.mesh import make_pod_mesh, make_production_mesh

    cfg = get_arch_config(args.arch)
    if not args.full:
        cfg = cfg.reduced()
    elif args.layers is not None:
        cfg = cfg.with_layers(args.layers)
    # legacy named flags first (deprecation-warned when explicit),
    # --exchange key=value pairs layered on top (later spellings win)
    # — both feed the same GroupSpec fields
    spec_kw = _legacy_spec_kw(args)
    for field, value in args.exchange:
        spec_kw[field] = value
    spec = GroupSpec(n_agents=args.agents, threshold=args.threshold,
                     minibatch=args.minibatch,
                     knowledge_mode="streaming", elastic=args.elastic,
                     **spec_kw)
    shape = ShapeConfig("train_cli", args.seq, args.batch, "train")
    opt = optim.adamw(args.lr)
    stream = StreamSpec(seed=args.seed)

    # mesh wiring reads the merged spec, so --exchange pods=N /
    # pod_axis=X and the legacy named flags behave identically
    mesh = None
    if args.mesh == "pods":
        if spec.pods < 1:
            raise SystemExit("--mesh pods needs --pods >= 1 (or "
                             "--exchange pods=N)")
        mesh = make_pod_mesh(spec.pods, pod_axis=spec.pod_axis)
        ctx = jax.set_mesh(mesh)
    elif args.mesh != "single":
        mesh = make_production_mesh(multi_pod=args.mesh == "prod-multipod")
        ctx = jax.set_mesh(mesh)
    else:
        import contextlib
        ctx = contextlib.nullcontext()

    key = jax.random.PRNGKey(args.seed)
    with ctx:
        # one protocol serves state init and the step: the carried
        # relevance state and the step's estimator can never drift
        from repro.core.exchange import build_exchange
        exchange = build_exchange(spec, mesh, kind="streaming")
        state = init_train_state(cfg, spec, opt, key,
                                 exchange=exchange)
        if args.restore:
            from repro.checkpoint import restore
            state = restore(args.restore, state, strict=False)
            print(f"restored full TrainState from {args.restore} "
                  f"(step {int(state.step)})")
        if mesh is not None:
            from repro.launch.shardings import agent_sharded_state
            state = agent_sharded_state(state, mesh, spec.pod_axis)
        # the state is donated: a step never holds two copies of the
        # params, optimiser moments and exchange window at once
        step_fn = jax.jit(make_group_train_step(cfg, spec, opt,
                                                exchange=exchange,
                                                mesh=mesh),
                          donate_argnums=0)
        n_params = sum(int(x.size) for x in
                       jax.tree.leaves(state.params)) // args.agents
        print(f"arch={args.arch} reduced={not args.full} "
              f"layers={cfg.n_layers} params/agent={n_params:,} "
              f"agents={args.agents}")
        t0 = time.perf_counter()
        compiled = step_fn.lower(
            state, make_group_batch(cfg, shape, stream, args.agents, 0)
        ).compile()
        compile_s = time.perf_counter() - t0
        print(f"compiled the step in {compile_s:.1f}s")
        steps = []
        for i in range(args.steps):
            t0 = time.perf_counter()
            batch = make_group_batch(cfg, shape, stream, args.agents, i)
            state, m = compiled(state, batch)
            m = jax.device_get(m)
            dt = time.perf_counter() - t0
            steps.append({"loss": np.asarray(m["loss"]),
                          "shared": bool(m["shared"]), "seconds": dt})
            losses = " ".join(f"{float(l):6.3f}" for l in m["loss"])
            tag = " <shared>" if steps[-1]["shared"] else ""
            print(f"step {i:4d} losses [{losses}] {dt * 1e3:.1f}ms{tag}")
        dt = sum(st["seconds"] for st in steps)
        toks = args.steps * args.agents * args.batch * args.seq
        print(f"{args.steps} steps in {dt:.1f}s "
              f"({toks / max(dt, 1e-9):,.0f} tokens/s)")
        if args.ckpt:
            save(args.ckpt, state.params, step=args.steps)
            print(f"saved params to {args.ckpt}")
        if args.ckpt_full:
            save(args.ckpt_full, state, step=int(state.step))
            print(f"saved full TrainState to {args.ckpt_full}")
    return TrainRun(steps, compile_s, compiled, n_params)


if __name__ == "__main__":
    from repro.common.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
