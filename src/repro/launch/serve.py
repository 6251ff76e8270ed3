"""Serving launcher: batched / continuous / multi-tenant group serving
for any model-zoo arch. ``--full`` serves the published widths,
``--layers N`` their first N whole layers; ``main(argv)`` returns the
generated tokens by request id.

Serving configuration rides one generic ``--serve key=value`` escape
hatch whose vocabulary derives from ``repro.serving.cli_options()``
(every ``ServeConfig`` field plus the engine-level knobs) — the same
registry-derived pattern as ``launch/train.py``'s ``--exchange``, so
new serving knobs never grow new argparse flags here.

    # fixed-batch (the seed behaviour)
    PYTHONPATH=src python -m repro.launch.serve --arch mamba2-780m \
        --requests 6 --serve engine=batch --serve slots=2

    # multi-tenant: 4 agents' policies from one mesh
    PYTHONPATH=src python -m repro.launch.serve --arch llama3.2-3b \
        --requests 12 --serve engine=group --serve agents=4 \
        --serve slots=4 --serve max_new_tokens=16
"""
from __future__ import annotations

import argparse
import time


def _serve_kv(text: str):
    """Parse one ``--serve key=value`` item against the serving
    vocabulary (``repro.serving.cli_options``): ServeConfig fields and
    engine-level knobs, values coerced to the declared type."""
    from repro.serving import cli_options
    opts = cli_options()
    key, sep, value = text.partition("=")
    if not sep:
        raise argparse.ArgumentTypeError(
            f"--serve wants key=value, got {text!r}")
    if key not in opts:
        raise argparse.ArgumentTypeError(
            f"unknown serve option {key!r}; valid keys: "
            f"{', '.join(sorted(opts))}")
    field, typ = opts[key]
    try:
        return field, typ(value)
    except ValueError:
        raise argparse.ArgumentTypeError(
            f"--serve {key} wants a {typ.__name__}, got {value!r}")


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--arch", default="llama3.2-3b")
    p.add_argument("--requests", type=int, default=6)
    p.add_argument("--prompt-len", type=int, default=16)
    p.add_argument("--serve", action="append", default=[],
                   type=_serve_kv, metavar="KEY=VALUE",
                   help="serving configuration "
                        "(repro.serving.cli_options): any ServeConfig "
                        "field (max_len= max_new_tokens= temperature= "
                        "eos_id=) or engine knob (engine=batch|"
                        "continuous|group, slots=, prompt_pad=, "
                        "agents=, router=fifo|fair). Repeatable; "
                        "later spellings win. Example: --serve "
                        "engine=group --serve agents=4 --serve "
                        "max_new_tokens=16")
    p.add_argument("--ckpt", default=None,
                   help="group engine: restore the published param "
                        "planes from a ParamStore checkpoint instead "
                        "of random init")
    p.add_argument("--full", action="store_true",
                   help="published widths and depth of --arch instead "
                        "of the reduced smoke config")
    p.add_argument("--layers", type=int, default=None,
                   help="with --full: keep the first N whole layers "
                        "(widths stay published)")
    p.add_argument("--seed", type=int, default=0)
    args = p.parse_args(argv)
    if args.layers is not None and not args.full:
        p.error("--layers cuts the depth of the --full config")

    import jax
    import numpy as np

    from repro.configs import get_arch_config
    from repro.models import get_model
    from repro.serving import (
        ContinuousBatcher,
        GroupRequest,
        GroupServeEngine,
        ParamStore,
        Router,
        ServeConfig,
        ServeEngine,
        ServeMetrics,
        serve_batches,
    )

    # defaults, then --serve pairs layered on top (later spellings win)
    knobs = {"engine": "batch", "slots": 2, "prompt_pad": 16,
             "agents": 1, "router": "fifo"}
    serve_kw = {}
    import dataclasses
    serve_fields = {f.name for f in dataclasses.fields(ServeConfig)}
    for field, value in args.serve:
        (serve_kw if field in serve_fields else knobs)[field] = value
    serve = ServeConfig(**{"max_len": 128, "max_new_tokens": 16,
                           **serve_kw})

    cfg = get_arch_config(args.arch)
    if not args.full:
        cfg = cfg.reduced()
    elif args.layers is not None:
        cfg = cfg.with_layers(args.layers)
    model = get_model(cfg)

    rng = np.random.default_rng(args.seed)
    prompts = [list(rng.integers(0, cfg.vocab_size,
                                 rng.integers(2, args.prompt_len)))
               for _ in range(args.requests)]

    t0 = time.time()
    n_out = 0
    results = {}
    if knobs["engine"] == "group":
        A = knobs["agents"]
        if args.ckpt:
            template = jax.eval_shape(
                lambda ks: jax.vmap(lambda k: model.init(cfg, k))(ks),
                jax.random.split(jax.random.PRNGKey(0), A))
            # placed once on the device, not shipped with every step
            store = ParamStore.load(args.ckpt, template,
                                    placer=jax.device_put)
            print(f"restored planes v{store.version} from {args.ckpt}")
        else:
            keys = jax.random.split(jax.random.PRNGKey(args.seed), A)
            store = ParamStore(
                jax.vmap(lambda k: model.init(cfg, k))(keys))
        metrics = ServeMetrics()
        engine = GroupServeEngine(cfg, store, serve,
                                  batch_size=knobs["slots"],
                                  prompt_pad=knobs["prompt_pad"],
                                  router=Router(knobs["router"]),
                                  metrics=metrics, seed=args.seed)
        reqs = [GroupRequest(rid, rid % A, pr)
                for rid, pr in enumerate(prompts)]
        out = engine.run(reqs)
        results = out
        for req in reqs:
            toks = out[req.rid]
            n_out += len(toks)
            print(f"req {req.rid} agent {req.agent_id}: "
                  f"prompt={np.asarray(req.prompt)} "
                  f"-> {np.asarray(toks)}")
        s = metrics.summary()
        print(f"agents={A} slots={knobs['slots']} "
              f"p50={s['latency_p50'] * 1e3:.0f}ms "
              f"p99={s['latency_p99'] * 1e3:.0f}ms "
              f"queue_depth_mean={s['queue_depth_mean']:.1f}")
    elif knobs["engine"] == "continuous":
        params = model.init(cfg, jax.random.PRNGKey(args.seed))
        batcher = ContinuousBatcher(cfg, params, serve,
                                    batch_size=knobs["slots"],
                                    prompt_pad=knobs["prompt_pad"])
        out = batcher.run(prompts)
        results = {rid: list(out[rid]) for rid in range(len(prompts))}
        for rid, pr in enumerate(prompts):
            n_out += len(out[rid])
            print(f"req {rid}: prompt={np.asarray(pr)} "
                  f"-> {np.asarray(out[rid])}")
    else:
        params = model.init(cfg, jax.random.PRNGKey(args.seed))
        engine = ServeEngine(cfg, params, serve)
        for bi, (toks, lens) in enumerate(
                serve_batches(prompts, knobs["slots"])):
            out = engine.generate(toks, lens, jax.random.PRNGKey(bi))
            n_out += out.shape[0] * out.shape[1]
            for row in range(out.shape[0]):
                rid = bi * knobs["slots"] + row
                if rid < len(prompts):          # not tail padding
                    results[rid] = [int(t) for t in out[row]]
                print(f"batch {bi} slot {row}: "
                      f"prompt={np.asarray(toks[row][:int(lens[row])])} "
                      f"-> {np.asarray(out[row])}")
    dt = time.time() - t0
    print(f"{n_out} tokens in {dt:.1f}s ({n_out / dt:,.0f} tok/s, "
          f"incl. compile)")
    return results


if __name__ == "__main__":
    from repro.common.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
