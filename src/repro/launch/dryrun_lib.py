"""Dry-run library: lower + compile every (arch × shape) on a given
mesh and extract the roofline terms. No jax device-state mutation here
— ``dryrun.py`` (the CLI) sets XLA_FLAGS before importing anything.

Step functions lowered per shape kind:
  train   → the DDAL group train step (repro.core.sharded_ddal)
  prefill → full-sequence forward building a fresh KV cache
  decode  → ONE new token against a seq_len-capacity cache
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Dict, Optional

import jax
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.common.sharding import axis_rules
from repro.configs import arch_for_shape, get_arch_config
from repro.configs.base import INPUT_SHAPES, ArchConfig, GroupSpec, ShapeConfig
from repro.core.sharded_ddal import make_group_train_step, train_state_specs
from repro.launch.mesh import serve_rules, train_rules
from repro.launch.shardings import (batch_partition_specs,
                                    cache_partition_specs,
                                    param_partition_specs,
                                    train_state_partition_specs)
from repro.models import cache_specs, get_model, input_specs
from repro.optim import adamw
from repro.roofline import analyze, model_flops


def _axis_size(mesh, axes) -> int:
    if axes is None:
        return 1
    if isinstance(axes, str):
        return mesh.shape[axes]
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    return n


def _sanitize(mesh, spec: P, shape) -> P:
    """jit in_shardings require divisibility — drop any spec entry
    whose mesh-axis product does not divide that dim (e.g. kv_heads=8
    over model=16, vocab=49155 over 16). Internal sharding constraints
    still apply; only the *input* layout falls back to replicated on
    that dim."""
    entries = list(spec) + [None] * (len(shape) - len(spec))
    out = []
    for dim, axes in zip(shape, entries):
        out.append(axes if axes and dim % _axis_size(mesh, axes) == 0
                   else None)
    return P(*out)


def _named(mesh, spec_tree, shape_tree=None):
    if shape_tree is None:
        return jax.tree.map(
            lambda s: NamedSharding(mesh, s), spec_tree,
            is_leaf=lambda x: isinstance(x, P))
    return jax.tree.map(
        lambda s, x: NamedSharding(mesh, _sanitize(mesh, s, x.shape)),
        spec_tree, shape_tree,
        is_leaf=lambda x: isinstance(x, P))


def _with_lead(specs: Dict[str, Any], n: int) -> Dict[str, Any]:
    return {k: jax.ShapeDtypeStruct((n,) + v.shape, v.dtype)
            for k, v in specs.items()}


@dataclasses.dataclass
class DryrunResult:
    arch: str
    shape: str
    mesh_name: str
    ok: bool
    error: Optional[str] = None
    memory: Optional[dict] = None
    roofline: Optional[dict] = None
    compile_s: float = 0.0

    def to_dict(self):
        return dataclasses.asdict(self)


def _mesh_name(mesh) -> str:
    return "x".join(str(mesh.shape[a]) for a in mesh.axis_names)


def _memory_dict(compiled) -> dict:
    ma = compiled.memory_analysis()
    out = {}
    for k in ("argument_size_in_bytes", "output_size_in_bytes",
              "temp_size_in_bytes", "generated_code_size_in_bytes"):
        v = getattr(ma, k, None)
        if v is not None:
            out[k] = int(v)
    out["total_bytes_per_device"] = (
        out.get("argument_size_in_bytes", 0)
        + out.get("temp_size_in_bytes", 0))
    return out


def lower_train(cfg: ArchConfig, shape: ShapeConfig, mesh,
                spec: GroupSpec, lr: float = 3e-4):
    """Lower the DDAL group train step on ``mesh``."""
    opt = adamw(lr)
    rules = train_rules(mesh)
    agent_axis = rules["agent"]
    # one protocol serves both the step and the partition specs: the
    # estimator decides what relevance state the TrainState carries,
    # so explicit exchange_estimator overrides shard correctly too
    from repro.core.exchange import build_exchange
    exchange = build_exchange(spec, kind="streaming")
    step_fn = make_group_train_step(cfg, spec, opt, exchange=exchange)

    state_shapes = train_state_specs(cfg, spec, opt)
    state_specs = train_state_partition_specs(
        cfg, rules, agent_axis,
        learn_relevance=exchange.estimator.learns,
        sketch_dim=exchange.estimator.sketch_dim)
    batch_shapes = _with_lead(input_specs(cfg, shape), spec.n_agents)
    bspecs = batch_partition_specs(cfg, shape, rules["batch"],
                                   lead=(agent_axis,))

    in_shardings = (_named(mesh, state_specs, state_shapes),
                    _named(mesh, bspecs, batch_shapes))
    with jax.set_mesh(mesh), axis_rules(rules):
        lowered = jax.jit(step_fn, in_shardings=in_shardings).lower(
            state_shapes, batch_shapes)
    return lowered


def lower_prefill(cfg: ArchConfig, shape: ShapeConfig, mesh):
    model = get_model(cfg)
    rules = serve_rules(mesh, shape.global_batch)
    batch_axes = rules["batch"]

    def prefill_step(params, batch):
        cache = model.make_cache(cfg, shape.global_batch, shape.seq_len)
        logits, new_cache = model.forward(cfg, params, batch, cache)
        return logits, new_cache

    from repro.models import param_specs
    pshapes = param_specs(cfg)
    pspecs = param_partition_specs(cfg, rules)
    bshapes = input_specs(cfg, shape)
    bspecs = batch_partition_specs(cfg, shape, batch_axes)
    in_shardings = (_named(mesh, pspecs, pshapes),
                    _named(mesh, bspecs, bshapes))
    with jax.set_mesh(mesh), axis_rules(rules):
        lowered = jax.jit(prefill_step, in_shardings=in_shardings
                          ).lower(pshapes, bshapes)
    return lowered


def lower_decode(cfg: ArchConfig, shape: ShapeConfig, mesh):
    model = get_model(cfg)
    rules = serve_rules(mesh, shape.global_batch)
    batch_axes = rules["batch"]

    def decode_step(params, batch, cache):
        return model.decode(cfg, params, batch, cache)

    from repro.models import param_specs
    pshapes = param_specs(cfg)
    pspecs = param_partition_specs(cfg, rules)
    bshapes = input_specs(cfg, shape)
    bspecs = batch_partition_specs(cfg, shape, batch_axes)
    cshapes = cache_specs(cfg, shape)
    cspecs = cache_partition_specs(cfg, shape, batch_axes)
    in_shardings = (_named(mesh, pspecs, pshapes),
                    _named(mesh, bspecs, bshapes),
                    _named(mesh, cspecs, cshapes))
    with jax.set_mesh(mesh), axis_rules(rules):
        lowered = jax.jit(decode_step, in_shardings=in_shardings
                          ).lower(pshapes, bshapes, cshapes)
    return lowered


def _lower_for(cfg, shape, mesh, group: Optional[GroupSpec]):
    if shape.kind == "train":
        n_agents = mesh.shape.get("pod", 1)
        spec = group or GroupSpec(n_agents=n_agents)
        return lower_train(cfg, shape, mesh, spec), spec
    if shape.kind == "prefill":
        return lower_prefill(cfg, shape, mesh), None
    return lower_decode(cfg, shape, mesh), None


# -- depth extrapolation -------------------------------------------------
# ``cost_analysis`` / the HLO parse see scan bodies ONCE, and fully
# unrolling 60–80-layer models is compile-time-prohibitive. Layer
# stacks are uniform, so every cost metric is affine in depth: compile
# two shallow *unrolled* variants (d1, d2 scanned layers / super-
# blocks), fit the line, evaluate at the full depth. Exact for FLOPs,
# bytes and collective bytes; memory comes from the full scanned
# compile (the artifact that must fit).
_D1, _D2 = 1, 3


def _depth_of(cfg: ArchConfig) -> int:
    if cfg.hybrid is not None:
        return cfg.hybrid.n_super_blocks
    return cfg.n_layers - cfg.first_k_dense


def _with_depth(cfg: ArchConfig, d: int) -> ArchConfig:
    if cfg.hybrid is not None:
        return cfg.with_(hybrid=dataclasses.replace(
            cfg.hybrid, n_super_blocks=d))
    return cfg.with_(n_layers=d + cfg.first_k_dense)


def _cost_metrics(compiled) -> Dict[str, float]:
    cost = compiled.cost_analysis()
    from repro.roofline.hlo import collective_bytes
    coll = collective_bytes(compiled.as_text())
    out = {"flops": float(cost.get("flops", 0.0)),
           "bytes": float(cost.get("bytes accessed", 0.0))}
    for k, v in coll.items():
        out[f"coll_{k}"] = float(v)
    return out


def _extrapolate(m1: Dict[str, float], m2: Dict[str, float],
                 d1: int, d2: int, full: int) -> Dict[str, float]:
    out = {}
    for k in m1:
        # per-depth cost is monotone in depth; cost-analysis jitter at
        # tiny shapes (B=1 decode) can give a negative slope — clamp
        # the SLOPE, keeping at least the shallow measurement
        slope = max((m2[k] - m1[k]) / (d2 - d1), 0.0)
        out[k] = m1[k] + slope * (full - d1)
    return out


def dryrun_pair(arch_id: str, shape_name: str, mesh, *,
                group: Optional[GroupSpec] = None,
                cfg_override: Optional[ArchConfig] = None,
                keep_artifacts: bool = False,
                skip_memory: bool = False) -> DryrunResult:
    """Lower + compile one (arch × shape) pair; return roofline record.

    Three compiles: full depth scanned (memory_analysis — the artifact
    that must fit), plus two shallow unrolled (exact per-depth costs,
    extrapolated to full depth)."""
    shape = INPUT_SHAPES[shape_name]
    cfg = cfg_override or arch_for_shape(get_arch_config(arch_id),
                                         shape_name)
    mesh_name = _mesh_name(mesh)
    chips = mesh.size
    t0 = time.time()
    try:
        # 1) full-depth scanned compile → memory + proof it lowers
        lowered, spec = _lower_for(cfg, shape, mesh, group)
        compiled = lowered.compile()
        mem = None if skip_memory else _memory_dict(compiled)

        # 2+3) shallow unrolled compiles → extrapolated exact costs
        full = _depth_of(cfg)
        d1, d2 = min(_D1, full), min(_D2, full)
        if d2 > d1:
            ms = []
            for d in (d1, d2):
                cfg_d = _with_depth(cfg, d).with_(unroll_layers=True)
                low_d, _ = _lower_for(cfg_d, shape, mesh, group)
                ms.append(_cost_metrics(low_d.compile()))
            metrics = _extrapolate(ms[0], ms[1], d1, d2, full)
        else:
            metrics = _cost_metrics(compiled)

        n_agents = spec.n_agents if spec is not None else 1
        mflops = model_flops(cfg, shape, n_agents)
        # cost_analysis & HLO shapes are per-device (post-partition);
        # scale to global so the spec's  X/(chips·BW)  formulas hold.
        cost = {"flops": metrics["flops"] * chips,
                "bytes accessed": metrics["bytes"] * chips}
        coll = {k[len("coll_"):]: v * chips for k, v in metrics.items()
                if k.startswith("coll_")}
        roof = analyze(arch_id, shape, mesh_name, chips, cost, coll,
                       mflops,
                       bytes_per_device=(mem or {}).get(
                           "total_bytes_per_device"))
        res = DryrunResult(arch=arch_id, shape=shape_name,
                           mesh_name=mesh_name, ok=True, memory=mem,
                           roofline=roof.to_dict(),
                           compile_s=time.time() - t0)
        if keep_artifacts:
            res.lowered = lowered        # type: ignore[attr-defined]
            res.compiled = compiled      # type: ignore[attr-defined]
        return res
    except Exception as e:                      # noqa: BLE001
        import traceback
        return DryrunResult(arch=arch_id, shape=shape_name,
                            mesh_name=mesh_name, ok=False,
                            error=f"{type(e).__name__}: {e}\n"
                                  f"{traceback.format_exc(limit=8)}",
                            compile_s=time.time() - t0)
