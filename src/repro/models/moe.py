"""Mixture-of-Experts layer with capacity-based scatter dispatch.

Dispatch is computed *per batch row* so that the position-in-expert
cumsum never crosses the data-parallel sharding boundary (no implicit
cross-device scan); experts are sharded over the "model" mesh axis
(expert parallelism) so GSPMD turns the dispatch scatter / combine
gather into the MoE all-to-all pattern.

Top-k routing with normalised gates (Qwen3 / DeepSeek style), capacity
factor with token dropping, load-balance auxiliary loss and router
z-loss. ``MoEConfig.scoring="sigmoid"`` routes as DeepSeek-V3's
noaux_tc: the top k of the sigmoid scores plus a correction bias
(``router_bias``) are chosen, and the gates are the chosen scores,
normalised to sum 1 (``norm_topk``), times ``routed_scaling``.

Held experts (``MoEConfig.n_held > 0``): the layer holds one device's
share of the experts, ``first_held`` to ``first_held + n_held - 1``.
The router still scores all ``n_experts`` and the top k are chosen
over all of them; the layer computes only the pairs routed to its
held experts, and the others add nothing here (their device adds
them). Dispatch drops no token: the pairs are sorted by held expert
and run through a grouped product (``jax.lax.ragged_dot``) sized for
the worst case, every token on every held expert it chose. The layer
counts the token-expert pairs each held expert got.
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.common.sharding import shard
from repro.models.common import dense_init
from repro.models.mlp import init_swiglu, swiglu


def init_moe(cfg, key):
    moe = cfg.moe
    kr, ke, ks = jax.random.split(key, 3)
    E, F, Ne = cfg.d_model, moe.expert_ff, moe.n_experts
    dt = cfg.dtype("param")
    kg, ku, kd = jax.random.split(ke, 3)
    Nh = moe.n_held or Ne
    p = {
        "router": dense_init(kr, (E, Ne), dt),
        "experts": {
            "w_gate": dense_init(kg, (Nh, E, F), dt),
            "w_up": dense_init(ku, (Nh, E, F), dt),
            "w_down": dense_init(kd, (Nh, F, E), dt),
        },
    }
    if moe.router_bias:
        # selection-only correction bias; starts at zero
        p["router_bias"] = jnp.zeros((Ne,), dt)
    if moe.n_shared:
        # shared (always-on) experts fused into one wider SwiGLU
        p["shared"] = init_swiglu(ks, E, F * moe.n_shared, dt)
    return p


def _expert_swiglu(experts, buf, cdt):
    """buf: (B, Ne, C, E) → (B, Ne, C, E) through per-expert SwiGLU."""
    wg = experts["w_gate"].astype(cdt)
    wu = experts["w_up"].astype(cdt)
    wd = experts["w_down"].astype(cdt)
    g = jnp.einsum("bxcd,xdf->bxcf", buf, wg)
    u = jnp.einsum("bxcd,xdf->bxcf", buf, wu)
    h = jax.nn.silu(g) * u
    return jnp.einsum("bxcf,xfd->bxcd", h, wd)


def _dispatch_indices(e_flat, gate_flat, Ne: int, C: int, k: int):
    """Sort-based capacity dispatch (per batch row).

    e_flat: (B, T=S·k) expert ids; gate_flat: (B, T) gate weights.
    Returns token_idx (B, Ne, C) int32 — the flat-token index occupying
    each (expert, capacity-slot) — plus w (B, Ne, C) gate weights
    (0 where the slot is empty) and src (B, Ne, C) source positions
    (token_idx // k). Slot order is the token's rank within its expert
    in original flat order (identical to the cumsum-scatter semantics:
    overflow beyond C is dropped).
    """
    B, T = e_flat.shape
    order = jnp.argsort(e_flat, axis=1, stable=True)     # (B, T)
    sorted_e = jnp.take_along_axis(e_flat, order, axis=1)
    start = jax.vmap(
        lambda se: jnp.searchsorted(se, jnp.arange(Ne), side="left")
    )(sorted_e)                                          # (B, Ne)
    end = jax.vmap(
        lambda se: jnp.searchsorted(se, jnp.arange(Ne), side="right")
    )(sorted_e)
    pos = start[:, :, None] + jnp.arange(C)[None, None, :]
    valid = pos < end[:, :, None]                        # (B, Ne, C)
    token_idx = jnp.take_along_axis(
        order, jnp.minimum(pos, T - 1).reshape(B, Ne * C),
        axis=1).reshape(B, Ne, C)
    w = jnp.take_along_axis(
        gate_flat, token_idx.reshape(B, Ne * C),
        axis=1).reshape(B, Ne, C) * valid
    return token_idx, w.astype(gate_flat.dtype), token_idx // k, valid


def _moe_expert_parallel(cfg, p, x, gate_flat, e_flat, model_axis: str):
    """Expert-parallel MoE under shard_map over ``model_axis``.

    Dispatch is a LOCAL gather (each device pulls the tokens its
    experts own — x is replicated over the model axis, so no
    collective); combine is a local scatter-add into a (B, S, E)
    partial followed by ONE psum over the model axis — the minimal
    GSPMD-expressible combine (vs. all-reducing the (B, Ne, C, E)
    dispatch buffer, which is what the dense scatter formulation
    lowers to).
    """
    moe = cfg.moe
    B, S, E = x.shape
    Ne, k = moe.n_experts, moe.top_k
    C = max(1, int(moe.capacity_factor * S * k / Ne))
    cdt = cfg.dtype("compute")
    token_idx, w, src, _ = _dispatch_indices(e_flat, gate_flat, Ne, C, k)

    from jax.sharding import PartitionSpec as P

    def local(x_l, experts_l, idx_l, w_l, src_l):
        # x_l: (B, S, E) [replicated over model]; experts_l leaves
        # (Ne/m, E, F); idx_l/w_l/src_l: (B, Ne/m, C). The "data" axis
        # is auto inside this manual-on-model region — constrain the
        # batch dim explicitly so GSPMD keeps the expert compute
        # data-sharded instead of replicating it per device.
        nloc = idx_l.shape[1]
        bidx = jnp.arange(B)[:, None, None]
        buf = x_l[bidx, src_l].astype(cdt)               # (B,nloc,C,E)
        buf = shard(buf, "batch", None, None, None)
        buf = buf * (w_l[..., None] != 0).astype(cdt)
        y = _expert_swiglu(experts_l, buf, cdt)          # (B,nloc,C,E)
        y = shard(y, "batch", None, None, None)
        contrib = y.astype(jnp.float32) * w_l[..., None].astype(
            jnp.float32)
        # fp32 combine: exact cross-expert accumulation, and bf16
        # psum crashes XLA:CPU ("invalid binary instruction copy")
        out_l = jnp.zeros((B, S, E), jnp.float32)
        out_l = out_l.at[bidx, src_l].add(contrib)
        out_l = shard(out_l, "batch", None, None)
        return jax.lax.psum(out_l, model_axis)

    # fp32 across the shard_map boundary: XLA:CPU CHECK-crashes on
    # bf16 psum, and shard_map's transpose of the replicated-x input /
    # psum'd output inserts psums of their COTANGENTS — keeping both
    # sides fp32 keeps every fwd+bwd psum fp32 (and exact).
    out = jax.shard_map(
        local,
        in_specs=(P(), jax.tree.map(lambda _: P(model_axis),
                                    p["experts"]),
                  P(None, model_axis, None), P(None, model_axis, None),
                  P(None, model_axis, None)),
        out_specs=P(),
        axis_names={model_axis},
        check_vma=False,   # jax 0.8: psum-invariant VMA check chokes
    )(x.astype(jnp.float32), p["experts"], token_idx,
      w.astype(jnp.float32), src)
    return out.astype(cdt)


def _moe_dense(cfg, p, x, gate_flat, e_flat):
    """Reference dense scatter dispatch (single-device / no-mesh)."""
    moe = cfg.moe
    B, S, E = x.shape
    Ne, k = moe.n_experts, moe.top_k
    cdt = cfg.dtype("compute")
    C = max(1, int(moe.capacity_factor * S * k / Ne))
    onehot = jax.nn.one_hot(e_flat, Ne, dtype=jnp.int32)
    pos_all = jnp.cumsum(onehot, axis=1) - 1             # (B, S·k, Ne)
    pos = jnp.take_along_axis(pos_all, e_flat[..., None], axis=2)[..., 0]
    keep = pos < C
    slot = jnp.where(keep, pos, C)                       # overflow slot C

    x_rep = jnp.repeat(x, k, axis=1)                     # (B, S·k, E)
    bidx = jnp.arange(B)[:, None] * jnp.ones_like(e_flat)
    buf = jnp.zeros((B, Ne, C + 1, E), cdt)
    buf = buf.at[bidx, e_flat, slot].set(x_rep.astype(cdt))
    buf = shard(buf, "batch", "experts", None, None)
    y_buf = _expert_swiglu(p["experts"], buf[:, :, :C], cdt)
    y_buf = jnp.pad(y_buf, ((0, 0), (0, 0), (0, 1), (0, 0)))
    out_rep = y_buf[bidx, e_flat, slot]                  # (B, S·k, E)
    w = (gate_flat * keep).astype(cdt)
    return jnp.sum((out_rep * w[..., None]).reshape(B, S, k, E), axis=2)


def _expert_axis():
    """The physical mesh axis experts shard over, if model code is
    running under installed sharding rules + a mesh context."""
    from repro.common.sharding import get_rules
    rules = get_rules()
    if not rules:
        return None
    axis = rules.get("experts")
    if axis is None:
        return None
    if axis not in jax.sharding.get_abstract_mesh().axis_names:
        return None
    return axis


def _route(moe, p, x, cdt):
    """Router scores, the chosen experts and their gates.
    Returns (logits, probs, gate, gate_idx); probs, the softmax the
    load-balance loss reads, is None for sigmoid routing."""
    if moe.scoring == "sigmoid":
        # the router in float32, as DeepSeek-V3 computes it: selection
        # is discontinuous, so it should not hang on bf16 rounding
        logits = jnp.dot(x.astype(jnp.float32),
                         p["router"].astype(jnp.float32),
                         precision=jax.lax.Precision.HIGHEST)
        scores = jax.nn.sigmoid(logits)
        choice = scores
        if moe.router_bias:
            choice = scores + jax.lax.stop_gradient(
                p["router_bias"].astype(jnp.float32))
        _, gate_idx = jax.lax.top_k(choice, moe.top_k)
        gate = jnp.take_along_axis(scores, gate_idx, axis=-1)
        if moe.norm_topk:
            gate = gate / (jnp.sum(gate, axis=-1, keepdims=True) + 1e-20)
        probs = None
    else:
        logits = (x @ p["router"].astype(jnp.float32).astype(cdt)
                  ).astype(jnp.float32)                  # (B,S,Ne)
        probs = jax.nn.softmax(logits, axis=-1)
        gate, gate_idx = jax.lax.top_k(probs, moe.top_k)  # (B,S,k)
        if moe.norm_topk:
            gate = gate / jnp.sum(gate, axis=-1, keepdims=True)
    if moe.routed_scaling != 1.0:
        gate = gate * moe.routed_scaling
    return logits, probs, gate, gate_idx


def _moe_held(cfg, p, x, gate, gate_idx):
    """Dropless dispatch onto the held experts. x: (B, S, E); gate,
    gate_idx: (B, S, k) over all experts. Returns the (B, S, E) sum of
    the held experts' gated outputs and the pairs each held expert got
    ((n_held,) int32)."""
    moe = cfg.moe
    B, S, E = x.shape
    k, Nh = moe.top_k, moe.n_held
    cdt = cfg.dtype("compute")
    T = B * S
    local = gate_idx.reshape(T * k) - moe.first_held
    held = (local >= 0) & (local < Nh)
    key = jnp.where(held, local, Nh).astype(jnp.int32)
    sizes = jnp.bincount(key, length=Nh + 1)[:Nh].astype(jnp.int32)
    # a token chooses an expert at most once, so the held experts get
    # at most min(k, n_held) pairs of each token: room for every pair
    rows = T * min(k, Nh)
    order = jnp.argsort(key, stable=True)[:rows]         # held first
    tok = order // k
    valid = (jnp.arange(rows) < jnp.sum(sizes))[:, None]
    w = gate.reshape(T * k)[order]
    ex = p["experts"]
    live = (sizes > 0)[:, None, None]

    def grouped(a, name):
        # the TPU's ragged dot leaves what no group covers unwritten:
        # the rows outside every group, and an empty group's weight
        # gradient. Select them away, here and (through the select's
        # transpose) in the backward pass
        w_ = jnp.where(live, ex[name].astype(cdt), 0)
        return jnp.where(valid, jax.lax.ragged_dot(a, w_, sizes), 0)

    xs = jnp.where(valid, x.reshape(T, E)[tok], 0).astype(cdt)
    with jax.named_scope("ddal.experts"):
        h = jax.nn.silu(grouped(xs, "w_gate")) * grouped(xs, "w_up")
        y = grouped(h, "w_down")
    out = jnp.zeros((T, E), jnp.float32).at[tok].add(
        y.astype(jnp.float32) * w[:, None])
    return out.reshape(B, S, E).astype(cdt), sizes


def moe_apply(cfg, p, x) -> Tuple[jnp.ndarray, jnp.ndarray,
                                  Dict[str, jnp.ndarray]]:
    """x: (B, S, E) → (out, aux_loss, stats).

    Three dispatch engines. Two with identical drop semantics (tested):
      * dense scatter (reference) — single-device/no-mesh path;
      * expert-parallel shard_map (gather dispatch + psum combine) —
        selected automatically under a mesh whose rules shard
        "experts"; cuts the MoE collective term ~500×.
    And, where the layer holds a share of the experts
    (``MoEConfig.n_held``), the dropless held-experts dispatch, whose
    ``stats["held_pairs"]`` is the (n_held,) count of pairs each held
    expert got; ``stats`` is empty otherwise.
    """
    moe = cfg.moe
    B, S, E = x.shape
    Ne, k = moe.n_experts, moe.top_k
    cdt = cfg.dtype("compute")

    logits, probs, gate, gate_idx = _route(moe, p, x, cdt)
    stats = {}
    if moe.n_held:
        out, stats["held_pairs"] = _moe_held(cfg, p, x, gate, gate_idx)
    else:
        e_flat = gate_idx.reshape(B, S * k)              # (B, S·k)
        gate_flat = gate.reshape(B, S * k)
        axis = None if cfg.moe_dispatch == "dense" else _expert_axis()
        if axis is not None and Ne % jax.sharding.get_abstract_mesh(
                ).shape[axis] == 0:
            out = _moe_expert_parallel(cfg, p, x, gate_flat, e_flat, axis)
        else:
            out = _moe_dense(cfg, p, x, gate_flat, e_flat)

    if moe.n_shared:
        out = out + swiglu(p["shared"], x, cdt)

    # ---- auxiliary losses --------------------------------------------
    aux = jnp.float32(0.0)
    if moe.aux_loss:
        # load balance: Ne * Σ_e (fraction dispatched)·(mean router prob)
        frac = jnp.mean(jax.nn.one_hot(gate_idx, Ne, dtype=jnp.float32),
                        axis=(0, 1, 2)) * k
        pmean = jnp.mean(probs, axis=(0, 1))
        aux = aux + moe.aux_loss * Ne * jnp.sum(frac * pmean)
    if moe.router_zloss:
        aux = aux + moe.router_zloss * jnp.mean(
            jnp.square(jax.scipy.special.logsumexp(logits, axis=-1)))
    return out, aux, stats
