"""Plain reference of kanana-2-30b-a3b's layers (DeepSeek-V3's
equations, arXiv:2412.19437), cut to one chip's share, and the weights
the benchmark makes for it.

Written from the published equations, not from the program under
test, and importing nothing of it. Each layer: RMSNorm, latent
attention, RMSNorm, then layer 0's dense SwiGLU or an expert layer.

- Latent attention (queries not compressed): q = h Wq per head, split
  into a 128-wide part and a 64-wide rotary part; the latent
  c = RMSNorm((h Wdkv)[:512]) and one shared rotary key
  (h Wdkv)[512:]; per head k = c Wuk, v = c Wuv. The rotary parts turn
  each channel pair (2i, 2i+1) by position × θ^(-2i/64) (interleaved
  pairs, ``rope_interleave``). Scores (q·k + q_rot·k_rot) / √192 under
  a causal mask, softmax, the weighted v, and the output projection.
- Expert layer: router scores s = sigmoid(h Wr) over all 128 experts,
  in float32; the top 6 of s + bias are chosen; each chosen expert's
  gate is its s over the sum of the 6 chosen s, times 2.448. Each held
  expert is a SwiGLU run on the tokens it was chosen by, gathered with
  room for every token (no capacity, nothing dropped), and scattered
  back times its gate. The 2 shared experts are one SwiGLU of width
  1536 on every token.

The share: the layer holds the experts ``first_held_expert`` to
``first_held_expert + n_routed_experts - 1`` of ``router_outputs``;
the others add nothing here, as in the program. Departures from the
published model: none in the mathematics. ``kv_b_proj`` is kept as
two matrices (Wuk, Wuv), the same map.

Everything runs in float32. ``mm`` is the one matrix product the
projections use, so that a control can put a lower precision there;
the router's product stays float32. Callers trace the reference under
``jax.default_matmul_precision("highest")``.

The weights are a pytree with the layout the program loads
(``embed``, ``lm_head``, ``final_norm``, ``layer0`` and ``layers``
stacked on a leading layer axis, with a leading agent axis above
that), made on the device in one jitted call from the seed.
"""
from __future__ import annotations

import math
from typing import Callable, Dict

import jax
import jax.numpy as jnp

from mamba2_ref import dot, rms_norm, seed_key

f32 = jnp.float32


def dims(conf: dict) -> Dict[str, int]:
    return {"E": conf["hidden_size"], "H": conf["num_attention_heads"],
            "DN": conf["qk_nope_head_dim"], "DR": conf["qk_rope_head_dim"],
            "DV": conf["v_head_dim"], "R": conf["kv_lora_rank"],
            "F": conf["moe_intermediate_size"],
            "FD": conf["intermediate_size"],
            "FS": conf["n_shared_experts"] * conf["moe_intermediate_size"],
            "NE": conf["router_outputs"], "NH": conf["n_routed_experts"],
            "K": conf["num_experts_per_tok"],
            "L": conf["num_hidden_layers"] - conf["first_k_dense_replace"],
            "V": conf["vocab_size"]}


def _attn_shapes(d, lead=()) -> Dict[str, tuple]:
    E, H, R = d["E"], d["H"], d["R"]
    return {"wq": lead + (E, H * (d["DN"] + d["DR"])),
            "w_dkv": lead + (E, R + d["DR"]), "ln_ckv": lead + (R,),
            "w_uk": lead + (R, H * d["DN"]), "w_uv": lead + (R, H * d["DV"]),
            "wo": lead + (H * d["DV"], E)}


def _swiglu_shapes(E, F, lead=()) -> Dict[str, tuple]:
    return {"w_gate": lead + (E, F), "w_up": lead + (E, F),
            "w_down": lead + (F, E)}


def leaf_shapes(conf: dict) -> Dict[str, tuple]:
    """Per-agent shape of every weight, by its path in the pytree."""
    if conf["first_k_dense_replace"] != 1:
        raise ValueError("the reference holds one leading dense layer")
    d = dims(conf)
    E, L = d["E"], d["L"]
    out = {"embed": (d["V"], E), "lm_head": (E, d["V"]),
           "final_norm": (E,), "layer0/ln1": (E,), "layer0/ln2": (E,),
           "layers/ln1": (L, E), "layers/ln2": (L, E),
           "layers/moe/router": (L, E, d["NE"]),
           "layers/moe/router_bias": (L, d["NE"])}
    out.update({f"layer0/attn/{k}": v for k, v in _attn_shapes(d).items()})
    out.update({f"layers/attn/{k}": v
                for k, v in _attn_shapes(d, (L,)).items()})
    out.update({f"layer0/mlp/{k}": v
                for k, v in _swiglu_shapes(E, d["FD"]).items()})
    out.update({f"layers/moe/shared/{k}": v
                for k, v in _swiglu_shapes(E, d["FS"], (L,)).items()})
    out.update({f"layers/moe/experts/{k}": v
                for k, v in _swiglu_shapes(E, d["F"], (L, d["NH"])).items()})
    return out


def _nest(flat: dict) -> dict:
    out: dict = {}
    for path, v in flat.items():
        *parents, leaf = path.split("/")
        node = out
        for p in parents:
            node = node.setdefault(p, {})
        node[leaf] = v
    return out


def init_agent(conf: dict, lo, hi, agent) -> dict:
    """Seeded float32 weights of one agent: norms 1, the correction
    bias 0, the embedding N(0, 0.02²), every product's matrix
    N(0, 1/fan-in)."""
    key = jax.random.fold_in(seed_key(lo, hi), agent)
    flat = {}
    for i, (path, shape) in enumerate(sorted(leaf_shapes(conf).items())):
        k = jax.random.fold_in(key, i)
        name = path.split("/")[-1]
        if path == "embed":
            v = 0.02 * jax.random.normal(k, shape, f32)
        elif name in ("ln1", "ln2", "ln_ckv", "final_norm"):
            v = jnp.ones(shape, f32)
        elif name == "router_bias":
            v = jnp.zeros(shape, f32)
        else:
            v = jax.random.normal(k, shape, f32) / math.sqrt(shape[-2])
        flat[path] = v
    return _nest(flat)


def init_params(conf: dict, lo, hi, n_agents: int) -> dict:
    """Seeded float32 weights of ``n_agents`` agents (leading axis)."""
    return jax.vmap(lambda a: init_agent(conf, lo, hi, a))(
        jnp.arange(n_agents))


def rotate_pairs(x, positions, theta: float):
    """Turn each channel pair (2i, 2i+1) of x (..., S, [H,] D) by
    position × θ^(-2i/D); positions (b, S)."""
    D = x.shape[-1]
    freq = theta ** (-jnp.arange(0, D, 2, dtype=f32) / D)
    ang = positions[..., None].astype(f32) * freq         # (b, S, D/2)
    if x.ndim == 4:
        ang = ang[:, :, None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos],
                     axis=-1).reshape(x.shape)


def attention(conf: dict, p: dict, h, positions, mm: Callable):
    d = dims(conf)
    b, S, _ = h.shape
    H, DN, DR, R = d["H"], d["DN"], d["DR"], d["R"]
    q = mm(h, p["wq"]).reshape(b, S, H, DN + DR)
    kv = mm(h, p["w_dkv"])
    c = rms_norm(kv[..., :R], p["ln_ckv"], conf["rms_norm_eps"])
    q_rot = rotate_pairs(q[..., DN:], positions, conf["rope_theta"])
    k_rot = rotate_pairs(kv[..., R:], positions, conf["rope_theta"])
    k = mm(c, p["w_uk"]).reshape(b, S, H, DN)
    v = mm(c, p["w_uv"]).reshape(b, S, H, d["DV"])
    scores = (jnp.einsum("bqhd,bkhd->bhqk", q[..., :DN], k)
              + jnp.einsum("bqhd,bkd->bhqk", q_rot, k_rot)) / math.sqrt(
                  DN + DR)
    causal = positions[:, None, :, None] >= positions[:, None, None, :]
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs, v).reshape(b, S, -1)
    return mm(out, p["wo"])


def swiglu(p: dict, x, mm: Callable):
    return mm(jax.nn.silu(mm(x, p["w_gate"])) * mm(x, p["w_up"]),
              p["w_down"])


def route(conf: dict, p: dict, h):
    """(chosen expert ids, gates), each (T, k), for the (T, E) tokens h."""
    s = jax.nn.sigmoid(jnp.dot(h, p["router"], precision="highest"))
    _, idx = jax.lax.top_k(s + p["router_bias"], conf["num_experts_per_tok"])
    gate = jnp.take_along_axis(s, idx, axis=-1)
    if conf["norm_topk_prob"]:
        gate = gate / (jnp.sum(gate, axis=-1, keepdims=True) + 1e-20)
    return idx, gate * conf["routed_scaling_factor"]


def held_experts(conf: dict, p: dict, h, mm: Callable):
    """The held experts' gated outputs, summed, for the (T, E) tokens
    h, and the (n_held,) count of tokens each was chosen by."""
    T = h.shape[0]
    idx, gate = route(conf, p, h)
    out = jnp.zeros_like(h)
    counts = []
    for j in range(conf["n_routed_experts"]):
        hit = idx == conf["first_held_expert"] + j               # (T, k)
        g = jnp.sum(jnp.where(hit, gate, 0.0), axis=-1)          # (T,)
        chosen = jnp.any(hit, axis=-1)
        n = jnp.sum(chosen)
        # room for every token: nothing is dropped
        tok = jnp.nonzero(chosen, size=T, fill_value=0)[0]
        w = jnp.where(jnp.arange(T) < n, g[tok], 0.0)
        ex = jax.tree.map(lambda x: x[j], p["experts"])
        out = out.at[tok].add(swiglu(ex, h[tok], mm) * w[:, None])
        counts.append(n)
    return out, jnp.stack(counts)


def moe(conf: dict, p: dict, h, mm: Callable):
    b, S, E = h.shape
    flat = h.reshape(b * S, E)
    out, _ = held_experts(conf, p, flat, mm)
    return (out + swiglu(p["shared"], flat, mm)).reshape(b, S, E)


def layer(conf: dict, p: dict, x, positions, mm: Callable, dense: bool):
    eps = conf["rms_norm_eps"]
    x = x + attention(conf, p["attn"], rms_norm(x, p["ln1"], eps),
                      positions, mm)
    h = rms_norm(x, p["ln2"], eps)
    return x + (swiglu(p["mlp"], h, mm) if dense
                else moe(conf, p["moe"], h, mm))


def logits(conf: dict, p: dict, tokens, mm: Callable = dot):
    """One agent's logits. tokens: (b, S) int32 -> (b, S, V) float32."""
    positions = jnp.broadcast_to(jnp.arange(tokens.shape[1]), tokens.shape)
    x = jax.checkpoint(lambda x_, lp: layer(conf, lp, x_, positions, mm,
                                            True))(p["embed"][tokens],
                                                   p["layer0"])

    def body(xc, lp):
        return jax.checkpoint(lambda x_, lp_: layer(
            conf, lp_, x_, positions, mm, False))(xc, lp), None

    x, _ = jax.lax.scan(body, x, p["layers"])
    x = rms_norm(x, p["final_norm"], conf["rms_norm_eps"])
    return mm(x, p["lm_head"])


def loss(conf: dict, p: dict, tokens, labels, mm: Callable = dot):
    """Mean next-token cross-entropy over every position."""
    lg = logits(conf, p, tokens, mm)
    lz = jax.scipy.special.logsumexp(lg, axis=-1)
    gold = jnp.take_along_axis(lg, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(lz - gold)
