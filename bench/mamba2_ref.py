"""Plain reference of the Mamba-2 language model (arXiv:2405.21060), and
the weights the benchmark makes for it.

Written from the paper, not from the program under test, and importing
nothing of it. Each layer: RMSNorm, input projections for z, x, B, C
and dt, a depthwise causal convolution with SiLU on x, B and C, the
SSD mixing in its quadratic (masked-attention) form over the whole
sequence, the D skip, the gated RMSNorm ``norm(y * silu(z))``, and the
output projection, added to the residual. The head is a final RMSNorm
and the projection to the vocabulary, tied to the embedding where the
configuration ties them. The table has ``vocab_rows`` rows: the token
ids padded to ``pad_vocab_size_multiple``. Departures from the
published model: none in the mathematics; the five input projections
are kept as separate matrices, which is the same map as the fused one.

Everything runs in float32. ``mm`` is the one matrix product the
projections use, so that a control can put a lower precision there;
callers trace the reference under ``jax.default_matmul_precision
("highest")``.

The weights are a pytree with the layout the program loads
(``embed``, ``lm_head`` if untied, ``final_norm`` and ``layers``
stacked on a leading layer axis, with a leading agent axis above
that), made on the device in one jitted call from the seed.
"""
from __future__ import annotations

import math
from typing import Callable, Dict

import jax
import jax.numpy as jnp

f32 = jnp.float32


def vocab_rows(conf: dict) -> int:
    """Rows of the embedding table: the token ids padded up to a
    multiple of ``pad_vocab_size_multiple``."""
    m = conf.get("pad_vocab_size_multiple", 1)
    return -(-conf["vocab_size"] // m) * m


def dims(conf: dict) -> Dict[str, int]:
    s = conf["ssm_cfg"]
    d_inner = s["expand"] * conf["d_model"]
    return {"E": conf["d_model"], "DI": d_inner,
            "H": d_inner // s["headdim"], "P": s["headdim"],
            "N": s["d_state"], "G": s["ngroups"], "K": s["d_conv"],
            "L": conf["n_layer"], "V": vocab_rows(conf)}


def seed_key(lo, hi):
    """One PRNG key from the two uint32 words of the run's seed."""
    k = jax.random.PRNGKey(0)
    return jax.random.fold_in(jax.random.fold_in(k, lo), hi)


def leaf_shapes(conf: dict) -> Dict[str, tuple]:
    """Per-agent shape of every weight, by its path in the pytree."""
    d = dims(conf)
    E, DI, H, K, L, V = d["E"], d["DI"], d["H"], d["K"], d["L"], d["V"]
    BC = d["G"] * d["N"]
    head = {} if conf["tie_embeddings"] else {"lm_head": (E, V)}
    return {
        **head, "embed": (V, E), "final_norm": (E,),
        "layers/ln": (L, E),
        "layers/mamba/w_z": (L, E, DI), "layers/mamba/w_x": (L, E, DI),
        "layers/mamba/w_B": (L, E, BC), "layers/mamba/w_C": (L, E, BC),
        "layers/mamba/w_dt": (L, E, H),
        "layers/mamba/conv_x/w": (L, K, DI), "layers/mamba/conv_x/b": (L, DI),
        "layers/mamba/conv_B/w": (L, K, BC), "layers/mamba/conv_B/b": (L, BC),
        "layers/mamba/conv_C/w": (L, K, BC), "layers/mamba/conv_C/b": (L, BC),
        "layers/mamba/A_log": (L, H), "layers/mamba/D": (L, H),
        "layers/mamba/dt_bias": (L, H), "layers/mamba/norm_w": (L, DI),
        "layers/mamba/out_proj": (L, DI, E),
    }


def _nest(flat: dict) -> dict:
    out: dict = {}
    for path, v in flat.items():
        node = out
        parts = path.split("/")
        for p in parts[:-1]:
            node = node.setdefault(p, {})
        node[parts[-1]] = v
    return out


def flatten(tree: dict, prefix: str = "") -> dict:
    out = {}
    for k, v in tree.items():
        path = f"{prefix}{k}"
        if isinstance(v, dict):
            out.update(flatten(v, path + "/"))
        else:
            out[path] = v
    return out


def init_agent(conf: dict, lo, hi, agent) -> dict:
    """Seeded float32 weights of one agent."""
    d = dims(conf)
    key = jax.random.fold_in(seed_key(lo, hi), agent)
    flat = {}
    for i, (path, shape) in enumerate(sorted(leaf_shapes(conf).items())):
        k = jax.random.fold_in(key, i)
        name = path.split("/")[-1]
        if path == "embed":
            v = 0.02 * jax.random.normal(k, shape, f32)
        elif name in ("ln", "final_norm", "norm_w", "D"):
            v = jnp.ones(shape, f32)
        elif name == "b":
            v = jnp.zeros(shape, f32)
        elif name == "w" and "conv" in path:
            v = 0.3 * jax.random.normal(k, shape, f32)
        elif name == "A_log":
            v = jnp.broadcast_to(jnp.log(jnp.linspace(1.0, 16.0, d["H"])),
                                 shape).astype(f32)
        elif name == "dt_bias":
            dt = jnp.exp(jax.random.uniform(k, shape, f32, math.log(1e-3),
                                            math.log(1e-1)))
            v = dt + jnp.log(-jnp.expm1(-dt))        # softplus^-1(dt)
        else:                                       # projections
            v = jax.random.normal(k, shape, f32) / math.sqrt(shape[-2])
        flat[path] = v
    return _nest(flat)


def init_params(conf: dict, lo, hi, n_agents: int) -> dict:
    """Seeded float32 weights of ``n_agents`` agents (leading axis)."""
    return jax.vmap(lambda a: init_agent(conf, lo, hi, a))(
        jnp.arange(n_agents))


def rms_norm(x, w, eps: float):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def dot(a, b):
    return jnp.dot(a, b, preferred_element_type=f32)


def _to_f8(x, dtype):
    """x scaled by its largest magnitude into ``dtype``'s range and
    rounded to it, with the scale that undoes it."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / float(
        jnp.finfo(dtype).max)
    # float8 values are exact in bfloat16, which every backend's matrix
    # unit takes: the products below are those of the float8 operands
    return (x / scale).astype(dtype).astype(jnp.bfloat16), scale


def f8_round(x):
    """x rounded to float8 e4m3 under a per-tensor scale, as float32."""
    q, scale = _to_f8(x, jnp.float8_e4m3fn)
    return q.astype(f32) * scale


@jax.custom_vjp
def fp8_dot(a, b):
    """The control's product, as float8 training recipes compute it:
    operands in e4m3 and the backward pass's incoming gradient in e5m2,
    each scaled per tensor by its largest magnitude, with float32
    accumulation. a: (..., k), b: (k, n)."""
    return _fp8_fwd(a, b)[0]


def _fp8_fwd(a, b):
    qa, sa = _to_f8(a, jnp.float8_e4m3fn)
    qb, sb = _to_f8(b, jnp.float8_e4m3fn)
    out = jnp.dot(qa, qb, preferred_element_type=f32) * (sa * sb)
    return out, (qa, sa, qb, sb)


def _fp8_bwd(res, g):
    qa, sa, qb, sb = res
    qg, sg = _to_f8(g, jnp.float8_e5m2)
    da = jnp.einsum("...n,kn->...k", qg, qb,
                    preferred_element_type=f32) * (sg * sb)
    db = jnp.einsum("...k,...n->kn", qa, qg,
                    preferred_element_type=f32) * (sa * sg)
    return da, db


fp8_dot.defvjp(_fp8_fwd, _fp8_bwd)


def causal_conv(x, w, b):
    """Depthwise causal convolution over time. x: (B, S, C), w: (K, C)."""
    K = w.shape[0]
    xp = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    S = x.shape[1]
    return sum(xp[:, i:i + S] * w[i] for i in range(K)) + b


def ssd_quadratic(x, dt, A, B, C):
    """y_t = sum_{s<=t} (C_t . B_s) exp(sum_{s<r<=t} dt_r A) dt_s x_s.

    x: (b, S, H, P), dt: (b, S, H), A: (H,), B, C: (b, S, G, N).
    """
    b, S, H, P = x.shape
    G = B.shape[2]
    cs = jnp.cumsum(dt * A, axis=1)                      # (b, S, H)
    diff = cs[:, :, None, :] - cs[:, None, :, :]         # (b, T, S, H)
    causal = jnp.tril(jnp.ones((S, S), bool))[None, :, :, None]
    decay = jnp.exp(jnp.where(causal, diff, -jnp.inf))
    cb = jnp.einsum("btgn,bsgn->btsg", C, B)             # (b, T, S, G)
    cb = jnp.repeat(cb, H // G, axis=3)                  # heads by group
    m = cb * decay * dt[:, None, :, :]
    return jnp.einsum("btsh,bshp->bthp", m, x)


def layer(conf: dict, p: dict, x, mm: Callable):
    d = dims(conf)
    eps = conf["norm_epsilon"]
    b, S, _ = x.shape
    h = rms_norm(x, p["ln"], eps)
    m = p["mamba"]
    z = mm(h, m["w_z"])
    xs = jax.nn.silu(causal_conv(mm(h, m["w_x"]), m["conv_x"]["w"],
                                 m["conv_x"]["b"]))
    Bs = jax.nn.silu(causal_conv(mm(h, m["w_B"]), m["conv_B"]["w"],
                                 m["conv_B"]["b"]))
    Cs = jax.nn.silu(causal_conv(mm(h, m["w_C"]), m["conv_C"]["w"],
                                 m["conv_C"]["b"]))
    dt = jax.nn.softplus(mm(h, m["w_dt"]) + m["dt_bias"])
    A = -jnp.exp(m["A_log"])
    xh = xs.reshape(b, S, d["H"], d["P"])
    y = ssd_quadratic(xh, dt, A, Bs.reshape(b, S, d["G"], d["N"]),
                      Cs.reshape(b, S, d["G"], d["N"]))
    y = (y + m["D"][:, None] * xh).reshape(b, S, d["DI"])
    y = rms_norm(y * jax.nn.silu(z), m["norm_w"], eps)
    return x + mm(y, m["out_proj"])


def logits(conf: dict, p: dict, tokens, mm: Callable = dot):
    """One agent's logits. tokens: (b, S) int32 -> (b, S, V) float32."""
    x = p["embed"][tokens]

    def body(xc, lp):
        return jax.checkpoint(lambda xc_, lp_: layer(conf, lp_, xc_, mm))(
            xc, lp), None

    x, _ = jax.lax.scan(body, x, p["layers"])
    x = rms_norm(x, p["final_norm"], conf["norm_epsilon"])
    return mm(x, p["embed"].T if conf["tie_embeddings"] else p["lm_head"])


def loss(conf: dict, p: dict, tokens, labels, mm: Callable = dot):
    """Mean next-token cross-entropy over every position."""
    lg = logits(conf, p, tokens, mm)
    lz = jax.scipy.special.logsumexp(lg, axis=-1)
    gold = jnp.take_along_axis(lg, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(lz - gold)
