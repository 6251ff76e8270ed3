"""Group training's inputs and its plain reference.

``make_batch`` is the cell's data feed: each agent's token stream is a
walk over the vocabulary in which the next token is the current one
plus one of the agent's own ``branches`` offsets, so next-token
prediction is learnable and the agents' streams differ. Tokens, labels
and positions for every step come from the seed and the step number.

``reference`` follows the program's first three steps in float32 and
plain jax: the model of ``mamba2_ref``, AdamW with global-norm
clipping, and DDAL's streaming eq. 4 (arXiv:2202.05135) over the share
window, with relevance from the cosine of the agents' sketched window
gradients (``sketch``), smoothed by an EMA from the uniform prior.
``gaps`` turns two sets of readings into the numbers that decide
``correct``.
"""
from __future__ import annotations

from typing import Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

import mamba2_ref as M

f32 = jnp.float32
DATA_SALT = 0x7A11


def make_batch(traffic: dict, vocab: int, lo, hi, step) -> dict:
    A, B, S = traffic["agents"], traffic["batch"], traffic["seq"]
    nb = traffic["branches"]
    key = jax.random.fold_in(M.seed_key(lo, hi), DATA_SALT)
    offsets = jax.random.randint(jax.random.fold_in(key, 1), (A, 1, 1, nb),
                                 1, vocab)
    k0, k1 = jax.random.split(jax.random.fold_in(
        jax.random.fold_in(key, 2), step))
    start = jax.random.randint(k0, (A, B, 1), 0, vocab)
    branch = jax.random.randint(k1, (A, B, S, 1), 0, nb)
    hops = jnp.take_along_axis(jnp.broadcast_to(offsets, (A, B, S, nb)),
                               branch, axis=3)[..., 0]
    walk = (start + jnp.concatenate(
        [jnp.zeros((A, B, 1), jnp.int32), jnp.cumsum(hops, axis=2)],
        axis=2)) % vocab
    pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (A, B, S))
    return {"tokens": walk[..., :-1].astype(jnp.int32),
            "labels": walk[..., 1:].astype(jnp.int32), "positions": pos}


# -- per-leaf norms ------------------------------------------------------
def leaf_norms(tree, agents: bool = True) -> Dict[str, jnp.ndarray]:
    """Norm of each weight, stacked layer weights split into their
    layers: path -> (A,) or (A, L) for a tree with a leading agent
    axis, () or (L,) for one agent's tree."""
    out = {}
    lead = int(agents)
    for path, x in M.flatten(tree).items():
        axes = tuple(range(lead + path.startswith("layers/"), x.ndim))
        out[path] = jnp.sqrt(jnp.sum(jnp.square(x.astype(f32)), axis=axes))
    return out


def per_leaf(norms: Dict[str, np.ndarray]) -> Dict[str, np.ndarray]:
    """Flatten to 'path[layer]' -> (A,) host arrays."""
    out = {}
    for path, v in norms.items():
        v = np.asarray(v, np.float64)
        if v.ndim == 2:
            for i in range(v.shape[1]):
                out[f"{path}[{i}]"] = v[:, i]
        else:
            out[path] = v
    return out


# -- the optimizer ---------------------------------------------------------
def clip_global(g, max_norm: float):
    norm = jnp.sqrt(sum(jnp.sum(jnp.square(x)) for x in jax.tree.leaves(g)))
    scale = jnp.minimum(1.0, max_norm / (norm + 1e-6))
    return jax.tree.map(lambda x: x * scale, g)


def adamw_step(opt: dict, p, m, v, g, count: int):
    g = clip_global(g, opt["clip"])
    b1, b2 = opt["b1"], opt["b2"]
    m = jax.tree.map(lambda mm, gg: b1 * mm + (1 - b1) * gg, m, g)
    v = jax.tree.map(lambda vv, gg: b2 * vv + (1 - b2) * gg * gg, v, g)
    bc1, bc2 = 1 - b1 ** count, 1 - b2 ** count
    p = jax.tree.map(lambda pp, mm, vv: pp - opt["lr"] * (
        (mm / bc1) / (jnp.sqrt(vv / bc2) + opt["eps"])
        + opt["weight_decay"] * pp), p, m, v)
    return p, m, v, g


# -- the relevance sketch ----------------------------------------------------
# The configuration's estimator (``grad_cos+sketch``) projects each
# agent's window gradients, read as one vector in the order of the
# weights' sorted paths, through a seeded +-1 matrix S (P, d) and takes
# the cosine of two agents' projections. S[p, j] is the top bit of a
# 32-bit multiply-xorshift hash of (the round's seed, p, j); the
# round's seed mixes the group's seed with the share round's index.
HASH = (0x9E3779B1, 0x85EBCA77, 0xC2B2AE3D)
U32 = 0xFFFFFFFF


def round_seed(seed: int, rnd: int) -> int:
    """The hash's seed in share round ``rnd``."""
    x = (seed * HASH[0] + rnd * HASH[1]) & U32
    x = ((x ^ (x >> 16)) * HASH[2]) & U32
    return x ^ (x >> 13)


def signs(seed, start, count: int, dim: int):
    """Rows ``start`` to ``start + count`` of S, float32 +-1; ``seed``
    and ``start`` are uint32."""
    u = jnp.uint32
    pos = start + jnp.arange(count, dtype=u)[:, None]
    col = jnp.arange(dim, dtype=u)[None, :]
    x = seed + pos * u(HASH[0]) + col * u(HASH[1])
    x = (x ^ (x >> 15)) * u(HASH[1])
    x = (x ^ (x >> 13)) * u(HASH[2])
    x = x ^ (x >> 16)
    return 1.0 - 2.0 * (x >> 31).astype(f32)


def sketch(trees: list, seed, dim: int, block: int = 32768,
           keep: Optional[Callable] = None, cast: Optional[Callable] = None):
    """The (len(trees), dim) projections of one-agent trees, ``block``
    positions at a time. ``keep(i)`` sketches only some leaves and
    ``cast`` rounds the gradients first (for the control and a planted
    fault; the cell's own check uses neither)."""
    acc = jnp.zeros((len(trees), dim), f32)
    offset = 0
    for i, xs in enumerate(zip(*[jax.tree.leaves(t) for t in trees])):
        size = xs[0].size
        if keep is None or keep(i):
            flat = [x.reshape(-1) if cast is None else cast(x.reshape(-1))
                    for x in xs]
            full, tail = divmod(size, block)

            def body(b, a, flat=flat, offset=offset):
                st = b * block
                g = jnp.stack([jax.lax.dynamic_slice_in_dim(x, st, block)
                               for x in flat])
                start = jnp.uint32(offset) + st.astype(jnp.uint32)
                return a + jnp.dot(g, signs(seed, start, block, dim))
            if full:
                acc = jax.lax.fori_loop(0, full, body, acc)
            if tail:
                g = jnp.stack([x[full * block:] for x in flat])
                acc = acc + jnp.dot(g, signs(
                    seed, jnp.uint32(offset + full * block), tail, dim))
        offset += size
    return acc


def relevance_obs(sk: np.ndarray) -> np.ndarray:
    """One share step's relevance observation from the agents' window
    sketches: (1 + cosine) / 2, floored at 1e-3, 1 on the diagonal."""
    nrm = np.linalg.norm(sk, axis=1)
    cos = np.clip(sk @ sk.T / np.outer(nrm, nrm), -1.0, 1.0)
    np.fill_diagonal(cos, 1.0)
    return np.clip(0.5 * (1 + cos), 1e-3, 1.0)


# -- the reference -----------------------------------------------------------
def reference(conf: dict, traffic: dict, lo: int, hi: int,
              mm: Callable = M.dot, rows: Optional[int] = None,
              exchange: bool = True, sketch_keep: Optional[Callable] = None,
              sketch_cast: Optional[Callable] = None) -> dict:
    """Readings of the first three steps of the group: losses (3, A),
    the first applied gradient's norms, the window sketch after step 1
    (A, d), the share step's relevance observation (A, A), and the
    weights' change after three steps. ``mm`` sets the precision of
    the projections, ``rows`` keeps only the first rows of each batch,
    ``exchange=False`` combines each agent's own window alone, and
    ``sketch_keep`` and ``sketch_cast`` go to ``sketch`` (all for the
    control and the planted faults; the cell's own check uses none)."""
    if traffic["threshold"] != 1 or traffic["minibatch"] != 2:
        raise ValueError("the reference follows threshold 1, minibatch 2: "
                         "a local step, then one share window of two")
    opt = traffic["adamw"]
    ex = traffic["exchange"]
    if ex.get("topology", "full") != "full":
        raise ValueError("the reference wires every agent to every other")
    A = traffic["agents"]
    dim = ex.get("relevance_sketch_dim", 0)
    if ex.get("exchange_estimator") != "grad_cos+sketch" or not dim:
        raise ValueError("the reference learns relevance from sketches: "
                         "grad_cos+sketch with relevance_sketch_dim > 0")
    nbrs = [list(range(A)) if exchange else [a] for a in range(A)]

    with jax.default_matmul_precision("highest"):
        vg = jax.jit(jax.value_and_grad(
            lambda p, t, lab: M.loss(conf, p, t, lab, mm)))
        batch = jax.jit(lambda s: make_batch(traffic, conf["vocab_size"],
                                             lo, hi, s))
        adam = jax.jit(lambda p, m, v, g, c: adamw_step(opt, p, m, v, g, c),
                       static_argnums=4)
        norms = jax.jit(lambda t: leaf_norms(t, agents=False))
        init = jax.jit(lambda a: M.init_agent(conf, lo, hi, a))
        sketch_of = jax.jit(lambda ts, s: sketch(
            ts, s, dim, keep=sketch_keep, cast=sketch_cast))
        # steps 1 and 2 make up share round 1
        seed = jnp.uint32(round_seed(ex.get("topology_seed", 0), 1))

        def rows_of(b, a):
            t, lab = b["tokens"][a], b["labels"][a]
            if rows is not None:
                t, lab = t[:rows], lab[:rows]
            return t, lab

        losses = np.zeros((3, A))
        g0, p1, m1, v1 = [], [], [], []
        b0 = jax.device_get(batch(0))
        for a in range(A):
            pa = init(a)
            l, g = vg(pa, *rows_of(b0, a))
            zeros = jax.tree.map(jnp.zeros_like, pa)
            pa, ma, va, gc = adam(pa, zeros, zeros, g, 1)
            losses[0, a] = float(l)
            g0.append(jax.device_get(norms(gc)))
            p1.append(pa)
            m1.append(ma)
            v1.append(va)
            del g, gc, zeros
        b1, b2 = jax.device_get(batch(1)), jax.device_get(batch(2))
        tg, rg = [], []
        sk1, skw = np.zeros((A, dim)), np.zeros((A, dim))
        for a in range(A):
            l1, g1 = vg(p1[a], *rows_of(b1, a))
            l2, g2 = vg(p1[a], *rows_of(b2, a))
            losses[1, a], losses[2, a] = float(l1), float(l2)
            s12 = np.asarray(jax.device_get(sketch_of([g1, g2], seed)),
                             np.float64)
            sk1[a], skw[a] = s12[0], s12[0] + s12[1]
            # T_t = max(t, 1): the window holds steps 1 and 2
            tg.append(jax.tree.map(lambda x, y: 1.0 * x + 2.0 * y, g1, g2))
            rg.append(jax.tree.map(lambda x, y: x + y, g1, g2))
            del g1, g2
        tsum, rsum = 3.0, 2.0
        # relevance learned from the window sketches' cosine, one EMA
        # step from the uniform prior
        obs = relevance_obs(skw)
        ema = ex.get("relevance_ema", 0.9)
        R = ema + (1 - ema) * obs
        p3 = []
        for dst in range(A):
            src = nbrs[dst]
            wt = 1.0 / (tsum * len(src))
            wr = R[src, dst] / (rsum * R[src, dst].sum())
            gbar = jax.tree.map(
                lambda *xs: 0.5 * sum(
                    wt * t + float(w) * r
                    for t, r, w in zip(xs[:len(src)], xs[len(src):], wr)),
                *[tg[s] for s in src], *[rg[s] for s in src])
            p, _, _, _ = adam(p1[dst], m1[dst], v1[dst], gbar, 2)
            p3.append(p)
            del gbar
        del tg, rg, m1, v1, p1
        delta = []
        for a in range(A):
            d = jax.tree.map(lambda x, y: x - y, p3[a], init(a))
            delta.append(jax.device_get(norms(d)))
            del d
        del p3
    return {"loss": losses, "grad": _stack_agents(g0),
            "delta": _stack_agents(delta), "sketch": sk1, "rel": obs}


def _stack_agents(per_agent: list) -> Dict[str, np.ndarray]:
    """[agent] -> {path: norms of one agent} into 'path[layer]' -> (A,)."""
    out: Dict[str, list] = {}
    for norms in per_agent:
        for k, v in per_leaf({p: np.asarray(x)[None] for p, x in
                              norms.items()}).items():
            out.setdefault(k, []).append(v[0])
    return {k: np.array(v) for k, v in out.items()}


def gaps(got: dict, ref: dict, zero_rule: float = 1e-3) -> Dict[str, float]:
    """The numbers compared. Each gap of norms is taken leaf by leaf,
    against the reference's norm of that leaf or of the median leaf,
    whichever is larger, and the worst leaf counts. The change of the
    weights leaves out leaves whose reference gradient is under
    ``zero_rule`` of the median leaf's: Adam moves those by round-off
    alone. The sketch is compared as a vector, the norm of the
    difference against the reference's norm, worst agent counted: a
    sketch with other signs keeps its norm. The relevance observation
    is compared entry by entry off the diagonal."""
    out = {"loss_gap": float(np.max(np.abs(got["loss"] - ref["loss"])
                                    / np.abs(ref["loss"])))}
    gmed = np.median(np.stack(list(ref["grad"].values())), axis=0)
    for key in ("grad", "delta"):
        names = sorted(ref[key])
        r = np.stack([ref[key][n] for n in names])          # (leaves, A)
        g = np.stack([got[key][n] for n in names])
        keep = np.ones(len(names), bool)
        if key == "delta":
            rg = np.stack([ref["grad"][n] for n in names])
            keep = np.all(rg >= zero_rule * gmed, axis=1)
        med = np.median(r[keep], axis=0)
        gap = np.abs(g - r) / np.maximum(r, med)
        out[f"{key}_gap"] = float(np.max(gap[keep]))
    out["sketch_gap"] = float(np.max(
        np.linalg.norm(got["sketch"] - ref["sketch"], axis=1)
        / np.linalg.norm(ref["sketch"], axis=1)))
    off = ~np.eye(len(ref["rel"]), dtype=bool)
    out["rel_gap"] = float(np.max(np.abs(got["rel"] - ref["rel"])[off]))
    return out
