"""Plain reference of a group's first three steps under the
``hierarchical`` exchange, one agent per device.

It follows what ``train_ref.reference`` follows for a fully wired
group, with the window and the wiring of the pods cells:

- step 0 is a local step: each agent applies its own gradient with
  AdamW (global-norm clipping);
- steps 1 and 2 make up one share window. Each agent's gradients
  enter its window planes in bfloat16, as the cell keeps them
  (``tg`` = g1·1 + g2·2 and ``rg`` = g1 + g2, each product and each sum
  rounded to bfloat16), and its window sketch in float32;
- at step 2, relevance is learned from the cosine of the window
  sketches (one EMA step from the uniform prior), and each agent
  applies eq. 4 (arXiv:2202.05135) over its in-neighbours: the agents
  of its own pod, and, for a pod's leader (its first agent), the other
  pods' leaders too.

The model is a module with ``init_agent(conf, lo, hi, agent)`` and
``loss(conf, params, tokens, labels, mm)``. The agents' weights and
windows are stacked on a leading agent axis spread over ``devices``
(one agent per device where there are as many), and each agent's
steps run on its own device; the eq. 4 sums are a weighted sum over
the agent axis, one weight at a time. Nothing of the program is
imported.
"""
from __future__ import annotations

from typing import Callable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

import train_ref as TR

bf16 = jnp.bfloat16


def in_neighbours(n: int, pod: int) -> list:
    """Sources of each destination: its pod's agents, and for a pod's
    first agent (its leader) every other pod's leader."""
    leaders = set(range(0, n, pod))
    out = []
    for dst in range(n):
        first = dst - dst % pod
        src = set(range(first, first + pod))
        if dst in leaders:
            src |= leaders
        out.append(sorted(src))
    return out


def reference(M, conf: dict, traffic: dict, lo: int, hi: int,
              devices: Sequence, mm: Optional[Callable] = None,
              half: bool = False, exchange: bool = True,
              sketch_keep: Optional[Callable] = None,
              sketch_cast: Optional[Callable] = None) -> dict:
    """Readings of the first three steps, as ``train_ref.reference``
    gives them. ``mm`` sets the projections' precision, ``half`` keeps
    the loss to the first half of each agent's tokens, ``exchange=False``
    combines each agent's own window alone, and ``sketch_keep`` and
    ``sketch_cast`` go to ``train_ref.sketch`` (all for the control and
    the planted faults; the cell's own check uses none)."""
    if traffic["threshold"] != 1 or traffic["minibatch"] != 2:
        raise ValueError("the reference follows threshold 1, minibatch 2: "
                         "a local step, then one share window of two")
    ex = traffic["exchange"]
    if ex.get("topology") != "hierarchical":
        raise ValueError("the reference wires pods of agents")
    if ex.get("knowledge_dtype") != "bfloat16":
        raise ValueError("the reference keeps bfloat16 window planes")
    dim = ex.get("relevance_sketch_dim", 0)
    if ex.get("exchange_estimator") != "grad_cos+sketch" or not dim:
        raise ValueError("the reference learns relevance from sketches: "
                         "grad_cos+sketch with relevance_sketch_dim > 0")
    A = traffic["agents"]
    nbrs = (in_neighbours(A, ex["degree"]) if exchange
            else [[a] for a in range(A)])
    opt = traffic["adamw"]
    kw = {} if mm is None else {"mm": mm}
    mesh = Mesh(np.asarray(devices), ("agents",))
    spread = NamedSharding(mesh, P("agents"))

    def per_agent(fn, shared=()):
        """``fn`` of one agent, run on each agent's device; arguments
        at the positions ``shared`` go whole to every agent."""
        def run(*args):
            specs = tuple(P() if i in shared else P("agents")
                          for i in range(len(args)))
            return jax.shard_map(
                jax.vmap(fn, in_axes=tuple(None if i in shared else 0
                                           for i in range(len(args)))),
                mesh=mesh, in_specs=specs, out_specs=P("agents"),
                check_vma=False)(*args)
        return jax.jit(run)

    def tokens(b):
        t, lab = b["tokens"], b["labels"]
        if half:
            t, lab = t[..., :t.shape[-1] // 2], lab[..., :lab.shape[-1] // 2]
        return jax.device_put((t, lab), spread)

    def grad(p, t, lab):
        return jax.value_and_grad(
            lambda q: M.loss(conf, q, t, lab, **kw))(p)

    def first_step(p, g):
        zeros = jax.tree.map(jnp.zeros_like, p)
        p, m, v, g = TR.adamw_step(opt, p, zeros, zeros, g, 1)
        return p, m, v, TR.leaf_norms(g, agents=False)

    def window(g1, g2, seed):
        # T_t = max(t, 1): the window holds steps 1 (T 1) and 2 (T 2)
        sk = TR.sketch([g1, g2], seed, dim, keep=sketch_keep,
                       cast=sketch_cast)
        tg = jax.tree.map(lambda x, y: x.astype(bf16)
                          + (2.0 * y).astype(bf16), g1, g2)
        rg = jax.tree.map(lambda x, y: x.astype(bf16) + y.astype(bf16),
                          g1, g2)
        return sk, tg, rg

    def share(p, m, v, g):
        return TR.adamw_step(opt, p, m, v, g, 2)[0]

    def delta(p, agent):
        return TR.leaf_norms(jax.tree.map(
            jnp.subtract, p, M.init_agent(conf, lo, hi, agent)),
            agents=False)

    @jax.jit
    def eq4(wt, wr, tg, rg):
        def rows(w, x):
            return jnp.tensordot(w, x.astype(jnp.float32), axes=(1, 0))
        return jax.lax.with_sharding_constraint(
            0.5 * (rows(wt, tg) + rows(wr, rg)), spread)

    with jax.default_matmul_precision("highest"):
        batch = jax.jit(lambda s: TR.make_batch(
            traffic, conf["vocab_size"], lo, hi, s))
        agents = jax.device_put(jnp.arange(A), spread)
        vg = per_agent(grad)
        p0 = per_agent(lambda a: M.init_agent(conf, lo, hi, a))(agents)
        l0, g = vg(p0, *tokens(batch(0)))
        p1, m1, v1, g0 = per_agent(first_step)(p0, g)
        del p0, g
        l1, g1 = vg(p1, *tokens(batch(1)))
        l2, g2 = vg(p1, *tokens(batch(2)))
        seed = jnp.uint32(TR.round_seed(ex.get("topology_seed", 0), 1))
        sk, tg, rg = per_agent(window, shared=(2,))(g1, g2, seed)
        del g1, g2
        s12 = np.asarray(jax.device_get(sk), np.float64)      # (A, 2, d)
        sk1, skw = s12[:, 0], s12[:, 0] + s12[:, 1]
        obs = TR.relevance_obs(skw)
        ema = ex.get("relevance_ema", 0.9)
        R = ema + (1 - ema) * obs
        tsum, rsum = 3.0, 2.0
        # eq. 4 weights of source s at destination d, zero off the graph
        wt, wr = np.zeros((A, A)), np.zeros((A, A))
        for dst, src in enumerate(nbrs):
            wt[dst, src] = 1.0 / (tsum * len(src))
            wr[dst, src] = R[src, dst] / (rsum * R[src, dst].sum())
        wt, wr = jnp.asarray(wt, jnp.float32), jnp.asarray(wr, jnp.float32)
        gbar = jax.tree.map(lambda t, r: eq4(wt, wr, t, r), tg, rg)
        del tg, rg
        p3 = per_agent(share)(p1, m1, v1, gbar)
        del gbar, p1, m1, v1
        d3 = per_agent(delta)(p3, agents)
    loss = np.stack([np.asarray(x) for x in (l0, l1, l2)])
    return {"loss": loss, "grad": TR.per_leaf(jax.device_get(g0)),
            "delta": TR.per_leaf(jax.device_get(d3)), "sketch": sk1,
            "rel": obs}
