"""Multi-host pod dispatch for hierarchical DDAL (ISSUE 3).

The ``hierarchical`` topology is pods-of-pods: dense exchange inside a
pod, sparse leader-to-leader exchange across pods. On a flat mesh the
streaming combine (``repro.core.sharded_ddal._combine_topo``) contracts
the full (A, A) adjacency over the sharded agent axis, so *every*
agent's accumulator planes cross whatever interconnect the axis is
mapped to — O(n·k·|params|) traffic. This module maps the pod
structure onto a real two-level ``(pod_axis, "agent")`` mesh instead:

* **intra-pod segment** — each destination's sum over its pod members
  runs entirely inside the pod's device row (``all_gather`` over the
  fast ``"agent"`` axis, ICI on a TPU pod), touching no cross-pod
  link;
* **leader-level segment** — only each pod's *leader* planes
  (tg/rg + the tsum/rsum scalars) cross the slow ``pod_axis`` (DCN):
  a ``ppermute`` rotation per leader edge-list shift, or a single
  ``psum`` when the leader clique is complete and unweighted (the
  leader's own plane is subtracted back out — the masked leader
  self-edge; it already entered through the intra-pod sum).

Cross-pod traffic is therefore O(pods · k_leader · |params|) per share
step instead of O(n · k · |params|) — it scales with the number of
pods, not the number of agents (``cross_pod_bytes`` /
``flat_exchange_bytes`` account both sides; the benchmark sweep in
``benchmarks/bench_topology_scaling.py --pods`` reports them).
Learned relevance rides the same placement: with
``GroupSpec.relevance_sketch_dim > 0`` the per-round gradient-cosine
observation is computed on the carried (n, d) window sketches, so
cross-pod relevance exchange is O(pods · n · d) bytes — never the
parameter-sized accumulators the exact Gram would contract
(``relevance_exchange_bytes`` accounts it, reported in
``benchmarks/bench_relevance_sketch.py``'s JSON record; the
no-parameter-sized-intermediate property itself is gated there by
the jaxpr peak-intermediate check).

Trainers reach this module through the exchange protocol's ``pod``
combiner strategy (``repro.core.exchange.combiners`` — selected by
``GroupSpec.pods > 0`` or ``exchange_combiner="pod"``), never
directly: ``make_pod_dispatch`` builds the combine closure once at
protocol-build time.

Equivalence oracle: both paths reuse ``_edge_sums`` /
``_finish_combine`` from ``sharded_ddal``, and with one pod the
cross-pod segment vanishes *statically* — the dispatched combine is
then the same computation as ``_combine_topo``, pinned bitwise in
``tests/test_pod_dispatch.py``. Everything runs on simulated devices
(``XLA_FLAGS=--xla_force_host_platform_device_count=8``), so the tests
exercise the real collectives on CPU rigs and CI alike; true
multi-process ``jax.distributed`` bring-up is the ROADMAP follow-up.
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.common.pytree import tree_map
from repro.core.sharded_ddal import (
    Knowledge,
    _edge_sums,
    _finish_combine,
    mask_knowledge,
)
from repro.core.topology import PodLayout, Topology, cross_pod_mask


class PodEdges(NamedTuple):
    """The hierarchical edge set split by the mesh axis it crosses.

    intra_mask:  (n, k) bool — edges local to the destination's pod
                 (same slot layout as ``topo.nbr``).
    leader_mask: (n, k) bool — cross-pod edges; validation guarantees
                 they connect pod leaders only.
    ledge:       (pods, pods) bool — leader adjacency
                 ``ledge[src_pod, dst_pod]``, diagonal False (the
                 leader self-edge is masked: a leader's own plane
                 enters eq. 4 through the intra-pod segment only).
    lslot:       (pods, pods) int32 — edge slot of src pod's leader in
                 dst leader's row (-1 where no edge), for per-edge
                 relevance lookup.
    """
    intra_mask: np.ndarray
    leader_mask: np.ndarray
    ledge: np.ndarray
    lslot: np.ndarray


def split_topology(topo: Topology, layout: PodLayout) -> PodEdges:
    """Partition the edge table into intra-pod and leader-level sets.

    Raises if any cross-pod edge is not leader→leader — such a graph
    has no two-level placement (a member's plane would need to ride
    the DCN axis directly)."""
    n, k = np.asarray(topo.nbr).shape
    if layout.n_agents != n:
        raise ValueError(
            f"layout covers {layout.n_agents} agents, topology has {n}")
    nbr = np.asarray(topo.nbr)
    mask = np.asarray(topo.mask)
    cross = cross_pod_mask(topo, layout)
    intra = mask & ~cross
    is_leader = np.asarray(layout.leader_mask)
    bad = cross & ~(is_leader[nbr] & is_leader[:, None])
    if bad.any():
        dst, slot = np.argwhere(bad)[0]
        raise ValueError(
            f"cross-pod edge {int(nbr[dst, slot])}→{int(dst)} does not "
            f"connect two pod leaders — the topology cannot be "
            f"pod-dispatched (only leader planes may cross the pod "
            f"axis)")
    pods = layout.n_pods
    pod_id = np.asarray(layout.pod_id)
    ledge = np.zeros((pods, pods), bool)
    lslot = np.full((pods, pods), -1, np.int32)
    for dst, slot in np.argwhere(cross):
        sp, dp = int(pod_id[nbr[dst, slot]]), int(pod_id[dst])
        ledge[sp, dp] = True
        lslot[sp, dp] = slot
    # ledge's diagonal is False by construction: a same-pod leader
    # edge cannot be in `cross`, so the leader self-edge lands in the
    # intra segment and is counted exactly once
    return PodEdges(intra_mask=intra, leader_mask=cross, ledge=ledge,
                    lslot=lslot)


# ---------------------------------------------------------------------
# traffic accounting
# ---------------------------------------------------------------------
def _edge_cost(n_params: int, dtype_bytes: int,
               quant_block: int = 0) -> int:
    """Bytes one directed edge moves per share step: the source's two
    accumulator planes (tg, rg) plus the (tsum, rsum) scalars. With
    ``quant_block > 0`` each plane is int8 wire format — 1 byte per
    element plus one fp32 scale per ``quant_block`` elements — instead
    of ``dtype_bytes`` per element (~4× lighter at fp32)."""
    if quant_block > 0:
        plane = n_params + (-(-n_params // quant_block)) * 4
    else:
        plane = n_params * dtype_bytes
    return 2 * plane + 2 * 4


def cross_pod_bytes(edges: PodEdges, n_params: int,
                    dtype_bytes: int = 4,
                    quant_block: int = 0) -> int:
    """Cross-pod traffic per share step of the *dispatched* combine:
    only the directed leader edges move data over the pod axis —
    O(pods · k_leader · |params|), independent of pod size.
    ``quant_block`` mirrors ``GroupSpec.knowledge_quant_block``: int8
    planes + per-block scales instead of ``dtype_bytes``/element."""
    return int(edges.ledge.sum()) * _edge_cost(n_params, dtype_bytes,
                                               quant_block)


def relevance_exchange_bytes(n_agents: int, n_params: int,
                             sketch_dim: int,
                             dtype_bytes: int = 4) -> int:
    """Bytes the learned-relevance observation moves across the agent
    sharding per share step (ISSUE 4). The exact ``grad_cos`` Gram
    contracts the (A, P) window accumulators against themselves, so
    every agent's parameter-sized ``rg`` rows cross the mesh —
    O(A · |params|). The sketched estimator
    (``GroupSpec.relevance_sketch_dim > 0``) gathers only the carried
    (A, d) window sketches (``Knowledge.sk``) — O(A · d) bytes,
    independent of |params|: at pod scale, O(pods · n · d) instead of
    anything parameter-sized."""
    per_row = n_params if sketch_dim <= 0 else sketch_dim
    return n_agents * per_row * dtype_bytes


def flat_exchange_bytes(topo: Topology, n_params: int,
                        dtype_bytes: int = 4,
                        quant_block: int = 0) -> int:
    """What the single-flat-mesh combine moves between devices: every
    non-self edge's source planes cross a device boundary (a flat
    placement gives pod structure no locality) — O(n · k · |params|),
    growing with agent count. ``quant_block`` as in
    :func:`cross_pod_bytes`."""
    nbr = np.asarray(topo.nbr)
    mask = np.asarray(topo.mask)
    self_edge = nbr == np.arange(nbr.shape[0])[:, None]
    return int((mask & ~self_edge).sum()) * _edge_cost(
        n_params, dtype_bytes, quant_block)


# ---------------------------------------------------------------------
# the dispatched combine
# ---------------------------------------------------------------------
def _leader_terms_dense(know: Knowledge, topo: Topology,
                        edges: PodEdges, rel):
    """Reference (single-device) leader-level segment: the same
    ``_edge_sums`` restricted to the cross-pod edge list."""
    lm = jnp.asarray(edges.leader_mask)
    return _edge_sums(know, topo.nbr, lm, jnp.where(lm, rel, 0.0))


def make_pod_dispatch(topo: Topology, layout: PodLayout, *,
                      mesh=None, pod_axis: str = "pod",
                      agent_axis: str = "agent"):
    """Build ``combine(know, rel=None, alive=None) -> ḡ`` for a
    hierarchical topology placed on pods.

    With ``mesh`` carrying both ``pod_axis`` and ``agent_axis`` the
    combine runs under ``shard_map``: intra-pod sums gather over the
    agent axis only, and the leader exchange is the only collective on
    the pod axis. Without a mesh (single-device rigs) the identical
    decomposition runs as plain array ops. ``rel`` overrides the
    per-edge relevance table (traced — the learned-R path); ``None``
    uses the topology's static table. ``alive`` ((n,) bool, elastic
    membership) zeroes dead agents' accumulator rows *before* either
    segment runs: a dead leader's cross-pod term is its own (now
    zero) plane, so nothing of its pod crosses the pod axis, and a
    dead member contributes zero to its pod's intra sums — dead
    destinations' output rows are garbage the trainer selects away.
    """
    edges = split_topology(topo, layout)
    if mesh is not None and (pod_axis in mesh.axis_names
                             and agent_axis in mesh.axis_names):
        return _make_sharded_dispatch(topo, layout, edges, mesh,
                                      pod_axis, agent_axis)
    return _make_reference_dispatch(topo, layout, edges)


def _make_reference_dispatch(topo: Topology, layout: PodLayout,
                             edges: PodEdges):
    """The decomposed combine as plain array ops (no mesh): intra-pod
    edge sums plus — statically skipped for one pod — the leader-level
    edge sums. With one pod the intra edge set *is* the full edge set,
    so this is the same computation as ``_combine_topo`` (the bitwise
    1-pod oracle)."""
    intra_mask = jnp.asarray(edges.intra_mask)
    multi_pod = layout.n_pods > 1

    def combine(know: Knowledge, rel: Optional[jnp.ndarray] = None,
                alive=None):
        rel = topo.relevance if rel is None else rel
        know = mask_knowledge(know, alive)
        tnum, tden, rnum, rden = _edge_sums(
            know, topo.nbr, intra_mask, jnp.where(intra_mask, rel, 0.0))
        if multi_pod:
            lt, ltd, lr, lrd = _leader_terms_dense(know, topo, edges,
                                                   rel)
            tnum = tree_map(jnp.add, tnum, lt)
            rnum = tree_map(jnp.add, rnum, lr)
            tden = tden + ltd
            rden = rden + lrd
        return _finish_combine(tnum, tden, rnum, rden)

    return combine


def _make_sharded_dispatch(topo: Topology, layout: PodLayout,
                           edges: PodEdges, mesh, pod_axis: str,
                           agent_axis: str):  # pragma: no cover — runs
    # only with a multi-device mesh: the `multi_device` tests cover it
    # inline in the CI multi-device lane / via subprocess re-exec
    # locally, both invisible to the fast lane's in-process pytest-cov
    """The decomposed combine under ``shard_map`` on a two-level mesh.

    Placement contract (validated): agents shard contiguously over
    ``(pod_axis, agent_axis)``, topology pods align with the mesh's
    pod rows (``layout.n_pods == mesh.shape[pod_axis]``), and the pod
    size divides evenly over the agent axis. Each device gathers its
    pod's accumulators over the agent axis (intra-pod traffic only),
    runs the pod-local ``_edge_sums``, and the leader segment moves
    exactly the leader planes across the pod axis.
    """
    from jax.sharding import PartitionSpec as P

    pods = layout.n_pods
    pod_size = layout.pod_size
    n_pod_dev = mesh.shape[pod_axis]
    n_agent_dev = mesh.shape[agent_axis]
    if pods != n_pod_dev:
        raise ValueError(
            f"topology has {pods} pods but mesh axis "
            f"{pod_axis!r} has {n_pod_dev} devices — pods must map "
            f"1:1 onto the pod axis")
    if pod_size % n_agent_dev:
        raise ValueError(
            f"pod size {pod_size} does not divide over the "
            f"{n_agent_dev}-device {agent_axis!r} axis")
    blk = pod_size // n_agent_dev
    k = topo.degree

    # pod-local intra edge tables: same slot layout as the global
    # table, sources remapped to pod-local indices (gather targets
    # after the all_gather). Stacked (pods, pod_size, k); the device's
    # pod row selects its slice by axis_index at trace time.
    nbr_g = np.asarray(topo.nbr).reshape(pods, pod_size, k)
    pod_lo = np.arange(pods)[:, None, None] * pod_size
    intra_nbr_local = nbr_g - pod_lo
    intra_mask_p = np.asarray(edges.intra_mask).reshape(
        pods, pod_size, k)
    intra_nbr_local = np.where(intra_mask_p, intra_nbr_local, 0)
    if ((intra_nbr_local < 0) | (intra_nbr_local >= pod_size)).any():
        raise ValueError("intra-pod edge escapes its pod — layout and "
                         "topology disagree")
    # leader bookkeeping: local row of the leader inside its pod, and
    # whether the (complete, unweighted) psum fast path applies.
    leader_local = (np.asarray(layout.leaders)
                    - np.arange(pods) * pod_size).astype(np.int32)
    complete = bool(edges.ledge.sum()
                    == pods * (pods - 1)) if pods > 1 else False
    rel_static = np.asarray(topo.relevance)
    uniform_leaders = bool(
        np.all(rel_static[np.asarray(edges.leader_mask)] == 1.0))

    def make_local_combine(fast: bool):
        return lambda *args: local_combine(fast, *args)

    def local_combine(fast, tg, tsum, rg, rsum, rel_rows):
        # gather the pod's accumulators over the fast agent axis —
        # intra-pod traffic only, no cross-pod collective
        gather = lambda x: jax.lax.all_gather(      # noqa: E731
            x, agent_axis, axis=0, tiled=True)
        tg_p = tree_map(gather, tg)                 # (pod_size, *param)
        rg_p = tree_map(gather, rg)
        tsum_p = gather(tsum)                       # (pod_size,)
        rsum_p = gather(rsum)
        rel_p = gather(rel_rows)                    # (pod_size, k)

        p = jax.lax.axis_index(pod_axis)
        nbr_l = jnp.asarray(intra_nbr_local)[p]     # (pod_size, k)
        mask_l = jnp.asarray(intra_mask_p)[p]
        know_p = Knowledge(tg=tg_p, tsum=tsum_p, rg=rg_p, rsum=rsum_p)
        tnum, tden, rnum, rden = _edge_sums(
            know_p, nbr_l, mask_l, jnp.where(mask_l, rel_p, 0.0))

        if pods > 1:
            lidx = jnp.asarray(leader_local)[p]
            take0 = lambda x: jnp.take(x, lidx, axis=0)  # noqa: E731
            own = (tree_map(take0, tg_p), take0(tsum_p),
                   tree_map(take0, rg_p), take0(rsum_p))
            if fast:
                # complete unweighted leader clique: one psum over the
                # pod axis, own plane subtracted back out (the masked
                # leader self-edge)
                tot = jax.tree.map(
                    lambda x: jax.lax.psum(x, pod_axis), own)
                xt, xts, xr, xrs = jax.tree.map(jnp.subtract, tot, own)
            else:
                # sparse / weighted leader edge list: one ppermute
                # rotation per shift, each edge weighted by the
                # destination row's per-edge relevance
                zeros = jax.tree.map(jnp.zeros_like, own)
                xt, xts, xr, xrs = zeros
                ledge_j = jnp.asarray(edges.ledge)
                lslot_j = jnp.asarray(edges.lslot)
                for s in range(1, pods):
                    perm = [(q, (q + s) % pods) for q in range(pods)]
                    rot = lambda x: jax.lax.ppermute(  # noqa: E731
                        x, pod_axis, perm)
                    r_tg, r_ts, r_rg, r_rs = jax.tree.map(rot, own)
                    src_pod = (p - s) % pods
                    e = ledge_j[src_pod, p].astype(jnp.float32)
                    slot = lslot_j[src_pod, p]
                    w = e * rel_p[lidx, jnp.maximum(slot, 0)]
                    xt = tree_map(lambda a, g: a + e * g, xt, r_tg)
                    xr = tree_map(lambda a, g: a + w * g, xr, r_rg)
                    xts = xts + e * r_ts
                    xrs = xrs + w * r_rs
            add_row = lambda acc, x: acc.at[lidx].add(x)  # noqa: E731
            tnum = tree_map(add_row, tnum, xt)
            rnum = tree_map(add_row, rnum, xr)
            tden = tden.at[lidx].add(xts)
            rden = rden.at[lidx].add(xrs)

        out = _finish_combine(tnum, tden, rnum, rden)
        start = jax.lax.axis_index(agent_axis) * blk
        return tree_map(
            lambda x: jax.lax.dynamic_slice_in_dim(x, start, blk, 0),
            out)

    def spec_of(x):
        return P((pod_axis, agent_axis), *([None] * (x.ndim - 1)))

    def combine(know: Knowledge, rel: Optional[jnp.ndarray] = None,
                alive=None):
        # the psum fast path assumes unweighted leader edges — the
        # static table can prove that, a (possibly traced) per-edge
        # override cannot, so any override takes the weighted
        # ppermute chain. Dead agents' rows are zeroed *before* the
        # shard_map, so what a dead leader psums/ppermutes across the
        # pod axis is a zero plane — it carries nothing.
        fast = complete and uniform_leaders and rel is None
        rel = topo.relevance if rel is None else rel
        know = mask_knowledge(know, alive)
        args = (know.tg, know.tsum, know.rg, know.rsum,
                jnp.asarray(rel, jnp.float32))
        in_specs = jax.tree.map(spec_of, args)
        out_specs = jax.tree.map(spec_of, know.tg)
        # replication checking is off: the per-device slices come from
        # axis_index-driven gathers the checker cannot see through
        return jax.shard_map(make_local_combine(fast), mesh=mesh,
                             in_specs=in_specs, out_specs=out_specs,
                             check_vma=False)(*args)

    return combine
