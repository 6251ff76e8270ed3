"""DDAL at pod scale — group-agent training of the model zoo.

Mapping: one GARL agent per **pod**. Parameters,
optimiser state and knowledge accumulators carry a leading
``(n_agents,)`` axis sharded ``P("pod")``; each agent consumes its own
data stream (its own "environment"). Cross-agent knowledge exchange is
expressed as reductions over the agent axis, which GSPMD lowers to
collectives over the pod interconnect — **only at share steps**, which
is DDAL's communication saving over lockstep data parallelism.

Knowledge is held in *streaming* form: per-agent accumulators
    tg = Σ_j T_j·g_j,  tsum = Σ_j T_j,  rg = Σ_j g_j,  rsum = Σ_j 1
over the pieces generated since the last share step. The eq. 4 average
over the union of all agents' windows is then

    ḡ(dst) = ½ ( Σ_src tg_src / Σ_src tsum_src
               + Σ_src R[src,dst]·rg_src / Σ_src R[src,dst]·rsum_src )

— mathematically identical to materialising every piece (the weighted
sum is linear), but O(1) memory instead of m copies of a 34B-parameter
gradient. This matches the paper's own experiment ("gradients generated
by and received during its previous 1000 epochs"). The ring-buffer
(piece-faithful) form lives in ``repro.core.ddal`` for agent-scale use.

Sparse topologies: with a ``repro.core.topology.Topology`` the share
step reduces over each destination's **in-neighbors** via a
segment-sum on the static edge list instead of a global all-reduce —
O(|E|) cross-pod traffic instead of O(A²) — and both eq. 4
normalisations (T and R) become neighbor-local. The ``full`` + uniform
case keeps the cheaper global-sum fast path.

Multi-host pod dispatch (ISSUE 3): with ``spec.pods > 0`` the
hierarchical combine splits into an intra-pod segment (local to the
fast ``"agent"`` mesh axis) and a leader-level segment in which only
each pod's leader planes cross ``spec.pod_axis`` —
``repro.core.pod_dispatch``; cross-pod traffic drops from
O(n·k·|params|) to O(pods·k_leader·|params|) per share step. The
1-pod case is bitwise the flat ``_combine_topo`` (both run the same
``_edge_sums`` / ``_finish_combine``).

Adaptive wiring (ISSUE 2): a ``DynamicTopology``
(``spec.resample_every > 0``) resamples the gossip edge list inside
the jitted step — the segment-sum consumes the traced table directly
— and ``spec.relevance_mode="grad_cos"`` learns per-edge relevance
from the cosine similarity of the agents' *window-accumulated*
gradients (``Knowledge.rg``, already a temporal average over the
share window), EMA-smoothed across share steps in ``Knowledge.rel``
(``repro.core.relevance``). Both default off; the static path is
untouched.

Exchange protocol (ISSUE 5): the train step no longer interprets any
of those flags itself — ``repro.core.exchange.build_exchange``
resolves them into strategy objects once, and the jitted step calls
``protocol.sketch_step`` (window accumulation), ``protocol.observe``
(the relevance update) and ``protocol.combine`` (flat segment-sum,
global fast path, or pod dispatch — decided at build time). The
``"auto"`` strategies trace exactly the ops the inline ladders used
to emit, so every pre-redesign configuration is bitwise-reproduced.

Sketched relevance (ISSUE 4): with ``spec.relevance_sketch_dim > 0``
the window additionally carries an (A, d) **gradient sketch**
(``Knowledge.sk``): every accumulation step also streams that epoch's
gradients through the seeded ±1 projection
(``repro.kernels.grad_sketch``) and adds the tiny (A, d) result —
the projection is linear and seeded per share round, so at share
time ``sk`` *is* the sketch of ``rg`` (up to the knowledge-dtype
cast) and the relevance observation is just ``cosine_rows(sk)``:
O(A²·d) instead of the exact O(A²·|params|) Gram. Under the pod
dispatch this is also what crosses the mesh for relevance — the (A, d)
sketch rows (O(pods·A·d) bytes), never anything parameter-sized
(``repro.core.pod_dispatch.relevance_exchange_bytes`` accounts it).

One agent per device: with a ``mesh`` whose agent axes
(``repro.launch.shardings.ddal_agent_axis``) hold one device per
agent, or a few agents each, the per-agent pieces — the forward and
backward pass and the window sketch — run under ``shard_map`` on the
agents' own devices, each device seeing only its agents. Nothing of
one agent's forward and backward then crosses a device, and a kernel
inside (the sketch's Pallas call, the expert layer's grouped product)
sees one device's arrays. A device that holds one agent runs it
unbatched. Without a mesh every agent runs under ``vmap`` on one
device. The window sketch's signs depend on position alone, so under
the mesh each device hashes a share of every large leaf's positions
for all agents, and the rows' sums are scattered back to their
devices (``repro.kernels.grad_sketch.ops.sketch_pytree``).

Device scopes: the train step names each of its pieces with
``jax.named_scope`` — ``ddal.grad`` (the agents' forward and
backward), ``ddal.window`` (accumulating and resetting the window),
``ddal.sketch`` (the window sketch), ``ddal.exchange`` (the share
step's cond), ``ddal.combine`` (relevance and eq. 4 inside it) and
``ddal.optimizer``. The names reach the compiled ops' ``op_name``
metadata, through vmap, autodiff and cond, so a profiler trace of
the step can be split by piece; they change nothing else.
"""
from __future__ import annotations

from typing import Any, Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.common.pytree import tree_map, tree_zeros_like
from repro.configs.base import ArchConfig, GroupSpec
from repro.core.weighting import training_experience
from repro.models import get_model
from repro.optim import Optimizer


class Knowledge(NamedTuple):
    tg: Any               # pytree, leaves (A, *param) fp32
    tsum: jnp.ndarray     # (A,)
    rg: Any
    rsum: jnp.ndarray     # (A,)
    rel: Any = None       # relevance-estimator state, persisted across
                          # window resets (repro.core.exchange): the
                          # (A, A) learned R EMA for the gradient
                          # estimators, an ObsStatsState pytree for
                          # obs_stats; None = uniform (nothing learned)
    sk: Any = None        # (A, d) window gradient sketch; None unless
                          # the estimator sketches (grad_cos+sketch)
    alive: Any = None     # (A,) bool elastic-membership mask, persisted
                          # across window resets like rel; None (the
                          # default — filtered out of the pytree) keeps
                          # non-elastic programs and existing
                          # checkpoints/shardings structurally unchanged


class TrainState(NamedTuple):
    params: Any           # leaves (A, *param)
    opt_state: Any
    know: Knowledge
    step: jnp.ndarray     # () int32


def init_knowledge(params, dtype=jnp.float32, rel=None,
                   sketch_dim: int = 0, alive=None) -> Knowledge:
    """Fresh (zeroed) share-window accumulators. ``rel`` is the learned
    relevance EMA to carry across the window reset — it persists over
    share steps, unlike the window sums (``sketch_dim > 0`` adds the
    (A, d) window sketch, which resets with them). ``alive`` is the
    elastic-membership mask, carried across resets like ``rel``."""
    A = jax.tree.leaves(params)[0].shape[0]
    acc = tree_map(lambda x: jnp.zeros(x.shape, jnp.dtype(dtype)),
                   params)
    sk = (jnp.zeros((A, sketch_dim), jnp.float32)
          if sketch_dim > 0 else None)
    return Knowledge(tg=acc, tsum=jnp.zeros((A,), jnp.float32),
                     rg=tree_zeros_like(acc),
                     rsum=jnp.zeros((A,), jnp.float32), rel=rel,
                     sk=sk, alive=alive)


def init_train_state(cfg: ArchConfig, spec: GroupSpec, opt: Optimizer,
                     key, exchange=None) -> TrainState:
    """Real initialisation (CPU tests / actual training). The
    relevance-state seed (``Knowledge.rel``) and the sketch width come
    from the spec's exchange estimator — pass the prebuilt
    ``exchange`` protocol if the train step got one, so the carried
    state matches what its estimator expects."""
    from repro.core.exchange import build_exchange
    if exchange is None:
        exchange = build_exchange(spec, kind="streaming")
    model = get_model(cfg)
    keys = jax.random.split(key, spec.n_agents)
    params = jax.vmap(lambda k: model.init(cfg, k))(keys)
    opt_state = jax.vmap(opt.init)(params)
    alive = (jnp.ones((spec.n_agents,), bool)
             if getattr(spec, "elastic", False) else None)
    return TrainState(params=params, opt_state=opt_state,
                      know=init_knowledge(params,
                                          jnp.dtype(spec.knowledge_dtype),
                                          rel=exchange.streaming_rel_init(),
                                          sketch_dim=exchange.sketch_dim,
                                          alive=alive),
                      step=jnp.zeros((), jnp.int32))


def train_state_specs(cfg: ArchConfig, spec: GroupSpec, opt: Optimizer
                      ) -> TrainState:
    """ShapeDtypeStruct version for the dry-run (no allocation)."""
    key = jax.random.PRNGKey(0)
    return jax.eval_shape(
        lambda k: init_train_state(cfg, spec, opt, k), key)


def _combine(know: Knowledge, R: jnp.ndarray, uniform: bool):
    """eq. 4 over the union of all agents' windows → per-dst ḡ with a
    leading (A,) axis (identical rows when R is uniform)."""
    A = know.tsum.shape[0]
    eps = 1e-12

    if uniform:
        # Σ over the (pod-sharded) agent axis → all-reduce over pods.
        tsum = jnp.maximum(jnp.sum(know.tsum), eps)
        rsum = jnp.maximum(jnp.sum(know.rsum), eps)

        def avg(tg_leaf, rg_leaf):
            t = jnp.sum(tg_leaf, axis=0) / tsum
            r = jnp.sum(rg_leaf, axis=0) / rsum
            g = 0.5 * (t + r)
            return jnp.broadcast_to(g[None], tg_leaf.shape)

        return tree_map(avg, know.tg, know.rg)

    # per-destination relevance: weighted gather over the agent axis
    r_t = jnp.maximum(jnp.sum(know.tsum), eps)             # T̂ is global
    rden = jnp.maximum(know.rsum @ R, eps)                 # (A_dst,)

    def avg(tg_leaf, rg_leaf):
        t = jnp.sum(tg_leaf, axis=0) / r_t                 # (*param,)
        r = jnp.tensordot(R, rg_leaf, axes=(0, 0))         # (A_dst,*param)
        r = r / jnp.reshape(rden, (A,) + (1,) * (r.ndim - 1))
        return 0.5 * (t[None] + r)

    return tree_map(avg, know.tg, know.rg)


def _edge_sums(know: Knowledge, nbr, mask, rel):
    """eq. 4 numerators/denominators over one edge list: for each
    destination, sum the sources' accumulators over its edge slots.
    The scalar sums reduce with a segment-sum over the edge list; the
    gradient leaves reduce with a masked adjacency matmul —
    mathematically the same segment-sum, but it never materialises
    (E, *param) gathered copies of the accumulators (a k-fold
    peak-memory blowup at LLM scale). Shared by the flat single-mesh
    combine and both segments (intra-pod, leader-level) of the pod
    dispatch, so the 1-pod dispatched path is the *same computation*
    as the flat path, not a reimplementation."""
    A, k = nbr.shape
    src = jnp.reshape(nbr, (-1,))                    # (E,) sources
    seg = jnp.repeat(jnp.arange(A), k)               # (E,) destinations
    m = jnp.reshape(mask, (-1,)).astype(jnp.float32)
    relf = jnp.reshape(jnp.where(mask, rel, 0.0), (-1,))

    def seg_sum(x):
        return jax.ops.segment_sum(x, seg, num_segments=A)

    tden = seg_sum(m * know.tsum[src])               # (A,)
    rden = seg_sum(relf * know.rsum[src])

    # dense (A, A) src→dst weights, zero off-graph (A = pods, small)
    Rd = jnp.zeros((A, A)).at[src, seg].add(relf)
    M = jnp.zeros((A, A)).at[src, seg].add(m)
    tnum = tree_map(lambda g: jnp.tensordot(M, g, axes=(0, 0)), know.tg)
    rnum = tree_map(lambda g: jnp.tensordot(Rd, g, axes=(0, 0)), know.rg)
    return tnum, tden, rnum, rden


def _finish_combine(tnum, tden, rnum, rden):
    """ḡ = ½(t/T̂ + r/R̂) with the eps clamp applied once, after every
    segment's contribution has been accumulated into the sums."""
    eps = 1e-12
    tden = jnp.maximum(tden, eps)
    rden = jnp.maximum(rden, eps)

    def avg(t, r):
        ex = (-1,) + (1,) * (t.ndim - 1)
        return 0.5 * (t / jnp.reshape(tden, ex)
                      + r / jnp.reshape(rden, ex))

    return tree_map(avg, tnum, rnum)


def _combine_topo(know: Knowledge, topo: Topology):
    """eq. 4 with neighbor-local normalisation: for each destination,
    both the T and R terms sum over its in-neighbors only. GSPMD
    lowers the contraction over the sharded agent axis to collectives
    that move only the masked edges' worth of data."""
    return _finish_combine(
        *_edge_sums(know, topo.nbr, topo.mask, topo.relevance))


def drop_topology_edges(topo: Topology, keep) -> Topology:
    """Cut edges whose message did not survive this share round
    (``keep``: (n, k) bool from ``Transport.deliver_mask``): the mask
    bit goes False and the edge relevance to exactly zero, so both
    eq. 4 sums in ``_edge_sums`` exclude the edge entirely — the
    streaming trainer's equivalent of the buffer trainer's hole slots
    and corruption quarantine. ``deliver_mask`` always keeps the
    self-loop, and ``_finish_combine``'s eps clamp covers even a
    destination with *no* surviving edge, so a faulty round degrades
    toward the local window, never toward NaN. An all-True ``keep``
    is a numerical identity (``mask & True``, ``where(True, rel,
    0)``) — but note the op is still traced, so zero-rate faulty
    streaming programs are equal in value, not in jaxpr."""
    k = jnp.asarray(keep, bool)
    return topo._replace(mask=topo.mask & k,
                         relevance=jnp.where(k, topo.relevance, 0.0))


# ---------------------------------------------------------------------
# elastic membership (alive-masked exchange)
# ---------------------------------------------------------------------
def _select_rows(mask, new, old):
    """Per-agent row select over matching pytrees: rows where ``mask``
    is True come from ``new``, the rest hold ``old`` — the elastic
    trainer's way of freezing dead agents' params/optimizer rows
    without multiply-masking live ones."""
    m = jnp.asarray(mask, bool)

    def sel(n_, o_):
        mm = jnp.reshape(m, (-1,) + (1,) * (n_.ndim - 1))
        return jnp.where(mm, n_, o_)

    return tree_map(sel, new, old)


def mask_knowledge(know: Knowledge, alive) -> Knowledge:
    """Zero dead agents' window rows (tg/rg leaves, tsum/rsum scalars,
    the sk sketch rows) so their eq. 4 numerator *and* denominator
    contributions are exactly zero in every combiner path — the flat
    global sum, the dense-R matmul, the ``_edge_sums`` segment-sum and
    the pod dispatch (a dead leader's planes are zero before anything
    crosses the pod axis). ``rel`` and ``alive`` ride through
    untouched; ``alive=None`` returns ``know`` unchanged (the
    non-elastic structural fixed point)."""
    if alive is None:
        return know
    a = jnp.asarray(alive, bool)

    def rows(x):
        m = jnp.reshape(a, (-1,) + (1,) * (x.ndim - 1))
        return jnp.where(m, x, jnp.zeros_like(x))

    return know._replace(
        tg=tree_map(rows, know.tg),
        rg=tree_map(rows, know.rg),
        tsum=jnp.where(a, know.tsum, 0.0),
        rsum=jnp.where(a, know.rsum, 0.0),
        sk=None if know.sk is None else rows(know.sk))


def quantize_knowledge_roundtrip(know: Knowledge,
                                 q_block: int) -> Knowledge:
    """Push the window's gradient planes (tg/rg leaves) through the
    int8 block-quantized wire format (``repro.kernels.ddal_wavg``) —
    what every cross-agent hop carries when
    ``GroupSpec.knowledge_quant_block > 0``. The streaming combiners
    apply this at combine time, so the ḡ the group consumes matches
    the buffer trainer's quantized-delay-line semantics while the
    window accumulators themselves stay fp32 (they never leave the
    agent's shard). ``q_block <= 0`` is the identity — the historical
    program, bit for bit."""
    if q_block <= 0:
        return know
    from repro.kernels.ddal_wavg import ops as wavg_ops

    def rt(tree):
        q, s = wavg_ops.quantize_tree(tree, q_block, lead=1)
        return wavg_ops.dequantize_tree(q, s, q_block)

    return know._replace(tg=rt(know.tg), rg=rt(know.rg))


def kill_agents(state: TrainState, dead) -> TrainState:
    """Host-side elastic transition: mark ``dead`` ((A,) bool) agents
    as gone. Their partial share window is zeroed — a half-window must
    never leak into a later share step — while their params/optimizer
    rows freeze in place and ``Knowledge.rel`` holds its last live
    estimate (the estimator's alive-gated EMA keeps it frozen from
    here). Checkpoint the state *before* killing to splice the agent
    back in later (``revive_agents``)."""
    know = state.know
    if know.alive is None:
        raise ValueError(
            "kill_agents needs an elastic TrainState — build the spec "
            "with GroupSpec(elastic=True) so Knowledge.alive exists")
    alive = know.alive & ~jnp.asarray(dead, bool)
    return state._replace(
        know=mask_knowledge(know, alive)._replace(alive=alive))


def revive_agents(state: TrainState, mask,
                  restore: Optional[TrainState] = None) -> TrainState:
    """Flip ``mask`` ((A,) bool) agents back alive. Their window rows
    are (re)zeroed — a revival starts from an empty window, never a
    stale one — and with ``restore`` (a checkpointed ``TrainState``)
    the revived agents' params/optimizer rows splice back from the
    checkpoint while every survivor's row is untouched."""
    know = state.know
    if know.alive is None:
        raise ValueError(
            "revive_agents needs an elastic TrainState — build the "
            "spec with GroupSpec(elastic=True) so Knowledge.alive "
            "exists")
    m = jnp.asarray(mask, bool)
    know = mask_knowledge(know, ~m)._replace(alive=know.alive | m)
    params, opt_state = state.params, state.opt_state
    if restore is not None:
        params = _select_rows(m, restore.params, params)
        opt_state = _select_rows(m, restore.opt_state, opt_state)
    return state._replace(params=params, opt_state=opt_state,
                          know=know)


def _per_agent_map(mesh, spec: GroupSpec):
    """``per_agent(fn, shared=(), stacked=False)`` maps ``fn`` over
    the leading agent axis of its arguments (``shared`` names the
    positions of arguments every agent gets whole) and stacks its
    outputs: under ``shard_map`` on the agents' own devices when
    ``mesh`` spreads the agents over its agent axes, else under
    ``vmap``. Where a device of the mesh holds one agent, ``fn`` runs
    on it unbatched: the TPU compiler refuses a ragged dot with a batch
    dimension, which ``vmap`` gives the held experts' grouped products
    even over one agent. A ``stacked`` ``fn`` takes and returns the
    agent axis itself, and gets each device's agents at once."""
    from repro.launch.shardings import ddal_agent_axis
    axis = ddal_agent_axis(mesh, spec.pod_axis) if mesh is not None else None
    names = (axis,) if isinstance(axis, str) else tuple(axis or ())
    devices = 1
    for a in names:
        devices *= mesh.shape[a]
    if names and spec.n_agents % devices:
        raise ValueError(
            f"{spec.n_agents} agents do not spread evenly over the "
            f"{devices} devices of mesh axes {names}")
    local = spec.n_agents // devices if names else spec.n_agents

    def per_agent(fn, shared=(), stacked=False):
        def mapped(*args):
            if stacked:
                return fn(*args)
            if names and local == 1:
                one = [a if i in shared else tree_map(lambda x: x[0], a)
                       for i, a in enumerate(args)]
                return tree_map(lambda x: x[None], fn(*one))
            return jax.vmap(fn, in_axes=tuple(
                None if i in shared else 0 for i in range(len(args))))(
                    *args)
        if not names:
            return mapped
        from jax.sharding import PartitionSpec as P

        def run(*args):
            specs = tuple(P() if i in shared else P(axis)
                          for i in range(len(args)))
            return jax.shard_map(
                mapped, mesh=mesh, in_specs=specs, out_specs=P(axis),
                axis_names=set(names), check_vma=False)(*args)
        return run

    return per_agent


def make_group_train_step(cfg: ArchConfig, spec: GroupSpec,
                          opt: Optimizer,
                          relevance: Optional[jnp.ndarray] = None,
                          loss_fn: Optional[Callable] = None,
                          topology=None,
                          mesh=None,
                          exchange=None):
    """Build the jittable DDAL train step.

    Returns step(state, batch) -> (state', metrics); ``batch`` leaves
    carry a leading (n_agents,) axis (each agent's own data stream).
    The model is resolved lazily from ``cfg`` only when no ``loss_fn``
    is supplied, so toy losses need no ArchConfig (pass ``cfg=None``).

    Exchange decisions live in the ``repro.core.exchange`` protocol
    (built from ``spec`` unless a prebuilt ``exchange`` is passed):
    the combiner strategy picks the global-sum fast path, the
    neighbor-local segment-sum, or — with ``spec.pods > 0`` — the
    two-level pod dispatch (``repro.core.pod_dispatch``), where the
    intra-pod segment stays local to the fast ``"agent"`` mesh axis
    and only the pod leaders' planes cross the ``spec.pod_axis`` axis.
    Pass the two-level ``mesh`` (``repro.launch.mesh.make_pod_mesh``)
    to run the real collective path; without a mesh the mathematically
    identical single-device decomposition runs instead, so the flag is
    meaningful on a 1-CPU rig too.

    ``mesh`` also places the per-agent pieces (module docstring: one
    agent per device); a prebuilt ``exchange`` must have been built
    over the same mesh. The step's metrics carry ``loss`` and,
    where the model counts them (``Model.loss_stats``), its counters,
    each with a leading (n_agents,) axis.
    """
    if loss_fn is None:
        model = get_model(cfg)
        if model.loss_stats is not None:
            def loss_stats(params, batch):
                return model.loss_stats(cfg, params, batch)
        else:
            def loss_stats(params, batch):
                return model.loss(cfg, params, batch), {}
    else:
        def loss_stats(params, batch):
            return loss_fn(params, batch), {}
    if exchange is None:
        from repro.core.exchange import build_exchange
        exchange = build_exchange(spec, mesh, kind="streaming",
                                  topology=topology,
                                  relevance=relevance)
    elif exchange.kind != "streaming":
        raise ValueError(
            f"the streaming train step needs a 'streaming' exchange "
            f"protocol, got {exchange.kind!r}")
    elif topology is not None or relevance is not None:
        raise ValueError(
            "topology/relevance would be silently ignored: they are "
            "baked into the protocol at build time — pass them to "
            "build_exchange(...) instead when supplying a prebuilt "
            "exchange")
    elif mesh is not None and mesh is not exchange.mesh:
        raise ValueError(
            "the prebuilt exchange was built over another mesh than the "
            "step's: pass the step's mesh to build_exchange(...)")
    per_agent = _per_agent_map(mesh, spec)
    learn_rel = exchange.learns
    sketch_dim = exchange.sketch_dim
    # elastic membership is a *static* build fact: non-elastic specs
    # trace exactly the historical program (no alive ops anywhere)
    elastic = bool(getattr(spec, "elastic", False))

    vopt = jax.vmap(opt.update, in_axes=(0, 0, 0, None))

    def train_step(state: TrainState, batch) -> Tuple[TrainState, Any]:
        # No parameter-sized array passes through a lax.cond: XLA copies
        # a conditional's pass-through outputs, and at published widths
        # those copies do not fit beside the state on one chip. The
        # per-epoch choice (warm-up / accumulate / share) is elementwise
        # selects instead, and only the eq. 4 combine — the one step
        # that moves data between agents — runs under a cond.
        step = state.step
        with jax.named_scope("ddal.grad"):
            (losses, stats), grads = per_agent(
                jax.value_and_grad(loss_stats, has_aux=True))(
                    state.params, batch)
        know = state.know
        alive = know.alive if elastic else None
        if elastic and alive is None:
            raise ValueError(
                "GroupSpec.elastic=True but Knowledge.alive is None — "
                "init the state through init_train_state / "
                "init_knowledge(..., alive=...) so the mask exists")

        warmup = step < spec.threshold
        is_share = jnp.logical_not(warmup) & (step % spec.minibatch == 0)
        rnd = (step + spec.minibatch - 1) // spec.minibatch

        # accumulate this epoch's piece into the local window (after
        # warm-up; elastic: live agents only — dead agents' gradients
        # are garbage, their data still flows)
        kdt = jnp.dtype(spec.knowledge_dtype)
        T_t = training_experience(step, spec.t_weighting)
        acc = jnp.logical_not(warmup)
        if elastic:
            acc = acc & alive

        def row_gate(x):
            return jnp.reshape(acc, (-1,) + (1,) * (x.ndim - 1))
        with jax.named_scope("ddal.window"):
            tg = tree_map(
                lambda a, g: jnp.where(
                    row_gate(a),
                    a + (T_t * g.astype(jnp.float32)).astype(kdt), a),
                know.tg, grads)
            rg = tree_map(
                lambda a, g: jnp.where(row_gate(a), a + g.astype(kdt),
                                       a),
                know.rg, grads)
            tsum = know.tsum + jnp.where(acc, T_t, 0.0)
            rsum = know.rsum + jnp.where(acc, 1.0, 0.0)
        sk = know.sk
        if sketch_dim > 0:
            # carry the window sketch: one streaming projection of
            # this epoch's grads, added to the (A, d) running sum.
            # The projection is linear and every step of the window
            # ending at share step t folds the same round index
            # ((step + mb − 1) // mb), so at share time sk IS the
            # sketch of rg — nothing parameter-sized is re-read.
            def add_sketch(_):
                contrib = per_agent(exchange.sketch_step, shared=(1,),
                                    stacked=True)(grads, rnd)
                if elastic:
                    contrib = jnp.where(alive[:, None], contrib, 0.0)
                return know.sk + contrib

            with jax.named_scope("ddal.sketch"):
                sk = jax.lax.cond(warmup, lambda _: know.sk, add_sketch,
                                  None)
        k2 = Knowledge(tg=tg, tsum=tsum, rg=rg, rsum=rsum,
                       rel=know.rel, sk=sk, alive=know.alive)

        def do_share(_):
            # window-accumulated grads are already a temporal average
            # over the share window — the estimator observes them (or
            # the carried (A, d) sketch, so only sketch rows — never
            # parameter planes — cross the mesh for relevance), then
            # the combiner strategy runs eq. 4.
            with jax.named_scope("ddal.combine"):
                rel = exchange.observe(k2.rel, grads=k2.rg, sketch=k2.sk,
                                       rnd=rnd, alive=alive)
                return exchange.combine(k2, rel, step, alive=alive), rel

        gbar_shape = jax.eval_shape(do_share, None)[0]

        def no_share(_):
            return (tree_map(lambda x: jnp.zeros(x.shape, x.dtype),
                             gbar_shape), k2.rel)

        with jax.named_scope("ddal.exchange"):
            gbar, rel = jax.lax.cond(is_share, do_share, no_share, None)

        # warm-up steps apply the local gradient, share steps ḡ, and
        # the steps in between only accumulate
        with jax.named_scope("ddal.optimizer"):
            g_apply = tree_map(lambda g, gb: jnp.where(is_share, gb, g),
                               grads, gbar)
            p2, o2 = vopt(g_apply, state.opt_state, state.params, step)
            update = warmup | is_share
            if elastic:
                update = update & alive
            params = _select_rows(update, p2, state.params)
            opt_state = _select_rows(update, o2, state.opt_state)

        def reset(x):                  # a share step empties the window
            return jnp.where(is_share, jnp.zeros_like(x), x)
        with jax.named_scope("ddal.window"):
            know = k2._replace(
                tg=tree_map(reset, k2.tg), tsum=reset(k2.tsum),
                rg=tree_map(reset, k2.rg), rsum=reset(k2.rsum), rel=rel,
                sk=None if sk is None else reset(sk))
        metrics = {"loss": losses, "step": step,
                   "shared": is_share.astype(jnp.int32), **stats}
        new_state = TrainState(params=params, opt_state=opt_state,
                               know=know, step=step + 1)
        return new_state, metrics

    return train_step
