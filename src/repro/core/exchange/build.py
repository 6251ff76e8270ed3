"""Assemble an :class:`ExchangeProtocol` from a ``GroupSpec``.

``build_exchange(spec, mesh=None)`` is the one place that turns
configuration into strategy objects: it resolves each of the four
families (schedule, estimator, delay model, combiner) against the
string-keyed registries — ``"auto"`` derives the key from the legacy
``GroupSpec`` flags, so every pre-redesign spelling maps onto exactly
the strategies that reproduce it bitwise — and returns one protocol
object both trainers loop over:

    protocol.topology_at(step, nbr, rel)  → the graph in force
    protocol.observe(rel, grads=..., …)   → updated relevance state
    protocol.combine(knowledge, rel, t)   → the eq. 4 update

``build_exchange`` is **pure**: it allocates no traced state and
closes only over host constants, so two calls with the same arguments
produce protocols whose jitted steps are bitwise-equal (pinned in
``tests/test_exchange.py``). That purity is what makes the protocol a
safe unit for a future ``jax.distributed`` driver to construct per
process.

Legacy-flag → strategy mapping (the full table lives in
``docs/exchange.md``):

==============================  =================================
GroupSpec flags                 strategies
==============================  =================================
``topology``/``degree``/seed    ``static`` schedule
``resample_every > 0``          ``dynamic`` schedule
``relevance_mode="uniform"``    ``uniform`` estimator
``relevance_mode="grad_cos"``   ``grad_cos`` estimator
``… + relevance_sketch_dim>0``  ``grad_cos+sketch`` estimator
``pods > 0``                    ``pod`` combiner
(buffer trainer)                ``store`` combiner
(streaming trainer)             ``flat`` combiner
==============================  =================================
"""
from __future__ import annotations

from typing import Any, Optional

import jax.numpy as jnp

from repro.core.exchange.combiners import _edge_effective
from repro.core.exchange.delays import DelayModel
from repro.core.exchange.estimators import (
    GradCosEstimator,
    ObsStatsEstimator,
    RelevanceEstimator,
    SketchedGradCosEstimator,
    UniformEstimator,
)
from repro.core.exchange.registry import (
    COMBINERS,
    DELAYS,
    ESTIMATORS,
    SCHEDULES,
)
from repro.core.exchange.schedules import (
    DynamicSchedule,
    RelevanceTopKSchedule,
    StaticSchedule,
    TopologySchedule,
)
from repro.core.topology import (
    DynamicTopology,
    Topology,
    make_topology,
)

KINDS = ("buffer", "streaming")


class ExchangeProtocol:
    """One knowledge-exchange protocol: the four strategies plus the
    spec facts the trainers still need, behind three calls.

    The buffer trainer (:class:`repro.core.ddal.DDAL`) carries
    ``(nbr, relevance)`` state and drives ``topology_at`` →
    ``observe`` → ``apply_relevance`` → (delay lines) → ``combine``;
    the streaming trainer carries relevance in ``Knowledge.rel`` and
    drives ``sketch_step`` (accumulation) → ``observe`` → ``combine``
    at share steps. Neither branches on a single ``GroupSpec`` flag —
    every decision was resolved here, once, at build time.
    """

    def __init__(self, *, spec, kind: str,
                 schedule: Optional[TopologySchedule],
                 estimator: RelevanceEstimator,
                 delay_model: DelayModel, combiner,
                 static_topology: Topology, transport=None, mesh=None):
        self.spec = spec
        self.kind = kind
        self.mesh = mesh        # the mesh the combiner was built over
        self.schedule = schedule
        self.estimator = estimator
        self.delay_model = delay_model
        self.combiner = combiner
        self.static_topology = static_topology
        self.transport = transport
        sched_delay = schedule.max_delay if schedule is not None else 0
        self.max_delay = max(sched_delay, spec.max_delay)
        if transport is not None:
            # jitter / retransmit backoff / the duplicate's +1 slot
            # all land deeper in the delay line; the headroom is
            # knob-derived (not plan-realised), so the compiled
            # program shape never depends on the fault draw
            self.max_delay += transport.extra_delay
        ms = getattr(spec, "max_staleness", None)
        decay = float(getattr(spec, "transport_decay", 1.0))
        #: stores/delay lines carry per-piece send epochs (staleness
        #: cutoff and/or age-discounted eq. 4 weighting reads them)
        self.track_born = bool(
            kind == "buffer"
            and (ms is not None
                 or (transport is not None and decay < 1.0)))

    def transport_at(self, step):
        """This step's per-edge fault slice (``None`` on a perfect
        transport — the trainers skip the faulted send path)."""
        return (None if self.transport is None
                else self.transport.at(step))

    # -- facts ---------------------------------------------------------
    @property
    def learns(self) -> bool:
        return self.estimator.learns

    @property
    def sketch_dim(self) -> int:
        return self.estimator.sketch_dim

    @property
    def wants_obs(self) -> bool:
        return self.estimator.wants_obs

    # -- state init ----------------------------------------------------
    def init_table(self) -> jnp.ndarray:
        return self.schedule.init_table()

    def init_relevance(self) -> Any:
        """Estimator state at its prior (the buffer trainer's
        ``GroupState.relevance``)."""
        return self.estimator.init(self.spec.n_agents)

    def streaming_rel_init(self) -> Any:
        """``Knowledge.rel`` seed: ``None`` when nothing is learned
        (keeps the uniform streaming state pytree unchanged)."""
        if not self.estimator.learns:
            return None
        return self.estimator.init(self.spec.n_agents)

    # -- the protocol --------------------------------------------------
    def topology_at(self, step, nbr, rel_state=None, alive=None):
        """(graph in force at ``step``, refreshed carried table).
        ``alive`` excludes dead agents from resampled gossip draws
        (elastic membership) — static tables are alive-gated at the
        send/combine sites instead."""
        rel = None
        if self.schedule.uses_relevance:
            rel = self.estimator.matrix(rel_state)
        nbr = self.schedule.refresh(step, nbr, rel, alive)
        return self.schedule.materialize(step, nbr, rel), nbr

    def observe(self, rel_state, *, grads=None, sketch=None, aux=None,
                rnd=0, enabled=True, alive=None):
        """One estimator update (identity for non-learning modes).
        ``alive`` freezes estimate entries that touch a dead agent."""
        return self.estimator.observe(rel_state, grads=grads,
                                      sketch=sketch, aux=aux, rnd=rnd,
                                      enabled=enabled, alive=alive)

    def apply_relevance(self, topo: Topology, rel_state) -> Topology:
        """Effective per-edge R = static prior × learned estimate on
        ``topo``'s edge table; ``topo`` untouched when nothing is
        learned (the structural uniform fixed point)."""
        if not self.estimator.learns:
            return topo
        return _edge_effective(topo, self.estimator.matrix(rel_state))

    def combine(self, knowledge, rel_state, step, alive=None):
        """The eq. 4 aggregation of the chosen combiner strategy.
        ``alive`` masks dead agents' contributions to exactly zero."""
        rel = None
        if self.estimator.learns and rel_state is not None:
            rel = self.estimator.matrix(rel_state)
        return self.combiner(knowledge, rel, step, alive)

    def sketch_step(self, grads, rnd):
        """This step's (n, d) window-sketch contribution (sketched
        estimators only — ``None`` otherwise)."""
        return self.estimator.sketch_step(grads, rnd)


# ---------------------------------------------------------------------
# per-family resolution
# ---------------------------------------------------------------------
def _schedule_key(spec) -> str:
    key = spec.exchange_schedule
    if key != "auto":
        return key
    return "dynamic" if spec.resample_every > 0 else "static"


def _estimator_key(spec) -> str:
    key = spec.exchange_estimator
    if key != "auto":
        return key
    if spec.relevance_mode == "uniform":
        return "uniform"
    return ("grad_cos+sketch" if spec.relevance_sketch_dim > 0
            else "grad_cos")


def _combiner_key(spec, kind: str) -> str:
    key = spec.exchange_combiner
    if key != "auto":
        return key
    if kind == "buffer":
        return "store"
    return "pod" if spec.pods > 0 else "flat"


def _delay_key(spec) -> str:
    key = spec.exchange_delay
    return "none" if key == "auto" else key


def _make_estimator(spec, obs_dim) -> RelevanceEstimator:
    key = _estimator_key(spec)
    cls = ESTIMATORS.get(key)
    if cls is UniformEstimator:
        return UniformEstimator()
    if cls is GradCosEstimator:
        return GradCosEstimator(spec.relevance_ema)
    if cls is SketchedGradCosEstimator:
        dim = spec.relevance_sketch_dim
        if dim <= 0:
            raise ValueError(
                "estimator 'grad_cos+sketch' needs "
                "relevance_sketch_dim > 0 (the sketch width)")
        return SketchedGradCosEstimator(spec.relevance_ema, dim,
                                        spec.topology_seed)
    if cls is ObsStatsEstimator:
        return ObsStatsEstimator(spec.relevance_ema, obs_dim)
    # user-registered estimators construct from the spec directly
    return cls(spec)


def _make_delay_model(spec, delay) -> DelayModel:
    key = _delay_key(spec)
    if key != "none" and delay is not None:
        raise ValueError(
            f"explicit delay= arrays and the {key!r} delay model are "
            f"mutually exclusive — pick one delay source")
    if key == "none":
        return DELAYS.get("none")()
    if key == "uniform":
        return DELAYS.get("uniform")(spec.max_delay)
    if key == "hops":
        return DELAYS.get("hops")(max(spec.max_delay, 1))
    return DELAYS.get(key)(spec)


def _make_schedule(spec, key: str, topology, relevance, delay,
                   delay_model: DelayModel
                   ) -> Optional[TopologySchedule]:
    """Resolve the schedule, attaching explicit ``relevance``/
    ``delay`` overrides and the delay model onto the right object
    (edge table for static graphs, dense carry for resampling ones)."""
    if topology is not None:
        # explicit graph object: honor it, attach overrides exactly as
        # the trainers always did — but never silently downgrade an
        # explicitly requested schedule strategy
        if isinstance(topology, DynamicTopology):
            if key == "relevance_topk":
                # rebuild the resampler around the dynamic object's
                # base, inheriting its dense carries
                sched = RelevanceTopKSchedule(
                    topology.base,
                    topology.resample_every or spec.resample_every,
                    topology.seed, spec.explore_eps,
                    dense_delay=topology.dense_delay,
                    dense_relevance=topology.dense_relevance)
                sched.with_dense(delay=delay, relevance=relevance)
                return sched.with_dense(
                    delay=delay_model.dense_scalar())
            if (spec.exchange_schedule == "static"
                    and topology.resample_every > 0):
                raise ValueError(
                    "exchange_schedule='static' pins a fixed graph "
                    "but the explicit DynamicTopology resamples every "
                    f"{topology.resample_every} epochs — pass its "
                    ".base (a static Topology) or drop the override")
            topology = topology.with_dense(delay=delay,
                                           relevance=relevance)
            scalar = delay_model.dense_scalar()
            if scalar is not None:
                topology = topology.with_dense(delay=scalar)
            if topology.dense_delay is None:
                topology._uniform_base_delay()  # validate early
            return DynamicSchedule(topology)
        if key == "relevance_topk":
            # a resampling schedule: per-edge attachment cannot follow
            # the table swaps, so annotations ride as dense carries
            sched = RelevanceTopKSchedule(topology, spec.resample_every,
                                          spec.topology_seed,
                                          spec.explore_eps)
            sched.with_dense(delay=delay, relevance=relevance)
            return sched.with_dense(delay=delay_model.dense_scalar())
        if key == "dynamic":
            raise ValueError(
                "schedule 'dynamic' was requested with an explicit "
                "static Topology — pass a DynamicTopology (it carries "
                "the resample cadence and dense annotations) or drop "
                "the explicit topology to build one from the spec")
        if relevance is not None:
            topology = topology.with_relevance(relevance)
        if delay is not None:
            topology = topology.with_delay(delay)
        return StaticSchedule(delay_model.attach(topology))

    built = make_topology(spec, delay=delay, relevance=relevance)
    if key == "relevance_topk":
        if isinstance(built, DynamicTopology):
            # make_topology already validated + dense-attached the
            # (n, n) overrides; inherit its carries wholesale
            base, dd, dr = (built.base, built.dense_delay,
                            built.dense_relevance)
        else:
            base, dd, dr = built, None, None
        sched = RelevanceTopKSchedule(base, spec.resample_every,
                                      spec.topology_seed,
                                      spec.explore_eps,
                                      dense_delay=dd,
                                      dense_relevance=dr)
        return sched.with_dense(delay=delay_model.dense_scalar())
    if isinstance(built, DynamicTopology):
        scalar = delay_model.dense_scalar()
        if scalar is not None:
            built = built.with_dense(delay=scalar)
        return DynamicSchedule(built)
    if key == "dynamic":
        raise ValueError(
            "schedule 'dynamic' needs resample_every >= 1 (and "
            "topology='random_k'); use 'static' for a fixed graph")
    return StaticSchedule(delay_model.attach(built))


# ---------------------------------------------------------------------
# the assembler
# ---------------------------------------------------------------------
def build_exchange(spec, mesh=None, *, kind: Optional[str] = None,
                   topology=None, relevance=None, delay=None,
                   obs_dim: Optional[int] = None,
                   use_wavg_kernel: bool = False) -> ExchangeProtocol:
    """Build the exchange protocol for ``spec``.

    ``kind`` selects the trainer family the protocol will serve —
    ``"buffer"`` (piece-faithful stores, :class:`repro.core.ddal.
    DDAL`) or ``"streaming"`` (window accumulators,
    :func:`repro.core.sharded_ddal.make_group_train_step`) — and
    defaults to ``spec.knowledge_mode``. ``topology`` /
    ``relevance`` / ``delay`` are the trainers' explicit-override
    arguments (a graph object, a dense or per-edge R prior, a delay
    matrix); ``obs_dim`` is required only by the ``obs_stats``
    estimator; ``mesh`` only by the ``pod`` combiner's collective
    lowering.
    """
    kind = kind or spec.knowledge_mode
    if kind not in KINDS:
        raise ValueError(
            f"unknown exchange kind {kind!r}; expected one of {KINDS}")

    sched_key = _schedule_key(spec)
    comb_key = _combiner_key(spec, kind)
    if kind == "buffer" and comb_key != "store":
        raise ValueError(
            f"the buffer trainer aggregates knowledge stores and "
            f"needs the 'store' combiner, got {comb_key!r}")
    if kind == "streaming" and comb_key == "store":
        raise ValueError(
            "the 'store' combiner aggregates ring-buffer pieces and "
            "only serves the buffer trainer; streaming wants 'flat' "
            "or 'pod'")

    if kind == "streaming" and _delay_key(spec) != "none":
        raise ValueError(
            f"delay model {_delay_key(spec)!r} has no effect on the "
            f"streaming trainer (window accumulators exchange at "
            f"share steps; there is no delay line to stale) — drop "
            f"exchange_delay, or use the buffer trainer for "
            f"asynchrony simulation")
    delay_model = _make_delay_model(spec, delay)
    estimator = _make_estimator(spec, obs_dim)
    if kind == "streaming" and estimator.wants_obs:
        raise ValueError(
            f"estimator {_estimator_key(spec)!r} needs the trainers' "
            f"observation side channel (metrics['obs_moments']), "
            f"which the streaming train step does not carry — it "
            f"would silently hold the uniform prior forever; use the "
            f"buffer trainer for observation-statistics relevance")

    from repro.core.transport import make_transport, transport_enabled
    faulty = transport_enabled(spec)
    if kind == "streaming":
        if getattr(spec, "max_staleness", None) is not None:
            raise ValueError(
                "max_staleness ages buffer-trainer arrival slots; the "
                "streaming trainer's window accumulators are rebuilt "
                "every share round and have no staleness to cut — "
                "drop max_staleness or use the buffer trainer")
        if faulty and (spec.transport_jitter > 0
                       or spec.transport_retransmit > 0):
            raise ValueError(
                "transport_jitter / transport_retransmit delay "
                "deliveries through the buffer trainer's delay line; "
                "the streaming trainer exchanges whole windows at "
                "share steps (no line to delay — a message is either "
                "in this round or gone), got jitter="
                f"{spec.transport_jitter}, retransmit="
                f"{spec.transport_retransmit}; zero them or use the "
                "buffer trainer")

    # the streaming global-sum fast path: no graph object at all when
    # the spec names the full topology with nothing time-varying (an
    # explicit relevance matrix then weights the dense eq. 4 directly)
    # — a faulty transport drops per-round *edges*, so it always
    # needs the edge-table path
    dense_R = None
    if (kind == "streaming" and topology is None
            and spec.topology == "full" and spec.resample_every == 0
            and sched_key == "static" and not faulty):
        schedule = None
        dense_R = relevance
    else:
        schedule = _make_schedule(spec, sched_key, topology, relevance,
                                  delay, delay_model)

    transport = make_transport(
        spec, tuple(schedule.base.nbr.shape)
        if schedule is not None else (spec.n_agents, spec.n_agents))

    combiner = COMBINERS.get(comb_key)(
        spec=spec, schedule=schedule, estimator=estimator,
        dense_R=dense_R, mesh=mesh, use_wavg_kernel=use_wavg_kernel,
        transport=transport)

    static_topo = schedule.base if schedule is not None else None
    return ExchangeProtocol(spec=spec, kind=kind, schedule=schedule,
                            estimator=estimator,
                            delay_model=delay_model, combiner=combiner,
                            static_topology=static_topo,
                            transport=transport, mesh=mesh)
