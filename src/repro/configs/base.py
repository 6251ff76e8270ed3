"""Architecture / shape / group configuration dataclasses.

Every assigned architecture gets a ``src/repro/configs/<id>.py`` module
exporting ``get_config() -> ArchConfig`` with the exact assigned
hyper-parameters (source citations in each file). ``ArchConfig.reduced``
produces the smoke-test variant (≤2 layers, d_model ≤ 512, ≤4 experts)
required to run a real forward/train step on CPU.
"""
from __future__ import annotations

from dataclasses import dataclass, replace
from typing import Optional, Tuple

import jax.numpy as jnp


@dataclass(frozen=True)
class MoEConfig:
    n_experts: int
    top_k: int
    expert_ff: int
    n_shared: int = 0          # shared (always-on) experts, DeepSeek-style
    capacity_factor: float = 1.25
    router_zloss: float = 1e-3
    aux_loss: float = 1e-2     # load-balance auxiliary loss weight
    # -- routing -------------------------------------------------------
    scoring: str = "softmax"   # softmax | sigmoid (DeepSeek-V3)
    router_bias: bool = False  # noaux_tc: a correction bias added to
                               # the scores for selection only
    routed_scaling: float = 1.0    # gates times this after top-k
    norm_topk: bool = True     # gates of the chosen k sum to 1
    # -- held experts (expert parallelism, one device's share) ---------
    n_held: int = 0            # experts this layer holds; 0 = all of
                               # them. Routing still chooses over all
                               # ``n_experts``; absent experts add
                               # nothing, and dispatch drops no token
    first_held: int = 0        # id of the first held expert

    def __post_init__(self):
        if self.scoring not in ("softmax", "sigmoid"):
            raise ValueError(f"unknown MoE scoring {self.scoring!r}")
        if self.scoring == "sigmoid" and self.aux_loss:
            raise ValueError("sigmoid routing balances by its correction "
                             "bias (noaux_tc): set aux_loss to 0")
        if self.n_held and not (
                0 <= self.first_held
                and self.first_held + self.n_held <= self.n_experts):
            last = self.first_held + self.n_held - 1
            raise ValueError(
                f"held experts {self.first_held}..{last} lie outside "
                f"the {self.n_experts} experts")


@dataclass(frozen=True)
class MLAConfig:
    """Multi-head Latent Attention (DeepSeek-V2)."""
    kv_lora_rank: int = 512
    qk_nope_dim: int = 128
    qk_rope_dim: int = 64
    v_dim: int = 128
    q_lora_rank: Optional[int] = None   # V2-Lite: queries not compressed
    rope_interleave: bool = False       # V3: rotate channel pairs
                                        # (2i, 2i+1), not halves


@dataclass(frozen=True)
class SSMConfig:
    """Mamba2 (SSD) block configuration."""
    d_state: int = 128
    expand: int = 2
    head_dim: int = 64
    n_groups: int = 1
    chunk: int = 256
    d_conv: int = 4


@dataclass(frozen=True)
class HybridConfig:
    """Zamba2-style: N super-blocks of (mamba_per_block Mamba2 layers +
    one SHARED attention/MLP block) plus tail Mamba2 layers."""
    n_super_blocks: int = 16
    mamba_per_block: int = 4
    tail_mamba: int = 1
    lora_rank: int = 128       # per-call-site LoRA on the shared block


@dataclass(frozen=True)
class ArchConfig:
    name: str
    family: str                # dense | moe | ssm | hybrid | vlm | audio
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int = 128
    qkv_bias: bool = False
    rope_mode: str = "standard"         # standard | mrope | none
    rope_theta: float = 1e6
    mrope_sections: Tuple[int, int, int] = (16, 24, 24)
    sliding_window: Optional[int] = None
    norm_eps: float = 1e-5
    tie_embeddings: bool = False
    moe: Optional[MoEConfig] = None
    mla: Optional[MLAConfig] = None
    ssm: Optional[SSMConfig] = None
    hybrid: Optional[HybridConfig] = None
    first_k_dense: int = 0              # deepseek: leading dense layers
    dense_ff: int = 0                   # d_ff of those dense layers
    # -- modality backbone stubs (per-spec carve-out) -----------------
    cross_attention: bool = False       # musicgen: cross-attn to cond.
    cond_len: int = 0                   # conditioning sequence length
    n_codebooks: int = 1                # musicgen: 4 EnCodec codebooks
    vision_prefix: int = 0              # qwen2-vl: # of patch embeddings
    # -- numerics / execution -----------------------------------------
    param_dtype: str = "float32"
    compute_dtype: str = "bfloat16"
    remat: bool = True
    unroll_layers: bool = False         # dry-run: exact HLO cost/collectives
    moe_dispatch: str = "auto"          # auto | dense | expert_parallel
    mla_absorb: bool = True             # MLA decode weight absorption
    attention_scores_dtype: str = "float32"   # float32 | bfloat16 (§Perf)
    attention_impl: str = "xla"         # xla | pallas | pallas_interpret
    ssd_impl: str = "xla"               # xla | pallas_interpret
    max_position: int = 1 << 20
    citation: str = ""

    # ------------------------------------------------------------------
    @property
    def q_proj_dim(self) -> int:
        return self.n_heads * self.head_dim

    @property
    def kv_proj_dim(self) -> int:
        return self.n_kv_heads * self.head_dim

    def dtype(self, which: str = "compute"):
        return jnp.dtype(self.param_dtype if which == "param" else
                         self.compute_dtype)

    def with_(self, **kw) -> "ArchConfig":
        return replace(self, **kw)

    def with_layers(self, n: int) -> "ArchConfig":
        """The first ``n`` whole layers at the published widths — the
        depth cut that fits a group of agents on one chip's share of
        the model. Hybrid configs count depth in super-blocks and are
        not cut here."""
        if self.hybrid is not None:
            raise ValueError(
                f"{self.name}: a hybrid config's depth is set by its "
                f"super-blocks, not by n_layers")
        if not 1 <= n <= self.n_layers:
            raise ValueError(
                f"{self.name} has {self.n_layers} layers; cannot keep "
                f"{n}")
        return replace(self, n_layers=n,
                       first_k_dense=min(self.first_k_dense, n))

    def reduced(self) -> "ArchConfig":
        """Smoke-test variant: ≤2 layers, d_model ≤ 512, ≤4 experts."""
        d_model = min(self.d_model, 256)
        head_dim = 32
        n_heads = min(self.n_heads, 4)
        n_kv = max(1, min(self.n_kv_heads, n_heads))
        # keep the GQA ratio interesting but legal
        while n_heads % n_kv:
            n_kv -= 1
        kw = dict(
            n_layers=2,
            d_model=d_model,
            n_heads=n_heads,
            n_kv_heads=n_kv,
            head_dim=head_dim,
            d_ff=min(self.d_ff, 512) if self.d_ff else 0,
            vocab_size=min(self.vocab_size, 512),
            cond_len=min(self.cond_len, 8) if self.cross_attention else 0,
            vision_prefix=min(self.vision_prefix, 8),
            max_position=1 << 14,
            param_dtype="float32",
            compute_dtype="float32",
            remat=False,
        )
        if self.moe is not None:
            kw["moe"] = replace(self.moe, n_experts=4,
                                top_k=min(self.moe.top_k, 2),
                                expert_ff=128,
                                n_shared=min(self.moe.n_shared, 1),
                                n_held=0, first_held=0)
        if self.mla is not None:
            kw["mla"] = replace(self.mla, kv_lora_rank=64, qk_nope_dim=32,
                                qk_rope_dim=16, v_dim=32)
        if self.ssm is not None:
            kw["ssm"] = replace(self.ssm, d_state=16, head_dim=16,
                                chunk=32)
        if self.hybrid is not None:
            kw["hybrid"] = replace(self.hybrid, n_super_blocks=1,
                                   mamba_per_block=1, tail_mamba=1,
                                   lora_rank=8)
            kw["n_layers"] = 3
        if self.first_k_dense:
            kw["dense_ff"] = 128
        if self.sliding_window is not None:
            kw["sliding_window"] = 16
        if self.rope_mode == "mrope":
            # sections must sum to head_dim/2 = 16
            kw["mrope_sections"] = (4, 6, 6)
        return replace(self, **kw)


# ---------------------------------------------------------------------
# Input shapes (assigned). ``kind`` selects which step function the
# dry-run lowers: train_step / prefill_step / decode_step.
# ---------------------------------------------------------------------
@dataclass(frozen=True)
class ShapeConfig:
    name: str
    seq_len: int
    global_batch: int
    kind: str                  # train | prefill | decode


INPUT_SHAPES = {
    "train_4k":    ShapeConfig("train_4k",    4_096,   256, "train"),
    "prefill_32k": ShapeConfig("prefill_32k", 32_768,   32, "prefill"),
    "decode_32k":  ShapeConfig("decode_32k",  32_768,  128, "decode"),
    "long_500k":   ShapeConfig("long_500k",  524_288,    1, "decode"),
}

# Dense (full-attention) archs fall back to a sliding-window variant for
# long_500k (sub-quadratic requirement).
LONG_CONTEXT_WINDOW = 8_192


@dataclass(frozen=True)
class GroupSpec:
    """DDAL group-agent training configuration (paper §5).

    Invalid combinations raise ``ValueError`` at construction (they
    used to surface as shape/index errors deep inside jit): unknown
    ``topology`` / ``relevance_mode`` strings, ``resample_every < 0``,
    and ``degree >= n_agents`` for ``random_k`` (the gossip degree
    counts the self-loop; k = n is spelled ``topology="full"``).
    """
    n_agents: int = 1
    threshold: int = 1_000       # warm-up epochs of independent learning
    minibatch: int = 100         # share/update cadence (paper's name)
    m_pieces: int = 8            # pieces retrieved from K_i ∪ K_-i
    knowledge_mode: str = "buffer"   # buffer | streaming (LLM-scale)
    knowledge_dtype: str = "float32" # streaming accumulators (bf16 halves
                                     # the cross-pod exchange traffic)
    # communication graph (repro.core.topology): full | ring | torus2d
    # | star | random_k | hierarchical
    topology: str = "full"
    degree: int = 4              # k for random_k; pod size for hierarchical
    pods: int = 0                # multi-host dispatch: map hierarchical
                                 # pods onto a two-level mesh (0 = flat
                                 # single-mesh combine; requires
                                 # n_agents == pods * degree)
    pod_axis: str = "pod"        # mesh axis the leader-level (DCN)
                                 # exchange crosses; intra-pod exchange
                                 # stays on the "agent" axis
    topology_seed: int = 0       # seed for random_k gossip sampling
    resample_every: int = 0      # dynamic gossip: resample the random_k
                                 # table every N epochs (0 = static)
    max_delay: int = 0           # async staleness simulation (epochs)
    t_weighting: str = "epochs"  # T_j source
    r_weighting: str = "uniform" # R_j source (paper §6 uses uniform)
    relevance_mode: str = "uniform"  # online R estimator: uniform |
                                     # grad_cos (repro.core.relevance)
    relevance_ema: float = 0.9   # EMA decay of the learned R estimate
    relevance_sketch_dim: int = 0    # grad_cos at LLM scale: stream
                                     # gradients through a seeded ±1
                                     # projection into (n, d) sketches
                                     # and cosine those — O(n·|params|)
                                     # + O(n²·d) instead of
                                     # O(n²·|params|); 0 = exact
                                     # pairwise cosines
    # -- exchange-protocol strategy overrides (repro.core.exchange) ---
    # "auto" derives each strategy from the legacy flags above (the
    # bitwise-pinned mapping); explicit keys select registered
    # strategies directly — e.g. exchange_schedule="relevance_topk"
    # (Gumbel top-k gossip over the learned R) or
    # exchange_estimator="obs_stats" (observation-overlap relevance).
    exchange_schedule: str = "auto"   # auto | static | dynamic |
                                      # relevance_topk
    exchange_estimator: str = "auto"  # auto | uniform | grad_cos |
                                      # grad_cos+sketch | obs_stats
    exchange_delay: str = "auto"      # auto | none | uniform | hops
    exchange_combiner: str = "auto"   # auto | flat | pod | store
    explore_eps: float = 0.1          # relevance_topk: per-destination
                                      # ε-greedy uniform-gossip rate
    elastic: bool = False             # elastic membership: thread a
                                      # per-agent alive mask through
                                      # the exchange (eq. 4 masking,
                                      # delay-line drop on death,
                                      # frozen relevance EMA, gossip
                                      # exclusion). False keeps every
                                      # trainer's jitted program
                                      # structurally unchanged.
    knowledge_quant_block: int = 0    # >0: store/ship knowledge planes
                                      # as int8 with one fp32 scale per
                                      # this many flat elements (~4×
                                      # lighter delay lines and
                                      # cross-pod bytes). Must be a
                                      # multiple of 128 dividing 8192
                                      # (whole sublane row groups of
                                      # the wavg kernel tile). 0 = fp32
                                      # planes, bitwise-legacy.
    # -- transport faults (repro.core.transport) ----------------------
    # Seeded per-edge message faults on the exchange path. All-zero
    # rates keep the exchange structurally identical to the perfect-
    # delivery programs (the same contract elastic=False honors).
    transport_loss: float = 0.0       # per-message per-edge loss prob.
    transport_dup: float = 0.0        # duplicate-delivery probability
    transport_corrupt: float = 0.0    # in-flight payload-garble prob.
                                      # (checksummed + quarantined at
                                      # deliver: exactly-zero eq. 4
                                      # weight)
    transport_jitter: int = 0         # max uniform extra delivery
                                      # delay (epochs) on top of the
                                      # delay model
    transport_retransmit: int = 0     # retry budget per lost message
                                      # (exponential backoff 1,2,4,…
                                      # epochs; resolved at plan time)
    transport_seed: int = 0           # fault-plan seed (numpy RNG —
                                      # never touches trainer PRNG)
    transport_horizon: int = 256      # planned epochs before the
                                      # fault history replays
    transport_decay: float = 1.0      # staleness discount per epoch
                                      # of arrival-slot age on the
                                      # eq. 4 T/R terms (1.0 = none)
    max_staleness: Optional[int] = None   # hard cutoff: arrival slots
                                      # older than this many epochs
                                      # get zero eq. 4 weight; when no
                                      # slot survives the agent falls
                                      # back to its purely-local
                                      # update. None disables age
                                      # tracking (buffer trainer only).
    exchange_transport: str = "auto"  # auto | none | faulty

    def __post_init__(self):
        # deferred imports: repro.core modules import this module for
        # the dataclass, so the name tables must resolve lazily.
        from repro.core.exchange import validate_choice
        from repro.core.relevance import RELEVANCE_MODES
        from repro.core.topology import TOPOLOGIES
        if self.topology not in TOPOLOGIES:
            raise ValueError(
                f"unknown topology {self.topology!r}; expected one of "
                f"{TOPOLOGIES}")
        if self.relevance_mode not in RELEVANCE_MODES:
            raise ValueError(
                f"unknown relevance_mode {self.relevance_mode!r}; "
                f"expected one of {RELEVANCE_MODES}")
        if self.resample_every < 0:
            raise ValueError(
                f"resample_every must be >= 0, got {self.resample_every}")
        if self.resample_every > 0 and self.topology != "random_k":
            raise ValueError(
                f"resample_every > 0 needs topology='random_k', got "
                f"{self.topology!r}")
        validate_choice("schedule", self.exchange_schedule)
        validate_choice("estimator", self.exchange_estimator)
        validate_choice("delay", self.exchange_delay)
        validate_choice("combiner", self.exchange_combiner)
        if self.exchange_schedule == "relevance_topk":
            if self.topology != "random_k" or self.resample_every < 1:
                raise ValueError(
                    "exchange_schedule='relevance_topk' resamples a "
                    "gossip graph and needs topology='random_k' with "
                    "resample_every >= 1, got "
                    f"topology={self.topology!r}, "
                    f"resample_every={self.resample_every}")
        if self.exchange_schedule == "static" and self.resample_every:
            raise ValueError(
                "exchange_schedule='static' pins a fixed graph but "
                f"resample_every={self.resample_every} requests "
                "resampling — drop one of them")
        if not 0.0 <= self.explore_eps <= 1.0:
            raise ValueError(
                f"explore_eps must be in [0, 1], got "
                f"{self.explore_eps}")
        if self.topology == "random_k":
            if not 1 <= self.degree < max(self.n_agents, 2):
                raise ValueError(
                    f"random_k degree must satisfy 1 <= degree < "
                    f"n_agents (self-loop included; use topology="
                    f"'full' for k = n), got degree={self.degree} "
                    f"with n_agents={self.n_agents}")
        if not 0.0 <= self.relevance_ema < 1.0:
            raise ValueError(
                f"relevance_ema must be in [0, 1), got "
                f"{self.relevance_ema}")
        if self.relevance_sketch_dim < 0:
            raise ValueError(
                f"relevance_sketch_dim must be >= 0 (0 = exact "
                f"pairwise cosines), got {self.relevance_sketch_dim}")
        if (self.exchange_estimator not in ("auto", "grad_cos+sketch")
                and self.relevance_sketch_dim > 0):
            raise ValueError(
                f"exchange_estimator={self.exchange_estimator!r} "
                "does not sketch and would silently ignore "
                f"relevance_sketch_dim={self.relevance_sketch_dim} — "
                "use 'grad_cos+sketch' (or drop the dim)")
        if (self.relevance_sketch_dim > 0
                and self.relevance_mode != "grad_cos"
                and self.exchange_estimator != "grad_cos+sketch"):
            raise ValueError(
                f"relevance_sketch_dim > 0 sketches the grad_cos "
                f"estimator and needs relevance_mode='grad_cos' (or "
                f"exchange_estimator='grad_cos+sketch'), got "
                f"{self.relevance_mode!r}")
        if self.pods < 0:
            raise ValueError(f"pods must be >= 0, got {self.pods}")
        if self.pods > 0:
            if self.topology != "hierarchical":
                raise ValueError(
                    f"pods > 0 maps hierarchical pods onto a two-level "
                    f"mesh and needs topology='hierarchical', got "
                    f"{self.topology!r}")
            if self.n_agents != self.pods * self.degree:
                raise ValueError(
                    f"pod dispatch needs n_agents == pods * degree "
                    f"(uniform pods of `degree` agents), got "
                    f"n_agents={self.n_agents}, pods={self.pods}, "
                    f"degree={self.degree}")
            if (not self.pod_axis
                    or not isinstance(self.pod_axis, str)
                    or self.pod_axis == "agent"):
                raise ValueError(
                    f"pod_axis must be a non-empty mesh axis name "
                    f"distinct from the intra-pod 'agent' axis, got "
                    f"{self.pod_axis!r}")
        qb = self.knowledge_quant_block
        if qb < 0:
            raise ValueError(
                f"knowledge_quant_block must be >= 0, got {qb}")
        if qb > 0 and (qb % 128 != 0 or 8192 % qb != 0):
            raise ValueError(
                f"knowledge_quant_block must be a multiple of 128 "
                f"dividing 8192 (one scale per whole sublane row group "
                f"of the wavg kernel tile), got {qb}")
        for name in ("transport_loss", "transport_dup",
                     "transport_corrupt"):
            p = getattr(self, name)
            if not 0.0 <= p <= 1.0:
                raise ValueError(
                    f"{name} is a per-message probability and must be "
                    f"in [0, 1], got {p}")
        if self.transport_jitter < 0:
            raise ValueError(
                f"transport_jitter must be >= 0 (max extra delivery "
                f"delay in epochs), got {self.transport_jitter}")
        if not 0 <= self.transport_retransmit <= 8:
            raise ValueError(
                f"transport_retransmit must be in [0, 8] (the delay "
                f"line grows by the 2^budget - 1 worst-case backoff), "
                f"got {self.transport_retransmit}")
        if self.transport_horizon < 1:
            raise ValueError(
                f"transport_horizon must be >= 1 (planned epochs "
                f"before the fault history replays), got "
                f"{self.transport_horizon}")
        if not 0.0 < self.transport_decay <= 1.0:
            raise ValueError(
                f"transport_decay must be in (0, 1] (per-epoch "
                f"staleness discount; 1.0 = none), got "
                f"{self.transport_decay}")
        if self.max_staleness is not None and self.max_staleness < 1:
            raise ValueError(
                f"max_staleness must be >= 1 (epochs; None disables "
                f"the cutoff), got {self.max_staleness}")
        validate_choice("transport", self.exchange_transport)
        if self.exchange_transport == "none" and (
                self.transport_loss > 0 or self.transport_dup > 0
                or self.transport_corrupt > 0
                or self.transport_jitter > 0):
            raise ValueError(
                "exchange_transport='none' would silently ignore the "
                "nonzero transport fault knobs (loss="
                f"{self.transport_loss}, dup={self.transport_dup}, "
                f"corrupt={self.transport_corrupt}, jitter="
                f"{self.transport_jitter}) — use 'faulty' (or 'auto') "
                "or zero the rates")
