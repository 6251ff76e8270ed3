"""Operations and bytes that the algorithms need, from their shapes.

Counted from what the algorithm must compute, whatever implements it:
a matrix product of (m, k) by (k, n) is 2mkn operations; elementwise
work is not counted. Recomputation for memory is not counted either,
so a model-FLOP utilization built on these is the model's, not the
implementation's.
"""
from __future__ import annotations

from typing import Dict, Iterable

from mamba2_ref import vocab_rows


def mamba2_matmul_params(conf: dict) -> int:
    """Weights that enter a matrix product once per token: the input
    and output projections of every layer, and the head over the whole
    (padded) table, tied or not. The embedding is a gather and does no
    product."""
    s = conf["ssm_cfg"]
    E = conf["d_model"]
    DI = s["expand"] * E
    H = DI // s["headdim"]
    BC = s["ngroups"] * s["d_state"]
    per_layer = E * (2 * DI + 2 * BC + H) + DI * E
    return conf["n_layer"] * per_layer + E * vocab_rows(conf)


def mamba2_ssd_forward_per_token(conf: dict, seq: int) -> float:
    """Forward SSD operations per token with the chunked algorithm
    (arXiv:2405.21060 section 6) at chunk length l over a sequence of
    ``seq``: C.B^T scores within a chunk (per group), the masked
    scores times x (per head), the chunk states B^T x, and the output
    from the entering state C.h (per head). The inter-chunk recurrence
    is (P N) per chunk and head, and is counted too."""
    s = conf["ssm_cfg"]
    DI = s["expand"] * conf["d_model"]
    P, N, G = s["headdim"], s["d_state"], s["ngroups"]
    H = DI // P
    lch = min(s["chunk_size"], seq)
    chunks = -(-seq // lch)
    per_chunk = (2 * lch * lch * N * G        # C B^T
                 + 2 * lch * lch * P * H      # (masked scores) x
                 + 2 * lch * N * P * H        # chunk state B^T x
                 + 2 * lch * N * P * H        # C h_in
                 + 2 * N * P * H)             # recurrence over chunks
    return conf["n_layer"] * per_chunk * chunks / seq


def mamba2_train_flops_per_token(conf: dict, seq: int) -> float:
    """Forward and backward: three times the forward work."""
    return 3.0 * (2 * mamba2_matmul_params(conf)
                  + mamba2_ssd_forward_per_token(conf, seq))


def sketch_work(leaf_sizes: Iterable[int], n: int, dim: int) -> Dict[str, float]:
    """One streaming sign projection of n agents' gradients into an
    (n, dim) sketch (``kernels/grad_sketch``): a (n, p) by (p, dim)
    product for each leaf of p positions; the gradients are read once
    in float32 and the sketch written once. The ±1 signs are generated,
    not read."""
    p = sum(int(x) for x in leaf_sizes)
    return {"flops": 2.0 * n * p * dim, "bytes": 4.0 * n * p + 4.0 * n * dim}


def wavg_work(n_dst: int, m_pieces: int, p: int) -> Dict[str, float]:
    """The eq. 4 weighted average (``kernels/ddal_wavg``): each of
    ``n_dst`` destinations reads ``m_pieces`` float32 planes of ``p``
    elements and writes one, with a multiply-add per element read."""
    return {"flops": 2.0 * n_dst * m_pieces * p,
            "bytes": 4.0 * n_dst * (m_pieces + 1) * p}


def mlp_flops(sizes: Iterable[int]) -> int:
    """Forward operations of one row through dense layers of ``sizes``
    (input first)."""
    s = list(sizes)
    return sum(2 * a * b for a, b in zip(s[:-1], s[1:]))


def a2c_epoch_flops(obs: int, hidden: int, actions: int, steps: int) -> int:
    """One A2C agent-epoch (arXiv:2202.05135 section 5.2): an episode of
    ``steps`` policy forwards, then the loss over the episode, which
    runs the policy and the value network on every observation and the
    value network on every next observation, and the backward pass,
    twice the forward work of the policy and the value network on the
    observations (the next observations' values carry no gradient)."""
    policy = mlp_flops([obs, hidden, hidden, actions])
    value = mlp_flops([obs, hidden, hidden, 1])
    return steps * (policy + (policy + 2 * value) + 2 * (policy + value))


def roofline_share(work: Dict[str, float], seconds: float,
                   peaks: dict) -> float:
    """Per cent of the chip's roofline: the least time the work could
    take, the larger of operations over peak operations and bytes over
    peak bandwidth, over the time it took."""
    least = max(work["flops"] / peaks["bf16_flops_s"],
                work["bytes"] / peaks["hbm_bytes_s"])
    return 100.0 * least / seconds
