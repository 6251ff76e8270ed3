"""Operations and bytes of the kanana-2-30b-a3b chip share, from its
shapes and from the counted token-expert pairs.

Counted as ``flops.py`` counts: a matrix product of (m, k) by (k, n)
is 2mkn operations, elementwise work and recomputation are not
counted, and a training step is three times the forward work.
"""
from __future__ import annotations

from typing import Dict

import kanana_ref as K


def forward_flops_per_token(conf: dict, seq: int,
                            pairs_per_token: float) -> float:
    """Forward operations per token: every projection of the held
    heads (q, the latent and its rotary key, the per-head keys and
    values from the latent, the output), causal attention over
    (seq + 1) / 2 keys on average, layer 0's dense SwiGLU, each expert
    layer's router and shared experts, ``pairs_per_token`` held-expert
    SwiGLUs per token over all expert layers, and the head."""
    d = K.dims(conf)
    E, H = d["E"], d["H"]
    attn = (E * H * (d["DN"] + d["DR"]) + E * (d["R"] + d["DR"])
            + d["R"] * H * (d["DN"] + d["DV"]) + H * d["DV"] * E)
    layers = conf["num_hidden_layers"]
    keys = (seq + 1) / 2.0
    scores = 2 * H * (d["DN"] + d["DR"] + d["DV"]) * keys
    matmul = (layers * attn + 3 * E * d["FD"]
              + d["L"] * (E * d["NE"] + 3 * E * d["FS"])
              + pairs_per_token * 3 * E * d["F"] + E * d["V"])
    return 2.0 * matmul + layers * scores


def train_flops_per_token(conf: dict, seq: int,
                          pairs_per_token: float) -> float:
    """Forward and backward: three times the forward work."""
    return 3.0 * forward_flops_per_token(conf, seq, pairs_per_token)


def expert_work(conf: dict, pairs: float, passes: int) -> Dict[str, float]:
    """The held experts' grouped products for ``pairs`` token-expert
    pairs over ``passes`` expert-layer passes, forward and backward:
    the SwiGLU's three products per pair, three times over; each pass
    reads the held experts' bfloat16 weights three times (forward, and
    the backward's two products) and each pair's bfloat16 rows: the
    token in and out and the two intermediate rows and their product,
    each three times."""
    d = K.dims(conf)
    E, F = d["E"], d["F"]
    weights = 3 * d["NH"] * E * F * 2
    return {"flops": 3.0 * pairs * 3 * 2 * E * F,
            "bytes": 3.0 * (passes * weights + pairs * (2 * E + 3 * F) * 2)}
