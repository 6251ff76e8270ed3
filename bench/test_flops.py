"""The benchmark's operation and byte counts against hand counts at a
small size."""
import pytest

import flops

TINY = {"d_model": 8, "n_layer": 1, "vocab_size": 10,
        "ssm_cfg": {"expand": 2, "headdim": 4, "d_state": 2, "ngroups": 1,
                    "chunk_size": 4, "d_conv": 4}}


def test_mamba2_matmul_params():
    # d_inner 16, 4 heads: w_z, w_x 8x16 each, w_B, w_C 8x2, w_dt 8x4,
    # out_proj 16x8, head 8x10
    assert flops.mamba2_matmul_params(TINY) == 8 * 16 * 2 + 8 * 2 * 2 \
        + 8 * 4 + 16 * 8 + 8 * 10


def test_mamba2_head_counts_the_padded_table():
    # 10 ids padded to 16 rows: the head is 8x16, tied or not
    padded = dict(TINY, pad_vocab_size_multiple=16, tie_embeddings=True)
    assert flops.mamba2_matmul_params(padded) \
        == flops.mamba2_matmul_params(TINY) + 8 * 6


def test_mamba2_ssd_and_train_per_token():
    # two chunks of 4 over 8 tokens; per chunk: C.B^T 2*4*4*2, scores.x
    # 2*4*4*4*4, states 2*4*2*4*4, C.h 2*4*2*4*4, recurrence 2*2*4*4
    per_chunk = 64 + 512 + 256 + 256 + 64
    assert flops.mamba2_ssd_forward_per_token(TINY, 8) == 2 * per_chunk / 8
    assert flops.mamba2_train_flops_per_token(TINY, 8) == 3 * (
        2 * 528 + 288)


def test_sketch_work():
    w = flops.sketch_work([100, 28], n=2, dim=4)
    assert w == {"flops": 2 * 2 * 128 * 4, "bytes": 4 * 2 * 128 + 4 * 2 * 4}


def test_wavg_work():
    assert flops.wavg_work(3, 2, 10) == {"flops": 120, "bytes": 360}


def test_mlp_flops():
    assert flops.mlp_flops([4, 64, 64, 2]) == 2 * (4 * 64 + 64 * 64 + 64 * 2)


def test_a2c_epoch_flops():
    # CartPole: 4 observations, 2 actions, hidden 64, 100 steps
    policy = 2 * (4 * 64 + 64 * 64 + 64 * 2)
    value = 2 * (4 * 64 + 64 * 64 + 64 * 1)
    rollout = 100 * policy
    loss_forward = 100 * (policy + value + value)
    loss_backward = 100 * 2 * (policy + value)
    assert flops.a2c_epoch_flops(4, 64, 2, 100) == (
        rollout + loss_forward + loss_backward)


@pytest.mark.parametrize("work,seconds,share", [
    ({"flops": 1e9, "bytes": 2e6}, 4e-3, 50.0),     # bound by bandwidth
    ({"flops": 3e9, "bytes": 1e6}, 3e-3, 100.0),    # bound by compute
])
def test_roofline_share(work, seconds, share):
    peaks = {"bf16_flops_s": 1e12, "hbm_bytes_s": 1e9}
    assert flops.roofline_share(work, seconds, peaks) == pytest.approx(share)
