"""The hybrid block's work module (``bench/work/mamba2_hybrid.py``) at the
smoke size of granite-4.0-h-micro, by hand: 2 Mamba layers (d 64, 8 heads
of 16, state 16, conv 4 over 160 channels) and 2 attention layers (4 q / 2
kv heads of 16), MLP 128, vocabulary 256 tied; 16-blocks at q_prune 0.5,
every matrix eligible (min_size 0) whose contraction is at least 64."""

import pytest

from bench import spec, sut

BENCH = spec.benchmark()


@pytest.fixture(scope="module")
def work():
    conf = spec.configuration(BENCH, "granite-4.0-h-micro")
    assert conf["reference"] == "mamba2_hybrid"
    return spec.work("mamba2_hybrid").of(sut.sizes(conf, smoke=True))


def test_layers_by_kind(work):
    assert (work.mamba_layers, work.attn_layers, work.ssm_layers) == (2, 2, 2)


def test_plan_bytes(work):
    # sparse: surviving weights + 4 per block + 4 per column block + 4 per
    # column; quant: weights + 4 per column; dense: 2 per weight
    w_z = 16 * 256 + 4 * 16 + 4 * 8 + 4 * 128  # (64, 128): 16 of 32 blocks
    w_xbc = 20 * 256 + 4 * 20 + 4 * 10 + 4 * 160  # (64, 160)
    w_dt = 64 * 8 + 4 * 8  # (64, 8): 8 columns do not tile by 16
    conv = 2 * 4 * 160  # contraction 4 < 64
    down = 16 * 256 + 4 * 16 + 4 * 4 + 4 * 64  # (128, 64)
    vectors = 2 * (3 * 8 + 128 + 160)  # dt_bias, A_log, D, norm, conv_b
    mamba = w_z + w_xbc + w_dt + conv + down + 2 * w_z + down + vectors
    wq = 8 * 256 + 4 * 8 + 4 * 4 + 4 * 64  # (64, 64)
    wk = 4 * 256 + 4 * 4 + 4 * 2 + 4 * 32  # (64, 32)
    attn = 2 * wq + 2 * wk + 2 * w_z + down
    tok = 256 * 64 + 4 * 64  # int8, one scale per column
    assert work.plan_bytes() == 2 * mamba + 2 * attn + tok == 121040


def test_needed_operations(work):
    mamba_w = 4096 + 5120 + 512 + 4096 + 3 * 4096  # z, xBC, dt, out, MLP
    attn_w = 2048 + 1024 + 1024 + 2048 + 3 * 4096
    assert work.layer_weights_per_token() == 2 * mamba_w + 2 * attn_w
    ssm = 2 * (5 * 128 * 16 + 2 * 4 * 160)  # recurrence and conv, per token
    assert work.ssm_flops() == ssm
    head = 2 * 64 * 256
    assert work.decode_flops(100) == (2 * (2 * mamba_w + 2 * attn_w) + head
                                      + 4 * 100 * 4 * 16 * 2 + ssm) == 285184
    assert work.prefill_flops(20) == ((2 * (2 * mamba_w + 2 * attn_w) + ssm) * 20
                                      + 2 * 4 * 16 * 20 * 21 * 2 + head)


def test_kernel_calls(work):
    # the state in and out, x, dt and y, B and C, per row; A and D once
    assert work.ssm_call(3) == (5.0 * 128 * 16 * 3,
                                3 * (2 * 4 * 16 * 128 + 4 * 3 * 128 + 4 * 2 * 16)
                                + 4 * 2 * 128)
    assert work.attn_call([10, 20]) == (4.0 * 30 * 4 * 16,
                                        30 * 2 * (2 * 16 + 8) + 4.0 * 2 * 4 * 16)
    # Mamba: w_z, w_xbc, w_out, gate+up, w_down; attention: wq, wk, wv, wo,
    # gate+up, w_down (w_dt is int8, not a walk)
    assert len(work.stream_calls(2)) == 2 * 5 + 2 * 6
