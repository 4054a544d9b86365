"""Operation and byte counts against hand counts for one block, and the
peaks table."""

import pytest

from benchmark import flops, peaks

D, F = 2048, 8192


def test_block_weights_by_hand():
    # Wq, Wk, Wv, Wo: 4 x 2048^2; W1, W2: 2 x 2048 x 8192
    assert flops.block_matmul_params(D, F) == 4 * 4194304 + 2 * 16777216
    assert flops.block_matmul_params(D, F) == 50331648
    assert flops.block_forward_flops_per_token(D, F) == 100663296


def test_attention_by_hand():
    # one query over 100 keys: q.k is 2*2048, a.v is 2*2048, per key
    assert flops.attention_forward_flops(D, 1, 100) == 4 * 2048 * 100
    # a causal row of 4 positions sees 1+2+3+4 keys
    assert flops.causal_attention_forward_flops(D, 4) == 4 * 2048 * 10


def test_train_flops_per_token_by_hand():
    seq, layers = 2048, 10
    fwd = layers * (seq * 100663296 + 4 * 2048 * (seq * (seq + 1) // 2))
    assert flops.train_flops_per_token(D, F, layers, seq) == \
        pytest.approx(3 * fwd / seq)
    # 6 N_block is the bulk: 10 blocks of 50.3 M
    assert flops.train_flops_per_token(D, F, layers, seq) == \
        pytest.approx(6 * 10 * 50331648 + 3 * 10 * 4 * 2048 * 1024.5)


def test_decode_step_counts_by_hand():
    ctx = [100, 300]
    assert flops.decode_step_flops(D, F, 24, ctx) == 24 * (
        2 * 100663296 + 4 * 2048 * 400)
    # bf16: every block weight once, and K and V of 400 cached tokens
    assert flops.decode_step_bytes(D, F, 24, ctx, "bfloat16") == \
        24 * 50331648 * 2 + 24 * 2 * 2048 * 2 * 400
    assert flops.decode_step_bytes(D, F, 24, ctx, "float32") == \
        2 * flops.decode_step_bytes(D, F, 24, ctx, "bfloat16")


def test_bytes_follow_the_stated_dtype_not_the_program():
    with pytest.raises(KeyError):
        flops.decode_step_bytes(D, F, 24, [1], "int3")


def test_roofline_picks_the_binding_peak():
    p = peaks.peaks_of("TPU v5 lite")
    t, bound = flops.roofline_seconds(197e12, 1.0, p)
    assert bound == "flops" and t == pytest.approx(1.0)
    t, bound = flops.roofline_seconds(1.0, 819e9, p)
    assert bound == "bytes" and t == pytest.approx(1.0)


def test_decode_is_bound_by_bytes_on_the_v5e():
    p = peaks.peaks_of("TPU v5 lite")
    ctx = [450] * 20
    _, bound = flops.roofline_seconds(
        flops.decode_step_flops(D, F, 24, ctx),
        flops.decode_step_bytes(D, F, 24, ctx, "bfloat16"), p)
    assert bound == "bytes"


def test_v5e_peaks_and_their_source():
    p = peaks.peaks_of("TPU v5 lite")
    assert p["bf16_flops"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    assert p["source"]


@pytest.mark.parametrize("kind", ["cpu", "TPU v4", "", "NVIDIA H100"])
def test_unknown_device_kind_is_an_error(kind):
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peaks_of(kind)
