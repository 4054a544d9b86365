"""Operation and byte counts against hand counts for one block, the
peaks table, and the two readers of the paged kernel's walk on
hand-made observations."""

import pytest

from benchmark import common, peaks
from benchmark.models import cgpt_block_flops as flops

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


# ---- the paged kernel's walk ------------------------------------------
def test_paged_live_bytes_by_hand():
    # 100 live blocks of 16 positions: K and V, 2048 wide, in 24 layers
    assert flops.paged_live_bytes(D, 24, 16, 100, "bfloat16") == \
        24 * 100 * 16 * 2 * 2048 * 2
    assert flops.paged_live_bytes(D, 24, 16, 100, "float32") == \
        2 * flops.paged_live_bytes(D, 24, 16, 100, "bfloat16")
    # the same bytes decode_step_bytes counts beside the weights
    assert flops.paged_live_bytes(D, 24, 16, 100, "bfloat16") == \
        flops.decode_step_bytes(D, F, 24, [1600], "bfloat16") \
        - flops.decode_step_bytes(D, F, 24, [], "bfloat16")


KERNEL = "_paged_flash_attention_tpu_custom_call"
CFG = {"n_embd": D, "n_inner": F, "n_layer": 24,
       "compute_dtype": "bfloat16",
       "deployment": {"block_tokens": 16, "decode_chunk": 8}}


def walk_obs(**over):
    """A served run whose traced stretch holds four decode programs and
    two probed rounds of 100 and 140 live blocks a call."""
    obs = {"kind": "open_loop", "cfg": CFG, "flops": flops,
           "peaks": peaks.peaks_of("TPU v5 lite"),
           "before": {"paged_blocks_live": 1000,
                      "paged_blocks_walked": 1600},
           "after": {"paged_blocks_live": 4000,
                     "paged_blocks_walked": 5600},
           "traced_rounds": [
               {"counted": {"chunks": 1, "paged_blocks_live": 100}},
               {"counted": {"admitted": 1}},      # an admission only
               {"counted": {"chunks": 1, "paged_blocks_live": 140}}],
           "trace": {"ops": {KERNEL: 0.05, "fusion_fusion": 1.0},
                     "programs": {"jit_decode": {"count": 4,
                                                 "seconds": 0.4}}}}
    obs.update(over)
    return obs


def test_walk_live_share_is_live_over_walked_in_the_window():
    read = common.load_reader("paged_walk_live_share")
    assert read(walk_obs()) == pytest.approx(100.0 * 3000 / 4000)


def test_paged_roofline_by_hand():
    # 120 blocks a call on average: bytes bound; 8 steps a round, four
    # rounds in the trace, against 0.05 s of the kernel's device time
    per_step = 24 * 120 * 16 * 2 * 2048 * 2 / 819e9
    assert common.load_reader("paged_attn_roofline")(walk_obs()) == \
        pytest.approx(100.0 * per_step * 8 * 4 / 0.05)


def test_a_float32_pool_is_counted_at_the_stated_dtype():
    """The bytes follow the configuration, not the program: a kernel
    that streams a float32 pool at the HBM peak reads 50."""
    per_step_f32 = flops.paged_live_bytes(D, 24, 16, 120, "float32")
    at_peak = per_step_f32 * 8 * 4 / 819e9
    obs = walk_obs(trace={"ops": {KERNEL: at_peak}, "programs": {
        "jit_decode": {"count": 4, "seconds": 1.0}}})
    assert common.load_reader("paged_attn_roofline")(obs) == \
        pytest.approx(50.0)


@pytest.mark.parametrize("name,over", [
    ("paged_walk_live_share", {"kind": "train_job"}),
    ("paged_walk_live_share", {"before": {}, "after": {}}),
    ("paged_walk_live_share",
     {"after": {"paged_blocks_live": 1000, "paged_blocks_walked": 1600}}),
    ("paged_attn_roofline", {"kind": "train_job"}),
    ("paged_attn_roofline", {"trace": None}),
    ("paged_attn_roofline", {"peaks": None}),
    ("paged_attn_roofline", {"traced_rounds": []}),
    ("paged_attn_roofline", {"trace": {"ops": {"fusion_fusion": 1.0},
                                       "programs": {}}}),
], ids=["share-training", "share-no-counters", "share-no-walk",
        "roofline-training", "roofline-untraced", "roofline-rehearsal",
        "roofline-no-rounds", "roofline-no-kernel"])
def test_walk_readers_report_nothing_where_there_is_nothing(name, over):
    assert common.load_reader(name)(walk_obs(**over)) is None
