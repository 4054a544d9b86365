"""The hybrid model's benchmark files: its configuration holds the
published widths, the ``fp8`` control fails its limits at the
rehearsal's sizes while the program passes them, its counts agree with
hand counts, and each of its readers reads a synthetic ``obs``.
(``test_bench_run.py`` rehearses the cell itself, as every cell.)"""

import json
import os

import numpy as np
import pytest

from benchmark import common, peaks, serve_cell

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "granite4hs-serve.chat-steady-g4hs"
with open(os.path.join(ROOT, "benchmark", "configs",
                       "granite4hs-serve.json")) as f:
    CFG = json.load(f)
MODEL = common.load_model(CFG, "granite4hs-serve.json")
FL = MODEL.flops
V5E = peaks.peaks_of("TPU v5 lite")


def test_the_configuration_holds_the_published_widths_uncut():
    published = {
        "hidden_size": 4096, "num_attention_heads": 32,
        "num_key_value_heads": 8, "mamba_n_heads": 128,
        "mamba_d_head": 64, "mamba_d_state": 128, "mamba_d_conv": 4,
        "mamba_chunk_size": 256, "mamba_n_groups": 1, "mamba_expand": 2,
        "router_outputs": 72, "num_experts_per_tok": 10,
        "intermediate_size": 768, "shared_intermediate_size": 1536,
        "attention_multiplier": 0.0078125, "embedding_multiplier": 12,
        "residual_multiplier": 0.22, "logits_scaling": 16,
        "rms_norm_eps": 1e-05}
    assert {k: CFG[k] for k in published} == published
    assert sorted(CFG["reduced"]) == ["num_hidden_layers",
                                      "num_local_experts", "vocab_size"]
    assert (CFG["num_hidden_layers"], CFG["num_local_experts"],
            CFG["vocab_size"]) == (10, 36, 50176)
    assert len(CFG["layer_types"]) == 40      # kept whole
    kinds = FL.kinds(CFG)
    assert kinds.count("mamba") == 9 and kinds.count("attention") == 1
    assert CFG["dtype"] == CFG["compute_dtype"] == "bfloat16"
    assert not hasattr(MODEL, "train_reference")     # served only
    with pytest.raises(common.Refused):
        common.need(MODEL, common.TRAIN_API)


def test_the_traffic_is_the_chat_mix_at_another_rate():
    def load(name):
        with open(os.path.join(ROOT, "benchmark", "traffic", name)) as f:
            mix = json.load(f)
        return {k: v for k, v in mix.items()
                if k not in ("why", "rate_per_s")}

    assert load("chat-steady-g4hs.json") == load("chat-steady.json")


def test_counts_against_hand_counts():
    assert FL.expert_params(CFG) == 3 * 4096 * 768 == 9_437_184
    assert FL.mixer_params(CFG, "mamba") == (
        4096 * (8192 + 8448 + 128) + 8192 * 4096) == 102_236_160
    assert FL.mixer_params(CFG, "attention") == (
        2 * 4096 * 4096 + 2 * 4096 * 1024) == 41_943_040
    assert FL.shared_params(CFG) == 3 * 4096 * 1536 + 4096 * 72
    # ISSUE 27's 2.31 GB of non-expert weights a step, at 2 B
    assert FL.nonexpert_params(CFG) == 1_153_761_280
    assert FL.head_params(CFG) == 50176 * 4096
    assert FL.state_numbers(CFG) == 128 * 64 * 128
    assert FL.conv_tail_numbers(CFG) == 3 * 8448
    assert FL.kv_numbers_per_token(CFG) == 2 * 8 * 128
    assert FL.grouped_bytes(CFG, 36) == 36 * 9_437_184 * 2
    assert FL.grouped_flops(CFG, 240) == 2 * 240 * 9_437_184
    assert FL.ssm_step_bytes(CFG, 9) == 2 * 9 * 1_048_576 * 4
    # one step, 24 live rows at 500 cached positions, 33 of 36 experts
    # touched a layer, 120 held picks a layer
    nflops, nbytes = FL.decode_round(
        CFG, 1, 24, 24 * 500, 330, 1200, 24 * 9)
    assert nbytes == (
        (1_153_761_280 + 205_520_896) * 2 + 330 * 9_437_184 * 2
        + 2 * 216 * 1_048_576 * 4 + 2 * 216 * 25_344 * 2
        + 12_000 * 2048 * 2)
    assert nflops == (
        2 * 24 * (1_153_761_280 + 205_520_896) + 2 * 1200 * 9_437_184
        + 5 * 216 * 1_048_576 + 2 * 12_000 * 2048 * 4)
    # the step is bytes-bound, ~13 ms on a v5e, as ISSUE 27 reckons
    least, bound = FL.roofline_seconds(nflops, nbytes, V5E)
    assert bound == "bytes" and 0.012 < least < 0.015


# -- the control -------------------------------------------------------
def test_the_fp8_control_fails_a_limit_the_program_passes():
    from deeplearning4j_tpu.serving import DecodeEngine, Request

    cfg = common.overlay(CFG, CFG["rehearsal"])
    cfg["kernels"] = None
    limits = cfg["check"]["limits"]
    seed = 3
    net = MODEL.build_net(cfg, seed)
    eng = DecodeEngine(net, n_slots=3, decode_chunk=4)
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, cfg["vocab_size"], n).tolist()
               for n in (20, 33, 50)]
    ids = [eng.submit(Request(p, 16)) for p in prompts]
    res = eng.run()
    samples = [(p, list(res[i].tokens)) for p, i in zip(prompts, ids)]
    prog, ctrl = MODEL.served_gaps(seed, cfg, samples, control="fp8")
    program = serve_cell.gap_numbers(prog)
    control = serve_cell.gap_numbers(ctrl)
    assert all(program[k] <= limits[k] for k in limits), program
    assert any(control[k] > limits[k] for k in limits), control


# -- the readers on a synthetic obs -------------------------------------
def reader(name):
    return common.load_reader(name)


def synthetic_obs():
    n, chunk = CFG["num_hidden_layers"], CFG["deployment"]["decode_chunk"]
    pure = {"moe_layer_steps": n * chunk, "moe_experts_touched": 2640,
            "moe_picks_held": 9600, "ssm_state_rows": 24 * 9 * chunk,
            "chunks": 1}
    # a round that also admitted a request: its prefill's counts are
    # in the totals and, alone, under prefill_<name>
    admitted = dict(pure, moe_layer_steps=n * chunk + n,
                    moe_experts_touched=3000, moe_picks_held=12_000,
                    ssm_state_rows=24 * 9 * chunk + 9,
                    prefill_moe_layer_steps=n,
                    prefill_moe_experts_touched=360,
                    prefill_moe_picks_held=2400,
                    prefill_ssm_state_rows=9)
    return {
        "kind": "open_loop", "cell": CELL, "cfg": CFG, "flops": FL,
        "peaks": V5E,
        "before": {"moe_picks": 1000, "moe_picks_held": 500,
                   "moe_experts_touched": 100, "moe_layer_steps": 10,
                   "moe_load_max": 40},
        "after": {"moe_picks": 21_000, "moe_picks_held": 10_400,
                  "moe_experts_touched": 2800, "moe_layer_steps": 90,
                  "moe_load_max": 640},
        "traced_rounds": [
            {"counted": pure, "contexts": [500] * 24, "active": 24},
            {"counted": admitted, "contexts": [500] * 24, "active": 24}],
        "trace": {"programs": {"jit_decode": {"seconds": 0.5,
                                              "count": 4}},
                  "ops": {"gmm_tpu_custom_call": 0.3,
                          "_ssm_step_update_tpu_custom_call": 0.1}}}


def test_counter_readers():
    obs = synthetic_obs()
    assert reader("moe_held_pick_share")(obs) == pytest.approx(
        100 * 9900 / 20_000)
    assert reader("moe_touched_share")(obs) == pytest.approx(
        100 * 2700 / (36 * 80))
    assert reader("moe_load_max_over_mean")(obs) == pytest.approx(
        36 * 600 / 9900)


def test_roofline_readers():
    obs = synthetic_obs()
    chunk = 8
    ctx = sum(500 - chunk + j + 1 for j in range(chunk)) * 24
    nflops, nbytes = FL.decode_round(CFG, chunk, 24 * chunk, ctx, 2640,
                                     9600, 24 * 9 * chunk)
    least = FL.roofline_seconds(nflops, nbytes, V5E)[0]
    # both rounds' decode parts are the same dispatch
    assert reader("g4hs_decode_step_roofline")(obs) == pytest.approx(
        100 * least * 4 / 0.5)
    grouped = FL.roofline_seconds(
        FL.grouped_flops(CFG, 10_800), FL.grouped_bytes(CFG, 2820),
        V5E)[0]
    assert reader("moe_grouped_roofline")(obs) == pytest.approx(
        100 * grouped * 4 / 0.3)
    step = FL.roofline_seconds(
        FL.ssm_step_flops(CFG, 1728), FL.ssm_step_bytes(CFG, 1728),
        V5E)[0]
    assert reader("ssm_step_roofline")(obs) == pytest.approx(
        100 * step * 4 / 0.1)
    for name in ("g4hs_decode_step_roofline", "moe_grouped_roofline",
                 "ssm_step_roofline"):
        assert 0 < reader(name)(obs) <= 100


@pytest.mark.parametrize("name", [
    "g4hs_decode_step_roofline", "moe_grouped_roofline",
    "ssm_step_roofline", "moe_load_max_over_mean", "moe_touched_share",
    "moe_held_pick_share"])
def test_a_reader_finds_nothing_in_a_program_without_the_counters(name):
    """The parent's engine counts none of this and its trace holds no
    such kernel: the reader returns None and does not raise."""
    obs = synthetic_obs()
    old = {"chunks": 7, "occupancy_sum": 3.0}
    obs.update(before=dict(old), after=dict(old, chunks=9),
               traced_rounds=[{"counted": {"chunks": 1},
                               "contexts": [400], "active": 1}])
    obs["trace"] = {"programs": {"jit_decode": {"seconds": 0.5,
                                                "count": 4}},
                    "ops": {"fusion_fusion": 0.2}}
    assert reader(name)(obs) is None
    assert reader(name)(dict(obs, kind="train_job")) is None
    assert reader(name)(dict(obs, trace=None)) is None
