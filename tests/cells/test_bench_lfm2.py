"""The LFM2 training cell's benchmark files: its configuration holds the
published widths (and every number of the catalog's entry), its
``BENCHMARK.json`` entries are the ones ISSUE 33 names, its counts agree
with hand counts, its three readers read a synthetic ``obs`` and find
nothing in another model's, the ``fp8`` control fails a limit the
program's precision passes at a size a test can hold, the reference's
blocks change nothing, and a tree without the program's part fails the
cell at once. (``test_bench_run.py`` rehearses the cell itself end to
end, as every cell.)"""

import json
import os

import numpy as np
import pytest

from benchmark import common, peaks, traffic
from benchmark.train_cell import compare

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "lfm2-8b-a1b-train.moe-step-8k"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
with open(os.path.join(ROOT, "benchmark", "configs",
                       "lfm2-8b-a1b-train.json")) as f:
    CFG = json.load(f)
with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    BENCH = json.load(f)
MODEL = common.load_model(CFG, "lfm2-8b-a1b-train.json")
FL = MODEL.flops
MIX = traffic.load("moe-step-8k")
V5E = peaks.peaks_of("TPU v5 lite")
READERS = ("lfm2_mfu", "lfm2_moe_grouped_train_roofline",
           "lfm2_expert_share")


def reader(name):
    return common.load_reader(name)


def test_the_configuration_holds_the_published_widths_uncut():
    published = {
        "hidden_size": 2048, "num_attention_heads": 32,
        "num_key_value_heads": 8, "intermediate_size": 7168,
        "moe_intermediate_size": 1792, "conv_L_cache": 3,
        "rope_theta": 1000000, "num_experts_per_tok": 4,
        "router_outputs": 32, "norm_topk_prob": True,
        "use_expert_bias": True, "routed_scaling_factor": 1,
        "norm_eps": 1e-05, "num_dense_layers": 2, "conv_bias": False}
    assert {k: CFG[k] for k in published} == published
    assert sorted(CFG["reduced"]) == ["num_experts", "num_hidden_layers",
                                      "vocab_size"]
    assert (CFG["num_hidden_layers"], CFG["num_experts"],
            CFG["vocab_size"]) == (5, 8, 16384)
    assert CFG["published"] == {"num_hidden_layers": 24, "num_experts": 32,
                                "vocab_size": 65536,
                                "params": "8.3B-A1.5B"}
    assert len(CFG["layer_types"]) == 24           # kept whole
    assert CFG["layers_held"] == [1, 2, 3, 4, 5]
    assert CFG["experts_held"] == [0, 8]
    # the one kind of dense layer once, then a whole period at 1 : 3
    assert FL.kinds(CFG) == [("conv", "dense"), ("attention", "experts"),
                             ("conv", "experts"), ("conv", "experts"),
                             ("conv", "experts")]
    assert CFG["compute_dtype"] == "bfloat16"
    common.need(MODEL, common.TRAIN_API)           # trained
    with pytest.raises(common.Refused):            # and not served
        common.need(MODEL, common.SERVE_API)
    for key in ("tie_word_embeddings", "equations", "weights",
                "expert_bias", "optimizer", "remat", "freeze_router"):
        assert key in CFG["assumed"], key
    # a share trained without its exchange holds its routing fixed
    assert CFG["freeze_router"] is True


@pytest.mark.skipif(not os.path.isfile(CATALOG),
                    reason="the catalog is not on this machine")
def test_the_file_holds_every_number_of_the_catalogs_entry():
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "LFM2-8B-A1B")
    assert CFG["source"] == row["source_url"]
    differ = sorted(k for k, v in row["config"].items()
                    if CFG.get(k, "absent") != v)
    assert differ == sorted(CFG["reduced"])


def test_the_benchmarks_entries_are_the_issues():
    conf = next(c for c in BENCH["configs"]
                if c["name"] == "lfm2-8b-a1b-train")
    assert conf == BENCH["configs"][-1]
    assert conf["reduced"] == ["num_hidden_layers", "num_experts",
                               "vocab_size"]
    assert conf["source"] == CFG["source"]
    cell = BENCH["workloads"][-1]
    assert (cell["name"], cell["config"], cell["traffic"],
            cell["chips"]) == (CELL, "lfm2-8b-a1b-train", "moe-step-8k", 1)
    assert all(len(e["why"]) <= 200 for e in (conf, cell))
    lists = {m["name"]: m.get("workloads")
             for m in BENCH["end_to_end"] + BENCH["per_layer"]}
    for name in ("train_tok_per_s", "data_wait_share",
                 "train_compiles_in_window", "train_device_idle_share"):
        assert lists[name] == ["cgpt1p3b-train.train-step", CELL], name
    assert lists["mfu"] == ["cgpt1p3b-train.train-step"]
    assert [m["name"] for m in BENCH["per_layer"][-3:]] == list(READERS)
    for m in BENCH["per_layer"][-3:]:
        assert (m["workloads"], m["source"], m["moves"], m["unit"]) == (
            [CELL], "device_trace", "train_tok_per_s", "%")
    reported = {m["name"] for m in common.metrics_for(
        BENCH, cell, "per_layer")}
    assert reported == set(READERS) | {
        "data_wait_share", "train_compiles_in_window",
        "train_device_idle_share"}
    assert {m["name"] for m in common.metrics_for(
        BENCH, cell, "end_to_end")} == {"train_tok_per_s", "setup_s"}


def test_the_traffic_is_the_issues():
    want = {"kind": "train_job", "batch": 2, "seq_len": 8192,
            "scan_steps": 1, "run_ahead_steps": 8, "pool_batches": 8,
            "checked_steps": 3}
    assert {k: MIX[k] for k in want} == want
    with open(os.path.join(ROOT, "benchmark", "traffic",
                           "train-step.json")) as f:
        block = json.load(f)
    for key in ("trace_after_s", "trace_seconds"):
        assert MIX[key] == block[key]
    feats, labels = MODEL.encode_batch(
        np.arange(2 * 9).reshape(2, 9), CFG)
    assert feats.dtype == labels.dtype == np.int32
    assert feats.shape == labels.shape == (2, 8)
    assert np.array_equal(feats[:, 1:], labels[:, :-1])


def test_counts_against_hand_counts():
    assert FL.expert_params(CFG) == 3 * 2048 * 1792 == 11_010_048
    assert FL.mixer_params(CFG, "conv") == 4 * 2048 * 2048 == 16_777_216
    assert FL.mixer_params(CFG, "attention") == (
        2 * 2048 * 2048 + 2 * 2048 * 512) == 10_485_760
    assert FL.held_picks_per_token(CFG) == 1.0
    assert FL.expert_layers(CFG) == 4
    # ISSUE 33's reckoning, a token forward (M operations): the dense
    # layer 121.7, the attention layer 21.0 + 33.6 + 22.0, a conv
    # expert layer 33.6 + 22.0, the head 67.1; routers 0.13 each
    dense = 2 * 16_777_216 + 2 * 3 * 2048 * 7168
    attn = 2 * 10_485_760 + 4 * 2048 * 8193 / 2 + 2 * 11_010_048
    conv = 2 * 16_777_216 + 2 * 11_010_048
    head = 2 * 16384 * 2048
    routers = 4 * 2 * 2048 * 32
    fwd = dense + attn + 3 * conv + head + routers
    assert FL.forward_flops_per_token(CFG, 8192) == pytest.approx(fwd)
    assert fwd == pytest.approx(432.5e6, rel=1e-3)
    step = FL.train_flops_per_step(CFG, MIX)
    assert step == pytest.approx(3 * 16384 * fwd)
    assert step == pytest.approx(21.3e12, rel=3e-3)
    # 507.8 M parameters held: 7.57 GiB at 16 B
    from benchmark.models import lfm2_moe_weights as weights
    n = 16384 * 2048 + 2048 + sum(
        int(np.prod(s)) for kind in weights.layer_kinds(CFG)
        for s in weights.layer_shapes(CFG, kind).values())
    assert n == 507_820_288 and 7.56 < n * 16 / 2**30 < 7.58
    # the grouped products of one layer and step: 16,384 held pairs
    pairs = FL.held_pairs_per_step(CFG, MIX)
    assert pairs == 16384
    assert FL.grouped_train_flops(CFG, pairs) == 6 * 16384 * 11_010_048
    assert FL.grouped_train_bytes(CFG, pairs) == 2 * (
        3 * 8 * 11_010_048 + 16384 * (3 * (2048 + 3584) + 3 * (1792 + 2048)))
    least, bound = FL.roofline_seconds(
        FL.grouped_train_flops(CFG, pairs),
        FL.grouped_train_bytes(CFG, pairs), V5E)
    assert bound == "flops" and 0.0054 < least < 0.0056


def synthetic_obs():
    """A traced stretch of 3 s: 16 step programs of 0.18 s, the device
    busy 2.94 s, the grouped calls 0.5 s forward and on the rows'
    cotangents and 0.2 s on the weights'."""
    return {"kind": "train_job", "cell": CELL, "cfg": CFG, "mix": MIX,
            "flops": FL, "peaks": V5E, "trace_window_s": 3.0,
            "trace": {"busy_s": 2.94,
                      "programs": {"jit_steps": {"seconds": 2.88,
                                                 "count": 16}},
                      "ops": {"gmm_tpu_custom_call": 0.5,
                              "tgmm_tpu_custom_call": 0.2,
                              "fusion_fusion": 1.0}}}


def test_readers():
    obs = synthetic_obs()
    step = FL.train_flops_per_step(CFG, MIX)
    assert reader("lfm2_mfu")(obs) == pytest.approx(
        100 * step * 16 / 2.88 * (2.94 / 3.0) / 197e12)
    least = FL.roofline_seconds(
        FL.grouped_train_flops(CFG, 16384),
        FL.grouped_train_bytes(CFG, 16384), V5E)[0]
    share = reader("lfm2_moe_grouped_train_roofline")(obs)
    assert share == pytest.approx(100 * least * 4 * 16 / 0.7)
    assert 0 < share < 100
    assert reader("lfm2_expert_share")(obs) == pytest.approx(
        100 * 0.7 / 2.94)


@pytest.mark.parametrize("name", READERS)
def test_a_reader_finds_nothing_to_read_elsewhere(name):
    """The parent under this PR's benchmark files, and the cells the
    benchmark had: another model's ``flops``, a trace without the
    grouped calls, a served cell, no trace at all."""
    other = common.load_by_path("models", "cgpt_block").flops
    assert reader(name)(dict(synthetic_obs(), flops=other)) is None
    bare = synthetic_obs()
    bare["trace"] = dict(bare["trace"], ops={"fusion_fusion": 1.0},
                         programs={})
    assert reader(name)(bare) is None
    assert reader(name)(dict(synthetic_obs(), kind="open_loop")) is None
    assert reader(name)(dict(synthetic_obs(), trace=None)) is None


@pytest.fixture(scope="module")
def readings():
    """The reference at 3 blocks of width 128 (heads of 64, as
    published), 2 rows of 64 tokens, three steps: in bfloat16 (what the
    configuration states) and in float8 (the control) against
    float32."""
    cfg = common.overlay(CFG, CFG["rehearsal"])
    cfg.update(hidden_size=128, num_attention_heads=2,
               num_key_value_heads=1, intermediate_size=256,
               moe_intermediate_size=64, initializer_range=0.06,
               expert_bias_std=0.002, vocab_size=128)
    mix = dict(pool_batches=3, batch=2, seq_len=64)
    # over seeds 1-3, under the configuration's freeze_router, bfloat16
    # read at most 4.8e-04 / 6.4e-03 / 2.9e-03 and float8 at least
    # 6.2e-04 / 2.0e-02 / 6.8e-03 (CPU)
    limits = {"loss_gap": 1e-3, "grad_norm_gap": 1.2e-2,
              "delta_norm_gap": 5e-3}
    out = {}
    for seed in (2,):
        pool = traffic.train_pool(mix, seed, cfg["vocab_size"])
        ref = MODEL.train_reference(seed, cfg, CFG["optimizer"], pool,
                                    "highest")
        out[seed] = {prec: compare(MODEL.train_reference(
            seed, cfg, CFG["optimizer"], pool, prec), ref, limits)
            for prec in ("bf16", "fp8")}
        assert ref["grad_norms"]["2.expert_bias"] == 0.0
        assert set(ref["grad_norms"]) == set(ref["delta_norms"])
    return out


def test_the_stated_precision_is_correct_and_the_control_is_not(readings):
    for seed, by_prec in readings.items():
        assert all(r["ok"] for r in by_prec["bf16"].values()), (
            seed, by_prec["bf16"])
        assert not by_prec["fp8"]["grad_norm_gap"]["ok"], by_prec["fp8"]
        assert not by_prec["fp8"]["delta_norm_gap"]["ok"], by_prec["fp8"]


def test_the_references_blocks_change_nothing(monkeypatch):
    """A block of queries against all the keys is the whole softmax."""
    import jax.numpy as jnp

    from benchmark.models import lfm2_moe_reference as reference
    from benchmark.models import lfm2_moe_weights as weights

    cfg = common.overlay(CFG, CFG["rehearsal"])
    row = jnp.asarray(np.random.default_rng(1).integers(
        0, cfg["vocab_size"], 64), jnp.int32)
    params = weights.make_params(9, cfg)
    whole = reference.row_logits(params, row, cfg,
                                 "highest")
    monkeypatch.setattr(reference, "QUERY_BLOCK", 16)
    blocks = reference.row_logits(params, row, cfg,
                                  "highest")
    np.testing.assert_allclose(blocks, whole, atol=2e-5)


def test_a_tree_without_the_programs_part_fails_the_cell_cleanly(
        run_python):
    """The parent's tree has no ``lfm2_moe_lm``: with this PR's
    benchmark files laid over it the cell must exit non-zero at once
    and print no result (here: the adapter's import of the zoo builder
    is made to fail as it does there)."""
    code = (
        "import sys, runpy\n"
        "import deeplearning4j_tpu.models.zoo as zoo\n"
        "del zoo.lfm2_moe_lm\n"
        f"sys.argv = ['run.py', '--workload', {CELL!r}, '--seed', '1',\n"
        "            '--seconds', '1', '--trace', '0', '--rehearse']\n"
        "runpy.run_path('benchmark/run.py', run_name='__main__')\n")
    out = run_python(["-c", code], timeout=300)
    assert out.returncode != 0
    assert "lfm2_moe_lm" in out.stderr
    assert '"correct"' not in out.stdout
