"""The evabyte model's benchmark files: its configuration is the catalog
row's but for the depth, its entries and its traffic are ISSUE 41's and
stand at the end of their lists (the driver takes additions nowhere
else), LFM2's whole and directly before them, the ``fp8`` control fails its limits at the rehearsal's sizes, its counts agree with
hand counts, each of its readers reads a synthetic ``obs``, and a tree
without the program's part fails the cell cleanly.
(``test_bench_run.py`` rehearses the cell itself, as every cell.)"""

import json
import os

import numpy as np
import pytest

from benchmark import common, peaks, serve_cell

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "evabyte-serve.docs-batch-eva"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
with open(os.path.join(ROOT, "benchmark", "configs",
                       "evabyte-serve.json")) as f:
    CFG = json.load(f)
with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    BENCH = json.load(f)
MODEL = common.load_model(CFG, "evabyte-serve.json")
FL = MODEL.flops
V5E = peaks.peaks_of("TPU v5 lite")
READERS = ("evabyte_decode_step_roofline", "eva_paged_attn_roofline",
           "eva_summary_read_share", "eva_mixer_share")
SERVED = ["cgpt1p3b-serve.chat-steady", "granite4hs-serve.chat-steady-g4hs",
          "trinity-large-serve.docs-mixed-tlp", CELL]


def test_the_configuration_is_the_catalog_rows_but_for_the_depth():
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog here")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "EvaByte")
    assert CFG["source"] == row["source_url"]
    differ = sorted(k for k, v in row["config"].items()
                    if CFG.get(k, "absent") != v)
    assert differ == sorted(CFG["reduced"]) == ["num_hidden_layers"]


def test_the_configuration_states_its_cut_and_its_deployment():
    assert (CFG["num_hidden_layers"], CFG["layers_held"]) == (
        16, list(range(16)))
    assert CFG["published"]["num_hidden_layers"] == 32
    assert CFG["deployment_of"] == ("2 chips, pipeline stages of 16 "
                                    "layers, this chip the first")
    for key in ("equations", "pooling_scale", "head", "pooling_vectors",
                "weights", "served_context"):
        assert key in CFG["assumed"], key
    dep = CFG["deployment"]
    assert {k: dep[k] for k in ("n_slots", "block_tokens", "decode_chunk",
                                "prefill_chunk", "prefix_cache_rows",
                                "admission_policy")} == {
        "n_slots": 8, "block_tokens": 16, "decode_chunk": 8,
        "prefill_chunk": 1024, "prefix_cache_rows": 0,
        "admission_policy": "ttft"}
    assert dep["block_tokens"] == CFG["chunk_size"]
    assert CFG["window_size"] % dep["prefill_chunk"] == 0
    # 8 slots x (a window's blocks + a round's + slack, and the served
    # context's summary blocks + the same): what the engine's kinds ask
    window = 2048 // 16 + 1 + 3
    summary = 16384 // (16 * 16) + 1 + 3
    assert dep["kv_blocks"] == 8 * (window + summary) == 1600
    assert CFG["served_context"] == 16384
    assert CFG["dtype"] == CFG["compute_dtype"] == "bfloat16"
    assert not hasattr(MODEL, "train_reference")     # served only
    with pytest.raises(common.Refused):
        common.need(MODEL, common.TRAIN_API)


def test_the_benchmarks_entries_are_the_issues_and_end_their_lists():
    conf = BENCH["configs"][-1]
    assert conf["name"] == "evabyte-serve"
    assert conf["reduced"] == ["num_hidden_layers"]
    assert conf["source"] == CFG["source"]
    assert conf["file"] == "benchmark/configs/evabyte-serve.json"
    cell = BENCH["workloads"][-1]
    assert (cell["name"], cell["config"], cell["traffic"],
            cell["chips"]) == (CELL, "evabyte-serve", "docs-batch-eva", 1)
    assert all(len(e["why"]) <= 200 for e in (conf, cell))
    per_layer = [m["name"] for m in BENCH["per_layer"]]
    assert per_layer[-4:] == list(READERS)
    for m in BENCH["per_layer"][-4:]:
        assert (m["workloads"], m["moves"], m["unit"]) == (
            [CELL], "tpot_mean_ms", "%")
    lists = {m["name"]: m.get("workloads")
             for m in BENCH["end_to_end"] + BENCH["per_layer"]}
    assert lists["tpot_mean_ms"] == SERVED
    shared = [name for name, cells in lists.items() if cells == SERVED]
    assert len(shared) == 18        # tpot_mean_ms and 17 shared readers
    assert lists["kv_window_released_share"] == [SERVED[2], CELL]
    reported = {m["name"] for m in common.metrics_for(
        BENCH, cell, "per_layer")}
    assert reported == set(READERS) | {"kv_window_released_share"} | (
        set(shared) - {"tpot_mean_ms"})
    assert {m["name"] for m in common.metrics_for(
        BENCH, cell, "end_to_end")} == {"tpot_mean_ms", "setup_s"}


def test_lfm2s_entries_stand_whole_directly_before_these():
    """``test_bench_lfm2.py`` pins LFM2's entries to the lists' tails and
    is not this PR's to edit; the driver takes new entries only at the
    tails. ``tests/conftest.py`` marks that test as expected to fail, and
    this holds what it held, one place up."""
    lfm2_cell = "lfm2-8b-a1b-train.moe-step-8k"
    conf = BENCH["configs"][-2]
    assert conf["name"] == "lfm2-8b-a1b-train"
    assert conf["reduced"] == ["num_hidden_layers", "num_experts",
                               "vocab_size"]
    cell = BENCH["workloads"][-2]
    assert (cell["name"], cell["config"], cell["traffic"],
            cell["chips"]) == (lfm2_cell, "lfm2-8b-a1b-train",
                               "moe-step-8k", 1)
    assert all(len(e["why"]) <= 200 for e in (conf, cell))
    lists = {m["name"]: m.get("workloads")
             for m in BENCH["end_to_end"] + BENCH["per_layer"]}
    for name in ("train_tok_per_s", "data_wait_share",
                 "train_compiles_in_window", "train_device_idle_share"):
        assert lists[name] == ["cgpt1p3b-train.train-step", lfm2_cell], name
    assert lists["mfu"] == ["cgpt1p3b-train.train-step"]
    readers = ["lfm2_mfu", "lfm2_moe_grouped_train_roofline",
               "lfm2_expert_share"]
    assert [m["name"] for m in BENCH["per_layer"][-7:-4]] == readers
    for m in BENCH["per_layer"][-7:-4]:
        assert (m["workloads"], m["source"], m["moves"], m["unit"]) == (
            [lfm2_cell], "device_trace", "train_tok_per_s", "%")
    assert {m["name"] for m in common.metrics_for(
        BENCH, cell, "per_layer")} == set(readers) | {
        "data_wait_share", "train_compiles_in_window",
        "train_device_idle_share"}
    assert {m["name"] for m in common.metrics_for(
        BENCH, cell, "end_to_end")} == {"train_tok_per_s", "setup_s"}


def test_the_traffic_is_the_issues():
    with open(os.path.join(ROOT, "benchmark", "traffic",
                           "docs-batch-eva.json")) as f:
        mix = json.load(f)
    want = {"kind": "closed_loop", "clients": 8, "requests": 96,
            "prompt": {"dist": "fixed", "value": 12000},
            "output": {"dist": "fixed", "value": 512},
            "max_total": 16384, "drain_limit_s": 60.0,
            "trace_after_s": 5.0, "trace_seconds": 3.0,
            "sharing": {"groups": 0, "prefix_tokens": 0}}
    assert {k: mix[k] for k in want} == want
    assert 20.0 <= mix["lead_in_s"] <= 40.0
    assert mix["max_total"] == CFG["served_context"]
    # 5 windows + 1,760: the decode crosses a window's end at its
    # 288th byte, with 640 and then 768 summaries visible
    w, c = CFG["window_size"], CFG["chunk_size"]
    assert (12000 // w, 12000 % w, w - 12000 % w) == (5, 1760, 288)
    assert (12000 // w * w // c, (12000 + 512) // w * w // c) == (640, 768)
    from benchmark import traffic

    sched = traffic.serving_schedule(mix, 2**31 + 7, 51.0,
                                     CFG["vocab_size"])
    assert (sched["kind"], sched["clients"]) == ("closed_loop", 8)
    assert len(sched["requests"]) == 96
    assert all(len(r["prompt"]) == 12000 and r["max_new"] == 512
               and r["due"] is None for r in sched["requests"])
    ids = np.asarray(sched["requests"][0]["prompt"])
    assert 0 <= ids.min() and ids.max() < 320 and len(set(ids)) > 300
    small = common.overlay(mix, mix["rehearsal"])
    assert small["prompt"]["value"] > CFG["rehearsal"]["window_size"]
    assert small["clients"] == 2


def test_counts_against_hand_counts():
    # Wq Wk Wv Wo 4096 x 4096 each; W1 W3 W2 4096 x 11008 each
    assert FL.layer_params(CFG) == (4 * 16_777_216
                                    + 3 * 45_088_768) == 202_375_168
    assert FL.head_params(CFG) == 320 * 4096       # head 0's rows
    assert FL.step_params(CFG) == 16 * 202_375_168 + 1_310_720
    assert FL.kv_numbers_per_entry(CFG) == 2 * 32 * 128
    assert FL.kind_windows(CFG) == {"summary": 16384, "window": 2048}
    assert FL.layers_of(CFG, "window") == FL.layers_of(CFG, "summary") == 16
    # an entry is 16 KiB a layer, 256 KiB over the 16
    assert FL.paged_bytes(CFG, 1000) == 1000 * 16 * 16384
    assert FL.paged_flops(CFG, 1000) == 2 * 1000 * 16 * 8192
    # a summary: 16 entries in, one out
    assert FL.writer_bytes(CFG, 32) == 32 * 17 * 16384
    # a round of 8 steps, 8 live rows at position 12,100: 1,860 exact
    # keys + 640 summaries a row and step (taken as equal), no chunk
    # completed
    entries = 64 * (1860 + 640)
    nflops, nbytes = FL.decode_round(CFG, 8, 64, entries, 0)
    assert nbytes == 8 * 2 * FL.step_params(CFG) + entries * 16 * 16384
    assert nflops == 2 * 64 * FL.step_params(CFG) + 2 * entries * 16 * 8192
    least, bound = FL.roofline_seconds(nflops, nbytes, V5E)
    assert bound == "bytes" and 0.113 < least < 0.115
    # an admission chunk of 1,024 bytes at offset 1,024 of window 3
    pairs = 1024 * 1024 + 1024 * 1025 // 2 + 1024 * 384
    nflops, nbytes = FL.admit_chunk(CFG, 1024, 2048 + 384, pairs,
                                    16 * 64)
    body = 16 * 202_375_168
    assert nflops == 2 * 1024 * body + 2 * 1_310_720 \
        + 2 * pairs * 16 * 8192
    assert nbytes == 2 * (body + 1_310_720) + (2048 + 384) * 16 * 16384 \
        + 1024 * 17 * 16384
    assert FL.roofline_seconds(nflops, nbytes, V5E)[1] == "flops"


def test_the_fp8_control_fails_a_limit_the_reference_passes():
    """At the rehearsal's sizes, teacher-forced: the reference's own
    first choices are the served tokens (gaps 0), and the token the fp8
    operands put first lies past both limits."""
    from benchmark.models import evabyte_reference as reference

    cfg = common.overlay(CFG, CFG["rehearsal"])
    limits = cfg["check"]["limits"]
    seed = 2**31 + 3
    rng = np.random.default_rng(3)
    samples = []
    for n in (40, 70):
        seq = rng.integers(0, cfg["vocab_size"], n + 12)
        logits = reference.forward_logits(seed, cfg, seq[None, :])[0]
        samples.append((seq[:n].tolist(),
                        logits[n - 1:n + 11].argmax(axis=-1).tolist()))
    prog, ctrl = MODEL.served_gaps(seed, cfg, samples, control="fp8")
    program = serve_cell.gap_numbers(prog)
    control = serve_cell.gap_numbers(ctrl)
    # (the 11 tokens after the first are the forced sequence's, not the
    # reference's choice after its own: only the first gap is 0)
    assert prog[0] == prog[12] == 0.0
    assert all(control[k] > limits[k] for k in limits), control
    assert program["served_logit_gap"] >= 0.0


# -- the readers on a synthetic obs -------------------------------------
def reader(name):
    return common.load_reader(name)


def synthetic_obs():
    # one layer's reads of a decode dispatch: 8 rows x 8 steps
    pure = {"chunks": 1, "eva_window_entries_read": 64 * 1800,
            "eva_summary_entries_read": 64 * 640,
            "eva_window_pairs_scored": 64 * 1800,
            "eva_summary_pairs_scored": 64 * 640,
            "eva_summaries_written": 16 * 4}
    # a round that also ran an admission chunk (1,024 queries at offset
    # 1,024 of a row's fourth window): its counts are in the totals and,
    # alone, under prefill_<name>
    chunk = {"eva_window_entries_read": 2048,
             "eva_summary_entries_read": 384,
             "eva_window_pairs_scored": 1024 * 1024 + 1024 * 1025 // 2,
             "eva_summary_pairs_scored": 1024 * 384,
             "eva_summaries_written": 16 * 64}
    admitted = {k: pure[k] + chunk.get(k, 0) for k in pure}
    admitted.update({"prefill_" + k: v for k, v in chunk.items()})
    return {
        "kind": "closed_loop", "cell": CELL, "cfg": CFG, "flops": FL,
        "peaks": V5E,
        "before": {"kv_blocks_spanned_w16384": 100,
                   "kv_blocks_held_w16384": 100,
                   "kv_blocks_spanned_w2048": 1000,
                   "kv_blocks_held_w2048": 200,
                   "eva_window_entries_read": 1000,
                   "eva_summary_entries_read": 500},
        "after": {"kv_blocks_spanned_w16384": 600,
                  "kv_blocks_held_w16384": 600,
                  "kv_blocks_spanned_w2048": 9000,
                  "kv_blocks_held_w2048": 1000,
                  "eva_window_entries_read": 7000,
                  "eva_summary_entries_read": 2500},
        "traced_rounds": [
            {"counted": pure, "contexts": [12100] * 8, "active": 8},
            {"counted": admitted, "contexts": [12100] * 8, "active": 8}],
        "trace": {"programs": {"jit_decode": {"seconds": 2.0,
                                              "count": 16}},
                  "ops": {"_paged_flash_attention_tpu_custom_call": 1.2}}}


def test_readers():
    obs = synthetic_obs()
    # the window's kind released 7,200 of its 8,000 spanned blocks, the
    # summaries' none of 500: 16 layers each
    assert reader("kv_window_released_share")(obs) == pytest.approx(
        100 * 7200 / 8500)
    # 2,000 summaries of 8,000 entries read
    assert reader("eva_summary_read_share")(obs) == pytest.approx(25.0)
    # the two rounds' decode parts are equal: one round's least time
    # over one program's time (2.0 / 16)
    nflops, nbytes = FL.decode_round(CFG, 8, 64, 64 * 2440, 64)
    least = FL.roofline_seconds(nflops, nbytes, V5E)[0]
    share = reader("evabyte_decode_step_roofline")(obs)
    assert share == pytest.approx(100 * least * 16 / 2.0)
    assert 0 < share < 100
    # the kernel: a decode dispatch's entries (bytes-bound), and in the
    # second round the chunk's pairs (flops-bound) beside them
    decode = FL.roofline_seconds(FL.paged_flops(CFG, 64 * 2440),
                                 FL.paged_bytes(CFG, 64 * 2440), V5E)
    pairs = 1024 * 1024 + 1024 * 1025 // 2 + 1024 * 384
    chunk = FL.roofline_seconds(FL.paged_flops(CFG, pairs),
                                FL.paged_bytes(CFG, 2432), V5E)
    assert (decode[1], chunk[1]) == ("bytes", "flops")
    share = reader("eva_paged_attn_roofline")(obs)
    assert share == pytest.approx(
        100 * (2 * decode[0] + chunk[0]) / 2 * 16 / 1.2)
    assert 0 < share < 100


@pytest.mark.parametrize("name", READERS[:3])
def test_a_reader_finds_nothing_in_a_program_without_the_counters(name):
    """Another model's ``flops`` and a program that counts nothing of
    EVA's: nothing to read, and nothing raised."""
    other = common.load_by_path("models", "granite_hybrid").flops
    obs = dict(synthetic_obs(), flops=other, before={}, after={"chunks": 9})
    obs["traced_rounds"] = [{"counted": {"chunks": 1}, "contexts": [5]}]
    assert reader(name)(obs) is None
    obs["flops"] = FL
    assert reader(name)(obs) is None
    assert reader(name)({"kind": "train_job"}) is None


def test_the_scope_reader_reads_the_eva_group():
    """``eva_mixer_share`` on a recorded trace that holds no ``eva``
    scope: 0, not an error (the fixture's groups are the block's)."""
    from benchmark import opscopes

    red = {"busy_s": 2.0, "groups": {"eva": 0.5, "ffn": 1.0},
           "phases": {}}
    obs = {"kind": "closed_loop", "opscopes": red}
    assert reader("eva_mixer_share")(obs) == pytest.approx(25.0)
    assert reader("eva_mixer_share")({"kind": "train_job"}) is None
    assert opscopes.cut("jit(decode)/decode/while/body/eva/window/"
                        "dot_general:") == ("decode", "eva", "window",
                                            False)
    assert opscopes.cut("jit(chunk_prefill)/admit/eva/write/scatter:")[
        :3] == ("admit", "eva", "write")


def test_a_tree_without_the_programs_part_fails_the_cell_cleanly(
        run_python):
    """The parent's tree has no ``evabyte_lm``: with this PR's benchmark
    files laid over it the cell must exit non-zero at once, before any
    load is offered (here: the adapter's import of the zoo builder is
    made to fail as it does there)."""
    code = (
        "import sys, runpy\n"
        "import deeplearning4j_tpu.models.zoo as zoo\n"
        "del zoo.evabyte_lm\n"
        f"sys.argv = ['run.py', '--workload', {CELL!r}, '--seed', '1',\n"
        "            '--seconds', '1', '--trace', '0', '--rehearse']\n"
        "runpy.run_path('benchmark/run.py', run_name='__main__')\n")
    out = run_python(["-c", code], timeout=300)
    assert out.returncode != 0
    assert "evabyte_lm" in out.stderr
    assert '"correct"' not in out.stdout


def test_the_references_blocks_change_nothing(monkeypatch):
    """A block of queries against its own window's keys and every
    earlier window's summaries is the sum over everything."""
    from benchmark.models import evabyte_reference as reference

    cfg = common.overlay(CFG, CFG["rehearsal"])
    toks = np.random.default_rng(1).integers(0, cfg["vocab_size"], (1, 128))
    whole = reference.forward_logits(9, cfg, toks)
    monkeypatch.setattr(reference, "QUERY_BLOCK", 8)
    reference._layer_step.clear_cache()
    try:
        blocks = reference.forward_logits(9, cfg, toks)
    finally:
        reference._layer_step.clear_cache()
    np.testing.assert_allclose(blocks, whole, atol=2e-5)
