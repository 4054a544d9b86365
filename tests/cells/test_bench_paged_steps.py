"""The reader of the paged kernel's paid steps on hand-made
observations, and its entry in ``BENCHMARK.json``."""

import json
import os

import pytest

from benchmark import common

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
NAME = "paged_step_live_share"


def step_obs(**over):
    """A served window in which one layer's calls paid 620 steps and
    scored 4,000 - 2,880 = 1,120 pool blocks' worth of keys in compute
    blocks of 8: 140 scoring steps."""
    obs = {"kind": "open_loop",
           "before": {"paged_blocks_walked": 2880,
                      "paged_blocks_per_step": 8,
                      "paged_steps_paid": 1380},
           "after": {"paged_blocks_walked": 4000,
                     "paged_blocks_per_step": 8,
                     "paged_steps_paid": 2000}}
    obs.update(over)
    return obs


def test_share_is_scoring_steps_over_paid_steps_in_the_window():
    assert common.load_reader(NAME)(step_obs()) == pytest.approx(
        100.0 * 140 / 620)


def test_a_walk_of_whole_live_blocks_reads_100():
    obs = step_obs(after={"paged_blocks_walked": 2880 + 8 * 620,
                          "paged_blocks_per_step": 8,
                          "paged_steps_paid": 2000})
    assert common.load_reader(NAME)(obs) == pytest.approx(100.0)


@pytest.mark.parametrize("over", [
    {"kind": "train_job"},
    {"before": {}, "after": {}},
    {"before": {"paged_blocks_walked": 2880, "paged_blocks_per_step": 8},
     "after": {"paged_blocks_walked": 4000, "paged_blocks_per_step": 8}},
    {"after": {"paged_blocks_walked": 2880, "paged_blocks_per_step": 8,
               "paged_steps_paid": 1380}},
    {"before": {"paged_blocks_walked": 0, "paged_blocks_per_step": 0,
                "paged_steps_paid": 0},
     "after": {"paged_blocks_walked": 0, "paged_blocks_per_step": 0,
               "paged_steps_paid": 48}},
], ids=["training", "no-counters", "parent-engine", "no-step-paid",
        "no-compute-block"])
def test_reports_nothing_where_there_is_nothing(over):
    assert common.load_reader(NAME)(step_obs(**over)) is None


def test_the_benchmark_lists_it_for_the_block_s_serving_cell():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry, = [m for m in bench["per_layer"] if m["name"] == NAME]
    assert entry == {
        "name": NAME, "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "kernels",
        "moves": "tpot_mean_ms",
        "workloads": ["cgpt1p3b-serve.chat-steady"]}
    cell, = [c for c in bench["workloads"]
             if c["name"] == "cgpt1p3b-serve.chat-steady"]
    assert NAME in {m["name"] for m in common.metrics_for(
        bench, cell, "per_layer")}
