"""The runner end to end: it refuses to measure without a TPU, and the
tiny rehearsal of each cell prints the contract's result line with no
device metric in it."""

import json
import os
import shutil

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
CELLS = [w["name"] for w in BENCH["workloads"]]


@pytest.fixture
def run(run_python):
    def go(args, cwd=ROOT):
        return run_python([os.path.join(cwd, "benchmark", "run.py")]
                          + args, cwd=cwd)

    return go


def last_json_line(stdout):
    lines = [ln for ln in stdout.strip().splitlines() if ln.strip()]
    return json.loads(lines[-1]) if lines and lines[-1].startswith(
        "{") else None


@pytest.mark.parametrize("cell", CELLS)
def test_refuses_a_backend_that_is_not_a_tpu(run, cell):
    out = run(["--workload", cell, "--seed", "1", "--seconds", "1",
               "--trace", "0"])
    assert out.returncode == 2, out.stderr[-2000:]
    assert last_json_line(out.stdout) is None
    assert "not 'tpu'" in out.stderr


def test_refuses_an_unknown_workload(run):
    out = run(["--workload", "no.such-cell", "--seed", "1", "--seconds",
               "1", "--trace", "0", "--rehearse"])
    assert out.returncode == 2 and last_json_line(out.stdout) is None


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_prints_the_result_line_and_no_device_metric(run, cell,
                                                               trace):
    out = run(["--workload", cell, "--seed", str(2**31 + 11),
               "--seconds", "3", "--trace", str(trace), "--rehearse"])
    assert out.returncode == 0, out.stderr[-3000:]
    res = last_json_line(out.stdout)
    assert res is not None
    assert {"correct", "attempted", "failed", "metrics",
            "device"} <= set(res)
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] > 0
    assert res["device"]["platform"] == "cpu"
    assert "busy_s" not in res["device"] and "breakdown" not in res
    counts = {m["name"] for m in BENCH["per_layer"]
              if m["source"] == "program_counter"}
    assert set(res["metrics"]) <= counts
    if trace == 0:
        assert res["metrics"] == {}
    # each number compared is printed beside its limit
    assert "compared " in out.stdout and "limit" in out.stdout


def test_fails_where_only_the_benchmark_is_checked_out(run, tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    for path in BENCH["paths"]:
        shutil.copytree(os.path.join(ROOT, path), tmp_path / path)
    out = run(["--workload", CELLS[0], "--seed", "1", "--seconds", "1",
               "--trace", "0"], cwd=str(tmp_path))
    assert out.returncode != 0
    assert last_json_line(out.stdout) is None
