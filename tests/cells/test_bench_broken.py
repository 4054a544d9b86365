"""``correct`` comes out false when the timed path is broken underneath.

Each test drives a whole run of a cell in a child process (the tiny
rehearsal, which skips only the harness's look for a chip) with one
fault put under it: a training step that returns its state unchanged or
leaves out half the batch, a served token altered where it is produced.
"""

import json

import pytest

TRAIN, SERVE = "cgpt1p3b-train.train-step", "cgpt1p3b-serve.chat-steady"

RUN = """
import sys
sys.path.insert(0, ".")
{fault}
from benchmark import run
raise SystemExit(run.main(["--workload", "{cell}", "--seed", "77",
    "--seconds", "2", "--trace", "0", "--rehearse"]))
"""

UNCHANGED_STATE = """
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
MultiLayerNetwork._apply_updates = (
    lambda self, params, upd_state, grads, iteration, grad_scale=1.0:
    (params, upd_state))
"""

HALF_THE_BATCH = """
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
whole = MultiLayerNetwork.fit_scan
def half(self, feats, labels, *a, **k):
    n = feats.shape[1] // 2
    return whole(self, feats[:, :n], labels[:, :n], *a, **k)
MultiLayerNetwork.fit_scan = half
"""

ALTERED_TOKEN = """
import deeplearning4j_tpu.serving.engine as engine
sound = engine.sample_tokens
def altered(probs, *a, **k):
    return (sound(probs, *a, **k) + 1) % probs.shape[-1]
engine.sample_tokens = altered
"""


def result_of(run_python, cell, fault):
    out = run_python(["-c", RUN.format(fault=fault, cell=cell)])
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1]), out.stdout


@pytest.mark.parametrize("cell,fault,number", [
    (TRAIN, UNCHANGED_STATE, "delta_norm_gap"),
    (TRAIN, HALF_THE_BATCH, "loss_gap"),
    (SERVE, ALTERED_TOKEN, "served_logit_gap"),
], ids=["state-unchanged", "half-the-batch", "altered-token"])
def test_a_broken_timed_path_is_not_correct(run_python, cell, fault,
                                            number):
    res, stdout = result_of(run_python, cell, fault)
    assert res["correct"] is False
    assert res["failed"] == 0          # the run itself went through
    row = next(ln for ln in stdout.splitlines()
               if f"compared {number}" in ln)
    assert "NOT OK" in row, row


@pytest.mark.parametrize("cell", [TRAIN, SERVE])
def test_the_same_run_unbroken_is_correct(run_python, cell):
    res, stdout = result_of(run_python, cell, "")
    assert res["correct"] is True, stdout[-2000:]
