"""The control of ``correct`` at a size a test run can hold: the plain
reference computed in float8 operands (the nearest precision below the
bfloat16 the configurations state) comes out not correct, and the same
reference in bfloat16 comes out correct, under limits set the way the
cells' limits were set (PERF.md): above the sound readings' largest,
below the control's smallest.

At 2 blocks of width 256 (two heads of 128), 4 rows of 64 tokens, the
bfloat16 readings over these seeds were at most loss 3.4e-05, gradient
norm 8.2e-04, change norm 5.0e-04, and the float8 readings at least
4.8e-04, 3.6e-03 and 3.4e-03 (CPU, float32 accumulation).
"""

import json
import os

import numpy as np
import pytest

from benchmark import common, traffic
from benchmark.models import cgpt_block_reference as block_reference
from benchmark.train_cell import compare

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CFG = dict(model="cgpt_block", vocab_size=64, n_embd=256, n_inner=1024,
           n_layer=2, n_head=2)
MODEL = common.load_model(CFG, "this test's CFG")
MIX = dict(pool_batches=3, batch=4, seq_len=64)
LIMITS = {"loss_gap": 1.3e-4, "grad_norm_gap": 1.8e-3,
          "delta_norm_gap": 1.4e-3}
HYPER = json.load(open(os.path.join(
    ROOT, "benchmark", "configs", "cgpt1p3b-train.json")))["optimizer"]


@pytest.fixture(scope="module", params=[1, 2, 3])
def readings(request):
    seed = request.param
    pool = traffic.train_pool(MIX, seed, CFG["vocab_size"])
    ref = MODEL.train_reference(seed, CFG, HYPER, pool, "highest")
    return {prec: compare(MODEL.train_reference(
        seed, CFG, HYPER, pool, prec), ref, LIMITS)
        for prec in ("bf16", "fp8")}


def test_the_stated_precision_is_correct(readings):
    assert all(row["ok"] for row in readings["bf16"].values()), \
        readings["bf16"]


def test_the_control_is_not_correct(readings):
    assert not all(row["ok"] for row in readings["fp8"].values()), \
        readings["fp8"]


def test_the_control_fails_the_gradient_and_the_loss(readings):
    assert not readings["fp8"]["grad_norm_gap"]["ok"]
    assert not readings["fp8"]["loss_gap"]["ok"]


def test_served_control_picks_tokens_below_the_reference_best():
    """Serving's control on the same small stack: over a few hundred
    positions the float8 forward puts first some token whose reference
    logit lies below the reference's best; the reference's own argmax
    has gap 0 by construction."""
    seed = 5
    rng = np.random.default_rng(seed)
    prompt = rng.integers(0, 64, 40).tolist()
    ref = block_reference.forward_logits(
        seed, CFG, np.asarray([prompt + [0] * 88]), "highest")
    served = []
    seq = list(prompt)
    for _ in range(48):     # greedy by the reference itself
        logits = block_reference.forward_logits(
            seed, CFG, np.asarray([seq + [0] * (128 - len(seq))]),
            "highest")
        served.append(int(logits[0, len(seq) - 1].argmax()))
        seq.append(served[-1])
    assert ref.shape == (1, 128, 64)
    prog, ctrl = MODEL.served_gaps(seed, CFG, [(prompt, served)],
                                   control="fp8")
    assert prog.shape == ctrl.shape == (48,)
    assert prog.max() < 1e-5
    assert ctrl.max() >= prog.max()
