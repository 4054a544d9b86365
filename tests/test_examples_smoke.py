"""Every example under examples/ runs to completion in CI.

The reference treats runnable examples as tests (SURVEY.md §4: tests are
small real runs); here each example executes as a subprocess in tiny-shape
smoke mode (DL4J_EXAMPLES_TINY=1) on the CPU backend
(DL4J_EXAMPLES_PLATFORM=cpu). XLA_FLAGS is dropped from the child env so
each example picks its own virtual-device count (pipeline_4d needs 16,
conftest pins 8 for in-process tests).
"""

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

EXAMPLES = [
    ("distributed_data_parallel.py", []),
    ("flagship_transformer.py", ["--width", "64", "--epochs", "1"]),
    ("fsdp_zero3_training.py", []),
    ("long_context_transformer.py", []),
    ("mnist_mlp.py", []),
    ("moe_expert_parallel.py", []),
    ("moe_lm_on_token_ids.py", []),
    ("native_pjrt_client.py", []),
    ("pipeline_4d_training.py", []),
    ("sequence_parallel_transformer.py", []),
    ("serving_gateway.py", []),
    ("serving_router.py", []),
    ("streaming_decode.py", []),
    ("word2vec_similarity.py", []),
]


def test_all_examples_listed():
    on_disk = sorted(
        f for f in os.listdir(os.path.join(REPO, "examples"))
        if f.endswith(".py"))
    assert on_disk == sorted(name for name, _ in EXAMPLES), (
        "examples/ and the smoke list diverged — add the new example "
        "(with a DL4J_EXAMPLES_TINY mode if it is heavy)")


#: even in tiny-shape mode these are the heaviest smokes (the
#: flagship runs the full train/eval/decode pipeline, ~30 s;
#: streaming_decode grew to SEVEN decode variants incl. a
#: tensor-parallel shard_map compile, ~13 s; serving_router grew to
#: SIX acts — affinity, failover, breaker, stitch, elastic scale-up,
#: and the ISSUE 13 tenant flood — ~17 s); they ride the slow tier
#: with the subprocess soaks so tier-1 stays inside its wall-time
#: budget — tier-1 covers the same engine/router/tenancy paths
#: through tests/test_serving_tp.py, tests/test_serving_paged.py,
#: tests/test_serving_router.py, and tests/test_tenancy.py.
#: ISSUE 14 added the KV-transfer act to serving_router (already
#: slow) plus tests/test_kv_transfer.py (+~1 min of tier-1): the
#: next-heaviest smokes (~6-8 s each) join the slow tier to
#: compensate — their paths stay tier-1-covered by
#: tests/test_sequence_parallel.py, tests/test_pipeline_expert.py,
#: and tests/test_serving_gateway.py.
#: ISSUE 15 added tests/test_router_journal.py + the fast
#: router-restart soak (~+45 s of tier-1): the next-heaviest smokes
#: (mnist_mlp ~5 s, fsdp_zero3_training ~4 s) join the slow tier —
#: tier-1 covers the same paths through tests/test_mnist_e2e.py and
#: tests/test_scaleout.py (FSDP composes are in
#: __graft_entry__.dryrun_multichip)
#: ISSUE 17 added tests/test_kv_tier.py + the tier paged-soak
#: variant (~+45 s of tier-1): the next-heaviest smokes
#: (long_context_transformer ~6 s, pipeline_4d_training ~7 s) join
#: the slow tier — tier-1 covers the same paths through
#: tests/test_remat_transformer.py (remat/long-context lowering)
#: and tests/test_homogeneous_pipeline.py +
#: tests/test_pipeline_solver.py (4D pipeline partitioning)
SLOW_EXAMPLES = {"flagship_transformer.py", "streaming_decode.py",
                 "serving_router.py",
                 "sequence_parallel_transformer.py",
                 "moe_expert_parallel.py",
                 "serving_gateway.py",
                 "mnist_mlp.py",
                 "fsdp_zero3_training.py",
                 "long_context_transformer.py",
                 "pipeline_4d_training.py"}


@pytest.mark.parametrize(
    "name,args",
    [pytest.param(n, a, marks=([pytest.mark.slow]
                               if n in SLOW_EXAMPLES else []))
     for n, a in EXAMPLES],
    ids=[n for n, _ in EXAMPLES])
def test_example_runs(name, args):
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["DL4J_EXAMPLES_PLATFORM"] = "cpu"
    env["DL4J_EXAMPLES_TINY"] = "1"
    p = subprocess.run(
        [sys.executable, os.path.join(REPO, "examples", name), *args],
        capture_output=True, text=True, timeout=900, env=env, cwd=REPO,
    )
    assert p.returncode == 0, (
        f"{name} exited {p.returncode}\n--- stdout\n{p.stdout[-4000:]}"
        f"\n--- stderr\n{p.stderr[-4000:]}")
