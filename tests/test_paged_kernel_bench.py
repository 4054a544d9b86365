"""``scripts/paged_kernel_bench.py --rehearse``: the script that times
the paged kernel alone runs through the interpreter off the chip, with
drawn contexts and with ``--context``, and prints the steps a call pays
beside those that score keys."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.parametrize("extra,contexts", [
    ([], None), (["--context", "40"], 40.0)], ids=["drawn", "context"])
def test_rehearsal_prints_paid_and_scoring_steps(extra, contexts):
    out = subprocess.run(
        [sys.executable, "scripts/paged_kernel_bench.py", "--rehearse",
         "--live", "0,3"] + extra, cwd=ROOT, capture_output=True,
        text=True, timeout=600, env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert out.returncode == 0, out.stderr[-2000:]
    lines = [json.loads(x) for x in out.stdout.splitlines()
             if x.startswith("{")]
    kernel = {x["live_rows"]: x for x in lines
              if x.get("program") == "kernel"}
    assert set(kernel) == {0, 3}
    assert all("ms_per_call" not in x for x in lines)
    # no live row: the grid alone, nothing scored
    idle, live = kernel[0], kernel[3]
    assert idle["steps_scoring"] == 0 and idle["steps_paid"] > 0
    assert live["steps_scoring"] >= 3
    assert (live["steps_paid"] - idle["steps_paid"]
            >= live["steps_scoring"])
    if contexts is not None:
        assert live["mean_context"] == contexts
    diffs = [x["kernel_vs_gather_max_diff"] for x in lines
             if "kernel_vs_gather_max_diff" in x]
    assert len(diffs) == 2 and max(diffs) < 0.02
