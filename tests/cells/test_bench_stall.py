"""The stall tool: it stops its child once the window has opened, lets
it go on, passes its output through and returns its exit code."""

import os

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
TOOL = os.path.join(ROOT, "benchmark", "tools", "stall.py")
#: ticks for a second and prints the longest it was kept from ticking
CHILD = ("import sys, time; print('[bench] window opens', flush=True); "
         "t0 = t = time.monotonic(); gap = 0.0\n"
         "while t - t0 < 1.0:\n"
         "    time.sleep(0.01); now = time.monotonic()\n"
         "    gap = max(gap, now - t); t = now\n"
         "print('gap', gap, flush=True); sys.exit({rc})")


@pytest.mark.parametrize("length,rc", [(0.5, 0), (0.0, 0), (0.5, 3)])
def test_stops_the_child_inside_its_window(run_python, length, rc):
    out = run_python([TOOL, "--after", "0.2", "--for", str(length), "--",
                      "python3", "-c", CHILD.format(rc=rc)])
    assert out.returncode == rc, out.stderr[-2000:]
    lines = out.stdout.splitlines()
    assert lines[0] == "[bench] window opens"
    gap = float(next(ln for ln in lines if ln.startswith("gap")).split()[1])
    if length:
        assert "[stall] continued" in lines
        assert gap >= 0.9 * length
    else:
        assert not any(ln.startswith("[stall]") for ln in lines)
