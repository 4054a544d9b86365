"""``scripts/scope_ops.py``: a scope group's compiled instructions, one
line each, from the small trace recorded on a TPU v5e that
``tests/cells/test_bench_opscopes.py`` reduces (two scoped products in
a loop, their gradient, an update)."""

import os
import sys

from scripts import scope_ops

FIXTURE = os.path.join(os.path.dirname(__file__), "cells", "fixtures",
                       "tiny_scopes.xplane.pb")


def _run(monkeypatch, capsys, *argv):
    monkeypatch.setattr(sys, "argv", ["scope_ops.py", "--trace", FIXTURE,
                                      *argv])
    assert scope_ops.main() == 0
    return capsys.readouterr().out.splitlines()


def test_a_groups_instructions_forward_and_backward(monkeypatch, capsys):
    lines = _run(monkeypatch, capsys, "--group", "attn", "--min-ms", "0")
    assert lines[0].startswith("/device:TPU:0: busy ")
    parts = [ln.split()[0:2] for ln in lines if " of busy" in ln]
    assert sorted(parts) == [["attn/qkv", "backward"],
                             ["attn/qkv", "forward"]]
    ops = [ln for ln in lines if " | " in ln][1:]
    # the products, the compiler-made copies given to their reader, and
    # nothing of the other group
    assert any("convolution fusion | %fusion" in ln
               and "transpose(jvp(attn))/qkv" in ln for ln in ops)
    assert any("copy-done | " in ln for ln in ops)
    assert not any("ffn" in ln.rsplit(" | ", 1)[1] for ln in ops)
    # largest first
    ms = [float(ln.split()[0]) for ln in ops]
    assert ms == sorted(ms, reverse=True)


def test_the_floor_leaves_out_the_small_ones(monkeypatch, capsys):
    lines = _run(monkeypatch, capsys, "--group", "ffn", "--min-ms", "50")
    assert any(ln.split()[0] == "ffn" and " of busy" in ln for ln in lines)
    assert [ln for ln in lines if " | " in ln][1:] == []
