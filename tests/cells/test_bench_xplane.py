"""The reduction from a trace to busy time, own time and named gaps:
on hand-made events, and on a small trace recorded on a TPU v5e
(``fixtures/tiny.xplane.pb``: four runs of a jitted program with a loop,
host sleeps between; recorded by ``benchmark/tools/record_fixture.py``).
"""

import os

import pytest

from benchmark import xplane

FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures",
                       "tiny.xplane.pb")


def test_busy_time_is_the_union_and_children_are_not_counted_twice():
    events = [(0, 100, "while"), (10, 40, "a"), (50, 90, "b"),
              (200, 300, "c")]
    merged, own = xplane.union_and_self_times(events)
    assert merged == [[0, 100], [200, 300]]
    assert own["while"] == pytest.approx(30e-9)
    assert own["a"] == pytest.approx(30e-9)
    assert own["b"] == pytest.approx(40e-9)
    assert own["c"] == pytest.approx(100e-9)
    assert sum(own.values()) == pytest.approx(200e-9)


def test_gaps_are_named_by_the_program_that_followed():
    merged = [[0, 100], [200, 300], [320, 400]]
    modules = [(0, 100, "jit_prefill(7)"), (190, 400, "jit_decode(12)")]
    gaps = xplane.name_gaps(merged, modules, 0, 450)
    assert gaps["before_jit_decode"] == pytest.approx(100e-9)
    assert gaps["within_jit_decode"] == pytest.approx(20e-9)
    assert gaps["after_last_program"] == pytest.approx(50e-9)


@pytest.mark.parametrize("raw,want", [
    ("jit_decode(1234567)", "jit_decode"), ("jit_steps", "jit_steps"),
    (" jit_a(1) ", "jit_a")])
def test_program_names_lose_their_run_ids(raw, want):
    assert xplane.program_name(raw) == want


def test_top_orders_and_cuts():
    table = {f"op{i}": float(i) for i in range(15)}
    top = xplane.top(table)
    assert len(top) == 10 and top[0] == ["op14", 14.0]


@pytest.fixture(scope="module")
def reduced():
    if not os.path.exists(FIXTURE):
        pytest.skip("no recorded trace")
    return xplane.reduce_trace(FIXTURE)


def test_fixture_has_one_chip_and_four_program_runs(reduced):
    assert reduced["chips"] == 1
    prog = reduced["programs"]["jit_tiny_step"]
    assert prog["count"] == 4
    assert 0.0 < prog["seconds"] < 0.1


def test_fixture_busy_time_lies_inside_its_span(reduced):
    assert 0.0 < reduced["busy_s"] < reduced["span_s"]
    assert sum(reduced["ops"].values()) == pytest.approx(
        reduced["busy_s"], rel=0.02)


def test_fixture_gaps_lie_before_the_program(reduced):
    gaps = reduced["gaps"]
    assert gaps["before_jit_tiny_step"] > 0.004   # three sleeps of 2 ms
    assert reduced["busy_s"] + sum(gaps.values()) == pytest.approx(
        reduced["span_s"], rel=1e-6)


def test_a_trace_without_a_device_plane_reduces_to_nothing(tmp_path):
    import jax
    import jax.numpy as jnp

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    jnp.ones((8, 8)).sum().block_until_ready()
    jax.profiler.stop_trace()
    path = xplane.find_trace(str(tmp_path))
    assert path is not None
    assert xplane.reduce_trace(path) is None
