"""Device idle time by host phase (``benchmark/hostspans.py``): the
attribution on hand-made intervals, the six readers on runs that have
nothing for them, and the reduction of a small trace recorded on a TPU
v5e with the program's spans in it (``fixtures/tiny_spans.xplane.pb``:
a two-layer engine behind the gateway serving a few streamed requests;
recorded by ``benchmark/tools/record_span_fixture.py``)."""

import os
import shutil

import jax.profiler
import pytest

from benchmark import common, hostspans, stats

FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures",
                       "tiny_spans.xplane.pb")
READERS = ["submit_lock_wait_p50_ms", "first_token_hold_p50_ms",
           "idle_gateway_share", "idle_scheduler_share",
           "idle_engine_host_share", "idle_unattributed_share"]

#: one stepper thread, in ns: a round with its leaves, then the
#: gateway's, then the next round's start
SPANS = sorted([
    (0, 1000, "serving.round"),
    (0, 100, "serving.sweeps"),
    (100, 400, "serving.admit"),
    (150, 250, "serving.prefill"),
    (400, 900, "serving.decode_chunk"),
    (400, 500, "serving.decode_dispatch"),
    (500, 900, "serving.token_sync"),
    (900, 980, "serving.commit"),
    (1000, 1100, "gateway.deliver"),
    (1150, 1300, "gateway.lock_yield"),
    (1300, 2000, "serving.round"),
    (1300, 1400, "serving.tables"),
], key=lambda s: (s[0], -s[1]))


def test_pieces_are_disjoint_and_named_by_the_innermost_span():
    pieces = hostspans.innermost(SPANS)
    for a, b in zip(pieces, pieces[1:]):
        assert a[1] <= b[0]
    assert (100, 150, "serving.admit") in pieces
    assert (150, 250, "serving.prefill") in pieces
    assert (250, 400, "serving.admit") in pieces
    assert (980, 1000, "serving.round") in pieces     # its own time
    assert (1400, 2000, "serving.round") in pieces
    assert not any(p[0] < 1125 < p[1] for p in pieces)   # under none


@pytest.mark.parametrize("idle,want", [
    # wholly under one leaf
    ([(910, 970, None)], {"serving.commit": 60e-9}),
    # split over two leaves, and the parent's own time between them
    ([(950, 1050, None)], {"serving.commit": 30e-9,
                           "serving.round": 20e-9,
                           "gateway.deliver": 50e-9}),
    # under none
    ([(1100, 1150, None)], {"unattributed": 50e-9}),
    # half under a leaf, half under none
    ([(1080, 1140, None)], {"gateway.deliver": 20e-9,
                            "unattributed": 40e-9}),
    # a gap inside a device program keeps its label
    ([(600, 700, "within_jit_decode")], {"within_jit_decode": 100e-9}),
    # a parent less its child
    ([(120, 300, None)], {"serving.admit": 80e-9,
                          "serving.prefill": 100e-9}),
])
def test_idle_goes_to_the_span_that_covers_it(idle, want):
    got = hostspans.attribute(idle, hostspans.innermost(SPANS))
    assert got == pytest.approx(want)


def test_gaps_inside_a_program_are_told_from_gaps_between():
    merged = [[0, 100], [200, 300], [320, 400]]
    modules = [(0, 100, "jit_prefill(7)"), (190, 400, "jit_decode(12)")]
    idle = hostspans.idle_intervals(merged, modules, 0, 450)
    assert idle == [(100, 200, None), (300, 320, "within_jit_decode"),
                    (400, 450, None)]


@pytest.mark.parametrize("leaf,group", [
    ("gateway.lock_yield", "gateway"), ("gateway.deliver", "gateway"),
    ("serving.sweeps", "scheduler"), ("serving.admit", "scheduler"),
    ("serving.reserve", "scheduler"),
    ("serving.commit", "engine step"), ("serving.tables", "engine step"),
    ("serving.round_end", "engine step"),
    ("serving.round", "engine step")])
def test_leaves_belong_to_the_benchmarks_layers(leaf, group):
    assert hostspans.group_of(leaf) == group


def test_clock_check_measures_how_far_a_program_lies_outside():
    modules = [(420, 880, "jit_decode(1)"), (90, 99, "jit_prefill(2)")]
    ok = hostspans.check_clock(modules, SPANS)
    assert ok == {"checked": 1, "median_lag_ns": 20,
                  "worst_outside_ns": 0.0}
    # after its round's sync, before the next round's dispatch
    spans = SPANS + [(5.0e6, 5.1e6, "serving.decode_dispatch")]
    late = hostspans.check_clock([(3.0e6, 3.1e6, "jit_decode(1)")],
                                 spans)
    assert late["worst_outside_ns"] == pytest.approx(3.0e6 - 900)
    # after the last dispatch the trace holds: launched by one it lost
    lost = hostspans.check_clock([(3.0e6, 3.1e6, "jit_decode(1)")],
                                 SPANS)
    assert lost["checked"] == 0


# ---- a whole reduction, from a stand-in for the profiler's file ------
class _Event:
    def __init__(self, start, end, name):
        self.start_ns, self.duration_ns = start, end - start
        self.name = name


class _Named:
    def __init__(self, name, **kw):
        self.name = name
        self.__dict__.update(kw)


def _profile(device_shift=0.0):
    """SPANS on a host line beside a noisier handler line, and a device
    that runs a prefill and two decode programs (shifted by
    ``device_shift`` ns against the host's clock)."""
    ops = [(160, 240, "%fusion.1 = f32[] fusion()"),
           (420, 600, "%fusion.2 = f32[] fusion()"),
           (640, 880, "%fusion.3 = f32[] fusion()"),
           (1390, 1900, "%fusion.2 = f32[] fusion()")]
    modules = [(160, 240, "jit_prefill(1)"), (420, 880, "jit_decode(2)"),
               (1390, 1900, "jit_decode(2)")]

    def line(name, events, shift=0.0):
        return _Named(name, events=[_Event(a + shift, b + shift, n)
                                    for a, b, n in events])

    return _Named("profile", planes=[
        _Named("/host:CPU", lines=[
            line("python", [(50, 60, "gateway.submit"),
                            (0, 2000, "PjitFunction(decode)")]),
            line("python", SPANS + [(400, 500, "PjitFunction(decode)")]),
        ]),
        _Named("/device:TPU:0", lines=[
            line("XLA Ops", ops, device_shift),
            line("XLA Modules", modules, device_shift)])])


@pytest.fixture
def profile_file(monkeypatch):
    def use(data):
        monkeypatch.setattr(
            jax.profiler, "ProfileData",
            _Named("ProfileData", from_file=lambda path: data))
    return use


def test_reduction_adds_up_to_the_idle_total(profile_file):
    profile_file(_profile())
    red = hostspans.reduce_trace("unused")
    # idle: 240-420, 600-640 (inside the decode program), 880-1390
    assert red["within"] == pytest.approx({"within_jit_decode": 40e-9})
    assert red["idle_s"] == pytest.approx((180 + 40 + 510) * 1e-9)
    assert red["leaves"]["gateway.lock_yield"] == pytest.approx(150e-9)
    assert red["unattributed_s"] == pytest.approx(50e-9)
    assert red["groups"] == pytest.approx({
        "gateway": 250e-9, "scheduler": 150e-9,
        "engine step": 240e-9})
    assert (sum(red["groups"].values()) + sum(red["within"].values())
            + red["unattributed_s"]) == pytest.approx(red["idle_s"])
    assert red["clock"]["checked"] == 1    # the second has no sync


def test_a_program_outside_its_spans_reduces_to_nothing(profile_file):
    data = _profile(device_shift=5.0e6)
    data.planes[0].lines[1].events.append(
        _Event(9.0e6, 9.1e6, "serving.decode_dispatch"))
    profile_file(data)
    assert hostspans.reduce_trace("unused") is None


def test_no_program_to_check_the_clock_by_reduces_to_nothing(
        profile_file):
    profile_file(_profile(device_shift=5.0e6))   # all past the spans
    assert hostspans.reduce_trace("unused") is None


def test_a_program_without_the_spans_reduces_to_nothing(profile_file):
    data = _profile()
    data.planes[0].lines = data.planes[0].lines[:1]
    profile_file(data)
    assert hostspans.reduce_trace("unused") is None


# ---- the readers, on runs that have nothing for them -----------------
TRAIN_OBS = {"kind": "train_job", "trace": {"busy_s": 1.0},
             "trace_window_s": 3.0, "peaks": {"bf16_flops": 1.0},
             "stats": stats, "records": []}
REHEARSAL_OBS = {"kind": "open_loop", "trace": None, "peaks": None,
                 "trace_window_s": 0.5, "stats": stats, "records": [
                     {"in_window": True, "ok": True, "timing": {
                         "ttft_s": 0.1, "first_delta_s": 0.2,
                         "gateway_wait_s": 0.01}}]}


@pytest.mark.parametrize("name", READERS)
@pytest.mark.parametrize("obs", [TRAIN_OBS, REHEARSAL_OBS],
                         ids=["training", "rehearsal"])
def test_readers_report_nothing_where_there_is_nothing(name, obs):
    assert common.load_reader(name)(dict(obs)) is None


def test_the_two_medians_read_the_requests_timing():
    def rec(ttft, first, wait, ok=True, in_window=True):
        return {"in_window": in_window, "ok": ok, "timing": {
            "ttft_s": ttft, "first_delta_s": first,
            "gateway_wait_s": wait}}

    obs = {"kind": "open_loop", "peaks": {"hbm_bytes_per_s": 1.0},
           "stats": stats, "records": [
               rec(0.10, 0.60, 0.30), rec(0.20, 0.75, 0.10),
               rec(0.30, 0.80, 0.20), rec(0.1, 9.0, 9.0, ok=False),
               rec(0.1, 9.0, 9.0, in_window=False),
               {"in_window": True, "ok": True,   # an older program's
                "timing": {"ttft_s": 0.1}}]}
    assert common.load_reader("first_token_hold_p50_ms")(obs) == \
        pytest.approx(500.0)
    assert common.load_reader("submit_lock_wait_p50_ms")(obs) == \
        pytest.approx(200.0)


def test_shares_are_of_the_traced_stretch():
    obs = {"kind": "open_loop", "trace_window_s": 2.0, "hostspans": {
        "groups": {"gateway": 0.02, "scheduler": 0.01,
                   "engine step": 0.1}, "unattributed_s": 0.004}}
    assert common.load_reader("idle_gateway_share")(obs) == \
        pytest.approx(1.0)
    assert common.load_reader("idle_scheduler_share")(obs) == \
        pytest.approx(0.5)
    assert common.load_reader("idle_engine_host_share")(obs) == \
        pytest.approx(5.0)
    assert common.load_reader("idle_unattributed_share")(obs) == \
        pytest.approx(0.2)


def test_the_reduction_is_of_the_cells_own_trace(tmp_path, monkeypatch):
    """``of`` reads the trace ``SubTrace`` left for ``obs["cell"]``,
    not whatever cell's trace is newest under the output directory."""
    if not os.path.exists(FIXTURE):
        pytest.skip("no recorded trace")
    monkeypatch.setattr(common, "OUT_DIR", str(tmp_path))
    for cell, content in (("mine.cell", FIXTURE), ("other.cell", None)):
        run_dir = os.path.join(common.trace_dir(cell), "plugins",
                               "profile", "2026_01_01")
        os.makedirs(run_dir)
        path = os.path.join(run_dir, "host.xplane.pb")
        if content:
            shutil.copy(content, path)
        else:                      # newer, and no trace at all
            with open(path, "wb") as f:
                f.write(b"not a trace")
    obs = {"kind": "open_loop", "cell": "mine.cell", "trace": {},
           "trace_window_s": 3.0}
    red = hostspans.of(obs)
    assert red is not None and red["clock"]["checked"] >= 3
    assert hostspans.of({"kind": "open_loop", "cell": "no.such-cell",
                         "trace": {}}) is None


# ---- the trace recorded on the chip ----------------------------------
@pytest.fixture(scope="module")
def recorded():
    if not os.path.exists(FIXTURE):
        pytest.skip("no recorded trace")
    return hostspans.reduce_trace(FIXTURE)


def test_fixture_is_small_enough_for_the_tree():
    if not os.path.exists(FIXTURE):
        pytest.skip("no recorded trace")
    assert os.path.getsize(FIXTURE) < 200 * 1024


def test_fixture_has_a_stepper_line_on_the_devices_clock(recorded):
    assert recorded is not None
    clock = recorded["clock"]
    assert clock["checked"] >= 3
    assert 0.0 <= clock["median_lag_ns"]
    assert clock["worst_outside_ns"] <= hostspans.CLOCK_LIMIT_NS


def test_fixture_idle_is_named_by_host_phases(recorded):
    between = recorded["idle_s"] - sum(recorded["within"].values())
    assert between > 0.0
    assert recorded["unattributed_s"] < 0.1 * between
    assert {"serving.commit", "serving.round_end",
            "gateway.lock_yield"} <= set(recorded["leaves"])
    assert sum(recorded["groups"].values()) == pytest.approx(
        sum(recorded["leaves"].values()))
