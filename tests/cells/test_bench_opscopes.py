"""Device busy time by the program's own layers
(``benchmark/opscopes.py``): a path's cut, the reduction of a hand-made
trace (a stand-in for the profiler's file, written through the module's
own schema), the fourteen readers on runs that have nothing for them,
and the reduction of a small trace recorded on a TPU v5e with two of
the program's scopes in it (``fixtures/tiny_scopes.xplane.pb``: a loop
of two scoped products, its gradient and an update; recorded and cut by
``benchmark/tools/record_scope_fixture.py``)."""

import json
import os
import shutil

import pytest

from benchmark import common, opscopes, xplane

FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures",
                       "tiny_scopes.xplane.pb")
with open(os.path.join(common.HERE, "scope_metrics.json")) as _f:
    ENTRIES = json.load(_f)["per_layer"]
READERS = [m["name"] for m in ENTRIES]


# ---- a path -----------------------------------------------------------
@pytest.mark.parametrize("tf_op,want", [
    ("jit(decode)/decode/while/body/closed_call/attn/qkv/dot_general:",
     ("decode", "attn", "qkv", False)),
    ("jit(steps)/while/body/transpose(jvp(attn))/qkv/dot_general",
     (None, "attn", "qkv", True)),
    # the library's flash program, which no scope wraps, by its entry
    ("jit(steps)/while/body/jvp(jit(flash_attention))/pallas_call",
     (None, "attn", "core", False)),
    ("jit(steps)/while/body/transpose(jvp(jit(flash_attention)))/"
     "flash_mha_bwd_dq_block_q=1024/pallas_call",
     (None, "attn", "core", True)),
    # the first group holds; a norm inside it is its own
    ("jit(prefill)/admit/attn/qkv/norm/rsqrt", ("admit", "attn", "qkv",
                                                False)),
    ("jit(steps)/while/body/jvp(moe)/experts/jit(_moe_grouped_product)/"
     "jit(gmm)/pallas_call", (None, "moe", "experts", False)),
    # a child of ANOTHER group is no child
    ("jit(f)/ffn/experts/dot_general", (None, "ffn", None, False)),
    ("jit(prefill)/admit/copy", ("admit", None, None, False)),
    ("jit(steps)/while/body/add", (None, None, None, False)),
    ("", (None, None, None, False)),
])
def test_a_path_is_cut_into_phase_group_child_and_way(tf_op, want):
    assert opscopes.cut(tf_op) == want


# ---- a whole reduction, from a stand-in for the profiler's file -------
#: (start ns, end ns, instruction, tf_op, hlo_category, flops, bytes,
#: program id): a training step's loop with a product on the way back,
#: the flash kernel, a re-layout the compiler made (no path: the
#: compiled program says ``fusion.9`` reads it) and the feed-forward's
#: product; a reduction nobody scoped; an admission program with a
#: product and a copy nothing reads
OPS = [
    (0, 1000, "%while.1 = (f32[]) while(%tuple.1)", "jit(steps)/while",
     "while", 999, 999, 11),
    (100, 400, "%fusion.1 = bf16[8] fusion(%p.1), kind=kOutput",
     "jit(steps)/while/body/closed_call/transpose(jvp(attn))/qkv/"
     "dot_general:", "convolution fusion", 600, 30, 11),
    (400, 600, "%jvp_jit_flash_attention__.3 = bf16[8] custom-call(%p.2),"
     ' custom_call_target="tpu_custom_call"',
     "jit(steps)/while/body/closed_call/jvp(jit(flash_attention))/"
     "pallas_call:", "custom-call", 400, 20, 11),
    (600, 700, "%copy.7 = bf16[8]{0} copy(%p.3)", "", "data formatting",
     0, 16, 11),
    (700, 900, "%fusion.9 = bf16[8] fusion(%copy.7), kind=kOutput",
     "jit(steps)/while/body/closed_call/jvp(ffn)/dot_general:",
     "convolution fusion", 200, 10, 11),
    (1000, 1100, "%reduce.4 = f32[] reduce(%gte.1)",
     "jit(steps)/reduce_sum:", "reduce", 8, 8, 11),
    (2000, 2300, "%fusion.5 = bf16[8] fusion(%p.4), kind=kOutput",
     "jit(prefill)/admit/attn/qkv/dot_general:", "convolution fusion",
     300, 15, 12),
    (2300, 2350, "%copy-done.1 = bf16[8] copy-done(%copy-start.1)", "",
     "copy-done", 0, 16, 12),
]
#: instructions of program 11's loop body: (id, name, opcode, op_name,
#: operands)
BODY = [
    (1, "p.3", "parameter", "", []),
    (2, "copy.7", "copy", "", [1]),
    (3, "fusion.9", "fusion",
     "jit(steps)/while/body/closed_call/jvp(ffn)/dot_general", [2]),
]


def _trace(tmp_path, ops=OPS, scoped=True):
    msgs = opscopes.messages()
    space = msgs["XSpace"]()
    plane = space.planes.add(name="/device:TPU:0")
    for i, name in enumerate(opscopes.STATS, start=1):
        entry = plane.stat_metadata.add(key=i)
        entry.value.id, entry.value.name = i, name
    stat_id = {name: i for i, name in enumerate(opscopes.STATS, start=1)}
    line = plane.lines.add(name=xplane.OPS_LINE, timestamp_ns=5)
    for k, (start, end, text, tf_op, cat, flops, nbytes, prog) in \
            enumerate(ops, start=1):
        line.events.add(metadata_id=k, offset_ps=start * 1000,
                        duration_ps=(end - start) * 1000)
        meta = plane.event_metadata.add(key=k).value
        meta.id, meta.name = k, text.encode()
        if tf_op and scoped:
            meta.stats.add(metadata_id=stat_id["tf_op"],
                           str_value=tf_op.encode())
        meta.stats.add(metadata_id=stat_id["hlo_category"],
                       str_value=cat.encode())
        meta.stats.add(metadata_id=stat_id["flops"], int64_value=flops)
        meta.stats.add(metadata_id=stat_id["bytes_accessed"],
                       int64_value=nbytes)
        meta.stats.add(metadata_id=stat_id["program_id"],
                       uint64_value=prog)
        if "reduce" in text:
            meta.stats.add(metadata_id=stat_id["source"],
                           str_value=b"x.py:3")
    # the compiled program of the training step, as the trace carries it
    program = msgs["HloProto"]()
    body = program.hlo_module.computations.add(id=1)
    for i, name, opcode, op_name, operands in BODY:
        inst = body.instructions.add(name=name, opcode=opcode, id=i,
                                     operand_ids=operands)
        inst.metadata.op_name = op_name.encode() if scoped else b""
    host = space.planes.add(name=opscopes.PROGRAMS_PLANE)
    entry = host.stat_metadata.add(key=1)
    entry.value.id, entry.value.name = 1, opscopes.HLO_STAT
    meta = host.event_metadata.add(key=1).value
    meta.id, meta.name = 1, b"jit_steps(11)"
    meta.stats.add(metadata_id=1,
                   bytes_value=program.SerializeToString())
    path = tmp_path / "stand_in.xplane.pb"
    path.write_bytes(space.SerializeToString())
    return str(path)


def test_groups_and_unscoped_add_up_to_busy(tmp_path):
    red = opscopes.reduce_trace(_trace(tmp_path))
    ns = 1e-9
    assert red["busy_s"] == pytest.approx(1450 * ns)
    # the loop's own time (1000 less its four children), the reduction
    # and the copy nothing reads are nobody's
    assert red["groups"] == pytest.approx({
        "attn": 800 * ns, "ffn": 300 * ns,
        opscopes.UNSCOPED: (200 + 100 + 50) * ns})
    assert sum(red["groups"].values()) == pytest.approx(red["busy_s"])
    assert red["children"] == pytest.approx({
        "attn/qkv": 600 * ns, "attn/core": 200 * ns, "ffn": 300 * ns,
        opscopes.UNSCOPED: 350 * ns})
    assert red["phases"] == pytest.approx({"admit": 300 * ns})
    assert red["admit_children"] == pytest.approx({"attn/qkv": 300 * ns})
    assert red["labels"]["fusion_fusion | attn/qkv"] == pytest.approx(
        600 * ns)
    assert red["backward_s"] == pytest.approx(300 * ns)
    # the compiler's copy went to the group that reads it, kept apart
    assert red["inherited"] == pytest.approx(
        {"ffn | data formatting": 100 * ns})
    assert red["categories"]["attn | custom-call"] == pytest.approx(
        200 * ns)
    # the loop's flops are its body's: not counted twice
    assert red["work"]["attn"] == pytest.approx(
        {"flops": 1300.0, "bytes": 65.0, "seconds": 800 * ns})
    assert red["work"][opscopes.UNSCOPED]["flops"] == 8.0
    top = red["unscoped_top"]
    assert [t[0] for t in top] == ["while_while", "reduce_reduce",
                                   "copy-done_copy-done"]
    assert top[1][2] == "x.py:3"
    opscopes.log_table(red, {"bf16_flops": 197e12,
                             "hbm_bytes_per_s": 819e9}, 0.0)


def test_a_trace_without_a_scoped_operation_reduces_to_nothing(tmp_path):
    assert opscopes.reduce_trace(_trace(tmp_path, scoped=False)) is None
    empty = tmp_path / "no_device.xplane.pb"
    empty.write_bytes(opscopes.messages()["XSpace"]().SerializeToString())
    assert opscopes.reduce_trace(str(empty)) is None


def test_a_search_for_an_heir_stops_at_a_loops_tuple():
    """A copy handed to a loop is followed INTO the loop (entry k of
    the tuple is ``get-tuple-element`` k of the body's parameter), not
    past the loop to whatever reads its result."""
    msgs = opscopes.messages()
    program = msgs["HloProto"]()
    entry = program.hlo_module.computations.add(id=1)
    body = program.hlo_module.computations.add(id=2)
    for i, name, opcode, op_name, operands in [
            (10, "w", "parameter", "", []),
            (11, "copy.1", "copy", "", [10]),
            (12, "copy.2", "copy", "", [10]),
            (13, "tuple.1", "tuple", "", [11, 12]),
            (14, "while.1", "while", "jit(f)/decode/while", [13]),
            (15, "gte.9", "get-tuple-element", "", [14]),
            (16, "fusion.9", "fusion", "jit(f)/decode/head/sample/x",
             [15])]:
        entry.instructions.add(name=name, opcode=opcode, id=i,
                               operand_ids=operands
                               ).metadata.op_name = op_name.encode()
    entry.instructions[4].called_computation_ids.extend([2, 3])
    for i, name, opcode, op_name, operands, index in [
            (20, "param", "parameter", "", [], 0),
            (21, "gte.0", "get-tuple-element", "", [20], 0),
            (22, "gte.1", "get-tuple-element", "", [20], 1),
            (23, "fusion.1", "fusion",
             "jit(f)/decode/while/body/attn/qkv/dot_general", [21], 0),
            (24, "fusion.2", "fusion",
             "jit(f)/decode/while/body/ffn/dot_general", [22], 0)]:
        inst = body.instructions.add(name=name, opcode=opcode, id=i,
                                     operand_ids=operands,
                                     tuple_index=index)
        inst.metadata.op_name = op_name.encode()
    heirs = opscopes.heirs_of(program)
    assert opscopes.cut(heirs["copy.1"])[:3] == ("decode", "attn", "qkv")
    assert opscopes.cut(heirs["copy.2"])[:3] == ("decode", "ffn", None)
    assert "tuple.1" not in heirs


# ---- the readers ------------------------------------------------------
def test_the_entries_kept_for_the_benchmark_are_well_formed():
    bench = common.load_benchmark()
    cells = {w["name"] for w in bench["workloads"]}
    e2e = {m["name"]: m.get("workloads") for m in bench["end_to_end"]}
    layers = {m["layer"] for m in bench["per_layer"]}
    assert len(ENTRIES) == 14 and len(set(READERS)) == 14
    assert not set(READERS) & {m["name"] for m in bench["per_layer"]}
    for m in ENTRIES:
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert common.NAME.match(m["name"]) and m["unit"] == "%"
        assert m["source"] == "device_trace" and m["layer"] in layers
        assert set(m["workloads"]) <= cells
        assert set(m["workloads"]) <= set(e2e[m["moves"]])
        assert m["name"].startswith("train_") == (
            m["moves"] == "train_tok_per_s")
        assert os.path.isfile(os.path.join(common.HERE, "metrics",
                                           m["name"] + ".py"))


TRAIN_OBS = {"kind": "train_job", "cell": "no.such-cell", "trace": None,
             "trace_window_s": 3.0, "peaks": None}
SERVED_OBS = {"kind": "open_loop", "cell": "no.such-cell", "trace": None,
              "trace_window_s": 3.0, "peaks": None}
RED = {"busy_s": 2.0, "phases": {"admit": 0.5, "decode": 1.5},
       "groups": {"attn": 0.8, "ffn": 0.6, "moe": 0.1, "mixer": 0.1,
                  "embed": 0.02, "head": 0.08, "update": 0.1,
                  "cast": 0.1, opscopes.UNSCOPED: 0.1}}
WANT = {"attn": 40.0, "ffn": 30.0, "moe": 5.0, "mixer": 5.0, "head": 5.0,
        "admission": 25.0, "unscoped": 5.0, "update": 10.0}


@pytest.mark.parametrize("name", READERS)
def test_a_reader_reports_in_its_kind_of_cell_only(name):
    read = common.load_reader(name)
    trained = name.startswith("train_")
    mine, other = ((TRAIN_OBS, SERVED_OBS) if trained
                   else (SERVED_OBS, TRAIN_OBS))
    # no trace (a rehearsal), and the other kind of cell with one
    assert read(dict(mine)) is None
    assert read(dict(other, opscopes=RED, trace={})) is None
    # a program without the vocabulary: a reduction of None
    assert read(dict(mine, opscopes=None, trace={})) is None
    got = read(dict(mine, opscopes=RED, trace={}))
    assert got == pytest.approx(
        WANT[name.replace("train_", "").split("_")[1]])


def test_the_reduction_is_of_the_cells_own_trace(tmp_path, monkeypatch):
    monkeypatch.setattr(common, "OUT_DIR", str(tmp_path))
    run_dir = os.path.join(common.trace_dir("mine.cell"), "plugins",
                           "profile", "2026_01_01")
    os.makedirs(run_dir)
    shutil.copy(_trace(tmp_path), os.path.join(run_dir, "h.xplane.pb"))
    obs = {"kind": "train_job", "cell": "mine.cell", "trace": {},
           "peaks": None}
    assert opscopes.share(obs, True, ("attn",)) == pytest.approx(
        100 * 800 / 1450)
    assert opscopes.share(obs, True, phase="admit") == pytest.approx(
        100 * 300 / 1450)
    assert obs["opscopes"]["busy_s"] > 0          # kept for the others
    assert opscopes.of({"kind": "train_job", "cell": "no.such-cell",
                        "trace": {}}) is None


# ---- the trace recorded on the chip -----------------------------------
@pytest.fixture(scope="module")
def recorded():
    if not os.path.exists(FIXTURE):
        pytest.skip("no recorded trace")
    with open(FIXTURE, "rb") as f:
        space = opscopes.messages()["XSpace"].FromString(f.read())
    plane = next(p for p in space.planes
                 if p.name.startswith("/device:TPU:"))
    names = opscopes.stat_names(plane)
    paths = [opscopes.statistics(names, e.value, ("tf_op",)).get("tf_op")
             for e in plane.event_metadata]
    return [p for p in paths if p], opscopes.reduce_trace(FIXTURE)


def test_fixture_is_small_enough_for_the_tree():
    if not os.path.exists(FIXTURE):
        pytest.skip("no recorded trace")
    assert os.path.getsize(FIXTURE) < 200 * 1024


def test_the_chips_own_paths_hold_both_scopes_and_their_way_back(
        recorded):
    paths, _ = recorded
    joined = "\n".join(paths)
    for want in ("/jvp(attn)/qkv/", "/jvp(ffn)/",
                 "/transpose(jvp(attn))/qkv/", "/transpose(jvp(ffn))/",
                 "/update/step/"):
        assert want in joined, want
    ways = {opscopes.cut(p) for p in paths}
    assert (None, "attn", "qkv", True) in ways
    assert (None, "ffn", None, False) in ways
    assert (None, "update", "step", False) in ways


def test_fixture_reduces_to_groups_that_add_up(recorded):
    _, red = recorded
    assert red is not None
    assert sum(red["groups"].values()) == pytest.approx(red["busy_s"])
    assert {"attn", "ffn", "update"} <= set(red["groups"])
    assert 0.0 < red["backward_s"] < red["busy_s"]
    assert red["work"]["attn"]["flops"] > 0
    # the same file through ``xplane``: the same busy time (it reads
    # whole nanoseconds, this the file's picoseconds: ~100 operations
    # of a quarter of a microsecond each)
    assert xplane.reduce_trace(FIXTURE)["busy_s"] == pytest.approx(
        red["busy_s"], rel=5e-3)
