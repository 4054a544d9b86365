"""A model comes as new files. The benchmark is copied to a scratch
tree, a fixture model is added there by new files only (an adapter, a
configuration file, two readers, entries appended to the copy of
``BENCHMARK.json``; the traffic files are the present ones), no file
that was there differs, and the rehearsal of a training and a serving
cell of the fixture runs to a result line with ``correct`` true. And a
configuration that names no model, or a missing one, is refused.

The fixture (``fixtures/toy_model``) is the program's bare
causal-attention stack, which ``fit_scan`` trains and ``DecodeEngine``
serves today: its parameter tree and its configuration keys are not the
block's. (The engine refuses a net that carries a recurrent state, so
an LSTM cannot stand here.)
"""

import hashlib
import json
import os
import shutil

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
TOY = os.path.join(os.path.dirname(os.path.abspath(__file__)), "fixtures",
                   "toy_model")
BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
CELLS = {"train_job": "toy-attn.train-step",
         "open_loop": "toy-attn.chat-steady"}
NEW_FILES = {"attn_lm.py": "benchmark/models/attn_lm.py",
             "toy-attn.json": "benchmark/configs/toy-attn.json",
             "toy_steps.py": "benchmark/metrics/toy_steps.py",
             "toy_evictions.py": "benchmark/metrics/toy_evictions.py"}


def hashes(root) -> dict:
    out = {}
    for top in ["BENCHMARK.json"] + BENCH["paths"]:
        path = os.path.join(root, top)
        files = [path] if os.path.isfile(path) else [
            os.path.join(d, f) for d, _, fs in os.walk(path) for f in fs
            if "__pycache__" not in d]
        for f in files:
            with open(f, "rb") as fh:
                out[os.path.relpath(f, root)] = hashlib.sha256(
                    fh.read()).hexdigest()
    return out


def copy_benchmark(dst) -> None:
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), dst)
    for path in BENCH["paths"]:
        shutil.copytree(os.path.join(ROOT, path), os.path.join(dst, path),
                        ignore=shutil.ignore_patterns("__pycache__"))


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    """The scratch tree with the fixture model added, and the hashes of
    what was there before."""
    root = str(tmp_path_factory.mktemp("bench_tree"))
    copy_benchmark(root)
    before = hashes(root)
    for name, rel in NEW_FILES.items():
        assert rel not in before
        shutil.copy(os.path.join(TOY, name), os.path.join(root, rel))
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({
        "name": "toy-attn", "source": "tests/cells/fixtures/toy_model",
        "file": NEW_FILES["toy-attn.json"], "reduced": [],
        "why": "a fixture: a model that is not the block"})
    for kind, name in CELLS.items():
        bench["workloads"].append({
            "name": name, "config": "toy-attn",
            "traffic": name.split(".", 1)[1], "chips": 1,
            "why": f"a fixture: a {kind} cell of a model brought as new "
                   "files"})
    for name, cell in (("toy_steps", CELLS["train_job"]),
                       ("toy_evictions", CELLS["open_loop"])):
        bench["per_layer"].append({
            "name": name, "unit": "count", "better": "higher",
            "source": "program_counter", "layer": "fixture",
            "moves": "setup_s", "workloads": [cell]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f, indent=1)
    return root, before


@pytest.fixture
def run(run_python, bench_env, monkeypatch):
    """``run.py`` of a tree; the program's package comes from the
    repository, which a scratch tree does not hold."""
    monkeypatch.setitem(bench_env, "PYTHONPATH", ROOT)

    def go(root, args):
        return run_python([os.path.join(root, "benchmark", "run.py")]
                          + args, cwd=root)

    return go


def last_json_line(stdout):
    lines = [ln for ln in stdout.strip().splitlines() if ln.strip()]
    return json.loads(lines[-1]) if lines and lines[-1].startswith(
        "{") else None


def test_the_model_is_added_by_new_files_only(tree):
    root, before = tree
    after = hashes(root)
    changed = sorted(f for f in before if after.get(f) != before[f])
    assert changed == ["BENCHMARK.json"]       # entries appended there
    assert sorted(set(after) - set(before)) == sorted(NEW_FILES.values())
    bench = json.load(open(os.path.join(root, "BENCHMARK.json")))
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        assert bench[group][:len(BENCH[group])] == BENCH[group]
    cfg = json.load(open(os.path.join(TOY, "toy-attn.json")))
    assert not {"n_embd", "n_inner", "n_head", "n_layer"} & set(cfg)


@pytest.mark.parametrize("kind", sorted(CELLS))
@pytest.mark.parametrize("trace", [0, 1])
def test_the_new_model_rehearses_to_a_correct_result(run, tree, kind,
                                                     trace):
    root, _ = tree
    out = run(root, ["--workload", CELLS[kind], "--seed", str(2**31 + 26),
                     "--seconds", "2", "--trace", str(trace),
                     "--rehearse"])
    assert out.returncode == 0, out.stderr[-3000:]
    res = last_json_line(out.stdout)
    assert res is not None and res["correct"] is True, out.stdout[-3000:]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert "bare attention layers" in out.stdout     # describe(cfg)
    # every number compared is in the line, last, beside its limit
    assert list(res)[-1] == "compared" and res["compared"]
    assert all(row["value"] <= row["limit"]
               for row in res["compared"].values())
    reader = "toy_steps" if kind == "train_job" else "toy_evictions"
    if trace:
        assert res["metrics"][reader]["value"] > 0
    else:
        assert res["metrics"] == {}


@pytest.mark.parametrize("model,says", [
    (None, 'states no "model"'),
    ("no_such_model", "no file models/no_such_model.py"),
    ("../common", "no file models/../common.py"),
    (7, 'states no "model"'),
], ids=["no-model", "missing-adapter", "not-a-name", "not-a-string"])
def test_a_configuration_without_its_model_is_refused(run, tmp_path, model,
                                                      says):
    root = str(tmp_path)
    copy_benchmark(root)
    conf = next(c for c in BENCH["configs"]
                if c["name"] == BENCH["workloads"][0]["config"])
    path = os.path.join(root, conf["file"])
    cfg = json.load(open(path))
    cfg.pop("model")
    if model is not None:
        cfg["model"] = model
    with open(path, "w") as f:
        json.dump(cfg, f)
    out = run(root, ["--workload", BENCH["workloads"][0]["name"], "--seed",
                     "1", "--seconds", "1", "--trace", "0", "--rehearse"])
    assert out.returncode == 2, out.stderr[-2000:]
    assert last_json_line(out.stdout) is None
    assert says in out.stderr


def test_an_adapter_that_lacks_a_call_is_refused(run, tree, tmp_path):
    """A served-only adapter cannot run a training cell."""
    root = str(tmp_path / "t")
    shutil.copytree(tree[0], root)
    path = os.path.join(root, NEW_FILES["attn_lm.py"])
    src = open(path).read().replace("def train_reference(",
                                    "def _no_train_reference(")
    with open(path, "w") as f:
        f.write(src)
    out = run(root, ["--workload", CELLS["train_job"], "--seed", "1",
                     "--seconds", "1", "--trace", "0", "--rehearse"])
    assert out.returncode == 2 and last_json_line(out.stdout) is None
    assert "lacks ['train_reference']" in out.stderr
