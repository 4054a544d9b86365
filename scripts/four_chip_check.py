"""The flagship paths on one four-chip host: did each run, and where did
the bytes land?

    python scripts/four_chip_check.py            # every stage, in order
    python scripts/four_chip_check.py tp train   # chosen stages
    python scripts/four_chip_check.py --tiny ... # rehearsal on a CPU host
                                                 # with 4+ virtual devices

A chip serves one process at a time, so this parent never touches jax:
it runs each stage as a child, ONE AFTER ANOTHER, and each child that
uses jax owns all the chips it can see until it exits. The ``fleet``
stage is the exception by design — it runs in this parent, which stays
off jax, and the CLI's own ``fleet_from_args`` gives each ``serve`` child
one chip. Every stage prints one JSON line (also appended to
``chiprun_out/four_chip.jsonl``) with per-device ``bytes_in_use``.

Stages: ``chips`` (one-chip children see one chip each, concurrently),
``tp`` (``DecodeEngine(tp=1|2|4)``, width-1024 flagship, paged),
``train`` (``ParallelTrainer`` dp=2 x tp=2 and dp=4, width-2048 flagship,
against one-chip steps), ``dryrun`` (``__graft_entry__.dryrun_multichip(4)``),
``fleet`` (``dl4j-tpu fleet --replicas 4`` wiring behind the router),
``local4`` (four one-chip engines in ONE process).
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
OUT = os.path.join(REPO, "chiprun_out", "four_chip.jsonl")
STAGES = ("chips", "tp", "train", "dryrun", "fleet", "local4")
TINY = "--tiny" in sys.argv  # rehearsal sizes, any backend


def size():
    import chip_smoke

    return chip_smoke.SIZES["tiny" if TINY else "full"]


def emit(stage: str, **fields) -> None:
    row = {"stage": stage, **fields}
    line = json.dumps(row, default=str)
    print(line, flush=True)
    os.makedirs(os.path.dirname(OUT), exist_ok=True)
    with open(OUT, "a") as f:
        f.write(line + "\n")


def device_bytes():
    import jax

    # the CPU backend (a --tiny rehearsal) keeps no memory statistics
    return {d.id: (d.memory_stats() or {}).get("bytes_in_use")
            for d in jax.devices()}


def device_tag():
    import jax

    d = jax.devices()
    return {"platform": d[0].platform, "kind": d[0].device_kind,
            "count": len(d), "jax": jax.__version__}


def require_tpu(n: int) -> None:
    import jax

    from deeplearning4j_tpu.util.compile_cache import (
        enable_compile_cache,
    )

    enable_compile_cache()
    if ((jax.default_backend() != "tpu" and not TINY)
            or len(jax.devices()) < n):
        raise SystemExit(
            f"needs {n} TPU chips; jax sees {len(jax.devices())} "
            f"{jax.default_backend()} device(s)")


def serve_net():
    import chip_smoke

    return chip_smoke.flagship(size()["vocab"], seed=11,
                               **size()["serve"])


def prompts(n=3):
    import numpy as np

    rng = np.random.default_rng(0)
    lens = size()["prompts"]
    return [rng.integers(0, size()["vocab"],
                         lens[i % len(lens)]).tolist()
            for i in range(n)]


# -- stage: one-chip children, all at once -----------------------------
CHILD = """
import jax, jax.numpy as jnp
d = jax.devices()
x = jnp.ones((256, 256)) @ jnp.ones((256, 256))
print("CHILD", [str(v) for v in d], d[0].device_kind, float(x[0, 0]))
"""


def stage_chips() -> None:
    from deeplearning4j_tpu.util.chips import chips_env, local_tpu_chips

    chips = local_tpu_chips()

    def wave(groups, env_of):
        """Start one child per group AT ONCE; what each one saw."""
        procs = {tuple(g): subprocess.Popen(
            [sys.executable, "-c", CHILD], stdout=subprocess.PIPE,
            stderr=subprocess.PIPE, text=True,
            env=dict(os.environ, **env_of(g))) for g in groups}
        rows, ok = {}, True
        for g, p in procs.items():
            try:
                out, err = p.communicate(timeout=180)
            except subprocess.TimeoutExpired:
                p.kill()
                out, err = p.communicate()
                out += " TIMEOUT"
            seen = [ln for ln in out.splitlines() if "CHILD" in ln]
            ok = ok and bool(seen) and seen[0].count("TPU_") == len(g)
            said = [ln for ln in err.splitlines()
                    if "hugepages" not in ln and "warnings.warn" not in ln]
            rows[",".join(map(str, g))] = (
                seen[0] if seen else f"rc={p.returncode} "
                                     + " | ".join(said[-4:])[-900:])
        return rows, ok

    singles, ok = wave([[c] for c in chips], chips_env)
    rows = {"one_chip_each": singles}
    if ok and len(chips) >= 4:
        # the sub-host groups a tp=2 fleet would want: they came up in
        # one of PR 21's two tries on the 2x2 host, so ``fleet`` gives a
        # tp>1 replica the whole host; kept to see whether that holds
        rows["two_chips_each"], _ = wave([chips[0:2], chips[2:4]],
                                         chips_env)
    if not ok:  # diagnostic: is the visible-chips variable alone enough?
        rows["visible_chips_only"], _ = wave(
            [[c] for c in chips],
            lambda g: {"TPU_VISIBLE_CHIPS": str(g[0])})
    emit("chips", local_tpu_chips=chips, ok=ok, children=rows)
    if not ok:
        raise RuntimeError("a one-chip child did not see exactly its chip")


# -- stage: tensor-parallel serving -------------------------------------
def stage_tp() -> None:
    import gc

    import chip_smoke
    from deeplearning4j_tpu.serving import DecodeEngine, Request

    require_tpu(4)
    net = serve_net()
    ids, rows = {}, {}
    for tp in (w for w in (1, 2, 4)
               if size()["serve"]["n_heads"] % w == 0):
        before = device_bytes()
        t0 = time.perf_counter()
        eng = DecodeEngine(net, n_slots=8, block_tokens=16,
                           prefix_cache_rows=8, tp=tp)
        rids = [eng.submit(Request(prompt=p,
                                   max_new_tokens=size()["n_new"]))
                for p in prompts(2)]
        done = eng.run()
        ids[tp] = [done[r].tokens for r in rids]
        rows[tp] = {
            "wall_s": round(time.perf_counter() - t0, 1),
            "bytes_before": before, "bytes_after": device_bytes(),
            "kv_shard_bytes": eng.kv_shard_bytes(),
            "pallas_calls": chip_smoke.decode_pallas_calls(eng),
            "ids_equal_tp1": ids[tp] == ids[1],
            # free-running ids part at the first bf16 near-tie; the
            # teacher-forced figures (argmax share, near-tie share,
            # worst ratio to the reference's top) cannot drift
            "free_running_match_vs_tp1": [
                sum(a == b for a, b in zip(x, y)) / len(y)
                for x, y in zip(ids[tp], ids[1])],
            "teacher_forced": [
                chip_smoke.forced_agreement(net, p, toks)
                for p, toks in zip(prompts(2), ids[tp])],
        }
        del eng, done
        gc.collect()
    emit("tp", device=device_tag(), tp=rows)


# -- stage: mesh training ------------------------------------------------
def stage_train() -> None:
    import numpy as np

    import chip_smoke
    from deeplearning4j_tpu.datasets.dataset import DataSet
    from deeplearning4j_tpu.datasets.markov import markov_lm_batches
    from deeplearning4j_tpu.parallel.data_parallel import ParallelTrainer
    from deeplearning4j_tpu.parallel.mesh import MeshSpec, make_mesh

    require_tpu(4)
    vocab, b, t, steps = (size()["vocab"], size()["batch"],
                          size()["seq"], 3)
    feats, labels, _ = markov_lm_batches(
        vocab, n_seq=steps * b, seq_len=t, seed=0, sample_seed=1)
    batches = [DataSet(feats[i * b:(i + 1) * b],
                       labels[i * b:(i + 1) * b]) for i in range(steps)]

    def net():
        return chip_smoke.flagship(vocab, lr=size()["lr"],
                                   warmup_steps=size()["warmup"],
                                   total_steps=1000,
                                   **size()["train"])

    rows = {}
    before = device_bytes()
    one = net()
    base = []
    for ds in batches:
        one.fit(ds)
        base.append(float(one.score_value))
    rows["one_chip"] = {"losses": base, "bytes_before": before,
                        "bytes_after": device_bytes()}
    del one
    chip_smoke.release_device_memory()
    for name, axes, kw in (
            ("dp2_tp2", {"dp": 2, "tp": 2}, {"tp_axis": "tp"}),
            ("dp4", {"dp": 4}, {})):
        before = device_bytes()
        t0 = time.perf_counter()
        trainer = ParallelTrainer(net(), make_mesh(MeshSpec(axes)),
                                  dp_axis="dp", **kw)
        losses = [float(trainer.fit(ds)) for ds in batches]
        delta = max(abs(a - c) for a, c in zip(losses, base))
        # the tolerance dryrun_multichip uses: 5e-3 x max(1, |loss|)
        tol = 5e-3 * max(1.0, max(abs(v) for v in base))
        rows[name] = {
            "losses": losses, "max_delta_vs_one_chip": delta,
            "tolerance": tol, "within": bool(delta <= tol),
            "finite": bool(np.all(np.isfinite(losses))),
            "wall_s": round(time.perf_counter() - t0, 1),
            "bytes_before": before, "bytes_after": device_bytes()}
        del trainer
        chip_smoke.release_device_memory()
    emit("train", device=device_tag(), train=rows)


def stage_dryrun() -> None:
    import __graft_entry__ as g

    require_tpu(4)
    before = device_bytes()
    t0 = time.perf_counter()
    g.dryrun_multichip(4)
    emit("dryrun", device=device_tag(), ok=True,
         wall_s=round(time.perf_counter() - t0, 1),
         bytes_before=before, bytes_after=device_bytes())


def stage_model(path: str) -> None:
    """Internal: write the serving flagship's zip, on the CPU platform."""
    import jax

    jax.config.update("jax_platforms", "cpu")
    from deeplearning4j_tpu.util.model_serializer import write_model

    write_model(serve_net(), path)


# -- stage: the subprocess fleet behind the router (parent off jax) ------
def stage_fleet() -> None:
    import jax

    from deeplearning4j_tpu.cli.driver import (
        build_parser,
        fleet_from_args,
    )
    from deeplearning4j_tpu.serving import GatewayClient, RouterClient
    from deeplearning4j_tpu.util.chips import local_tpu_chips
    from deeplearning4j_tpu.util.compile_cache import (
        enable_compile_cache,
    )

    enable_compile_cache()
    workdir = tempfile.mkdtemp(prefix="four_chip_")
    model = os.path.join(workdir, "flagship_lm.zip")
    # the model is written by a child on the CPU platform: this parent
    # must end the stage without ever having initialised a backend
    subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--child", "model",
         model] + ["--tiny"] * TINY, check=True)
    args = build_parser().parse_args([
        "fleet", "--model", model, "--replicas", "4", "--port", "0",
        "--block-tokens", "16", "--slots", "8"])
    t0 = time.perf_counter()
    seeds, router, controller = fleet_from_args(args)
    try:
        boot_s = round(time.perf_counter() - t0, 1)
        router.start()
        client = RouterClient(router.address, timeout_s=600.0)
        answers = []
        for p in prompts(8):
            res = client.generate(p, size()["n_new"])
            answers.append([len(p), res["finish_reason"],
                            len(res["tokens"])])
        health = client.healthz()
        emit("fleet", boot_s=boot_s,
             ready_lines=[r.ready_line for r in seeds],
             answers_len_finish_n=answers,
             replicas={s["replica_id"]: {
                 "state": s["state"], "routed": s["requests_routed"],
                 "finished": GatewayClient(s["address"]).healthz()[
                     "requests_finished"]}
                 for s in health["replicas"]},
             parent_backends=list(jax._src.xla_bridge._backends))
    finally:
        router.close()
        controller.shutdown_fleet()
    if local_tpu_chips():
        # asking for more replicas than chips must fail at boot, by name
        args.replicas = len(local_tpu_chips()) + 1
        try:
            extra, _, ctl = fleet_from_args(args)
            ctl.shutdown_fleet()
            refused = "NOT REFUSED"
        except ValueError as e:
            refused = str(e)
        emit("fleet_overask", refused=refused)


# -- stage: four engines in one process ----------------------------------
def stage_local4() -> None:
    from deeplearning4j_tpu.serving import DecodeEngine, Request

    require_tpu(4)
    net = serve_net()
    rows = []
    engines = []
    for i in range(4):
        before = device_bytes()
        eng = DecodeEngine(net, n_slots=8, block_tokens=16)
        rid = eng.submit(Request(prompt=prompts()[0],
                                 max_new_tokens=8))
        eng.run()
        engines.append(eng)
        leaf = next(iter(eng._pool.values()))["pk"]
        rows.append({"engine": i, "bytes_before": before,
                     "bytes_after": device_bytes(),
                     "pool_devices": sorted(
                         d.id for d in leaf.devices())})
    emit("local4", device=device_tag(), engines=rows)


def main(argv) -> int:
    argv = [a for a in argv if a != "--tiny"]
    if argv[:1] == ["--child"]:  # one jax-using stage, in its own process
        globals()[f"stage_{argv[1]}"](*argv[2:])
        return 0
    stages = argv or list(STAGES)
    failed = []
    for stage in stages:
        if stage not in STAGES:
            raise SystemExit(f"unknown stage {stage!r}: {STAGES}")
        t0 = time.perf_counter()
        if stage == "fleet" and "chips" in failed:
            print("stage fleet: skipped, the chips stage failed",
                  flush=True)
            failed.append(stage)
            continue
        if stage in ("chips", "fleet"):
            # these hand chips to children; they run HERE, off jax
            try:
                globals()[f"stage_{stage}"]()
                rc = 0
            except Exception as e:  # report and go on to the next stage
                import traceback

                traceback.print_exc()
                emit(stage, error=f"{type(e).__name__}: {e}")
                rc = 1
        else:
            rc = subprocess.run(
                [sys.executable, os.path.abspath(__file__), "--child",
                 stage] + ["--tiny"] * TINY).returncode
            if rc:
                emit(stage, error=f"child exited {rc}")
        print(f"stage {stage}: rc={rc} "
              f"{time.perf_counter() - t0:.1f}s", flush=True)
        if rc:
            failed.append(stage)
    print("failed stages:", failed or "none", flush=True)
    return 1 if failed else 0


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1:]))
