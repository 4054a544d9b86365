"""CLI driver: ``dl4j-tpu {train,test,predict,worker,serve}``.

Reference parity (deeplearning4j-cli, SURVEY.md §2.8 + §5.6 plane 4):
- ``train``  — build a net from a conf JSON (the model-config-is-the-
  wire-format property, §5.6) or a properties file, fit it on the input
  source, save a model zip (util/model_serializer single-zip format).
- ``test``   — load a model zip, evaluate on the input, print
  Evaluation.stats() (reference subcommands/Test.java).
- ``predict``— load a model zip, write argmax class predictions (or raw
  probabilities with --raw) as CSV (reference subcommands/Predict.java).
- ``serve``  — load an LM-shaped model zip and run the streaming HTTP
  serving gateway over it (serving/gateway.py, ISSUE 5): blocking +
  SSE generation, cancel, metrics, drain-to-snapshot on shutdown,
  restore-on-boot when the snapshot exists.

Input sources (reference FileScheme → RecordReader resolution):
- ``mnist`` / ``mnist-test`` / ``iris``  — built-in datasets
- ``path.csv``  — numeric CSV, last column = integer class label
- ``path.npz``  — numpy archive with ``features`` [+ ``labels``] arrays
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from typing import Optional, Tuple

import numpy as np


# ---------------------------------------------------------------------------
# input resolution (the FileScheme / RecordReader role)
# ---------------------------------------------------------------------------

def load_csv(path: str, num_classes: Optional[int] = None,
             label_column: int = -1) -> Tuple[np.ndarray, Optional[np.ndarray]]:
    """Numeric CSV → (features, one-hot labels). ``label_column=None``
    (via --no-labels) means feature-only input for predict."""
    from deeplearning4j_tpu.native_rt import read_csv

    data = read_csv(path)
    if label_column is None:
        return data.astype(np.float32), None
    labels_raw = data[:, label_column].astype(int)
    feats = np.delete(data, label_column % data.shape[1], axis=1)
    n_cls = num_classes or int(labels_raw.max()) + 1
    labels = np.eye(n_cls, dtype=np.float32)[labels_raw]
    return feats.astype(np.float32), labels


def resolve_input(uri: str, num_classes: Optional[int] = None,
                  with_labels: bool = True,
                  num_examples: Optional[int] = None):
    """URI/path → (features, labels|None)."""
    if uri == "iris":
        from deeplearning4j_tpu.datasets.iris import iris_dataset

        ds = iris_dataset()
        return np.asarray(ds.features), np.asarray(ds.labels)
    if uri in ("mnist", "mnist-test"):
        from deeplearning4j_tpu.datasets.mnist import mnist_dataset

        ds = mnist_dataset(train=(uri == "mnist"),
                           num_examples=num_examples)
        return np.asarray(ds.features), np.asarray(ds.labels)
    if not os.path.exists(uri):
        raise FileNotFoundError(f"input not found: {uri}")
    if uri.endswith(".npz"):
        arc = np.load(uri)
        feats = arc["features"].astype(np.float32)
        labels = arc["labels"].astype(np.float32) if (
            with_labels and "labels" in arc) else None
        return feats, labels
    return load_csv(uri, num_classes,
                    label_column=-1 if with_labels else None)


# ---------------------------------------------------------------------------
# conf resolution (JSON conf or java-style properties file)
# ---------------------------------------------------------------------------

def _conf_from_properties(path: str):
    """Minimal properties-file network spec (reference Train.java builds a
    conf from a properties file): keys ``layers`` (comma sizes, e.g.
    784,500,10), ``activation``, ``learning_rate``, ``updater``, ``seed``,
    ``iterations``, ``loss``."""
    props = {}
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line or line.startswith(("#", "!")):
                continue
            key, _, value = line.partition("=")
            props[key.strip()] = value.strip()

    from deeplearning4j_tpu.nn.conf import NeuralNetConfiguration, Updater
    from deeplearning4j_tpu.nn.conf import layers as L
    from deeplearning4j_tpu.ops.losses import LossFunction

    sizes = [int(s) for s in props["layers"].split(",")]
    if len(sizes) < 2:
        raise ValueError("properties 'layers' needs >=2 comma-separated sizes")
    builder = (NeuralNetConfiguration.Builder()
               .seed(int(props.get("seed", 12345)))
               .iterations(int(props.get("iterations", 1)))
               .learning_rate(float(props.get("learning_rate", 0.1)))
               .updater(Updater[props.get("updater", "SGD").upper()])
               .list())
    act = props.get("activation", "relu")
    loss = LossFunction[props.get("loss", "MCXENT").upper()]
    for i in range(len(sizes) - 2):
        builder.layer(i, L.DenseLayer(n_in=sizes[i], n_out=sizes[i + 1],
                                      activation=act))
    builder.layer(len(sizes) - 2,
                  L.OutputLayer(n_in=sizes[-2], n_out=sizes[-1],
                                activation="softmax", loss_function=loss))
    return builder.build()


def resolve_conf(path: str):
    from deeplearning4j_tpu.nn.conf.multi_layer import MultiLayerConfiguration

    if path.endswith((".properties", ".props")):
        return _conf_from_properties(path)
    with open(path) as f:
        return MultiLayerConfiguration.from_json(f.read())


# ---------------------------------------------------------------------------
# subcommands
# ---------------------------------------------------------------------------

def _cmd_train(args) -> int:
    from deeplearning4j_tpu.datasets.dataset import DataSet
    from deeplearning4j_tpu.datasets.iterator import ListDataSetIterator
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.optimize.listeners import ScoreIterationListener
    from deeplearning4j_tpu.util.model_serializer import write_model

    conf = resolve_conf(args.conf)
    net = MultiLayerNetwork(conf).init()
    if args.verbose:
        net.set_listeners(ScoreIterationListener(10))
    feats, labels = resolve_input(args.input, num_classes=args.num_classes,
                                  num_examples=args.num_examples)
    if labels is None:
        raise ValueError("training input must include labels")
    batch = args.batch_size or len(feats)
    sets = [DataSet(feats[i:i + batch], labels[i:i + batch])
            for i in range(0, len(feats), batch)]
    target = net
    if getattr(args, "pp_interleave", None) not in (None, 1) \
            and not args.mesh:
        raise SystemExit(
            "--pp-interleave requires --mesh with a pp axis")
    if args.mesh:
        from deeplearning4j_tpu.parallel.data_parallel import (
            ParallelTrainer,
        )
        from deeplearning4j_tpu.parallel.mesh import MeshSpec, make_mesh

        try:
            spec = {
                k.strip(): int(v)
                for k, v in (part.split("=")
                             for part in args.mesh.split(","))
            }
        except ValueError:
            raise SystemExit(
                f"--mesh {args.mesh!r}: expected 'axis=N[,axis=N...]'")
        if "dp" not in spec and "pp" not in spec:
            raise SystemExit(
                "--mesh must include a dp axis (the batch shards over "
                "it), e.g. 'dp=8' or 'dp=2,tp=4' — or a pp axis for "
                "pipeline stages ('pp=4', 'dp=2,pp=2,tp=2')")
        interleave = int(getattr(args, "pp_interleave", None) or 1)
        if interleave < 1:
            raise SystemExit(
                f"--pp-interleave {interleave}: must be >= 1")
        if interleave > 1 and "pp" not in spec:
            raise SystemExit(
                "--pp-interleave requires a pp axis in --mesh")
        pp_microbatches = 4
        if interleave > 1:
            # interleaved schedule is collision-free at M <= S
            pp_microbatches = min(pp_microbatches, spec["pp"])
            if pp_microbatches < 4:
                print(f"note: capped pipeline microbatches to "
                      f"pp={pp_microbatches} for the interleaved "
                      "schedule (changes microbatch size)")
        if "pp" in spec:
            bad = sorted(set(spec) & {"fsdp", "ep"})
            if bad:
                raise SystemExit(
                    f"--mesh axes {bad} do not compose with pp: the "
                    "pipeline trainers support pp [+ dp] (packed-row) "
                    "and dp x pp x sp x tp (homogeneous stages)")
        # Batches shard over dp (x fsdp) and split into pipeline
        # microbatches under pp: drop ragged tails so every device
        # gets an equal slice (standard data-parallel trimming).
        div = spec.get("dp", 1) * spec.get("fsdp", 1)
        if "pp" in spec:
            div *= pp_microbatches
        trimmed = [ds for ds in (
            DataSet(ds.features[:len(ds.features) // div * div],
                    ds.labels[:len(ds.features) // div * div])
            for ds in sets) if ds.features.shape[0] > 0]
        dropped = (sum(s.features.shape[0] for s in sets)
                   - sum(s.features.shape[0] for s in trimmed))
        if not trimmed:
            raise SystemExit(
                f"--mesh {args.mesh!r}: every batch is smaller than the "
                f"{div} data shards; raise --batch-size")
        if dropped:
            print(f"note: dropped {dropped} ragged-tail examples so "
                  f"batches divide the {div} data shards")
        sets = trimmed
        if "pp" in spec and ("tp" in spec or "sp" in spec
                             or interleave > 1):
            # dp x pp x sp x tp needs per-tensor layouts / sharded-time
            # ticks / stage-stacked chunks: the homogeneous trainer
            # (parallel/homogeneous_pipeline.py). sp additionally
            # requires the conf's attention beans to carry
            # ring_axis="sp" — the trainer checks and says so.
            from deeplearning4j_tpu.parallel.homogeneous_pipeline import (  # noqa: E501
                HomogeneousPipelineTrainer,
            )

            target = HomogeneousPipelineTrainer(
                net, make_mesh(MeshSpec(spec)),
                tp_axis="tp" if "tp" in spec else None,
                sp_axis="sp" if "sp" in spec else None,
                n_microbatches=pp_microbatches,
                interleave=interleave)
        elif "pp" in spec:
            from deeplearning4j_tpu.parallel.pipeline_parallel import (
                PipelineTrainer,
            )

            target = PipelineTrainer(
                net, make_mesh(MeshSpec(spec)),
                n_microbatches=pp_microbatches)
        else:
            target = ParallelTrainer(
                net, make_mesh(MeshSpec(spec)),
                tp_axis="tp" if "tp" in spec else None,
                fsdp_axis="fsdp" if "fsdp" in spec else None,
                ep_axis="ep" if "ep" in spec else None,
                sp_axis="sp" if "sp" in spec else None,
            )
    for _ in range(args.epochs):
        target.fit(ListDataSetIterator(sets))
    write_model(net, args.output)
    if target is not net:
        # Mesh-trained nets (sp confs especially) score through the
        # trainer's step — the last fit already computed it.
        score = float(net.score_value)
    else:
        score = net.score(DataSet(feats[:batch], labels[:batch]))
    print(f"saved model to {args.output} (final score {score:.6f})")
    return 0


def _cmd_test(args) -> int:
    from deeplearning4j_tpu.eval.evaluation import Evaluation
    from deeplearning4j_tpu.util.model_serializer import restore_model

    net = restore_model(args.model)
    feats, labels = resolve_input(args.input, num_classes=args.num_classes,
                                  num_examples=args.num_examples)
    if labels is None:
        raise ValueError("test input must include labels")
    ev = Evaluation()
    out = np.asarray(net.output(feats))
    ev.eval(labels, out)
    print(ev.stats())
    return 0


def _cmd_predict(args) -> int:
    from deeplearning4j_tpu.util.model_serializer import restore_model

    net = restore_model(args.model)
    feats, _ = resolve_input(args.input, with_labels=args.has_labels,
                             num_examples=args.num_examples)
    out = np.asarray(net.output(feats))
    if args.raw:
        rows = out
        fmt = "%.8f"
    else:
        rows = net.predict(feats).reshape(-1, 1)
        fmt = "%d"
    if args.output == "-":
        np.savetxt(sys.stdout, rows, fmt=fmt, delimiter=",")
    else:
        np.savetxt(args.output, rows, fmt=fmt, delimiter=",")
        print(f"wrote {rows.shape[0]} predictions to {args.output}")
    return 0


def _cmd_worker(args) -> int:
    """Long-running cluster worker: register with the coordinator,
    heartbeat, pull jobs, perform, repeat until the run is marked done
    (reference WorkerActor pull loop, MasterActor.java:106-139). The
    performer class is read from the coordinator's config registry
    (key ``worker.performer`` = "module:ClassName"), mirroring the
    reference's reflective WorkerPerformerFactory."""
    import importlib
    import threading
    import time as _time

    from deeplearning4j_tpu.scaleout.coordinator import CoordinatorClient

    addr = args.coordinator
    if "://" not in addr:
        addr = "http://" + addr
    tracker = CoordinatorClient(addr)
    worker_id = f"worker-{args.worker_id}"
    tracker.add_worker(worker_id)

    # Dedicated 1s heartbeat thread (WorkerActor.java:168): a
    # long-running perform() must NOT look like a dead worker, or its
    # in-flight job gets requeued and double-counted (same guard as
    # runner.py's in-process _Worker).
    stop = threading.Event()

    def _beat() -> None:
        while not stop.is_set():
            try:
                tracker.heartbeat(worker_id)
            except OSError:
                pass  # transient coordinator hiccup; keep beating
            stop.wait(1.0)

    beat_thread = threading.Thread(target=_beat, daemon=True)
    beat_thread.start()

    try:
        # Workers may start before the master registers the performer
        # (ClusterSetup launches them right after upload) — wait for it.
        spec = None
        while spec is None and not tracker.is_done():
            spec = tracker.get_config("worker.performer")
            if spec is None:
                _time.sleep(args.poll_interval)
        if spec is None:
            return 0
        mod_name, _, cls_name = str(spec).partition(":")
        performer = getattr(importlib.import_module(mod_name), cls_name)()

        seen_version = -1
        while not tracker.is_done():
            # Pull the latest aggregated state down before training
            # (the broadcast leg of the iterative-reduce round).
            version, value = tracker.poll_update(seen_version)
            if value is not None:
                performer.update(value)
            seen_version = version
            job = tracker.request_job(worker_id)
            if job is None:
                _time.sleep(args.poll_interval)
                continue
            result = performer.perform(job)
            if result is not None:
                tracker.submit_result(job.job_id, result)
            tracker.clear_job(job.job_id)
    finally:
        stop.set()
        beat_thread.join(timeout=2.0)
    return 0


#: --use-flash-paged CLI spelling -> DecodeEngine toggle value
FLASH_PAGED_MODES = {"auto": None, "on": True, "off": False,
                     "interpret": "interpret"}


def tenants_from_args(args):
    """Build the :class:`TenantRegistry` from repeated ``--tenant``
    specs (``name[:key=value]...`` — see ``TenantSpec.parse``), or
    None when no spec was given (tenancy stays off: the seed FIFO
    scheduler, zero per-tenant bookkeeping)."""
    specs = getattr(args, "tenant", None) or []
    if not specs:
        return None
    from deeplearning4j_tpu.serving import TenantRegistry, TenantSpec

    return TenantRegistry(tuple(TenantSpec.parse(s) for s in specs))


def gateway_from_args(args):
    """Build (or restore) the serving gateway the ``serve`` subcommand
    runs — factored out so tests can drive the exact CLI path without
    the serve-forever loop. Restore-on-boot: when ``--snapshot`` names
    an existing drain snapshot, the engine resumes that state (same
    ids) instead of starting fresh."""
    from deeplearning4j_tpu.serving import DecodeEngine, ServingGateway
    from deeplearning4j_tpu.util.model_serializer import restore_model

    tenants = tenants_from_args(args)

    def net():
        # the engine adopts this net and only serves it: no moments
        return restore_model(args.model, updater_state=False)

    def engine():
        return DecodeEngine(
            net(), n_slots=args.slots,
            decode_chunk=args.decode_chunk,
            prefix_cache_rows=args.prefix_cache_rows,
            prefill_chunk=args.prefill_chunk,
            admission_policy=args.admission_policy,
            max_queue=args.max_queue,
            paranoid=args.paranoid,
            spec_draft_len=args.spec_draft_len,
            block_tokens=args.block_tokens,
            kv_blocks=args.kv_blocks,
            tp=getattr(args, "tp", 1),
            use_flash_paged=FLASH_PAGED_MODES[
                getattr(args, "use_flash_paged", "auto")],
            tenants=tenants,
            async_rounds=getattr(args, "async_rounds", False),
            fused_rounds=getattr(args, "fused_rounds", 0),
            kv_host_tier_bytes=getattr(args, "kv_host_tier_bytes",
                                       0),
            kv_disk_tier_path=getattr(args, "kv_disk_tier_path",
                                      None),
            kv_disk_tier_bytes=getattr(args, "kv_disk_tier_bytes",
                                       None))

    return ServingGateway.boot(
        engine, snapshot_path=args.snapshot,
        net_factory=net,
        # the HOST wins layout knobs on restore: the snapshot wire
        # format is tp-invariant, so a drain taken at one width
        # restores at whatever this host can shard. The tenant
        # registry likewise: this host's --tenant specs override the
        # snapshot's (None = keep the snapshot's registry).
        restore_kwargs={
            "tp": getattr(args, "tp", 1),
            "use_flash_paged": FLASH_PAGED_MODES[
                getattr(args, "use_flash_paged", "auto")],
            "tenants": tenants},
        host=args.host, port=args.port,
        replica_id=getattr(args, "replica_id", None),
        role=getattr(args, "role", "any"))


def router_from_args(args):
    """Build the multi-replica serving router the ``route``
    subcommand runs — factored out so tests can drive the exact CLI
    path without the serve-forever loop."""
    from deeplearning4j_tpu.serving import ServingRouter

    replicas = [a.strip() for a in args.replicas.split(",")
                if a.strip()]
    return ServingRouter(
        replicas, host=args.host, port=args.port,
        affinity_block_tokens=args.affinity_block_tokens,
        health_interval_s=args.health_interval,
        failure_threshold=args.failure_threshold,
        probe_interval_s=args.probe_interval,
        max_replays=args.max_replays,
        tenants=tenants_from_args(args),
        journal_path=getattr(args, "journal_path", None),
        fsync=getattr(args, "fsync", "batched"))


def _cmd_route(args) -> int:
    import time as _time

    router = router_from_args(args).start()
    wal = ""
    if getattr(args, "journal_path", None):
        wal = (f", WAL {args.journal_path} "
               f"(fsync={args.fsync}, recovered "
               f"{router.stats['recovered_entries']} entries, "
               f"{router.stats['recovered_open']} open)")
    print(f"routing on {router.address} over "
          f"{len(router._replicas)} replicas "
          f"(POST /v1/generate, GET /v1/healthz, GET /v1/metrics, "
          f"POST /v1/replicas/drain){wal}", flush=True)
    try:
        while True:
            _time.sleep(0.5)
    except KeyboardInterrupt:
        print("stopping router (replicas keep serving)...")
    finally:
        router.close()
    return 0


def _serve_child_argv(args, port: int, replica_id: str):
    """Child argv for one fleet replica: this same CLI's ``serve``
    subcommand on an ephemeral port with a stable replica id."""
    argv = [sys.executable, "-m", "deeplearning4j_tpu.cli", "serve",
            "--model", args.model,
            "--host", "127.0.0.1", "--port", str(port),
            "--replica-id", replica_id,
            "--slots", str(args.slots),
            "--decode-chunk", str(args.decode_chunk),
            "--prefix-cache-rows", str(args.prefix_cache_rows),
            "--prefill-chunk", str(args.prefill_chunk),
            "--admission-policy", args.admission_policy]
    argv += ["--block-tokens", str(args.block_tokens)]
    if args.kv_blocks is not None:
        argv += ["--kv-blocks", str(args.kv_blocks)]
    if getattr(args, "kv_host_tier_bytes", 0):
        argv += ["--kv-host-tier-bytes",
                 str(args.kv_host_tier_bytes)]
    if getattr(args, "kv_disk_tier_path", None):
        # per-replica subdirectory: ring files are engine-local
        argv += ["--kv-disk-tier-path",
                 os.path.join(args.kv_disk_tier_path,
                              replica_id)]
        if getattr(args, "kv_disk_tier_bytes", None) is not None:
            argv += ["--kv-disk-tier-bytes",
                     str(args.kv_disk_tier_bytes)]
    if getattr(args, "tp", 1) != 1:
        argv += ["--tp", str(args.tp)]
    if getattr(args, "use_flash_paged", "auto") != "auto":
        argv += ["--use-flash-paged", args.use_flash_paged]
    if getattr(args, "async_rounds", False):
        argv += ["--async-rounds"]
    if getattr(args, "fused_rounds", 0):
        argv += ["--fused-rounds", str(args.fused_rounds)]
    for spec in getattr(args, "tenant", None) or []:
        # every replica enforces the same tenant table the router
        # rate-limits by — quotas and priorities are fleet-wide
        argv += ["--tenant", spec]
    return argv


def fleet_from_args(args):
    """Build the elastic fleet the ``fleet`` subcommand runs — N
    subprocess ``serve`` replicas, the failure-tolerant router over
    them, and the SLO-driven :class:`FleetController` that breathes
    the fleet (spawns replicas on pressure/TTFT-SLO violations,
    drains idle ones, `controller.rolling_upgrade()` for
    zero-downtime model upgrades). Factored out so tests can drive
    the exact CLI wiring without the serve-forever loop. Returns
    ``(replicas, router, controller)`` — none of them started."""
    from deeplearning4j_tpu.serving import (
        FleetController,
        ServingRouter,
    )
    from deeplearning4j_tpu.serving.replica_proc import (
        ReplicaProcess,
        free_port,
    )
    from deeplearning4j_tpu.util.chips import chips_env, local_tpu_chips

    # One process for each chip: a TPU chip serves one process at a
    # time, so every child is started seeing only the chips it owns
    # (this parent never initialises a jax backend and so holds none).
    # On a host without a TPU the children inherit the environment.
    chips = local_tpu_chips()
    per = getattr(args, "tp", 1)
    # tp=1: one chip each. A tp>1 replica takes the WHOLE host with the
    # environment unchanged: two-chip sub-host groups came up in only
    # one of two tries on the 2x2 host (CHANGES.md PR 21), so they are
    # not relied on
    groups = ([[c] for c in chips] if per == 1
              else [chips] if per <= len(chips) else [])
    if chips and args.replicas > len(groups):
        raise ValueError(
            f"--replicas {args.replicas} at --tp {per} does not fit "
            f"this host's {len(chips)} TPU chip(s) {chips}: a chip "
            "serves one process, a tp=1 replica takes one chip and a "
            f"tp>1 replica the whole host (room for {len(groups)})")
    max_replicas = args.max_replicas
    if chips and max_replicas > len(groups):
        max_replicas = len(groups)
        print(f"--max-replicas {args.max_replicas} capped at "
              f"{max_replicas}: {len(chips)} TPU chip(s), {per} per "
              "replica", flush=True)
    leased = {}  # index into groups -> the ReplicaProcess holding it

    def spawn(replica_id: str):
        env = slot = None
        if chips:
            slot = next((i for i in range(len(groups))
                         if i not in leased or not leased[i].alive),
                        None)
            if slot is None:
                raise RuntimeError(
                    f"no free TPU chip for replica {replica_id}: "
                    f"all of {groups} are held by live replicas")
            if per == 1:
                env = dict(os.environ, **chips_env(groups[slot]))
        port = free_port()
        proc = ReplicaProcess(
            _serve_child_argv(args, port, replica_id),
            replica_id=replica_id, port=port,
            ready_pattern="serving on", env=env)
        if slot is not None:
            leased[slot] = proc
        return proc

    def factory(replica_id: str):
        proc = spawn(replica_id)
        try:
            proc.wait_ready(timeout_s=300.0)
        except BaseException:
            proc.shutdown()  # a wedged boot must not leak the child
            raise
        return proc

    # spawn all seeds first so their XLA inits overlap, then wait;
    # ANY failure before the caller owns the fleet (a wedged boot, a
    # bad router port, rejected controller bounds) must reap every
    # child already spawned — orphaned serve subprocesses outlive
    # the CLI
    seeds = [spawn(f"fleet-{i}") for i in range(args.replicas)]
    try:
        for r in seeds:
            r.wait_ready(timeout_s=300.0)
        router = ServingRouter(
            [r.address for r in seeds], host=args.host,
            port=args.port,
            affinity_block_tokens=args.affinity_block_tokens,
            tenants=tenants_from_args(args))
        controller = FleetController(
            router, replica_factory=factory,
            min_replicas=args.min_replicas,
            max_replicas=max_replicas,
            eval_interval_s=args.eval_interval,
            ttft_p99_slo_s=args.ttft_slo,
            pressure_high=args.pressure_high,
            pressure_low=args.pressure_low,
            cooldown_s=args.cooldown, id_prefix="fleet-auto")
    except BaseException:
        from deeplearning4j_tpu.serving.replica_proc import (
            shutdown_all,
        )

        shutdown_all(seeds)
        raise
    for r in seeds:
        controller.adopt(r)
    return seeds, router, controller


def _cmd_fleet(args) -> int:
    import time as _time

    print(f"booting {args.replicas} replica(s)...", flush=True)
    seeds, router, controller = fleet_from_args(args)
    try:
        router.start()
        controller.start()
        print(f"fleet routing on {router.address} over "
              f"{len(seeds)} replicas, controller live "
              f"(min {controller.min_replicas} / max "
              f"{controller.max_replicas}, TTFT-p99 SLO "
              f"{controller.ttft_p99_slo_s}); scale timeline at "
              f"GET /v1/trace as fleet.scale spans", flush=True)
        try:
            while True:
                _time.sleep(0.5)
        except KeyboardInterrupt:
            print("stopping fleet (drain + reap)...")
    finally:
        controller.close()
        router.close()
        # the seeds were adopted, so shutdown_fleet reaps everything
        controller.shutdown_fleet()
    return 0


def _cmd_client(args) -> int:
    """One generation against a running gateway or router
    (``dl4j-tpu client``): the smallest way to exercise a serving
    deployment — including its tenancy surface (``--tenant`` /
    ``--priority`` ride the request; a 429 prints that tenant's own
    Retry-After instead of dying with a traceback)."""
    from deeplearning4j_tpu.serving import GatewayClient, GatewayError

    try:
        prompt = [int(t) for t in args.prompt.split(",") if t.strip()]
    except ValueError:
        raise SystemExit(
            f"--prompt {args.prompt!r}: expected comma-separated "
            "token ids, e.g. '1,4,7,2'")
    if not prompt:
        raise SystemExit("--prompt must carry at least one token id")
    kwargs = {}
    if args.tenant is not None:
        kwargs["tenant"] = args.tenant
    if args.priority is not None:
        kwargs["priority"] = args.priority
    if args.temperature:
        kwargs["temperature"] = args.temperature
    client = GatewayClient(args.address, timeout_s=args.timeout)
    try:
        if args.stream:
            stream = client.stream(prompt, args.max_new_tokens,
                                   **kwargs)
            tokens = []
            for delta in stream:
                tokens.extend(delta)
                print(f"delta: {delta}", flush=True)
            result = stream.result or {}
        else:
            result = client.generate(prompt, args.max_new_tokens,
                                     **kwargs)
            tokens = result.get("tokens", [])
    except GatewayError as e:
        if e.status == 429:
            tenant = e.payload.get("tenant")
            print(f"429 throttled"
                  + (f" (tenant {tenant})" if tenant else "")
                  + f": retry after {e.retry_after_s}s "
                  f"({e.payload.get('error')})")
            return 2
        raise SystemExit(f"request failed: {e}")
    print(f"tokens: {tokens}")
    print(f"finish_reason: {result.get('finish_reason')}"
          + (f" tenant: {result['tenant']}"
             if result.get("tenant") else ""))
    return 0 if result.get("finish_reason") in ("length", "eos") \
        else 1


def _cmd_serve(args) -> int:
    import time as _time

    import jax

    gw = gateway_from_args(args).start()
    tp_ctx = gw.engine.tp_ctx
    devices = (list(tp_ctx.mesh.devices.flat) if tp_ctx
               else jax.devices()[:1])
    # flush: a fleet parent reads this line through a pipe as the
    # boot handshake (ReplicaProcess ready_pattern) — block-buffered
    # stdout would hold it until the buffer filled
    stats = devices[0].memory_stats() or {}
    print(f"serving on {gw.address} "
          f"(POST /v1/generate, GET /v1/healthz, GET /v1/metrics) "
          f"device {','.join(str(d) for d in devices)} "
          f"[{devices[0].device_kind}] bytes_in_use="
          f"{stats.get('bytes_in_use')}", flush=True)
    try:
        while gw.failure is None:
            _time.sleep(0.5)
    except KeyboardInterrupt:
        print("draining...")
    if gw.failure is not None:
        # the stepping thread died (gateway._fail printed the
        # traceback): nothing left to drain, and exit 0 would tell a
        # supervisor the replica shut down cleanly
        gw.close()
        print(f"serve failed: {gw.failure}", file=sys.stderr)
        return 1
    summary = gw.drain(timeout_s=args.drain_timeout)
    gw.close()
    if summary["snapshot"]:
        print(f"snapshot ({summary['carried']} in-flight "
              f"requests) -> {summary['snapshot']}")
    return 0


# ---------------------------------------------------------------------------
# parser
# ---------------------------------------------------------------------------

def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="dl4j-tpu",
        description="Train, test, and predict with deeplearning4j_tpu "
                    "models (reference: dl4j CLI train/test/predict).")
    sub = p.add_subparsers(dest="command", required=True)

    def common(sp, model_in: bool):
        sp.add_argument("--input", required=True,
                        help="data source: mnist | mnist-test | iris | "
                             "path.csv | path.npz")
        sp.add_argument("--num-classes", type=int, default=None)
        sp.add_argument("--num-examples", type=int, default=None,
                        help="cap examples loaded from built-in datasets")
        if model_in:
            sp.add_argument("--model", required=True,
                            help="model zip from train")

    t = sub.add_parser("train", help="fit a network and save a model zip")
    common(t, model_in=False)
    t.add_argument("--conf", required=True,
                   help="MultiLayerConfiguration JSON or .properties file")
    t.add_argument("--output", required=True, help="model zip path")
    t.add_argument("--epochs", type=int, default=1)
    t.add_argument("--batch-size", type=int, default=None)
    t.add_argument("--verbose", action="store_true")
    t.add_argument(
        "--mesh", default=None,
        help="train over a device mesh, e.g. 'dp=8', 'dp=2,tp=4', "
             "'pp=4' (GPipe stages), or 'dp=2,pp=2,tp=2' / "
             "'pp=2,sp=2,tp=2' (homogeneous-stage pipeline; sp needs "
             "conf attention beans built with ring_axis='sp'): "
             "axis sizes multiply to the device count; axes named "
             "tp/fsdp/ep/sp engage the corresponding ParallelTrainer "
             "sharding (dp shards the batch)")
    t.add_argument(
        "--pp-interleave", type=int, default=1,
        help="virtual-stage interleave depth for pipeline meshes "
             "(homogeneous-stage models only; ~V x smaller pipeline "
             "bubble at the same microbatch count)")
    t.set_defaults(fn=_cmd_train)

    e = sub.add_parser("test", help="evaluate a saved model")
    common(e, model_in=True)
    e.set_defaults(fn=_cmd_test)

    r = sub.add_parser("predict", help="write predictions for an input")
    common(r, model_in=True)
    r.add_argument("--output", default="-",
                   help="CSV path or '-' for stdout")
    r.add_argument("--raw", action="store_true",
                   help="write class probabilities instead of argmax")
    r.add_argument("--has-labels", action="store_true",
                   help="input CSV has a trailing label column to strip")
    r.set_defaults(fn=_cmd_predict)

    w = sub.add_parser(
        "worker",
        help="run a cluster worker against a coordinator control plane")
    w.add_argument("--coordinator", required=True,
                   help="coordinator address host:port")
    w.add_argument("--worker-id", type=int, default=0)
    w.add_argument("--poll-interval", type=float, default=0.5)
    w.set_defaults(fn=_cmd_worker)

    s = sub.add_parser(
        "serve",
        help="serve an LM model zip over the streaming HTTP gateway")
    s.add_argument("--model", required=True,
                   help="LM-shaped model zip from train")
    s.add_argument("--host", default="127.0.0.1")
    s.add_argument("--port", type=int, default=8421)
    s.add_argument("--slots", type=int, default=8,
                   help="concurrent KV-cache slots (batch width)")
    s.add_argument("--decode-chunk", type=int, default=8)
    s.add_argument("--prefix-cache-rows", type=int, default=0,
                   help="radix prefix cache rows (0 = off)")
    s.add_argument("--prefill-chunk", type=int, default=0,
                   help="chunked-admission width (0 = blocking)")
    s.add_argument("--admission-policy", default="ttft",
                   choices=("ttft", "decode"))
    s.add_argument("--max-queue", type=int, default=None,
                   help="bounded admission queue (full => HTTP 429)")
    s.add_argument("--paranoid", action="store_true",
                   help="per-round health check + quarantine")
    s.add_argument("--spec-draft-len", type=int, default=0,
                   help="speculative n-gram draft length K (0 = off)")
    s.add_argument("--block-tokens", type=int, default=16,
                   help="tokens per block of the KV pool that slots "
                        "and the prefix trie share (pow2)")
    s.add_argument("--kv-blocks", type=int, default=None,
                   help="block-pool size (default: a window for "
                        "every slot and every trie entry)")
    s.add_argument("--tp", type=int, default=1,
                   help="tensor-parallel shards: decode/verify/chunk "
                        "run as shard_map programs over attention "
                        "heads, per-shard KV bytes = total/TP "
                        "(1 = single-chip)")
    s.add_argument("--use-flash-paged", default="auto",
                   choices=("auto", "on", "off", "interpret"),
                   help="pallas paged-attention decode kernel: auto "
                        "= kernel on TPU / XLA gather elsewhere, on "
                        "= force kernel (TPU), off = gather always, "
                        "interpret = kernel via the pallas "
                        "interpreter (CPU parity testing)")
    s.add_argument("--role", default="any",
                   choices=("any", "prefill", "decode"),
                   help="disaggregation role (ISSUE 14): prefill = "
                        "admission-heavy tier + warm-KV donor, "
                        "decode = long-decode tier that pulls KV on "
                        "miss, any = role-blind")
    s.add_argument("--async-rounds", action="store_true",
                   help="double-buffer decode rounds (ISSUE 14): "
                        "round N's token fetch defers to the next "
                        "step so the inter-round host gap overlaps "
                        "device compute (ids stay bit-identical)")
    s.add_argument("--fused-rounds", type=int, default=0,
                   metavar="K",
                   help="fuse up to K decision-free decode rounds "
                        "into one on-device scan (ISSUE 16; 0 = "
                        "off). Greedy ids stay bit-identical to "
                        "stepped mode; SSE deltas arrive in chunks "
                        "of up to K * decode_chunk tokens")
    s.add_argument("--kv-host-tier-bytes", type=int, default=0,
                   help="host-DRAM spill-tier budget in bytes "
                        "(ISSUE 17): trie victims evicted under HBM "
                        "pressure pack into a host LRU this large "
                        "and reload via the jitted KV import instead "
                        "of recomputing (0 = off; needs "
                        "--prefix-cache-rows > 0)")
    s.add_argument("--kv-disk-tier-path", default=None,
                   help="disk-ring directory for spill-tier "
                        "overflow (ISSUE 17): payloads past the "
                        "host budget demote to files here instead "
                        "of dropping (unset = host-only tier)")
    s.add_argument("--kv-disk-tier-bytes", type=int, default=None,
                   help="byte cap for the disk ring (oldest files "
                        "dropped past it; unset = unbounded)")
    s.add_argument("--snapshot", default=None,
                   help="drain-snapshot path: written on shutdown, "
                        "restored on boot when present")
    s.add_argument("--drain-timeout", type=float, default=30.0,
                   help="seconds to settle in-flight work on shutdown")
    s.add_argument("--replica-id", default=None,
                   help="stable replica identity for a router tier "
                        "(affinity keys hash against it; defaults "
                        "to host:port)")
    s.add_argument("--tenant", action="append", default=None,
                   metavar="SPEC",
                   help="tenant service class, repeatable "
                        "(ISSUE 13): name[:key=value]... with keys "
                        "priority/weight/slots/queue/rps/burst, "
                        "e.g. premium:priority=2:weight=4:slots=4; "
                        "any --tenant enables the weighted-fair "
                        "scheduler (none = the seed FIFO engine)")
    s.set_defaults(fn=_cmd_serve)

    fl = sub.add_parser(
        "fleet",
        help="run an ELASTIC fleet: N serve replicas + router + "
             "SLO-driven autoscaling controller (ISSUE 11)")
    fl.add_argument("--model", required=True,
                    help="LM-shaped model zip every replica serves")
    fl.add_argument("--host", default="127.0.0.1")
    fl.add_argument("--port", type=int, default=8420,
                    help="the router's port (replicas take "
                         "ephemeral ports)")
    fl.add_argument("--replicas", type=int, default=2,
                    help="initial fleet size")
    fl.add_argument("--min-replicas", type=int, default=1)
    fl.add_argument("--max-replicas", type=int, default=4)
    fl.add_argument("--ttft-slo", type=float, default=None,
                    help="TTFT p99 SLO in seconds (windowed over "
                         "the federated scrape); unset = "
                         "pressure-only control")
    fl.add_argument("--pressure-high", type=float, default=2.0,
                    help="in-flight-per-slot above this = breach")
    fl.add_argument("--pressure-low", type=float, default=0.25,
                    help="in-flight-per-slot below this = idle "
                         "(the hysteresis band between the two "
                         "holds)")
    fl.add_argument("--eval-interval", type=float, default=0.5,
                    help="control-loop period in seconds")
    fl.add_argument("--cooldown", type=float, default=5.0,
                    help="seconds after any scale event before the "
                         "next may fire")
    fl.add_argument("--affinity-block-tokens", type=int, default=16)
    fl.add_argument("--slots", type=int, default=8)
    fl.add_argument("--decode-chunk", type=int, default=8)
    fl.add_argument("--prefix-cache-rows", type=int, default=8)
    fl.add_argument("--prefill-chunk", type=int, default=0)
    fl.add_argument("--admission-policy", default="ttft",
                    choices=("ttft", "decode"))
    fl.add_argument("--block-tokens", type=int, default=16)
    fl.add_argument("--kv-blocks", type=int, default=None)
    fl.add_argument("--kv-host-tier-bytes", type=int, default=0,
                    help="host-DRAM spill-tier budget per replica "
                         "(ISSUE 17; 0 = off)")
    fl.add_argument("--kv-disk-tier-path", default=None,
                    help="disk-ring base directory for spill-tier "
                         "overflow (each replica rings a "
                         "subdirectory)")
    fl.add_argument("--kv-disk-tier-bytes", type=int, default=None,
                    help="per-replica disk-ring byte cap")
    fl.add_argument("--async-rounds", action="store_true",
                    help="double-buffered decode rounds on every "
                         "replica (ISSUE 14)")
    fl.add_argument("--fused-rounds", type=int, default=0,
                    metavar="K",
                    help="fused multi-round decode scans on every "
                         "replica (ISSUE 16; 0 = off)")
    fl.add_argument("--tp", type=int, default=1,
                    help="tensor-parallel shards per replica (every "
                         "replica serves at the same width)")
    fl.add_argument("--use-flash-paged", default="auto",
                    choices=("auto", "on", "off", "interpret"))
    fl.add_argument("--tenant", action="append", default=None,
                    metavar="SPEC",
                    help="tenant service class, repeatable "
                         "(ISSUE 13): name[:key=value]... — armed on "
                         "EVERY replica's scheduler AND the router's "
                         "rate limiter (rps/burst keys)")
    fl.set_defaults(fn=_cmd_fleet)

    rt = sub.add_parser(
        "route",
        help="front N serve replicas with the failure-tolerant "
             "prefix-aware router")
    rt.add_argument("--replicas", required=True,
                    help="comma-separated replica addresses "
                         "(host:port of running `serve` gateways — "
                         "all must serve the SAME model/seed)")
    rt.add_argument("--host", default="127.0.0.1")
    rt.add_argument("--port", type=int, default=8420)
    rt.add_argument("--affinity-block-tokens", type=int, default=16,
                    help="prefix-affinity hash granularity (match "
                         "the replicas' --block-tokens under paged "
                         "KV)")
    rt.add_argument("--health-interval", type=float, default=0.25,
                    help="seconds between /v1/healthz scrapes")
    rt.add_argument("--failure-threshold", type=int, default=3,
                    help="consecutive failures before a replica's "
                         "circuit breaker opens")
    rt.add_argument("--probe-interval", type=float, default=1.0,
                    help="half-open probe period for dead replicas")
    rt.add_argument("--max-replays", type=int, default=3,
                    help="replay budget per request across replica "
                         "deaths")
    rt.add_argument("--tenant", action="append", default=None,
                    metavar="SPEC",
                    help="tenant service class, repeatable "
                         "(ISSUE 13): arms the router's per-tenant "
                         "token-bucket rate limits (rps/burst keys)")
    rt.add_argument("--journal-path", default=None,
                    help="crash-safe write-ahead journal (ISSUE 15): "
                         "a router restarted against the same file "
                         "replays open streams on live replicas, "
                         "restores tenant buckets + warm-KV "
                         "beliefs, and serves client resumes "
                         "(Last-Event-ID) from the recovered "
                         "breadcrumbs")
    rt.add_argument("--fsync", default="batched",
                    choices=("per_record", "batched", "off"),
                    help="WAL durability policy: per_record "
                         "(power-loss safe, per-record latency), "
                         "batched (default: SIGKILL-safe, fsync "
                         "coalesced), off (flush-only)")
    rt.set_defaults(fn=_cmd_route)

    cl = sub.add_parser(
        "client",
        help="send one generation to a running serve/route/fleet "
             "deployment (ISSUE 13: --tenant/--priority ride the "
             "request)")
    cl.add_argument("--address", required=True,
                    help="gateway or router address host:port")
    cl.add_argument("--prompt", required=True,
                    help="comma-separated token ids, e.g. '1,4,7,2'")
    cl.add_argument("--max-new-tokens", type=int, default=16)
    cl.add_argument("--tenant", default=None,
                    help="tenant to bill the request against "
                         "(quotas, rate limits, priority class; "
                         "default = the unlabeled 'default' class)")
    cl.add_argument("--priority", type=int, default=None,
                    help="per-request priority override — clamped "
                         "to the tenant's class (you can lower your "
                         "own batch traffic, never self-boost)")
    cl.add_argument("--temperature", type=float, default=0.0)
    cl.add_argument("--stream", action="store_true",
                    help="SSE streaming instead of one blocking call")
    cl.add_argument("--timeout", type=float, default=120.0)
    cl.set_defaults(fn=_cmd_client)
    return p


def main(argv=None) -> int:
    from deeplearning4j_tpu.util.compile_cache import (
        enable_compile_cache,
    )

    args = build_parser().parse_args(argv)
    # sets a config value, initialises no backend: the fleet parent
    # stays off the chip, and its serve children share the directory
    enable_compile_cache()
    return args.fn(args)


if __name__ == "__main__":
    raise SystemExit(main())
