"""Two-process jax.distributed integration.

The reference tests its cluster code by running the REAL protocol
in-process (BaseSparkTest.java:44-60 spins local[*] Spark in the JVM;
SURVEY.md §4); the equivalent here is two actual OS processes gang-
bootstrapped through ``jax.distributed`` on the CPU backend, each owning
one XLA device, jointly forming a 2-device dp mesh: initialize_multihost,
a ParallelTrainer synchronous step with host-local feeds, the
host_local_to_global/sync_hosts helpers, and the MultiHostContext
heartbeat path against a live CoordinatorServer.
"""

import json
import os
import socket
import subprocess
import sys

import numpy as np
import pytest

from deeplearning4j_tpu.scaleout.coordinator import (
    CoordinatorClient,
    CoordinatorServer,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_WORKER = """
import os, sys, json
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
import jax
jax.config.update("jax_platforms", "cpu")
sys.path.insert(0, "@REPO@")
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from deeplearning4j_tpu.parallel.multihost import (
    MultiHostContext,
    host_local_to_global,
    initialize_multihost,
    sync_hosts,
)

pid = int(sys.argv[1])
jd_port = sys.argv[2]
coord_url = sys.argv[3]

got_pid = initialize_multihost(
    coordinator_address="127.0.0.1:" + jd_port,
    num_processes=2,
    process_id=pid,
)
assert got_pid == pid == jax.process_index(), (got_pid, pid)
assert jax.process_count() == 2
assert jax.device_count() == 2 and jax.local_device_count() == 1
# idempotent re-entry
assert initialize_multihost() == pid

ctx = MultiHostContext(coordinator_url=coord_url, heartbeat_interval=0.2)
assert ctx.is_chief() == (pid == 0)

from deeplearning4j_tpu.datasets.dataset import DataSet
from deeplearning4j_tpu.models.zoo import mlp
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.parallel.data_parallel import ParallelTrainer

mesh = Mesh(np.array(jax.devices()).reshape(2), ("dp",))
net = MultiLayerNetwork(mlp((8, 6, 2), lr=0.1, seed=7)).init()
trainer = ParallelTrainer(net, mesh)

rng = np.random.default_rng(0)          # same stream on both hosts
x_full = rng.normal(size=(8, 8)).astype(np.float32)
y_full = np.eye(2, dtype=np.float32)[rng.integers(0, 2, 8)]
lo, hi = pid * 4, (pid + 1) * 4         # my host-local slice
scores = []
for step in range(3):
    scores.append(trainer.fit(DataSet(x_full[lo:hi], y_full[lo:hi])))
sync_hosts("after-train")

# host_local_to_global/global_to_host_local round trip
from deeplearning4j_tpu.parallel.multihost import global_to_host_local
g = host_local_to_global(x_full[lo:hi], mesh, P("dp"))
assert g.shape == (8, 8)                # global batch assembled
back = global_to_host_local(g, mesh, P("dp"))
np.testing.assert_allclose(back, x_full[lo:hi])

checksum = float(
    sum(float(np.abs(np.asarray(v)).sum())
        for k in net.params for v in net.params[k].values()))
import time as _t
_t.sleep(0.6)                            # let heartbeats land
# Membership + heartbeat visible on the control plane while alive.
hb_client = ctx._hb.client
members = set(hb_client.workers())
assert {"host-0", "host-1"} <= members, members
assert hb_client.last_heartbeat(ctx.worker_id) is not None
sync_hosts("membership-checked")
print(json.dumps({"pid": pid, "scores": scores, "checksum": checksum}),
      flush=True)
ctx.close()
"""


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _communicate_all(procs, timeout):
    """communicate() every worker; kill whatever is still alive on any
    failure so a deadlocked gang (one worker dead, its peer blocked in
    a cross-host collective) never outlives its test."""
    results = []
    try:
        for p in procs:
            out, err = p.communicate(timeout=timeout)
            results.append((out, err, p.returncode))
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
                p.wait()
    return results


def test_two_process_gang_trains_in_lockstep(tmp_path):
    server = CoordinatorServer()
    server.start()
    try:
        jd_port = str(_free_port())
        script = tmp_path / "worker.py"
        script.write_text(_WORKER.replace("@REPO@", REPO))
        env = {k: v for k, v in os.environ.items()
               if k not in ("XLA_FLAGS", "JAX_PLATFORMS")}
        procs = [
            subprocess.Popen(
                [sys.executable, str(script), str(pid), jd_port,
                 server.address],
                stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                text=True, env=env,
            )
            for pid in range(2)
        ]
        outs = []
        for out, err, rc in _communicate_all(procs, 240):
            assert rc == 0, f"worker failed:\n{err}\n{out}"
            outs.append(json.loads(out.strip().splitlines()[-1]))

        by_pid = {o["pid"]: o for o in outs}
        assert set(by_pid) == {0, 1}
        # Gang consistency: synchronous data-parallel training must give
        # BOTH processes identical scores and identical parameters.
        np.testing.assert_allclose(
            by_pid[0]["scores"], by_pid[1]["scores"], rtol=1e-6)
        np.testing.assert_allclose(
            by_pid[0]["checksum"], by_pid[1]["checksum"], rtol=1e-6)
        assert by_pid[0]["scores"][-1] < by_pid[0]["scores"][0]

        # Elastic-membership path: the workers asserted their own
        # registration + heartbeats while alive (inside _WORKER); after
        # ctx.close() a clean exit must have DEREGISTERED both — a
        # clean shutdown must not look like a crash to the evictor.
        client = CoordinatorClient(server.address)
        remaining = set(client.workers())
        assert not ({"host-0", "host-1"} & remaining), remaining
    finally:
        server.stop()


_TP_PP_WORKER = """
import os, sys, json
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import jax
jax.config.update("jax_platforms", "cpu")
sys.path.insert(0, "@REPO@")
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from deeplearning4j_tpu.parallel.multihost import (
    initialize_multihost,
    sync_hosts,
)

pid = int(sys.argv[1])
jd_port = sys.argv[2]

initialize_multihost(
    coordinator_address="127.0.0.1:" + jd_port,
    num_processes=2,
    process_id=pid,
)
assert jax.device_count() == 4 and jax.local_device_count() == 2

from deeplearning4j_tpu.datasets.dataset import DataSet
from deeplearning4j_tpu.models.zoo import mlp
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.parallel.data_parallel import ParallelTrainer
from deeplearning4j_tpu.parallel.pipeline_parallel import PipelineTrainer

rng = np.random.default_rng(0)          # same stream on both hosts
x_full = rng.normal(size=(8, 8)).astype(np.float32)
y_full = np.eye(2, dtype=np.float32)[rng.integers(0, 2, 8)]

# ---- dp x tp spanning the process boundary: dp rows = processes, so
# the Megatron col/row all-reduces ride the cross-host transport.
mesh = Mesh(np.array(jax.devices()).reshape(2, 2), ("dp", "tp"))
net = MultiLayerNetwork(mlp((8, 6, 2), lr=0.1, seed=7)).init()
trainer = ParallelTrainer(net, mesh, tp_axis="tp")
lo, hi = pid * 4, (pid + 1) * 4
tp_scores = [float(trainer.fit(DataSet(x_full[lo:hi], y_full[lo:hi])))
             for _ in range(3)]
tp_checksum = float(
    sum(float(np.abs(np.asarray(v)).sum())
        for k in net.params for v in net.params[k].values()))
sync_hosts("tp-done")

# ---- pp spanning the process boundary: 4 stages over 4 devices (2 per
# host) — activations ppermute across hosts, params stage-sharded so
# each HOST stores only half the model.
pmesh = Mesh(np.array(jax.devices()).reshape(4), ("pp",))
pnet = MultiLayerNetwork(mlp((8, 7, 6, 5, 2), lr=0.1, seed=9)).init()
ptrainer = PipelineTrainer(pnet, pmesh, n_microbatches=2)
pp_scores = [float(ptrainer.fit(DataSet(x_full, y_full)))
             for _ in range(3)]
local_bytes = sum(
    sh.data.nbytes
    for buf in (ptrainer._theta, ptrainer._ustate, ptrainer._sstate)
    for sh in buf.addressable_shards)
total_bytes = sum(
    (ptrainer._p_pack.width + ptrainer._u_pack.width
     + ptrainer._s_pack.width) * 4 for _ in range(4))
pp_checksum = float(
    sum(float(np.abs(np.asarray(v)).sum())
        for k in pnet.params for v in pnet.params[k].values()))
sync_hosts("pp-done")

# ---- sp spanning the process boundary: conf-level ring attention — the
# K/V-block ppermute rotates across the host transport; each host feeds
# only its local half of the TIME axis (host_local_to_global assembly).
from deeplearning4j_tpu.models.zoo import transformer_lm

smesh = Mesh(np.array(jax.devices()).reshape(4), ("sp",))
snet = MultiLayerNetwork(transformer_lm(
    n_in=6, width=8, n_layers=1, n_heads=2, n_classes=4,
    lr=3e-2, ring_axis="sp")).init()
strainer = ParallelTrainer(snet, smesh, sp_axis="sp")
T = 16
x_seq = rng.normal(size=(2, 6, T)).astype(np.float32)
ids = rng.integers(0, 4, size=(2, T))
y_seq = np.zeros((2, 4, T), np.float32)
for i in range(2):
    y_seq[i, ids[i], np.arange(T)] = 1.0
tlo, thi = pid * (T // 2), (pid + 1) * (T // 2)
sp_scores = [float(strainer.fit(DataSet(
    x_seq[:, :, tlo:thi], y_seq[:, :, tlo:thi]))) for _ in range(3)]
sp_checksum = float(
    sum(float(np.abs(np.asarray(v)).sum())
        for k in snet.params for v in snet.params[k].values()))
sync_hosts("sp-done")

# ---- pp x sp on one mesh spanning the process boundary: pipeline
# stages ppermute across hosts WHILE ring attention rotates K/V over
# sp inside every tick (the homogeneous stage-stacked trainer); each
# host stores half the block stack.
from deeplearning4j_tpu.models.zoo import transformer_lm_flagship
from deeplearning4j_tpu.parallel.homogeneous_pipeline import (
    HomogeneousPipelineTrainer,
)

hmesh = Mesh(np.array(jax.devices()).reshape(2, 2), ("pp", "sp"))
hnet = MultiLayerNetwork(transformer_lm_flagship(
    vocab=6, width=8, n_layers=5, n_heads=2, lr=1e-2,
    warmup_steps=2, total_steps=100, seed=3,
    ring_axis="sp")).init()
htrainer = HomogeneousPipelineTrainer(
    hnet, hmesh, sp_axis="sp", n_microbatches=2)
Th = 8
hx = rng.normal(size=(4, 6, Th)).astype(np.float32)
hids = rng.integers(0, 6, size=(4, Th))
hy = np.zeros((4, 6, Th), np.float32)
for i in range(4):
    hy[i, hids[i], np.arange(Th)] = 1.0
hsp_scores = [float(htrainer.fit(DataSet(hx, hy)))
              for _ in range(3)]
hsp_local_bytes = max(
    htrainer.per_device_state_bytes().get(d, 0)
    for d in jax.local_devices())
hsp_total = htrainer.total_stack_bytes()
hsp_checksum = float(
    sum(float(np.abs(np.asarray(v)).sum())
        for k in hnet.params for v in hnet.params[k].values()))
sync_hosts("hsp-done")
print(json.dumps({
    "pid": pid, "tp_scores": tp_scores, "tp_checksum": tp_checksum,
    "pp_scores": pp_scores, "pp_checksum": pp_checksum,
    "sp_scores": sp_scores, "sp_checksum": sp_checksum,
    "hsp_scores": hsp_scores, "hsp_checksum": hsp_checksum,
    "hsp_local_bytes": hsp_local_bytes, "hsp_total": hsp_total,
    "local_bytes": local_bytes, "total_bytes": total_bytes,
}), flush=True)
"""


def test_two_process_tp_and_pp_mesh_spans_hosts(tmp_path):
    """Round-2 review item 4: cross-host collective lowering beyond dp
    — a dp x tp step (Megatron all-reduces across the process boundary),
    a 4-stage pipeline whose ppermute ring and stage-sharded params
    span both processes, and a conf-level sequence-parallel transformer
    whose ring-attention K/V rotation crosses hosts (each host feeds
    its local half of the time axis)."""
    jd_port = str(_free_port())
    script = tmp_path / "worker_tp_pp.py"
    script.write_text(_TP_PP_WORKER.replace("@REPO@", REPO))
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "JAX_PLATFORMS")}
    procs = [
        subprocess.Popen(
            [sys.executable, str(script), str(pid), jd_port],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE,
            text=True, env=env,
        )
        for pid in range(2)
    ]
    outs = []
    for out, err, rc in _communicate_all(procs, 300):
        assert rc == 0, f"worker failed:\n{err}\n{out}"
        outs.append(json.loads(out.strip().splitlines()[-1]))
    by_pid = {o["pid"]: o for o in outs}
    assert set(by_pid) == {0, 1}
    for key in ("tp_scores", "pp_scores", "sp_scores", "hsp_scores"):
        np.testing.assert_allclose(
            by_pid[0][key], by_pid[1][key], rtol=1e-6)
        assert by_pid[0][key][-1] < by_pid[0][key][0]
    for key in ("tp_checksum", "pp_checksum", "sp_checksum",
                "hsp_checksum"):
        np.testing.assert_allclose(
            by_pid[0][key], by_pid[1][key], rtol=1e-6)
    # Stage sharding across hosts: each host stores HALF the packed
    # model (2 of 4 stage rows), not a replica.
    for o in outs:
        assert o["local_bytes"] * 2 == o["total_bytes"], o
        # homogeneous pp x sp: this host's devices each hold half the
        # stacked block params (pp=2 spans the process boundary; sp
        # replicates the stack within a stage)
        assert o["hsp_local_bytes"] * 2 == o["hsp_total"], o


_ELASTIC_WORKER = """
import os, sys, json
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
import jax
jax.config.update("jax_platforms", "cpu")
sys.path.insert(0, "@REPO@")
import numpy as np
from jax.sharding import Mesh

from deeplearning4j_tpu.parallel.multihost import (
    MultiHostContext,
    initialize_multihost,
    sync_hosts,
)

pid = int(sys.argv[1])
jd_port = sys.argv[2]
coord_url = sys.argv[3]
ckpt_dir = sys.argv[4]

initialize_multihost(
    coordinator_address="127.0.0.1:" + jd_port,
    num_processes=2, process_id=pid)
ctx = MultiHostContext(coordinator_url=coord_url, heartbeat_interval=0.2)

from deeplearning4j_tpu.checkpoint.manager import CheckpointManager
from deeplearning4j_tpu.datasets.dataset import DataSet
from deeplearning4j_tpu.models.zoo import mlp
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.parallel.data_parallel import ParallelTrainer

mesh = Mesh(np.array(jax.devices()).reshape(2), ("dp",))
net = MultiLayerNetwork(mlp((8, 6, 2), lr=0.1, seed=7)).init()
trainer = ParallelTrainer(net, mesh)
rng = np.random.default_rng(0)
x_full = rng.normal(size=(8, 8)).astype(np.float32)
y_full = np.eye(2, dtype=np.float32)[rng.integers(0, 2, 8)]
lo, hi = pid * 4, (pid + 1) * 4
scores = [float(trainer.fit(DataSet(x_full[lo:hi], y_full[lo:hi])))
          for _ in range(4)]
sync_hosts("trained")
if pid == 0:
    CheckpointManager(ckpt_dir, async_save=False).save(
        4, net, score=scores[-1], metadata={"step": 4})
sync_hosts("checkpointed")
print(json.dumps({"pid": pid, "scores": scores}), flush=True)
if pid == 1:
    os._exit(1)   # simulated crash: no deregistration, no cleanup
ctx.close()       # survivor deregisters cleanly...
os._exit(0)       # ...and skips the jax.distributed atexit barrier,
                  # which would error against the dead peer
"""

_RESUME_WORKER = """
import os, sys, json
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
import jax
jax.config.update("jax_platforms", "cpu")
sys.path.insert(0, "@REPO@")
import numpy as np
from jax.sharding import Mesh

ckpt_dir = sys.argv[1]

from deeplearning4j_tpu.checkpoint.manager import CheckpointManager
from deeplearning4j_tpu.datasets.dataset import DataSet
from deeplearning4j_tpu.models.zoo import mlp
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.parallel.data_parallel import ParallelTrainer

mgr = CheckpointManager(ckpt_dir, async_save=False)
latest = mgr.latest_step()
assert latest == 4, latest
# restore() returns a complete net — using it directly (no throwaway
# init) makes this a strict restore-completeness check.
net, meta = mgr.restore(latest)

# Shrunk mesh: the survivor's single device, dp=1.
mesh = Mesh(np.array(jax.devices()).reshape(1), ("dp",))
trainer = ParallelTrainer(net, mesh)
rng = np.random.default_rng(0)
x_full = rng.normal(size=(8, 8)).astype(np.float32)
y_full = np.eye(2, dtype=np.float32)[rng.integers(0, 2, 8)]
scores = [float(trainer.fit(DataSet(x_full, y_full)))
          for _ in range(3)]
print(json.dumps({"resume_scores": scores,
                  "ckpt_score": meta.get("score")}), flush=True)
"""


def test_elastic_restart_resumes_on_shrunk_mesh(tmp_path):
    """Round-2 review item 4 (elastic path): a 2-process gang trains
    and checkpoints; one process crashes (no deregistration — the
    control plane must see the stale worker); a fresh single-process
    run restores the checkpoint and keeps training on a dp=1 mesh."""
    server = CoordinatorServer()
    server.start()
    try:
        jd_port = str(_free_port())
        ckpt = str(tmp_path / "ckpt")
        script = tmp_path / "worker_elastic.py"
        script.write_text(_ELASTIC_WORKER.replace("@REPO@", REPO))
        env = {k: v for k, v in os.environ.items()
               if k not in ("XLA_FLAGS", "JAX_PLATFORMS")}
        procs = [
            subprocess.Popen(
                [sys.executable, str(script), str(pid), jd_port,
                 server.address, ckpt],
                stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                text=True, env=env,
            )
            for pid in range(2)
        ]
        outs = {}
        rcs = {}
        for pid, (out, err, rc) in enumerate(
                _communicate_all(procs, 240)):
            rcs[pid] = rc
            line = [ln for ln in out.strip().splitlines()
                    if ln.startswith("{")]
            assert line, f"no output from worker {pid}:\n{err}\n{out}"
            outs[pid] = json.loads(line[-1])
        assert rcs[0] == 0
        assert rcs[1] == 1  # the simulated crash
        np.testing.assert_allclose(
            outs[0]["scores"], outs[1]["scores"], rtol=1e-6)

        # Crash detection: host-1 never deregistered — the control
        # plane still lists it (a clean exit would have removed it,
        # as asserted in the lockstep test above).
        client = CoordinatorClient(server.address)
        assert "host-1" in set(client.workers())

        # Resume on the shrunk mesh from the checkpoint.
        rscript = tmp_path / "worker_resume.py"
        rscript.write_text(_RESUME_WORKER.replace("@REPO@", REPO))
        p = subprocess.Popen(
            [sys.executable, str(rscript), ckpt],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE,
            text=True, env=env)
        (out, err, rc), = _communicate_all([p], 240)
        assert rc == 0, f"resume failed:\n{err}\n{out}"
        res = json.loads(out.strip().splitlines()[-1])
        # Continuity: resumed training continues the descent from the
        # checkpointed score instead of restarting from scratch.
        gang_scores = outs[0]["scores"]
        assert res["resume_scores"][0] < gang_scores[0]
        assert res["resume_scores"][-1] <= res["resume_scores"][0]
    finally:
        server.stop()
