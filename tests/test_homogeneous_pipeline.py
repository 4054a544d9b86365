"""HomogeneousPipelineTrainer: dp x pp x tp on stage-stacked blocks
(round-4 review item 3 — the packed-row trainer's documented tp wall,
closed for homogeneous-stage models).

Same verification pattern as tests/test_pipeline_expert.py for the
packed trainer: single-device trajectory parity, per-device memory
accounting (1/(S*T) here), and validation errors."""

import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from deeplearning4j_tpu.datasets.dataset import DataSet
from deeplearning4j_tpu.models.zoo import transformer_lm_flagship
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.parallel.homogeneous_pipeline import (
    HomogeneousPipelineTrainer,
    find_homogeneous_run,
)
from deeplearning4j_tpu.parallel.mesh import MeshSpec, make_mesh

V, W, T = 8, 12, 12  # V != W so block 0 carries Wi (the pre group)


def _net(n_layers=5, seed=11, width=W, heads=2, remat=False):
    # layer 0 projects V -> width (its Wi leaf breaks homogeneity), so
    # the homogeneous run is blocks 1..n_layers-1 + pre/post replicated
    conf = transformer_lm_flagship(
        vocab=V, width=width, n_layers=n_layers, n_heads=heads,
        lr=1e-2, warmup_steps=4, total_steps=400, seed=seed,
        remat=remat)
    return MultiLayerNetwork(conf).init()


def _batch(n=8, t=T, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, V, t)).astype(np.float32)
    y = np.zeros((n, V, t), np.float32)
    idx = rng.integers(0, V, (n, t))
    for i in range(n):
        y[i, idx[i], np.arange(t)] = 1.0
    return x, y


class TestRunDetection:
    def test_finds_block_run(self):
        net = _net(n_layers=5)
        start, end = find_homogeneous_run(net)
        # layer 0 (with Wi) excluded; LayerNorm + head excluded
        assert (start, end) == (1, 5)

    def test_indivisible_run_rejected(self):
        net = _net(n_layers=4)  # run of 3 blocks, S=2
        mesh = make_mesh(MeshSpec({"pp": 2}))
        with pytest.raises(ValueError, match="not divisible"):
            HomogeneousPipelineTrainer(net, mesh, n_microbatches=2)


class TestTrajectoryParity:
    def _parity(self, mesh_axes, tp_axis=None, steps=3):
        x, y = _batch()
        ref = _net()
        pp_net = _net()
        mesh = make_mesh(MeshSpec(mesh_axes))
        trainer = HomogeneousPipelineTrainer(
            pp_net, mesh, n_microbatches=4, tp_axis=tp_axis)
        for _ in range(steps):
            ref.fit(DataSet(x, y))
            s_pp = trainer.fit(DataSet(x, y))
        np.testing.assert_allclose(
            s_pp, float(ref.score_value), rtol=2e-4)
        for si in ref.params:
            for name, p in ref.params[si].items():
                np.testing.assert_allclose(
                    np.asarray(pp_net.params[si][name]),
                    np.asarray(p), atol=3e-4,
                    err_msg=f"param {si}/{name} diverged")

    def test_pp_matches_single_device(self):
        self._parity({"pp": 2})

    def test_pp_tp_matches_single_device(self):
        self._parity({"pp": 2, "tp": 2}, tp_axis="tp")

    def test_dp_pp_tp_matches_single_device(self):
        self._parity({"dp": 2, "pp": 2, "tp": 2}, tp_axis="tp")

    def test_fit_scan_matches_fit(self):
        x, y = _batch(n=8)
        a = _net()
        b = _net()
        mesh = make_mesh(MeshSpec({"pp": 2, "tp": 2}))
        ta = HomogeneousPipelineTrainer(
            a, mesh, n_microbatches=2, tp_axis="tp")
        tb = HomogeneousPipelineTrainer(
            b, mesh, n_microbatches=2, tp_axis="tp")
        K = 3
        fs = np.stack([x] * K)
        ys = np.stack([y] * K)
        scores_scan = np.asarray(tb.fit_scan(fs, ys))
        scores_fit = [ta.fit(DataSet(x, y)) for _ in range(K)]
        np.testing.assert_allclose(
            scores_scan, scores_fit, rtol=2e-4)
        for si in a.params:
            for name, p in a.params[si].items():
                np.testing.assert_allclose(
                    np.asarray(b.params[si][name]), np.asarray(p),
                    atol=3e-4, err_msg=f"{si}/{name}")


class TestMemoryAccounting:
    def test_per_device_stack_bytes_1_over_ST(self):
        """Each device holds ~1/(S*T) of the stacked block params +
        updater state — the dp x pp x tp memory claim, asserted the way
        test_pipeline_expert.py:634 asserts the packed trainer's 1/S."""
        net = _net(n_layers=5, width=16, heads=2)
        mesh = make_mesh(MeshSpec({"pp": 2, "tp": 2}))
        trainer = HomogeneousPipelineTrainer(
            net, mesh, n_microbatches=2, tp_axis="tp")
        per_dev = trainer.per_device_state_bytes()
        total = trainer.total_stack_bytes()
        S, Tp = 2, 2
        assert len(per_dev) == S * Tp
        for d, nbytes in per_dev.items():
            # exact: every stacked leaf dim is divisible by its axis
            frac = nbytes / total
            assert abs(frac - 1 / (S * Tp)) < 0.02, (
                f"{d}: {frac:.3f} of total, expected ~{1/(S*Tp):.3f}")

    def test_tp_specs_applied(self):
        net = _net(n_layers=5)
        mesh = make_mesh(MeshSpec({"pp": 2, "tp": 2}))
        trainer = HomogeneousPipelineTrainer(
            net, mesh, n_microbatches=2, tp_axis="tp")
        trainer._ensure_placed()
        _, stack_p, _, _, stack_u, _ = trainer._state
        assert tuple(stack_p["Wq"].sharding.spec) == (
            "pp", None, None, "tp")
        assert tuple(stack_p["W2"].sharding.spec) == (
            "pp", None, "tp", None)
        # Adam state mirrors the param layout
        assert tuple(stack_u["m"]["Wq"].sharding.spec) == (
            "pp", None, None, "tp")


class TestMixedPrecisionAndRemat:
    def test_bf16_pp_tp_matches_bf16_single_device(self):
        """The homogeneous trainer's compute-dtype path (bf16 blocks,
        f32 master params + output head) must track single-device
        mixed-precision fit."""
        x, y = _batch(t=8)

        def build():
            net = _net()
            for c in net.conf.confs:
                c.compute_dtype = "bfloat16"
            return net

        ref, pp_net = build(), build()
        mesh = make_mesh(MeshSpec({"pp": 2, "tp": 2}))
        trainer = HomogeneousPipelineTrainer(
            pp_net, mesh, n_microbatches=2, tp_axis="tp")
        for _ in range(2):
            ref.fit(DataSet(x, y))
            s_pp = trainer.fit(DataSet(x, y))
        # bf16 hop buffers + bf16 compute: tolerances match the packed
        # trainer's mixed-precision parity tests
        np.testing.assert_allclose(
            s_pp, float(ref.score_value), rtol=5e-3)
        for si in ref.params:
            for name, p in ref.params[si].items():
                np.testing.assert_allclose(
                    np.asarray(pp_net.params[si][name]),
                    np.asarray(p), atol=5e-3,
                    err_msg=f"{si}/{name} diverged under bf16 pp x tp")

    def test_remat_pp_matches_single_device(self):
        x, y = _batch(t=8)
        ref, pp_net = _net(remat=True), _net(remat=True)
        mesh = make_mesh(MeshSpec({"pp": 2}))
        trainer = HomogeneousPipelineTrainer(
            pp_net, mesh, n_microbatches=2)
        for _ in range(2):
            ref.fit(DataSet(x, y))
            s_pp = trainer.fit(DataSet(x, y))
        np.testing.assert_allclose(
            s_pp, float(ref.score_value), rtol=2e-4)


class TestValidation:
    def test_rejects_tp_on_non_transformer_stack(self):
        from deeplearning4j_tpu.models.zoo import mlp

        net = MultiLayerNetwork(
            mlp(sizes=(12, 8, 8, 8, 8, 8, 10))).init()
        mesh = make_mesh(MeshSpec({"pp": 2, "tp": 2}))
        with pytest.raises(ValueError, match="TransformerBlock"):
            HomogeneousPipelineTrainer(
                net, mesh, tp_axis="tp", n_microbatches=2)

    def test_plain_pp_on_dense_stack_works(self):
        """Without tp, any homogeneous run pipelines (Dense stacks)."""
        from deeplearning4j_tpu.models.zoo import mlp

        x = np.random.default_rng(0).normal(size=(8, 12)).astype(
            np.float32)
        y = np.eye(10, dtype=np.float32)[
            np.random.default_rng(1).integers(0, 10, 8)]
        sizes = (12, 8, 8, 8, 8, 8, 10)
        ref = MultiLayerNetwork(mlp(sizes=sizes)).init()
        net = MultiLayerNetwork(mlp(sizes=sizes)).init()
        mesh = make_mesh(MeshSpec({"pp": 2}))
        trainer = HomogeneousPipelineTrainer(
            net, mesh, n_microbatches=2)
        # run = the four interior 8->8 Dense layers; 12->8 & head repl.
        assert trainer.run[1] - trainer.run[0] == 4
        for _ in range(2):
            ref.fit(DataSet(x, y))
            s = trainer.fit(DataSet(x, y))
        np.testing.assert_allclose(s, float(ref.score_value),
                                   rtol=2e-4)

    def test_rejects_masks(self):
        net = _net()
        mesh = make_mesh(MeshSpec({"pp": 2}))
        trainer = HomogeneousPipelineTrainer(
            net, mesh, n_microbatches=2)
        x, y = _batch()
        ds = DataSet(x, y)
        ds.labels_mask = np.ones((8, T), np.float32)
        with pytest.raises(ValueError, match="mask"):
            trainer.fit(ds)


class TestInterleavedSchedule:
    """interleave=V: each device hosts V round-robin chunks of the
    stack, cutting the pipeline-fill bubble ~V x at the same
    microbatch count (Megatron-LM interleaved schedule,
    arXiv:2104.04473 §2.2) — the GPipe alternative of raising M pays
    with M x activation liveness instead."""

    def test_bubble_math(self):
        from deeplearning4j_tpu.parallel.homogeneous_pipeline import (
            interleaved_bubble_fraction,
        )
        from deeplearning4j_tpu.parallel.pipeline_parallel import (
            bubble_fraction,
        )

        # V=1 reduces exactly to GPipe
        assert interleaved_bubble_fraction(4, 8) == bubble_fraction(4, 8)
        # at M=S=4: V=2 cuts 3/7 -> 3/11, V=4 -> 3/19
        assert interleaved_bubble_fraction(4, 4, 1) == 3 / 7
        assert interleaved_bubble_fraction(4, 4, 2) == 3 / 11
        assert interleaved_bubble_fraction(4, 4, 4) == 3 / 19
        # deeper interleave strictly shrinks the bubble
        assert (interleaved_bubble_fraction(4, 4, 4)
                < interleaved_bubble_fraction(4, 4, 2)
                < interleaved_bubble_fraction(4, 4, 1))

    def _parity(self, mesh_axes, interleave, tp_axis=None, steps=3,
                n_layers=5):
        x, y = _batch()
        ref = _net(n_layers=n_layers)
        pp_net = _net(n_layers=n_layers)
        mesh = make_mesh(MeshSpec(mesh_axes))
        trainer = HomogeneousPipelineTrainer(
            pp_net, mesh, n_microbatches=2, tp_axis=tp_axis,
            interleave=interleave)
        for _ in range(steps):
            ref.fit(DataSet(x, y))
            s_pp = trainer.fit(DataSet(x, y))
        np.testing.assert_allclose(
            s_pp, float(ref.score_value), rtol=2e-4)
        for si in ref.params:
            for name, p in ref.params[si].items():
                np.testing.assert_allclose(
                    np.asarray(pp_net.params[si][name]),
                    np.asarray(p), atol=3e-4,
                    err_msg=f"param {si}/{name} diverged (V>1)")

    def test_interleave2_matches_single_device(self):
        self._parity({"pp": 2}, interleave=2)

    def test_interleave4_matches_single_device(self):
        # run of 8 blocks over pp=2 x V=4 (one block per chunk)
        self._parity({"pp": 2}, interleave=4, n_layers=9)

    def test_interleave_dp_pp_tp_matches_single_device(self):
        self._parity({"dp": 2, "pp": 2, "tp": 2}, interleave=2,
                     tp_axis="tp")

    def test_fit_scan_interleaved(self):
        x, y = _batch(n=4)
        a, b = _net(), _net()
        mesh = make_mesh(MeshSpec({"pp": 2}))
        ta = HomogeneousPipelineTrainer(
            a, mesh, n_microbatches=2, interleave=2)
        tb = HomogeneousPipelineTrainer(
            b, mesh, n_microbatches=2, interleave=2)
        K = 3
        scores_scan = np.asarray(
            tb.fit_scan(np.stack([x] * K), np.stack([y] * K)))
        scores_fit = [ta.fit(DataSet(x, y)) for _ in range(K)]
        np.testing.assert_allclose(scores_scan, scores_fit, rtol=2e-4)

    def test_per_device_bytes_unchanged_by_interleave(self):
        """V chunks per device hold the same total bytes as one stage
        slice — interleaving reshuffles WHICH blocks a device owns,
        not how many (still 1/(S*T) of the stack)."""
        net = _net(n_layers=5, width=16, heads=2)
        mesh = make_mesh(MeshSpec({"pp": 2, "tp": 2}))
        trainer = HomogeneousPipelineTrainer(
            net, mesh, n_microbatches=2, tp_axis="tp", interleave=2)
        per_dev = trainer.per_device_state_bytes()
        total = trainer.total_stack_bytes()
        assert len(per_dev) == 4
        for d, nbytes in per_dev.items():
            assert abs(nbytes / total - 1 / 4) < 0.02, (d, nbytes)

    def test_round_robin_chunk_assignment(self):
        """Stacked leaf [V, S, k, ...]: device d's slice holds chunks
        {j*S + d} — execution-order chunk c sits at [c // S, c % S]."""
        net = _net(n_layers=9)  # run = blocks 1..8
        mesh = make_mesh(MeshSpec({"pp": 2}))
        trainer = HomogeneousPipelineTrainer(
            net, mesh, n_microbatches=2, interleave=4)
        stacked = trainer._stack_tree(net.params)["Wq"]
        assert stacked.shape[:3] == (4, 2, 1)
        for c in range(8):  # chunk c == block 1 + c (k == 1)
            np.testing.assert_array_equal(
                stacked[c // 2, c % 2, 0],
                np.asarray(net.params[str(1 + c)]["Wq"]))

    def test_rejects_m_greater_than_s(self):
        net = _net()
        mesh = make_mesh(MeshSpec({"pp": 2}))
        with pytest.raises(ValueError, match="collision-free"):
            HomogeneousPipelineTrainer(
                net, mesh, n_microbatches=4, interleave=2)

    def test_rejects_indivisible_interleave(self):
        net = _net(n_layers=5)  # run of 4, pp=2 -> V=4 needs 8
        mesh = make_mesh(MeshSpec({"pp": 2}))
        with pytest.raises(ValueError, match="not divisible"):
            HomogeneousPipelineTrainer(
                net, mesh, n_microbatches=2, interleave=4)


class TestElasticMeshResume:
    def test_checkpoint_on_interleaved_pp2_resumes_on_pp4(self,
                                                          tmp_path):
        """The stacked state syncs back to net.params/updater_state at
        end-of-fit, so a standard save/load moves training between
        ARBITRARY mesh shapes: steps 0-1 on pp=2 x interleave=2, then
        resume on pp=4 plain — the continued trajectory matches an
        uninterrupted single-device run."""
        x, y = _batch()
        ref = _net(n_layers=9)
        a = _net(n_layers=9)
        mesh2 = make_mesh(MeshSpec({"pp": 2}))
        tr_a = HomogeneousPipelineTrainer(
            a, mesh2, n_microbatches=2, interleave=2)
        for _ in range(2):
            ref.fit(DataSet(x, y))
            tr_a.fit(DataSet(x, y))
        path = str(tmp_path / "mid.zip")
        a.save(path)

        b = MultiLayerNetwork.load(path)
        mesh4 = make_mesh(MeshSpec({"pp": 4}))
        tr_b = HomogeneousPipelineTrainer(b, mesh4, n_microbatches=4)
        s = float("nan")
        for _ in range(2):
            ref.fit(DataSet(x, y))
            s = tr_b.fit(DataSet(x, y))
        np.testing.assert_allclose(s, float(ref.score_value),
                                   rtol=2e-4)
        for si in ref.params:
            for name, p in ref.params[si].items():
                np.testing.assert_allclose(
                    np.asarray(b.params[si][name]), np.asarray(p),
                    atol=3e-4, err_msg=f"{si}/{name}")


class TestSequenceParallelComposition:
    """sp INSIDE the pipeline ticks: activations' time axis sharded
    over sp, ring attention (conf-level ring_axis) runs per tick, the
    pp ppermute hops each time-shard independently — dp x pp x sp (x
    tp) on ONE mesh, the canonical long-context large-model layout."""

    def _sp_net(self, ring_axis, n_layers=5):
        from deeplearning4j_tpu.models.zoo import transformer_lm_flagship

        conf = transformer_lm_flagship(
            vocab=V, width=W, n_layers=n_layers, n_heads=2, lr=5e-3,
            warmup_steps=4, total_steps=400, seed=11,
            ring_axis=ring_axis)
        return MultiLayerNetwork(conf).init()

    def _parity(self, mesh_axes, steps=3, **kw):
        x, y = _batch(t=16)
        ref = self._sp_net(None)
        sp_net = self._sp_net("sp")
        mesh = make_mesh(MeshSpec(mesh_axes))
        trainer = HomogeneousPipelineTrainer(
            sp_net, mesh, sp_axis="sp", n_microbatches=2, **kw)
        for _ in range(steps):
            ref.fit(DataSet(x, y))
            s_pp = trainer.fit(DataSet(x, y))
        np.testing.assert_allclose(
            s_pp, float(ref.score_value), rtol=2e-4)
        for si in ref.params:
            for name, p in ref.params[si].items():
                np.testing.assert_allclose(
                    np.asarray(sp_net.params[si][name]),
                    np.asarray(p), atol=3e-4,
                    err_msg=f"param {si}/{name} diverged under pp x sp")

    def test_pp_sp_matches_single_device(self):
        self._parity({"pp": 2, "sp": 2})

    def test_dp_pp_sp_matches_single_device(self):
        """The same ``_parity``, in a process of its own: all 8 virtual
        devices on three axes rendezvous on ONE thread pool, and inside
        a worker of the six-worker suite that process aborted (XLA ends
        a process whose collective has waited 40 s for a participant).
        The child has a client and a pool to itself, waits for a starved
        participant as long as its own limit, and cannot take the
        worker's other tests down with it."""
        env = dict(os.environ, JAX_PLATFORMS="cpu", XLA_FLAGS=(
            "--xla_force_host_platform_device_count=8 "
            "--xla_cpu_collective_call_terminate_timeout_seconds=600"))
        p = subprocess.run(
            [sys.executable, "-c",
             "from tests.test_homogeneous_pipeline import "
             "TestSequenceParallelComposition as T; "
             "T()._parity({'dp': 2, 'pp': 2, 'sp': 2})"],
            env=env, cwd=os.path.dirname(os.path.dirname(__file__)),
            capture_output=True, text=True, timeout=600)
        assert p.returncode == 0, p.stderr[-4000:]

    def test_pp_sp_tp_matches_single_device(self):
        self._parity({"pp": 2, "sp": 2, "tp": 2}, tp_axis="tp")

    def test_pp_sp_interleaved_matches_single_device(self):
        self._parity({"pp": 2, "sp": 2}, interleave=2)

    def test_requires_ring_axis_on_blocks(self):
        net = self._sp_net(None)  # blocks without ring_axis
        mesh = make_mesh(MeshSpec({"pp": 2, "sp": 2}))
        with pytest.raises(ValueError, match="ring_axis"):
            HomogeneousPipelineTrainer(
                net, mesh, sp_axis="sp", n_microbatches=2)

    def test_time_axis_must_divide_sp(self):
        net = self._sp_net("sp")
        mesh = make_mesh(MeshSpec({"pp": 2, "sp": 2}))
        trainer = HomogeneousPipelineTrainer(
            net, mesh, sp_axis="sp", n_microbatches=2)
        x, y = _batch(t=9)  # 9 % 2 != 0
        # _validate_sp_batch fires before device_put with the crafted
        # message (the opaque PartitionSpec error never surfaces)
        with pytest.raises(ValueError,
                           match="time axis 9 not divisible"):
            trainer.fit(DataSet(x, y))
