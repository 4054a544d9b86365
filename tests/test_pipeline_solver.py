"""Full-batch solvers (CG / LBFGS / LineGD / Hessian-free) under
pipeline parallelism.

Round-3 review weak item 5 residual: PipelineTrainer used to reject
every non-SGD optimization algorithm, shrinking PP's usable surface.
Now the BaseOptimizer loop (reference BaseOptimizer.optimize :163-226,
Solver.java:42 dispatch) drives a stage-sharded ``PipelinedProblem``:
the solver's x IS the [S, Kp] P(pp) theta buffer, value/grad probes run
the microbatched GPipe schedule, and directions / line-search moves /
L-BFGS history inherit the sharding through jnp arithmetic — 1/S model
memory per device, same as the SGD path.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.datasets.dataset import DataSet
from deeplearning4j_tpu.models.zoo import mlp
from deeplearning4j_tpu.nn.conf.enums import (
    BackpropType,
    OptimizationAlgorithm as OA,
)
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.parallel.mesh import MeshSpec, make_mesh
from deeplearning4j_tpu.parallel.pipeline_parallel import (
    PipelinedProblem,
    PipelineTrainer,
)


def _net(algo, sizes=(784, 128, 64, 32, 10), iters=4, lr=0.05):
    conf = mlp(sizes, lr=lr)
    for c in conf.confs:
        c.optimization_algo = algo
    conf.confs[0].num_iterations = iters
    return MultiLayerNetwork(conf).init()


def _batch(n=32, d=784, k=10, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, d)).astype(np.float32)
    y = np.zeros((n, k), np.float32)
    y[np.arange(n), rng.integers(0, k, n)] = 1.0
    return DataSet(x, y)


class TestPipelinedSolverParity:
    @pytest.mark.parametrize("algo", [
        OA.CONJUGATE_GRADIENT, OA.LBFGS, OA.LINE_GRADIENT_DESCENT])
    def test_matches_single_device_solver(self, algo):
        """Same conf, same batch: the pipelined solver must track the
        single-device Solver's score trajectory. Exact param equality
        is NOT expected after several iterations — line-search branch
        decisions compare f32 scalars whose pipelined summation order
        differs at the ulp level — so scores gate tightly and params
        loosely."""
        ds = _batch()
        net_sd = _net(algo)
        net_sd.fit(ds)
        net_pp = _net(algo)
        mesh = make_mesh(MeshSpec({"pp": 4}))
        tr = PipelineTrainer(net_pp, mesh, n_microbatches=4)
        s = tr.fit(ds)
        assert net_pp.iteration == net_sd.iteration
        assert abs(s - float(net_sd.score_value)) < 1e-4
        for k in net_sd.params:
            for name in net_sd.params[k]:
                np.testing.assert_allclose(
                    np.asarray(net_pp.params[k][name]),
                    np.asarray(net_sd.params[k][name]),
                    rtol=0.05, atol=1e-3)

    def test_dp_pp_composes(self):
        """CG on a dp=2 x pp=4 mesh: the batch shards over dp, theta
        over pp; the solver score still matches single-device."""
        ds = _batch()
        net_sd = _net(OA.CONJUGATE_GRADIENT)
        net_sd.fit(ds)
        net_pp = _net(OA.CONJUGATE_GRADIENT)
        mesh = make_mesh(MeshSpec({"dp": 2, "pp": 4}))
        tr = PipelineTrainer(net_pp, mesh, n_microbatches=2)
        s = tr.fit(ds)
        assert abs(s - float(net_sd.score_value)) < 1e-4

    def test_masked_time_series_solver_matches_single_device(self):
        """Masked sequences through the pipelined solver: the masked
        global-mean machinery is the SAME closure the SGD step uses
        (make_loss_fn), so CG line-search probes see the exact masked
        loss the single-device FlatProblem computes."""
        from deeplearning4j_tpu.models.zoo import lstm_classifier

        def build():
            conf = lstm_classifier(n_in=6, n_hidden=8, n_classes=3,
                                   lr=0.05)
            for c in conf.confs:
                c.optimization_algo = OA.CONJUGATE_GRADIENT
            conf.confs[0].num_iterations = 3
            return MultiLayerNetwork(conf).init()

        rng = np.random.default_rng(1)
        b, t = 8, 5
        x = rng.normal(size=(b, 6, t)).astype(np.float32)
        y = np.zeros((b, 3, t), np.float32)
        idx = rng.integers(0, 3, (b, t))
        for i in range(b):
            y[i, idx[i], np.arange(t)] = 1.0
        fm = np.ones((b, t), np.float32)
        fm[b // 2:, 3:] = 0.0  # uneven masks across microbatches
        ds = DataSet(x, y, features_mask=fm, labels_mask=fm.copy())

        net_sd = build()
        net_sd.fit(ds)
        net_pp = build()
        mesh = make_mesh(MeshSpec({"pp": 2}))
        tr = PipelineTrainer(net_pp, mesh, n_microbatches=2,
                             stage_ranges=[(0, 1), (1, 2)])
        s = tr.fit(ds)
        assert abs(s - float(net_sd.score_value)) < 1e-4

    def test_solver_descends_over_batches(self):
        """Multi-batch fit: each batch gets its own full solver run
        (reference Solver semantics: optimize() per batch)."""
        net = _net(OA.LBFGS, iters=3)
        mesh = make_mesh(MeshSpec({"pp": 4}))
        tr = PipelineTrainer(net, mesh, n_microbatches=4)
        first = tr.fit(_batch(seed=1))
        last = tr.fit(_batch(seed=1))
        assert last < first


class TestPipelinedHessianFree:
    def _problem_pair(self):
        ds = _batch(n=16, d=64)
        net_sd = _net(OA.HESSIAN_FREE, sizes=(64, 32, 16, 16, 10))
        net_pp = _net(OA.HESSIAN_FREE, sizes=(64, 32, 16, 16, 10))
        mesh = make_mesh(MeshSpec({"pp": 4}))
        tr = PipelineTrainer(net_pp, mesh, n_microbatches=2)
        from deeplearning4j_tpu.optimize.solver import FlatProblem

        return FlatProblem(net_sd, ds), PipelinedProblem(tr, ds), tr, ds

    def test_hvp_operator_matches_flat(self):
        """The pipelined R-op (jvp through the shard_map'd gradient)
        must agree with the single-device forward-over-reverse HVP on
        basis-independent invariants: f, ||g||, g.v, v.Hv, ||Hv|| for
        the all-ones direction (padding masked out on the packed
        side)."""
        fprob, pprob, tr, _ = self._problem_pair()
        s_f, g_f = fprob.value_and_grad(fprob.x0)
        s_p, g_p = pprob.value_and_grad(pprob.x0)
        assert abs(float(s_f) - float(s_p)) < 1e-5
        np.testing.assert_allclose(
            float(jnp.vdot(g_f, g_f)), float(jnp.vdot(g_p, g_p)),
            rtol=1e-5)
        v_f = jnp.ones_like(fprob.x0) * 0.01
        mask = np.zeros(pprob.x0.shape, np.float32)
        for s_i, (_, _, _, n) in enumerate(tr._p_pack.specs):
            mask[s_i, :n] = 1.0
        v_p = jnp.ones_like(pprob.x0) * 0.01 * mask
        h_f = fprob.hessian_vector_product(fprob.x0, v_f)
        h_p = pprob.hessian_vector_product(pprob.x0, v_p)
        for a, b in [
            (jnp.vdot(g_f, v_f), jnp.vdot(g_p, v_p)),
            (jnp.vdot(v_f, h_f), jnp.vdot(v_p, h_p)),
            (jnp.vdot(h_f, h_f), jnp.vdot(h_p, h_p)),
        ]:
            np.testing.assert_allclose(float(a), float(b), rtol=1e-4)

    def test_hf_trains_under_pp(self):
        """End-to-end: HF's truncated-Newton directions (50 inner CG
        iterations of pipelined HVPs) descend. Bitwise trajectory
        parity with single-device is NOT asserted: 50 f32 CG
        iterations amplify ulp-level summation-order differences
        chaotically (the operator itself is exact — see above)."""
        _, _, tr, ds = self._problem_pair()
        before = float(tr._fit_solver_batch(ds))
        tr.net.conf.confs[0].num_iterations = 3
        after = tr.fit(ds)
        assert after < before


class TestPipelinedSolverMechanics:
    def test_solver_state_stays_stage_sharded(self):
        """1/S memory through the solver path: theta after a CG fit is
        still a [S, Kp] P(pp) buffer — no device ever held the full
        model."""
        net = _net(OA.CONJUGATE_GRADIENT, iters=2)
        mesh = make_mesh(MeshSpec({"pp": 4}))
        tr = PipelineTrainer(net, mesh, n_microbatches=4)
        tr.fit(_batch())
        buf = tr._theta
        assert buf.shape[0] == 4
        per_dev = {s.device: s.data.nbytes for s in buf.addressable_shards}
        total = buf.nbytes
        for d, b in per_dev.items():
            assert b <= total // 4 + 1, (d, b, total)

    def test_tbptt_with_solver_raises(self):
        conf = mlp((8, 8, 8, 8, 2), lr=0.05)
        for c in conf.confs:
            c.optimization_algo = OA.LBFGS
        conf.backprop_type = BackpropType.TRUNCATED_BPTT
        net = MultiLayerNetwork(conf).init()
        mesh = make_mesh(MeshSpec({"pp": 4}))
        with pytest.raises(ValueError, match="full-batch"):
            PipelineTrainer(net, mesh, n_microbatches=2)

    def test_fit_scan_with_solver_raises(self):
        net = _net(OA.CONJUGATE_GRADIENT)
        mesh = make_mesh(MeshSpec({"pp": 4}))
        tr = PipelineTrainer(net, mesh, n_microbatches=4)
        with pytest.raises(ValueError, match="SGD fast path"):
            tr.fit_scan(np.zeros((2, 32, 784), np.float32),
                        np.zeros((2, 32, 10), np.float32))

    def test_listeners_fire_per_solver_iteration(self):
        from deeplearning4j_tpu.optimize.listeners import (
            ScoreIterationListener,
        )

        net = _net(OA.LINE_GRADIENT_DESCENT, iters=3)
        seen = []

        class Rec(ScoreIterationListener):
            def iteration_done(self, model, iteration):
                # params must be observable (synced) at callback time
                seen.append((iteration, float(np.asarray(
                    model.params["0"]["W"]).sum())))

        net.listeners.append(Rec(1))
        mesh = make_mesh(MeshSpec({"pp": 4}))
        tr = PipelineTrainer(net, mesh, n_microbatches=4)
        tr.fit(_batch())
        assert [i for i, _ in seen] == [1, 2, 3]
        # params move between iterations and the listener saw the moves
        assert len({w for _, w in seen}) > 1
