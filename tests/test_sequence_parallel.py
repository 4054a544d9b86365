"""Sequence-parallelism tests on the virtual 8-device CPU mesh.

Validates ring attention against dense single-device attention and the
distributed scan against a plain lax.scan (SURVEY.md §4 pattern:
distributed-without-a-cluster, like the reference's BaseSparkTest).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.parallel.mesh import MeshSpec, make_mesh
from deeplearning4j_tpu.parallel.sequence_parallel import (
    make_ring_attention,
    sp_scan,
)
from jax import shard_map
from jax.sharding import NamedSharding, PartitionSpec as P


def _dense_attention(q, k, v, causal=True):
    d = q.shape[-1]
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) / jnp.sqrt(
        jnp.asarray(d, q.dtype)
    )
    if causal:
        t = q.shape[2]
        mask = jnp.tril(jnp.ones((t, t), bool))
        scores = jnp.where(mask, scores, -jnp.inf)
    w = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", w, v)


class TestRingAttention:
    @pytest.mark.parametrize("causal", [True, False])
    def test_matches_dense(self, causal):
        mesh = make_mesh(MeshSpec({"sp": 8}))
        rng = np.random.default_rng(0)
        b, h, t, d = 2, 3, 64, 16  # t sharded 8 ways -> 8 per device
        q = jnp.asarray(rng.normal(size=(b, h, t, d)), jnp.float32)
        k = jnp.asarray(rng.normal(size=(b, h, t, d)), jnp.float32)
        v = jnp.asarray(rng.normal(size=(b, h, t, d)), jnp.float32)
        ring = jax.jit(make_ring_attention(mesh, "sp", causal=causal))
        out = ring(q, k, v)
        expected = _dense_attention(q, k, v, causal)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(expected), atol=2e-5
        )

    def test_gradients_flow(self):
        mesh = make_mesh(MeshSpec({"sp": 4}))
        rng = np.random.default_rng(1)
        q = jnp.asarray(rng.normal(size=(1, 2, 16, 8)), jnp.float32)
        ring = make_ring_attention(mesh, "sp", causal=True)

        def loss_ring(q):
            return jnp.sum(ring(q, q, q) ** 2)

        def loss_dense(q):
            return jnp.sum(_dense_attention(q, q, q) ** 2)

        g_ring = jax.jit(jax.grad(loss_ring))(q)
        g_dense = jax.grad(loss_dense)(q)
        np.testing.assert_allclose(
            np.asarray(g_ring), np.asarray(g_dense), atol=1e-4
        )


class TestSpScan:
    def test_matches_serial_scan(self):
        mesh = make_mesh(MeshSpec({"sp": 8}))
        rng = np.random.default_rng(2)
        t, d = 64, 4
        xs = jnp.asarray(rng.normal(size=(t, d)), jnp.float32)
        w = jnp.asarray(rng.normal(size=(d, d)) * 0.1, jnp.float32)

        def step(carry, x):
            new = jnp.tanh(carry @ w + x)
            return new, new

        carry0 = jnp.zeros((d,), jnp.float32)
        expected_carry, expected_ys = jax.lax.scan(step, carry0, xs)

        sp_fn = shard_map(
            lambda xs_local: sp_scan(step, carry0, xs_local, "sp"),
            mesh=mesh,
            in_specs=P("sp", None),
            out_specs=(P(), P("sp", None)),
            check_vma=False,
        )
        carry, ys = jax.jit(sp_fn)(xs)
        np.testing.assert_allclose(
            np.asarray(ys), np.asarray(expected_ys), atol=1e-5
        )
        np.testing.assert_allclose(
            np.asarray(carry), np.asarray(expected_carry), atol=1e-5
        )


class TestRingAttentionMask:
    @pytest.mark.parametrize("causal", [True, False])
    def test_key_mask_matches_dense(self, causal):
        """Padded keys must be excluded from the ring softmax exactly as
        the dense path excludes them."""
        mesh = make_mesh(MeshSpec({"sp": 4}))
        rng = np.random.default_rng(2)
        b, h, t, d = 2, 2, 32, 8
        q = jnp.asarray(rng.normal(size=(b, h, t, d)), jnp.float32)
        k = jnp.asarray(rng.normal(size=(b, h, t, d)), jnp.float32)
        v = jnp.asarray(rng.normal(size=(b, h, t, d)), jnp.float32)
        mask = np.ones((b, t), np.float32)
        mask[0, 20:] = 0.0  # example 0: last 12 steps are padding
        mask[1, 5:] = 0.0   # example 1: nearly all padding
        mask = jnp.asarray(mask)

        ring = jax.jit(
            make_ring_attention(mesh, "sp", causal=causal, masked=True)
        )
        out = np.asarray(ring(q, k, v, mask))

        dscores = jnp.einsum("bhqd,bhkd->bhqk", q, k) / jnp.sqrt(
            jnp.asarray(d, jnp.float32)
        )
        neg = -jnp.inf
        if causal:
            cm = jnp.tril(jnp.ones((t, t), bool))
            dscores = jnp.where(cm, dscores, neg)
        dscores = jnp.where(mask[:, None, None, :] > 0, dscores, neg)
        w = jax.nn.softmax(dscores, axis=-1)
        expected = np.asarray(jnp.einsum("bhqk,bhkd->bhqd", w, v))

        valid_q = np.asarray(mask) > 0  # only compare non-padded queries
        np.testing.assert_allclose(
            out[valid_q[:, None, :].repeat(h, 1)],
            expected[valid_q[:, None, :].repeat(h, 1)],
            atol=2e-5,
        )


def _lm_batch(rng, n, c, t, k):
    from tests.helpers import lm_batch

    x, y = lm_batch(rng, n, c, t, k)
    return jnp.asarray(x), jnp.asarray(y)


def _transformer(ring_axis=None, seed=7, n_in=8, width=16, n_classes=8):
    from deeplearning4j_tpu.models.zoo import transformer_lm

    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork

    return MultiLayerNetwork(transformer_lm(
        n_in=n_in, width=width, n_layers=2, n_heads=2,
        n_classes=n_classes, lr=1e-2, seed=seed,
        ring_axis=ring_axis)).init()


class TestConfLevelSequenceParallel:
    """ParallelTrainer(sp_axis=...): a conf-built transformer trains with
    its time axis sharded over the mesh — ring attention + exact global
    loss, single-device trajectory parity (the BaseSparkTest pattern:
    distributed semantics validated without a cluster)."""

    def test_sp_matches_single_device(self):
        from deeplearning4j_tpu.datasets.dataset import DataSet
        from deeplearning4j_tpu.parallel.data_parallel import (
            ParallelTrainer,
        )

        rng = np.random.default_rng(0)
        x, y = _lm_batch(rng, n=4, c=8, t=32, k=8)

        ref = _transformer(ring_axis=None)
        sp_net = _transformer(ring_axis="sp")
        mesh = make_mesh(MeshSpec({"sp": 8}))
        trainer = ParallelTrainer(sp_net, mesh, sp_axis="sp")

        scores_ref, scores_sp = [], []
        for _ in range(3):
            ref.fit(DataSet(x, y))
            scores_ref.append(float(ref.score_value))
            scores_sp.append(trainer.fit(DataSet(x, y)))
        np.testing.assert_allclose(scores_sp, scores_ref, rtol=2e-4)
        for si in ref.params:
            for name, p in ref.params[si].items():
                np.testing.assert_allclose(
                    np.asarray(sp_net.params[si][name]), np.asarray(p),
                    atol=2e-4,
                    err_msg=f"param {si}/{name} diverged under sp",
                )

    def test_dp_sp_composed_masked_parity(self):
        """dp x sp mesh with UNEVEN label masks: the global masked mean
        must match single-device exactly even though time shards carry
        different mask counts."""
        from deeplearning4j_tpu.datasets.dataset import DataSet
        from deeplearning4j_tpu.parallel.data_parallel import (
            ParallelTrainer,
        )

        rng = np.random.default_rng(1)
        x, y = _lm_batch(rng, n=4, c=8, t=16, k=8)
        fm = np.ones((4, 16), np.float32)
        fm[0, 10:] = 0.0
        fm[2, 3:] = 0.0  # nearly everything masked: uneven across shards
        lm = fm.copy()
        lm[1, :2] = 0.0
        fm, lm = jnp.asarray(fm), jnp.asarray(lm)

        ref = _transformer(ring_axis=None)
        sp_net = _transformer(ring_axis="sp")
        mesh = make_mesh(MeshSpec({"dp": 2, "sp": 4}))
        trainer = ParallelTrainer(sp_net, mesh, sp_axis="sp")

        for _ in range(2):
            ref.fit(DataSet(x, y, features_mask=fm, labels_mask=lm))
            s_sp = trainer.fit(
                DataSet(x, y, features_mask=fm, labels_mask=lm))
        np.testing.assert_allclose(
            s_sp, float(ref.score_value), rtol=2e-4)
        for si in ref.params:
            for name, p in ref.params[si].items():
                np.testing.assert_allclose(
                    np.asarray(sp_net.params[si][name]), np.asarray(p),
                    atol=2e-4,
                    err_msg=f"param {si}/{name} diverged under dp x sp",
                )

    def test_sp_fit_scan_parity(self):
        """K fused steps inside the shard_map match K sequential
        single-device fit() calls."""
        from deeplearning4j_tpu.datasets.dataset import DataSet
        from deeplearning4j_tpu.parallel.data_parallel import (
            ParallelTrainer,
        )

        rng = np.random.default_rng(2)
        K = 4
        fs, ys = [], []
        for _ in range(K):
            x, y = _lm_batch(rng, n=2, c=8, t=16, k=8)
            fs.append(x)
            ys.append(y)
        fs = jnp.stack(fs)
        ys = jnp.stack(ys)

        ref = _transformer(ring_axis=None)
        sp_net = _transformer(ring_axis="sp")
        mesh = make_mesh(MeshSpec({"dp": 2, "sp": 4}))
        trainer = ParallelTrainer(sp_net, mesh, sp_axis="sp")

        for i in range(K):
            ref.fit(DataSet(fs[i], ys[i]))
        scores = trainer.fit_scan(fs, ys)
        assert scores.shape == (K,)
        np.testing.assert_allclose(
            float(scores[-1]), float(ref.score_value), rtol=2e-4)
        for si in ref.params:
            for name, p in ref.params[si].items():
                np.testing.assert_allclose(
                    np.asarray(sp_net.params[si][name]), np.asarray(p),
                    atol=3e-4,
                    err_msg=f"param {si}/{name} diverged under sp scan",
                )

    def test_sp_rejects_non_shardable(self):
        from deeplearning4j_tpu.models.zoo import lenet5
        from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
        from deeplearning4j_tpu.parallel.data_parallel import (
            ParallelTrainer,
        )

        mesh = make_mesh(MeshSpec({"sp": 8}))
        with pytest.raises(ValueError, match="not time-shardable"):
            ParallelTrainer(
                MultiLayerNetwork(lenet5()), mesh, sp_axis="sp")
        # ring_axis mismatch must be caught, not silently run dense
        with pytest.raises(ValueError, match="ring_axis"):
            ParallelTrainer(
                _transformer(ring_axis=None), mesh, sp_axis="sp")

    def test_sp_moe_ghost_routing_trains(self):
        """MoE transformer under sp: per-time-shard capacity routing is
        the documented deviation; the composed net must still train
        (loss decreases, params finite)."""
        from deeplearning4j_tpu.datasets.dataset import DataSet
        from deeplearning4j_tpu.models.zoo import moe_transformer_lm
        from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
        from deeplearning4j_tpu.parallel.data_parallel import (
            ParallelTrainer,
        )

        rng = np.random.default_rng(3)
        x, y = _lm_batch(rng, n=4, c=8, t=16, k=8)
        net = MultiLayerNetwork(moe_transformer_lm(
            n_in=8, width=16, n_blocks=1, n_heads=2, n_classes=8,
            n_experts=4, lr=5e-2, seed=11, ring_axis="sp")).init()
        mesh = make_mesh(MeshSpec({"dp": 2, "sp": 4}))
        trainer = ParallelTrainer(net, mesh, sp_axis="sp")
        first = trainer.fit(DataSet(x, y))
        last = first
        for _ in range(14):
            last = trainer.fit(DataSet(x, y))
        assert np.isfinite(last)
        assert last < first

    def test_sp_rejects_unsupported_modes(self):
        from deeplearning4j_tpu.parallel.data_parallel import (
            ParallelTrainer,
        )

        mesh = make_mesh(MeshSpec({"sp": 8}))
        with pytest.raises(ValueError, match="accumulate_gradients"):
            ParallelTrainer(_transformer(ring_axis="sp"), mesh,
                            sp_axis="sp", accumulate_gradients=True)
        with pytest.raises(ValueError, match="synchronous"):
            ParallelTrainer(_transformer(ring_axis="sp"), mesh,
                            sp_axis="sp", average_each_iteration=False)

    def test_sp_rejects_non_sgd_and_headless(self):
        from deeplearning4j_tpu.models.zoo import transformer_lm
        from deeplearning4j_tpu.nn.conf.enums import (
            OptimizationAlgorithm,
        )
        from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
        from deeplearning4j_tpu.parallel.data_parallel import (
            ParallelTrainer,
        )

        mesh = make_mesh(MeshSpec({"sp": 8}))
        conf = transformer_lm(n_in=8, width=16, n_layers=1, n_heads=2,
                              n_classes=8, ring_axis="sp")
        for c in conf.confs:
            c.optimization_algo = OptimizationAlgorithm.LBFGS
        with pytest.raises(ValueError, match="SGD"):
            ParallelTrainer(MultiLayerNetwork(conf), mesh, sp_axis="sp")

        headless = transformer_lm(n_in=8, width=16, n_layers=1,
                                  n_heads=2, n_classes=8, ring_axis="sp")
        headless.confs = headless.confs[:-1]  # drop the output layer
        with pytest.raises(ValueError, match="output layer"):
            ParallelTrainer(MultiLayerNetwork(headless), mesh,
                            sp_axis="sp")

    def test_sp_rejects_dp_collision(self):
        from deeplearning4j_tpu.parallel.data_parallel import (
            ParallelTrainer,
        )

        mesh = make_mesh(MeshSpec({"dp": 2, "sp": 4}))
        with pytest.raises(ValueError, match="distinct from dp_axis"):
            ParallelTrainer(_transformer(ring_axis="dp"), mesh,
                            sp_axis="dp")


class TestBlockwiseRing:
    """block_size sub-chunks the visiting K/V block through the same
    online softmax — identical math, bounded score memory."""

    @pytest.mark.parametrize("causal", [True, False])
    def test_blockwise_equals_whole_block(self, causal):
        mesh = make_mesh(MeshSpec({"sp": 4}))
        rng = np.random.default_rng(5)
        b, h, t, d = 2, 2, 64, 8  # 16 per device; sub-blocks of 4
        q = jnp.asarray(rng.normal(size=(b, h, t, d)), jnp.float32)
        k = jnp.asarray(rng.normal(size=(b, h, t, d)), jnp.float32)
        v = jnp.asarray(rng.normal(size=(b, h, t, d)), jnp.float32)
        whole = jax.jit(make_ring_attention(mesh, "sp", causal=causal))
        blocked = jax.jit(make_ring_attention(
            mesh, "sp", causal=causal, block_size=4))
        np.testing.assert_allclose(
            np.asarray(blocked(q, k, v)), np.asarray(whole(q, k, v)),
            atol=2e-6)

    def test_blockwise_masked_and_grads(self):
        mesh = make_mesh(MeshSpec({"sp": 4}))
        rng = np.random.default_rng(6)
        b, h, t, d = 2, 2, 32, 8
        q = jnp.asarray(rng.normal(size=(b, h, t, d)), jnp.float32)
        mask = np.ones((b, t), np.float32)
        mask[0, 20:] = 0.0
        mask = jnp.asarray(mask)
        whole = make_ring_attention(mesh, "sp", masked=True)
        blocked = make_ring_attention(
            mesh, "sp", masked=True, block_size=8)
        np.testing.assert_allclose(
            np.asarray(blocked(q, q, q, mask)),
            np.asarray(whole(q, q, q, mask)), atol=2e-6)
        g_whole = jax.grad(
            lambda q: jnp.sum(whole(q, q, q, mask) ** 2))(q)
        g_blocked = jax.jit(jax.grad(
            lambda q: jnp.sum(blocked(q, q, q, mask) ** 2)))(q)
        np.testing.assert_allclose(
            np.asarray(g_blocked), np.asarray(g_whole), atol=1e-4)

    def test_conf_level_ring_block_size_trains(self):
        """ParallelTrainer sp path with ring_block_size set: parity with
        the whole-block sp net."""
        from deeplearning4j_tpu.datasets.dataset import DataSet
        from deeplearning4j_tpu.parallel.data_parallel import (
            ParallelTrainer,
        )

        rng = np.random.default_rng(7)
        x, y = _lm_batch(rng, n=2, c=8, t=32, k=8)

        def mk(bs):
            net = _transformer(ring_axis="sp")
            for c in net.conf.confs:
                if hasattr(c.layer, "ring_block_size"):
                    c.layer.ring_block_size = bs
            return net

        mesh = make_mesh(MeshSpec({"sp": 4}))
        a = ParallelTrainer(mk(None), mesh, sp_axis="sp")
        b_ = ParallelTrainer(mk(4), mesh, sp_axis="sp")
        for _ in range(2):
            sa = a.fit(DataSet(x, y))
            sb = b_.fit(DataSet(x, y))
        np.testing.assert_allclose(sb, sa, rtol=1e-5)

    def test_indivisible_block_size_raises(self):
        mesh = make_mesh(MeshSpec({"sp": 4}))
        q = jnp.zeros((1, 2, 24, 8), jnp.float32)  # 6 per device
        ring = make_ring_attention(mesh, "sp", block_size=4)
        with pytest.raises(ValueError, match="divide"):
            jax.jit(ring)(q, q, q)

    def test_non_positive_block_size_raises(self):
        mesh = make_mesh(MeshSpec({"sp": 4}))
        q = jnp.zeros((1, 2, 16, 8), jnp.float32)
        for bad in (0, -4):
            ring = make_ring_attention(mesh, "sp", block_size=bad)
            with pytest.raises(ValueError, match="positive"):
                jax.jit(ring)(q, q, q)


class TestSpTpComposition:
    """dp x sp x tp on one mesh: ring attention runs over the manual sp
    axis while the projection weights stay GSPMD-auto head-sharded over
    tp (XLA inserts the Megatron collectives around the ring) — 3D
    attention parallelism with single-device trajectory parity."""

    def test_dp_sp_tp_matches_single_device(self):
        from deeplearning4j_tpu.datasets.dataset import DataSet
        from deeplearning4j_tpu.parallel.data_parallel import (
            ParallelTrainer,
        )

        rng = np.random.default_rng(9)
        x, y = _lm_batch(rng, n=4, c=8, t=16, k=8)
        ref = _transformer(ring_axis=None, seed=3)
        net = _transformer(ring_axis="sp", seed=3)
        mesh = make_mesh(MeshSpec({"dp": 2, "sp": 2, "tp": 2}))
        trainer = ParallelTrainer(net, mesh, sp_axis="sp", tp_axis="tp")
        assert "tp" in tuple(net.params["0"]["Wq"].sharding.spec)
        for _ in range(3):
            ref.fit(DataSet(x, y))
            s = trainer.fit(DataSet(x, y))
        np.testing.assert_allclose(s, float(ref.score_value), rtol=2e-4)
        for si in ref.params:
            for name, p in ref.params[si].items():
                np.testing.assert_allclose(
                    np.asarray(net.params[si][name]), np.asarray(p),
                    atol=3e-4,
                    err_msg=f"param {si}/{name} diverged under 3D",
                )

    def test_sp_tp_fit_scan(self):
        from deeplearning4j_tpu.parallel.data_parallel import (
            ParallelTrainer,
        )

        rng = np.random.default_rng(10)
        K = 3
        fs, ys = zip(*[_lm_batch(rng, n=2, c=8, t=16, k=8)
                       for _ in range(K)])
        fs, ys = jnp.stack(fs), jnp.stack(ys)
        ref = _transformer(ring_axis=None, seed=5)
        net = _transformer(ring_axis="sp", seed=5)
        mesh = make_mesh(MeshSpec({"sp": 4, "tp": 2}))
        trainer = ParallelTrainer(net, mesh, sp_axis="sp", tp_axis="tp")
        from deeplearning4j_tpu.datasets.dataset import DataSet

        for i in range(K):
            ref.fit(DataSet(fs[i], ys[i]))
        scores = trainer.fit_scan(fs, ys)
        np.testing.assert_allclose(
            float(scores[-1]), float(ref.score_value), rtol=2e-4)

    def test_standalone_ring_plus_tp_still_rejected(self):
        from deeplearning4j_tpu.parallel.data_parallel import (
            ParallelTrainer,
        )

        mesh = make_mesh(MeshSpec({"tp": 2, "dp": 4}))
        with pytest.raises(ValueError, match="sp_axis"):
            ParallelTrainer(
                _transformer(ring_axis="ring"), mesh, tp_axis="tp")


class TestUlyssesAttention:
    """All-to-all (DeepSpeed-Ulysses) sequence parallelism: the other
    standard SP schedule — heads scatter over the ring, time gathers,
    full-sequence attention per device."""

    @pytest.mark.parametrize("causal", [True, False])
    def test_matches_dense(self, causal):
        from deeplearning4j_tpu.parallel.sequence_parallel import (
            ulysses_attention,
        )

        mesh = make_mesh(MeshSpec({"sp": 4}))
        rng = np.random.default_rng(11)
        b, h, t, d = 2, 4, 32, 8
        q = jnp.asarray(rng.normal(size=(b, h, t, d)), jnp.float32)
        k = jnp.asarray(rng.normal(size=(b, h, t, d)), jnp.float32)
        v = jnp.asarray(rng.normal(size=(b, h, t, d)), jnp.float32)
        spec = P(None, None, "sp", None)
        uly = jax.jit(shard_map(
            lambda q, k, v: ulysses_attention(
                q, k, v, "sp", causal=causal),
            mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
            check_vma=False))
        np.testing.assert_allclose(
            np.asarray(uly(q, k, v)),
            np.asarray(_dense_attention(q, k, v, causal)), atol=2e-5)

    def test_masked_matches_dense(self):
        from deeplearning4j_tpu.parallel.sequence_parallel import (
            ulysses_attention,
        )

        mesh = make_mesh(MeshSpec({"sp": 4}))
        rng = np.random.default_rng(12)
        b, h, t, d = 2, 4, 32, 8
        q = jnp.asarray(rng.normal(size=(b, h, t, d)), jnp.float32)
        mask = np.ones((b, t), np.float32)
        mask[0, 20:] = 0.0
        mask[1, 5:] = 0.0
        mask = jnp.asarray(mask)
        spec = P(None, None, "sp", None)
        uly = jax.jit(shard_map(
            lambda q, m: ulysses_attention(
                q, q, q, "sp", causal=True, key_mask=m),
            mesh=mesh, in_specs=(spec, P(None, "sp")), out_specs=spec,
            check_vma=False))
        out = np.asarray(uly(q, mask))
        dscores = jnp.einsum("bhqd,bhkd->bhqk", q, q) / jnp.sqrt(
            jnp.asarray(d, jnp.float32))
        dscores = jnp.where(
            jnp.tril(jnp.ones((t, t), bool)), dscores, -jnp.inf)
        dscores = jnp.where(mask[:, None, None, :] > 0, dscores, -jnp.inf)
        w = jax.nn.softmax(dscores, axis=-1)
        expected = np.asarray(jnp.einsum("bhqk,bhkd->bhqd", w, q))
        valid_q = np.asarray(mask) > 0
        sel = valid_q[:, None, :].repeat(h, 1)
        np.testing.assert_allclose(out[sel], expected[sel], atol=2e-5)

    def test_conf_level_ulysses_matches_single_device(self):
        from deeplearning4j_tpu.datasets.dataset import DataSet
        from deeplearning4j_tpu.parallel.data_parallel import (
            ParallelTrainer,
        )

        rng = np.random.default_rng(13)
        x, y = _lm_batch(rng, n=4, c=8, t=16, k=8)
        ref = _transformer(ring_axis=None, seed=6)
        net = _transformer(ring_axis="sp", seed=6)
        for c in net.conf.confs:
            if hasattr(c.layer, "sp_mode"):
                c.layer.sp_mode = "ulysses"
        mesh = make_mesh(MeshSpec({"dp": 4, "sp": 2}))
        trainer = ParallelTrainer(net, mesh, sp_axis="sp")
        for _ in range(3):
            ref.fit(DataSet(x, y))
            s = trainer.fit(DataSet(x, y))
        np.testing.assert_allclose(s, float(ref.score_value), rtol=2e-4)
        for si in ref.params:
            for name, p in ref.params[si].items():
                np.testing.assert_allclose(
                    np.asarray(net.params[si][name]), np.asarray(p),
                    atol=2e-4,
                    err_msg=f"param {si}/{name} diverged under ulysses",
                )

    def test_indivisible_heads_and_tp_compose_raise(self):
        from deeplearning4j_tpu.parallel.data_parallel import (
            ParallelTrainer,
        )
        from deeplearning4j_tpu.parallel.sequence_parallel import (
            ulysses_attention,
        )

        mesh = make_mesh(MeshSpec({"sp": 4}))
        q = jnp.zeros((1, 2, 16, 8), jnp.float32)  # 2 heads, sp=4
        spec = P(None, None, "sp", None)
        fn = shard_map(
            lambda q: ulysses_attention(q, q, q, "sp"),
            mesh=mesh, in_specs=(spec,), out_specs=spec,
            check_vma=False)
        with pytest.raises(ValueError, match="divisible"):
            jax.jit(fn)(q)

        uly_net = _transformer(ring_axis="sp", seed=6)
        for c in uly_net.conf.confs:
            if hasattr(c.layer, "sp_mode"):
                c.layer.sp_mode = "ulysses"
        mesh3 = make_mesh(MeshSpec({"dp": 2, "sp": 2, "tp": 2}))
        with pytest.raises(ValueError, match="cannot compose with tp"):
            ParallelTrainer(uly_net, mesh3, sp_axis="sp", tp_axis="tp")

    def test_ulysses_rejects_ring_block_size(self):
        from deeplearning4j_tpu.datasets.dataset import DataSet
        from deeplearning4j_tpu.parallel.data_parallel import (
            ParallelTrainer,
        )

        net = _transformer(ring_axis="sp", seed=6)
        for c in net.conf.confs:
            if hasattr(c.layer, "sp_mode"):
                c.layer.sp_mode = "ulysses"
                c.layer.ring_block_size = 4
        mesh = make_mesh(MeshSpec({"dp": 4, "sp": 2}))
        trainer = ParallelTrainer(net, mesh, sp_axis="sp")
        rng = np.random.default_rng(14)
        x, y = _lm_batch(rng, n=4, c=8, t=16, k=8)
        with pytest.raises(ValueError, match="ring_block_size"):
            trainer.fit(DataSet(x, y))


class TestRecurrentSequenceParallel:
    """LSTM/GRU recurrences under conf-level sp: the time scan runs as a
    distributed sp_scan (carry hops the ring) — exact full BPTT with
    O(T/P) activation memory, where the reference's only long-sequence
    device was TRUNCATED BPTT."""

    def _rnn_net(self, kind, ring_axis=None, seed=4):
        from deeplearning4j_tpu.nn.conf import (
            NeuralNetConfiguration,
            Updater,
        )
        from deeplearning4j_tpu.nn.conf import layers as L
        from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
        from deeplearning4j_tpu.ops.losses import LossFunction

        lc = (L.GravesLSTM if kind == "lstm" else L.GRU)(
            n_in=6, n_out=10, activation="tanh", ring_axis=ring_axis)
        conf = (
            NeuralNetConfiguration.Builder().seed(seed)
            .learning_rate(0.05).updater(Updater.SGD)
            .list()
            .layer(0, lc)
            .layer(1, L.RnnOutputLayer(
                n_in=10, n_out=4, activation="softmax",
                loss_function=LossFunction.MCXENT))
            .build()
        )
        return MultiLayerNetwork(conf).init()

    @pytest.mark.parametrize("kind", ["lstm", "gru"])
    def test_matches_single_device(self, kind):
        from deeplearning4j_tpu.datasets.dataset import DataSet
        from deeplearning4j_tpu.parallel.data_parallel import (
            ParallelTrainer,
        )

        rng = np.random.default_rng(15)
        x, y = _lm_batch(rng, n=4, c=6, t=16, k=4)
        ref = self._rnn_net(kind)
        net = self._rnn_net(kind, ring_axis="sp")
        mesh = make_mesh(MeshSpec({"dp": 2, "sp": 4}))
        trainer = ParallelTrainer(net, mesh, sp_axis="sp")
        for _ in range(3):
            ref.fit(DataSet(x, y))
            s = trainer.fit(DataSet(x, y))
        np.testing.assert_allclose(s, float(ref.score_value), rtol=2e-4)
        for si in ref.params:
            for name, p in ref.params[si].items():
                np.testing.assert_allclose(
                    np.asarray(net.params[si][name]), np.asarray(p),
                    atol=2e-4,
                    err_msg=f"{kind} param {si}/{name} diverged",
                )

    def test_masked_lstm_matches_single_device(self):
        """Masked variable-length sequences: mask chunks ride the sp
        shards and the held-state semantics (h frozen through masked
        steps) must survive the carry handoff."""
        from deeplearning4j_tpu.datasets.dataset import DataSet
        from deeplearning4j_tpu.parallel.data_parallel import (
            ParallelTrainer,
        )

        rng = np.random.default_rng(16)
        x, y = _lm_batch(rng, n=4, c=6, t=16, k=4)
        fm = np.ones((4, 16), np.float32)
        fm[0, 9:] = 0.0   # ends mid-shard
        fm[2, 3:] = 0.0   # ends in the first shard
        lm = jnp.asarray(fm)
        fm = jnp.asarray(fm)
        ref = self._rnn_net("lstm")
        net = self._rnn_net("lstm", ring_axis="sp")
        mesh = make_mesh(MeshSpec({"sp": 4}))
        trainer = ParallelTrainer(net, mesh, sp_axis="sp")
        for _ in range(2):
            ref.fit(DataSet(x, y, features_mask=fm, labels_mask=lm))
            s = trainer.fit(
                DataSet(x, y, features_mask=fm, labels_mask=lm))
        np.testing.assert_allclose(s, float(ref.score_value), rtol=2e-4)
        for si in ref.params:
            for name, p in ref.params[si].items():
                np.testing.assert_allclose(
                    np.asarray(net.params[si][name]), np.asarray(p),
                    atol=2e-4, err_msg=f"param {si}/{name} diverged",
                )

    def test_bilstm_rejects_ring(self):
        from deeplearning4j_tpu.nn.conf import NeuralNetConfiguration
        from deeplearning4j_tpu.nn.conf import layers as L
        from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
        from deeplearning4j_tpu.ops.losses import LossFunction

        conf = (
            NeuralNetConfiguration.Builder().seed(1).learning_rate(0.05)
            .list()
            .layer(0, L.GravesBidirectionalLSTM(
                n_in=6, n_out=10, activation="tanh", ring_axis="sp"))
            .layer(1, L.RnnOutputLayer(
                n_in=10, n_out=4, activation="softmax",
                loss_function=LossFunction.MCXENT))
            .build()
        )
        net = MultiLayerNetwork(conf).init()
        x = np.zeros((2, 6, 8), np.float32)
        y = np.zeros((2, 4, 8), np.float32)
        with pytest.raises(ValueError, match="REVERSED"):
            net.fit(x, y)
