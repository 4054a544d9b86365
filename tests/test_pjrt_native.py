"""Native C++ PJRT client: compile + execute a jax-exported program on
the real accelerator without Python compute in the loop (SURVEY.md §2.9
native layer / §7 stage 1).

Two subprocess stages: stage 1 exports portable VHLO+CompileOptions with
jax on CPU; stage 2 initialises no jax backend (a chip belongs to one
PJRT client at a time) and drives the accelerator purely through the C++
client. Both need a TPU on this host and skip without one."""

import os
import subprocess
import sys
import textwrap

import pytest

from deeplearning4j_tpu.util.chips import local_tpu_chips

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

needs_tpu = pytest.mark.skipif(
    not local_tpu_chips(), reason="no TPU chip on this host")

EXPORT_STAGE = textwrap.dedent("""
    import sys
    sys.path.insert(0, %r)
    import numpy as np
    import jax
    jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp
    from deeplearning4j_tpu.native_rt.pjrt import serialize_for_pjrt

    W = jnp.asarray(np.arange(12, dtype=np.float32).reshape(3, 4) * 0.1)
    def f(x):
        return jax.nn.relu(x @ W - 1.0)
    code, copts = serialize_for_pjrt(f, jnp.zeros((2, 3), jnp.float32))
    x = np.array([[1, 2, 3], [4, 5, 6]], np.float32)
    open(sys.argv[1] + "/prog.vhlo", "wb").write(code)
    open(sys.argv[1] + "/copts.pb", "wb").write(copts)
    np.save(sys.argv[1] + "/input.npy", x)
    np.save(sys.argv[1] + "/expected.npy", np.asarray(f(jnp.asarray(x))))
    print("EXPORTED")
""") % (REPO,)

RUN_STAGE = textwrap.dedent("""
    import sys
    sys.path.insert(0, %r)
    import numpy as np
    # importing the package pulls jax in but initialises no backend —
    # the only PJRT client in this process is ours
    from deeplearning4j_tpu.native_rt.pjrt import (
        PjrtClient, tpu_plugin_path)

    d = sys.argv[1]
    plugin = tpu_plugin_path()
    assert plugin
    code = open(d + "/prog.vhlo", "rb").read()
    copts = open(d + "/copts.pb", "rb").read()
    x = np.load(d + "/input.npy")
    expected = np.load(d + "/expected.npy")
    with PjrtClient(plugin, "") as client:
        assert client.device_count() >= 1
        platform = client.platform()
        got = client.run_f32(code, x, copts).reshape(expected.shape)
    # the TPU matmul path runs bf16 passes by default
    np.testing.assert_allclose(got, expected, rtol=5e-2, atol=5e-2)
    import jax
    assert not getattr(jax._src.xla_bridge, "_backends", {}), \
        "no jax backend should have initialized in this process"
    print("PJRT_NATIVE_OK on", platform)
""") % (REPO,)


@needs_tpu
def test_cpp_pjrt_client_executes_on_device(tmp_path):
    env = dict(os.environ)
    env.pop("JAX_PLATFORMS", None)
    r1 = subprocess.run(
        [sys.executable, "-c", EXPORT_STAGE, str(tmp_path)], env=env,
        capture_output=True, timeout=300)
    assert r1.returncode == 0, r1.stderr.decode()[-1500:]

    r2 = subprocess.run(
        [sys.executable, "-c", RUN_STAGE, str(tmp_path)], env=env,
        capture_output=True, timeout=300)
    assert r2.returncode == 0, (r2.stdout.decode()[-500:],
                                r2.stderr.decode()[-1500:])
    assert b"PJRT_NATIVE_OK" in r2.stdout


EXPORT_NET_STAGE = textwrap.dedent("""
    import sys
    sys.path.insert(0, %r)
    import numpy as np
    import jax
    jax.config.update("jax_platforms", "cpu")
    from deeplearning4j_tpu.nn.conf import NeuralNetConfiguration
    from deeplearning4j_tpu.nn.conf import layers as L
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.ops.losses import LossFunction
    from deeplearning4j_tpu.native_rt.pjrt import export_network_for_native

    rng = np.random.default_rng(0)
    cls = rng.integers(0, 3, 96)
    x = rng.normal(loc=cls[:, None], size=(96, 6)).astype(np.float32)
    y = np.eye(3, dtype=np.float32)[cls]
    conf = (NeuralNetConfiguration.Builder().seed(3).learning_rate(0.1)
            .list()
            .layer(0, L.DenseLayer(n_in=6, n_out=16, activation="tanh"))
            .layer(1, L.OutputLayer(n_in=16, n_out=3, activation="softmax",
                                    loss_function=LossFunction.MCXENT))
            .build())
    net = MultiLayerNetwork(conf).init()
    for _ in range(30):
        net.fit(x, y)
    probe = x[:8]
    code, copts = export_network_for_native(net, probe)
    d = sys.argv[1]
    open(d + "/net.vhlo", "wb").write(code)
    open(d + "/net_copts.pb", "wb").write(copts)
    np.save(d + "/net_x.npy", probe)
    np.save(d + "/net_expected.npy", np.asarray(net.output(probe)))
    print("EXPORTED")
""") % (REPO,)

RUN_NET_STAGE = textwrap.dedent("""
    import sys
    sys.path.insert(0, %r)
    import numpy as np
    from deeplearning4j_tpu.native_rt.pjrt import (
        PjrtClient, tpu_plugin_path)
    d = sys.argv[1]
    with PjrtClient(tpu_plugin_path(), "") as client:
        got = client.run_f32(
            open(d + "/net.vhlo", "rb").read(),
            np.load(d + "/net_x.npy"),
            open(d + "/net_copts.pb", "rb").read()).reshape(8, 3)
    expected = np.load(d + "/net_expected.npy")
    # full-precision serving export: tight tolerance
    np.testing.assert_allclose(got, expected, rtol=1e-4, atol=1e-4)
    # still a softmax: rows sum to one, argmax preserved
    np.testing.assert_allclose(got.sum(axis=1), 1.0, atol=1e-3)
    assert (got.argmax(1) == expected.argmax(1)).all()
    print("NATIVE_SERVING_OK")
""") % (REPO,)


@needs_tpu
def test_trained_network_served_natively(tmp_path):
    env = dict(os.environ)
    env.pop("JAX_PLATFORMS", None)
    r1 = subprocess.run(
        [sys.executable, "-c", EXPORT_NET_STAGE, str(tmp_path)], env=env,
        capture_output=True, timeout=300)
    assert r1.returncode == 0, r1.stderr.decode()[-1500:]
    r2 = subprocess.run(
        [sys.executable, "-c", RUN_NET_STAGE, str(tmp_path)],
        env=env, capture_output=True, timeout=300)
    assert r2.returncode == 0, (r2.stdout.decode()[-300:],
                                r2.stderr.decode()[-1500:])
    assert b"NATIVE_SERVING_OK" in r2.stdout


def test_export_computation_graph_serializes():
    """Regression: graph branch of export_network_for_native must track
    ComputationGraph._forward_fn's 3-tuple return (serialize-only — no
    native client needed)."""
    import numpy as np

    from deeplearning4j_tpu.native_rt.pjrt import export_network_for_native
    from deeplearning4j_tpu.nn.conf import NeuralNetConfiguration
    from deeplearning4j_tpu.nn.conf import layers as L
    from deeplearning4j_tpu.nn.graph import ComputationGraph
    from deeplearning4j_tpu.ops.losses import LossFunction

    conf = (NeuralNetConfiguration.Builder().seed(5).learning_rate(0.1)
            .graph_builder().add_inputs("in")
            .add_layer("d", L.DenseLayer(n_in=4, n_out=8, activation="tanh"),
                       "in")
            .add_layer("out", L.OutputLayer(
                n_in=8, n_out=3, activation="softmax",
                loss_function=LossFunction.MCXENT), "d")
            .set_outputs("out").build())
    graph = ComputationGraph(conf).init()
    code, copts = export_network_for_native(
        graph, np.zeros((2, 4), np.float32))
    assert len(code) > 0 and len(copts) > 0
