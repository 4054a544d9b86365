"""Native KV-cache decode serving (round-4 review item 7): the C++
PJRT client compiles the transformer decode step ONCE and streams
tokens through it with the cache device-resident — no jax/Python
compute in the loop. Parity vs the jax rnn_time_step streaming path.

Same two-stage subprocess shape as test_pjrt_native.py: stage 1
exports with jax-on-CPU; stage 2 initialises no jax backend and drives
the accelerator purely through the native client."""

import os
import subprocess
import sys
import textwrap

import pytest

from deeplearning4j_tpu.util.chips import local_tpu_chips

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

EXPORT_STAGE = textwrap.dedent("""
    import sys
    sys.path.insert(0, %r)
    import numpy as np
    import jax
    jax.config.update("jax_platforms", "cpu")
    from deeplearning4j_tpu.models.zoo import transformer_lm
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.native_rt.pjrt import (
        export_decode_step_for_native)

    net = MultiLayerNetwork(transformer_lm(
        n_in=16, width=32, n_layers=2, n_heads=4, n_classes=16,
        seed=7)).init()
    for c in net.conf.confs:
        if hasattr(c.layer, "stream_max_t"):
            c.layer.stream_max_t = 32

    code, copts, template, _ = export_decode_step_for_native(net)
    d = sys.argv[1]
    open(d + "/dec.vhlo", "wb").write(code)
    open(d + "/dec_copts.pb", "wb").write(copts)
    np.savez(d + "/cache0.npz", *template)

    # reference: jax streaming over 6 tokens
    rng = np.random.default_rng(0)
    xs = rng.normal(size=(6, 1, 16, 1)).astype(np.float32)
    with jax.default_matmul_precision("highest"):
        net.rnn_clear_previous_state()
        outs = [np.asarray(net.rnn_time_step(x)) for x in xs]
    np.save(d + "/dec_xs.npy", xs)
    np.save(d + "/dec_expected.npy", np.stack(outs))
    print("EXPORTED")
""") % (REPO,)

RUN_STAGE = textwrap.dedent("""
    import sys
    sys.path.insert(0, %r)
    import numpy as np
    from deeplearning4j_tpu.native_rt.pjrt import (
        CompiledProgram, PjrtClient, buffer_from_host, tpu_plugin_path)

    d = sys.argv[1]
    code = open(d + "/dec.vhlo", "rb").read()
    copts = open(d + "/dec_copts.pb", "rb").read()
    z = np.load(d + "/cache0.npz")
    cache0 = [z[k] for k in z.files]
    xs = np.load(d + "/dec_xs.npy")
    expected = np.load(d + "/dec_expected.npy")

    with PjrtClient(tpu_plugin_path(), "") as client:
        prog = CompiledProgram(client, code, copts)
        cache = [buffer_from_host(client, c) for c in cache0]
        outs = []
        for x in xs:
            inp = buffer_from_host(client, x)
            res = prog.execute([inp] + cache)
            inp.destroy()
            logits, new_cache = res[0], res[1:]
            outs.append(logits.to_host().reshape(expected.shape[1:]))
            logits.destroy()
            for b in cache:
                b.destroy()
            cache = new_cache
        prog.destroy()
    got = np.stack(outs)
    np.testing.assert_allclose(got, expected, rtol=5e-3, atol=5e-3)
    assert (got.argmax(axis=2) == expected.argmax(axis=2)).all()
    print("NATIVE_DECODE_OK", got.shape)
""") % (REPO,)


@pytest.mark.skipif(
    not local_tpu_chips(), reason="no TPU chip on this host")
def test_native_kv_cache_decode(tmp_path):
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
    assert b"NATIVE_DECODE_OK" in r2.stdout


def test_export_decode_step_serializes():
    """CPU-only check: the decode-step export produces VHLO + a cache
    template whose leaves match the streaming state structure."""
    import numpy as np

    from deeplearning4j_tpu.models.zoo import transformer_lm
    from deeplearning4j_tpu.native_rt.pjrt import (
        export_decode_step_for_native,
    )
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork

    net = MultiLayerNetwork(transformer_lm(
        n_in=8, width=16, n_layers=2, n_heads=2, n_classes=8)).init()
    for c in net.conf.confs:
        if hasattr(c.layer, "stream_max_t"):
            c.layer.stream_max_t = 16
    code, copts, template, _ = export_decode_step_for_native(net)
    assert len(code) > 0 and len(copts) > 0
    # 2 attention layers x {k, v, filled}
    assert len(template) == 6
    shapes = sorted(t.shape for t in template)
    assert shapes[0] == (1,)  # per-slot filled counters, [N] at N=1
    assert any(len(s) == 4 and s[2] == 16 for s in shapes)  # [1,H,16,dh]
    assert all(t.dtype == np.float32 for t in template)
