"""Drive the accelerator through the native C++ PJRT client.

Stage 1 (this process): export a jax function to portable VHLO.
Stage 2 (subprocess, no jax backend): compile + execute through
native/pjrt_client.cpp — the framework's nd4j-equivalent native layer.

Run: python examples/native_pjrt_client.py
"""

import os
import subprocess
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

RUN_STAGE = """
import sys
sys.path.insert(0, {repo!r})
import numpy as np
from deeplearning4j_tpu.native_rt.pjrt import PjrtClient, tpu_plugin_path
d = {workdir!r}
plugin = tpu_plugin_path()
if plugin is None:
    print("no TPU chip on this machine; skipping run stage")
    raise SystemExit(0)
client = PjrtClient(plugin, "")
print("platform:", client.platform(), "devices:", client.device_count())
got = client.run_f32(open(d + "/prog.vhlo", "rb").read(),
                     np.load(d + "/x.npy"),
                     open(d + "/copts.pb", "rb").read())
print("native PJRT output:", got.tolist())
client.close()
"""


def main():
    import jax

    jax.config.update("jax_platforms", "cpu")  # export only
    import jax.numpy as jnp

    from deeplearning4j_tpu.native_rt.pjrt import serialize_for_pjrt

    def f(x):
        return jnp.tanh(x) * 2.0 + 1.0

    x = np.linspace(-1, 1, 8).astype(np.float32)
    code, copts = serialize_for_pjrt(f, jnp.zeros((8,), jnp.float32))
    with tempfile.TemporaryDirectory() as d:
        open(d + "/prog.vhlo", "wb").write(code)
        open(d + "/copts.pb", "wb").write(copts)
        np.save(d + "/x.npy", x)
        script = RUN_STAGE.format(
            repo=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            workdir=d)
        subprocess.run([sys.executable, "-c", script], check=True)
    print("expected:", (np.tanh(x) * 2 + 1).tolist())


if __name__ == "__main__":
    main()
