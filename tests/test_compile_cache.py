"""The compile-cache rule and the chip smoke's off-chip refusal.

The cache directory is part of the cache key, so it must be placeable
from outside (``JAX_COMPILATION_CACHE_DIR``) and otherwise be ONE fixed
path — the same in every interpreter, or nothing ever hits. And
``chip_smoke.py`` must never pass by quietly running on the CPU.
"""

import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

PROBE = """
import sys
sys.path.insert(0, %r)
import jax
from deeplearning4j_tpu.util.compile_cache import enable_compile_cache
returned = enable_compile_cache()
print(returned)
print(jax.config.jax_compilation_cache_dir)
print(list(jax._src.xla_bridge._backends))
""" % (REPO,)


def _probe(env_dir=None):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = env_dir
    p = subprocess.run([sys.executable, "-c", PROBE], env=env,
                       capture_output=True, text=True, timeout=120)
    assert p.returncode == 0, p.stderr[-1500:]
    return p.stdout.strip().splitlines()


def test_placed_from_outside_is_left_alone(tmp_path):
    placed = str(tmp_path / "elsewhere")
    returned, configured, backends = _probe(placed)
    assert returned == configured == placed
    assert backends == "[]"  # choosing a directory takes no chip


def test_default_is_one_fixed_path_under_the_checkout():
    first, second = _probe(), _probe()
    want = os.path.join(REPO, ".jax_cache")
    assert first[:2] == [want, want]
    assert second == first  # identical across fresh interpreters


def test_exactly_one_place_names_a_cache_directory():
    hits = []
    for root, dirs, files in os.walk(REPO):
        dirs[:] = [d for d in dirs
                   if not d.startswith((".", "_")) and d != "tests"]
        for f in files:
            if not f.endswith(".py"):
                continue
            path = os.path.join(root, f)
            with open(path, encoding="utf-8") as fh:
                if "compilation_cache_dir" in fh.read():
                    hits.append(os.path.relpath(path, REPO))
    assert hits == ["deeplearning4j_tpu/util/compile_cache.py"], hits


def test_chip_smoke_refuses_to_run_off_chip():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")], env=env,
        capture_output=True, text=True, timeout=120, cwd=REPO)
    assert p.returncode != 0
    assert p.stdout.strip() == ""  # no result line of any kind
    reason = p.stderr.strip().splitlines()
    assert len(reason) == 1 and "not 'tpu'" in reason[0], p.stderr
