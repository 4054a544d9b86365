"""Shared by the benchmark's tests: child processes of these tests run
on the CPU and keep their compile cache in one temporary directory, so
nothing of the repository's own cache is read or written."""

import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


@pytest.fixture(scope="session")
def bench_env(tmp_path_factory):
    cache = tmp_path_factory.mktemp("bench_jax_cache")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(cache))
    env.pop("BENCH_RUN", None)
    return env


@pytest.fixture(scope="session")
def run_python(bench_env):
    """Run a Python snippet or script in a child process from the
    repository's root."""

    def run(args, cwd=ROOT, timeout=600):
        return subprocess.run([sys.executable] + list(args), cwd=cwd,
                              env=bench_env, timeout=timeout,
                              capture_output=True, text=True)

    return run
