"""Test configuration: force an 8-virtual-device CPU JAX platform.

Multi-host/multi-chip behavior is tested on a virtual CPU mesh exactly the
way the reference tests distributed code without a cluster (BaseSparkTest
spins local[*] Spark in-JVM; SURVEY.md §4): 8 XLA host-platform devices
stand in for an 8-chip TPU slice.
"""

import os
import sys

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import contextlib

import jax
import pytest

# Unit tests run on the CPU backend whatever the host offers (tier-1
# also sets JAX_PLATFORMS=cpu): deterministic, parallel-safe, and the
# 8 virtual devices above need the host platform.
jax.config.update("jax_platforms", "cpu")


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: long soak variants excluded from the tier-1 budget "
        "(deselected via -m 'not slow')")


# ``tests/cells/test_bench_lfm2.py`` pins LFM2's entries to the LAST places
# of BENCHMARK.json's lists, and the driver takes a PR's new entries only
# at the END of those lists (PR 41 was refused for putting its own before
# them). The file is the accepted benchmark's, not a program PR's to edit,
# so the pin cannot hold while any later entry exists. Strict: the
# `benchmark` PR that loosens the pin (ROADMAP W15) sees an XPASS fail here
# and takes this out. What the test held besides the places is held by
# ``test_bench_evabyte.py::test_lfm2s_entries_stand_whole_directly_before_these``.
_PINNED_TO_THE_TAIL = (
    "tests/cells/test_bench_lfm2.py::"
    "test_the_benchmarks_entries_are_the_issues")


def pytest_collection_modifyitems(config, items):
    for item in items:
        if item.nodeid == _PINNED_TO_THE_TAIL:
            item.add_marker(pytest.mark.xfail(
                strict=True,
                reason="pins LFM2's entries to the tails of BENCHMARK.json's "
                       "lists, where the driver puts every later PR's"))


def _compile_counts_of(target):
    """Executable counts for a no-retrace target: a jitted callable
    (``jax.jit`` cache size), anything exposing ``compile_counts()``
    (DecodeEngine, BlockPool), or a zero-arg callable returning
    a counts dict."""
    if hasattr(target, "compile_counts"):
        return dict(target.compile_counts())
    if hasattr(target, "_cache_size"):
        return {"jit": int(target._cache_size())}
    if callable(target):
        return dict(target())
    raise TypeError(
        f"assert_no_retrace target {target!r} is neither a jitted "
        "callable, nor exposes compile_counts(), nor is a zero-arg "
        "counts callable")


@contextlib.contextmanager
def _assert_no_retrace(*targets):
    before = [_compile_counts_of(t) for t in targets]
    yield
    after = [_compile_counts_of(t) for t in targets]
    assert after == before, (
        "jit cache grew inside an assert_no_retrace block (a retrace "
        f"slipped into a warmed path): {before} -> {after}")


@pytest.fixture
def assert_no_retrace():
    """Context manager asserting that warmed jitted computations do not
    compile new executables inside the block::

        with assert_no_retrace(engine):          # compile_counts()
            engine.run()
        with assert_no_retrace(fn_jit, other):   # jax.jit callables
            fn_jit(x)

    The serving engine's bounded-compile-count invariant fails tier-1
    through this helper, not just the on-chip bench gate."""
    return _assert_no_retrace
