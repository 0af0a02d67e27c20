"""Test configuration: force an 8-device virtual CPU mesh BEFORE jax import.

Parity with the reference's test strategy (SURVEY.md §4): the reference fakes a
single-process DeepSpeed world (tests/subprocess_runner.py:37-50); JAX lets us do
better — a real 8-device mesh on CPU so collectives and shardings are exercised
for real.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture
def key():
    return jax.random.PRNGKey(42)


@pytest.fixture
def rng():
    return np.random.default_rng(42)


@pytest.fixture(autouse=True, scope="module")
def _xla_cache_hygiene():
    """Drop jit caches (and their live XLA CPU executables) after every test
    module. The monolithic single-process run historically segfaulted inside
    XLA's backend_compile_and_load after several hundred accumulated
    compilations (not OOM, not fd/map/thread exhaustion — compiling even a
    trivial program crashes once enough varied executables are live).
    Bounding the live
    executable set per module keeps the monolith viable; the sharded
    run_tests.sh remains the canonical gate."""
    yield
    jax.clear_caches()
