"""Test fixture: run all tests on a virtual 8-device CPU mesh.

The idiomatic equivalent of the reference's `local[*]` Spark test fixture
⟦SparkTestUtils.sparkTest⟧ (SURVEY.md §4): `--xla_force_host_platform_device_count=8`
gives 8 XLA CPU devices so the real `psum`/`shard_map`/`pjit` code paths execute
in-process without TPU hardware. Must run before jax is imported anywhere.
"""
import atexit
import os
import shutil
import tempfile

os.environ["JAX_PLATFORMS"] = "cpu"
# One persistent compile cache per test session, named through the variable
# the program honours (runtime/compile_store.compilation_cache_dir): no test loads what
# an earlier session — or another machine — compiled, and the checkout's own
# .jax_cache stays untouched by tests. Driver children inherit it.
os.environ["JAX_COMPILATION_CACHE_DIR"] = tempfile.mkdtemp(
    prefix="photon-test-xla-")
atexit.register(shutil.rmtree, os.environ["JAX_COMPILATION_CACHE_DIR"],
                ignore_errors=True)
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

# The variable above already holds jax to the CPU; pinning the config too
# keeps that true for a test that rewrites the environment, so tests never
# try to claim a real chip.
jax.config.update("jax_platforms", "cpu")

# The reference's math is double-precision (Breeze/JVM); enable x64 so golden
# and finite-difference tests can compare at full precision. Production entry
# points still default to float32/bfloat16 arrays.
jax.config.update("jax_enable_x64", True)

import numpy as np  # noqa: E402
import pytest  # noqa: E402

# Bound the process's mmap-region count. Every compiled XLA executable holds
# mmap'd JIT code pages, and jax's per-process executable caches never free
# them — ~350 tests push the process past vm.max_map_count (default 65530),
# at which point LLVM's code-page mmap fails ("LLVM compilation error:
# Cannot allocate memory") and jaxlib SEGFAULTS/ABORTS instead of raising
# (the round-4/5 1-in-2 'Fatal Python error' at ~test 256). Clearing jax's caches every N tests caps the
# live-executable count; the handful of re-compiles costs ~2 min across the
# suite, a crash costs the whole run.
_TESTS_PER_CACHE_CLEAR = 100
_test_counter = {"n": 0}


@pytest.fixture(autouse=True)
def _bound_jit_executable_maps():
    yield
    _test_counter["n"] += 1
    if _test_counter["n"] % _TESTS_PER_CACHE_CLEAR == 0:
        jax.clear_caches()


@pytest.fixture
def rng():
    return np.random.default_rng(42)
