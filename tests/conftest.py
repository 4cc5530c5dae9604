"""Test harness: run everything on an 8-device virtual CPU mesh.

The reference had no way to exercise its distributed path without a real
cluster (SURVEY.md §4, §5.8 — NCCL hard-coded at train.py:34). Here the same
pjit/shard_map code runs on 8 fake CPU devices, so data-parallel ==
single-device equivalence, sharding, and ring attention are all CI-testable.
"""

import os

# Force CPU before jax initialises its backends: tests must be hermetic and
# fast even on a machine that has an accelerator.
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

# Persistent compilation cache (same as run_tests.sh): the suite is
# compile-dominated, and the cache pays off twice — across runs, and
# WITHIN one run wherever distinct jit wrappers lower identical programs
# (every serving test builds its own engine whose prefill/decode programs
# are byte-identical across tests). Safe to delete the directory anytime.
os.environ.setdefault(
    "JAX_COMPILATION_CACHE_DIR",
    os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                 ".jax_test_cache"),
)
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "1")

import jax  # noqa: E402

jax.config.update("jax_threefry_partitionable", True)

import pytest  # noqa: E402


@pytest.fixture(scope="session")
def eight_devices():
    devs = jax.devices()
    assert len(devs) >= 8, f"expected 8 virtual CPU devices, got {len(devs)}"
    return devs[:8]
