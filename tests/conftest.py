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
# 0.2 s, not 1 (ISSUE 63): an engine's programs at the suite's tiny sizes
# compile in 0.3-0.9 s each, and every test that builds an engine of a stack
# another built lowers the same programs again; the op-by-op programs (20-50
# ms) stay out. Measured cold on tests/test_serving.py alone: 149 s at 1,
# 99 s and 59 entries at 0.2, 103 s and 291 entries at 0.
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0.2")

import jax  # noqa: E402

jax.config.update("jax_threefry_partitionable", True)

import pytest  # noqa: E402

# the shared modules' asserts read as a test file's do
pytest.register_assert_rewrite("oracles", "stacks", "stack_contract")


@pytest.fixture(scope="session")
def eight_devices():
    devs = jax.devices()
    assert len(devs) >= 8, f"expected 8 virtual CPU devices, got {len(devs)}"
    return devs[:8]


def walk_in_blocks_with(monkeypatch):
    """``walk_in_blocks_with(monkeypatch)(rows)``: every walk worked out
    after it (``attention.step_walk``: a bare step's, an engine's at its
    construction) is a ``StepWalk`` of ``rows``-row blocks, whatever the
    leaves; the rule itself reads tiny slices in one pass, as it does
    narrow per-head leaves. ``alone=True``, the default, gives the walk a
    row beside whose bytes a step's cost is nothing, so a block is read
    for all lanes together only where every lane needs it; ``alone=False``
    a row of one byte, beside which the cost is everything, so any block
    two lanes need is read for all. ``kernel=True`` makes it the Pallas
    kernel's walk (in interpret mode here: every lane alone whatever a
    row's bytes). Returns the walk of an ``s``-row slice, for a test that
    hands one to the attention itself."""
    from mingpt_distributed_tpu.ops import attention

    def patch(rows, alone=True, kernel=False):
        def walk_of(s):
            return attention.StepWalk(
                s, 1 << 40 if alone else 1, rows if s % rows == 0 else s,
                kernel)
        monkeypatch.setattr(
            attention, "step_walk",
            lambda leaves, itemsize, latent=False, whole=True:
            walk_of(leaves[0][2]))
        return walk_of
    return patch


@pytest.fixture
def walk_in_blocks(monkeypatch):
    """:func:`walk_in_blocks_with` this test's ``monkeypatch``."""
    return walk_in_blocks_with(monkeypatch)


@pytest.fixture(autouse=True)
def _a_test_that_patches_traces_its_own_programs(request):
    """The session's shared programs (``oracles.shared_jit``) are keyed by
    their arguments, and a patch is none of them: a test that asks for
    ``monkeypatch``, itself or through a fixture, runs under an epoch of its
    own, so it is served no trace made before its patch and leaves none to
    the tests after it."""
    if "monkeypatch" not in request.fixturenames:
        yield
        return
    import oracles
    oracles.EPOCH[0] = request.node.nodeid
    yield
    oracles.EPOCH[0] = 0
