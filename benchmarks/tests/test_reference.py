"""The plain reference against the program at a small size on the CPU, in
float32 on both sides: two implementations of the same equations."""

import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.harness import spec
from benchmarks.tests import reference_cases
from mingpt_distributed_tpu.config import GPTConfig
from mingpt_distributed_tpu.models import gpt

SIZES = {"n_head": 3, "layer_norm_epsilon": 1e-5}


def test_reference_agrees_with_the_program_in_float32():
    reference = spec.load_reference({"reference": "reference.py"})
    cfg = GPTConfig.make(n_layer=3, n_head=3, n_embd=48, vocab_size=211,
                         block_size=32, dtype="float32", embd_pdrop=0.0,
                         resid_pdrop=0.0, attn_pdrop=0.0)
    params = gpt.init(jax.random.key(0), cfg)
    params["wpe"] = 0.02 * jax.random.normal(jax.random.key(1),
                                             params["wpe"].shape)
    # biases and LayerNorm parameters off their trivial init
    params = jax.tree.map(
        lambda a: a + 0.01 * jax.random.normal(jax.random.key(2), a.shape),
        params)
    tokens = jax.random.randint(jax.random.key(3), (2, 32), 0, 211)
    targets = jnp.where(jnp.arange(32) % 5 == 0, -1, jnp.roll(tokens, -1, 1))
    want_logits, want_loss = gpt.forward(params, tokens, cfg, targets=targets)

    weights = reference.weights_from_program(params)
    x, ks, vs = reference.hidden(weights, tokens, SIZES)
    got = reference.logits(weights, x)
    np.testing.assert_allclose(got, want_logits, atol=2e-5)
    assert ks.shape == vs.shape == (3, 2, 32, 3, 16)
    np.testing.assert_allclose(
        reference.loss(weights, tokens, targets, SIZES), want_loss, rtol=1e-5)


# -- the twin ------------------------------------------------------------------

#: sha256 of ``str(jax.make_jaxpr(hidden))`` on ``reference_cases.case``, taken
#: from each file **as the parent of PR 36 had it** (no ``act_dtype``): with
#: no dtype a reference traces to the same program as before, so it computes
#: bit for bit what it computed (PR 36 also ran both files on these cases:
#: every output equal). A ``benchmark`` PR that changes a reference's
#: equations on purpose takes the new digest from its own file.
PARENT_JAXPR = {"reference.py": "07c00dbe97ca539c",
                "references/deepseek_v3.py": "5e30566c27b2a663",
                "references/minicpm_sala.py": "4e92d8326d3b4a29"}


def rel(a, b):
    return float(jnp.sqrt(jnp.sum((a - b) ** 2) / jnp.sum(b ** 2)))


@pytest.mark.parametrize("reference_file", sorted(PARENT_JAXPR))
def test_with_no_dtype_a_reference_is_the_parent_s_program(reference_file):
    reference, weights, tokens, sizes = reference_cases.case(reference_file)
    text = str(jax.make_jaxpr(
        lambda w, t: reference.hidden(w, t, sizes))(weights, tokens))
    assert hashlib.sha256(text.encode()).hexdigest()[:16] \
        == PARENT_JAXPR[reference_file]


@pytest.mark.parametrize("reference_file", sorted(PARENT_JAXPR))
def test_the_twin_at_float32_is_the_reference_and_at_bfloat16_is_near_it(
        reference_file):
    reference, weights, tokens, sizes = reference_cases.case(reference_file)
    plain = reference.hidden(weights, tokens, sizes)
    same = reference.hidden(weights, tokens, sizes, act_dtype=jnp.float32)
    assert len(plain) == len(same)
    for a, b in zip(plain, same):
        np.testing.assert_array_equal(a, b)
    twin = reference.hidden(weights, tokens, sizes, act_dtype=jnp.bfloat16)
    for a, b in zip(twin[1:3], plain[1:3]):
        assert a.dtype == jnp.float32 and a.shape == b.shape
        # rounded to 8 bits at every step, and nothing worse than that
        assert 5e-4 < rel(a, b) < 2e-2
        # the rows a twin hands over are bfloat16 numbers, as a pool's are
        np.testing.assert_array_equal(a, a.astype(jnp.bfloat16))


def test_the_gpt2_twin_s_error_grows_with_depth():
    """12 against 48 tiny layers: what the law of PRs 22-35 fitted with a
    power of the depth, the twin reads off the arithmetic."""
    by_depth = {}
    for n_layer in (12, 48):
        reference, weights, tokens, sizes = reference_cases.case(
            "reference.py", n_layer=n_layer, rows=1)
        _, ks, _ = reference.hidden(weights, tokens, sizes)
        assert ks.shape[0] == n_layer
        _, twin_ks, _ = reference.hidden(weights, tokens, sizes,
                                         act_dtype=jnp.bfloat16)
        by_depth[n_layer] = (rel(twin_ks, ks),
                             rel(twin_ks[:n_layer // 4], ks[:n_layer // 4]),
                             rel(twin_ks[-n_layer // 4:], ks[-n_layer // 4:]))
    assert by_depth[48][0] > 1.2 * by_depth[12][0]
    for whole, first, last in by_depth.values():
        assert first < whole < last
