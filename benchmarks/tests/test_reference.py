"""The plain reference against the program at a small size on the CPU, in
float32 on both sides: two implementations of the same equations."""

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.harness import spec
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
