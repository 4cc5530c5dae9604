"""Gemma-2-style logit soft-capping: cap * tanh(logits / cap) on attention
scores (before masking) and/or on the LM-head logits. The einsum oracle
defines the semantics; the flash kernel (fwd + hand-written tanh-chain
backward) must match; the loss must agree between the dense and chunked CE
heads; decode must agree with training forward."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mingpt_distributed_tpu.config import ConfigError, GPTConfig
from mingpt_distributed_tpu.models import generate as gen
from mingpt_distributed_tpu.models import gpt
from mingpt_distributed_tpu.ops import attention as attn_ops
from mingpt_distributed_tpu.ops import flash_attention as flash
from oracles import dense_greedy


def qkv(b=2, t=128, h=2, hd=16, seed=0):
    ks = jax.random.split(jax.random.key(seed), 3)
    return (
        2.0 * jax.random.normal(ks[0], (b, t, h, hd)),  # 2x: tanh bites
        2.0 * jax.random.normal(ks[1], (b, t, h, hd)),
        jax.random.normal(ks[2], (b, t, h, hd)),
    )


def test_einsum_softcap_matches_reference():
    q, k, v = qkv()
    cap = 5.0
    logits = jnp.einsum("bthd,bshd->bhts", q, k) / jnp.sqrt(16.0)
    logits = cap * jnp.tanh(logits / cap)
    t = q.shape[1]
    ok = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]
    logits = jnp.where(ok[None, None], logits, -jnp.inf)
    want = jnp.einsum(
        "bhts,bshd->bthd", jax.nn.softmax(logits, axis=-1), v)
    got = attn_ops.causal_attention(q, k, v, logit_softcap=cap)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    # and it actually changes the result
    plain = attn_ops.causal_attention(q, k, v)
    assert not np.allclose(np.asarray(got), np.asarray(plain), atol=1e-4)


@pytest.mark.parametrize("t,window", [(128, None), (384, None), (384, 96)])
def test_flash_softcap_matches_oracle(t, window):
    """Multi-block grids (t=384 -> block 128) so the capped scores flow
    through the streaming/skip machinery; also composed with a window."""
    q, k, v = qkv(t=t, seed=3)
    cap = 5.0
    want = attn_ops.causal_attention(q, k, v, window=window,
                                     logit_softcap=cap)
    got = flash.causal_attention(q, k, v, window=window, logit_softcap=cap)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("window", [None, 96])
def test_flash_softcap_gradients_match_oracle(window):
    """The hand-written backward must chain through the tanh (factor
    1 - (s_capped/cap)^2, computed from UNMASKED capped scores so masked
    entries can't overflow to NaN) — including composed with the sliding
    window's extra masking/skip logic in both bwd kernels."""
    q, k, v = qkv(t=384, seed=5)
    cap = 5.0

    def loss(fn):
        return lambda q, k, v: jnp.sum(
            jnp.square(fn(q, k, v, logit_softcap=cap, window=window)))

    g_want = jax.grad(loss(attn_ops.causal_attention), argnums=(0, 1, 2))(
        q, k, v)
    g_got = jax.grad(loss(flash.causal_attention), argnums=(0, 1, 2))(
        q, k, v)
    for want, got, name in zip(g_want, g_got, "qkv"):
        assert np.isfinite(np.asarray(got)).all(), f"d{name} not finite"
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-4,
            err_msg=f"d{name}",
        )


def test_final_softcap_dense_and_chunked_loss_agree():
    cfg_kw = dict(
        n_layer=2, n_head=2, n_embd=32, vocab_size=50, block_size=32,
        embd_pdrop=0.0, resid_pdrop=0.0, attn_pdrop=0.0, dtype="float32",
        final_logit_softcap=8.0,
    )
    cfg_dense = GPTConfig.make(**cfg_kw, loss_chunks=0)
    cfg_chunk = GPTConfig.make(**cfg_kw, loss_chunks=4)
    params = gpt.init(jax.random.key(0), cfg_dense)
    tokens = jax.random.randint(jax.random.key(1), (2, 32), 0, 50)
    _, dense_loss = gpt.forward(params, tokens, cfg_dense, targets=tokens)
    _, chunk_loss = gpt.forward(
        params, tokens, cfg_chunk, targets=tokens, return_logits=False)
    np.testing.assert_allclose(float(dense_loss), float(chunk_loss),
                               rtol=1e-6)
    # and the cap matters: without it the loss differs
    cfg_plain = GPTConfig.make(**{**cfg_kw, "final_logit_softcap": None})
    _, plain_loss = gpt.forward(params, tokens, cfg_plain, targets=tokens)
    assert abs(float(plain_loss) - float(dense_loss)) > 1e-6


def test_softcap_generation_matches_dense_oracle():
    cfg = GPTConfig.make(
        n_layer=2, n_head=2, n_embd=32, vocab_size=50, block_size=32,
        embd_pdrop=0.0, resid_pdrop=0.0, attn_pdrop=0.0, dtype="float32",
        attn_logit_softcap=5.0, final_logit_softcap=8.0,
    )
    params = gpt.init(jax.random.key(0), cfg)
    prompt = jax.random.randint(jax.random.key(1), (2, 5), 0, 50)
    idx = dense_greedy(params, cfg, prompt, 10)
    got = gen.generate(params, cfg, prompt, 10)
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(got))


def test_softcap_config_validation():
    with pytest.raises(ConfigError, match="attn_logit_softcap"):
        GPTConfig.make(n_layer=2, n_head=2, n_embd=32, attn_logit_softcap=0.0)
    # r4: softcap composes with the sp attentions — accepted, not refused
    for attention in ("ring", "ulysses"):
        cfg = GPTConfig.make(n_layer=2, n_head=2, n_embd=32,
                             attention=attention, attn_logit_softcap=5.0)
        assert cfg.attn_logit_softcap == 5.0
    with pytest.raises(ConfigError, match="final_logit_softcap"):
        GPTConfig.make(n_layer=2, n_head=2, n_embd=32,
                       final_logit_softcap=-1.0)
