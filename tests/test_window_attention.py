"""Sliding-window (banded) attention — Mistral-style, beyond-parity.

The einsum oracle defines the semantics (q sees the last `window` positions,
itself included); the flash kernel must match it bit-for-tolerance in fwd
and grads while SKIPPING out-of-band blocks (compute O(T*window)); the
KV-cached decode path must agree with the dense forward.
"""

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
        jax.random.normal(ks[0], (b, t, h, hd)),
        jax.random.normal(ks[1], (b, t, h, hd)),
        jax.random.normal(ks[2], (b, t, h, hd)),
    )


def dense_banded_reference(q, k, v, window):
    """Brute-force banded softmax attention in fp64-ish numpy-free jax."""
    b, t, h, hd = q.shape
    logits = jnp.einsum("bthd,bshd->bhts", q, k) / jnp.sqrt(float(hd))
    qp = jnp.arange(t)[:, None]
    kp = jnp.arange(t)[None, :]
    ok = (qp >= kp) & (qp - kp < window)
    logits = jnp.where(ok[None, None], logits, -jnp.inf)
    probs = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhts,bshd->bthd", probs, v)


@pytest.mark.parametrize("window", [1, 7, 16, 100, 128])
def test_einsum_oracle_matches_banded_reference(window):
    q, k, v = qkv()
    want = dense_banded_reference(q, k, v, window)
    got = attn_ops.causal_attention(q, k, v, window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("t,window", [
    (128, 16),    # single-block grid: in-block band masking only
    # t=384 -> block 128, nb=3 (NOT 256, which _block_sizes tiles as one
    # 256 block): a real multi-block grid, so the block-skip machinery
    # (_kv_lo/_q_hi activity + clipped BlockSpec streams) actually runs
    (384, 96),    # band inside one block but sliding across boundaries
    (384, 128),   # window == block
    (384, 200),   # band spans 2-3 k blocks per q block
    (384, 500),   # window > T: degenerates to full causal
])
def test_flash_window_matches_oracle(t, window):
    q, k, v = qkv(t=t, seed=3)
    assert flash.supported_block(t) < t or t <= 128, "want multi-block"
    want = attn_ops.causal_attention(q, k, v, window=window)
    got = flash.causal_attention(q, k, v, window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_flash_window_gradients_match_oracle():
    # multi-block grid (block 128, nb=3) — the skip/clip paths run in all
    # three kernels (fwd, dq, dkv), including q rows whose FIRST active k
    # block is not block 0
    q, k, v = qkv(t=384, seed=5)
    window = 96

    def loss(fn):
        return lambda q, k, v: jnp.sum(
            jnp.square(fn(q, k, v, window=window)))

    g_want = jax.grad(loss(attn_ops.causal_attention), argnums=(0, 1, 2))(
        q, k, v)
    g_got = jax.grad(loss(flash.causal_attention), argnums=(0, 1, 2))(
        q, k, v)
    for want, got, name in zip(g_want, g_got, "qkv"):
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-4,
            err_msg=f"d{name}",
        )


def test_block_activity_math_matches_bruteforce():
    """_kv_lo/_q_hi (the kernel's block-skip bounds) must cover exactly the
    blocks containing any in-band (q, k) pair."""
    block = 8
    for window in (1, 3, 8, 9, 20, 64):
        for nb in (1, 4, 7):
            t = nb * block
            for qi in range(nb):
                lo = int(max(qi * block - (window - 1), 0)) // block
                # brute force: k blocks with any live pair for this q block
                live = set()
                for qq in range(qi * block, (qi + 1) * block):
                    for kk in range(t):
                        if kk <= qq and qq - kk < window:
                            live.add(kk // block)
                want_lo = min(live)
                want_hi = max(live)
                assert lo == want_lo, (window, qi, lo, want_lo)
                assert qi == want_hi  # diagonal always the last active
            for kj in range(nb):
                hi = min(int((kj * block + block + window - 2) // block), nb - 1)
                live = set()
                for kk in range(kj * block, (kj + 1) * block):
                    for qq in range(t):
                        if kk <= qq and qq - kk < window:
                            live.add(qq // block)
                if live:
                    assert hi == max(live), (window, kj, hi, max(live))
                    assert kj == min(live)


def test_model_forward_and_cached_decode_agree_with_window():
    """The KV-cached decode path applies the same band as training
    forward: cached greedy == reference-style dense re-forward greedy."""
    cfg = GPTConfig.make(
        n_layer=2, n_head=2, n_embd=32, vocab_size=50, block_size=32,
        embd_pdrop=0.0, resid_pdrop=0.0, attn_pdrop=0.0, dtype="float32",
        attention_window=8,
    )
    params = gpt.init(jax.random.key(0), cfg)
    prompt = jax.random.randint(jax.random.key(1), (2, 12), 0, 50)
    idx = dense_greedy(params, cfg, prompt, 10)
    got = gen.generate(params, cfg, prompt, 10)
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(got))

    # windowed attention really changes the function (sanity: not a no-op)
    cfg_full = GPTConfig.make(
        n_layer=2, n_head=2, n_embd=32, vocab_size=50, block_size=32,
        embd_pdrop=0.0, resid_pdrop=0.0, attn_pdrop=0.0, dtype="float32",
    )
    full_logits, _ = gpt.forward(params, prompt, cfg_full)
    win_logits, _ = gpt.forward(params, prompt, cfg)
    assert not np.allclose(np.asarray(full_logits), np.asarray(win_logits))


def test_mistral_presets_resolve():
    cfg = GPTConfig.make(model_type="mistral-tiny")
    assert cfg.attention_window == 64 and cfg.swiglu and cfg.rope
    big = GPTConfig.make(model_type="mistral-7b")
    assert big.attention_window == 4096 and big.n_kv_head == 8


def test_window_config_validation():
    with pytest.raises(ConfigError, match="attention_window"):
        GPTConfig.make(n_layer=2, n_head=2, n_embd=32, attention_window=0)
    # r4: the window composes with the sp attentions (banded ring / local
    # ulysses) — these configs are now accepted, not refused
    for attention in ("ring", "ulysses"):
        cfg = GPTConfig.make(n_layer=2, n_head=2, n_embd=32,
                             attention=attention, attention_window=8)
        assert cfg.attention_window == 8
