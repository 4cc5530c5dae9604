"""Flash-attention kernel parity vs the einsum oracle (fwd + grads), run in
Pallas interpret mode on CPU (SURVEY §7 hard-part #4: correctness vs the
oracle first, performance on hardware second)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mingpt_distributed_tpu.config import GPTConfig
from mingpt_distributed_tpu.models import gpt
from mingpt_distributed_tpu.ops import attention as attn_ops
from mingpt_distributed_tpu.ops import flash_attention as flash
from program_digests import (_equations, kernel_matmuls, kernels_digest,
                             pallas_calls)


def qkv(b=2, t=128, h=4, kv=None, hd=32, seed=0, dtype=jnp.float32):
    kv = kv or h
    ks = jax.random.split(jax.random.key(seed), 3)
    q = jax.random.normal(ks[0], (b, t, h, hd), dtype)
    k = jax.random.normal(ks[1], (b, t, kv, hd), dtype)
    v = jax.random.normal(ks[2], (b, t, kv, hd), dtype)
    return q, k, v


def test_forward_parity():
    q, k, v = qkv()
    want = attn_ops.causal_attention(q, k, v)
    got = flash.causal_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_forward_parity_gqa():
    q, k, v = qkv(h=4, kv=2)
    want = attn_ops.causal_attention(q, k, v)
    got = flash.causal_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_forward_parity_multiblock():
    # T=256 -> block 128 x 2: exercises the streaming-softmax accumulation
    q, k, v = qkv(t=256, seed=3)
    want = attn_ops.causal_attention(q, k, v)
    got = flash.causal_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_gradient_parity():
    q, k, v = qkv(t=128, seed=5)

    def loss(fn, q, k, v):
        return jnp.sum(jnp.square(fn(q, k, v)))

    g_want = jax.grad(lambda *a: loss(attn_ops.causal_attention, *a),
                      argnums=(0, 1, 2))(q, k, v)
    g_got = jax.grad(lambda *a: loss(flash.causal_attention, *a),
                     argnums=(0, 1, 2))(q, k, v)
    for want, got, name in zip(g_want, g_got, "qkv"):
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-4,
            err_msg=f"d{name} mismatch",
        )


def test_gradient_parity_gqa_multiblock():
    q, k, v = qkv(t=256, h=4, kv=1, seed=7)

    def loss(fn, q, k, v):
        return jnp.sum(jnp.square(fn(q, k, v)))

    g_want = jax.grad(lambda *a: loss(attn_ops.causal_attention, *a),
                      argnums=(0, 1, 2))(q, k, v)
    g_got = jax.grad(lambda *a: loss(flash.causal_attention, *a),
                     argnums=(0, 1, 2))(q, k, v)
    for want, got, name in zip(g_want, g_got, "qkv"):
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-4,
            err_msg=f"d{name} mismatch",
        )


def test_gradient_parity_long_sequence():
    """T=1024 -> 512-blocks streamed via the grid (the FA2 re-tiling): the
    per-cell VMEM footprint must not depend on T, and the scratch-carried
    online softmax must stay exact across many k blocks."""
    q, k, v = qkv(b=1, t=1024, h=2, seed=11)

    def loss(fn, q, k, v):
        return jnp.sum(jnp.square(fn(q, k, v)))

    want = attn_ops.causal_attention(q, k, v)
    got = flash.causal_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    g_want = jax.grad(lambda *a: loss(attn_ops.causal_attention, *a),
                      argnums=(0, 1, 2))(q, k, v)
    g_got = jax.grad(lambda *a: loss(flash.causal_attention, *a),
                     argnums=(0, 1, 2))(q, k, v)
    for want, got, name in zip(g_want, g_got, "qkv"):
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=5e-4, atol=5e-4,
            err_msg=f"d{name} mismatch",
        )


def test_fallback_paths_route_to_oracle():
    # dropout active -> einsum fallback (still correct, just not flash)
    q, k, v = qkv(t=64)
    out = flash.causal_attention(
        q, k, v, attn_pdrop=0.5, dropout_key=jax.random.key(0),
        deterministic=False,
    )
    assert out.shape == q.shape
    # decode-style (q_len 1 vs cache 64) -> fallback with kv_offset
    out = flash.causal_attention(q[:, :1], k, v, kv_offset=63)
    assert out.shape == (2, 1, 4, 32)
    # odd T -> fallback
    out = flash.causal_attention(q[:, :37], k[:, :37], v[:, :37])
    want = attn_ops.causal_attention(q[:, :37], k[:, :37], v[:, :37])
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_model_forward_with_flash_matches_einsum():
    """End-to-end: gpt_config.attention=flash must reproduce einsum logits."""
    base = dict(
        n_layer=2, n_head=2, n_embd=32, vocab_size=50, block_size=128,
        embd_pdrop=0.0, resid_pdrop=0.0, attn_pdrop=0.0, dtype="float32",
    )
    cfg_e = GPTConfig.make(**base, attention="einsum")
    cfg_f = GPTConfig.make(**base, attention="flash")
    params = gpt.init(jax.random.key(0), cfg_e)
    tokens = jax.random.randint(jax.random.key(1), (2, 128), 0, 50)
    le, _ = gpt.forward(params, tokens, cfg_e, targets=tokens)
    lf, _ = gpt.forward(params, tokens, cfg_f, targets=tokens)
    np.testing.assert_allclose(np.asarray(lf), np.asarray(le),
                               rtol=2e-4, atol=2e-4)


def _dense_noncausal(q, k, v):
    """Non-causal reference: softmax(QK^T/sqrt(hd))V + its log-sum-exp."""
    hd = q.shape[-1]
    s = jnp.einsum("bthd,bshd->bhts", q, k,
                   preferred_element_type=jnp.float32) / jnp.sqrt(
                       jnp.asarray(hd, jnp.float32))
    lse = jax.nn.logsumexp(s, axis=-1)  # (B, H, T)
    out = jnp.einsum("bhts,bshd->bthd", jax.nn.softmax(s, -1), v)
    return out.astype(q.dtype), lse


def test_flash_with_lse_noncausal_parity():
    """The non-causal kernel mode (ring attention's off-diagonal hops):
    out and lse both match the dense reference."""
    import math

    b, t, h, hd = 2, 256, 2, 32
    q, k, v = qkv(b=b, t=t, h=h, hd=hd, seed=5)
    want_out, want_lse = _dense_noncausal(q, k, v)

    to_bh = lambda x: x.transpose(0, 2, 1, 3).reshape(b * h, t, hd)
    out, lse = flash.flash_with_lse(
        to_bh(q), to_bh(k), to_bh(v), 1.0 / math.sqrt(hd), 128, False
    )
    out = out.reshape(b, h, t, hd).transpose(0, 2, 1, 3)
    lse = lse.reshape(b, h, t)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want_out),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(want_lse),
                               rtol=1e-5, atol=1e-5)


def test_flash_with_lse_cotangent():
    """Gradients that flow through BOTH outputs (out and lse) match the
    dense reference — the lse cotangent folds into the delta term."""
    import math

    b, t, h, hd = 1, 128, 2, 16
    q, k, v = qkv(b=b, t=t, h=h, hd=hd, seed=9)
    to_bh = lambda x: x.transpose(0, 2, 1, 3).reshape(b * h, t, hd)

    def loss_flash(q, k, v):
        out, lse = flash.flash_with_lse(
            to_bh(q), to_bh(k), to_bh(v), 1.0 / math.sqrt(hd), 128, False
        )
        return (out.astype(jnp.float32) ** 2).sum() + (lse * 0.3).sum()

    def loss_dense(q, k, v):
        out, lse = _dense_noncausal(q, k, v)
        return (out.astype(jnp.float32) ** 2).sum() + (lse * 0.3).sum()

    g_f = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_d = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(g_f, g_d):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=1e-4, atol=1e-4)


# --- native-layout (B, T, D) kernel path -----------------------------------


def _both_layouts(q, k, v, monkeypatch, **kw):
    """Run flash.causal_attention with the btd path and the transpose path."""
    monkeypatch.setenv("FLASH_LAYOUT", "auto")
    got_btd = flash.causal_attention(q, k, v, **kw)
    monkeypatch.setenv("FLASH_LAYOUT", "bh")
    got_bh = flash.causal_attention(q, k, v, **kw)
    return got_btd, got_bh


def test_btd_pack_table():
    assert flash._btd_pack(12, 64) == 2   # gpt2
    assert flash._btd_pack(4, 32) == 4
    assert flash._btd_pack(32, 128) == 1  # llama-shaped
    assert flash._btd_pack(3, 64) is None   # odd head count can't pair
    assert flash._btd_pack(4, 48) is None   # 48 doesn't divide 128


def test_btd_forward_and_grad_parity(monkeypatch):
    """The native-layout path must agree with the transpose path AND the
    oracle (fwd + all grads) — h=4/hd=32 routes to pack=4."""
    q, k, v = qkv(t=256, seed=13)
    got_btd, got_bh = _both_layouts(q, k, v, monkeypatch)
    want = attn_ops.causal_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(got_btd), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(got_bh), np.asarray(want),
                               rtol=1e-5, atol=1e-5)

    def loss(fn, q, k, v):
        return jnp.sum(jnp.square(fn(q, k, v)))

    monkeypatch.setenv("FLASH_LAYOUT", "auto")
    g_got = jax.grad(lambda *a: loss(flash.causal_attention, *a),
                     argnums=(0, 1, 2))(q, k, v)
    g_want = jax.grad(lambda *a: loss(attn_ops.causal_attention, *a),
                      argnums=(0, 1, 2))(q, k, v)
    for want_g, got_g, name in zip(g_want, g_got, "qkv"):
        np.testing.assert_allclose(
            np.asarray(got_g), np.asarray(want_g), rtol=2e-4, atol=2e-4,
            err_msg=f"d{name} mismatch (btd)",
        )


def test_btd_pack1_head_dim_128(monkeypatch):
    """hd=128 -> pack=1 (llama head dim): single-head cells, no pairing."""
    q, k, v = qkv(t=128, h=2, hd=128, seed=17)
    got_btd, got_bh = _both_layouts(q, k, v, monkeypatch)
    want = attn_ops.causal_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(got_btd), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(got_btd), np.asarray(got_bh),
                               rtol=1e-6, atol=1e-6)


def test_btd_window_softcap_grad_parity(monkeypatch):
    """Sliding window + logit softcap compose on the native-layout path,
    forward and backward (the mistral/gemma kernel features)."""
    q, k, v = qkv(t=256, seed=19)
    kw = dict(window=40, logit_softcap=30.0)
    got_btd, got_bh = _both_layouts(q, k, v, monkeypatch, **kw)
    want = attn_ops.causal_attention(q, k, v, **kw)
    np.testing.assert_allclose(np.asarray(got_btd), np.asarray(want),
                               rtol=1e-5, atol=1e-5)

    def loss(fn, q, k, v):
        return jnp.sum(jnp.square(fn(q, k, v, **kw)))

    monkeypatch.setenv("FLASH_LAYOUT", "auto")
    g_got = jax.grad(lambda *a: loss(flash.causal_attention, *a),
                     argnums=(0, 1, 2))(q, k, v)
    g_want = jax.grad(lambda *a: loss(attn_ops.causal_attention, *a),
                      argnums=(0, 1, 2))(q, k, v)
    for want_g, got_g, name in zip(g_want, g_got, "qkv"):
        np.testing.assert_allclose(
            np.asarray(got_g), np.asarray(want_g), rtol=2e-4, atol=2e-4,
            err_msg=f"d{name} mismatch (btd window+softcap)",
        )


def test_btd_gqa_grad_parity(monkeypatch):
    """GQA routes through repeat_kv OUTSIDE the custom vjp: autodiff must
    sum dk/dv over the query-head group exactly as the oracle does."""
    q, k, v = qkv(t=128, h=4, kv=2, seed=23)

    def loss(fn, q, k, v):
        return jnp.sum(jnp.square(fn(q, k, v)))

    monkeypatch.setenv("FLASH_LAYOUT", "auto")
    g_got = jax.grad(lambda *a: loss(flash.causal_attention, *a),
                     argnums=(0, 1, 2))(q, k, v)
    g_want = jax.grad(lambda *a: loss(attn_ops.causal_attention, *a),
                      argnums=(0, 1, 2))(q, k, v)
    for want_g, got_g, name in zip(g_want, g_got, "qkv"):
        np.testing.assert_allclose(
            np.asarray(got_g), np.asarray(want_g), rtol=2e-4, atol=2e-4,
            err_msg=f"d{name} mismatch (btd gqa)",
        )


def btd_backwards(q, k, v, block, window=None, softcap=None):
    """(dq, dk, dv) of sum(out**2) by each native-layout backward, called by
    name on one forward's residuals: ``(fused, split)``, each in the
    model's (B, T, H, hd). An odd head count is padded with zero heads up
    to the pack unit, as ``causal_attention`` pads it."""
    b, t, h, hd = q.shape
    unit = max(128 // hd, 1)
    hp = -(-h // unit) * unit
    flat = lambda x: jnp.pad(x.reshape(b, t, h * hd),
                             ((0, 0), (0, 0), (0, (hp - h) * hd)))
    q2, k2, v2 = flat(q), flat(k), flat(v)
    scale = 1.0 / np.sqrt(hd)
    out, lse = flash._flash_fwd_btd(q2, k2, v2, hp, scale, block,
                                    window=window, softcap=softcap)
    do = 2.0 * out
    args = (q2, k2, v2, do, lse, flash._btd_delta(out, do, hp), b, t, hd,
            flash._btd_pack(hp, hd), t // block, scale, block, window,
            softcap)
    unflat = lambda g: g[..., :h * hd].reshape(b, t, h, hd)
    return ([unflat(g) for g in flash._flash_bwd_btd_fused(*args)],
            [unflat(g) for g in flash._flash_bwd_btd_split(*args)])


#: what the rule (``_fused_bwd_fits``) now decides for every training
#: shape: nb = 2 carries the cross-kj dq slab, the parked dq out-spec flush
#: and the full-cell qi > kj branch; window + softcap every masked-cell
#: branch; nb = 1 the one diagonal cell; nb = 4 a slab written over three
#: outer sweeps; h = 3 the zero-head pad; hd = 128 one head a cell
FUSED_CASES = {
    "causal-nb2": dict(t=256, block=128),
    "window-softcap-nb2": dict(t=256, block=128, window=40, softcap=30.0),
    "nb1": dict(t=128, block=128),
    "nb4": dict(t=512, block=128),
    "nb4-window": dict(t=512, block=128, window=200),
    "odd-heads-pad": dict(t=256, block=128, h=3),
    "hd128-pack1": dict(t=256, block=128, h=2, hd=128),
    # PR 54: a block of 256 or more walks its diagonal cells as a staircase
    # in the fused kernel; the split pair keeps the whole-cell body, so the
    # two bodies are held to each other here
    "staircase-nb1": dict(t=256, block=256),
    "staircase-nb2": dict(t=512, block=256),
    "staircase-softcap-nb1": dict(t=512, block=512, h=2, hd=64,
                                  softcap=30.0),
    "staircase-hd128-odd-heads": dict(t=512, block=256, h=3, hd=128),
}


@pytest.mark.parametrize("case", FUSED_CASES)
def test_btd_fused_backward_parity(case):
    """The fused dq+dk+dv kernel must match the split kernels (to 1e-6:
    dq sums over k blocks in ascending order in both; to 1e-5 where the
    fused kernel's diagonal cells are a staircase, whose sums associate
    otherwise) AND the oracle."""
    kw = dict(FUSED_CASES[case])
    split_tol = 1e-5 if case.startswith("staircase") else 1e-6
    block, window, softcap = (kw.pop("block"), kw.pop("window", None),
                              kw.pop("softcap", None))
    q, k, v = qkv(seed=29, **kw)
    g_fused, g_split = btd_backwards(q, k, v, block, window, softcap)
    g_want = jax.grad(
        lambda *a: jnp.sum(jnp.square(attn_ops.causal_attention(
            *a, window=window, logit_softcap=softcap))),
        argnums=(0, 1, 2))(q, k, v)
    for want, fused, split, name in zip(g_want, g_fused, g_split, "qkv"):
        np.testing.assert_allclose(
            np.asarray(fused), np.asarray(want), rtol=2e-4, atol=2e-4,
            err_msg=f"d{name} fused-vs-oracle mismatch ({case})",
        )
        np.testing.assert_allclose(
            np.asarray(fused), np.asarray(split), rtol=split_tol,
            atol=split_tol,
            err_msg=f"d{name} fused-vs-split mismatch ({case})",
        )


@pytest.mark.parametrize("shape,fused", [
    ((1, 1024, 12, 64), True),      # the training cells' T: nb = 2
    ((1, 512, 3, 32), True),        # nb = 1, padded heads
    ((1, 8192, 1, 128), True),      # the scratch at its limit: 4 MiB
    ((1, 16384, 1, 128), False),    # 8 MiB: the split pair (traced only)
    ((1, 16384, 2, 64), False),
])
def test_backward_is_chosen_from_the_shape(shape, fused, monkeypatch):
    """One dq+dk+dv kernel wherever its dq slab fits the budget, the split
    pair beyond: by static shapes alone (traced, never run)."""
    monkeypatch.delenv("FLASH_BLOCK", raising=False)
    monkeypatch.delenv("FLASH_LAYOUT", raising=False)
    x = jax.ShapeDtypeStruct(shape, jnp.bfloat16)
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda q, k, v: jnp.sum(flash.causal_attention(q, k, v)
                                .astype(jnp.float32)),
        argnums=(0, 1, 2)))(x, x, x)
    calls = [len(pallas_calls(jaxpr, n)) for n in (
        "flash_fwd", "flash_bwd_fused", "flash_bwd_dq", "flash_bwd_dkv")]
    assert calls == ([1, 1, 0, 0] if fused else [1, 0, 1, 1])


# --- the forward's pair kept through a checkpoint (PR 64) --------------------


@pytest.mark.parametrize("layout", ["native", "bh"])
@pytest.mark.parametrize("n_head", [2, 3])     # 3: zero heads pad the pair
@pytest.mark.parametrize("unroll", [False, True])
@pytest.mark.parametrize("remat", [False, True])
def test_under_remat_the_forward_kernel_runs_once_a_layer(
        remat, unroll, n_head, layout, monkeypatch):
    """A layer under ``remat`` is computed again in the backward but for
    the two values the forward rules name (SAVED_OUT, SAVED_LSE), which
    ``gpt._remat``'s policy keeps: the gradient holds ``flash_fwd`` once a
    layer, where a bare ``jax.checkpoint`` held it twice, and the backward
    kernel once a layer, as the plain step does. A scanned stack holds a
    layer's calls once (the forward's scan and the backward's), an unrolled
    one once a layer. Traced, never run."""
    monkeypatch.delenv("FLASH_BLOCK", raising=False)
    monkeypatch.setenv("FLASH_LAYOUT", "bh" if layout == "bh" else "auto")
    layers = 2
    cfg = GPTConfig.make(
        n_layer=layers, n_head=n_head, n_embd=64 * n_head, vocab_size=65,
        block_size=128, dtype="float32", attention="flash", remat=remat,
        unroll_layers=unroll, embd_pdrop=0.0, resid_pdrop=0.0,
        attn_pdrop=0.0)
    params = jax.eval_shape(lambda: gpt.init(jax.random.key(0), cfg))
    tokens = jax.ShapeDtypeStruct((2, 128), jnp.int32)
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda p, tok: gpt.forward(p, tok, cfg, targets=tok)[1]))(
            params, tokens)
    backward = {"native": ["flash_bwd_fused"],
                "bh": ["flash_bwd_dq", "flash_bwd_dkv"]}[layout]
    calls = [len(pallas_calls(jaxpr, n)) for n in ["flash_fwd"] + backward]
    once = layers if unroll else 1
    assert calls == [once] * len(calls)
    # the pair is named (and its log-sum-exp laid out densely) only where a
    # checkpoint is there to keep it: the plain step is the parent's
    names = [str(e.params["name"]) for e in _equations(jaxpr.jaxpr)
             if e.primitive.name == "name"]
    assert sorted(names) == sorted(
        [flash.SAVED_OUT, flash.SAVED_LSE] * once if remat else [])


@pytest.mark.parametrize("n_head,layout", [(2, "native"), (3, "native"),
                                           (2, "bh")])
def test_the_kept_pair_changes_no_number(n_head, layout, monkeypatch):
    """``keep_pair`` hands the backward rule the log-sum-exp without its
    trailing 1 and tied to the output; the axis goes back on before the
    kernel: output and gradients are the plain call's bit for bit."""
    monkeypatch.delenv("FLASH_BLOCK", raising=False)
    monkeypatch.setenv("FLASH_LAYOUT", "bh" if layout == "bh" else "auto")
    q, k, v = qkv(b=1, t=256, h=n_head, hd=64, seed=11)

    def run(keep):
        return jax.value_and_grad(
            lambda q, k, v: jnp.sum(jnp.square(flash.causal_attention(
                q, k, v, keep_pair=keep))), argnums=(0, 1, 2))(q, k, v)

    jax.tree.map(np.testing.assert_array_equal, run(False), run(True))


# --- the staircase of a diagonal cell (PR 54) --------------------------------


def attention_grad_jaxpr(shape, **kw):
    x = jax.ShapeDtypeStruct(shape, jnp.bfloat16)
    return jax.make_jaxpr(jax.grad(
        lambda q, k, v: jnp.sum(flash.causal_attention(q, k, v, **kw)
                                .astype(jnp.float32)),
        argnums=(0, 1, 2)))(x, x, x)


@pytest.fixture
def no_flash_env(monkeypatch):
    monkeypatch.delenv("FLASH_BLOCK", raising=False)
    monkeypatch.delenv("FLASH_LAYOUT", raising=False)


@pytest.mark.parametrize("block,want", [
    (64, None), (128, None), (192, None), (256, 128), (320, None),
    (384, 128), (512, 128)])
def test_the_group_height_is_chosen_from_the_block(block, want):
    """The rule's table: blocks under 256 (and a block no whole number of
    groups tiles) keep the whole-cell body; 256 walks two groups of 128
    rows, 512 four. One constant, nothing a user sets."""
    assert flash.DIAG_GROUP_ROWS == 128
    assert flash._diag_group_rows(block) == want


@pytest.mark.parametrize("t,hd,groups", [
    (128, 64, 1), (128, 128, 1), (256, 64, 2), (256, 128, 2),
    (512, 64, 4), (512, 128, 4), (1024, 64, 4), (2048, 128, 4)])
def test_a_diagonal_cell_holds_the_staircases_matmuls(t, hd, groups,
                                                      no_flash_env):
    """The kernels' own jaxprs say what a cell does. A diagonal body of the
    forward holds 2 x pack x groups ``dot_general``s (a group's QK^T and
    PV) against a full cell's 2 x pack, the fused backward's
    5 x pack x groups against 5 x pack; at T = 128 (block 128) the one
    body is the parent's; where every cell is diagonal (nb 1) the full
    body is traced all the same."""
    pack = 128 // hd if hd < 128 else 1
    jaxpr = attention_grad_jaxpr((1, t, 2, hd))
    assert kernel_matmuls(jaxpr, "flash_fwd") == [
        [2 * pack * groups, 2 * pack]]
    assert kernel_matmuls(jaxpr, "flash_bwd_fused") == [
        [5 * pack * groups, 5 * pack]]


#: forward and gradient parity against the oracle through the public entry,
#: every case a staircase (block 256 or 512, no window): nb 1 at both
#: blocks, the training cells' shapes (a sequence of each), nb 4, one head
#: a cell, grouped keys, a soft-capped layer
STAIRCASE_CASES = {
    "nb1-T256": dict(t=256, h=2, hd=64),
    "nb1-T512": dict(t=512, h=2, hd=64),
    "nb2-124m-cell": dict(b=1, t=1024, h=12, hd=64),
    "nb2-xl-cell-25-to-26-heads": dict(b=1, t=1024, h=25, hd=64),
    "nb4": dict(b=1, t=2048, h=2, hd=64),
    "hd128-pack1": dict(b=1, t=1024, h=1, hd=128),
    "gqa": dict(b=1, t=512, h=4, kv=2, hd=64),
    "softcap": dict(b=1, t=512, h=2, hd=64, softcap=30.0),
}


@pytest.mark.parametrize("case", STAIRCASE_CASES)
def test_staircase_forward_and_grad_parity(case, no_flash_env):
    kw = dict(STAIRCASE_CASES[case])
    cap = kw.pop("softcap", None)
    q, k, v = qkv(seed=37, **kw)
    assert flash._diag_group_rows(flash._block_sizes(q.shape[1])) == 128

    def loss(fn, q, k, v):
        return jnp.sum(jnp.square(fn(q, k, v, logit_softcap=cap)))

    want = attn_ops.causal_attention(q, k, v, logit_softcap=cap)
    got = flash.causal_attention(q, k, v, logit_softcap=cap)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    g_got = jax.grad(lambda *a: loss(flash.causal_attention, *a),
                     argnums=(0, 1, 2))(q, k, v)
    g_want = jax.grad(lambda *a: loss(attn_ops.causal_attention, *a),
                      argnums=(0, 1, 2))(q, k, v)
    for want_g, got_g, name in zip(g_want, g_got, "qkv"):
        np.testing.assert_allclose(
            np.asarray(got_g), np.asarray(want_g), rtol=2e-4, atol=2e-4,
            err_msg=f"d{name} mismatch ({case})",
        )


def _split_pair_jaxpr(window):
    b, t, h, hd, block = 1, 1024, 2, 64, 512
    x = jax.ShapeDtypeStruct((b, t, h * hd), jnp.bfloat16)
    vec = jax.ShapeDtypeStruct((b, h, t, 1), jnp.float32)
    return jax.make_jaxpr(
        lambda q, k, v, do, lse, delta: flash._flash_bwd_btd_split(
            q, k, v, do, lse, delta, b, t, hd, 2, t // block, 0.125, block,
            window, None))(x, x, x, x, vec, vec)


def _ring_hop_jaxpr(q_offset, window):
    x = jax.ShapeDtypeStruct((4, 1024, 64), jnp.bfloat16)

    def loss(q, k, v):
        out, lse = flash.flash_with_lse(q, k, v, 0.125, 512, True, window,
                                        None, q_offset)
        return jnp.sum(out.astype(jnp.float32)) + jnp.sum(lse)

    return jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(x, x, x)


#: ``kernels_digest`` of what PR 54's parent traced, made on its tree: the
#: kernels the staircase leaves alone trace as before. Under each is the
#: forward and the backward of ``causal_attention`` at (1, T, H, hd)
#: bfloat16 unless it says otherwise.
PARENT_KERNEL_DIGESTS = {
    # blocks of 128: under the rule
    "block128-T128": (lambda: attention_grad_jaxpr((1, 128, 12, 64)),
                      "d75c7a7df54b81b4"),
    "block128-T384": (lambda: attention_grad_jaxpr((1, 384, 4, 64)),
                      "1c89ec6f7bd3934c"),
    # a windowed layer: a band's edge crosses cells
    "window-T1024": (lambda: attention_grad_jaxpr((1, 1024, 12, 64),
                                                  window=256),
                     "5e3a7e82ae38171a"),
    "window-softcap-T2048-hd128": (
        lambda: attention_grad_jaxpr((1, 2048, 2, 128), window=600,
                                     logit_softcap=30.0),
        "510599a5213c394b"),
    # the split dq / dkv pair, called by name at the cells' block
    "split-pair": (lambda: _split_pair_jaxpr(None), "8352eef7d5a8c7c9"),
    "split-pair-window": (lambda: _split_pair_jaxpr(300),
                          "64ee02e550e8b4b7"),
    # the (BH, T, hd) kernels: a head size the native layout cannot take,
    # and the ring's hops (q_offset != 0)
    "bh-layout": (lambda: attention_grad_jaxpr((1, 1024, 3, 48)),
                  "2403c93b403119e0"),
    "ring-hop": (lambda: _ring_hop_jaxpr(512, None), "9f4d51f4625ea84d"),
    "ring-hop-window": (lambda: _ring_hop_jaxpr(1024, 700),
                        "acdee39ba0650295"),
}
#: the parent's digest of the training cell's two kernels, which the
#: staircase changes on purpose
PARENT_CELL_DIGEST = "09089616b7747180"


@pytest.mark.parametrize("case", PARENT_KERNEL_DIGESTS)
def test_kernels_outside_the_staircase_trace_as_the_parents(case,
                                                            no_flash_env):
    make, want = PARENT_KERNEL_DIGESTS[case]
    assert kernels_digest(make()) == want


def test_the_training_cells_kernels_are_not_the_parents(no_flash_env):
    assert kernels_digest(
        attention_grad_jaxpr((1, 1024, 12, 64))) != PARENT_CELL_DIGEST


def test_btd_odd_head_count_pads(monkeypatch):
    """Odd H (gpt2-xl's 25 heads) takes the btd path via zero-head
    padding: forward and all grads must still match the oracle."""
    monkeypatch.setenv("FLASH_LAYOUT", "auto")
    q, k, v = qkv(t=128, h=3, hd=32, seed=31)

    def loss(fn, q, k, v):
        return jnp.sum(jnp.square(fn(q, k, v)))

    got = flash.causal_attention(q, k, v)
    want = attn_ops.causal_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    g_got = jax.grad(lambda *a: loss(flash.causal_attention, *a),
                     argnums=(0, 1, 2))(q, k, v)
    g_want = jax.grad(lambda *a: loss(attn_ops.causal_attention, *a),
                      argnums=(0, 1, 2))(q, k, v)
    for want_g, got_g, name in zip(g_want, g_got, "qkv"):
        np.testing.assert_allclose(
            np.asarray(got_g), np.asarray(want_g), rtol=2e-4, atol=2e-4,
            err_msg=f"d{name} mismatch (odd-H pad)",
        )
