"""Model-core tests — SURVEY §4's "do better" list: causality (the test that
would have caught B6), loss at init ≈ ln(vocab), shapes, ignore_index, llama
toggles, remat equivalence."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mingpt_distributed_tpu.config import GPTConfig
from mingpt_distributed_tpu.models import gpt
from program_digests import _equations


def small_cfg(**kw):
    base = dict(
        n_layer=2, n_head=2, n_embd=32, vocab_size=65, block_size=16,
        embd_pdrop=0.0, resid_pdrop=0.0, attn_pdrop=0.0, dtype="float32",
    )
    base.update(kw)
    return GPTConfig.make(**base)


def test_forward_shapes_and_loss_at_init():
    cfg = small_cfg()
    params = gpt.init(jax.random.key(0), cfg)
    tokens = jax.random.randint(jax.random.key(1), (4, 16), 0, cfg.vocab_size)
    logits, loss = gpt.forward(params, tokens, cfg, targets=tokens)
    assert logits.shape == (4, 16, 65)
    assert logits.dtype == jnp.float32
    # At init the model is ~uniform: CE ≈ ln(vocab_size).
    assert abs(float(loss) - np.log(65)) < 0.2


def test_causality():
    """Logits at position t must not change when tokens > t change (B6)."""
    cfg = small_cfg()
    params = gpt.init(jax.random.key(0), cfg)
    a = jax.random.randint(jax.random.key(1), (1, 16), 0, 65)
    b = a.at[:, 10:].set((a[:, 10:] + 7) % 65)  # perturb the future
    la, _ = gpt.forward(params, a, cfg)
    lb, _ = gpt.forward(params, b, cfg)
    np.testing.assert_allclose(la[:, :10], lb[:, :10], rtol=1e-5, atol=1e-5)
    # and the perturbed tail must actually differ (sanity of the test itself)
    assert not np.allclose(la[:, 10:], lb[:, 10:], atol=1e-5)


def test_ignore_index_masks_loss():
    cfg = small_cfg()
    params = gpt.init(jax.random.key(0), cfg)
    tokens = jax.random.randint(jax.random.key(1), (2, 16), 0, 65)
    targets_full = tokens
    targets_masked = targets_full.at[:, :8].set(-1)
    _, loss_full = gpt.forward(params, tokens, cfg, targets=targets_full)
    _, loss_masked = gpt.forward(params, tokens, cfg, targets=targets_masked)
    assert not np.isnan(float(loss_masked))
    assert float(loss_full) != float(loss_masked)
    # all-masked -> zero loss, no NaN (divide-by-zero guard)
    _, loss_none = gpt.forward(
        params, tokens, cfg, targets=jnp.full_like(tokens, -1)
    )
    assert float(loss_none) == 0.0


def test_dropout_train_vs_eval():
    cfg = small_cfg(embd_pdrop=0.5, resid_pdrop=0.5, attn_pdrop=0.5)
    params = gpt.init(jax.random.key(0), cfg)
    tokens = jax.random.randint(jax.random.key(1), (2, 16), 0, 65)
    l1, _ = gpt.forward(params, tokens, cfg, rng=jax.random.key(2), deterministic=False)
    l2, _ = gpt.forward(params, tokens, cfg, rng=jax.random.key(3), deterministic=False)
    le, _ = gpt.forward(params, tokens, cfg)
    assert not np.allclose(l1, l2)  # different dropout masks
    le2, _ = gpt.forward(params, tokens, cfg)
    np.testing.assert_array_equal(le, le2)  # eval is deterministic


@pytest.mark.parametrize("attention,t,unroll", [
    ("einsum", 16, False),
    ("flash", 128, False),   # a tileable T: the kernels run (interpret mode)
    ("flash", 128, True),
])
def test_remat_matches_plain(attention, t, unroll):
    """``remat`` changes what a step keeps, not what it computes. Under the
    flash kernels the saved pair (SAVED_OUT, SAVED_LSE) is what the forward
    made and everything else is made again by the same equations on the
    same inputs: layer by layer (``unroll_layers``) the loss and every
    gradient leaf are the plain step's bit for bit. A scanned stack's
    backward body is one program that XLA fuses anew around the
    recomputation, so there the gradients agree to rounding."""
    kw = dict(attention=attention, block_size=t, unroll_layers=unroll)
    cfg, cfg_r = small_cfg(**kw), small_cfg(remat=True, **kw)
    params = gpt.init(jax.random.key(0), cfg)
    tokens = jax.random.randint(jax.random.key(1), (2, t), 0, 65)

    def loss_of(c):
        def f(p):
            return gpt.forward(p, tokens, c, targets=tokens)[1]
        return f

    l0, g0 = jax.value_and_grad(loss_of(cfg))(params)
    l1, g1 = jax.value_and_grad(loss_of(cfg_r))(params)
    if unroll:
        assert float(l0) == float(l1)
        jax.tree.map(np.testing.assert_array_equal, g0, g1)
        return
    np.testing.assert_allclose(float(l0), float(l1), rtol=1e-6)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6), g0, g1
    )


@pytest.mark.parametrize("unroll,remat,want", [
    (False, False, (27, 2, 422)),
    (False, True, (34, 3, 534)),
    (True, False, (51, 3, 863)),
    (True, True, (65, 5, 1061)),
])
def test_remat_without_a_named_value_recomputes_the_whole_layer(
        unroll, remat, want):
    """The rule's other half: ``gpt._remat``'s policy saves two names, and a
    body that holds neither (the einsum attention) keeps nothing but its
    inputs. Its gradient's ``dot_general``s, ``exp``s and equations in all
    are PR 64's parent's, which wrapped the layer in a bare
    ``jax.checkpoint``: a PR that changes a layer's equations counts them
    again, and the ``remat`` rows move with the plain ones."""
    cfg = small_cfg(attention="einsum", remat=remat, unroll_layers=unroll)
    params = jax.eval_shape(lambda: gpt.init(jax.random.key(0), cfg))
    tokens = jax.ShapeDtypeStruct((2, 16), jnp.int32)
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda p, tok: gpt.forward(p, tok, cfg, targets=tok)[1]))(
            params, tokens)
    names = [e.primitive.name for e in _equations(jaxpr.jaxpr)]
    assert "name" not in names
    assert (names.count("dot_general"), names.count("exp"),
            len(names)) == want


def test_llama_mode_forward_and_causality():
    cfg = small_cfg(
        rope=True, swiglu=True, rmsnorm=True, n_kv_head=1, tie_weights=True,
    )
    params = gpt.init(jax.random.key(0), cfg)
    assert "wpe" not in params and "head" not in params
    assert "bq" not in params["blocks"] and "ln1_bias" not in params["blocks"]
    a = jax.random.randint(jax.random.key(1), (1, 16), 0, 65)
    b = a.at[:, 12:].set((a[:, 12:] + 3) % 65)
    la, loss = gpt.forward(params, a, cfg, targets=a)
    lb, _ = gpt.forward(params, b, cfg)
    np.testing.assert_allclose(la[:, :12], lb[:, :12], rtol=1e-5, atol=1e-5)
    # Tied weights correlate head with the input embedding in the residual
    # stream, so init loss sits a bit *below* ln(V) — just require sane.
    assert 2.0 < float(loss) < np.log(65) + 0.3


def test_seq_longer_than_block_rejected():
    cfg = small_cfg()
    params = gpt.init(jax.random.key(0), cfg)
    tokens = jnp.zeros((1, 32), dtype=jnp.int32)
    with pytest.raises(ValueError, match="block_size"):
        gpt.forward(params, tokens, cfg)


def test_param_count_gpt2_preset():
    # Shape-only init (eval_shape — no arrays) on the real preset.
    def count(cfg):
        shapes = jax.eval_shape(lambda k: gpt.init(k, cfg), jax.random.key(0))
        return sum(int(np.prod(s.shape)) for s in jax.tree.leaves(shapes))

    # Weight-tied: the canonical "124M" (124,439,808 exactly).
    assert count(GPTConfig.make(model_type="gpt2", tie_weights=True)) == 124439808
    # Untied (the reference's separate bias-free head, model.py:249): +V*D.
    assert count(GPTConfig.make(model_type="gpt2")) == 124439808 + 50257 * 768


def test_gradients_flow_everywhere():
    cfg = small_cfg()
    params = gpt.init(jax.random.key(0), cfg)
    tokens = jax.random.randint(jax.random.key(1), (2, 16), 0, 65)
    g = jax.grad(lambda p: gpt.forward(p, tokens, cfg, targets=tokens)[1])(params)
    zero_leaves = [
        path for path, leaf in jax.tree_util.tree_leaves_with_path(g)
        if float(jnp.abs(leaf).max()) == 0.0
    ]
    assert not zero_leaves, f"dead params: {zero_leaves}"


def test_chunked_cross_entropy_matches_dense():
    """loss_chunks>1 must be loss- and grad-equivalent to the dense head
    (it is the same math, computed per sequence chunk under jax.checkpoint
    so the (B, T, V) logits never materialise whole)."""
    import dataclasses

    cfg_d = dataclasses.replace(small_cfg(), loss_chunks=0)
    cfg_c = dataclasses.replace(small_cfg(), loss_chunks=4)
    params = gpt.init(jax.random.key(0), cfg_d)
    tokens = jax.random.randint(jax.random.key(1), (4, 16), 0, 65)
    tgt = tokens.at[0, :3].set(-1)  # exercise ignore_index in both paths

    _, l_d = gpt.forward(params, tokens, cfg_d, targets=tgt)
    _, l_c = gpt.forward(params, tokens, cfg_c, targets=tgt,
                         return_logits=False)
    assert abs(float(l_d) - float(l_c)) < 1e-6

    g_d = jax.grad(lambda p: gpt.forward(p, tokens, cfg_d, targets=tgt)[1])(params)
    g_c = jax.grad(
        lambda p: gpt.forward(p, tokens, cfg_c, targets=tgt,
                              return_logits=False)[1]
    )(params)
    for a, b in zip(jax.tree.leaves(g_d), jax.tree.leaves(g_c)):
        np.testing.assert_allclose(a, b, atol=1e-6)


def test_chunked_cross_entropy_unrolled_matches_dense():
    """The unrolled chunk loop (cfg.unroll_layers threads into
    chunked_cross_entropy) must match the dense head exactly, loss and
    grads, including ignore_index handling."""
    import dataclasses

    cfg_d = dataclasses.replace(small_cfg(), loss_chunks=0)
    cfg_u = dataclasses.replace(small_cfg(), loss_chunks=4,
                                unroll_layers=True)
    params = gpt.init(jax.random.key(0), cfg_d)
    tokens = jax.random.randint(jax.random.key(1), (4, 16), 0, 65)
    tgt = tokens.at[0, :3].set(-1)

    _, l_d = gpt.forward(params, tokens, cfg_d, targets=tgt)
    _, l_u = gpt.forward(params, tokens, cfg_u, targets=tgt,
                         return_logits=False)
    assert abs(float(l_d) - float(l_u)) < 1e-6

    g_d = jax.grad(lambda p: gpt.forward(p, tokens, cfg_d, targets=tgt)[1])(params)
    g_u = jax.grad(
        lambda p: gpt.forward(p, tokens, cfg_u, targets=tgt,
                              return_logits=False)[1]
    )(params)
    for a, b in zip(jax.tree.leaves(g_d), jax.tree.leaves(g_u)):
        np.testing.assert_allclose(a, b, atol=1e-6)


def test_chunked_cross_entropy_indivisible_t_snaps_to_divisor():
    """loss_chunks=7 with T=16 snaps to 4 chunks (largest divisor <= 7) —
    never silently dense — and the loss is unchanged; a prime T (no
    divisor > 1) degrades to the dense head, also unchanged."""
    import dataclasses

    cfg = dataclasses.replace(small_cfg(), loss_chunks=7)
    cfg_dense = dataclasses.replace(small_cfg(), loss_chunks=0)
    params = gpt.init(jax.random.key(0), cfg)
    tokens = jax.random.randint(jax.random.key(1), (2, 16), 0, 65)
    _, loss = gpt.forward(params, tokens, cfg, targets=tokens,
                          return_logits=False)
    _, want = gpt.forward(params, tokens, cfg_dense, targets=tokens)
    assert abs(float(loss) - float(want)) < 1e-6

    cfg13 = dataclasses.replace(
        small_cfg(block_size=13), loss_chunks=8)
    cfg13_dense = dataclasses.replace(
        small_cfg(block_size=13), loss_chunks=0)
    params13 = gpt.init(jax.random.key(0), cfg13)
    toks13 = jax.random.randint(jax.random.key(1), (2, 13), 0, 65)
    _, l13 = gpt.forward(params13, toks13, cfg13, targets=toks13,
                         return_logits=False)
    _, w13 = gpt.forward(params13, toks13, cfg13_dense, targets=toks13)
    assert abs(float(l13) - float(w13)) < 1e-6


@pytest.mark.parametrize("unroll, per_grad, per_call", [
    (False, 3, 1), (True, 12, 4)], ids=["scan", "unrolled"])
@pytest.mark.parametrize("cap", [None, 30.0], ids=["nocap", "cap30"])
def test_chunked_cross_entropy_holds_three_head_matmuls_a_chunk(
        unroll, per_grad, per_call, cap):
    """The mechanism's counter (ISSUE 56): differentiated, a chunk's logits
    are computed once and its gradient taken in the forward sweep, so the
    program holds the matmul, ``dx`` and the addend to ``dW`` and nothing
    recomputed (the scan's body once, the unrolled loop at 4 chunks 12;
    under ``jax.checkpoint`` these read 4 and 16); not differentiated it
    holds the one matmul a chunk. Unrolled, a barrier stands between a
    chunk and the next, or nothing keeps the scheduler from computing every
    chunk's logits before it uses the first."""
    b, t, d, v = 2, 16, 8, 40
    x = jnp.zeros((b, t, d), jnp.bfloat16)
    w = jnp.zeros((d, v), jnp.bfloat16)
    tgt = jnp.zeros((b, t), jnp.int32)
    loss = lambda x, w: gpt.chunked_cross_entropy(
        x, w, tgt, 4, softcap=cap, unroll=unroll)

    def counts(jaxpr):
        names = [eqn.primitive.name for eqn in _equations(jaxpr)
                 if eqn.primitive.name != "dot_general" or any(
                     v in var.aval.shape
                     for var in (*eqn.invars, *eqn.outvars))]
        return (names.count("dot_general"),
                names.count("optimization_barrier"),
                [n for n in names if "checkpoint" in n or "remat" in n])

    barriers = 3 if unroll else 0
    assert counts(jax.make_jaxpr(loss)(x, w).jaxpr) == (per_call, barriers, [])
    grad = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1)))(x, w)
    assert counts(grad.jaxpr) == (per_grad, barriers, [])
    # both rules stand under the ``ce`` mark, the backward's two products
    # too (model.train_ce_ms_per_step reads all of it)
    for eqn in grad.jaxpr.eqns:
        assert "ce" in re.split(r"[/()]", str(eqn.source_info.name_stack)), eqn


@pytest.mark.parametrize("tied", [False, True], ids=["untied", "tied"])
@pytest.mark.parametrize("cap", [None, 30.0], ids=["nocap", "cap30"])
@pytest.mark.parametrize("unroll", [False, True], ids=["scan", "unrolled"])
def test_chunked_cross_entropy_gradients_match_dense(unroll, cap, tied):
    """Loss and the gradients of ``x``, the head and, tied, ``wte`` (through
    the transpose) equal the dense ``cross_entropy`` path's: with some
    targets -1, with all of them -1 (loss 0, gradients 0, no NaN), under a
    cotangent other than 1, and in the primals' dtypes under bfloat16."""
    from mingpt_distributed_tpu.ops import attention as attn_ops

    b, t, d, v = 4, 16, 32, 65
    k = jax.random.split(jax.random.key(3), 3)
    x = jax.random.normal(k[0], (b, t, d))
    table = 0.3 * jax.random.normal(k[1], (v, d) if tied else (d, v))
    tgt = jax.random.randint(k[2], (b, t), 0, v).at[0, :3].set(-1)

    def head(x, table):
        return (table.T if tied else table).astype(x.dtype)

    def dense(x, table, tgt, scale=1.0):
        logits = jnp.einsum("btd,dv->btv", x, head(x, table),
                            preferred_element_type=jnp.float32)
        return scale * gpt.cross_entropy(attn_ops.softcap(logits, cap), tgt)

    def chunked(x, table, tgt, scale=1.0, shards=1):
        return scale * gpt.chunked_cross_entropy(
            x, head(x, table), tgt, 4, softcap=cap, unroll=unroll,
            batch_shards=shards)

    both = lambda f, *a: jax.value_and_grad(f, argnums=(0, 1))(*a)
    for scale in (1.0, 3.0):
        want, got = both(dense, x, table, tgt, scale), \
            both(chunked, x, table, tgt, scale)
        for a, c in zip(jax.tree.leaves(want), jax.tree.leaves(got)):
            np.testing.assert_allclose(c, a, rtol=1e-5, atol=1e-6)

    # dW carried a slab a batch shard (2 of a batch of 4; 3 does not divide
    # it and falls back to one) sums to the same gradient
    want = both(dense, x, table, tgt)
    for shards in (2, 3):
        got = both(lambda *a: chunked(*a, shards=shards), x, table, tgt)
        for a, c in zip(jax.tree.leaves(want), jax.tree.leaves(got)):
            np.testing.assert_allclose(c, a, rtol=1e-5, atol=1e-6)

    loss, grads = both(chunked, x, table, jnp.full_like(tgt, -1))
    assert float(loss) == 0.0
    assert all(not np.asarray(g).any() for g in grads)

    # bfloat16 activations over float32 master weights, as the trainer runs
    xb = x.astype(jnp.bfloat16)
    want, got = both(dense, xb, table, tgt), both(chunked, xb, table, tgt)
    assert got[1][0].dtype == jnp.bfloat16 and got[1][1].dtype == jnp.float32
    assert got[1][1].shape == table.shape
    for a, c in zip(jax.tree.leaves(want), jax.tree.leaves(got)):
        np.testing.assert_allclose(
            np.asarray(c, np.float32), np.asarray(a, np.float32),
            rtol=2 ** -7, atol=1e-5)
    # a bfloat16 head gets a bfloat16 gradient
    gw = jax.grad(chunked, argnums=1)(xb, table.astype(jnp.bfloat16), tgt)
    assert gw.dtype == jnp.bfloat16


def test_loss_only_mode_returns_no_logits():
    """return_logits=False -> (None, loss); loss matches the dense path."""
    cfg = small_cfg()
    params = gpt.init(jax.random.key(0), cfg)
    tokens = jax.random.randint(jax.random.key(1), (2, 16), 0, 65)
    logits, loss = gpt.forward(params, tokens, cfg, targets=tokens,
                               return_logits=False)
    assert logits is None
    logits_d, loss_d = gpt.forward(params, tokens, cfg, targets=tokens)
    assert logits_d.shape == (2, 16, 65)
    assert abs(float(loss) - float(loss_d)) < 1e-6


def test_unroll_layers_matches_scan():
    """cfg.unroll_layers replaces the layer lax.scan with a static python
    loop (round-4 perf: removes the scan's DUS activation stacking) — it
    must be semantically invisible: same logits, same loss, same grads."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from mingpt_distributed_tpu.config import GPTConfig
    from mingpt_distributed_tpu.models import gpt

    base = dict(
        n_layer=3, n_head=2, n_embd=32, vocab_size=64, block_size=16,
        embd_pdrop=0.0, resid_pdrop=0.0, attn_pdrop=0.0, dtype="float32",
    )
    cfg_scan = GPTConfig.make(**base)
    cfg_unroll = GPTConfig.make(**base, unroll_layers=True)
    params = gpt.init(jax.random.key(0), cfg_scan)
    tokens = jax.random.randint(jax.random.key(1), (2, 16), 0, 64)

    logits_a, loss_a = gpt.forward(params, tokens, cfg_scan, targets=tokens)
    logits_b, loss_b = gpt.forward(params, tokens, cfg_unroll,
                                   targets=tokens)
    np.testing.assert_allclose(np.asarray(logits_b), np.asarray(logits_a),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(float(loss_b), float(loss_a), rtol=1e-6)

    g_a = jax.grad(lambda p: gpt.forward(p, tokens, cfg_scan,
                                         targets=tokens)[1])(params)
    g_b = jax.grad(lambda p: gpt.forward(p, tokens, cfg_unroll,
                                         targets=tokens)[1])(params)
    for (pa, a), b in zip(
        jax.tree_util.tree_leaves_with_path(g_a), jax.tree.leaves(g_b)
    ):
        np.testing.assert_allclose(
            np.asarray(b), np.asarray(a), rtol=1e-4, atol=1e-5,
            err_msg=f"grad mismatch at {jax.tree_util.keystr(pa)}",
        )

    # dropout path: keys are split identically, so training-mode forward
    # with the same rng must match exactly as well
    cfg_s2 = GPTConfig.make(**{**base, "resid_pdrop": 0.3})
    cfg_u2 = GPTConfig.make(**{**base, "resid_pdrop": 0.3},
                            unroll_layers=True)
    la, _ = gpt.forward(params, tokens, cfg_s2, rng=jax.random.key(5),
                        deterministic=False)
    lb, _ = gpt.forward(params, tokens, cfg_u2, rng=jax.random.key(5),
                        deterministic=False)
    np.testing.assert_allclose(np.asarray(lb), np.asarray(la),
                               rtol=1e-5, atol=1e-5)
