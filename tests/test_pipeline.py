"""Pipeline parallelism (pp mesh axis, parallel/pipeline.py): the GPipe
microbatch schedule must be semantically invisible — logits, grads and loss
trajectories identical to the dense single-device scan. Reference has no PP
at all (SURVEY §2.2: nn.Sequential on one device, model.py:245-246)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mingpt_distributed_tpu.config import GPTConfig, MeshConfig
from mingpt_distributed_tpu.models import gpt
from mingpt_distributed_tpu.parallel import mesh as mesh_lib
from program_digests import forward_digest


def cfg_and_inputs(n_layer=4, batch=8, **kw):
    base = dict(
        n_layer=n_layer, n_head=2, n_embd=32, vocab_size=64, block_size=16,
        embd_pdrop=0.0, resid_pdrop=0.0, attn_pdrop=0.0, dtype="float32",
    )
    base.update(kw)
    cfg = GPTConfig.make(**base)
    params = gpt.init(jax.random.key(0), cfg)
    tokens = jax.random.randint(jax.random.key(1), (batch, 16), 0, 64)
    return cfg, params, tokens


def pp_mesh(eight_devices, pp, dp):
    n = pp * dp
    return mesh_lib.make_mesh(
        MeshConfig(pp=pp, dp=dp, fsdp=1, tp=1, sp=1),
        devices=eight_devices[:n],
    )


def test_pp_forward_matches_dense(eight_devices):
    cfg, params, tokens = cfg_and_inputs()
    want_logits, want_loss = gpt.forward(params, tokens, cfg, targets=tokens)
    mesh = pp_mesh(eight_devices, pp=4, dp=2)
    got_logits, got_loss = jax.jit(
        lambda p, t: gpt.forward(p, t, cfg, targets=t, mesh=mesh)
    )(params, tokens)
    np.testing.assert_allclose(
        np.asarray(got_logits), np.asarray(want_logits), rtol=2e-4, atol=2e-4
    )
    np.testing.assert_allclose(
        float(got_loss), float(want_loss), rtol=1e-5
    )


def test_pp_gradients_match_dense(eight_devices):
    cfg, params, tokens = cfg_and_inputs()
    mesh = pp_mesh(eight_devices, pp=4, dp=2)

    def loss_fn(p, m):
        return gpt.forward(p, tokens, cfg, targets=tokens, mesh=m)[1]

    g_want = jax.grad(lambda p: loss_fn(p, None))(params)
    g_got = jax.jit(jax.grad(lambda p: loss_fn(p, mesh)))(params)
    flat_want = jax.tree_util.tree_leaves_with_path(g_want)
    flat_got = jax.tree.leaves(g_got)
    for (path, want), got in zip(flat_want, flat_got):
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=5e-4, atol=5e-4,
            err_msg=f"grad mismatch at {jax.tree_util.keystr(path)}",
        )


def test_pp_more_microbatches_than_stages(eight_devices):
    """M > pp shrinks the bubble; semantics must not change."""
    cfg, params, tokens = cfg_and_inputs(n_layer=2, pp_microbatches=4)
    want_logits, _ = gpt.forward(params, tokens, cfg, targets=tokens)
    mesh = pp_mesh(eight_devices, pp=2, dp=2)
    got_logits, _ = jax.jit(
        lambda p, t: gpt.forward(p, t, cfg, targets=t, mesh=mesh)
    )(params, tokens)
    np.testing.assert_allclose(
        np.asarray(got_logits), np.asarray(want_logits), rtol=2e-4, atol=2e-4
    )


def test_pp_rope_llama_mode(eight_devices):
    """RoPE tables are shard_map consts; llama toggles must survive pp."""
    cfg, params, tokens = cfg_and_inputs(
        rope=True, swiglu=True, rmsnorm=True, n_kv_head=1, tie_weights=True
    )
    want_logits, _ = gpt.forward(params, tokens, cfg)
    mesh = pp_mesh(eight_devices, pp=4, dp=2)
    got_logits, _ = jax.jit(lambda p, t: gpt.forward(p, t, cfg, mesh=mesh))(
        params, tokens
    )
    np.testing.assert_allclose(
        np.asarray(got_logits), np.asarray(want_logits), rtol=2e-4, atol=2e-4
    )


def test_pp_dropout_decorrelated_across_microbatches(eight_devices):
    """With identical rows everywhere, dropout masks must DIFFER between
    microbatches — a shared per-layer key applied to every microbatch would
    make row i of microbatch 0 equal row i of microbatch 1."""
    cfg, params, _ = cfg_and_inputs(
        n_layer=2, resid_pdrop=0.5, pp_microbatches=2
    )
    tokens = jnp.tile(jnp.arange(16, dtype=jnp.int32)[None], (8, 1))
    mesh = pp_mesh(eight_devices, pp=2, dp=1)
    logits, _ = jax.jit(
        lambda p, t, r: gpt.forward(
            p, t, cfg, rng=r, deterministic=False, mesh=mesh
        )
    )(params, tokens, jax.random.key(3))
    la = np.asarray(logits)
    # rows within one microbatch share the mb but not the mask row -> differ;
    # the regression: row 0 (mb 0) vs row 4 (mb 1) must also differ
    assert not np.allclose(la[0], la[4], atol=1e-6)


def test_pp_layer_indivisible_rejected(eight_devices):
    cfg, params, tokens = cfg_and_inputs(n_layer=3)
    mesh = pp_mesh(eight_devices, pp=4, dp=2)
    with pytest.raises(ValueError, match="not divisible by pp"):
        gpt.forward(params, tokens, cfg, mesh=mesh)


def test_pp_trainer_matches_dp(tmp_path, eight_devices):
    """Full jitted train step through GPTTrainer: a pp=2 x dp=2 mesh must
    reproduce the pure-DP loss trajectory (same global batch, same seed)."""
    from tests.test_trainer import losses_for

    l_dp = losses_for(tmp_path, MeshConfig(dp=-1), name="pp_a")
    l_pp = losses_for(tmp_path, MeshConfig(pp=2, dp=2, fsdp=1), name="pp_b")
    np.testing.assert_allclose(l_dp, l_pp, rtol=2e-4, atol=2e-4)


def test_pp_params_sharded_by_stage(tmp_path, eight_devices):
    from tests.test_trainer import make_trainer

    tr = make_trainer(
        tmp_path, mesh_cfg=MeshConfig(pp=2, dp=2, fsdp=1), snapshot="pp_c"
    )
    wq = tr.state["params"]["blocks"]["wq"]  # (n_layer, d, nh*hd)
    # layer axis split over 2 stages
    shard = wq.addressable_shards[0].data
    assert shard.shape[0] == wq.shape[0] // 2


def test_pp_with_ring_sp_matches_dense(eight_devices):
    """pp=2 x sp=4: ring attention runs INSIDE the pipeline's manual region
    (sequence stays sharded stage-to-stage) — logits/loss must match the
    dense single-device forward."""
    cfg, params, tokens = cfg_and_inputs(attention="ring")
    want_logits, want_loss = gpt.forward(params, tokens, cfg, targets=tokens)
    mesh = mesh_lib.make_mesh(
        MeshConfig(pp=2, dp=1, fsdp=1, tp=1, sp=4), devices=eight_devices
    )
    got_logits, got_loss = jax.jit(
        lambda p, t: gpt.forward(p, t, cfg, targets=t, mesh=mesh)
    )(params, tokens)
    np.testing.assert_allclose(
        np.asarray(got_logits), np.asarray(want_logits), rtol=2e-4, atol=2e-4
    )
    np.testing.assert_allclose(float(got_loss), float(want_loss), rtol=1e-5)


def test_pp_with_ulysses_sp_matches_dense(eight_devices):
    """pp=2 x sp=2 with Ulysses all-to-all inside the stages."""
    cfg, params, tokens = cfg_and_inputs(attention="ulysses")
    want_logits, want_loss = gpt.forward(params, tokens, cfg, targets=tokens)
    mesh = mesh_lib.make_mesh(
        MeshConfig(pp=2, dp=2, fsdp=1, tp=1, sp=2), devices=eight_devices
    )
    got_logits, got_loss = jax.jit(
        lambda p, t: gpt.forward(p, t, cfg, targets=t, mesh=mesh)
    )(params, tokens)
    np.testing.assert_allclose(
        np.asarray(got_logits), np.asarray(want_logits), rtol=2e-4, atol=2e-4
    )
    np.testing.assert_allclose(float(got_loss), float(want_loss), rtol=1e-5)


def test_pp_with_ring_sp_gradients(eight_devices):
    cfg, params, tokens = cfg_and_inputs(attention="ring")
    mesh = mesh_lib.make_mesh(
        MeshConfig(pp=2, dp=1, fsdp=1, tp=1, sp=4), devices=eight_devices
    )
    g_want = jax.grad(
        lambda p: gpt.forward(p, tokens, cfg, targets=tokens)[1]
    )(params)
    g_got = jax.jit(jax.grad(
        lambda p: gpt.forward(p, tokens, cfg, targets=tokens, mesh=mesh)[1]
    ))(params)
    for a, b in zip(jax.tree.leaves(g_got), jax.tree.leaves(g_want)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4)


def test_pp_with_moe_matches_no_pp(eight_devices):
    """pp=2 x MoE (ep=1, experts replicated per stage): loss — including the
    load-balancing aux — matches the same model without pipeline stages.
    capacity_factor is generous so no tokens drop and routing is identical
    regardless of microbatch grouping."""
    cfg, params, tokens = cfg_and_inputs(
        n_experts=2, moe_top_k=1, moe_capacity_factor=4.0
    )
    _, want_loss = gpt.forward(params, tokens, cfg, targets=tokens)
    mesh = mesh_lib.make_mesh(
        MeshConfig(pp=2, dp=4, fsdp=1, tp=1, sp=1), devices=eight_devices
    )
    _, got_loss = jax.jit(
        lambda p, t: gpt.forward(p, t, cfg, targets=t, mesh=mesh)
    )(params, tokens)
    # fp32 reassociation only: router means are computed over per-microbatch
    # groups (16 tokens) vs one 128-token group dense — same math
    np.testing.assert_allclose(float(got_loss), float(want_loss), rtol=1e-4)


def test_pp_with_ep_matches_no_pp(eight_devices):
    """pp=2 x ep=2: experts stay SHARDED inside the
    pipeline region (xs_specs keeps the ep axis on w_e* leaves) and the
    MoE runs manual expert parallelism (two all_to_alls, ops/moe.py
    ep_axis) — the loss must match the dense no-mesh model. Generous
    capacity so routing is grouping-invariant, as in the ep=1 pp test."""
    cfg, params, tokens = cfg_and_inputs(
        n_experts=2, moe_top_k=1, moe_capacity_factor=4.0
    )
    _, want_loss = gpt.forward(params, tokens, cfg, targets=tokens)
    mesh = mesh_lib.make_mesh(
        MeshConfig(pp=2, dp=2, fsdp=1, tp=1, sp=1, ep=2),
        devices=eight_devices,
    )
    _, got_loss = jax.jit(
        lambda p, t: gpt.forward(p, t, cfg, targets=t, mesh=mesh)
    )(params, tokens)
    np.testing.assert_allclose(float(got_loss), float(want_loss), rtol=1e-4)


@pytest.mark.mid
def test_pp_with_ep_keeps_experts_sharded_in_region(eight_devices,
                                                    monkeypatch):
    """The in-region sharding assert: inside the pp x ep region each shard
    must hold E/ep experts (w_e1 leading dim), not gathered copies —
    captured from the moe_mlp call the pipeline's stage body makes."""
    from mingpt_distributed_tpu.ops import moe as moe_mod

    seen = []
    real = moe_mod.moe_mlp

    def capture(x, w_router, w_e1, w_e2, **kw):
        seen.append({"w_e1": tuple(w_e1.shape),
                     "router_e": w_router.shape[1],
                     "ep_axis": kw.get("ep_axis")})
        return real(x, w_router, w_e1, w_e2, **kw)

    monkeypatch.setattr(moe_mod, "moe_mlp", capture)
    cfg, params, tokens = cfg_and_inputs(
        n_experts=2, moe_top_k=1, moe_capacity_factor=4.0
    )
    mesh = mesh_lib.make_mesh(
        MeshConfig(pp=2, dp=2, fsdp=1, tp=1, sp=1, ep=2),
        devices=eight_devices,
    )
    gpt.forward(params, tokens, cfg, targets=tokens, mesh=mesh)
    assert seen, "moe_mlp never called inside the pipeline"
    for rec in seen:
        assert rec["ep_axis"] == "ep"
        assert rec["w_e1"][0] == 1, rec  # E/ep = 2/2 local experts
        assert rec["router_e"] == 2, rec  # router sees ALL experts


def test_pp_ep_gradients_match_dense(eight_devices):
    """Gradients through the manual-ep MoE inside pipeline stages (a2a
    transpose + router gradient + aux) must match the dense model."""
    cfg, params, tokens = cfg_and_inputs(
        n_experts=2, moe_top_k=1, moe_capacity_factor=4.0
    )
    mesh = mesh_lib.make_mesh(
        MeshConfig(pp=2, dp=2, fsdp=1, tp=1, sp=1, ep=2),
        devices=eight_devices,
    )

    def loss_fn(p, m):
        return gpt.forward(p, tokens, cfg, targets=tokens, mesh=m)[1]

    g_want = jax.grad(lambda p: loss_fn(p, None))(params)
    g_got = jax.jit(jax.grad(lambda p: loss_fn(p, mesh)))(params)
    for a, b in zip(jax.tree.leaves(g_got), jax.tree.leaves(g_want)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4)


def test_pp_ep_indivisible_experts_refused(eight_devices):
    cfg, params, tokens = cfg_and_inputs(
        n_experts=3, moe_top_k=1, moe_capacity_factor=4.0
    )
    mesh = mesh_lib.make_mesh(
        MeshConfig(pp=2, dp=2, fsdp=1, tp=1, sp=1, ep=2),
        devices=eight_devices,
    )
    with pytest.raises(ValueError, match="not divisible by ep"):
        gpt.forward(params, tokens, cfg, targets=tokens, mesh=mesh)


def test_pp_tp_forward_matches_dense(eight_devices):
    """pp=2 x tp=2 x dp=2: megatron-tp runs INSIDE the pipeline stages
    (per-shard heads/ffn columns, one psum per residual branch) — logits
    and loss must match the dense single-device forward."""
    cfg, params, tokens = cfg_and_inputs()
    want_logits, want_loss = gpt.forward(params, tokens, cfg, targets=tokens)
    mesh = mesh_lib.make_mesh(
        MeshConfig(pp=2, dp=2, fsdp=1, tp=2, sp=1), devices=eight_devices
    )
    got_logits, got_loss = jax.jit(
        lambda p, t: gpt.forward(p, t, cfg, targets=t, mesh=mesh)
    )(params, tokens)
    np.testing.assert_allclose(
        np.asarray(got_logits), np.asarray(want_logits), rtol=2e-4, atol=2e-4
    )
    np.testing.assert_allclose(float(got_loss), float(want_loss), rtol=1e-5)


def test_pp_tp_gradients_match_dense(eight_devices):
    cfg, params, tokens = cfg_and_inputs()
    mesh = mesh_lib.make_mesh(
        MeshConfig(pp=2, dp=2, fsdp=1, tp=2, sp=1), devices=eight_devices
    )
    g_want = jax.grad(
        lambda p: gpt.forward(p, tokens, cfg, targets=tokens)[1]
    )(params)
    g_got = jax.jit(jax.grad(
        lambda p: gpt.forward(p, tokens, cfg, targets=tokens, mesh=mesh)[1]
    ))(params)
    flat_want = jax.tree_util.tree_leaves_with_path(g_want)
    for (path, want), got in zip(flat_want, jax.tree.leaves(g_got)):
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=5e-4, atol=5e-4,
            err_msg=f"grad mismatch at {jax.tree_util.keystr(path)}",
        )


def test_pp_tp_swiglu_llama_mode(eight_devices):
    """tp inside pp with the llama toggles (SwiGLU row/column split, RoPE,
    GQA kv_heads split over tp)."""
    cfg, params, tokens = cfg_and_inputs(
        rope=True, swiglu=True, rmsnorm=True, tie_weights=True
    )
    want_logits, want_loss = gpt.forward(params, tokens, cfg, targets=tokens)
    mesh = mesh_lib.make_mesh(
        MeshConfig(pp=2, dp=2, fsdp=1, tp=2, sp=1), devices=eight_devices
    )
    got_logits, got_loss = jax.jit(
        lambda p, t: gpt.forward(p, t, cfg, targets=t, mesh=mesh)
    )(params, tokens)
    np.testing.assert_allclose(
        np.asarray(got_logits), np.asarray(want_logits), rtol=2e-4, atol=2e-4
    )
    np.testing.assert_allclose(float(got_loss), float(want_loss), rtol=1e-5)


def test_pp_tp_fsdp_params_stay_sharded_inside_region(
    eight_devices, monkeypatch
):
    """The memory assertion: inside the pipeline's manual
    region, tp must still be SPLIT on the weights _block actually computes
    with (not gathered at entry), and fsdp must be gathered per-layer at
    point of use. Shapes are recorded at trace time inside the region."""
    cfg, params, tokens = cfg_and_inputs()  # n_head=2, d=32 -> nhd=32
    mesh = mesh_lib.make_mesh(
        MeshConfig(pp=2, dp=1, fsdp=2, tp=2, sp=1), devices=eight_devices
    )
    seen = {}
    real_block = gpt._block

    def recording_block(x, blk, *a, **kw):
        seen["wq"] = blk["wq"].shape
        seen["w_fc"] = blk["w_fc"].shape
        seen["wo"] = blk["wo"].shape
        return real_block(x, blk, *a, **kw)

    monkeypatch.setattr(gpt, "_block", recording_block)
    _, loss = jax.jit(
        lambda p, t: gpt.forward(p, t, cfg, targets=t, mesh=mesh)
    )(params, tokens)

    d, nhd, ffn = 32, 32, 128
    # tp LIVE inside the region: output columns halved on column-parallel
    # weights, input rows halved on row-parallel weights...
    assert seen["wq"] == (d, nhd // 2), seen
    assert seen["w_fc"] == (d, ffn // 2), seen
    assert seen["wo"] == (nhd // 2, d), seen
    # ...and the fsdp factor is GONE at point of use (per-layer JIT gather
    # restored the full d rows: sharded at rest, whole only while computing)
    assert np.isfinite(float(loss))


def test_pp_tp_trainer_matches_dp(tmp_path, eight_devices):
    """Full jitted train step: pp=2 x tp=2 x dp=2 must reproduce the
    pure-DP loss trajectory."""
    from tests.test_trainer import losses_for

    l_dp = losses_for(tmp_path, MeshConfig(dp=-1), name="pt_a")
    l_pptp = losses_for(
        tmp_path, MeshConfig(pp=2, dp=2, fsdp=1, tp=2), name="pt_b"
    )
    np.testing.assert_allclose(l_dp, l_pptp, rtol=2e-4, atol=2e-4)


def test_1f1b_forward_matches_dense(eight_devices):
    """pp_schedule=1f1b: forward is the same GPipe scan — logits and loss
    must match the dense single-device forward exactly like gpipe does."""
    cfg, params, tokens = cfg_and_inputs(pp_schedule="1f1b", pp_microbatches=4)
    want_logits, want_loss = gpt.forward(params, tokens, cfg, targets=tokens)
    mesh = pp_mesh(eight_devices, pp=4, dp=2)
    got_logits, got_loss = jax.jit(
        lambda p, t: gpt.forward(p, t, cfg, targets=t, mesh=mesh)
    )(params, tokens)
    np.testing.assert_allclose(
        np.asarray(got_logits), np.asarray(want_logits), rtol=2e-4, atol=2e-4
    )
    np.testing.assert_allclose(float(got_loss), float(want_loss), rtol=1e-5)


def test_1f1b_gradients_match_dense(eight_devices):
    """The hand-written 1F1B backward (recompute + interleaved transpose +
    O(pp) ring stash) must produce the same gradients as autodiff through
    the dense scan — for every parameter leaf."""
    cfg, params, tokens = cfg_and_inputs(pp_schedule="1f1b", pp_microbatches=4)
    mesh = pp_mesh(eight_devices, pp=4, dp=2)
    g_want = jax.grad(
        lambda p: gpt.forward(p, tokens, cfg, targets=tokens)[1]
    )(params)
    g_got = jax.jit(jax.grad(
        lambda p: gpt.forward(p, tokens, cfg, targets=tokens, mesh=mesh)[1]
    ))(params)
    flat_want = jax.tree_util.tree_leaves_with_path(g_want)
    for (path, want), got in zip(flat_want, jax.tree.leaves(g_got)):
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=5e-4, atol=5e-4,
            err_msg=f"1f1b grad mismatch at {jax.tree_util.keystr(path)}",
        )


def test_1f1b_with_tp_gradients(eight_devices):
    """1f1b composes with megatron-tp inside the stages."""
    cfg, params, tokens = cfg_and_inputs(pp_schedule="1f1b")
    mesh = mesh_lib.make_mesh(
        MeshConfig(pp=2, dp=2, fsdp=1, tp=2, sp=1), devices=eight_devices
    )
    g_want = jax.grad(
        lambda p: gpt.forward(p, tokens, cfg, targets=tokens)[1]
    )(params)
    g_got = jax.jit(jax.grad(
        lambda p: gpt.forward(p, tokens, cfg, targets=tokens, mesh=mesh)[1]
    ))(params)
    for a, b in zip(jax.tree.leaves(g_got), jax.tree.leaves(g_want)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-4, atol=5e-4)


@pytest.mark.mid
def test_1f1b_matches_gpipe_with_dropout(eight_devices):
    """Same rng => identical loss under both schedules (the 1f1b custom vjp
    must carry the non-differentiable per-layer PRNG keys through its
    residuals and give them float0 cotangents)."""
    mesh = pp_mesh(eight_devices, pp=2, dp=1)
    tokens = jnp.tile(jnp.arange(16, dtype=jnp.int32)[None], (8, 1))

    losses = {}
    grads = {}
    for sched in ("gpipe", "1f1b"):
        cfg, params, _ = cfg_and_inputs(
            n_layer=2, resid_pdrop=0.3, pp_microbatches=2, pp_schedule=sched
        )

        def loss_fn(p):
            return gpt.forward(
                p, tokens, cfg, targets=tokens, rng=jax.random.key(5),
                deterministic=False, mesh=mesh,
            )[1]

        losses[sched] = float(jax.jit(loss_fn)(params))
        grads[sched] = jax.jit(jax.grad(loss_fn))(params)

    np.testing.assert_allclose(losses["gpipe"], losses["1f1b"], rtol=1e-5)
    for a, b in zip(jax.tree.leaves(grads["gpipe"]),
                    jax.tree.leaves(grads["1f1b"])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-4, atol=5e-4)


@pytest.mark.mid
def test_1f1b_with_moe_aux_gradients(eight_devices):
    """The aux (load-balancing) loss cotangent flows through the 1f1b
    backward: grads must match the dense run including the aux term."""
    cfg, params, tokens = cfg_and_inputs(
        n_experts=2, moe_top_k=1, moe_capacity_factor=4.0,
        pp_schedule="1f1b",
    )
    mesh = mesh_lib.make_mesh(
        MeshConfig(pp=2, dp=4, fsdp=1, tp=1, sp=1), devices=eight_devices
    )
    g_want = jax.grad(
        lambda p: gpt.forward(p, tokens, cfg, targets=tokens)[1]
    )(params)
    g_got = jax.jit(jax.grad(
        lambda p: gpt.forward(p, tokens, cfg, targets=tokens, mesh=mesh)[1]
    ))(params)
    for a, b in zip(jax.tree.leaves(g_got), jax.tree.leaves(g_want)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4)


@pytest.mark.mid
def test_pp_tp_sp_triple_composition(eight_devices):
    """pp=2 x tp=2 x sp=2 with ring attention: megatron-tp (local heads)
    composes with the zigzag ring over sp INSIDE pipeline stages — logits
    and grads must match the dense single-device run."""
    cfg, params, tokens = cfg_and_inputs(n_head=4, attention="ring")
    want_logits, want_loss = gpt.forward(params, tokens, cfg, targets=tokens)
    mesh = mesh_lib.make_mesh(
        MeshConfig(pp=2, dp=1, fsdp=1, tp=2, sp=2), devices=eight_devices
    )
    got_logits, got_loss = jax.jit(
        lambda p, t: gpt.forward(p, t, cfg, targets=t, mesh=mesh)
    )(params, tokens)
    np.testing.assert_allclose(
        np.asarray(got_logits), np.asarray(want_logits), rtol=2e-4, atol=2e-4
    )
    np.testing.assert_allclose(float(got_loss), float(want_loss), rtol=1e-5)

    g_want = jax.grad(
        lambda p: gpt.forward(p, tokens, cfg, targets=tokens)[1]
    )(params)
    g_got = jax.jit(jax.grad(
        lambda p: gpt.forward(p, tokens, cfg, targets=tokens, mesh=mesh)[1]
    ))(params)
    for a, b in zip(jax.tree.leaves(g_got), jax.tree.leaves(g_want)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-4, atol=5e-4)


@pytest.mark.mid
def test_pp_tp_flash_window_softcap(eight_devices):
    """The Pallas flash kernel — with sliding window AND logit softcap —
    runs inside the pipeline's manual region composed with megatron-tp:
    logits must match the dense single-device run."""
    cfg, params, tokens = cfg_and_inputs(
        attention="flash", attention_window=8, attn_logit_softcap=10.0
    )
    # reference run uses the EINSUM oracle so a kernel bug can't cancel
    # out on both sides — this asserts kernel AND composition at once
    import dataclasses

    cfg_oracle = dataclasses.replace(cfg, attention="einsum")
    want_logits, want_loss = gpt.forward(
        params, tokens, cfg_oracle, targets=tokens)
    mesh = mesh_lib.make_mesh(
        MeshConfig(pp=2, dp=2, fsdp=1, tp=2, sp=1), devices=eight_devices
    )
    got_logits, got_loss = jax.jit(
        lambda p, t: gpt.forward(p, t, cfg, targets=t, mesh=mesh)
    )(params, tokens)
    np.testing.assert_allclose(
        np.asarray(got_logits), np.asarray(want_logits), rtol=2e-4, atol=2e-4
    )
    np.testing.assert_allclose(float(got_loss), float(want_loss), rtol=1e-5)


@pytest.mark.mid
def test_pp_sp_attention_dropout_runs(eight_devices):
    """The reference-parity default attn_pdrop=0.1 must
    train under pp x sp — the refusal is lifted and the manual-sp shard
    bodies carry the dropout. Same rng -> identical loss (keyed, not
    nondeterministic); different rng -> different loss; grads finite."""
    cfg, params, tokens = cfg_and_inputs(attention="ring", attn_pdrop=0.5)
    mesh = mesh_lib.make_mesh(
        MeshConfig(pp=2, dp=1, fsdp=1, tp=1, sp=4), devices=eight_devices
    )

    def loss_fn(p, r):
        return gpt.forward(
            p, tokens, cfg, targets=tokens, rng=r, deterministic=False,
            mesh=mesh,
        )[1]

    step = jax.jit(jax.value_and_grad(loss_fn))
    l1, g1 = step(params, jax.random.key(3))
    l1b, _ = step(params, jax.random.key(3))
    l2, _ = step(params, jax.random.key(4))
    assert np.isfinite(float(l1))
    assert float(l1) == float(l1b)
    assert float(l1) != float(l2)
    for leaf in jax.tree.leaves(g1):
        assert np.isfinite(np.asarray(leaf)).all()


@pytest.mark.mid
def test_pp_ulysses_sp_attention_dropout_runs(eight_devices):
    cfg, params, tokens = cfg_and_inputs(
        n_head=4, attention="ulysses", attn_pdrop=0.3
    )
    mesh = mesh_lib.make_mesh(
        MeshConfig(pp=2, dp=2, fsdp=1, tp=1, sp=2), devices=eight_devices
    )
    loss = jax.jit(lambda p, r: gpt.forward(
        p, tokens, cfg, targets=tokens, rng=r, deterministic=False,
        mesh=mesh,
    )[1])
    l1 = loss(params, jax.random.key(0))
    assert np.isfinite(float(l1))


@pytest.mark.mid
def test_pp_dropout_decorrelated_across_dp(eight_devices):
    """dp shards inside the pipeline's manual region hold DIFFERENT rows
    but previously drew identical masks from the replicated layer key: with
    identical data everywhere, row 0 (dp shard 0) and the first row of dp
    shard 1 must differ under dropout."""
    cfg, params, _ = cfg_and_inputs(
        n_layer=2, resid_pdrop=0.5, pp_microbatches=2
    )
    tokens = jnp.tile(jnp.arange(16, dtype=jnp.int32)[None], (8, 1))
    mesh = mesh_lib.make_mesh(
        MeshConfig(pp=2, dp=2, fsdp=1, tp=1, sp=1), devices=eight_devices[:4]
    )
    logits, _ = jax.jit(
        lambda p, t, r: gpt.forward(
            p, t, cfg, rng=r, deterministic=False, mesh=mesh
        )
    )(params, tokens, jax.random.key(3))
    la = np.asarray(logits)
    # batch rows 0-3 live on dp shard 0, rows 4-7 on dp shard 1; row 0 and
    # row 4 share the microbatch index, so only the batch-shard fold can
    # decorrelate them
    assert not np.allclose(la[0], la[4], atol=1e-6)


def test_pp_schedule_cost_model_is_measured(eight_devices):
    """The 1F1B cost model was folklore — price it with
    the compiler. XLA's memory_analysis/cost_analysis on the compiled pp
    train step give schedule-comparable temp-memory and FLOP numbers:

      gpipe no-remat: stashes every microbatch activation -> most temp
      1f1b:           O(pp) stash custom-vjp               -> ~4x less temp
                      than gpipe no-remat, at ~+30% FLOPs (re-forward)
      gpipe + remat:  least temp, ~+10% FLOPs

    The assertions pin the ORDERING (the sizes shift with model/microbatch
    count); docs/hparams.md records the measured example."""
    cfg_kw = dict(
        n_layer=4, n_head=2, n_embd=64, vocab_size=128, block_size=32,
        embd_pdrop=0.0, resid_pdrop=0.0, attn_pdrop=0.0, dtype="float32",
        pp_microbatches=8,
    )
    mesh = mesh_lib.make_mesh(
        MeshConfig(pp=2, dp=1, fsdp=1, tp=1, sp=1), devices=eight_devices[:2]
    )
    tokens = jax.random.randint(jax.random.key(1), (16, 32), 0, 128)

    def analyze(schedule, remat):
        cfg = GPTConfig.make(**cfg_kw, pp_schedule=schedule, remat=remat)
        params = gpt.init(jax.random.key(0), cfg)
        f = jax.jit(jax.grad(
            lambda p: gpt.forward(p, tokens, cfg, targets=tokens,
                                  mesh=mesh)[1]))
        c = f.lower(params).compile()
        ma = c.memory_analysis()
        ca = c.cost_analysis()
        if ma is None or ca is None:
            import pytest
            pytest.skip("backend exposes no memory/cost analysis")
        flops = ca["flops"] if "flops" in ca else None
        return ma.temp_size_in_bytes, flops

    mem_gpipe, fl_gpipe = analyze("gpipe", False)
    mem_remat, fl_remat = analyze("gpipe", True)
    mem_1f1b, fl_1f1b = analyze("1f1b", False)

    # memory: gpipe stashes all M microbatches; 1f1b only O(pp) of them
    assert mem_1f1b < 0.5 * mem_gpipe, (mem_1f1b, mem_gpipe)
    assert mem_remat < mem_gpipe, (mem_remat, mem_gpipe)
    # flops: both memory-savers pay recompute; 1f1b pays more (re-forward
    # per stage-microbatch) than remat's single re-forward
    if fl_gpipe is not None:
        assert fl_1f1b > fl_gpipe
        assert fl_remat > fl_gpipe


# -- the manual region's programs, pinned -------------------------------------

#: ``gpt.forward`` under ``pp`` with each thing ``_block`` does by hand inside
#: the pipeline's ``shard_map``: case -> (the model's keys over
#: ``cfg_and_inputs``'s, the mesh, training mode with both dropouts on)
PIPELINE_CASES = {
    "tp": ({}, dict(pp=2, dp=2, tp=2), False),
    "tp-swiglu-rope": (dict(rope=True, swiglu=True, rmsnorm=True),
                       dict(pp=2, dp=2, tp=2), False),
    "tp-train": (dict(resid_pdrop=0.1, attn_pdrop=0.1),
                 dict(pp=2, dp=2, tp=2), True),
    "ep": (dict(n_experts=2, moe_top_k=1, moe_capacity_factor=4.0),
           dict(pp=2, dp=2, ep=2), False),
    "sp-ring": (dict(attention="ring"), dict(pp=2, dp=1, sp=4), False),
    "sp-ulysses": (dict(attention="ulysses"), dict(pp=2, dp=2, sp=2), False),
}
#: sha256 of those forwards' jaxprs (tests/program_digests.py, which prints
#: this table when run), made on the commit before PR 46 (f3c5a18). A PR that
#: changes one of these programs on purpose makes them again: PR 54 did for
#: ``sp-ulysses`` (9117e548c73802de until then), whose shard calls the
#: native-layout flash kernel, since then through a jitted caller
#: (``flash_attention._native_forward``); the kernel in it is the parent's
#: (tests/test_flash_attention.py pins the kernels by themselves).
PIPELINE_FORWARD_DIGESTS = {
    "tp": "12e96d246e9f9b24",
    "tp-swiglu-rope": "c7abc62a79a528d7",
    "tp-train": "e6cb4c72ef76c1ca",
    "ep": "a294a2478048bcd0",
    "sp-ring": "392831f04bdd94d0",
    "sp-ulysses": "885931ec3f61470f",
}


def pipeline_digest(case, devices):
    sizes, axes, train = PIPELINE_CASES[case]
    cfg, _, tokens = cfg_and_inputs(**sizes)
    mesh = mesh_lib.make_mesh(MeshConfig(**axes), devices=devices)
    return forward_digest(cfg, mesh=mesh, train=train, tokens=tokens.shape)


@pytest.mark.parametrize("case", sorted(PIPELINE_FORWARD_DIGESTS))
def test_the_pipeline_s_forward_is_the_parent_s(case, eight_devices):
    """``_block`` under manual ``tp`` (its shard's heads, the ``psum`` before
    each bias), ``ep`` (the experts' two ``all_to_all``) and ``sp`` (the
    shard's rows of the rope tables, the attention override) traces, jaxpr
    for jaxpr, to the program of the commit the table was made on."""
    assert pipeline_digest(case, eight_devices) == \
        PIPELINE_FORWARD_DIGESTS[case]
