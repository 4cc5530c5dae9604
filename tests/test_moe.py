"""Mixture-of-experts (ops/moe.py) + expert parallelism over the ep axis.
The reference has a dense MLP only (SURVEY §2.2: EP/MoE absent,
model.py:179-184); these tests pin the routing math to the dense oracle
where they must coincide and check sharding/e2e training behaviour."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mingpt_distributed_tpu.config import ConfigError, GPTConfig, MeshConfig
from mingpt_distributed_tpu.models import generate as gen
from mingpt_distributed_tpu.models import gpt
from mingpt_distributed_tpu.ops import layers as L
from mingpt_distributed_tpu.ops import moe
from mingpt_distributed_tpu.parallel import mesh as mesh_lib
from oracles import dense_greedy


def test_single_expert_equals_dense_mlp():
    """E=1, k=1, ample capacity: routing is the identity, so the MoE layer
    must reproduce the dense GELU MLP with the same weights exactly."""
    d, f = 16, 32
    key = jax.random.key(0)
    x = jax.random.normal(key, (2, 8, d), jnp.float32)
    w1 = jax.random.normal(jax.random.key(1), (d, f)) * 0.2
    w2 = jax.random.normal(jax.random.key(2), (f, d)) * 0.2
    wr = jnp.zeros((d, 1))
    out, aux = moe.moe_mlp(
        x, wr, w1[None], w2[None], top_k=1, capacity_factor=2.0,
    )
    want = L.mlp_gelu(x, w1, None, w2, None)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(float(aux), 1.0, rtol=1e-6)  # E * 1 * 1


def test_topk_routing_mixes_experts():
    d, f, e = 8, 16, 4
    x = jax.random.normal(jax.random.key(0), (1, 32, d), jnp.float32)
    wr = jax.random.normal(jax.random.key(1), (d, e))
    w1 = jax.random.normal(jax.random.key(2), (e, d, f)) * 0.2
    w2 = jax.random.normal(jax.random.key(3), (e, f, d)) * 0.2
    out1, _ = moe.moe_mlp(x, wr, w1, w2, top_k=1, capacity_factor=4.0)
    out2, _ = moe.moe_mlp(x, wr, w1, w2, top_k=2, capacity_factor=4.0)
    assert out1.shape == out2.shape == x.shape
    # k=2 folds in a second expert: outputs must differ from k=1
    assert float(jnp.abs(out1 - out2).max()) > 1e-6


def test_capacity_overflow_drops_not_crashes():
    d, f, e = 8, 16, 2
    x = jax.random.normal(jax.random.key(0), (1, 64, d), jnp.float32)
    # router heavily biased to expert 0 -> guaranteed overflow at tiny cap
    wr = jnp.zeros((d, e)).at[:, 0].set(5.0)
    w1 = jax.random.normal(jax.random.key(2), (e, d, f)) * 0.2
    w2 = jax.random.normal(jax.random.key(3), (e, f, d)) * 0.2
    out, aux = moe.moe_mlp(x, wr, w1, w2, top_k=1, capacity_factor=0.25)
    assert np.isfinite(np.asarray(out)).all() and np.isfinite(float(aux))
    # dropped tokens contribute zero (residual carries them in the block)
    norms = jnp.linalg.norm(out[0], axis=-1)
    assert float(jnp.min(norms)) == 0.0


def test_switch_k1_router_gets_task_gradient():
    """k=1 must scale expert output by the RAW router prob (Switch): with
    renormalised gates the weight is identically 1 and the router would get
    zero task-loss gradient — it could never learn to specialize."""
    d, f, e = 8, 16, 4
    x = jax.random.normal(jax.random.key(0), (1, 32, d), jnp.float32)
    params = {
        "wr": jax.random.normal(jax.random.key(1), (d, e)),
        "w1": jax.random.normal(jax.random.key(2), (e, d, f)) * 0.2,
        "w2": jax.random.normal(jax.random.key(3), (e, f, d)) * 0.2,
    }

    def task_loss(p):  # NO aux term — gradient must come from the task
        out, _ = moe.moe_mlp(x, p["wr"], p["w1"], p["w2"],
                             top_k=1, capacity_factor=2.0)
        return jnp.sum(out ** 2)

    g = jax.grad(task_loss)(params)
    assert float(jnp.abs(g["wr"]).max()) > 0


def test_grouped_dispatch_linear_memory():
    """Groups bound the one-hot dispatch to O(group * S), not O(S^2): the
    routed result must be identical whether S spans one group or many (with
    non-binding capacity)."""
    d, f, e = 8, 16, 2
    w1 = jax.random.normal(jax.random.key(2), (e, d, f)) * 0.2
    w2 = jax.random.normal(jax.random.key(3), (e, f, d)) * 0.2
    wr = jax.random.normal(jax.random.key(1), (d, e))
    x = jax.random.normal(jax.random.key(0), (2, moe.MAX_GROUP, d))
    out, _ = moe.moe_mlp(x, wr, w1, w2, top_k=1, capacity_factor=2.0)
    # same tokens as a single smaller batch (one group) must agree
    out_small, _ = moe.moe_mlp(x[:1], wr, w1, w2, top_k=1,
                               capacity_factor=2.0)
    np.testing.assert_allclose(np.asarray(out[:1]), np.asarray(out_small),
                               rtol=1e-5, atol=1e-5)


def test_gradients_flow_to_router_and_experts():
    d, f, e = 8, 16, 4
    x = jax.random.normal(jax.random.key(0), (1, 32, d), jnp.float32)
    params = {
        "wr": jax.random.normal(jax.random.key(1), (d, e)),
        "w1": jax.random.normal(jax.random.key(2), (e, d, f)) * 0.2,
        "w2": jax.random.normal(jax.random.key(3), (e, f, d)) * 0.2,
    }

    def loss(p):
        out, aux = moe.moe_mlp(x, p["wr"], p["w1"], p["w2"],
                               top_k=2, capacity_factor=2.0)
        return jnp.sum(out ** 2) + 0.01 * aux

    g = jax.grad(loss)(params)
    for name in ("wr", "w1", "w2"):
        assert float(jnp.abs(g[name]).max()) > 0, f"zero grad for {name}"


def test_moe_forward_and_loss():
    cfg = GPTConfig.make(
        n_layer=2, n_head=2, n_embd=32, vocab_size=64, block_size=16,
        embd_pdrop=0.0, resid_pdrop=0.0, attn_pdrop=0.0, dtype="float32",
        n_experts=4, moe_top_k=2,
    )
    params = gpt.init(jax.random.key(0), cfg)
    assert params["blocks"]["w_e1"].shape == (2, 4, 32, 128)
    tokens = jax.random.randint(jax.random.key(1), (2, 16), 0, 64)
    logits, loss = gpt.forward(params, tokens, cfg, targets=tokens)
    assert logits.shape == (2, 16, 64) and np.isfinite(float(loss))
    # aux weight actually contributes: zero-weight config gives lower loss
    cfg0 = GPTConfig.make(
        n_layer=2, n_head=2, n_embd=32, vocab_size=64, block_size=16,
        embd_pdrop=0.0, resid_pdrop=0.0, attn_pdrop=0.0, dtype="float32",
        n_experts=4, moe_top_k=2, moe_aux_weight=0.0,
    )
    _, loss0 = gpt.forward(params, tokens, cfg0, targets=tokens)
    assert float(loss) > float(loss0)


def test_moe_generation_matches_dense_oracle():
    """The KV-cached decode path must route identically to gpt.forward.

    Capacity must not bind (factor=E makes cap >= tokens): capacity-dropped
    tokens depend on how many tokens are evaluated together, so incremental
    decode only matches a full re-forward when nothing is dropped."""
    cfg = GPTConfig.make(
        n_layer=2, n_head=2, n_embd=32, vocab_size=50, block_size=32,
        embd_pdrop=0.0, resid_pdrop=0.0, attn_pdrop=0.0, dtype="float32",
        n_experts=2, moe_top_k=1, moe_capacity_factor=2.0,
    )
    params = gpt.init(jax.random.key(0), cfg)
    prompt = jax.random.randint(jax.random.key(1), (2, 5), 0, 50)
    # room for every token of a call, however many: the padding drops none
    want = dense_greedy(params, cfg, prompt, 8)
    got = gen.generate(params, cfg, prompt, 8)
    np.testing.assert_array_equal(np.asarray(want), np.asarray(got))


def test_moe_sharded_matches_unsharded(eight_devices):
    """ep=4 sharding is layout, not semantics: logits must match the
    single-device forward bit-closely (GSPMD inserts the all-to-alls)."""
    cfg = GPTConfig.make(
        n_layer=2, n_head=2, n_embd=32, vocab_size=64, block_size=16,
        embd_pdrop=0.0, resid_pdrop=0.0, attn_pdrop=0.0, dtype="float32",
        n_experts=4, moe_top_k=2,
    )
    params = gpt.init(jax.random.key(0), cfg)
    tokens = jax.random.randint(jax.random.key(1), (8, 16), 0, 64)
    want, want_loss = gpt.forward(params, tokens, cfg, targets=tokens)

    mesh = mesh_lib.make_mesh(
        MeshConfig(dp=2, fsdp=1, ep=4, tp=1, sp=1), devices=eight_devices
    )
    shardings = mesh_lib.param_shardings(
        mesh, jax.eval_shape(lambda: params)
    )
    sharded = jax.device_put(params, shardings)
    got, got_loss = jax.jit(
        lambda p, t: gpt.forward(p, t, cfg, targets=t)
    )(sharded, jax.device_put(tokens, mesh_lib.batch_sharding(mesh)))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(float(got_loss), float(want_loss), rtol=1e-5)


def test_moe_trainer_learns(tmp_path, eight_devices):
    """End-to-end: an MoE model trains under the jitted sharded train step
    and the loss goes down; expert params land sharded over ep."""
    from tests.test_trainer import CORPUS

    from mingpt_distributed_tpu.config import (
        DataConfig, OptimizerConfig, TrainerConfig,
    )
    from mingpt_distributed_tpu.data.char_dataset import CharDataset
    from mingpt_distributed_tpu.training.trainer import GPTTrainer

    ds = CharDataset(
        DataConfig(path="<inline>", block_size=16, train_split=0.9),
        text=CORPUS,
    )
    train, test = ds.split()
    gcfg = GPTConfig.make(
        n_layer=2, n_head=2, n_embd=32, vocab_size=ds.vocab_size,
        block_size=16, embd_pdrop=0.0, resid_pdrop=0.0, attn_pdrop=0.0,
        dtype="float32", n_experts=4, moe_top_k=2,
    )
    tcfg = TrainerConfig.make(
        max_epochs=1, batch_size=16, grad_norm_clip=1.0, save_every=100,
        log_every=1000, seed=7, max_steps=8,
        snapshot_path=str(tmp_path / "moe2.msgpack"),
    )
    mesh = mesh_lib.make_mesh(
        MeshConfig(dp=2, fsdp=1, ep=2, tp=1, sp=1), devices=eight_devices[:4]
    )
    tr = GPTTrainer(tcfg, gcfg, OptimizerConfig(learning_rate=1e-2),
                    train, test, mesh=mesh)
    w_e1 = tr.state["params"]["blocks"]["w_e1"]  # (L, E, D, F)
    assert w_e1.addressable_shards[0].data.shape[1] == w_e1.shape[1] // 2
    first, last = None, None
    for xy in tr.train_iter.epoch_batches():
        tr.state, m = tr._train_step(tr.state, tr._put_batch(xy), tr.base_rng)
        loss = float(jax.device_get(m["loss"]))
        first = first if first is not None else loss
        last = loss
        if tr.train_iter.state.step_in_epoch >= 8:
            break
    assert last < first  # it learns


def test_moe_config_validation():
    with pytest.raises(ConfigError, match="moe_top_k"):
        GPTConfig.make(n_layer=2, n_head=2, n_embd=32, n_experts=2,
                       moe_top_k=3)


def test_swiglu_single_expert_equals_dense_swiglu():
    """Mixtral-style SwiGLU experts: E=1 must reduce to the dense SwiGLU MLP
    with the same weights."""
    d, f = 16, 32
    x = jax.random.normal(jax.random.key(0), (2, 8, d), jnp.float32)
    wg = jax.random.normal(jax.random.key(1), (d, f)) * 0.2
    wu = jax.random.normal(jax.random.key(2), (d, f)) * 0.2
    wd = jax.random.normal(jax.random.key(3), (f, d)) * 0.2
    out, _ = moe.moe_mlp(
        x, jnp.zeros((d, 1)), wu[None], wd[None], top_k=1,
        capacity_factor=2.0, w_gate=wg[None],
    )
    want = L.mlp_swiglu(x, wg, wu, wd)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_mixtral_style_model_trains_and_generates():
    """llama toggles + MoE together (the Mixtral family): forward, loss,
    grads, and KV-cached generation parity."""
    cfg = GPTConfig.make(
        n_layer=2, n_head=2, n_embd=32, vocab_size=50, block_size=32,
        embd_pdrop=0.0, resid_pdrop=0.0, attn_pdrop=0.0, dtype="float32",
        rope=True, swiglu=True, rmsnorm=True, n_kv_head=1, tie_weights=True,
        n_experts=2, moe_top_k=2, moe_capacity_factor=2.0,
    )
    params = gpt.init(jax.random.key(0), cfg)
    assert params["blocks"]["w_eg"].shape == params["blocks"]["w_e1"].shape
    tokens = jax.random.randint(jax.random.key(1), (2, 16), 0, 50)
    _, loss = gpt.forward(params, tokens, cfg, targets=tokens)
    assert np.isfinite(float(loss))
    g = jax.grad(
        lambda p: gpt.forward(p, tokens, cfg, targets=tokens)[1]
    )(params)
    assert float(jnp.abs(g["blocks"]["w_eg"]).max()) > 0
    prompt = jax.random.randint(jax.random.key(2), (1, 4), 0, 50)
    want = dense_greedy(params, cfg, prompt, 6)
    got = gen.generate(params, cfg, prompt, 6)
    np.testing.assert_array_equal(np.asarray(want), np.asarray(got))


def test_mixtral_preset_forward():
    """The mixtral presets resolve and run: SwiGLU experts + top-2 routing +
    rope/rmsnorm/GQA composed via one model_type string."""
    import dataclasses

    from mingpt_distributed_tpu.config import GPTConfig

    cfg = GPTConfig.make(model_type="mixtral-tiny", block_size=16,
                         vocab_size=64, dtype="float32",
                         embd_pdrop=0.0, resid_pdrop=0.0, attn_pdrop=0.0)
    assert cfg.n_experts == 4 and cfg.moe_top_k == 2 and cfg.swiglu
    params = gpt.init(jax.random.key(0), cfg)
    toks = jax.random.randint(jax.random.key(1), (2, 16), 0, 64)
    logits, loss = gpt.forward(params, toks, cfg, targets=toks)
    assert logits.shape == (2, 16, 64)
    assert np.isfinite(float(loss))
