"""A looped stack (ISSUE 37, Ouro): the layers run ``n_passes`` times over one
set of weights, the final norm closes every pass and is carried, a norm
stands after each sublayer, an exit gate reads every pass, and the cache has
a plane a pass and layer. CPU, tiny, float32, seeded weights, against the
plain reference ``benchmarks/references/ouro.py``."""

import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import stacks
from benchmarks.references import ouro
from mingpt_distributed_tpu.config import ConfigError, GPTConfig, MeshConfig
from mingpt_distributed_tpu.models import generate as gen
from mingpt_distributed_tpu.models import gpt
from mingpt_distributed_tpu.parallel import mesh as mesh_lib
from mingpt_distributed_tpu.serving import InferenceServer
from oracles import solo_greedy
from program_digests import cached_digests

STACK = stacks.OURO
PASSES, LAYERS, BLOCK, VOCAB = (stacks.OURO_PASSES, stacks.OURO_LAYERS,
                                stacks.OURO_BLOCK, stacks.OURO_VOCAB)
LOOPED, SIZES = stacks.LOOPED_GPT, stacks.LOOPED_SIZES


@pytest.fixture(scope="module")
def model():
    cfg, params = stacks.model(STACK)
    return cfg, params, stacks.tokens_of(cfg, 2, 24)


@pytest.fixture(scope="module")
def reference(model):
    cfg, params, tokens = model
    weights = ouro.weights_from_program(params)
    programs = stacks.reference_programs(STACK, SIZES)
    x, ks, vs, gates = programs.hidden(weights, tokens)
    return programs.logits(weights, x), ks, vs, gates


def test_init_draws_what_a_missing_part_would_hide(model):
    cfg, params, _ = model
    blocks = params["blocks"]
    for name in ("ln1_post_scale", "ln2_post_scale"):
        scale = np.asarray(blocks[name])
        assert scale.shape == (LAYERS, 64)
        assert 0.05 <= scale.min() < scale.max() <= 0.2
    assert params["exit_gate_w"].shape == (64,)
    assert float(jnp.abs(params["exit_gate_w"]).min()) > 0.0
    assert float(params["exit_gate_b"]) != 0.0
    assert gen.cache_leaf_shapes(cfg, 5)["k"] == (
        PASSES * LAYERS, 5, BLOCK, 4, 16)
    assert cfg.cache_planes == PASSES * LAYERS


def test_forward_agrees_with_the_reference(model, reference):
    """Logits of the last pass, and the gate's logits of every pass."""
    cfg, params, tokens = model
    logits, loss, gates = gpt.forward(params, tokens, cfg, return_gates=True)
    ref_logits, _, _, ref_gates = reference
    assert loss is None and gates.shape == (PASSES, 2, 24)
    np.testing.assert_allclose(logits, ref_logits, atol=2e-5)
    np.testing.assert_allclose(gates, ref_gates, atol=2e-5)
    # the passes differ: a gate that read one pass four times would not
    assert float(jnp.abs(gates[1:] - gates[:-1]).min(0).max()) > 1e-3
    mass = gpt.exit_mass(gates)
    np.testing.assert_allclose(mass, ouro.exit_mass(ref_gates), atol=1e-5)
    np.testing.assert_allclose(mass.sum(0), 1.0, atol=1e-6)


def cached(cfg, params, tokens, split, counter=True):
    """Prefill ``tokens[:, :split]`` in the chunks ``split`` names, then decode
    the rest a token at a time, the two lanes at their own positions."""
    forward = stacks.forward_cached
    cache = gen.init_cache(cfg, tokens.shape[0])
    if counter:
        cache[gen.LOOP_PASSES] = gen.init_loop_passes(cfg)
    out, at = [], 0
    for end in split:
        logits, cache = forward(params, tokens[:, at:end], cache, at, cfg)
        at = end
    out.append(logits)
    for i in range(at, tokens.shape[1] - 1):
        logits, cache = forward(
            params, tokens[:, i:i + 1], cache,
            np.full((tokens.shape[0],), i), cfg)
        out.append(logits)
    return jnp.stack(out, 1), cache, at


@pytest.mark.parametrize("split", [(12,), (8, 14)],
                         ids=["one-chunk", "two-chunks"])
def test_prefill_then_decode_agrees_with_the_full_forward(model, reference,
                                                          split):
    """Through the cache: the logits of every step and the keys and values
    of every one of the passes x layers planes."""
    cfg, params, tokens = model
    ref_logits, ks, vs, _ = reference
    logits, cache, at = cached(cfg, params, tokens, split)
    n = tokens.shape[1] - 1
    np.testing.assert_allclose(logits, ref_logits[:, at - 1:n], atol=2e-5)
    assert cache["k"].shape[0] == ks.shape[0] == PASSES * LAYERS
    np.testing.assert_allclose(cache["k"][:, :, :n], ks[:, :, :n], atol=2e-5)
    np.testing.assert_allclose(cache["v"][:, :, :n], vs[:, :, :n], atol=2e-5)
    # every token took every pass: token-passes, tokens, and a mass that
    # sums to the tokens
    counter = np.asarray(cache[gen.LOOP_PASSES])
    assert counter[1] == 2 * n and counter[0] == PASSES * 2 * n
    np.testing.assert_allclose(counter[2:].sum(), 2 * n, rtol=1e-5)


def test_lanes_at_different_positions(model, reference):
    """The serving decode step: each lane at its own position, one parked
    and not live; the live lanes' rows and the counter say who ran."""
    cfg, params, tokens = model
    ref_logits, ks, vs, _ = reference
    cache = gen.init_cache(cfg, 3)
    cache[gen.LOOP_PASSES] = gen.init_loop_passes(cfg)
    lane = lambda c, s: {n: a[:, s:s + 1] for n, a in c.items() if a.ndim == 5}
    for slot, n in ((0, 9), (1, 15)):
        one = dict(lane(cache, slot), **{gen.LOOP_PASSES: cache[gen.LOOP_PASSES]})
        _, one = stacks.forward_cached(params, tokens[slot:slot + 1, :n], one,
                                       0, cfg)
        cache = {name: a if a.ndim != 5 else cache[name].at[:, slot].set(a[:, 0])
                 for name, a in one.items()}
    positions = np.asarray([9, 15, BLOCK - 1])
    live = np.asarray([True, True, False])
    step = np.asarray([tokens[0, 9], tokens[1, 15], 0])[:, None]
    logits, cache = stacks.forward_cached(
        params, step, cache, positions, cfg, valid=live[:, None])
    np.testing.assert_allclose(logits[0], ref_logits[0, 9], atol=2e-5)
    np.testing.assert_allclose(logits[1], ref_logits[1, 15], atol=2e-5)
    np.testing.assert_allclose(cache["k"][:, 0, :10], ks[:, 0, :10], atol=2e-5)
    np.testing.assert_allclose(cache["v"][:, 1, :16], vs[:, 1, :16], atol=2e-5)
    counter = np.asarray(cache[gen.LOOP_PASSES])
    assert counter[1] == 9 + 15 + 2 and counter[0] == PASSES * counter[1]


# -- negative controls: what the reference would catch ------------------------

def off_by(cfg, params, tokens, reference, forward=stacks.forward):
    logits = forward(params, tokens, cfg)[0]
    return float(jnp.abs(logits - reference[0]).max())


def test_a_pass_dropped_shows(model, reference):
    cfg, params, tokens = model
    fewer = dataclasses.replace(cfg, n_passes=PASSES - 1)
    assert off_by(fewer, params, tokens, reference) > 1e-2


def test_the_post_sublayer_norms_left_out_show(model, reference):
    cfg, params, tokens = model
    plain = dataclasses.replace(cfg, post_norms=False)
    assert off_by(plain, params, tokens, reference) > 1e-2


def test_the_final_norm_not_carried_shows(model, reference, monkeypatch):
    """A stack that normed only before the head: the passes run on, the
    norm at the end."""
    cfg, params, tokens = model
    real = gpt._norm
    calls = []

    def only_the_last(x, scale, bias, c):
        if scale is params["lnf_scale"]:
            calls.append(1)
            if len(calls) < PASSES:
                return x
        return real(x, scale, bias, c)

    monkeypatch.setattr(gpt, "_norm", only_the_last)
    # op by op: the patch knows the final norm's scale by the array it is
    assert off_by(cfg, params, tokens, reference, gpt.forward) > 1e-2
    assert len(calls) == PASSES


def test_a_pass_reading_the_pass_before_s_plane_shows(model, reference,
                                                      monkeypatch):
    """Pass t attends its own keys and values: shifted to pass t-1's planes
    the decode steps' logits leave the reference."""
    cfg, params, tokens = model
    real = gen.attn_ops.causal_attend_step

    def shifted(q, k_cache, v_cache, plane, *args, **kwargs):
        return real(q, k_cache, v_cache, jnp.maximum(plane - LAYERS, 0),
                    *args, **kwargs)

    monkeypatch.setattr(gen.attn_ops, "causal_attend_step", shifted)
    logits, _, at = cached(cfg, params, tokens, (12,))
    n = tokens.shape[1] - 1
    # the prefill's own logits are untouched, the decode steps' are not
    np.testing.assert_allclose(logits[:, 0], reference[0][:, at - 1],
                               atol=2e-5)
    assert float(jnp.abs(logits[:, 1:] - reference[0][:, at:n]).max()) > 1e-3


def test_a_float32_stream_under_bfloat16_matmuls(model, reference):
    """``residual_dtype`` float32: the matmuls, the cache and the head stay
    bfloat16, the stream between them does not, and the logits lie nearer
    the float32 reference than a bfloat16 stream's; the cached path agrees
    with the uncached one."""
    cfg, params, tokens = model
    bf16 = dataclasses.replace(cfg, dtype="bfloat16")
    kept = dataclasses.replace(bf16, residual_dtype="float32")
    assert (bf16.stream_dtype, kept.stream_dtype) == ("bfloat16", "float32")
    off = {c.stream_dtype: float(jnp.abs(
        stacks.forward(params, tokens, c)[0] - reference[0]).mean())
        for c in (bf16, kept)}
    assert off["float32"] < off["bfloat16"]     # 24 sums; 384 at size
    logits, cache, at = cached(kept, params, tokens, (12,))
    assert cache["k"].dtype == jnp.bfloat16 and logits.dtype == jnp.float32
    full = stacks.forward(params, tokens, kept)[0]
    assert float(jnp.abs(logits - full[:, at - 1:-1]).max()) < 0.05
    # every matmul of a weight runs in bfloat16: no weight is cast up
    text = str(jax.make_jaxpr(lambda p, t: gpt.forward(p, t, kept))(
        jax.tree.map(lambda a: a.astype(jnp.bfloat16), params), tokens))
    assert not re.search(r"f32\[\d+,\d+\] = convert_element_type.*bf16\[64,",
                         text)


# -- what is refused, a sentence each ------------------------------------------

@pytest.mark.parametrize("change,match", [
    (dict(exit_threshold=0.9), "which keys and values that lane then owes"),
    (dict(pp_microbatches=2), "not pipelined"),
    (dict(rmsnorm=False, swiglu=False, rope=False), "RMS-normed"),
    (dict(n_experts=4, moe_top_k=2), "per-head rows and a dense MLP"),
    (dict(n_passes=0), "n_passes must be >= 1"),
    (dict(residual_dtype="float16"), "residual_dtype"),
], ids=["exit-threshold", "pipeline-stages", "layernorm", "experts",
        "no-pass", "stream-dtype"])
def test_validate_refuses_what_is_not_built(change, match):
    with pytest.raises(ConfigError, match=match):
        GPTConfig.make(**{**LOOPED, **change})


def test_validate_refuses_a_looped_hybrid_stack():
    hybrid = dict(n_layer=2, n_head=4, n_embd=64, n_kv_head=2, vocab_size=VOCAB,
                  block_size=BLOCK, rope=True, swiglu=True, rmsnorm=True,
                  tie_weights=False, lightning_heads=4, lightning_head_dim=16,
                  mixer_types=("lightning-attn", "minicpm4"),
                  sparse_kernel_size=8, sparse_kernel_stride=4,
                  sparse_block_size=16, sparse_topk=2, sparse_window=16,
                  sparse_dense_len=32, n_passes=2)
    with pytest.raises(ConfigError, match="hybrid stack"):
        GPTConfig.make(**hybrid)


def test_a_looped_stack_is_not_trained_or_pipelined(model):
    cfg, params, tokens = model
    with pytest.raises(NotImplementedError, match="expected loss over the"):
        gpt.forward(params, tokens, cfg, targets=tokens)
    with pytest.raises(NotImplementedError, match="expected loss over the"):
        gpt.forward(params, tokens, cfg, rng=jax.random.key(0),
                    deterministic=False)
    mesh = mesh_lib.make_mesh(MeshConfig(pp=2, dp=1),
                              devices=jax.devices()[:2])
    with pytest.raises(NotImplementedError, match="pipeline stages run"):
        gpt.forward(params, tokens, cfg, mesh=mesh)


def test_speculation_refuses_a_looped_stack(model):
    cfg, params, _ = model
    draft = GPTConfig.make(**{**LOOPED, "n_passes": 1, "n_layer": 1})
    with pytest.raises(ConfigError, match="speculation .spec_k. is not built"):
        InferenceServer(params, cfg, n_slots=2, warmup=False, spec_k=2,
                        draft_params=gpt.init(jax.random.key(2), draft),
                        draft_cfg=draft)


# -- what works over passes x layers planes, shown -----------------------------

def serve(cfg, params, prompts, **options):
    server = InferenceServer(params, cfg, **STACK.serve, warmup=True,
                             **options)
    return stacks.serve(server, prompts, 8), server


@pytest.fixture(scope="module")
def served(model):
    cfg, params, _ = model
    rng = np.random.default_rng(0)
    head = rng.integers(1, VOCAB, size=20).tolist()
    prompts = [head + rng.integers(1, VOCAB, size=6).tolist()
               for _ in range(4)]
    tokens, server = serve(cfg, params, prompts)
    return prompts, tokens, server


def test_the_server_emits_the_solo_tokens_and_counts_its_passes(model, served):
    cfg, params, _ = model
    prompts, tokens, server = served
    assert tokens == [solo_greedy(params, cfg, p, 8) for p in prompts]
    summary = server.metrics.summary()
    assert summary["kv_bytes_per_row"] == PASSES * LAYERS * 2 * 4 * 16 * 4
    assert summary["kv_bytes_per_row"] == server.engine.kv_bytes_per_row
    # 4 prompts of 26 and 7 decode steps each (the 8th token needs none)
    assert summary["loop_tokens"] == 4 * (26 + 7)
    assert summary["loop_token_passes"] == PASSES * summary["loop_tokens"]
    assert len(summary["loop_exit_mass"]) == PASSES
    assert sum(summary["loop_exit_mass"]) == pytest.approx(1.0, abs=1e-5)
    assert all(m > 0.0 for m in summary["loop_exit_mass"])
    assert server.watchdog.recompiles == 0
    assert server.compile_counts()["decode"] == 1


@pytest.mark.parametrize("options", [
    dict(prefix_cache_mb=4.0), dict(prefill_chunk=8), dict(kv_dtype="int8"),
    dict(mesh="tp2")], ids=["prefix-store", "chunked-prefill", "int8-pool",
                            "tp2"])
def test_the_servers_options_work_over_the_planes(model, served, options):
    """The prefix store, chunked prefill, an int8 pool and tensor parallelism
    take the planes as they take layers: the greedy tokens are the plain
    server's, and no program is compiled after the warm-up."""
    cfg, params, _ = model
    prompts, tokens, _ = served
    if options.get("mesh") == "tp2":
        options = dict(mesh=mesh_lib.make_mesh(
            MeshConfig(tp=2), devices=jax.devices()[:2]))
    got, server = serve(cfg, params, prompts, **options)
    assert got == tokens
    assert server.watchdog.recompiles == 0
    summary = server.metrics.summary()
    if "prefix_cache_mb" in options:
        assert summary["prefix_hits"] >= 2
        assert summary["loop_tokens"] < 4 * (26 + 7)    # rows not recomputed
    else:
        assert summary["loop_token_passes"] == PASSES * 4 * (26 + 7)


def test_a_dense_model_carries_no_counter_and_reports_none():
    cfg = GPTConfig.make(**{**LOOPED, "n_passes": 1, "post_norms": False,
                            "exit_gate": False})
    server = InferenceServer(gpt.init(jax.random.key(0), cfg), cfg, n_slots=2,
                             warmup=False)
    assert gen.LOOP_PASSES not in server.engine.pool.cache
    summary = server.metrics.summary()
    assert summary["loop_token_passes"] is None
    assert summary["loop_tokens"] is None and summary["loop_exit_mass"] is None


def test_one_pass_with_a_gate_counts_one_pass_a_token():
    cfg, params = stacks.model(STACK, n_passes=1)
    tokens = stacks.tokens_of(cfg, 1, 10)
    _, cache, _ = cached(cfg, params, tokens, (6,))
    np.testing.assert_allclose(np.asarray(cache[gen.LOOP_PASSES]),
                               [9.0, 9.0, 9.0], rtol=1e-6)
    weights = ouro.weights_from_program(params)
    programs = stacks.reference_programs(STACK, dict(SIZES, total_ut_steps=1))
    x, *_ = programs.hidden(weights, tokens)
    np.testing.assert_allclose(stacks.forward(params, tokens, cfg)[0],
                               programs.logits(weights, x), atol=2e-5)


# -- one pass, no new norm: the programs of before ----------------------------

TINY = dict(n_layer=2, n_head=4, n_embd=32, vocab_size=64, block_size=32,
            embd_pdrop=0.0, resid_pdrop=0.0, attn_pdrop=0.0, dtype="float32")
BEFORE = {
    "gpt2": dict(TINY, tie_weights=False),
    "rope-dense": dict(TINY, rope=True, swiglu=True, rmsnorm=True, n_kv_head=2,
                       tie_weights=False),
    "kanana-tiny": dict(TINY, rope=True, rope_interleave=True, swiglu=True,
                        rmsnorm=True, tie_weights=False, kv_lora_rank=16,
                        qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=8,
                        n_dense_layers=1, ffn_dim=48, n_experts=8, moe_top_k=2,
                        moe_ffn_dim=16, n_shared_experts=2,
                        moe_scoring="sigmoid", moe_route_scale=2.448),
    "minicpm-tiny": dict(model_type="minicpm-sala-tiny"),
}
#: sha256 of the programs' jaxprs (``program_digests.cached_digests``; run
#: that module and it prints this table): ``gpt.forward`` with the cached
#: forward of a chunk (a scalar offset), and the cached forward of a decode
#: step (a position a lane). The first made on the commit
#: before PR 45 (182a0b7) and equal on this one; the step's made again by PR
#: 45, which changed it on purpose for every stack but the hybrid (the walk
#: over lanes and blocks: tests/test_lane_walk.py), and by PR 49 for the three
#: whose projections are turned per head (``gpt.head_projection``'s boundary:
#: tests/test_cast_once.py holds that it is all that moved), and by PR 61 for
#: ``kanana-tiny`` alone, both programs: the dropless route's counts are one
#: entry longer (``moe_expert_runs``, the experts that held a row;
#: tests/test_smallthinker.py holds that it is all the routed decode programs
#: gained). A PR that changes one of these programs on purpose makes them
#: again.
DIGESTS = {
    "gpt2": ("619763836527199f", "6abd6276e2abe99c"),
    "rope-dense": ("8f70e9db6dc0658d", "952357a0a289dbae"),
    "kanana-tiny": ("54d583f332e869e4", "5a954ac5a35c342a"),
    "minicpm-tiny": ("3073a533c705512c", "e99fe37a1d657b13"),
}


@pytest.mark.parametrize("arch", sorted(BEFORE))
def test_one_pass_and_no_new_norm_trace_to_the_programs_of_before(arch):
    """Every architecture the repo had traces, jaxpr for jaxpr, to the
    program it had before a stack could loop, and the new fields at their
    defaults change nothing of it."""
    cfg = GPTConfig.make(**BEFORE[arch])
    assert (cfg.n_passes, cfg.post_norms, cfg.exit_gate) == (1, False, False)
    assert cfg.cache_planes == cfg.n_layer
    assert cached_digests(cfg) == DIGESTS[arch]
    assert gen.init_loop_passes(cfg) is None
    params = gpt.init(jax.random.key(0), cfg)
    assert not {"exit_gate_w", "exit_gate_b"} & set(params)
    assert all("post" not in n for s in params.values()
               if isinstance(s, dict) for n in s)
