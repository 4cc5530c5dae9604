"""kanana-2-30b-a3b's architecture (DeepSeek-V3's: a latent cache, a dense
layer before sigmoid-routed dropless experts with a shared one) at a tiny
size on the CPU, seeded weights: the program against the plain reference
``benchmarks/references/deepseek_v3.py`` (logits, loss, cached rows), the
absorbed cached forward against the published form, the route's contract,
and the latent pool through the serving paths that move rows about."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import stacks
from mingpt_distributed_tpu.config import ConfigError, GPTConfig, MeshConfig
from mingpt_distributed_tpu.models import generate as gen
from mingpt_distributed_tpu.models import gpt
from mingpt_distributed_tpu.ops import attention as attn_ops
from mingpt_distributed_tpu.ops import moe
from mingpt_distributed_tpu.parallel import mesh as mesh_lib
from mingpt_distributed_tpu.serving import InferenceServer, Request
from mingpt_distributed_tpu.serving.engine import DecodeEngine
from oracles import solo_greedy
from stack_contract import (  # noqa: F401
    pytest_generate_tests, stack, test_a_planted_fault_reads_not_ok,
    test_combinations_that_are_not_built_are_refused_with_a_sentence,
    test_in_bfloat16_the_engine_holds_the_check_s_law,
    test_the_preset_is_the_published_model)

STACK = stacks.LATENT
VOCAB, BLOCK = stacks.LATENT_VOCAB, stacks.LATENT_BLOCK
TINY, SIZES = stacks.LATENT_GPT, stacks.LATENT_SIZES


@pytest.fixture(scope="module")
def reference():
    return stacks.reference_of(STACK)


@pytest.fixture(scope="module")
def programs():
    return stacks.reference_programs(STACK, SIZES)


def model(**over):
    """Config and parameters with the norms' scales and the bias off their
    initial values, where a factor left out would not show
    (``STACK.perturbed``); the same objects every time."""
    return stacks.model(STACK, **over)


def tokens(n, batch=2, seed=3):
    return stacks.tokens_of(model()[0], batch, n, seed)


# -- the reference and the uncached forward ----------------------------------

@pytest.mark.parametrize("told", [False, True],
                         ids=["own-choice", "told-its-own-choice"])
def test_the_reference_is_the_program_s_forward_in_float32(reference,
                                                           programs, told):
    """Two implementations of one set of equations, float32 on both sides:
    logits to 2e-5 and the loss to 1e-5 relative (sums in another order:
    the reference's experts run masked in blocks, the program's grouped).
    The routed contract: what the layer takes its k best of comes back
    (score + bias for an expert layer, a row with no near-tie for the dense
    one), and a table that holds the reference's own choice changes
    nothing."""
    cfg, params = model()
    seq = tokens(40)
    targets = np.where(np.arange(40) % 5 == 0, -1, np.roll(seq, -1, 1))
    want_logits, want_loss = stacks.forward(params, seq, cfg, targets=targets)
    weights = reference.weights_from_program(params)
    x, ks, vs, router = programs.hidden(weights, seq)
    assert router.shape == (3, 2, 40, 8) and router.dtype == np.float32
    dense_row = np.asarray(router[0, 0, 0])
    assert dense_row.tolist() == [1, 1, 1, -1, -1, -1, -1, -1]
    if told:
        own = np.argsort(-np.asarray(router), -1, kind="stable")[..., :3]
        x, ks, vs, again = programs.hidden(
            weights, seq, own[..., ::-1].astype(np.int32))
        np.testing.assert_allclose(again, router, atol=1e-6)
    np.testing.assert_allclose(programs.logits(weights, x), want_logits,
                               atol=2e-5)
    assert ks.shape == (3, 2, 40, 1, 8) and vs.shape == (3, 2, 40, 1, 32)
    np.testing.assert_allclose(
        programs.loss(weights, seq, targets), want_loss, rtol=1e-5)


def test_the_reference_refuses_what_it_does_not_write(reference):
    cfg, params = model()
    weights = reference.weights_from_program(params)
    for key, value in (("q_lora_rank", 16), ("n_group", 2),
                       ("scoring_func", "softmax")):
        with pytest.raises(ValueError):
            reference.hidden(weights, tokens(8), {**SIZES, key: value})


@pytest.mark.parametrize("what", ["dense_blocks.w_down", "blocks.w_sd",
                                  "blocks.w_e2", "blocks.e_bias"])
def test_every_part_of_the_mlp_is_in(what):
    """Zeroing the dense first layer's MLP, the shared expert, the routed
    experts or the bias (which moves the choice) changes the output."""
    cfg, params = model()
    seq = tokens(24)
    base, _ = stacks.forward(params, seq, cfg)
    part, leaf = what.split(".")
    without = {**params, part: {**params[part], leaf: jnp.zeros_like(
        params[part][leaf])}}
    got, _ = stacks.forward(without, seq, cfg)
    assert float(jnp.abs(got - base).max()) > 1e-4


# -- rope --------------------------------------------------------------------

def test_interleaved_rope_turns_neighbours_and_half_split_does_not():
    x = jax.random.normal(jax.random.key(0), (2, 5, 3, 8))
    cos, sin = attn_ops.rope_tables(jnp.arange(5), 8, 500.0)
    got = np.asarray(attn_ops.apply_rope(x, cos, sin, interleave=True))
    pairs = np.asarray(x).reshape(2, 5, 3, 4, 2)
    turned = (pairs[..., 0] + 1j * pairs[..., 1]) * np.asarray(
        cos + 1j * sin)[None, :, None, :]
    want = np.stack([turned.real, turned.imag], -1).reshape(2, 5, 3, 8)
    np.testing.assert_allclose(got, want, atol=1e-6)
    half = np.asarray(attn_ops.apply_rope(x, cos, sin))
    assert np.abs(half - want).max() > 0.1
    # position 0 is the identity either way
    np.testing.assert_allclose(got[:, 0], np.asarray(x)[:, 0], atol=1e-7)


def test_a_half_split_rope_disagrees_with_the_reference(reference, programs):
    cfg, params = model(rope_interleave=False)
    seq = tokens(24)
    logits, _ = stacks.forward(params, seq, cfg)
    weights = reference.weights_from_program(params)
    ref = programs.logits(weights, programs.hidden(weights, seq)[0])
    assert float(jnp.abs(ref - logits).max()) > 1e-3
    half = stacks.reference_programs(
        STACK, {**SIZES, "rope_interleave": False})
    same = half.logits(weights, half.hidden(weights, seq)[0])
    np.testing.assert_allclose(same, logits, atol=2e-5)


# -- the cached forward: absorbed, against the published form -----------------

def cached_logits(cfg, params, seq, n_prompt):
    """Prefill ``seq[:n_prompt]`` then decode the rest a token a step at a
    position a lane: the logits after every step, and the cache."""
    cache = gen.init_cache(cfg, 2)
    # a jit a call: a test calls this before its patch and after it, inside
    # one epoch of the shared programs
    step = jax.jit(lambda c, t, o: gen._forward_cached(params, t, c, o, cfg))
    logits, cache = step(cache, seq[:, :n_prompt], 0)
    out = [logits]
    for t in range(n_prompt, seq.shape[1]):
        logits, cache = step(cache, seq[:, t:t + 1], np.full((2,), t))
        out.append(logits)
    return jnp.stack(out, 1), cache


def test_the_absorbed_cached_forward_equals_the_published_form(reference,
                                                               programs):
    """Prefill and decode attend the cached latents absorbed (queries through
    W_UK, outputs through W_UV); ``gpt.forward`` and the reference take the
    latent up to per-head keys and values. Float32 both: logits to 1e-4
    (another order of the same sums), the cached rows (the rotated rope key
    and the normed latent, nothing else) to 1e-5."""
    cfg, params = model()
    seq = tokens(30)
    got, cache = cached_logits(cfg, params, seq, 20)
    want, _ = stacks.forward(params, seq, cfg)
    np.testing.assert_allclose(got, want[:, 19:], atol=1e-4)
    weights = reference.weights_from_program(params)
    x, ks, vs, _ = programs.hidden(weights, seq)
    np.testing.assert_allclose(programs.logits(weights, x)[:, 19:], got,
                               atol=1e-4)
    assert cache["k"].shape == (3, 2, BLOCK, 1, 8)
    assert cache["v"].shape == (3, 2, BLOCK, 1, 32)
    np.testing.assert_allclose(cache["k"][:, :, :30], ks, atol=1e-5)
    np.testing.assert_allclose(cache["v"][:, :, :30], vs, atol=1e-5)


def test_a_long_prefill_walks_the_slice_in_blocks(monkeypatch):
    """Past ``LATENT_KV_BLOCK`` rows a chunk attends block by block under a
    running softmax and stops at its own last row: the same numbers."""
    cfg, params = model()
    seq = tokens(40)
    want, _ = cached_logits(cfg, params, seq, 36)
    monkeypatch.setattr(attn_ops, "LATENT_KV_BLOCK", 16)
    got, _ = cached_logits(cfg, params, seq, 36)
    np.testing.assert_allclose(got, want, atol=1e-5)


@pytest.mark.parametrize("walk", [False, True], ids=["one-pass", "walked"])
def test_the_two_part_step_is_the_laid_over_step(walk, walk_in_blocks):
    """``latent_attend_step`` reads the cache as it lies and attends each
    lane's own new row beside it; what it replaced laid the new rows over
    the slice (``_lay_rows_over``) and attended that in one pass. The same
    sums in another order: float32 rounding apart."""
    keys = jax.random.split(jax.random.key(6), 6)
    lanes, rows, heads, r, e = 3, 32, 4, 16, 4
    # walked, or as the rule has so small a house: in one pass
    plan = walk_in_blocks(8)(rows) if walk else attn_ops.StepWalk(rows, 1, 0)
    q_lat = jax.random.normal(keys[0], (lanes, 1, heads, r))
    q_pe = jax.random.normal(keys[1], (lanes, 1, heads, e))
    latents = jax.random.normal(keys[2], (2, lanes, rows, 1, r))
    pes = jax.random.normal(keys[3], (2, lanes, rows, 1, e))
    new = jax.random.normal(keys[4], (lanes, 1, 1, r))
    new_pe = jax.random.normal(keys[5], (lanes, 1, 1, e))
    positions = jnp.array([0, 13, rows - 1])
    got = attn_ops.latent_attend_step(
        q_lat, q_pe, latents, pes, 1, new, new_pe, positions, plan,
        scale=0.2)
    want = attn_ops.latent_attention(
        q_lat, q_pe, gen._lay_rows_over(latents[1], new, positions),
        gen._lay_rows_over(pes[1], new_pe, positions), kv_offset=positions,
        scale=0.2)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-6)
    # a reach short of a lane's position cuts that lane alone
    if walk:
        short = attn_ops.latent_attend_step(
            q_lat, q_pe, latents, pes, 1, new, new_pe, positions, plan,
            frontier=jnp.minimum(positions, 13), scale=0.2)
        np.testing.assert_array_equal(short[:2], got[:2])
        assert not np.array_equal(short[2], got[2])


def slice_sized_selects(text, lanes, rows):
    """``select`` instructions of a lowered (StableHLO) or compiled (HLO)
    program whose result is a layer's slice of the pool, ``(lanes, rows, 1,
    size)``."""
    return re.findall(
        rf"= \w+\[{lanes},{rows},1,\d+\]\S* select\(.*"
        rf"|stablehlo\.select.*tensor<{lanes}x{rows}x1x\d+x\w+>$", text,
        re.M)


def test_the_decode_program_selects_nothing_of_a_slice_s_size(walk_in_blocks):
    """The engine's decode program for a latent pool, lowered and compiled
    for this backend: no ``select`` of a ``(lanes, rows, 1, size)`` operand
    (the laid-over form had one a leaf and layer, and on the chip it bound
    the read: PERF.md, PRs 30 and 33). The old form is the control."""
    walk_in_blocks(16)
    cfg, params = model()
    eng = DecodeEngine(params, cfg, n_slots=5, prefill_len=32)
    (_, _, jitted, args, kwargs), = [
        p for p in eng.programs() if p[0] == "decode"]
    lowered = jitted.lower(*args, **kwargs)
    for text in (lowered.as_text(), lowered.compile().as_text()):
        assert "while" in text                    # the walk is in it
        assert not slice_sized_selects(text, 5, BLOCK)

    def laid_over(cache, rows, positions):
        return gen._lay_rows_over(cache["v"][1], rows, positions).sum(1)
    control = jax.jit(laid_over).lower(
        eng.pool.cache, jnp.zeros((5, 1, 1, 32)), jnp.zeros(5, jnp.int32))
    for text in (control.as_text(), control.compile().as_text()):
        assert slice_sized_selects(text, 5, BLOCK)


def expert_sized(text, cfg, layers):
    """Instructions of compiled HLO ``text`` that make a buffer shaped as
    one layer's (E, D, F) experts or the ``layers``-deep stack's: anything
    but the leaf itself seen under another shape."""
    widths = {(cfg.n_embd, cfg.expert_width), (cfg.expert_width, cfg.n_embd)}
    made = [(m.group(0), [int(n) for n in m.group(1).split(",")])
            for m in re.finditer(
                r"= \w+\[([\d,]+)\]\S* "
                r"(?!parameter|bitcast|get-tuple-element)[\w-]+\(.*", text)]
    return [line for line, dims in made if tuple(dims[-2:]) in widths
            and np.prod(dims[:-2]) in (cfg.n_experts, layers * cfg.n_experts)]


def test_the_decode_program_copies_nothing_of_an_expert_stack_s_size():
    """The engine's decode and prefill programs compiled for this backend:
    the stacked expert leaves reach the experts' loop as they lie: nothing
    of a layer's experts' size, or the stack's, is copied, sliced or
    converted on the way (sliced out before the loop, a layer's experts are
    copied whole: on the chip 1.1 GB a layer, PERF.md, PR 30). That form is
    the control."""
    cfg, params = model()
    eng = DecodeEngine(params, cfg, n_slots=5, prefill_len=32)
    for family in ("decode", "prefill"):
        _, _, jitted, args, kwargs = [
            p for p in eng.programs() if p[0] == family][-1]
        text = jitted.lower(*args, **kwargs).compile().as_text()
        assert "moe_experts/while" in text          # the loop is in it
        assert not expert_sized(text, cfg, 2)

    def sliced_first(x, chosen, blocks):
        return moe.grouped_swiglu(x, chosen, *(
            blocks[n][1] for n in ("w_eg", "w_e1", "w_e2")))[0]
    control = jax.jit(sliced_first).lower(
        jnp.zeros((5, 64)), jnp.zeros((5, 3), jnp.int32), params["blocks"])
    assert expert_sized(control.compile().as_text(), cfg, 2)


@pytest.mark.parametrize("furthest", [15, 16, 17],
                         ids=["below-an-edge", "on-an-edge", "above-an-edge"])
def test_the_engine_s_step_under_a_live_mask_is_its_step_under_all_true(
        furthest, walk_in_blocks):
    """Through ``DecodeEngine.decode_step`` on the whole tiny model (a
    dense layer, routed layers): the live lanes' tokens equal and their
    written rows equal to 1e-6 (the routed layers' grouped matmuls see the
    dead lanes' other rows beside them). A live lane at the window's last
    row beside parked lanes reads its whole slot."""
    walk_in_blocks(16)
    cfg, params = model()
    eng = DecodeEngine(params, cfg, n_slots=4, prefill_len=32)
    keys = jax.random.split(jax.random.key(8), 2)
    pool = {"k": jax.random.normal(keys[0], eng.pool.cache["k"].shape),
            "v": jax.random.normal(keys[1], eng.pool.cache["v"].shape)}

    def step(positions, live):
        eng.pool.cache = {**eng.pool.cache, **jax.tree.map(jnp.array, pool)}
        s = eng.n_slots
        tokens = eng.decode_step(
            np.array([3, 9, 27, 41], np.int32), positions,
            np.ones(s, np.float32), np.zeros(s, np.int32),
            np.ones(s, np.float32), np.zeros(s, bool),
            np.zeros(s, np.uint32), None, live)
        return tokens, {n: np.asarray(eng.pool.cache[n]) for n in ("k", "v")}

    for positions, live in (
            ([furthest, 5, BLOCK - 1, 50], [True, True, False, False]),
            ([BLOCK - 1, 5, BLOCK - 1, BLOCK - 1],
             [True, True, False, False])):
        positions, live = np.array(positions, np.int32), np.array(live)
        got, got_rows = step(positions, live)
        want, want_rows = step(positions, np.ones(4, bool))
        np.testing.assert_array_equal(got[live], want[live])
        for name in ("k", "v"):
            for lane in np.flatnonzero(live):
                np.testing.assert_allclose(
                    got_rows[name][:, lane, positions[lane]],
                    want_rows[name][:, lane, positions[lane]], rtol=1e-6,
                    atol=1e-7)
    assert eng.compile_counts()["decode"] == 1


def test_the_engine_s_programs_agree_with_the_reference(reference):
    """Prefill then decode through ``DecodeEngine``'s own programs against
    the reference's full forward over the same sequence, float32: each
    emitted token's logit within 1e-4 of the reference's best (logits, not
    tokens: with seeded weights the best two are often a rounding apart),
    the rows the programs left in the slot to 1e-5 relative."""
    cfg, params = model()
    eng = DecodeEngine(params, cfg, n_slots=3, prefill_len=32,
                       prefill_buckets=[16, 32])
    prompt = np.asarray(tokens(21, batch=1, seed=5)[0])
    tok, _ = eng.prefill_chunk_call(1, prompt.tolist(), 0, 1.0, None, None,
                                    False, 0)
    seq = prompt.tolist() + [tok]
    for _ in range(5):
        t, p = np.zeros(3, np.int32), np.full(3, BLOCK - 1, np.int32)
        t[1], p[1] = seq[-1], len(seq) - 1
        nxt = eng.decode_step(t, p, np.ones(3, np.float32),
                              np.zeros(3, np.int32), np.ones(3, np.float32),
                              np.zeros(3, bool), np.zeros(3, np.uint32))
        seq.append(int(nxt[1]))
    weights = reference.weights_from_program(eng.params)
    x, ks, vs, _ = reference.hidden(weights, np.asarray([seq[:-1]]), SIZES)
    ref = np.asarray(reference.logits(weights, x[0, 20:]))
    assert (ref.max(-1) - ref[np.arange(6), seq[21:]]).max() <= 1e-4
    for name, rows in (("k", ks), ("v", vs)):
        got = np.asarray(eng.pool.cache[name][:, 1, :26])
        want = np.asarray(rows[:, 0])
        assert np.sqrt(((got - want) ** 2).sum() / (want ** 2).sum()) < 1e-5
    # 26 real tokens took 3 routes in each of 2 expert layers; the lanes
    # without a request and the prefill's padding were neither routed nor
    # counted, and the loops ran fewer blocks than their layouts have
    # (PR 61: a twelfth entry, the experts that held a row, a call, summed)
    counter = eng.moe_rows()
    assert counter.shape == (2, 12)
    assert counter[:, 8].tolist() == [78, 78]
    assert counter[:, :8].sum(1).tolist() == [78, 78]
    assert (counter[:, 9] < counter[:, 10]).all() and (counter[:, 9] > 0).all()
    # six calls (a prefill and five steps): an expert or more each, at most
    # eight, and never more than the blocks that ran
    assert (counter[:, 11] >= 6).all() and (counter[:, 11] <= 48).all()
    assert (counter[:, 11] <= counter[:, 9]).all()


def test_a_bfloat16_engine_keeps_one_copy_of_the_weights():
    """bfloat16 activations and parameters, as kanana is served:
    ``cast_once_params`` returns the tree it was given. (The check's law in
    bfloat16, and the reference of another rope that fails it, are the
    contract's, by ``STACK.verdict_lengths`` and ``STACK.faults``.)"""
    cfg, params = model(**stacks.BF16)
    eng = DecodeEngine(params, cfg, **STACK.serve)
    assert eng.program_params is eng.params is params
    assert eng.n_cast_leaves == 0
    assert eng.program_param_bytes == 2 * gpt.param_count(params)


# -- the route ----------------------------------------------------------------

def routed(n=48, e=8, d=32, f=16, seed=0, skew=0.0):
    keys = jax.random.split(jax.random.key(seed), 6)
    x = jax.random.normal(keys[0], (n, d))
    w_router = jax.random.normal(keys[1], (d, e))
    # skew: every token's scores lean to the first experts
    bias = jnp.zeros((e,)).at[:3].set(skew)
    w = [0.1 * jax.random.normal(k, s) for k, s in zip(
        keys[2:5], ((e, d, f), (e, d, f), (e, f, d)))]
    return x, w_router, bias, w


def dense_experts(x, chosen, gates, w):
    """Every chosen expert on its token, the plain way."""
    gate, up, down = w
    inner = jax.nn.silu(jnp.einsum("nd,edf->nef", x, gate)) \
        * jnp.einsum("nd,edf->nef", x, up)
    every = jnp.einsum("nef,efd->ned", inner, down)
    picked = jnp.take_along_axis(every, chosen[..., None], axis=1)
    return jnp.einsum("nkd,nk->nd", picked, gates)


def test_gates_are_the_scores_normalised_and_scaled():
    x, w_router, _, _ = routed()
    bias = jnp.linspace(-0.5, 0.5, 8)
    chosen, gates, select = moe.sigmoid_routes(
        x, w_router, bias, top_k=3, norm_topk=True, route_scale=2.448)
    np.testing.assert_allclose(gates.sum(-1), 2.448, rtol=1e-5)
    scores = jax.nn.sigmoid(x @ w_router)
    np.testing.assert_allclose(select, scores + bias, atol=1e-6)
    # the bias moves the choice...
    plain, plain_gates, _ = moe.sigmoid_routes(
        x, w_router, jnp.zeros(8), top_k=3, norm_topk=True, route_scale=2.448)
    assert (np.sort(chosen, -1) != np.sort(plain, -1)).any()
    # ...and never a gate: a gate is its expert's score over the chosen sum
    picked = jnp.take_along_axis(scores, chosen, -1)
    np.testing.assert_allclose(
        gates, 2.448 * picked / picked.sum(-1, keepdims=True), rtol=1e-5)
    _, raw, _ = moe.sigmoid_routes(x, w_router, bias, top_k=3,
                                   norm_topk=False, route_scale=1.0)
    np.testing.assert_allclose(raw, picked, rtol=1e-6)


@pytest.mark.parametrize("skew", [0.0, 4.0], ids=["level", "three-hot"])
def test_the_dropless_route_computes_every_row_where_capacity_drops(skew):
    """With every token leaning to three experts of eight, the capacity
    route at factor 1 drops most rows; the dropless route computes all of
    them, its counter says so, and the result is the plain sum."""
    x, w_router, bias, w = routed(skew=skew)
    chosen, gates, _ = moe.sigmoid_routes(
        x, w_router, bias, top_k=3, norm_topk=True, route_scale=1.0)
    out, counts = jax.jit(moe.grouped_swiglu)(x, chosen, w[1], w[0], w[2])
    want = dense_experts(x, chosen, gates, (w[1], w[0], w[2]))
    np.testing.assert_allclose(jnp.einsum("nkd,nk->nd", out, gates), want,
                               atol=1e-5)
    assert int(counts[8]) == 48 * 3 == int(counts[:8].sum())
    np.testing.assert_array_equal(
        counts[:8], np.bincount(np.asarray(chosen).ravel(), minlength=8))
    if skew:
        assert int(counts[:3].sum()) == 48 * 3      # all on three experts
        # the same load through the capacity route: rows are dropped
        full = moe.moe_mlp(x[None], w_router + skew * jnp.eye(32, 8)[:, :8],
                           w[0], w[2], top_k=3, capacity_factor=8 / 3,
                           w_gate=w[1])[0]
        tight = moe.moe_mlp(x[None], w_router + skew * jnp.eye(32, 8)[:, :8],
                            w[0], w[2], top_k=3, capacity_factor=1.0,
                            w_gate=w[1])[0]
        assert float(jnp.abs(full - tight).max()) > 1e-3


def test_the_counter_counts_the_valid_tokens_only():
    x, w_router, bias, w = routed()
    chosen, _, _ = moe.sigmoid_routes(x, w_router, bias, top_k=3,
                                      norm_topk=True, route_scale=1.0)
    valid = jnp.arange(48) < 10
    _, counts = moe.grouped_swiglu(x, chosen, w[1], w[0], w[2], valid)
    assert int(counts[8]) == 30 == int(counts[:8].sum())
    np.testing.assert_array_equal(
        counts[:8], np.bincount(np.asarray(chosen[:10]).ravel(), minlength=8))


def route_by_route(x, chosen, w, bm):
    """Each route's row through its expert's three matrices, alone in a
    block of ``bm`` rows of zeros (a matmul of another height may sum a
    row in another order)."""
    gate, up, down = (np.asarray(a) for a in w)
    out = np.zeros(chosen.shape + x.shape[1:], np.float32)
    for t, j in np.ndindex(*chosen.shape):
        ex = int(chosen[t, j])
        rows = jnp.zeros((bm,) + x.shape[1:]).at[0].set(x[t])
        inner = jax.nn.silu(jnp.dot(rows, gate[ex])) * jnp.dot(rows, up[ex])
        out[t, j] = jnp.dot(inner, down[ex])[0]
    return out


@pytest.mark.parametrize("leaves", ["a-layer-s", "the-stack-s"])
@pytest.mark.parametrize("load,held", [
    ("even", "some"), ("one-expert", "some"), ("skewed", "some"),
    ("skewed", "none"), ("skewed", "unsaid"), ("even", "unsaid")])
def test_only_the_blocks_that_hold_a_valid_token_s_route_are_run(
        load, held, leaves):
    """The routes of ``valid`` tokens alone are laid out, and the loop takes
    through an expert only the blocks that hold one: a valid token's
    outputs are, exactly, its rows through its experts' matrices whatever
    else the call holds; any other token's are zeros; the counts say what
    ran (``valid=None``: every token is a request's). Both loops: over a
    layer's own leaves (a conditional a step: the form that is
    differentiated) and over the stack's with the layer's index (as many
    steps as blocks hold a row: the cached forward's)."""
    n, k, e = 24, 2, 8
    x, w_router, bias, w = routed(n=n, skew=4.0)
    w = (w[1], w[0], w[2])
    stacked = leaves == "the-stack-s"
    given = tuple(jnp.stack([-a, a]) for a in w) + (1,) if stacked else w
    chosen = {
        "even": (k * jnp.arange(n)[:, None] + jnp.arange(k)) % e,
        "one-expert": jnp.full((n, k), 3),
        "skewed": moe.sigmoid_routes(x, w_router, bias, top_k=k,
                                     norm_topk=True, route_scale=1.0)[0],
    }[load].astype(jnp.int32)
    valid = {"some": (jnp.arange(n) % 3 != 1) & (jnp.arange(n) < 20),
             "none": jnp.zeros(n, bool), "unsaid": None}[held]
    out, counts = jax.jit(
        lambda x, chosen, wg, wu, wd, valid, layer=None: moe.grouped_swiglu(
            x, chosen, wg, wu, wd, valid, layer), static_argnums=6)(
        x, chosen, *given[:3], valid, *given[3:])
    out, counts = np.asarray(out), np.asarray(counts)
    assert out.shape == (n, k, 32) and counts.shape == (e + 4,)
    assert np.isfinite(out).all()

    asked = np.ones(n, bool) if valid is None else np.asarray(valid)
    bm = moe._row_block(n * k, e)
    np.testing.assert_array_equal(out[asked],
                                  route_by_route(x, chosen, w, bm)[asked])
    assert not out[~asked].any()
    sizes = np.bincount(np.asarray(chosen)[asked].ravel(), minlength=e)
    np.testing.assert_array_equal(counts[:e], sizes)
    assert counts[e] == k * asked.sum() == sizes.sum()
    assert counts[e + 1] == (-(-sizes // bm)).sum()
    assert counts[e + 2] == -(-n * k // bm) + e - 1
    assert counts[e + 3] == (sizes > 0).sum()       # PR 61: the experts held
    if held == "none":
        assert counts[e + 1] == 0
    elif load == "one-expert":
        assert counts[e + 1] == -(-k * asked.sum() // bm) > 1


@pytest.fixture
def kernel_path(monkeypatch):
    """The cached path's blocks through the Pallas kernel, interpreted (off
    the chip ``grouped_swiglu`` keeps the loop; the kernel's ``interpret``
    is still ``flash_attention._interpret()``'s word, true here). The
    kernel's jitted caller forgets its traces on both sides."""
    monkeypatch.setattr(moe, "_mosaic_compiles", lambda: True)
    run_blocks = moe._run_blocks
    run_blocks.clear_cache()
    yield
    run_blocks.clear_cache()


def stacked_call(x, chosen, w, valid):
    """``grouped_swiglu`` on layer 1 of a two-layer stack whose layer 0
    holds the negatives (a wrong ``first`` shows), jitted anew."""
    given = tuple(jnp.stack([-a, a]) for a in w)
    out, counts = jax.jit(lambda x, chosen, wg, wu, wd, valid:
                          moe.grouped_swiglu(x, chosen, wg, wu, wd, valid, 1))(
        x, chosen, *given, valid)
    return np.asarray(out), np.asarray(counts)


def skewed(n=24, k=2, e=8, f=16):
    """-> (tokens, the experts' (gate, up, down), each token's k experts by
    a router that leans to the first three)."""
    x, w_router, bias, w = routed(n=n, e=e, f=f, skew=4.0)
    chosen = moe.sigmoid_routes(x, w_router, bias, top_k=k, norm_topk=True,
                                route_scale=1.0)[0]
    return x, (w[1], w[0], w[2]), chosen


#: bm -> (tokens, routes a token, experts) whose layout rule yields it
BLOCK_SHAPES = {8: (24, 2, 8), 128: (96, 2, 2), 256: (200, 2, 2)}


@pytest.mark.parametrize("held", ["some", "none", "unsaid"])
@pytest.mark.parametrize("bm", sorted(BLOCK_SHAPES))
def test_the_kernel_runs_the_blocks_the_loop_runs(bm, held, monkeypatch):
    """The Pallas kernel (interpret mode) in the loop's place, on the same
    layout, at every height of block the cells' programs lay (8 rows in a
    decode step, 128-256 in a prefill): a valid token's outputs are its rows
    through its experts' matrices exactly, as the loop's are; any other
    token's are zeros (with no valid token the grid has no step); the counts
    agree field for field."""
    n, k, e = BLOCK_SHAPES[bm]
    assert moe._row_block(n * k, e) == bm
    x, w, chosen = skewed(n, k, e)
    valid = {"some": (jnp.arange(n) % 3 != 1) & (jnp.arange(n) < n - 4),
             "none": jnp.zeros(n, bool), "unsaid": None}[held]
    looped, loop_counts = stacked_call(x, chosen, w, valid)
    monkeypatch.setattr(moe, "_mosaic_compiles", lambda: True)
    out, counts = stacked_call(x, chosen, w, valid)

    np.testing.assert_array_equal(counts, loop_counts)
    np.testing.assert_array_equal(out, looped)
    asked = np.ones(n, bool) if valid is None else np.asarray(valid)
    np.testing.assert_array_equal(out[asked],
                                  route_by_route(x, chosen, w, bm)[asked])
    assert not out[~asked].any()
    assert counts[e] == k * asked.sum()
    assert (counts[e + 1] == 0) == (held == "none")


def test_an_expert_s_blocks_in_a_row_go_through_the_kernel(kernel_path):
    """One expert holding every route: six blocks of 8 rows in a row read
    the same three matrices (the pipeline fetches them once), and each
    block's rows are its own."""
    n, k, e = 24, 2, 8
    x, w, _ = skewed(n, k, e)
    chosen = jnp.full((n, k), 3, jnp.int32)
    out, counts = stacked_call(x, chosen, w, None)
    np.testing.assert_array_equal(out, route_by_route(x, chosen, w, 8))
    assert counts[e + 1] == 6 and counts[3] == n * k


def test_what_lies_past_the_blocks_that_ran_is_never_read(kernel_path,
                                                           monkeypatch):
    """The kernel writes the blocks that hold a row and no other; whatever
    the buffer holds past them (on the chip: anything) reaches no output:
    a route nobody asked for reads the fill, not the layout."""
    n, e = 24, 8
    x, w, chosen = skewed(n, 2, e)
    valid = jnp.arange(n) % 3 != 1
    clean, counts = stacked_call(x, chosen, w, valid)
    run_blocks, planted = moe._run_blocks, []

    def with_garbage(laid, expert, ran, *weights):
        out = run_blocks(laid, expert, ran, *weights)
        past = jnp.arange(out.shape[0]) >= ran
        planted.append(out.shape[0])
        return jnp.where(past[:, None, None], jnp.nan, out)
    monkeypatch.setattr(moe, "_run_blocks", with_garbage)
    dirty, dirty_counts = stacked_call(x, chosen, w, valid)
    assert planted and planted[0] > counts[e + 1]   # there are such blocks
    np.testing.assert_array_equal(dirty, clean)
    np.testing.assert_array_equal(dirty_counts, counts)


@pytest.mark.parametrize("f,budget,tile", [
    (768, 64 << 20, 768), (512, 64 << 20, 512), (768, 12 << 20, 384),
    (768, 5 << 20, 128), (200, 1 << 20, 200)],
    ids=["kanana-whole", "laguna-whole", "halves", "lanes", "no-divisor"])
def test_the_kernel_tiles_an_expert_s_width_where_its_buffers_ask(
        f, budget, tile, monkeypatch):
    """An expert's three matrices twice over fit the kernel's buffers whole
    at both cells' sizes; a wider expert takes the largest multiple of 128
    columns that divides ``F`` and fits."""
    monkeypatch.setattr(moe, "_WEIGHT_BUFFERS", budget)
    assert moe._width_tile(2048, f, 2) == tile


def test_a_tiled_width_sums_to_the_whole_one_s_product(kernel_path,
                                                       monkeypatch):
    """Two tiles of ``F``: the down product of the second adds to the
    first's in the output block (float32; the sum's order is the only
    difference from the loop's)."""
    f = 256
    x, w, chosen = skewed(f=f)
    whole, counts = stacked_call(x, chosen, w, None)
    monkeypatch.setattr(moe, "_WEIGHT_BUFFERS", 2 * 3 * 32 * 128 * 4)
    assert moe._width_tile(32, f, 4) == 128
    moe._run_blocks.clear_cache()
    tiled, tiled_counts = stacked_call(x, chosen, w, None)
    np.testing.assert_array_equal(tiled_counts, counts)
    np.testing.assert_allclose(tiled, whole, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(tiled, route_by_route(x, chosen, w, 8),
                               rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("steered", [False, True], ids=["as-it-is", "steered"])
def test_off_the_chip_the_cached_path_keeps_the_loop(steered, monkeypatch):
    """Which of the three forms a call takes follows from what it can see:
    without ``layer`` (the call ``gpt.forward`` trains through) the static
    loop with a conditional a step, whatever the backend; with ``layer`` the
    dynamic loop where Mosaic does not compile (here: the CPU suite's pinned
    serving programs stand as they were) and the kernel where it does, which
    is ``flash_attention._interpret``'s word, asked through its module as a
    compile rehearsal steers it."""
    from mingpt_distributed_tpu.ops import flash_attention
    x, _, _, w = routed(n=6)
    stack = tuple(jnp.stack([a, a]) for a in (w[1], w[0], w[2]))
    chosen = jnp.zeros((6, 2), jnp.int32)
    if steered:
        monkeypatch.setattr(flash_attention, "_interpret", lambda: False)
        moe._run_blocks.clear_cache()
    cached = str(jax.make_jaxpr(lambda *a: moe.grouped_swiglu(*a, None, 1))(
        x, chosen, *stack))
    trained = str(jax.make_jaxpr(lambda *a: moe.grouped_swiglu(*a))(
        x, chosen, w[1], w[0], w[2]))
    moe._run_blocks.clear_cache()
    assert ("pallas_call" in cached) == steered
    assert ("grouped_swiglu" in cached) == steered
    assert "pallas_call" not in trained and "cond" in trained
    if not steered:
        lowered = jax.jit(lambda *a: moe.grouped_swiglu(*a, None, 1)).lower(
            x, chosen, *stack).as_text(debug_info=True)
        assert "moe_experts/while" in lowered


def test_a_lane_s_output_does_not_depend_on_which_lanes_are_live():
    """The decode step routes the lanes' rows together; a lane alone, or
    beside other tokens, takes the same experts and gets the same output
    (to the last bits: a matmul's row may be summed in another order beside
    other rows, nothing more)."""
    x, w_router, bias, w = routed(n=6, seed=4)
    run = jax.jit(lambda rows: moe.moe_dropless(
        rows[:, None], w_router, bias, w[1], w[0], w[2], top_k=3,
        route_scale=2.448)[0][:, 0])
    routes = jax.jit(lambda rows: moe.sigmoid_routes(
        rows, w_router, bias, top_k=3, norm_topk=True, route_scale=2.448)[:2])
    together = run(x)
    for lane in range(6):
        np.testing.assert_allclose(run(x[lane:lane + 1])[0], together[lane],
                                   rtol=1e-5, atol=1e-7)
    others = x.at[1:].set(jax.random.normal(jax.random.key(9), (5, 32)))
    np.testing.assert_allclose(run(others)[0], together[0], rtol=1e-5,
                               atol=1e-7)
    for a, b in zip(routes(others), routes(x)):
        np.testing.assert_array_equal(a[0], b[0])


# -- configuration: the sentences and the preset are the contract's, by
# ``STACK.refused`` and ``STACK.published`` ------------------------------------------------------------

@pytest.mark.parametrize("how", ["int8-pool", "tp-over-the-latent"])
def test_the_engine_refuses_what_the_latent_pool_is_not_built_for(how):
    cfg, params = model()
    kwargs = {"kv_dtype": "int8"} if how == "int8-pool" else {
        "mesh": mesh_lib.make_mesh(MeshConfig(tp=2), devices=jax.devices()[:2])}
    with pytest.raises(ConfigError, match="latent"):
        DecodeEngine(params, cfg, n_slots=2, **kwargs)


def test_parameters_are_made_in_param_dtype_and_a_mesh_still_builds():
    cfg, _ = model(**stacks.BF16)
    params = jax.eval_shape(lambda: gpt.init(jax.random.key(0), cfg))
    assert {a.dtype for a in jax.tree.leaves(params)} == {jnp.dtype("bfloat16")}
    assert params["dense_blocks"]["w_gate"].shape == (1, 64, 96)
    assert params["blocks"]["w_e1"].shape == (2, 8, 64, 24)
    assert params["blocks"]["w_sg"].shape == (2, 64, 48)
    mesh = mesh_lib.make_mesh(MeshConfig(dp=2, fsdp=2, tp=2),
                              devices=jax.devices()[:8])
    shardings = mesh_lib.param_shardings(mesh, params)
    assert jax.tree.structure(shardings) == jax.tree.structure(params)
    # a bfloat16 draw is the float32 draw rounded, not another stream
    f32 = gpt.init(jax.random.key(0),
                   stacks.tiny_cfg(STACK, dtype="bfloat16"))
    bf16 = gpt.init(jax.random.key(0), cfg)
    np.testing.assert_array_equal(
        np.asarray(f32["blocks"]["w_kv_b"].astype(jnp.bfloat16), np.float32),
        np.asarray(bf16["blocks"]["w_kv_b"], np.float32))


def test_the_training_path_takes_the_new_leaves():
    """Every leaf has a decay rule (the bias and the latent's norm scale are
    not decayed) and the loss has a finite gradient in every leaf; the
    router's comes through the gates (the bias, which only moves a choice,
    has none, as published)."""
    from mingpt_distributed_tpu.training import optimizer

    cfg, params = model()
    mask = optimizer.decay_mask(params)
    assert mask["blocks"]["w_kv_b"] and mask["blocks"]["w_sd"]
    assert not mask["blocks"]["e_bias"]
    assert not mask["dense_blocks"]["kv_norm_scale"]
    seq = tokens(24)
    grads = jax.jit(jax.grad(lambda p: gpt.forward(
        p, seq, cfg, targets=np.roll(seq, -1, 1))[1]))(params)
    for path, g in jax.tree_util.tree_leaves_with_path(grads):
        assert bool(jnp.isfinite(g).all()), path
    assert float(jnp.abs(grads["blocks"]["w_router"]).max()) > 0
    assert float(jnp.abs(grads["dense_blocks"]["w_kv_a"]).max()) > 0
    assert float(jnp.abs(grads["blocks"]["e_bias"]).max()) == 0


# -- the latent pool through the serving paths --------------------------------

def serve(cfg, params, prompts, **kwargs):
    server = InferenceServer(params, cfg, n_slots=2, prefill_len=32,
                             prefill_buckets=[8, 16, 32], **kwargs)
    handles = server.generate_batch([
        Request(prompt=p, max_new_tokens=6, do_sample=False) for p in prompts])
    return server, [h.tokens for h in handles]


@pytest.fixture(scope="module")
def served():
    cfg, params = model()
    rng = np.random.default_rng(0)
    shared = rng.integers(0, VOCAB, size=17).tolist()
    prompts = [shared + rng.integers(0, VOCAB, size=n).tolist()
               for n in (3, 5, 4, 6, 2)]
    server, tokens = serve(cfg, params, prompts)
    return cfg, params, prompts, server, tokens


def test_slots_are_reused_and_the_tokens_are_solo_generate_s(served):
    """Five requests through two slots: a slot's next tenant attends none
    of the last one's latents, and every greedy stream is ``generate``'s."""
    cfg, params, prompts, server, tokens = served
    for prompt, got in zip(prompts, tokens):
        assert got == solo_greedy(params, cfg, prompt, 6)
    summary = server.metrics.summary()
    assert summary["kv_bytes_per_row"] == 3 * (8 + 32) * 4
    assert summary["moe_dropped_rows"] == 0
    assert summary["moe_routed_rows"] > 0
    assert summary["moe_load_max_over_mean"] >= 1.0
    assert 0 < summary["moe_blocks_run"] < summary["moe_blocks_laid"]
    assert summary["program_weights_cast"] == 0
    facts = server.engine.pool.audit_facts()
    assert facts["cache_leaf_shapes"] == {"k": (3, 2, BLOCK, 1, 8),
                                          "v": (3, 2, BLOCK, 1, 32)}
    assert facts["cache_leaf_elems"] == 3 * 2 * BLOCK * 8


def test_a_request_s_last_step_at_the_window_s_last_row_is_routed():
    """A request that fills the window takes its last step where a lane
    without a request is parked; ``live`` and not the position says which
    it is, so its last token is ``generate``'s (its routes laid out like
    any other step's), beside a free lane and with every lane busy."""
    cfg, params = model()
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, VOCAB, size=n).tolist() for n in (30, 29)]
    new = [BLOCK - len(p) + 1 for p in prompts]
    want = [solo_greedy(params, cfg, p, n) for p, n in zip(prompts, new)]
    for busy in (1, 2):
        server = InferenceServer(params, cfg, n_slots=2, prefill_len=32)
        handles = server.generate_batch([
            Request(prompt=p, max_new_tokens=n, do_sample=False)
            for p, n in zip(prompts[:busy], new)])
        assert [h.tokens for h in handles] == want[:busy]
        assert server.metrics.summary()["moe_dropped_rows"] == 0


def test_the_prefix_store_copies_latent_rows(served):
    cfg, params, prompts, _, want = served
    server, got = serve(cfg, params, prompts, prefix_cache_mb=1.0)
    assert got == want
    summary = server.metrics.summary()
    assert summary["prefix_hits"] >= 3 and summary["prefix_rows_reused"] >= 48
    (_, entry), *_ = server.engine.prefix_store.entries()
    assert sorted(entry) == ["k", "v"]
    assert entry["k"].shape[3:] == (1, 8) and entry["v"].shape[3:] == (1, 32)


def test_speculative_verify_scores_against_the_latent_pool(served):
    """A draft of another architecture proposes, the latent target verifies:
    the tokens are the plain server's."""
    cfg, params, prompts, _, want = served
    draft_cfg = GPTConfig.make(
        n_layer=1, n_head=2, n_embd=32, vocab_size=VOCAB, block_size=BLOCK,
        dtype="float32", embd_pdrop=0.0, resid_pdrop=0.0, attn_pdrop=0.0)
    draft = gpt.init(jax.random.key(7), draft_cfg)
    server, got = serve(cfg, params, prompts, draft_params=draft,
                        draft_cfg=draft_cfg, spec_k=2)
    assert got == want
    assert server.metrics.summary()["spec_rounds"] > 0
    assert server.metrics.summary()["moe_dropped_rows"] == 0


def test_a_dense_model_s_summary_has_the_fields_and_no_counter():
    cfg = GPTConfig.make(n_layer=1, n_head=2, n_embd=32, vocab_size=VOCAB,
                         block_size=BLOCK, dtype="float32", embd_pdrop=0.0,
                         resid_pdrop=0.0, attn_pdrop=0.0)
    server = InferenceServer(gpt.init(jax.random.key(0), cfg), cfg, n_slots=2)
    summary = server.metrics.summary()
    assert summary["kv_bytes_per_row"] == 1 * 2 * 32 * 4
    assert summary["moe_routed_rows"] is None
    assert summary["moe_dropped_rows"] is None
    assert summary["moe_blocks_run"] is summary["moe_blocks_laid"] is None
    assert sorted(server.engine.pool.cache) == ["k", "v"]
