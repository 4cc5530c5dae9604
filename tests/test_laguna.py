"""Laguna-XS.2 on the normal path, at a tiny size on the CPU: a stack whose
softmax attention layers differ in kind (``GPTConfig.layer_types``: full
layers that cache a row a position beside window layers that keep their last
``attention_window`` rows a slot in a ring), head counts and rotations by
kind, a gate a head, and the dropless route under softmax scores, against
the plain reference ``benchmarks/references/laguna.py``: through
``gpt.forward``, the cached forward, ``DecodeEngine`` and
``InferenceServer``, and the benchmark's cell through the path the driver
runs."""

import dataclasses
import json
import math
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import rehearse
from benchmarks.harness import check, compiles, serve_cell, spec
from mingpt_distributed_tpu.config import (
    FULL_ATTN, WINDOW_ATTN, ConfigError, GPTConfig)
from mingpt_distributed_tpu.models import generate as gen
from mingpt_distributed_tpu.models import gpt
from mingpt_distributed_tpu.ops import attention as attn_ops
from mingpt_distributed_tpu.ops import moe
from mingpt_distributed_tpu.serving import InferenceServer, Request
from mingpt_distributed_tpu.serving import engine as engine_lib
from mingpt_distributed_tpu.serving.engine import DecodeEngine

CELL = "laguna-xs.2.serve-long-decode"
SEED = 2_500_000_001        # past 32 signed bits, as the driver's seeds are
WINDOW = 16
NEW_READERS = ("attention.ring_ms_per_step", "kv.ring_bytes_per_slot",
               "kv.ring_read_row_share")


def tiny_cell() -> spec.Cell:
    return rehearse.tiny(spec.load_cell(CELL))


def tiny_cfg(**over) -> GPTConfig:
    """The cell's own program at ``rehearse.tiny``'s size: five layers (full
    and dense; window, window, window; full), 6 and 8 query heads over 2 KV
    heads of 16, a window of 16, 2 of 8 experts beside a shared one."""
    gpt_config = tiny_cell().config["program"]["gpt_config"]
    return GPTConfig.make(**{**gpt_config, "dtype": "float32",
                             "param_dtype": "float32", **over})


def sizes_of(cfg: GPTConfig) -> dict:
    """What the reference reads of a configuration file, from the program's
    config: the cell's own ``key_map``, applied as ``rehearse.tiny`` does."""
    key_map = spec.load_cell(CELL).config["program"]["key_map"]
    return {published: getattr(cfg, field)
            for published, field in key_map.items()}


@pytest.fixture(scope="module")
def reference():
    return spec.load_reference(spec.load_cell(CELL).config)


@pytest.fixture(scope="module")
def model():
    cfg = tiny_cfg()
    return cfg, gpt.init(jax.random.key(3), cfg)


def tokens_of(cfg, batch, t, seed=1):
    return jax.random.randint(jax.random.key(seed), (batch, t), 0,
                              cfg.vocab_size)


# -- the program against the reference, float32 ------------------------------

def test_the_full_forward_is_the_reference_s(reference, model):
    cfg, params = model
    toks = tokens_of(cfg, 2, 100)
    logits, loss = gpt.forward(params, toks, cfg, targets=toks)
    w = reference.weights_from_program(params)
    x, ks, vs, router = reference.hidden(w, toks, sizes_of(cfg))
    assert reference.cached_layers(sizes_of(cfg)) == (0, 4)
    assert ks.shape == vs.shape == (2, 2, 100, cfg.kv_heads, cfg.head_dim)
    assert router.shape == (5, 2, 100, cfg.n_experts)
    np.testing.assert_allclose(logits, reference.logits(w, x), atol=2e-6)
    np.testing.assert_allclose(
        loss, reference.loss(w, toks, toks, sizes_of(cfg)), atol=1e-5)


def test_the_reference_s_experts_are_every_expert_under_a_zero_gate(
        reference, model):
    """The reference's loop over blocks of sorted token-expert pairs
    against every token through every expert, weighed by a gate that is
    zero where the expert was not chosen, in blocks so small that an
    expert's pairs span several."""
    cfg, params = model
    w = reference.weights_from_program(params)["moe"]
    ks = jax.random.split(jax.random.key(7), 3)
    h = jax.random.normal(ks[0], (2, 40, cfg.n_embd))
    chosen = jnp.argsort(jax.random.normal(ks[1], (2, 40, 8)))[..., :2]
    g = jax.random.uniform(ks[2], (2, 40, 2))
    reference_rows = reference.EXPERT_ROWS
    try:
        reference.EXPERT_ROWS = 8
        got = reference._chosen_experts(h, w, 1, chosen, g, lambda a: a)
    finally:
        reference.EXPERT_ROWS = reference_rows
    gates = (jax.nn.one_hot(chosen, 8) * g[..., None]).sum(-2)
    f32 = lambda a: a.astype(jnp.float32)
    inner = jax.nn.silu(jnp.einsum("btd,edf->btef", h, f32(w["eg"][1]))) \
        * jnp.einsum("btd,edf->btef", h, f32(w["eu"][1]))
    want = jnp.einsum("btef,efd,bte->btd", inner, f32(w["ed"][1]), gates)
    np.testing.assert_allclose(got, want, atol=2e-6)


@pytest.mark.parametrize("n_prompt, steps", [(9, 40), (40, 24), (64, 36)])
def test_prefill_then_decode_through_the_cache_is_the_reference_s_forward(
        reference, model, n_prompt, steps):
    """Logits after the prefill and after every decode step, and the full
    layers' rows, against the reference's forward over the whole sequence.
    The lanes pass twice the window (16) in every case: from a prompt
    shorter than it (the ring is filled by the steps), from one that has
    wrapped it twice, and from one that fills its bucket."""
    cfg, params = model
    n = n_prompt + steps
    assert n > 2 * WINDOW
    toks = tokens_of(cfg, 2, n)
    w = reference.weights_from_program(params)
    x, ks, vs, _ = reference.hidden(w, toks, sizes_of(cfg))
    ref_logits = reference.logits(w, x)
    cache = gen.init_cache(cfg, 2)
    assert cache[gen.RING_K].shape == (3, 2, WINDOW, 1, 2 * 16)
    assert cache["k"].shape == (2, 2, cfg.block_size, 1, 2 * 16)
    forward = jax.jit(lambda toks, cache, offset: gen._forward_cached(
        params, toks, cache, offset, cfg))
    logits, cache = forward(toks[:, :n_prompt], cache, 0)
    np.testing.assert_allclose(logits, ref_logits[:, n_prompt - 1], atol=2e-6)
    for i in range(n_prompt, n):
        logits, cache = forward(toks[:, i:i + 1], cache, jnp.full((2,), i))
        np.testing.assert_allclose(logits, ref_logits[:, i], atol=2e-6)
    # a row keeps its two heads side by side: the same numbers in order
    for name, rows in (("k", ks), ("v", vs)):
        np.testing.assert_allclose(
            cache[name][:, :, :n].reshape(rows.shape), rows, atol=1e-5)


def test_the_cached_path_is_the_uncached_forward(model):
    """``gpt.forward`` without a cache (every row, the window layers masked
    by age) against solo ``generate`` (a prefill, then steps of one token
    under one offset, the ring read rolled into the order of its
    positions)."""
    cfg, params = model
    toks = tokens_of(cfg, 2, 12)
    out = gen.generate(params, cfg, toks, 50)
    logits, _ = gpt.forward(params, out[:, :-1], cfg)
    np.testing.assert_array_equal(
        out[:, 12:], jnp.argmax(logits[:, 11:], -1))


def test_a_chunk_that_ends_in_padding_leaves_its_real_rows_in_the_ring(model):
    """A bucket's padding after a prompt leaves no row in a ring: the
    window layers' rings after a padded prefill are those of the prompt
    alone."""
    cfg, params = model
    toks = tokens_of(cfg, 1, 64)
    valid = (jnp.arange(64) < 37)[None]
    prefill = jax.jit(lambda toks, valid: gen._forward_cached_hidden(
        params, toks, gen.init_cache(cfg, 1), 0, cfg, valid))
    _, padded = prefill(toks, valid)
    _, exact = prefill(toks[:, :37], None)
    for name in gen.RINGS:
        np.testing.assert_allclose(padded[name], exact[name], atol=1e-6)


# -- the ring against every row under the band mask --------------------------

@pytest.mark.parametrize("position", [0, 3, 15, 16, 17, 31, 32, 45])
def test_the_ring_step_is_the_band_over_every_row(position):
    """A lane at ``position`` over a ring of 16 against the same keys and
    values kept a row a position under ``causal_attention``'s window: equal
    to rounding, whether the lane is younger than the window, stands at its
    edge or has wrapped it. The second lane stands one position behind."""
    b, kv, g, hd = 2, 2, 4, 16
    ks = jax.random.split(jax.random.key(position), 3)
    k_all = jax.random.normal(ks[0], (b, 48, kv, hd))
    v_all = jax.random.normal(ks[1], (b, 48, kv, hd))
    q = jax.random.normal(ks[2], (b, 1, kv * g, hd))
    pos = np.array([position, max(position - 1, 0)])
    ring_k = np.full((1, b, WINDOW, kv, hd), 7.0, np.float32)  # stale rows
    ring_v = np.full((1, b, WINDOW, kv, hd), -7.0, np.float32)
    for lane in range(b):
        for t in range(pos[lane]):
            ring_k[0, lane, t % WINDOW] = k_all[lane, t]
            ring_v[0, lane, t % WINDOW] = v_all[lane, t]
    new = [jnp.stack([a[lane, pos[lane]] for lane in range(b)])[:, None]
           for a in (k_all, v_all)]
    walk = attn_ops.step_walk([ring_k.shape, ring_v.shape], 4)
    out = attn_ops.ring_attend_step(
        q, jnp.asarray(ring_k), jnp.asarray(ring_v), 0, *new,
        jnp.asarray(pos), walk)
    for lane in range(b):
        n = pos[lane] + 1
        want = attn_ops.causal_attention(
            q[lane:lane + 1], k_all[lane:lane + 1, :n],
            v_all[lane:lane + 1, :n], kv_offset=n - 1, window=WINDOW)
        np.testing.assert_allclose(out[lane], want[0], atol=1e-6)


@pytest.mark.parametrize("window, q_start, k_start", [
    (None, 0, 0), (None, 32, 0), (16, 0, 0), (16, 24, -8), (24, 40, 8)])
def test_the_banded_walk_is_one_pass_under_the_same_mask(window, q_start,
                                                         k_start):
    """``banded_attention`` a block of 8 queries and keys at a time against
    itself in one pass (a length that is no whole number of blocks):
    queries and keys at positions of their own, keys at negative positions
    masked."""
    ks = jax.random.split(jax.random.key(0), 3)
    q = jax.random.normal(ks[0], (2, 32, 8, 16))
    k = jax.random.normal(ks[1], (2, 64, 2, 16))
    v = jax.random.normal(ks[2], (2, 64, 2, 16))
    kw = dict(q_start=q_start, k_start=k_start, window=window)
    walked = attn_ops.banded_attention(q, k, v, block=8, **kw)
    whole = attn_ops.banded_attention(q, k, v, block=7, **kw)
    np.testing.assert_allclose(walked, whole, atol=2e-6)
    if k_start == 0 and q_start == 0:
        np.testing.assert_allclose(walked, attn_ops.causal_attention(
            q, k[:, :32], v[:, :32], window=window), atol=2e-6)


# -- the rotations, by hand ---------------------------------------------------

def test_yarn_s_frequencies_are_the_published_formula_worked_by_hand():
    """The full layers' rotation at the published sizes: 64 of a head's 128
    dimensions, theta 500,000, factor 64 over 4,096 positions, beta 64 and
    1. The correction range is pairs 4 to 15: below it a pair turns at its
    own frequency, above it at a 64th of it, between by the ramp."""
    dim, theta = 64, 500000.0
    freq = attn_ops.yarn_inv_freq(dim, theta, 64.0, 4096, 64.0, 1.0)
    low = dim * math.log(4096 / (64 * 2 * math.pi)) / (2 * math.log(theta))
    high = dim * math.log(4096 / (1 * 2 * math.pi)) / (2 * math.log(theta))
    assert (math.floor(low), math.ceil(high)) == (5, 16)
    own = lambda i: theta ** (-2 * i / dim)
    assert freq[0] == pytest.approx(1.0) and freq[5] == pytest.approx(own(5))
    assert freq[16] == pytest.approx(own(16) / 64)
    assert freq[31] == pytest.approx(own(31) / 64)
    ramp = (10 - 5) / (16 - 5)
    assert freq[10] == pytest.approx(
        own(10) / 64 * ramp + own(10) * (1 - ramp))
    cos, sin = attn_ops.yarn_rope_tables(
        jnp.array([0, 7, 5000]), dim, theta, 64.0, 4096, 64.0, 1.0,
        1.4158883083359672)
    assert cos.shape == (3, 32)
    np.testing.assert_allclose(cos[0], 1.4158883083359672, rtol=1e-6)
    np.testing.assert_allclose(
        sin[1, 10], 1.4158883083359672 * math.sin(7 * freq[10]), rtol=1e-5)
    assert 0.1 * math.log(64) + 1 == pytest.approx(1.4158883083359672)


@pytest.mark.parametrize("position", [1, 9, 130])
def test_a_partial_rotation_turns_the_first_part_of_a_head(position):
    """Tables of 4 pairs on a head of 16: dimensions ``(i, i + 4)`` for
    ``i < 4`` turn by ``position * theta^(-2i/8)``, dimensions 8 to 15 pass
    through."""
    x = jax.random.normal(jax.random.key(position), (1, 1, 3, 16))
    cos, sin = attn_ops.rope_tables(jnp.array([position]), 8, 10000.0)
    got = np.asarray(attn_ops.apply_rope(x, cos, sin))
    x = np.asarray(x)
    np.testing.assert_array_equal(got[..., 8:], x[..., 8:])
    for i in range(4):
        angle = position * 10000.0 ** (-2 * i / 8)
        a, b = x[..., i], x[..., i + 4]
        np.testing.assert_allclose(
            got[..., i], a * math.cos(angle) - b * math.sin(angle), atol=1e-5)
        np.testing.assert_allclose(
            got[..., i + 4], b * math.cos(angle) + a * math.sin(angle),
            atol=1e-5)


def test_each_kind_of_layer_rotates_by_its_own_rule(model):
    cfg, _ = model
    assert cfg.rope_spec(FULL_ATTN) == (
        8, 500000.0, (64, 4096, 64, 1, 1.4158883083359672))
    assert cfg.rope_spec(WINDOW_ATTN) == (16, 10000.0, None)
    assert cfg.kind_heads(FULL_ATTN) == (6, 2, 16)
    assert cfg.kind_heads(WINDOW_ATTN) == (8, 2, 16)
    assert cfg.kind_window(FULL_ATTN) is None
    assert cfg.kind_window(WINDOW_ATTN) == WINDOW
    pos = jnp.arange(5)
    full = gpt.layer_rope(cfg, FULL_ATTN, pos)
    assert full[0].shape == (5, 4)
    np.testing.assert_allclose(full[0][0], 1.4158883083359672, rtol=1e-6)
    window = gpt.layer_rope(cfg, WINDOW_ATTN, pos)
    np.testing.assert_allclose(window[0], attn_ops.rope_tables(
        pos, 16, 10000.0)[0])


# -- the route ---------------------------------------------------------------

def test_the_route_is_a_plain_softmax_top_k():
    """The k largest logits, their softmax probabilities over all experts
    renormalised over the chosen, times the scale; every chosen expert
    computes its token whatever the load."""
    ks = jax.random.split(jax.random.key(5), 5)
    n, d, e, f, k = 40, 32, 8, 16, 3
    h = jax.random.normal(ks[0], (n, d))
    w_router = jax.random.normal(ks[1], (d, e))
    chosen, gates, z = moe.softmax_routes(h, w_router, top_k=k,
                                          route_scale=2.5)
    p = np.asarray(jax.nn.softmax(h @ w_router, -1))
    order = np.argsort(-np.asarray(z), -1, kind="stable")[:, :k]
    np.testing.assert_array_equal(chosen, order)
    picked = np.take_along_axis(p, order, -1)
    np.testing.assert_allclose(
        gates, 2.5 * picked / picked.sum(-1, keepdims=True), rtol=1e-5)
    w_gate, w_up = (jax.random.normal(a, (e, d, f)) * 0.1 for a in ks[2:4])
    w_down = jax.random.normal(ks[4], (e, f, d)) * 0.1
    out, counts = moe.moe_dropless(
        h[None], w_router, None, w_gate, w_up, w_down, top_k=k,
        route_scale=2.5, scoring="softmax")
    want = np.zeros((n, d), np.float32)
    for t in range(n):
        for j, ex in enumerate(order[t]):
            inner = jax.nn.silu(h[t] @ w_gate[ex]) * (h[t] @ w_up[ex])
            want[t] += float(gates[t, j]) * np.asarray(inner @ w_down[ex])
    np.testing.assert_allclose(out[0], want, atol=1e-5)
    assert int(counts[:e].sum()) == int(counts[e]) == n * k   # none dropped


# -- the server ----------------------------------------------------------------

def test_the_server_serves_mixed_lengths_and_a_freed_slot_shows_nothing(model):
    """Two slots, five requests, so every slot is freed and taken again: a
    long request's ring and rows under a short one that follows it (shorter
    than the window: what the ring held is masked by age), greedy tokens
    those of solo ``generate``."""
    cfg, params = model
    server = InferenceServer(params, cfg, n_slots=2, prefill_len=64,
                             prefill_buckets=[32, 64], warmup=True)
    prompts = [tokens_of(cfg, 1, n, seed=n)[0].tolist()
               for n in (60, 5, 33, 9, 17)]
    handles = [server.submit(Request(prompt=p, max_new_tokens=40,
                                     do_sample=False)) for p in prompts]
    while server.step():
        pass
    for p, h in zip(prompts, handles):
        solo = gen.generate(params, cfg, jnp.asarray([p]), 40)[0, len(p):]
        assert h.tokens == solo.tolist()
    s = server.metrics.summary()
    eng = server.engine
    # three rings of keys and of values: 16 rows of 2 heads of 16, float32
    assert s["ring_bytes_per_slot"] == eng.ring_bytes_per_slot \
        == 3 * 2 * WINDOW * 2 * 16 * 4
    assert s["ring_rows_per_slot"] == eng.ring_rows_per_slot == WINDOW
    # two full layers' rows a position
    assert s["kv_bytes_per_row"] == eng.kv_bytes_per_row == 2 * 2 * 2 * 16 * 4
    assert 0 < s["ring_rows_live"] < s["ring_rows_read"]
    assert s["moe_dropped_rows"] == 0 and s["moe_routed_rows"] > 0
    assert server.compile_counts()["decode"] == 1
    assert server.compile_counts()["prefill"] == 2
    facts = eng.pool.audit_facts()
    assert set(facts["cache_leaf_shapes"]) == {"k", "v", *gen.RINGS}
    assert facts["cache_leaf_shapes"][gen.RING_K] == (3, 2, WINDOW, 1, 32)
    assert eng.audit_contracts()["decode"]["donated"] == 5
    assert eng.migratable_rows(60, 60) == 0


def test_the_server_s_rings_and_rows_through_the_kernel_s_walk(
        model, walk_in_blocks):
    """The same two slots and five requests with every layer's walk the
    Pallas kernel's (ISSUE 62; interpret mode, blocks of 8 rows: a ring of
    16 is two, read to a lane's own reach): the full layers' 6 grouped
    queries a KV head and the rings' 8, a ring that wraps under a long
    request and is younger than the window under the short one that takes
    its slot, greedy tokens those of solo ``generate``. The gauge counts
    the five layers, and the rings' rows are counted by the kernel's rule:
    no block is read for both lanes, so none of a lane that is not
    live."""
    walk_in_blocks(8, kernel=True)
    cfg, params = model
    server = InferenceServer(params, cfg, n_slots=2, prefill_len=64,
                             prefill_buckets=[32, 64], warmup=True)
    assert server.engine.walk.kernel and server.engine.ring_walk.kernel
    assert server.engine.ring_walk == (WINDOW, 1 << 40, 8, True)
    prompts = [tokens_of(cfg, 1, n, seed=n)[0].tolist()
               for n in (60, 5, 33, 9, 17)]
    handles = [server.submit(Request(prompt=p, max_new_tokens=40,
                                     do_sample=False)) for p in prompts]
    while server.step():
        pass
    for p, h in zip(prompts, handles):
        solo = gen.generate(params, cfg, jnp.asarray([p]), 40)[0, len(p):]
        assert h.tokens == solo.tolist()
    s = server.metrics.summary()
    assert s["decode_kernel_walk_layers"] == cfg.n_layer == 5
    assert s["ring_planes"] == server.engine.ring_planes == 3
    # whole blocks of 8 to each live lane's reach: under a block a
    # lane-step past the rows inside the windows
    assert 0 < s["ring_rows_live"] < s["ring_rows_read"] \
        <= s["ring_rows_live"] + 8 * s["tokens_generated"]
    assert s["ring_rows_read"] % 8 == 0
    assert server.compile_counts()["decode"] == 1


def test_the_ring_s_counters_follow_the_program_s_rule():
    """A ring of 512 rows walked in blocks of 256. Of a lane past the
    window 511 rows are inside it, of a younger lane those it has written,
    of a lane that is not live none. Among four lanes a block that two
    need is read for all four (``attn_ops.step_plan``); among 64 each
    lane is read alone, as far as it has written."""
    walk = attn_ops.StepWalk(512, 4096, 256)
    pos = np.array([1900, 100, 8191, 300])
    live = np.array([True, True, False, True])
    read, inside = engine_lib.ring_rows(pos, live, walk)
    assert (read, inside) == (2 * 4 * 256, 511 + 100 + 0 + 300)
    pos, live = np.full(64, 8191), np.zeros(64, bool)
    pos[:4], live[:4] = [1900, 100, 700, 300], True
    read, inside = engine_lib.ring_rows(pos, live, walk)
    assert (read, inside) == (512 + 256 + 512 + 512, 511 + 100 + 511 + 300)


# -- what is not built is refused, a sentence each ---------------------------

@pytest.mark.parametrize("over, sentence", [
    (dict(layer_types=["full_attention"] * 4), "for each of the 5 layers"),
    (dict(layer_types=["sliding_attention"] * 5), "needs a full attention"),
    (dict(attention_window=None), "set it"),
    (dict(attention="flash"), "built for attention='einsum'"),
    (dict(rmsnorm=False), "needs rope, rmsnorm and swiglu"),
    (dict(window_n_head=7), "not divisible by the 2 KV heads"),
    (dict(rope_fraction=0.2), "an even number of them"),
    (dict(rope_yarn=[1.0, 4096, 64, 1, 1.0]), "rope_yarn is"),
    (dict(head_size=0), "it is positive"),
    (dict(post_norms=True), "are not written for it"),
    (dict(pp_microbatches=2), "is not pipelined"),
    (dict(rope_interleave=True), "no rope_interleave"),
    (dict(moe_dropless=False, n_shared_experts=0, moe_route_scale=1.0),
     "routes without dropping"),
    (dict(moe_dropless=False), "built for the dropless route only"),
    (dict(moe_norm_topk=False), "renormalises the chosen experts'"),
    (dict(layer_types=None), "belong to a stack of layer_types"),
])
def test_combinations_that_are_not_built_are_refused_with_a_sentence(
        over, sentence):
    with pytest.raises(ConfigError, match=sentence):
        tiny_cfg(**over)


@pytest.mark.parametrize("how, sentence", [
    (dict(kv_dtype="int8"), "no scale for a ring"),
    (dict(prefix_cache_mb=1.0), "no prefix store"),
    (dict(mesh="tp2"), "served on one device"),
    (dict(prefill_len=64, prefill_chunk=32), "whole prompts"),
])
def test_the_engine_refuses_what_a_ring_is_not_built_for(model, how,
                                                         sentence):
    cfg, params = model
    if how.get("mesh"):
        how = dict(mesh=jax.sharding.Mesh(
            np.asarray(jax.devices()[:2]).reshape(2), ("tp",)))
    with pytest.raises(ConfigError, match=sentence):
        DecodeEngine(params, cfg, n_slots=2, **how)


def test_speculation_and_migration_over_a_ring_are_refused(model):
    cfg, params = model
    with pytest.raises(ConfigError, match="no roll-back restores"):
        InferenceServer(params, cfg, n_slots=2, draft_params=params,
                        draft_cfg=cfg, spec_k=2)
    eng = DecodeEngine(params, cfg, n_slots=2)
    with pytest.raises(ValueError, match="the window layers' rings"):
        eng.extract_slot_rows(0, eng.buckets[0])


def test_training_and_a_split_mesh_are_refused_by_the_forward(model):
    cfg, params = model
    toks = tokens_of(cfg, 1, 16)
    with pytest.raises(NotImplementedError, match="not trained"):
        gpt.forward(params, toks, cfg, rng=jax.random.key(0),
                    deterministic=False)
    mesh = jax.sharding.Mesh(
        np.asarray(jax.devices()[:2]).reshape(2), ("tp",))
    with pytest.raises(NotImplementedError, match="not split over pp or tp"):
        gpt.forward(params, toks, cfg, mesh=mesh)


# -- precision: what the check lets through and what it does not -------------

def bf16_model():
    cfg = tiny_cfg(dtype="bfloat16", param_dtype="bfloat16")
    return cfg, gpt.init(jax.random.key(3), cfg)


def verdict_of(reference, cfg, params, sizes, weights=None):
    """``check.serve_verdict`` over three prompts that wrap the window,
    eight decode steps each. ``weights``: what the reference computes with,
    where the program's tree is not the model's (a planted fault)."""
    if weights is not None:
        reference = types.SimpleNamespace(**{
            **vars(reference), "weights_from_program": lambda _: weights})
    server = InferenceServer(params, cfg, n_slots=2, prefill_len=64,
                             prefill_buckets=[32, 64], warmup=True)
    rng = np.random.default_rng(SEED)
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in (21, 40, 60)]
    return check.serve_verdict(reference, sizes, server, prompts, 8)


def test_in_bfloat16_the_engine_holds_the_check_s_law(reference):
    cfg, params = bf16_model()
    verdict = verdict_of(reference, cfg, params, sizes_of(cfg))
    assert verdict["ok"], json.dumps(verdict)[:2000]
    for case in verdict["cases"]:
        assert len(case["k_rel_layers"]) == 2      # the full layers' planes
        assert len(case["route_margin_layers"]) == 5
        assert set(case["compared"]) >= {"k_in_8bit", "v_in_8bit"}


def _no_gate(monkeypatch, cfg, params):
    real = gpt.attention_out
    monkeypatch.setattr(gpt, "attention_out", lambda att, blk, *a, **kw: real(
        att, {n: v for n, v in blk.items() if n != "w_hg"}, *a, **kw))
    return cfg


def _sigmoid_gates(monkeypatch, cfg, params):
    def routes(h, w_router, *, top_k, route_scale):
        z = h.astype(jnp.float32) @ w_router.astype(jnp.float32)
        chosen = jax.lax.top_k(z, top_k)[1]
        gates = jnp.take_along_axis(jax.nn.sigmoid(z), chosen, axis=-1)
        return (chosen.astype(jnp.int32),
                gates / gates.sum(-1, keepdims=True) * route_scale, z)

    monkeypatch.setattr(moe, "softmax_routes", routes)
    return cfg


def _window_one_row_short(monkeypatch, cfg, params):
    return dataclasses.replace(cfg, attention_window=WINDOW - 1)


def _the_other_kind_s_rotation(monkeypatch, cfg, params):
    real = GPTConfig.rope_spec
    other = {FULL_ATTN: WINDOW_ATTN, WINDOW_ATTN: FULL_ATTN}
    monkeypatch.setattr(GPTConfig, "rope_spec",
                        lambda self, kind=None: real(self, other[kind]))
    return cfg


def _unscaled_routed_sum(monkeypatch, cfg, params):
    return dataclasses.replace(cfg, moe_route_scale=1.0)


@pytest.mark.parametrize("plant", [
    _no_gate, _sigmoid_gates, _window_one_row_short,
    _the_other_kind_s_rotation, _unscaled_routed_sum],
    ids=lambda f: f.__name__.strip("_"))
def test_a_planted_fault_reads_not_ok(reference, monkeypatch, plant):
    """The tiny cell's program with one thing wrong, against the reference
    under the true sizes and the same weights: the verdict is not ``ok``."""
    cfg, params = bf16_model()
    sizes = sizes_of(cfg)
    faulty = plant(monkeypatch, cfg, params)
    verdict = verdict_of(reference, faulty, params, sizes,
                         reference.weights_from_program(params))
    assert not verdict["ok"], json.dumps(verdict["cases"][0]["compared"])


# -- the configuration file and the cell ---------------------------------------

def test_the_configuration_file_holds_the_published_widths():
    cell = spec.load_cell(CELL)
    config = cell.config
    assert config["reduced"] == [
        "num_hidden_layers", "layer_types", "mlp_layer_types",
        "num_attention_heads_per_layer", "max_position_embeddings"]
    assert (config["hidden_size"], config["head_dim"],
            config["num_key_value_heads"], config["num_attention_heads"],
            config["intermediate_size"], config["num_experts"],
            config["num_experts_per_tok"], config["moe_intermediate_size"],
            config["shared_expert_intermediate_size"], config["sliding_window"],
            config["vocab_size"], config["moe_routed_scaling_factor"]) == (
        2048, 128, 8, 48, 8192, 256, 8, 512, 512, 512, 100352, 2.5)
    assert config["num_attention_heads_per_layer"] == [48, 64, 64, 64, 48]
    assert config["rope_parameters"]["full_attention"]["factor"] == 64
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        rows = [json.loads(line) for line in f]
    published = next(r for r in rows if r["name"] == "Laguna-XS.2")["config"]
    assert config["source"] == next(
        r for r in rows if r["name"] == "Laguna-XS.2")["source_url"]
    for key, value in published.items():
        if key in config["reduced"]:
            n = config["num_hidden_layers"]
            assert config[key] == (value[:n] if isinstance(value, list)
                                   else config[key])
        else:
            assert config[key] == value, key
    cfg = spec.gpt_config(cell, training=False)
    assert cfg.param_dtype == cfg.dtype == "bfloat16"
    assert spec.server_options(cell) == {
        "prefill_len": 4096, "prefill_buckets": [1024, 2048, 4096],
        "n_slots": cell.found["server"]["n_slots"]}
    for key in ("weights", "router scoring", "norm_topk_prob", "gating"):
        assert key in config["assumed"]
    wrong = dataclasses.replace(cell, config=dict(config, sliding_window=256))
    with pytest.raises(spec.SpecError, match="sliding_window"):
        spec.gpt_config(wrong, training=False)
    wrong = dataclasses.replace(cell, config=dict(
        config, num_attention_heads_per_layer=[48] * 5))
    with pytest.raises(spec.SpecError, match="heads_per_layer"):
        spec.gpt_config(wrong, training=False)


def test_the_slot_and_the_weights_are_the_size_the_configuration_states():
    cfg = spec.gpt_config(spec.load_cell(CELL), training=False)
    size = {n: int(np.prod(s)) * 2
            for n, s in gen.cache_leaf_shapes(cfg, 1).items()}
    assert size["k"] + size["v"] == 2 * 8192 * 4096           # 67.1 MB
    assert size[gen.RING_K] + size[gen.RING_V] == 6_291_456   # 3 x 512 x 4 KB
    assert sum(size.values()) == 73_400_320
    count = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(
        jax.eval_shape(lambda: gpt.init(jax.random.key(0), cfg))))
    assert 3.869e9 < count < 3.871e9


@pytest.fixture(scope="module")
def cell_run():
    return serve_cell.run(
        tiny_cell(), seed=SEED, seconds=1.0, traced=False,
        devices=jax.devices()[:1], t_process=0.0,
        compiles=compiles.CompileCounter())


def test_the_cell_agrees_with_its_reference_through_the_whole_path(cell_run):
    """bfloat16, the engine's own programs, ``serve_cell.Driver`` and
    ``check.serve_verdict`` as the driver runs them: the full layers' rows
    inside the twin's law once the reference has followed the program's
    routes across the window layers, no program compiled in the window."""
    verdict = cell_run["verdict"]
    assert verdict["ok"], verdict
    assert verdict["compiled_in_window"] == 0
    assert len(verdict["cases"]) == 3
    for case in verdict["cases"]:
        assert len(case["k_rel_layers"]) == len(case["v_rel_layers"]) == 2
        assert case["route_banded_layers"][0] == 0      # the dense layer
    assert cell_run["failed"] == 0 and cell_run["attempted"] > 0


def test_the_ring_s_counters_reach_the_readers(cell_run):
    play = cell_run["evidence"]["play"]
    closed = play.close_counters
    assert closed["ring_bytes_per_slot"] == 3 * 2 * WINDOW * 2 * 16 * 2
    assert closed["ring_rows_per_slot"] == WINDOW
    assert closed["ring_rows_read"] > play.open_counters["ring_rows_read"]
    assert closed["moe_dropped_rows"] == 0
    # untraced: the readers find nothing and say so
    for name in NEW_READERS:
        assert spec.load_reader(name).read(cell_run["evidence"]) is None
    traced = dataclasses.replace(play, trace_open=play.open_counters,
                                 trace_close=closed)
    evidence = dict(cell_run["evidence"], play=traced)
    assert spec.load_reader("kv.ring_bytes_per_slot").read(evidence) \
        == closed["ring_bytes_per_slot"]
    assert 0 < spec.load_reader("kv.ring_read_row_share").read(evidence) < 100


@pytest.mark.parametrize("reader", NEW_READERS)
def test_a_new_reader_finds_nothing_on_a_program_without_a_ring(reader):
    read = spec.load_reader(reader).read
    play = serve_cell.Play(n_slots=4, block_size=128)
    # the parent's summary: no such gauge, no such counter
    play.trace_open, play.trace_close = {"steps": 1}, {"steps": 9}
    assert read({"play": play, "trace": None}) is None
    assert read({"play": None, "trace": None}) is None
    # a program with the fields and no ring reads None in each
    play.trace_open = play.trace_close = {
        "ring_bytes_per_slot": None, "ring_rows_read": None,
        "ring_rows_live": None}
    assert read({"play": play, "trace": None}) is None


def test_the_ring_readers_read_two_readings_of_the_counters():
    play = serve_cell.Play(n_slots=64, block_size=8192)
    play.trace_open = {"ring_bytes_per_slot": 6_291_456,
                       "ring_rows_read": 1000, "ring_rows_live": 900}
    play.trace_close = {"ring_bytes_per_slot": 6_291_456,
                        "ring_rows_read": 5000, "ring_rows_live": 3900}
    assert spec.load_reader("kv.ring_bytes_per_slot").read(
        {"play": play}) == 6_291_456
    assert spec.load_reader("kv.ring_read_row_share").read(
        {"play": play}) == 75.0
    play.trace_close = dict(play.trace_open)
    assert spec.load_reader("kv.ring_read_row_share").read(
        {"play": play}) is None                       # no decode step


def test_the_manifest_lists_the_cell_where_it_reports():
    cell = spec.load_cell(CELL)
    assert [m["name"] for m in cell.end_to_end] == ["itl_p50_ms", "setup_s"]
    names = {m["name"] for m in cell.per_layer}
    kanana = {m["name"] for m in spec.load_cell(
        "kanana-2-30b-a3b.serve-long-decode").per_layer}
    # kanana's lists but the one whose reader finds nothing to read here:
    # it takes the experts a layer from ``first_k_dense_replace`` and
    # ``n_routed_experts``, which this configuration does not publish; and
    # PR 62's reader of the kernel that walks rows side by side (kanana's
    # latent pool keeps the XLA walk)
    assert names == (kanana - {"moe.rows_per_expert_round"}) \
        | set(NEW_READERS) | {"kernel.rows_attend_roofline"}
    assert "engine.decode_hbm_roofline" not in names
    play = serve_cell.Play(n_slots=64, block_size=8192)
    play.trace_open = {"moe_routed_rows": 0, "steps": 0}
    play.trace_close = {"moe_routed_rows": 4096, "steps": 8}
    assert spec.load_reader("moe.rows_per_expert_round").read(
        {"play": play, "cell": cell}) is None
    # the new readers were listed for this cell and no other; PR 61 appended
    # the next stack of rings to them
    manifest = spec.load_manifest()
    for metric in manifest["per_layer"]:
        if metric["name"] in NEW_READERS:
            assert metric["workloads"] == [
                CELL, "smallthinker-21b-a3b.serve-past-window"]
