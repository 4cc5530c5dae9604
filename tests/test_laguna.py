"""Laguna-XS.2 on the normal path, at a tiny size on the CPU: a stack whose
softmax attention layers differ in kind (``GPTConfig.layer_types``: full
layers that cache a row a position beside window layers that keep their last
``attention_window`` rows a slot in a ring), head counts and rotations by
kind, a gate a head, and the dropless route under softmax scores, against
the plain reference ``benchmarks/references/laguna.py``: through
``gpt.forward``, the cached forward, ``DecodeEngine`` and
``InferenceServer``, and the benchmark's cell through the path the driver
runs. What every served family proves is ``tests/stack_contract.py``'s; here
is what is peculiar to this one."""

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import stacks
from benchmarks.harness import serve_cell, spec
from mingpt_distributed_tpu.config import FULL_ATTN, WINDOW_ATTN, ConfigError
from mingpt_distributed_tpu.models import generate as gen
from mingpt_distributed_tpu.models import gpt
from mingpt_distributed_tpu.ops import attention as attn_ops
from mingpt_distributed_tpu.ops import moe
from mingpt_distributed_tpu.serving import InferenceServer
from mingpt_distributed_tpu.serving import engine as engine_lib
from mingpt_distributed_tpu.serving.engine import DecodeEngine
from oracles import solo_greedy
from stack_contract import (  # noqa: F401
    cell_run, model, pytest_generate_tests, reference, stack,
    test_a_planted_fault_reads_not_ok,
    test_combinations_that_are_not_built_are_refused_with_a_sentence,
    test_in_bfloat16_the_engine_holds_the_check_s_law,
    test_the_cached_path_is_the_uncached_forward,
    test_the_cell_agrees_with_its_reference_through_the_whole_path,
    test_the_configuration_file_holds_the_published_widths,
    test_the_full_forward_is_the_reference_s,
    test_the_manifest_lists_the_cell_where_it_reports,
    test_the_slot_and_the_weights_are_the_size_the_configuration_states,
    test_training_and_a_split_mesh_are_refused_by_the_forward)
from stacks import WINDOW, tokens_of

STACK = stacks.LAGUNA
NEW_READERS = ("attention.ring_ms_per_step", "kv.ring_bytes_per_slot",
               "kv.ring_read_row_share")


# -- the program against the reference, float32 ------------------------------

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
    toks = tokens_of(cfg, 2, cfg.block_size)
    w = reference.weights_from_program(params)
    programs = stacks.reference_programs(STACK, stacks.sizes_of(STACK, cfg))
    # one program for the three cases: what stands after position n the
    # reference's answers before it do not read
    x, ks, vs, _ = programs.hidden(w, toks)
    ref_logits = np.asarray(programs.logits(w, x))
    cache = gen.init_cache(cfg, 2)
    assert cache[gen.RING_K].shape == (3, 2, WINDOW, 1, 2 * 16)
    assert cache["k"].shape == (2, 2, cfg.block_size, 1, 2 * 16)
    logits, cache = stacks.forward_cached(
        params, toks[:, :n_prompt], cache, 0, cfg)
    np.testing.assert_allclose(logits, ref_logits[:, n_prompt - 1], atol=2e-6)
    for i in range(n_prompt, n):
        logits, cache = stacks.forward_cached(
            params, toks[:, i:i + 1], cache, np.full((2,), i), cfg)
        np.testing.assert_allclose(logits, ref_logits[:, i], atol=2e-6)
    # a row keeps its two heads side by side: the same numbers in order
    for name, rows in (("k", ks), ("v", vs)):
        want = rows[:, :, :n]
        np.testing.assert_allclose(
            cache[name][:, :, :n].reshape(want.shape), want, atol=1e-5)


def test_a_chunk_that_ends_in_padding_leaves_its_real_rows_in_the_ring(model):
    """A bucket's padding after a prompt leaves no row in a ring: the
    window layers' rings after a padded prefill are those of the prompt
    alone."""
    cfg, params = model
    toks = tokens_of(cfg, 1, 64)
    valid = (np.arange(64) < 37)[None]
    prefill = lambda toks, valid: stacks.forward_cached_hidden(
        params, toks, gen.init_cache(cfg, 1), 0, cfg, valid)
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
    server = InferenceServer(params, cfg, **STACK.serve, warmup=True)
    prompts = [tokens_of(cfg, 1, n, seed=n)[0].tolist()
               for n in (60, 5, 33, 9, 17)]
    for p, tokens in zip(prompts, stacks.serve(server, prompts, 40)):
        assert tokens == solo_greedy(params, cfg, p, 40)
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
    server = InferenceServer(params, cfg, **STACK.serve, warmup=True)
    assert server.engine.walk.kernel and server.engine.ring_walk.kernel
    assert server.engine.ring_walk == (WINDOW, 1 << 40, 8, True)
    prompts = [tokens_of(cfg, 1, n, seed=n)[0].tolist()
               for n in (60, 5, 33, 9, 17)]
    for p, tokens in zip(prompts, stacks.serve(server, prompts, 40)):
        assert tokens == solo_greedy(params, cfg, p, 40)
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


# -- what is not built is refused: the config's sentences are the contract's,
# by ``STACK.refused``; the engine's are the ring's own -----------------------

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


# -- the configuration file and the cell ---------------------------------------

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


def test_the_experts_a_round_reader_finds_nothing_to_read_here():
    """``moe.rows_per_expert_round`` takes the experts a layer from
    ``first_k_dense_replace`` and ``n_routed_experts``, which this
    configuration does not publish: it is not listed for the cell
    (``STACK.absent_readers``), and asked it reads nothing."""
    play = serve_cell.Play(n_slots=64, block_size=8192)
    play.trace_open = {"moe_routed_rows": 0, "steps": 0}
    play.trace_close = {"moe_routed_rows": 4096, "steps": 8}
    assert spec.load_reader("moe.rows_per_expert_round").read(
        {"play": play, "cell": spec.load_cell(STACK.cell)}) is None
