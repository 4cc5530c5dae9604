"""SmallThinker-21BA3B-Instruct on the normal path, at a tiny size on the CPU:
a router that reads the attention's input (``GPTConfig.moe_router_input``),
ReLU-gated experts (``expert_act``), full layers that carry no position
(``rope_fraction`` 0) beside window layers whose rings the lanes stand
under, cross while they decode and have passed, against the plain reference
``benchmarks/references/smallthinker.py``: through ``gpt.forward``, the
cached forward, ``InferenceServer`` and the benchmark's cell through the
path the driver runs. What every served family proves is
``tests/stack_contract.py``'s; here is what is peculiar to this one."""

import collections
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from program_digests import _abstract_params, _equations, _ids

import stacks
from benchmarks.harness import check, serve_cell, spec
from mingpt_distributed_tpu.config import (
    FULL_ATTN, WINDOW_ATTN, ConfigError, GPTConfig)
from mingpt_distributed_tpu.models import generate as gen
from mingpt_distributed_tpu.models import gpt
from mingpt_distributed_tpu.ops import moe
from mingpt_distributed_tpu.serving import InferenceServer
from oracles import solo_greedy
from stack_contract import (  # noqa: F401
    cell_run, model, pytest_generate_tests, reference, stack,
    test_a_planted_fault_reads_not_ok,
    test_combinations_that_are_not_built_are_refused_with_a_sentence,
    test_in_bfloat16_the_engine_holds_the_check_s_law,
    test_the_cached_path_is_the_uncached_forward,
    test_the_cell_agrees_with_its_reference_through_the_whole_path,
    test_the_full_forward_is_the_reference_s,
    test_the_manifest_lists_the_cell_where_it_reports,
    test_the_slot_and_the_weights_are_the_size_the_configuration_states,
    test_training_and_a_split_mesh_are_refused_by_the_forward)
from stacks import WINDOW, tokens_of

STACK = stacks.SMALLTHINKER
LAGUNA = "laguna-xs.2.serve-long-decode"
KANANA = "kanana-2-30b-a3b.serve-long-decode"
NEW_READERS = ("moe.route_ms_per_step", "kernel.grouped_glu_roofline",
               "moe.expert_runs_per_step")


# -- the program against the reference, float32 ------------------------------
# Tolerances: both sides are float32 and differ in the order of their sums
# (the program attends in blocks of 512 under a running softmax and runs its
# experts in blocks of 8 rows, the reference in blocks of 512 queries and 128
# pairs): logits of order 1 agree to a few 1e-7, and 2e-6 is five times what
# the worst case reads. A fault below moves them by 1e-3 and more.

def test_the_reference_s_experts_are_every_expert_under_a_zero_gate(
        reference, model):
    """The reference's loop over blocks of sorted token-expert pairs against
    every token through every ReLU-gated expert, weighed by a gate that is
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
    inner = jax.nn.relu(jnp.einsum("btd,edf->btef", h, f32(w["eg"][1]))) \
        * jnp.einsum("btd,edf->btef", h, f32(w["eu"][1]))
    want = jnp.einsum("btef,efd,bte->btd", inner, f32(w["ed"][1]), gates)
    np.testing.assert_allclose(got, want, atol=2e-6)


def lanes_cache(cfg, params, toks, prompts):
    """A cache of ``len(prompts)`` lanes, lane ``b`` prefilled alone with
    the first ``prompts[b]`` tokens of ``toks[b]`` (a prefill has one
    offset for its batch), and each lane's logits after its prompt."""
    lanes = [stacks.forward_cached(params, toks[b:b + 1, :n],
                                   gen.init_cache(cfg, 1), 0, cfg)
             for b, n in enumerate(prompts)]
    cache = {name: jnp.concatenate([c[name] for _, c in lanes], axis=1)
             for name in lanes[0][1]}
    return cache, jnp.concatenate([lg for lg, _ in lanes])


@pytest.mark.parametrize("prompts, steps", [
    ((4, 9, 40), 10), ((1, 15, 33), 24), ((12, 16, 64), 40)])
def test_lanes_under_crossing_and_past_the_window_decode_in_one_batch(
        reference, model, prompts, steps):
    """Prefill, then decode steps of one batch whose lanes stand at their
    own positions: the first stays under the window of 16 (or crosses it
    late), the second crosses it while it decodes, the third starts past
    twice the window. Every step's logits of every lane, and the full
    layers' rows, against the reference's forward over the lanes' whole
    sequences (one program for the three: what stands after a lane's last
    token it does not read)."""
    cfg, params = model
    toks = tokens_of(cfg, 3, cfg.block_size, seed=sum(prompts))
    w = reference.weights_from_program(params)
    programs = stacks.reference_programs(STACK, stacks.sizes_of(STACK, cfg))
    assert prompts[1] <= WINDOW < prompts[1] + steps    # crosses it
    assert prompts[2] >= 2 * WINDOW
    x, ref_k, ref_v, _ = programs.hidden(w, toks)
    ref_logits = np.asarray(programs.logits(w, x))
    cache, logits = lanes_cache(cfg, params, toks, prompts)
    assert cache[gen.RING_K].shape == (3, 3, WINDOW, 1, 2 * 16)
    at = np.asarray(prompts)
    for i in range(steps + 1):
        np.testing.assert_allclose(
            logits, ref_logits[np.arange(3), at - 1], atol=2e-6)
        if i == steps:
            break
        new = toks[np.arange(3), at][:, None]
        logits, cache = stacks.forward_cached(params, new, cache, at, cfg)
        at = at + 1
    for b, p in enumerate(prompts):
        for name, rows in (("k", ref_k), ("v", ref_v)):
            want = rows[:, b, :p + steps]
            np.testing.assert_allclose(
                cache[name][:, b, :p + steps].reshape(want.shape), want,
                atol=1e-5)


def test_a_prompt_longer_than_the_ring_leaves_its_last_rows_there(model):
    """A chunk of 40 tokens through a ring of 16: the ring afterwards holds
    the rows of positions 24-39, the row of ``p`` at ``p mod 16``, whether
    the chunk stood alone or ended in a bucket's padding. The first window
    layer's keys depend on the full layer under it alone, so they are the
    rows the same prompt leaves at ``p`` in a ring wide enough to keep
    every one."""
    cfg, params = model
    toks = tokens_of(cfg, 1, 64)
    valid = (np.arange(64) < 40)[None]

    def prefill(cfg, toks, valid):
        return stacks.forward_cached_hidden(
            params, toks, gen.init_cache(cfg, 1), 0, cfg, valid)[1]

    padded = prefill(cfg, toks, valid)
    exact = prefill(cfg, toks[:, :40], None)
    wide = prefill(dataclasses.replace(cfg, attention_window=64),
                   toks[:, :40], None)
    for name in gen.RINGS:
        np.testing.assert_allclose(padded[name], exact[name], atol=1e-6)
        for p in range(24, 40):
            np.testing.assert_allclose(
                exact[name][0, 0, p % WINDOW], wide[name][0, 0, p], atol=1e-6)


# -- a kind without positions ----------------------------------------------

def test_a_full_layer_rotates_nothing_and_a_window_layer_every_dimension(
        model):
    cfg, params = model
    assert cfg.rope_spec(FULL_ATTN) == (0, 1500000.0, None)
    assert cfg.rope_spec(WINDOW_ATTN) == (16, 1500000.0, None)
    assert cfg.rope_layout == [0, 1, 1, 1, 0]
    assert cfg.window_layout == [0, 1, 1, 1, 0]
    pos = jnp.arange(5)
    assert gpt.layer_rope(cfg, FULL_ATTN, pos) is None
    assert gpt.layer_rope(cfg, WINDOW_ATTN, pos)[0].shape == (5, 8)
    # without a rotation the keys are the projection itself, at any position
    _, blk, _, _ = gpt.kind_layer_params(params, cfg, 0)
    h = jax.random.normal(jax.random.key(0), (1, 3, cfg.n_embd))
    q, k, v = gpt.attention_parts(h, blk, cfg, cfg.kind_heads(FULL_ATTN),
                                  gpt.layer_rope(cfg, FULL_ATTN, pos[:3]))
    np.testing.assert_allclose(
        k.reshape(1, 3, -1), h @ blk["wk"], atol=1e-6)
    # a full layer's logits do not move when the same tokens stand later:
    # a stack of full layers alone would be blind to order, which is why
    # a stack in which no kind rotates is refused
    with pytest.raises(ConfigError, match="no kind rotates"):
        stacks.tiny_cfg(STACK, window_rope_fraction=0.0)


# -- the route ---------------------------------------------------------------

def test_the_route_is_a_plain_softmax_top_k_of_the_attention_s_input(model):
    """``early_route`` of a layer against NumPy: the k largest of ``h W_r``
    in float32 (ties to the lowest index), gates the softmax over the
    chosen alone; and the block hands the experts that route, made from the
    first norm's output, while they compute on the second's."""
    cfg, params = model
    _, blk, _, _ = gpt.kind_layer_params(params, cfg, 1)
    h = jax.random.normal(jax.random.key(2), (2, 20, cfg.n_embd))
    chosen, gates = gpt.early_route(h, blk, cfg)
    z = np.asarray(h, np.float32).reshape(40, -1) @ np.asarray(blk["w_router"])
    order = np.argsort(-z, -1, kind="stable")[:, :cfg.moe_top_k]
    np.testing.assert_array_equal(chosen, order)
    picked = np.exp(np.take_along_axis(z, order, -1))
    np.testing.assert_allclose(
        gates, picked / picked.sum(-1, keepdims=True), rtol=1e-5)
    # a layer whose router reads the MLP's input makes no early route
    late = dataclasses.replace(cfg, moe_router_input="mlp")
    assert gpt.early_route(h, blk, late) is None

    seen = {}
    real = moe.dropless_routes

    def spy(tokens, *a, **kw):
        seen["routed"] = tokens
        return real(tokens, *a, **kw)

    x = jax.random.normal(jax.random.key(4), (1, 12, cfg.n_embd))
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(moe, "dropless_routes", spy)
        gpt._block(x, blk, cfg, gpt.layer_rope(cfg, WINDOW_ATTN,
                                               jnp.arange(12)),
                   None, True, kind=WINDOW_ATTN)
    np.testing.assert_allclose(seen["routed"].reshape(x.shape), gpt._norm(
        x, blk["ln1_scale"], None, cfg), atol=1e-6)


def test_the_route_and_the_experts_compose_as_one_call():
    """``moe_dropless`` handed the route of its own input is
    ``moe_dropless`` making it; handed another's, the experts of that
    route on its input."""
    ks = jax.random.split(jax.random.key(5), 6)
    n, d, e, f, k = 40, 32, 8, 16, 3
    x, other = (jax.random.normal(a, (1, n, d)) for a in ks[:2])
    w_router = jax.random.normal(ks[2], (d, e))
    w_gate, w_up = (jax.random.normal(a, (e, d, f)) * 0.1 for a in ks[3:5])
    w_down = jax.random.normal(ks[5], (e, f, d)) * 0.1
    kw = dict(top_k=k, scoring="softmax", act="relu")
    own, counts = moe.moe_dropless(x, w_router, None, w_gate, w_up, w_down,
                                   **kw)
    route = moe.dropless_routes(x[0], w_router, None, top_k=k,
                                scoring="softmax")
    handed, _ = moe.moe_dropless(x, w_router, None, w_gate, w_up, w_down,
                                 route=route, **kw)
    np.testing.assert_array_equal(own, handed)
    chosen, gates = moe.dropless_routes(other[0], w_router, None, top_k=k,
                                        scoring="softmax")
    out, _ = moe.moe_dropless(x, w_router, None, w_gate, w_up, w_down,
                              route=(chosen, gates), **kw)
    want = np.zeros((n, d), np.float32)
    for t in range(n):
        for j, ex in enumerate(np.asarray(chosen[t])):
            inner = jax.nn.relu(x[0, t] @ w_gate[ex]) * (x[0, t] @ w_up[ex])
            want[t] += float(gates[t, j]) * np.asarray(inner @ w_down[ex])
    np.testing.assert_allclose(out[0], want, atol=1e-5)
    assert float(jnp.abs(out - own).max()) > 1e-3
    # nothing dropped; the experts that held a row are counted
    assert int(counts[:e].sum()) == int(counts[e]) == n * k
    assert int(counts[e + 3]) == int((np.asarray(counts[:e]) > 0).sum())


# -- the gate activation, in the loop and in the kernel ----------------------

@pytest.fixture
def kernel_path(monkeypatch):
    """The cached path's blocks through the Pallas kernel, interpreted
    (``tests/test_latent_experts.py``'s fixture)."""
    monkeypatch.setattr(moe, "_mosaic_compiles", lambda: True)
    run_blocks = moe._run_blocks
    run_blocks.clear_cache()
    yield
    run_blocks.clear_cache()


def _experts_case(n=24, d=32, e=8, f=16, k=2):
    ks = jax.random.split(jax.random.key(11), 5)
    x = jax.random.normal(ks[0], (n, d))
    chosen = jnp.argsort(jax.random.normal(ks[1], (n, e)))[:, :k].astype(
        jnp.int32)
    w = tuple(jnp.stack([-a, a]) for a in (
        jax.random.normal(ks[2], (e, d, f)) * 0.2,
        jax.random.normal(ks[3], (e, d, f)) * 0.2,
        jax.random.normal(ks[4], (e, f, d)) * 0.2))
    valid = jnp.arange(n) % 5 != 0
    return x, chosen, w, valid


def _stacked(act, x, chosen, w, valid):
    return jax.jit(lambda x, chosen, wg, wu, wd, valid: moe.grouped_swiglu(
        x, chosen, wg, wu, wd, valid, 1, act))(x, chosen, *w, valid)


@pytest.mark.parametrize("act", ["relu", "silu"])
def test_the_kernel_s_block_is_the_loop_s_under_either_gate(kernel_path, act):
    """The Pallas body in interpret mode against the XLA loop over the same
    layout, for the ReLU gate and for the SiLU gate it had: equal outputs
    and equal counts; and the kernel is compiled under the gate's name."""
    case = _experts_case()
    kernel, kernel_counts = _stacked(act, *case)
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(moe, "_mosaic_compiles", lambda: False)
        loop, loop_counts = _stacked(act, *case)
    np.testing.assert_allclose(kernel, loop, atol=1e-5)
    np.testing.assert_array_equal(kernel_counts, loop_counts)
    jaxpr = jax.make_jaxpr(lambda x, chosen, wg, wu, wd, valid:
                           moe.grouped_swiglu(x, chosen, wg, wu, wd, valid,
                                              1, act))(
        case[0], case[1], *case[2], case[3])
    names = [eqn.params["name"] for eqn in _equations(jaxpr.jaxpr)
             if eqn.primitive.name == "pallas_call"]
    assert names == [moe.KERNEL_NAMES[act]]
    assert moe.KERNEL_NAMES == {"silu": "grouped_swiglu",
                                "relu": "grouped_reglu"}


def test_the_relu_gate_is_not_the_silu_gate(kernel_path):
    x, chosen, w, valid = _experts_case()
    relu, _ = _stacked("relu", x, chosen, w, valid)
    silu, _ = _stacked("silu", x, chosen, w, valid)
    assert float(jnp.abs(relu - silu).max()) > 1e-2
    # by hand, a routed token of the relu gate
    t = 1
    for j, ex in enumerate(np.asarray(chosen[t])):
        wg, wu, wd = (a[1, ex] for a in w)
        want = (jax.nn.relu(x[t] @ wg) * (x[t] @ wu)) @ wd
        np.testing.assert_allclose(relu[t, j], want, atol=1e-5)
    np.testing.assert_array_equal(relu[0], 0.0)     # token 0 is not valid


# -- the server ----------------------------------------------------------------

def test_the_server_serves_lanes_on_both_sides_of_the_window(model):
    """Two slots, five requests, so every slot is freed and taken again:
    prompts shorter than the window whose answers cross it, prompts past
    it, a short request in a slot a long one left; greedy tokens those of
    solo ``generate``; one decode program and one prefill program a
    bucket."""
    cfg, params = model
    server = InferenceServer(params, cfg, **STACK.serve, warmup=True)
    prompts = [tokens_of(cfg, 1, n, seed=n)[0].tolist()
               for n in (60, 5, 33, 9, 17)]
    news = (40, 6, 40, 30, 3)
    for p, n, tokens in zip(prompts, news,
                            stacks.serve(server, prompts, news)):
        assert tokens == solo_greedy(params, cfg, p, n)
    s = server.metrics.summary()
    assert s["ring_rows_per_slot"] == WINDOW
    assert 0 < s["ring_rows_live"] <= s["ring_rows_read"]
    assert s["moe_dropped_rows"] == 0 and s["moe_routed_rows"] > 0
    # an expert run is an expert that held a row: at most E a call and
    # layer, at least one, and never more than the rows
    assert 0 < s["moe_expert_runs"] <= s["moe_routed_rows"]
    assert s["moe_expert_runs"] <= s["moe_blocks_run"]
    assert server.compile_counts()["decode"] == 1
    assert server.compile_counts()["prefill"] == 2
    rows = server.engine.moe_rows()
    assert rows.shape == (5, cfg.n_experts + 4)


# -- what is not built is refused: the contract's, by ``STACK.refused`` --------

def test_a_dense_model_s_expert_fields_stay_at_their_defaults():
    with pytest.raises(ConfigError, match="are the dropless route's"):
        GPTConfig.make(n_layer=2, n_head=2, n_embd=32, expert_act="relu")
    cfg = GPTConfig.make(n_layer=2, n_head=2, n_embd=32)
    assert (cfg.expert_act, cfg.moe_router_input) == ("silu", "mlp")
    assert cfg.rope_layout == [0, 0] and cfg.window_layout == [0, 0]
    assert not cfg.router_softmax


# -- precision: the check's law and the planted faults are the contract's ----

def test_a_planted_fault_moves_the_float32_logits(reference, model,
                                                  monkeypatch, plant):
    """``STACK.faults`` through ``gpt.forward`` in float32: each moves the
    logits by a thousand times the tolerance the program is held to."""
    cfg, params = model
    toks = tokens_of(cfg, 1, 48)
    w = reference.weights_from_program(params)
    programs = stacks.reference_programs(STACK, stacks.sizes_of(STACK, cfg))
    want = programs.logits(w, programs.hidden(w, toks)[0])
    faulty, _ = plant(monkeypatch, cfg, stacks.sizes_of(STACK, cfg))
    got, _ = stacks.forward(params, toks, faulty)
    assert float(jnp.abs(got - want).max()) > 2e-3


# -- the configuration file and the cell ---------------------------------------

def test_the_configuration_file_holds_the_catalog_row_key_for_key():
    cell = spec.load_cell(STACK.cell)
    config = cell.config
    row = stacks.catalog_row("SmallThinker-21BA3B-Instruct")
    assert config["source"] == row["source_url"]
    assert config["reduced"] == [
        "num_hidden_layers", "rope_layout", "sliding_window_layout"]
    for key, value in row["config"].items():
        if key == "num_hidden_layers":
            assert (config[key], value) == (5, 52)
        elif key in config["reduced"]:
            assert config[key] == value[:5] == [0, 1, 1, 1, 0]
        else:
            assert config[key] == value, key
    # every published number is tied to a field or a property of the program
    # (and ``num_experts_per_tok``, the name the yardstick's self-check
    # reads a routed reference's k under: an alias, tied to the same field)
    mapped = set(config["program"]["key_map"])
    assert mapped == (set(row["config"]) - {"model_name"}) | {
        "num_experts_per_tok"}
    assert config["num_experts_per_tok"] \
        == config["moe_num_active_primary_experts"] == 6
    cfg = spec.gpt_config(cell, training=False)
    assert cfg.param_dtype == cfg.dtype == "bfloat16"
    assert (cfg.expert_act, cfg.moe_router_input) == ("relu", "attn")
    assert spec.server_options(cell) == {
        "prefill_len": 6144, "prefill_buckets": [3072, 6144],
        "n_slots": cell.found["server"]["n_slots"]}
    for key in ("weights", "hidden_act", "router placement", "router input",
                "qk norm", "bias", "rotation", "every layer sparse"):
        assert key in config["assumed"]
    wrong = dataclasses.replace(cell, config=dict(
        config, sliding_window_size=2048))
    with pytest.raises(spec.SpecError, match="sliding_window_size"):
        spec.gpt_config(wrong, training=False)
    wrong = dataclasses.replace(cell, config=dict(
        config, rope_layout=[1, 1, 1, 1, 1]))
    with pytest.raises(spec.SpecError, match="rope_layout"):
        spec.gpt_config(wrong, training=False)


def test_the_mix_deals_one_checked_prompt_past_the_window():
    """The mix's lengths as every seed is dealt them: prompts inside the
    two buckets, three of sixteen past the 4,096 window, and of the two
    checked prompts (one a bucket, in the order of arrival) the second is
    longer than the window, so the ring's wrap is compared on the chip."""
    from benchmarks.harness import traffic

    cell = spec.load_cell(STACK.cell)
    reqs = traffic.requests(cell.mix, 1000, STACK.seed, rate=cell.found[
        "rate_req_s"], horizon_s=60.0)
    again = traffic.requests(cell.mix, 1000, STACK.seed + 7, rate=cell.found[
        "rate_req_s"], horizon_s=60.0)
    lengths = [len(r.prompt) for r in reqs]
    assert lengths == [len(r.prompt) for r in again]
    assert min(lengths) >= 768 and max(lengths) == 6144
    block = lengths[:16]
    assert sum(n > 4096 for n in block) == 3
    picked = check.pick_prompts(reqs, (3072, 6144), cell.mix["check_prompts"])
    assert [len(p) <= 3072 for p in picked] == [True, False]
    assert len(picked[1]) > 4096
    ends = [len(r.prompt) + r.max_new_tokens for r in reqs[:16]]
    assert max(ends) <= spec.gpt_config(cell, training=False).block_size
    assert 4 <= sum(e > 4096 for e in ends) <= 8


def test_the_new_counter_reaches_the_readers(cell_run):
    play = cell_run["evidence"]["play"]
    closed, opened = play.close_counters, play.open_counters
    assert closed["moe_expert_runs"] > opened["moe_expert_runs"] > 0
    assert closed["moe_dropped_rows"] == 0
    # untraced: the readers find nothing and say so
    for name in NEW_READERS:
        assert spec.load_reader(name).read(cell_run["evidence"]) is None
    traced = dataclasses.replace(play, trace_open=opened, trace_close=closed)
    evidence = dict(cell_run["evidence"], play=traced)
    per_step = spec.load_reader("moe.expert_runs_per_step").read(evidence)
    # five layers of 8 experts: at most 40 a call, a round a call or two
    # (and none in a round the open loop's server idles through)
    assert 0 < per_step <= 2 * 5 * 8


# -- the three readers, with and without their sources ------------------------

def test_the_counter_s_reader_reads_two_readings():
    read = spec.load_reader("moe.expert_runs_per_step").read
    play = serve_cell.Play(n_slots=64, block_size=16384)
    play.trace_open = {"moe_expert_runs": 1000, "steps": 10}
    play.trace_close = {"moe_expert_runs": 1000 + 30 * 265, "steps": 40}
    assert read({"play": play}) == 265.0
    play.trace_close = dict(play.trace_open)
    assert read({"play": play}) is None                 # no round
    # the parent's summary: no such counter
    play.trace_open, play.trace_close = {"steps": 1}, {"steps": 9}
    assert read({"play": play}) is None
    play.trace_open = play.trace_close = {"moe_expert_runs": None, "steps": 3}
    assert read({"play": play}) is None
    assert read({"play": None}) is None and read({}) is None


def test_the_route_s_reader_reads_its_scope_and_nothing_without_it():
    from benchmarks.harness import scopes

    read = spec.load_reader("moe.route_ms_per_step").read
    assert read({"trace": None, "program_spans": []}) is None
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(scopes, "ms_by_scope", lambda ev, part: {
            "runs": 3, "unmatched_ms": 0.0, "unscoped_ms": 0.1,
            "by_scope": {"moe_route": 0.25, "ffn": 1.0}})
        assert read({}) == 0.25
        # a program whose router reads the MLP's input has no such scope
        patch.setattr(scopes, "ms_by_scope", lambda ev, part: {
            "runs": 3, "unmatched_ms": 0.0, "unscoped_ms": 0.1,
            "by_scope": {"ffn": 1.0}})
        assert read({}) is None


def test_the_roofline_s_operations_and_bytes_are_the_published_widths():
    reader = spec.load_reader("kernel.grouped_glu_roofline")
    config = spec.load_cell(STACK.cell).config
    peaks = {"flops": 197e12, "hbm_bytes_s": 819e9}
    per_expert = 3 * 2560 * 768 * 2         # 11.8 MB
    # a decode round: 18 lanes x 6 rows through 53 experts reads bytes
    assert reader.least_seconds(config, 108, 53, peaks) == pytest.approx(
        53 * per_expert / 819e9)
    # a 6,144-token prefill through all 64 is bound by operations
    rows = 6144 * 6
    assert reader.least_seconds(config, rows, 64, peaks) == pytest.approx(
        rows * 6 * 2560 * 768 / 197e12)
    assert rows * 6 * 2560 * 768 / 197e12 > 64 * per_expert / 819e9


# -- the accepted routed cells' programs --------------------------------------

#: ``jax.make_jaxpr``'s equations by primitive, of the decode step of the two
#: routed configurations of the benchmark at their tiny sizes, made on the
#: parent commit (2ab25cb) with this file's ``decode_primitives``: what PR 61
#: may add to them is the counter ``moe_expert_runs``, once an expert layer
#: (the rows each expert computed compared with 0, the sum of that, and one
#: conversion and one broadcast around them) and nothing else: no matmul, no
#: gather, no loop. The counts vector, and the pool's leaf, are one entry
#: longer: CHANGES.md, PR 61.
PARENT_DECODE_PRIMITIVES = {
    KANANA: {
        "add": 61, "and": 2, "broadcast_in_dim": 107, "concatenate":
        8, "convert_element_type": 67, "cos": 2, "cumsum": 2, "div":
        15, "dot_general": 32, "dynamic_slice": 10,
        "dynamic_update_slice": 7, "eq": 2, "exp": 4, "gather": 19,
        "iota": 19, "jit": 21, "logistic": 4, "lt": 25, "lt_to": 1,
        "max": 2, "min": 1, "mul": 57, "ne": 4, "neg": 4,
        "optimization_barrier": 2, "pow": 2, "reduce_max": 2,
        "reduce_sum": 14, "rem": 2, "reshape": 24, "rsqrt": 7, "scan":
        1, "scatter": 1, "select_n": 29, "sign": 4, "sin": 2, "slice":
        60, "square": 7, "squeeze": 53, "sub": 12, "top_k": 1,
        "transpose": 4, "while": 1},
    LAGUNA: {
        "add": 144, "and": 15, "broadcast_in_dim": 256, "concatenate":
        32, "convert_element_type": 196, "cos": 5, "cumsum": 8, "div":
        44, "dot_general": 76, "dynamic_slice": 34,
        "dynamic_update_slice": 16, "eq": 17, "exp": 14, "gather": 25,
        "gt": 2, "iota": 43, "jit": 86, "le": 3, "logistic": 14, "lt":
        88, "lt_to": 4, "max": 9, "min": 7, "mul": 134, "ne": 24,
        "neg": 11, "optimization_barrier": 15, "pow": 3, "reduce_max":
        9, "reduce_sum": 40, "rem": 12, "reshape": 116, "rsqrt": 11,
        "scan": 4, "scatter": 4, "select_n": 106, "sign": 16, "sin":
        5, "slice": 156, "sqrt": 5, "square": 11, "squeeze": 144,
        "stop_gradient": 4, "sub": 48, "top_k": 4, "while": 4},
}
THE_COUNTER_ADDS = ("gt", "reduce_sum", "convert_element_type",
                    "broadcast_in_dim")


def decode_primitives(cell_name):
    cfg = GPTConfig.make(
        **stacks.tiny_cell(cell_name).config["program"]["gpt_config"])
    params = _abstract_params(cfg)
    cache = jax.eval_shape(lambda: dict(
        gen.init_cache(cfg, 3), **{gen.MOE_ROWS: gen.init_moe_rows(cfg)}))
    step = lambda p, t, c, o: gen._forward_cached(
        p, t, c, o, cfg, valid=jnp.ones(t.shape, bool))
    jaxpr = jax.make_jaxpr(step)(params, _ids(3, 1), cache, _ids(3))
    return cfg, collections.Counter(
        eqn.primitive.name for eqn in _equations(jaxpr.jaxpr))


@pytest.mark.parametrize("cell_name", [KANANA, LAGUNA])
def test_an_accepted_routed_decode_program_gains_the_counter_alone(cell_name):
    cfg, now = decode_primitives(cell_name)
    expert_layers = cfg.n_layer - cfg.n_dense_layers
    want = collections.Counter(PARENT_DECODE_PRIMITIVES[cell_name])
    for primitive in THE_COUNTER_ADDS:
        want[primitive] += expert_layers
    assert now == want, {p: (now[p], want[p]) for p in set(now) | set(want)
                         if now[p] != want[p]}
    assert (cfg.expert_act, cfg.moe_router_input) == ("silu", "mlp")
    assert gen.init_moe_rows(cfg).shape == (expert_layers, cfg.n_experts + 4)
