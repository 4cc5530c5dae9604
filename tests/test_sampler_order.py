"""The serving sampler orders the vocabulary only when a lane's filter
needs it (PR 31).

``engine._select_next_slots`` used to sort the ``(S, V)`` logits twice a
round whatever the lanes asked for. It now sorts once, under a
``lax.cond`` on the program's own inputs, and not at all in a round with no
lane that samples under top-k or top-p. What must hold, on the CPU:

* every lane's token is the two-sort sampler's, exactly, over the grid of
  parameters below (``_two_sort_reference`` is that sampler's body as it
  stood, kept here as the reference), and so is every token of a request
  served with that sampler put back into the programs, the one at the
  window's last row included;
* a freed slot asks the sampler for nothing (``SlotTable.release``);
* the lowered decode and prefill programs hold one sort, inside a
  conditional's branch; the compiled verify program holds none;
* one executable a program family whatever the requests ask for;
* ``summary()["sampler_sorted_rounds"]`` counts the rounds that sorted.
"""

import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mingpt_distributed_tpu.config import GPTConfig
from mingpt_distributed_tpu.models import gpt
from mingpt_distributed_tpu.serving import InferenceServer, Request
from mingpt_distributed_tpu.serving import engine as engine_mod
from mingpt_distributed_tpu.serving.engine import (
    _select_next_slots,
    sampler_orders,
)
from mingpt_distributed_tpu.serving.scheduler import SlotTable
from oracles import solo_greedy


def _two_sort_reference(logits, keys, temps, top_ks, top_ps, do_sample):
    """``_select_next_slots`` as it was before PR 31, verbatim."""
    v = logits.shape[-1]
    logits = logits / jnp.maximum(temps, 1e-8)[:, None]
    # top-k with per-slot k: threshold at the k-th largest value; k=V is a
    # no-op, so "disabled" rides as k_eff = V
    k_eff = jnp.where(top_ks > 0, jnp.minimum(top_ks, v), v)
    desc = jnp.sort(logits, axis=-1)[:, ::-1]
    kth = jnp.take_along_axis(desc, (k_eff - 1)[:, None], axis=-1)
    logits = jnp.where(logits < kth, -jnp.inf, logits)
    # nucleus: smallest prefix of the (re-sorted, post-top-k) distribution
    # whose preceding cumulative mass is < top_p; top token unconditional
    desc2 = jnp.sort(logits, axis=-1)[:, ::-1]
    probs = jax.nn.softmax(desc2, axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    keep = (cum - probs) < top_ps[:, None]
    keep = keep.at[:, 0].set(True)
    kth2 = jnp.min(jnp.where(keep, desc2, jnp.inf), axis=-1, keepdims=True)
    nucleus_on = (top_ps < 1.0)[:, None]
    logits = jnp.where(nucleus_on & (logits < kth2), -jnp.inf, logits)
    sampled = jax.vmap(lambda l, k: jax.random.categorical(k, l))(logits, keys)
    greedy = jnp.argmax(logits, axis=-1)
    return jnp.where(do_sample, sampled, greedy).astype(jnp.int32)


V = 257
DRAWS = 4  # key sets a case is drawn under


def _lanes(s, temp=1.0, top_k=0, top_p=1.0, do_sample=True):
    """Every lane of ``s`` with the same parameters."""
    return dict(temps=np.full(s, temp, np.float32),
                top_ks=np.full(s, top_k, np.int32),
                top_ps=np.full(s, top_p, np.float32),
                do_sample=np.full(s, do_sample, bool))


def _mixed(s):
    """Greedy, filtered and plain-temperature lanes in one batch, each
    lane at a temperature of its own."""
    kinds = [(True, 5, 1.0), (False, 0, 1.0), (True, 0, 1.0), (True, 0, 0.9),
             (True, 40, 0.3), (False, 3, 0.5), (True, V + 9, 1.0),
             (True, 1, 0.0)]
    kinds = (kinds * s)[:s]
    return dict(temps=np.linspace(0.6, 1.7, s).astype(np.float32),
                top_ks=np.array([k[1] for k in kinds], np.int32),
                top_ps=np.array([k[2] for k in kinds], np.float32),
                do_sample=np.array([k[0] for k in kinds], bool))


# name -> (lane parameters for S lanes, the round orders the vocabulary)
GRID = {
    "all-greedy": (lambda s: _lanes(s, do_sample=False), False),
    "greedy-under-stale-filters":
        (lambda s: _lanes(s, 0.8, 7, 0.4, do_sample=False), False),
    "temperature-only": (lambda s: _lanes(s, 0.7), False),
    "top-k-1": (lambda s: _lanes(s, 1.1, top_k=1), True),
    "top-k-5": (lambda s: _lanes(s, 1.1, top_k=5), True),
    "top-k-V": (lambda s: _lanes(s, 1.1, top_k=V), True),
    "top-k-over-V": (lambda s: _lanes(s, 1.1, top_k=V + 100), True),
    "top-p-0.0": (lambda s: _lanes(s, 0.9, top_p=0.0), True),
    "top-p-0.3": (lambda s: _lanes(s, 0.9, top_p=0.3), True),
    "top-p-0.9": (lambda s: _lanes(s, 0.9, top_p=0.9), True),
    "top-p-1.0": (lambda s: _lanes(s, 0.9, top_p=1.0), False),
    "top-k-5-top-p-0.9": (lambda s: _lanes(s, 1.3, 5, 0.9), True),
    "top-k-40-top-p-0.3": (lambda s: _lanes(s, 1.3, 40, 0.3), True),
    "mixed-lanes": (_mixed, True),
}


def _logits(s, v, seed, tied=False):
    x = jax.random.normal(jax.random.key(seed), (s, v), jnp.float32) * 3.0
    if tied:
        # half-unit steps: the top value and the k-th are shared by several
        # tokens in most rows
        x = jnp.round(x) / 2
    return x


def _assert_same_tokens(logits, params):
    """New sampler against the two-sort one under DRAWS sets of keys."""
    s = logits.shape[0]
    new = jax.jit(_select_next_slots)
    old = jax.jit(_two_sort_reference)
    for draw in range(DRAWS):
        keys = jax.random.split(jax.random.key(1000 + draw), s)
        want = np.asarray(old(logits, keys, **params))
        got = np.asarray(new(logits, keys, **params))
        assert got.dtype == np.int32
        np.testing.assert_array_equal(got, want)
    return want


@pytest.mark.parametrize("s", [1, 8])
@pytest.mark.parametrize("case", sorted(GRID))
def test_tokens_equal_the_two_sort_sampler(case, s):
    make, orders = GRID[case]
    params = make(s)
    assert bool(sampler_orders(params["do_sample"], params["top_ks"],
                               params["top_ps"])) == orders
    _assert_same_tokens(_logits(s, V, seed=s), params)


@pytest.mark.parametrize("s", [1, 8])
@pytest.mark.parametrize("case", ["all-greedy", "top-k-5", "top-p-0.3",
                                  "top-k-5-top-p-0.9", "mixed-lanes"])
def test_tied_logits_break_as_before(case, s):
    """Ties at the top and at the thresholds: the filters keep by value and
    the argmax takes the first, in both samplers."""
    logits = _logits(s, V, seed=7 + s, tied=True)
    assert np.unique(np.asarray(logits)).size < V // 4
    _assert_same_tokens(logits, GRID[case][0](s))


@pytest.mark.parametrize("s", [1, 8])
@pytest.mark.parametrize("others", ["all-greedy", "temperature-only",
                                    "top-p-0.9"])
def test_a_freed_slot_asks_for_nothing(others, s):
    """A slot whose nucleus tenant has left rides beside ``s`` lanes of
    another kind: ``release`` put it back as a table is built, so it alone
    does not make the round sort, and every lane's token is the two-sort
    sampler's."""
    table, fresh = SlotTable(s + 1, block_size=32), SlotTable(s + 1, 32)
    table.bind(0, types.SimpleNamespace(), seed=9)
    table.start_decode(0, token=3, position=5, req=Request(
        prompt=[1], do_sample=True, temperature=0.7, top_k=4, top_p=0.5))
    assert sampler_orders(table.do_sample, table.top_ks, table.top_ps)
    table.release(0)
    for name in ("positions", "temps", "top_ks", "top_ps", "do_sample",
                 "seeds"):
        np.testing.assert_array_equal(getattr(table, name),
                                      getattr(fresh, name))
    params = GRID[others][0](s)
    params = {name: np.concatenate([getattr(table, name)[:1], lanes])
              for name, lanes in params.items()}
    assert bool(sampler_orders(params["do_sample"], params["top_ks"],
                               params["top_ps"])) == GRID[others][1]
    _assert_same_tokens(_logits(s + 1, V, seed=21), params)


def test_gpt2_vocabulary_mixed_lanes():
    """One case at the benchmark's 50,257 rows: greedy, filtered and
    plain-temperature lanes, and the sampled lanes do leave the argmax."""
    s, v = 8, 50257
    logits = _logits(s, v, seed=3)
    params = _mixed(s)
    want = _assert_same_tokens(logits, params)
    greedy = np.asarray(jnp.argmax(logits, axis=-1))
    np.testing.assert_array_equal(
        want[~params["do_sample"]], greedy[~params["do_sample"]])
    assert (want != greedy).any()


# ---------------------------------------------------------------------------
# the programs' structure
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def cfg_params():
    cfg = GPTConfig.make(
        n_layer=2, n_head=2, n_embd=32, vocab_size=50, block_size=32,
        embd_pdrop=0.0, resid_pdrop=0.0, attn_pdrop=0.0, dtype="float32",
    )
    return cfg, gpt.init(jax.random.key(0), cfg)


def _sorts(jaxpr, in_cond=False):
    """(sorts outside every conditional, sorts inside a conditional's
    branch) of a jaxpr, its sub-programs walked."""
    outside = inside = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "sort":
            outside, inside = outside + (not in_cond), inside + in_cond
        under = in_cond or eqn.primitive.name == "cond"
        for sub in jax.core.jaxprs_in_params(eqn.params):
            o, i = _sorts(sub, under)
            outside, inside = outside + o, inside + i
    return outside, inside


@pytest.fixture(scope="module")
def spec_server(cfg_params):
    cfg, params = cfg_params
    return InferenceServer(params, cfg, n_slots=3, prefill_buckets=(8, 32),
                           draft_params=params, draft_cfg=cfg, spec_k=2)


def _programs(server):
    progs = list(server.engine.programs()) + list(server.spec.programs())
    return {f"{family}{'.' if variant else ''}{variant}": (jitted, args, kw)
            for family, variant, jitted, args, kw in progs}


@pytest.mark.parametrize("program", ["decode", "prefill.b8", "prefill.b32",
                                     "draft_decode", "draft_prefill.b32"])
def test_one_sort_inside_a_conditional(spec_server, program):
    jitted, args, kw = _programs(spec_server)[program]
    assert jitted.lower(*args, **kw).as_text().count("stablehlo.sort") == 1
    assert _sorts(jitted.trace(*args, **kw).jaxpr.jaxpr) == (0, 1)


def test_verify_program_compiles_to_no_sort(spec_server):
    """``_verify_impl`` hands the sampler ``do_sample`` as a constant
    ``zeros``: no sort outside a conditional as lowered, and the compiler
    folds the conditional away."""
    jitted, args, kw = _programs(spec_server)["verify.k2"]
    assert _sorts(jitted.trace(*args, **kw).jaxpr.jaxpr)[0] == 0
    compiled = jitted.lower(*args, **kw).compile().as_text()
    assert " sort(" not in compiled and "conditional(" not in compiled


# ---------------------------------------------------------------------------
# one executable a family, and the counter
# ---------------------------------------------------------------------------

PROMPTS = [[1, 2, 3, 4, 5], [7, 8, 9], [10, 11, 12, 13], [40, 41]]
NUCLEUS = dict(do_sample=True, temperature=0.8, top_p=0.9)
TEMPERATURE = dict(do_sample=True, temperature=1.3)


def _filtered_live(server):
    """Whether the decode round the next ``step()`` runs has a filtered
    sampling request in it: one that decodes already, or one whose prefill
    that step finishes first (every prompt here is one chunk)."""
    waiting = list(server.queue)[:server.engine.pool.free_count]
    handles = server.slots.live_handles() + waiting
    return any(h.request.do_sample and (h.request.top_k is not None
                                        or h.request.top_p is not None)
               for h in handles if not h.finished)


def test_mixed_requests_compile_nothing_and_count_their_rounds(cfg_params):
    """Greedy, temperature and nucleus requests through one warmed server:
    decode stays one executable, prefill within its buckets, the watchdog
    counts nothing, and ``sampler_sorted_rounds`` is the number of decode
    steps launched with a filtered sampling request live (a round launches
    one, none where it syncs first, two after that: scheduler ``step``)."""
    cfg, params = cfg_params
    server = InferenceServer(params, cfg, n_slots=3, warmup=True,
                             prefill_buckets=(8, 32), recompile_fail=True)
    before = server.compile_counts()
    assert before["decode"] == 1 and before["prefill"] == 2
    assert server.summary()["sampler_sorted_rounds"] == 0  # the warm-up's
    greedy = server.submit(Request(prompt=PROMPTS[0], max_new_tokens=12))
    warm = server.submit(Request(prompt=PROMPTS[1], max_new_tokens=9, seed=3,
                                 **TEMPERATURE))
    for _ in range(3):
        server.step()
    assert server.summary()["sampler_sorted_rounds"] == 0
    nucleus = server.submit(Request(prompt=PROMPTS[2], max_new_tokens=4,
                                    seed=4, **NUCLEUS))
    top_k = server.submit(Request(prompt=PROMPTS[3], max_new_tokens=3, seed=5,
                                  do_sample=True, top_k=5))
    expected = 0
    busy = True
    launches = lambda: server.summary()["decode_launches"]
    while busy:
        live, before_round = _filtered_live(server), launches()
        busy = server.step()
        expected += live * (launches() - before_round)
    assert all(h.finished for h in (greedy, warm, nucleus, top_k))
    summary = server.summary()
    assert 0 < expected < summary["decode_launches"]
    assert summary["sampler_sorted_rounds"] == expected
    assert server.compile_counts() == before
    fam = server.metrics.registry.counter(
        "mingpt_recompiles_total", labels=("family",))
    assert sum(child.value for _, child in fam.children()) == 0
    assert greedy.tokens == solo_greedy(params, cfg, PROMPTS[0], 12)


@pytest.mark.parametrize("traffic,sorts", [
    ("greedy", False), ("temperature", False), ("nucleus", True),
    ("greedy-with-filters-set", False)])
def test_counter_by_traffic(cfg_params, traffic, sorts):
    """All requests of one kind: the counter reads 0 under greedy or
    plain-temperature traffic (a greedy request's ``top_p`` moves nothing)
    and every decode round under nucleus traffic."""
    cfg, params = cfg_params
    kind = {"greedy": {}, "temperature": TEMPERATURE, "nucleus": NUCLEUS,
            "greedy-with-filters-set": dict(top_k=4, top_p=0.5)}[traffic]
    server = InferenceServer(params, cfg, n_slots=2)
    for i, p in enumerate(PROMPTS[:3]):
        server.submit(Request(prompt=p, max_new_tokens=6, seed=i, **kind))
    server.run_until_drained(max_steps=100)
    summary = server.summary()
    assert summary["requests_completed"] == 3
    if sorts:
        # every round but those with nothing left to decode (a round may
        # only admit and prefill; this traffic has none such)
        assert summary["sampler_sorted_rounds"] == summary["steps"] > 0
    else:
        assert summary["sampler_sorted_rounds"] == 0 < summary["steps"]


def test_a_finished_requests_lane_counts_nothing(cfg_params):
    """The scheduler resets a freed slot's sampling parameters: a greedy
    request that decodes on beside a finished nucleus request's parked lane
    sorts nothing, and its tokens are solo ``generate()``'s."""
    cfg, params = cfg_params
    server = InferenceServer(params, cfg, n_slots=2)
    nucleus = server.submit(Request(prompt=PROMPTS[1], max_new_tokens=3,
                                    seed=1, do_sample=True, top_p=0.5))
    greedy = server.submit(Request(prompt=PROMPTS[0], max_new_tokens=14))
    server.step()
    slot = nucleus.slot
    assert server.slots.do_sample[slot] and server.slots.top_ps[slot] == 0.5
    while not nucleus.finished:
        server.step()
    sorted_rounds = server.summary()["sampler_sorted_rounds"]
    assert sorted_rounds > 0 and not greedy.finished
    freed = server.slots
    assert not freed.do_sample[slot] and freed.top_ps[slot] == 1.0
    assert freed.positions[slot] == freed.parked
    steps = server.summary()["steps"]
    server.run_until_drained(max_steps=100)
    assert server.summary()["steps"] > steps + 5
    assert server.summary()["sampler_sorted_rounds"] == sorted_rounds
    assert greedy.tokens == solo_greedy(params, cfg, PROMPTS[0], 14)


# ---------------------------------------------------------------------------
# a request that runs to the end of the window
# ---------------------------------------------------------------------------

# a sampling request's last decode step is at the window's last row, the
# row a free lane is parked at: what a lane asks of the sampler is read
# from its parameters, never from where it stands
TO_THE_END = {
    "nucleus": (NUCLEUS, True),
    "top-k": (dict(do_sample=True, temperature=1.2, top_k=5), True),
    "top-k-nucleus": (dict(do_sample=True, top_k=12, top_p=0.7), True),
    "temperature": (TEMPERATURE, False),
    "greedy": ({}, False),
}
COMPANY = ["alone", "beside-greedy", "beside-speculation"]


def _serve_to_the_end(cfg, params, kind, company, seed):
    """One request of ``kind`` that asks for more tokens than the window
    has room for, alone in the batch or beside a shorter greedy request
    (which, with a draft model, speculates). Returns the request's handle
    and the server's summary."""
    spec = (dict(draft_params=params, draft_cfg=cfg, spec_k=2)
            if company == "beside-speculation" else {})
    server = InferenceServer(params, cfg, n_slots=2, **spec)
    handle = server.submit(Request(prompt=PROMPTS[0], max_new_tokens=1000,
                                   seed=seed, **kind))
    if company != "alone":
        server.submit(Request(prompt=PROMPTS[1], max_new_tokens=10))
    server.run_until_drained(max_steps=200)
    assert handle.finished
    return handle, server.summary()


@pytest.fixture(scope="module")
def two_sort_tokens(cfg_params):
    """kind -> the tokens of that request served alone by programs that
    hold the two-sort sampler (each engine jits closures of its own, so
    one built under the patch traces the reference), kept over the
    module."""
    cfg, params = cfg_params
    served = {}

    def tokens(kind):
        if kind not in served:
            with pytest.MonkeyPatch.context() as patch:
                patch.setattr(engine_mod, "_select_next_slots",
                              _two_sort_reference)
                handle, _ = _serve_to_the_end(
                    cfg, params, TO_THE_END[kind][0], "alone", seed=5)
            served[kind] = handle.tokens
        return served[kind]

    return tokens


@pytest.mark.parametrize("company", COMPANY)
@pytest.mark.parametrize("kind", sorted(TO_THE_END))
def test_a_request_that_fills_the_window(cfg_params, two_sort_tokens, kind,
                                         company):
    """Every token, the last one at row ``block_size - 1`` included, is
    the two-sort programs' under the same seed, whoever shares the batch;
    and a filtered request counts every one of its decode rounds."""
    cfg, params = cfg_params
    request, sorts = TO_THE_END[kind]
    handle, summary = _serve_to_the_end(cfg, params, request, company, seed=5)
    room = cfg.block_size - len(PROMPTS[0]) + 1
    assert len(handle.tokens) == room
    assert handle.tokens == two_sort_tokens(kind)
    if kind == "greedy":
        assert handle.tokens == solo_greedy(params, cfg, PROMPTS[0], room)
    # the first token is the prefill's; each of the others took a round
    assert summary["sampler_sorted_rounds"] == (room - 1 if sorts else 0)


def test_the_benchmarks_greedy_traffic_sorts_in_no_round():
    """``gpt2-124m.serve-decode`` at the rehearsal's tiny size through the
    benchmark's own driver: the counter reaches a reader through the play's
    readings of ``summary()`` and reads 0 over rounds that decoded."""
    from benchmarks import rehearse
    from benchmarks.harness import compiles, serve_cell, spec

    run = serve_cell.run(
        rehearse.tiny(spec.load_cell("gpt2-124m.serve-decode")), seed=31,
        seconds=1.0, traced=False, devices=jax.devices()[:1], t_process=0.0,
        compiles=compiles.CompileCounter())
    assert run["verdict"]["ok"], run["verdict"]
    opened, closed = (run["evidence"]["play"].open_counters,
                      run["evidence"]["play"].close_counters)
    assert closed["steps"] > opened["steps"] and closed["lanes"] > 0
    assert opened["sampler_sorted_rounds"] == 0
    assert closed["sampler_sorted_rounds"] == 0
