"""Continuous-batching server tests — CPU, tiny config, `not slow` tier.

The load-bearing guarantees:
* slot pool allocate/free is deterministic and exhaustion-safe; requests
  queue when slots are full and are admitted as slots free;
* a request admitted MID-DECODE (while other slots are half-way through)
  produces greedy output token-identical to solo generate() on its prompt;
* after warmup, serving any number of requests never recompiles (exactly
  one trace per compiled program — prefill and decode);
* per-request stop conditions (max_new_tokens, EOS) retire independently;
* the serving metrics counters add up.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mingpt_distributed_tpu.config import GPTConfig
from mingpt_distributed_tpu.models import generate as gen
from mingpt_distributed_tpu.models import gpt
from mingpt_distributed_tpu.ops import attention as attn_ops
from mingpt_distributed_tpu.serving import engine as engine_mod
from mingpt_distributed_tpu.serving import (
    InferenceServer,
    QueueFullError,
    Request,
    SlotKVPool,
)
from oracles import solo_greedy


@pytest.fixture(scope="module")
def cfg_params():
    cfg = GPTConfig.make(
        n_layer=2, n_head=2, n_embd=32, vocab_size=50, block_size=32,
        embd_pdrop=0.0, resid_pdrop=0.0, attn_pdrop=0.0, dtype="float32",
    )
    return cfg, gpt.init(jax.random.key(0), cfg)


PROMPTS = [[1, 2, 3, 4, 5], [7, 8, 9], [10, 11, 12, 13], [40, 41], [20, 21, 22]]


# ---------------------------------------------------------------------------
# slot pool
# ---------------------------------------------------------------------------


def test_pool_allocate_free_exhaustion(cfg_params):
    cfg, _ = cfg_params
    pool = SlotKVPool(cfg, 3)
    assert pool.cache["k"].shape == (
        cfg.n_layer, 3, cfg.block_size, cfg.kv_heads, cfg.head_dim)
    # deterministic lowest-first allocation
    assert [pool.allocate() for _ in range(3)] == [0, 1, 2]
    assert pool.free_count == 0 and pool.used_count == 3
    assert pool.allocate() is None  # exhausted, not an error
    pool.free(1)
    assert pool.allocate() == 1  # reuses the freed slot
    with pytest.raises(ValueError):
        pool.free(5)  # out of range
    pool.free(2)
    with pytest.raises(ValueError):
        pool.free(2)  # double free


# ---------------------------------------------------------------------------
# scheduler
# ---------------------------------------------------------------------------


def test_requests_queue_when_slots_full(cfg_params):
    cfg, params = cfg_params
    server = InferenceServer(params, cfg, n_slots=2)
    handles = [server.submit(Request(prompt=p, max_new_tokens=6))
               for p in PROMPTS[:4]]
    # 4 requests, 2 slots: two must sit in the queue after the first round
    server.step()
    assert len(server.queue) == 2
    assert server.engine.pool.free_count == 0
    server.run_until_drained(max_steps=100)
    for p, h in zip(PROMPTS[:4], handles):
        assert h.finished and h.finish_reason == "length"
        assert h.tokens == solo_greedy(params, cfg, p, 6)
    assert server.metrics.requests_completed == 4


def test_mid_decode_admission_matches_solo_and_never_recompiles(cfg_params):
    """The acceptance-criteria test: >= 3 concurrent requests with
    staggered arrivals, each greedy output token-identical to solo
    generate(), and no recompilation after warmup (trace counts stay 1)."""
    cfg, params = cfg_params
    server = InferenceServer(params, cfg, n_slots=3)
    n = 10
    h1 = server.submit(Request(prompt=PROMPTS[0], max_new_tokens=n))
    server.step()  # h1 prefilled (warmup: both programs trace here or next)
    server.step()  # h1 mid-decode
    h2 = server.submit(Request(prompt=PROMPTS[1], max_new_tokens=n))
    server.step()  # h2 admitted while h1 decodes
    h3 = server.submit(Request(prompt=PROMPTS[2], max_new_tokens=n))
    server.step()
    # all three in flight at once — genuinely concurrent
    assert server.engine.pool.used_count == 3
    server.run_until_drained(max_steps=100)
    for p, h in zip(PROMPTS[:3], (h1, h2, h3)):
        assert h.tokens == solo_greedy(params, cfg, p, n), h.request_id
    # late-arriving request after everything drained: still no new trace
    h4 = server.submit(Request(prompt=PROMPTS[3], max_new_tokens=4))
    server.run_until_drained(max_steps=100)
    assert h4.tokens == solo_greedy(params, cfg, PROMPTS[3], 4)
    # default ladder at block_size=32 is a single bucket: still one
    # prefill trace, one decode trace, no prefix-copy programs
    assert server.compile_counts() == {
        "prefill": 1, "decode": 1, "prefix_load": 0, "prefix_save": 0}


def test_per_request_stop_conditions(cfg_params):
    cfg, params = cfg_params
    solo = solo_greedy(params, cfg, PROMPTS[0], 10)
    eos = solo[3]  # greedy decode will produce this at index 3
    server = InferenceServer(params, cfg, n_slots=3)
    h_len3 = server.submit(Request(prompt=PROMPTS[1], max_new_tokens=3))
    h_len8 = server.submit(Request(prompt=PROMPTS[2], max_new_tokens=8))
    h_eos = server.submit(
        Request(prompt=PROMPTS[0], max_new_tokens=10, eos_id=eos))
    server.run_until_drained(max_steps=100)
    assert h_len3.finish_reason == "length" and len(h_len3.tokens) == 3
    assert h_len8.finish_reason == "length" and len(h_len8.tokens) == 8
    # EOS stops early; the EOS token is included in the output
    assert h_eos.finish_reason == "eos"
    assert h_eos.tokens == solo[:4]


def test_max_new_one_finishes_at_prefill(cfg_params):
    cfg, params = cfg_params
    server = InferenceServer(params, cfg, n_slots=2)
    h = server.submit(Request(prompt=PROMPTS[0], max_new_tokens=1))
    server.run_until_drained(max_steps=10)
    assert h.finished and len(h.tokens) == 1
    assert h.tokens == solo_greedy(params, cfg, PROMPTS[0], 1)
    # the slot was freed without ever joining the decode batch
    assert server.engine.pool.free_count == 2


def test_sampled_tenant_does_not_perturb_greedy_tenant(cfg_params):
    """Per-slot sampling params are traced arrays in ONE shared program: a
    high-temperature sampled request decoding alongside a greedy one must
    leave the greedy lane's tokens exactly solo."""
    cfg, params = cfg_params
    server = InferenceServer(params, cfg, n_slots=2)
    h_greedy = server.submit(Request(prompt=PROMPTS[0], max_new_tokens=8))
    h_sampled = server.submit(Request(
        prompt=PROMPTS[1], max_new_tokens=8, do_sample=True,
        temperature=1.5, top_k=10, seed=7))
    server.run_until_drained(max_steps=100)
    assert h_greedy.tokens == solo_greedy(params, cfg, PROMPTS[0], 8)
    assert len(h_sampled.tokens) == 8
    assert all(0 <= t < cfg.vocab_size for t in h_sampled.tokens)


def test_sampled_request_reproducible_by_seed(cfg_params):
    """A sampled request's tokens depend on its seed, not its co-tenants:
    same seed alone vs alongside another request gives the same tokens."""
    cfg, params = cfg_params

    def run(extra: bool):
        server = InferenceServer(params, cfg, n_slots=2)
        h = server.submit(Request(
            prompt=PROMPTS[1], max_new_tokens=8, do_sample=True,
            temperature=0.9, top_k=12, seed=3))
        if extra:
            server.submit(Request(prompt=PROMPTS[2], max_new_tokens=8,
                                  do_sample=True, seed=11))
        server.run_until_drained(max_steps=100)
        return h.tokens

    assert run(extra=False) == run(extra=True)


# seeds whose low 32 bits are all jax.random.key keeps: the edges of int32,
# uint32 and the first bit past them
KEY_SEEDS = [0, 1, 2**31 - 1, 2**31, 2**32 - 1]


@pytest.mark.parametrize("index", [0, 1, 511])
@pytest.mark.parametrize("seed", KEY_SEEDS)
def test_lane_keys_match_eager_fold_in(seed, index):
    """The keys the decode and prefill programs derive inside their traces
    are, bit for bit, the keys the scheduler used to fold eagerly."""
    from mingpt_distributed_tpu.serving.engine import lane_keys, request_seeds

    want = np.asarray(jax.random.key_data(
        jax.random.fold_in(jax.random.key(seed), index)))
    # the decode program's form: (S,) seeds and indices, other lanes beside
    seeds = request_seeds([7, seed, 2**40 + seed])
    assert seeds.dtype == np.uint32 and seeds[1] == seeds[2] == seed
    got = jax.jit(lambda s, i: jax.random.key_data(lane_keys(s, i)))(
        seeds, np.array([3, index, index], np.int32))
    assert np.array_equal(np.asarray(got)[1], want)
    assert np.array_equal(np.asarray(got)[2], want)
    if index == 0:
        # the prefill program's form: one traced scalar seed, index 0
        one = jax.jit(lambda s: jax.random.key_data(lane_keys(s[None])))(
            request_seeds(seed)[()])
        assert np.array_equal(np.asarray(one)[0], want)
    # typed keys (what the benchmark's check still holds) give the seed back
    assert request_seeds(jax.random.key(seed)) == seed


def test_programs_sample_as_under_eagerly_folded_keys(cfg_params):
    """Sampled lanes: the programs fed seeds and indices emit what the same
    programs emit when handed ``fold_in(key(seed), index)`` folded eagerly,
    the parent's way (typed keys pass through ``lane_keys`` as they are)."""
    cfg, params = cfg_params
    from mingpt_distributed_tpu.serving import DecodeEngine

    seeds, prompt = [2**31, 5], [1, 2, 3, 4]
    by_seed, by_key = (DecodeEngine(params, cfg, 2) for _ in range(2))
    vec = lambda x, dt: np.full(2, x, dt)
    toks = {0: [], 1: []}
    for which, eng in enumerate((by_seed, by_key)):
        first = []
        for slot, seed in enumerate(seeds):
            if which == 0:
                tok, _ = eng.prefill_chunk_call(
                    slot, prompt, 0, 1.3, None, None, True, seed)
            else:
                padded = np.zeros(eng.bucket_for(len(prompt)), np.int32)
                padded[:len(prompt)] = prompt
                tok, eng.pool.cache = eng._prefill_jit(
                    eng.params, eng.pool.cache, padded, np.int32(len(prompt)),
                    np.int32(0), np.int32(slot), np.float32(1.3), np.int32(0),
                    np.float32(1.0), np.bool_(True),
                    jax.random.fold_in(jax.random.key(seed), 0))
            first.append(int(tok))
        cur = np.asarray(first, np.int32)
        toks[which].append(cur.tolist())
        for i in range(1, 6):
            pos = vec(len(prompt) + i - 1, np.int32)
            if which == 0:
                cur = eng.decode_step(
                    cur, pos, vec(1.3, np.float32), vec(0, np.int32),
                    vec(1.0, np.float32), vec(True, bool),
                    np.asarray(seeds), vec(i, np.int32))
            else:
                keys = jnp.stack([jax.random.fold_in(jax.random.key(s), i)
                                  for s in seeds])
                cur, eng.pool.cache = eng._decode_jit(
                    eng.params, eng.pool.cache, cur, pos,
                    vec(1.3, np.float32), vec(0, np.int32),
                    vec(1.0, np.float32), vec(True, bool), keys)
                cur = np.asarray(cur)
            toks[which].append(cur.tolist())
    assert toks[0] == toks[1]
    # and the two lanes, same prompt and parameters, differ by seed alone
    assert [t[0] for t in toks[0]] != [t[1] for t in toks[0]]


def test_mixed_lanes_and_a_reused_slot_keep_each_requests_seed(cfg_params):
    """Greedy and sampled lanes in one round, and a slot that a second
    sampled request with another seed takes over: every greedy request is
    solo ``generate()``'s, every sampled one is what it is alone in a fresh
    server (no stale seed in a released slot), and the seed vector holds
    exactly the live requests' seeds."""
    cfg, params = cfg_params
    sampled = dict(prompt=PROMPTS[1], do_sample=True, temperature=1.4,
                   top_k=20)

    def alone(**kw):
        srv = InferenceServer(params, cfg, n_slots=2)
        h = srv.submit(Request(**kw))
        srv.run_until_drained(max_steps=100)
        return h.tokens

    server = InferenceServer(params, cfg, n_slots=2)
    g1 = server.submit(Request(prompt=PROMPTS[0], max_new_tokens=14))
    s1 = server.submit(Request(max_new_tokens=4, seed=2**32 + 5, **sampled))
    s2 = server.submit(Request(max_new_tokens=6, seed=9, **sampled))
    server.step()
    assert server.slots.seeds.tolist() == [0, 5]  # the low 32 bits
    while not s1.finished:
        server.step()
    while s2.slot is None:
        server.step()
    # s2 took s1's slot while g1 still decodes beside it
    assert s2.slot == 1 and not g1.finished
    assert server.slots.seeds.tolist() == [0, 9]
    g2 = server.submit(Request(prompt=PROMPTS[2], max_new_tokens=5))
    server.run_until_drained(max_steps=100)
    assert g1.tokens == solo_greedy(params, cfg, PROMPTS[0], 14)
    assert g2.tokens == solo_greedy(params, cfg, PROMPTS[2], 5)
    assert s1.tokens == alone(max_new_tokens=4, seed=5, **sampled)
    assert s2.tokens == alone(max_new_tokens=6, seed=9, **sampled)
    assert s2.tokens[:4] != s1.tokens
    assert not server.slots.seeds.any()
    assert not any(isinstance(v, jax.Array)
                   for v in vars(server.slots).values())


def test_a_round_dispatches_nothing_but_its_programs(cfg_params, monkeypatch):
    """After warmup the plain server's admit -> prefill -> decode path makes
    no eager ``jax.random.fold_in`` / ``jax.random.key`` / ``jnp.stack``
    call: a round is one call of the decode program (plus one prefill
    program a chunk), and nothing compiles."""
    cfg, params = cfg_params
    server = InferenceServer(params, cfg, n_slots=4, warmup=True,
                             prefill_buckets=(8, 32))
    counts = server.compile_counts()
    assert counts["decode"] == 1 and counts["prefill"] <= 2
    handles = [server.submit(Request(
        prompt=p, max_new_tokens=9, do_sample=bool(i % 2), seed=i))
        for i, p in enumerate(PROMPTS[:3])]
    server.step()
    assert len(server.slots.decoding_slots()) == 3

    def refuse(name):
        def raiser(*a, **k):
            raise AssertionError(f"eager {name} on the serving path")
        return raiser

    monkeypatch.setattr(jax.random, "fold_in", refuse("jax.random.fold_in"))
    monkeypatch.setattr(jax.random, "key", refuse("jax.random.key"))
    monkeypatch.setattr(jnp, "stack", refuse("jnp.stack"))
    decode_calls = []
    real_decode = server.engine.launch_decode
    monkeypatch.setattr(
        server.engine, "launch_decode",
        lambda *a, **k: decode_calls.append(1) or real_decode(*a, **k))
    rounds = 0
    for _ in range(3):
        server.step()
        rounds += 1
    # an admission and its prefill in between, then to the end
    late = server.submit(Request(prompt=PROMPTS[3], max_new_tokens=3,
                                 do_sample=True, seed=2**31))
    while server.step():
        rounds += 1
    rounds += 1
    monkeypatch.undo()
    assert all(h.finished for h in handles) and late.finished
    # every launch is synced, and a round syncs one step at most
    assert 0 < len(decode_calls) <= rounds
    assert server.compile_counts() == counts
    assert server.watchdog.recompiles == 0


def test_long_prompt_cropped_and_max_new_clamped(cfg_params):
    cfg, params = cfg_params
    server = InferenceServer(params, cfg, n_slots=1)
    long_prompt = list(range(1, 41))  # 40 > block_size=32
    h = server.submit(Request(prompt=long_prompt, max_new_tokens=50))
    assert len(h.prompt_used) == cfg.block_size
    # decode positions must stay inside the window
    assert h.max_new_effective == 1
    server.run_until_drained(max_steps=10)
    assert h.finished and len(h.tokens) == 1


def test_metrics_counters_add_up(cfg_params):
    cfg, params = cfg_params
    server = InferenceServer(params, cfg, n_slots=2)
    streamed = []
    server.on_token = lambda h, t: streamed.append((h.request_id, t))
    handles = server.generate_batch(
        [Request(prompt=p, max_new_tokens=5) for p in PROMPTS[:3]])
    m = server.summary()
    total = sum(len(h.tokens) for h in handles)
    assert m["requests_submitted"] == 3
    assert m["requests_completed"] == 3
    assert m["prefills"] == 3
    assert m["tokens_generated"] == total == 15
    assert len(streamed) == total  # every token streamed exactly once
    assert m["ttft_mean_s"] is not None and m["ttft_mean_s"] >= 0
    assert m["itl_mean_s"] is not None and m["itl_mean_s"] >= 0
    assert m["slot_utilization"] is not None and 0 < m["slot_utilization"] <= 1
    assert m["queue_depth"] == 0 and m["slots_active"] == 0


def test_request_validation(cfg_params):
    cfg, params = cfg_params
    server = InferenceServer(params, cfg, n_slots=1)
    with pytest.raises(ValueError):
        server.submit(Request(prompt=[], max_new_tokens=3))
    with pytest.raises(ValueError):
        server.submit(Request(prompt=[1], max_new_tokens=0))


# ---------------------------------------------------------------------------
# robustness: bounded queue, deadlines, callback isolation (ISSUE 2)
# ---------------------------------------------------------------------------


def test_bounded_queue_rejects_beyond_limit(cfg_params):
    """max_queue bounds WAITING requests; over-limit submissions raise
    QueueFullError cleanly and are counted, already-queued work drains."""
    cfg, params = cfg_params
    server = InferenceServer(params, cfg, n_slots=1, max_queue=2)
    h_ok = [server.submit(Request(prompt=p, max_new_tokens=3))
            for p in PROMPTS[:2]]
    with pytest.raises(QueueFullError):
        server.submit(Request(prompt=PROMPTS[2], max_new_tokens=3))
    assert server.metrics.requests_rejected == 1
    assert server.metrics.requests_submitted == 2
    server.run_until_drained(max_steps=100)
    for h in h_ok:
        assert h.finished and h.finish_reason == "length"
    # capacity freed: submissions are accepted again
    h3 = server.submit(Request(prompt=PROMPTS[2], max_new_tokens=3))
    server.run_until_drained(max_steps=100)
    assert h3.finished


def test_deadline_expires_queued_request_without_taking_a_slot(cfg_params):
    cfg, params = cfg_params
    t = {"now": 0.0}
    server = InferenceServer(params, cfg, n_slots=1, clock=lambda: t["now"])
    h_busy = server.submit(Request(prompt=PROMPTS[0], max_new_tokens=8))
    h_doomed = server.submit(
        Request(prompt=PROMPTS[1], max_new_tokens=8, deadline_s=5.0))
    server.step()  # h_busy admitted, h_doomed queued
    assert h_busy.slot is not None and not h_doomed.finished
    t["now"] = 6.0  # past h_doomed's deadline while it still waits
    server.step()
    assert h_doomed.finished and h_doomed.finish_reason == "deadline"
    assert h_doomed.tokens == []  # expired before ever taking a slot
    server.run_until_drained(max_steps=100)
    assert h_busy.finish_reason == "length"
    assert server.metrics.requests_expired == 1


def test_deadline_frees_slot_of_abandoned_mid_decode_request(cfg_params):
    """An in-flight request past its deadline must release its KV slot at
    the next step boundary — an abandoned caller can't pin a slot."""
    cfg, params = cfg_params
    t = {"now": 0.0}
    server = InferenceServer(params, cfg, n_slots=1, clock=lambda: t["now"],
                             default_deadline_s=10.0)
    h = server.submit(Request(prompt=PROMPTS[0], max_new_tokens=1000))
    server.step()
    server.step()
    assert not h.finished and h.slot is not None
    t["now"] = 11.0
    server.step()
    assert h.finished and h.finish_reason == "deadline"
    assert h.slot is None and server.engine.pool.free_count == 1
    # the freed slot is immediately reusable, decode state intact
    h2 = server.submit(Request(prompt=PROMPTS[1], max_new_tokens=4,
                               deadline_s=100.0))
    server.run_until_drained(max_steps=100)
    assert h2.finish_reason == "length"
    assert h2.tokens == solo_greedy(params, cfg, PROMPTS[1], 4)


def test_raising_callback_frees_slot_and_server_keeps_serving(cfg_params):
    cfg, params = cfg_params
    calls = {"n": 0}

    def bad_cb(handle, tok):
        calls["n"] += 1
        raise RuntimeError("consumer went away")

    server = InferenceServer(params, cfg, n_slots=2, on_token=bad_cb)
    h_bad = server.submit(Request(prompt=PROMPTS[0], max_new_tokens=8))
    server.step()  # prefill emits the first token -> callback raises
    assert h_bad.finished and h_bad.finish_reason == "error"
    assert isinstance(h_bad.error, RuntimeError)
    assert server.engine.pool.free_count == 2  # slot released, not leaked
    assert server.metrics.requests_failed == 1
    # server survives: a well-behaved request still decodes to parity
    server.on_token = None
    h_ok = server.submit(Request(prompt=PROMPTS[1], max_new_tokens=6))
    server.run_until_drained(max_steps=100)
    assert h_ok.tokens == solo_greedy(params, cfg, PROMPTS[1], 6)


# ---------------------------------------------------------------------------
# prefill overhaul (ISSUE 3): bucket ladder, chunked prefill, prefix reuse
# ---------------------------------------------------------------------------


MIXED_PROMPTS = [
    list(range(1, 4)),                     # 3 tokens  -> bucket 4
    list(range(5, 12)),                    # 7 tokens  -> bucket 8
    list(range(2, 15)),                    # 13 tokens -> bucket 16
    list(range(3, 25)),                    # 22 tokens -> bucket 32
    [9, 8, 7, 6, 5],                       # 5 tokens  -> bucket 8
    list(range(10, 40)),                   # 30 tokens -> bucket 32
]


def test_bucket_ladder_trace_count_bounded_with_warmup(cfg_params):
    """The acceptance trace-count assert: warmup pre-traces exactly the
    ladder, admitting prompts of mixed lengths compiles nothing further
    (<= ladder-size prefill programs + 1 decode for the server's
    lifetime), every greedy output stays solo-exact, and short prompts
    are forwarded at their bucket length, not block_size."""
    cfg, params = cfg_params
    buckets = (4, 8, 16, 32)
    server = InferenceServer(params, cfg, n_slots=2, prefill_buckets=buckets,
                             warmup=True)
    assert server.engine.buckets == buckets
    counts = server.compile_counts()
    assert counts == {"prefill": len(buckets), "decode": 1,
                      "prefix_load": 0, "prefix_save": 0}
    # cap max_new so prompt+new fits the window (the server has no
    # sliding-window decode path to compare against)
    n_for = {id(p): min(5, cfg.block_size - len(p)) for p in MIXED_PROMPTS}
    handles = server.generate_batch(
        [Request(prompt=p, max_new_tokens=n_for[id(p)])
         for p in MIXED_PROMPTS])
    for p, h in zip(MIXED_PROMPTS, handles):
        assert h.tokens == solo_greedy(params, cfg, p, n_for[id(p)]), \
            h.request_id
    # a 3-token prompt paid a 4-token forward, not a 32-token one
    hist = server.metrics.bucket_histogram
    assert hist.get(4) and hist.get(32)
    # warmup saw every shape: serving the whole mix compiled nothing new
    assert server.compile_counts() == counts


def test_recompile_watchdog_quiet_after_warmup(cfg_params):
    """ISSUE 5 acceptance: with warmup the watchdog arms at construction
    and serving a mixed-length batch registers ZERO recompiles — the
    machine-checked version of the compile_counts equality above."""
    cfg, params = cfg_params
    server = InferenceServer(params, cfg, n_slots=2,
                             prefill_buckets=(4, 8, 16, 32), warmup=True)
    assert server.watchdog.armed
    n_for = {id(p): min(4, cfg.block_size - len(p)) for p in MIXED_PROMPTS}
    handles = server.generate_batch(
        [Request(prompt=p, max_new_tokens=n_for[id(p)])
         for p in MIXED_PROMPTS])
    assert all(h.finished for h in handles)
    assert server.watchdog.recompiles == 0


def test_recompile_watchdog_counts_cold_traces(cfg_params):
    """Armed BEFORE any trace exists (no warmup), the first request's
    prefill+decode compilations surface as recompiles, labeled by
    program family in the shared registry counter."""
    from mingpt_distributed_tpu.telemetry import SpanTracer

    cfg, params = cfg_params
    tracer = SpanTracer()
    server = InferenceServer(params, cfg, n_slots=2, tracer=tracer)
    assert not server.watchdog.armed
    server.watchdog.arm()
    server.submit(Request(prompt=PROMPTS[0], max_new_tokens=4))
    server.run_until_drained(max_steps=50)
    # cold start traced prefill once and decode once, each counted once
    assert server.watchdog.recompiles == 2
    fam = server.metrics.registry.counter(
        "mingpt_recompiles_total", labels=("family",))
    by_family = {labels["family"]: child.value
                 for labels, child in fam.children() if child.value}
    assert by_family == {"prefill": 1.0, "decode": 1.0}
    # the firing is mirrored into the span tracer as point events
    fired = {r["family"] for r in tracer.records()
             if r.get("kind") == "event" and r.get("name") == "recompile"}
    assert fired == {"prefill", "decode"}


def test_chunked_prefill_staggered_admission_parity(cfg_params):
    """A long prompt admitted mid-decode prefills in chunks across
    scheduler rounds while the co-tenant keeps decoding — the decode
    batch advances one token EVERY chunked round (inter-token latency
    bounded by one chunk, not one prompt) and both outputs stay
    token-identical to solo generate()."""
    cfg, params = cfg_params
    server = InferenceServer(params, cfg, n_slots=2,
                             prefill_buckets=(4, 8, 16, 32), prefill_chunk=8)
    short = PROMPTS[0]
    long_p = MIXED_PROMPTS[5]  # 30 tokens -> 4 chunks of <= 8
    h1 = server.submit(Request(prompt=short, max_new_tokens=10))
    server.step()
    server.step()  # h1 mid-decode
    h2 = server.submit(Request(prompt=long_p, max_new_tokens=2))
    progress = []
    while not h2.tokens and len(progress) < 50:  # until h2's first token
        before = len(h1.tokens)
        server.step()
        progress.append(len(h1.tokens) - before)
    # every admission/chunk round also advanced the decoding co-tenant
    assert len(progress) >= 4 and all(d == 1 for d in progress)
    server.run_until_drained(max_steps=100)
    assert h2.tokens == solo_greedy(params, cfg, long_p, 2)
    assert h1.tokens == solo_greedy(params, cfg, short, 10)
    assert server.metrics.prefill_chunks >= 4 + 1


def test_prefix_reuse_hits_and_stays_token_identical(cfg_params):
    """The system-prompt case: a second request sharing a >= bucket-sized
    prefix copies those KV rows (no recompute) and prefills only the
    tail; its greedy output must stay solo-exact. Also the edge where the
    hit covers everything but one token — the tail must still be
    prefilled because the first sampled token needs the last prompt
    position's logits."""
    cfg, params = cfg_params
    system = list(range(1, 17))            # 16 shared tokens
    a = system + [20, 21, 22]
    b = system + [30, 31]
    server = InferenceServer(params, cfg, n_slots=1,
                             prefill_buckets=(4, 8, 16, 32),
                             prefix_cache_mb=8.0)
    ha = server.submit(Request(prompt=a, max_new_tokens=4))
    server.run_until_drained(max_steps=100)
    tokens_after_a = server.metrics.prefill_tokens
    hb = server.submit(Request(prompt=b, max_new_tokens=4))
    server.run_until_drained(max_steps=100)
    assert ha.tokens == solo_greedy(params, cfg, a, 4)
    assert hb.tokens == solo_greedy(params, cfg, b, 4)
    m = server.metrics
    assert m.prefix_lookups == 2 and m.prefix_hits == 1
    assert m.prefix_rows_reused == 16 == hb.prefix_rows
    # b's admission forwarded only its tail (2 tokens past the hit)
    assert m.prefill_tokens - tokens_after_a == len(b) - 16
    assert 0 < m.prefix_hit_rate < 1
    # one-token tail: prompt == stored prefix + 1 token
    hc = server.generate_batch(
        [Request(prompt=system + [41], max_new_tokens=3)])[0]
    assert hc.prefix_rows == 16
    assert hc.tokens == solo_greedy(params, cfg, system + [41], 3)


def test_all_three_mechanisms_combined_parity(cfg_params):
    """Acceptance: bucketing + chunking + prefix reuse enabled at once,
    staggered admissions, mixed greedy/sampled tenants — greedy outputs
    token-identical to solo generate(), trace counts bounded."""
    cfg, params = cfg_params
    buckets = (4, 8, 16, 32)
    server = InferenceServer(params, cfg, n_slots=2, prefill_buckets=buckets,
                             prefill_chunk=8, prefix_cache_mb=8.0,
                             warmup=False)
    shared = list(range(3, 20))  # 17 tokens: 16 storable
    reqs = [
        Request(prompt=shared + [25, 26], max_new_tokens=6),
        Request(prompt=PROMPTS[0], max_new_tokens=8, do_sample=True,
                temperature=1.3, top_k=9, seed=5),
        Request(prompt=shared + [27], max_new_tokens=5),
        Request(prompt=MIXED_PROMPTS[5], max_new_tokens=2),
    ]
    handles = []
    for r in reqs:
        handles.append(server.submit(r))
        server.step()  # staggered: each arrival lands mid-flight
    server.run_until_drained(max_steps=200)
    for r, h in zip(reqs, handles):
        if not r.do_sample:
            assert h.tokens == solo_greedy(
                params, cfg, list(r.prompt), r.max_new_tokens), h.request_id
    assert server.metrics.prefix_hits >= 1
    counts = server.compile_counts()
    assert counts["decode"] == 1
    assert counts["prefill"] <= len(server.engine.buckets) + 1
    assert counts["prefix_load"] <= len(buckets)
    assert counts["prefix_save"] <= len(buckets)


def test_final_chunk_shift_back_at_window_edge(cfg_params):
    """When the final chunk's bucket would overrun block_size, the
    scheduler shifts the chunk window back and re-prefills the overlap —
    output must stay exact. Ladder (5, 32) + chunk 5 on a 32-token
    prompt: the last chunk (2 tokens at offset 30) pads to bucket 5,
    which overruns the window (35 > 32) and must shift back to 27."""
    cfg, params = cfg_params
    prompt = list(range(1, 33))  # 32 tokens == block_size
    server = InferenceServer(params, cfg, n_slots=1,
                             prefill_buckets=(5, 32), prefill_chunk=5)
    h = server.submit(Request(prompt=prompt, max_new_tokens=1))
    server.run_until_drained(max_steps=50)
    assert h.tokens == solo_greedy(params, cfg, prompt, 1)


def test_prefix_store_lru_and_byte_bounds(cfg_params):
    """PrefixKVStore unit semantics: proper-prefix lookup, longest-match
    wins, LRU eviction under the byte budget, oversized entries refused."""
    from mingpt_distributed_tpu.serving import PrefixKVStore

    def entry(rows):
        a = jnp.zeros((rows,), jnp.float32)
        return {"k": a, "v": a}  # 8 bytes per row total

    store = PrefixKVStore(capacity_bytes=80)  # room for 10 rows
    assert store.insert((1, 2, 3), entry(3))          # 24 bytes
    assert store.insert((1, 2, 3, 4, 5), entry(5))    # +40 = 64
    # longest proper prefix wins
    rows, _ = store.lookup((1, 2, 3, 4, 5, 6))
    assert rows == 5
    # an exact-length match is NOT a proper prefix of itself (a hit must
    # leave >= 1 tail token): only the shorter entry qualifies
    rows, _ = store.lookup((1, 2, 3, 4, 5))
    assert rows == 3
    assert store.lookup((9, 9, 9)) is None
    # inserting 32 more bytes exceeds the 80-byte budget -> evicts the
    # least recently used entry, which is (1,2,3,4,5)... except both
    # lookups above refreshed it and (1,2,3) last, so (1,2,3,4,5) goes
    assert store.insert((7, 8, 9, 10), entry(4))
    assert not store.contains((1, 2, 3, 4, 5))
    assert store.contains((1, 2, 3))
    # an entry bigger than the whole budget is refused outright
    assert not store.insert((5,) * 20, entry(20))
    assert store.used_bytes <= store.capacity_bytes


def test_prefill_flops_scale_with_bucket(cfg_params):
    """Acceptance: admission cost tracks prompt length. The compiled
    small-bucket prefill must cost a fraction of the full-window program
    (cost_analysis flops), which is also exactly what a prefix-cache hit
    saves — the tail-only prefill runs the small program."""
    cfg, params = cfg_params
    from mingpt_distributed_tpu.serving import DecodeEngine

    engine = DecodeEngine(params, cfg, n_slots=1, prefill_buckets=(4, 32))

    def prefill_flops(bucket):
        args = (
            params, engine.pool.cache,
            jnp.zeros(bucket, jnp.int32), np.int32(1), np.int32(0),
            np.int32(0), np.float32(1.0), np.int32(0), np.float32(1.0),
            np.bool_(False), np.uint32(0),
        )
        compiled = engine._prefill_jit.lower(*args).compile()
        cost = compiled.cost_analysis()
        if isinstance(cost, list):  # older jaxlib returns [dict]
            cost = cost[0]
        return cost.get("flops")

    small, full = prefill_flops(4), prefill_flops(32)
    if small is None or full is None:
        pytest.skip("backend reports no cost_analysis flops")
    # 4-token bucket does a 4-row forward; 32-token does 32 rows + the
    # quadratic attention term — demand at least the linear-term gap
    assert small < full / 4


def test_llama_mode_serving_parity(cfg_params):
    """RoPE/SwiGLU/RMSNorm/GQA config through the same server: the engine
    reuses generate()'s cached block, so every architecture knob that
    decodes solo must also serve."""
    cfg = GPTConfig.make(
        n_layer=2, n_head=2, n_embd=32, vocab_size=50, block_size=32,
        embd_pdrop=0.0, resid_pdrop=0.0, attn_pdrop=0.0, dtype="float32",
        rope=True, swiglu=True, rmsnorm=True, n_kv_head=1, tie_weights=True,
    )
    params = gpt.init(jax.random.key(0), cfg)
    server = InferenceServer(params, cfg, n_slots=2)
    handles = server.generate_batch(
        [Request(prompt=p, max_new_tokens=6) for p in PROMPTS[:3]])
    for p, h in zip(PROMPTS[:3], handles):
        assert h.tokens == solo_greedy(params, cfg, p, 6)


# ---------------------------------------------------------------------------
# hardened validation, typed backpressure, mid-prefill expiry (ISSUE 6)
# ---------------------------------------------------------------------------


def test_validation_rejects_malformed_sampling_params(cfg_params):
    """Malformed requests bounce at the door with ValueError — a NaN
    temperature must never reach the compiled sampler, where it would
    silently poison its slot's logits."""
    cfg, params = cfg_params
    server = InferenceServer(params, cfg, n_slots=1)
    bad = [
        Request(prompt=[1], max_new_tokens=3, temperature=float("nan")),
        Request(prompt=[1], max_new_tokens=3, temperature=float("inf")),
        Request(prompt=[1], max_new_tokens=3, temperature=-0.5),
        Request(prompt=[1], max_new_tokens=3, top_k=0),
        Request(prompt=[1], max_new_tokens=3, top_p=0.0),
        Request(prompt=[1], max_new_tokens=3, top_p=1.5),
        Request(prompt=[1], max_new_tokens=3, top_p=float("nan")),
        Request(prompt=[1], max_new_tokens=-2),
        Request(prompt=[1], max_new_tokens=3, deadline_s=-1.0),
        Request(prompt=[1], max_new_tokens=3, deadline_s=float("inf")),
    ]
    for r in bad:
        with pytest.raises(ValueError):
            server.submit(r)
    assert server.metrics.requests_submitted == 0  # none were accepted


def test_strict_window_rejects_instead_of_cropping(cfg_params):
    """strict_window=True turns the documented crop/clamp semantics into
    up-front rejection; the default server keeps cropping (covered by
    test_long_prompt_cropped_and_max_new_clamped)."""
    cfg, params = cfg_params
    strict = InferenceServer(params, cfg, n_slots=1, strict_window=True)
    with pytest.raises(ValueError):  # prompt longer than the window
        strict.submit(Request(prompt=list(range(1, 41)), max_new_tokens=2))
    with pytest.raises(ValueError):  # 30 + 4 - 1 > block_size=32
        strict.submit(Request(prompt=list(range(1, 31)), max_new_tokens=4))
    # an in-window request passes validation and still has full parity
    h = strict.submit(Request(prompt=PROMPTS[0], max_new_tokens=4))
    strict.run_until_drained(max_steps=100)
    assert h.tokens == solo_greedy(params, cfg, PROMPTS[0], 4)


def test_queue_full_error_carries_backpressure_payload(cfg_params):
    """QueueFullError is typed backpressure: it reports the observed
    queue depth and a suggested retry-after, and the rejection lands in
    mingpt_serving_rejected_total{reason="queue_full"}."""
    cfg, params = cfg_params
    server = InferenceServer(params, cfg, n_slots=1, max_queue=1)
    server.submit(Request(prompt=PROMPTS[0], max_new_tokens=3))
    with pytest.raises(QueueFullError) as ei:
        server.submit(Request(prompt=PROMPTS[1], max_new_tokens=3))
    err = ei.value
    assert err.queue_depth == 1
    assert err.retry_after_s is not None and err.retry_after_s >= 0.05
    assert server.metrics.rejected_by_reason["queue_full"] == 1
    server.run_until_drained(max_steps=100)


def test_deadline_expiry_mid_prefill_frees_slot_and_counts(cfg_params):
    """A request whose deadline passes while its prompt is still
    prefilling in chunks must release its slot (and any prefix-cache
    bookkeeping) at the next round and count as expired — a slow caller
    can't strand a half-prefilled KV lane."""
    cfg, params = cfg_params
    t = {"now": 0.0}
    server = InferenceServer(params, cfg, n_slots=1, prefill_chunk=4,
                             prefix_cache_mb=1.0, clock=lambda: t["now"])
    prompt = list(range(1, 21))  # 20 tokens -> 5 chunks of 4
    h = server.submit(Request(prompt=prompt, max_new_tokens=4,
                              deadline_s=5.0))
    server.step()  # admitted + exactly one chunk: caught mid-prefill
    assert h.slot is not None and h.prefilling
    assert 0 < h.prefill_pos < len(prompt)
    assert server.engine.pool.free_count == 0
    t["now"] = 6.0
    server.step()  # deadline sweep runs before admission
    assert h.finished and h.finish_reason == "deadline"
    assert h.slot is None and not h.prefilling
    assert h.tokens == []  # never reached its first token
    assert server.engine.pool.free_count == 1  # lane fully released
    assert server.metrics.requests_expired == 1
    # the freed lane serves the next request with full parity
    h2 = server.submit(Request(prompt=PROMPTS[1], max_new_tokens=4))
    server.run_until_drained(max_steps=100)
    assert h2.tokens == solo_greedy(params, cfg, PROMPTS[1], 4)


# ---------------------------------------------------------------------------
# speculative decoding (serving/speculative.py)
# ---------------------------------------------------------------------------


def truncated_draft(params, cfg, n_layer=1):
    """A real small draft sharing the target's embeddings and head: the
    target's first ``n_layer`` stacked transformer blocks (serve.py's
    ``--draft-config self:N``)."""
    dcfg = dataclasses.replace(cfg, n_layer=n_layer)
    dparams = dict(params)
    dparams["blocks"] = jax.tree.map(lambda a: a[:n_layer], params["blocks"])
    return dparams, dcfg


def test_spec_identical_draft_parity_and_one_verify_trace(cfg_params):
    """Draft == target: every proposal is accepted, every burst is k+1
    tokens, output stays token-exact with solo generate(), and the whole
    run costs exactly ONE verify trace and ONE draft decode trace —
    speculation's compile count is O(1), not O(requests) or O(position)."""
    cfg, params = cfg_params
    server = InferenceServer(params, cfg, n_slots=3, warmup=True,
                             draft_params=params, draft_cfg=cfg, spec_k=3)
    n = 10
    h1 = server.submit(Request(prompt=PROMPTS[0], max_new_tokens=n))
    server.step()
    h2 = server.submit(Request(prompt=PROMPTS[1], max_new_tokens=n))
    server.step()  # h2 admitted while h1 is mid-burst decoding
    h3 = server.submit(Request(prompt=PROMPTS[2], max_new_tokens=n))
    server.run_until_drained(max_steps=100)
    for p, h in zip(PROMPTS[:3], (h1, h2, h3)):
        assert h.tokens == solo_greedy(params, cfg, p, n), h.request_id
        # identical draft: the target agrees with every proposal
        assert h.spec_proposed > 0
        assert h.spec_accepted == h.spec_proposed
    # every program family traced exactly once at warmup, nothing since —
    # including the spec families (verify has traced scalars for
    # offset/slot, so rounds at every position share one executable).
    # NB: the prefix-copy counts are omitted — those jits wrap bare
    # module functions, so their trace cache is shared across engine
    # instances and other tests in the session contaminate it.
    counts = server.compile_counts()
    assert set(counts) == {"prefill", "decode", "prefix_load",
                           "prefix_save", "verify", "draft_prefill",
                           "draft_decode"}
    assert counts["prefill"] == 1 and counts["decode"] == 1
    assert counts["verify"] == 1
    assert counts["draft_prefill"] == 1 and counts["draft_decode"] == 1
    assert server.watchdog.recompiles == 0
    assert server.metrics.spec_rounds > 0
    assert server.metrics.spec_accept_rate == 1.0
    assert server.metrics.spec_tokens_per_verify_mean == 4.0


def test_spec_distinct_draft_rejections_roll_back_exactly(cfg_params):
    """A genuinely weaker draft (the target's first layer only) gets
    proposals rejected; rejected cache rows roll back via the stale-row
    invariant and output is still token-exact with solo generate()."""
    cfg, params = cfg_params
    dparams, dcfg = truncated_draft(params, cfg)
    server = InferenceServer(params, cfg, n_slots=4, warmup=True,
                            draft_params=dparams, draft_cfg=dcfg, spec_k=3)
    n = 8
    handles = server.generate_batch(
        [Request(prompt=p, max_new_tokens=n) for p in PROMPTS[:4]])
    for p, h in zip(PROMPTS[:4], handles):
        assert h.tokens == solo_greedy(params, cfg, p, n), h.request_id
    # the 1-layer draft must actually diverge somewhere, or this test
    # proves nothing about rollback
    assert server.metrics.spec_proposed > 0
    assert server.metrics.spec_accepted < server.metrics.spec_proposed
    counts = server.compile_counts()
    assert counts["verify"] == 1 and counts["draft_decode"] == 1
    assert server.watchdog.recompiles == 0


def test_spec_eos_mid_burst_truncates_and_frees_both_pools(cfg_params):
    """EOS landing in the middle of an accepted burst: the burst tail
    after the EOS token is dropped (never streamed), the request retires
    as "eos", and BOTH the target and the mirrored draft slot free."""
    cfg, params = cfg_params
    solo = solo_greedy(params, cfg, PROMPTS[0], 12)
    # k=3 bursts emit indices 1-4, 5-8, 9-12 after the prefill token at
    # index 0: pick an eos whose FIRST occurrence is mid-burst (not the
    # last index of a burst), so retirement must truncate a burst
    idx = next(i for i in (1, 2, 3, 5, 6, 7, 9, 10, 11)
               if solo.index(solo[i]) == i)
    server = InferenceServer(params, cfg, n_slots=2, warmup=True,
                             draft_params=params, draft_cfg=cfg, spec_k=3)
    h = server.submit(Request(prompt=PROMPTS[0], max_new_tokens=12,
                              eos_id=solo[idx]))
    server.run_until_drained(max_steps=100)
    assert h.finish_reason == "eos"
    assert h.tokens == solo[:idx + 1]  # burst tail after EOS dropped
    assert server.engine.pool.free_count == 2
    assert server.spec.draft.engine.pool.free_count == 2


def test_spec_deadline_mid_burst_frees_both_pools(cfg_params):
    """A deadline crossing BETWEEN tokens of one accepted burst: the
    burst is the new round granularity, so expiry is enforced mid-burst —
    the tail is dropped, finish_reason is "deadline", and both the target
    and draft slots free in the same round."""
    cfg, params = cfg_params
    solo = solo_greedy(params, cfg, PROMPTS[0], 12)
    t = {"now": 0.0}

    def on_token(handle, tok):
        # the clock jumps past the deadline after the 3rd visible token:
        # prefill emitted index 0, so the burst of indices 1-4 is cut
        # after index 2 by the mid-burst check (the round-top sweep at
        # now=0.0 had already passed)
        if len(handle.tokens) == 3:
            t["now"] = 100.0

    server = InferenceServer(params, cfg, n_slots=2, warmup=True,
                             clock=lambda: t["now"], on_token=on_token,
                             draft_params=params, draft_cfg=cfg, spec_k=3)
    h = server.submit(Request(prompt=PROMPTS[0], max_new_tokens=12,
                              deadline_s=5.0))
    server.run_until_drained(max_steps=100)
    assert h.finish_reason == "deadline"
    assert h.tokens == solo[:3]  # mid-burst cut: indices 3-4 never emitted
    assert server.engine.pool.free_count == 2
    assert server.spec.draft.engine.pool.free_count == 2
    assert server.metrics.requests_expired == 1


def test_spec_sampled_lane_falls_back_to_plain_path(cfg_params):
    """Sampled lanes never speculate (per-token key folding must stay
    bit-identical), and they coexist with speculating greedy lanes in the
    same round — the plain step parks speculating lanes while the verify
    program is their row-writer."""
    cfg, params = cfg_params
    sampled = Request(prompt=PROMPTS[1], max_new_tokens=8, do_sample=True,
                      temperature=0.9, top_k=20, seed=7)
    plain_server = InferenceServer(params, cfg, n_slots=2)
    want = plain_server.generate_batch([dataclasses.replace(sampled)])[0]
    server = InferenceServer(params, cfg, n_slots=2, warmup=True,
                             draft_params=params, draft_cfg=cfg, spec_k=3)
    h_greedy = server.submit(Request(prompt=PROMPTS[0], max_new_tokens=8))
    h_sampled = server.submit(dataclasses.replace(sampled))
    server.run_until_drained(max_steps=100)
    assert h_greedy.tokens == solo_greedy(params, cfg, PROMPTS[0], 8)
    assert h_sampled.tokens == want.tokens  # same seed, same stream
    assert h_sampled.spec_proposed == 0  # never entered the spec path
    assert h_greedy.spec_proposed > 0


def test_spec_window_tail_falls_back_to_plain_decode(cfg_params):
    """Near the end of the cache window there is no room for k+1 verify
    rows: the lane falls back to the plain one-token step for the tail
    (the ONLY decode trace in the run) and parity still holds end-to-end."""
    cfg, params = cfg_params
    prompt = list(range(1, 26))  # positions start at 25, block_size 32
    n = 8  # exactly the clamped window: decode feeds positions 25..31
    server = InferenceServer(params, cfg, n_slots=1,
                             draft_params=params, draft_cfg=cfg, spec_k=2)
    h = server.generate_batch([Request(prompt=prompt, max_new_tokens=n)])[0]
    assert h.tokens == solo_greedy(params, cfg, prompt, n)
    # spec rounds at pos 25 and 28 (rows fit: pos+3 <= 32), plain tail at
    # pos 31 — so the decode family traced exactly once, ON DEMAND, and
    # verify stayed at one executable across offsets (prefix-copy counts
    # omitted: their jit cache is shared across engine instances)
    counts = server.compile_counts()
    assert counts["prefill"] == 1 and counts["decode"] == 1
    assert counts["verify"] == 1
    assert counts["draft_prefill"] == 1 and counts["draft_decode"] == 1
    assert 0 < h.spec_accepted <= h.spec_proposed


def test_spec_with_chunked_prefill_and_prefix_reuse(cfg_params):
    """Speculation composed with chunked prefill + shared-prefix reuse:
    the combined machinery stays token-exact and the verify family stays
    at one executable."""
    cfg, params = cfg_params
    server = InferenceServer(
        params, cfg, n_slots=2, prefill_chunk=4, prefix_cache_mb=1.0,
        prefill_buckets=(4, 8, 16, 32), warmup=True,
        draft_params=params, draft_cfg=cfg, spec_k=3)
    shared = [5, 6, 7, 8, 9, 10, 11, 12]
    prompts = [shared + [13], shared + [14], PROMPTS[0]]
    n = 6
    # stagger so the first twin's prefix is SAVED before the second's
    # admission lookup (save happens at end-of-prefill)
    h0 = server.generate_batch([Request(prompt=prompts[0],
                                        max_new_tokens=n)])[0]
    rest = server.generate_batch(
        [Request(prompt=p, max_new_tokens=n) for p in prompts[1:]])
    for p, h in zip(prompts, [h0] + rest):
        assert h.tokens == solo_greedy(params, cfg, p, n), h.request_id
    assert server.metrics.prefix_hits >= 1  # the second twin reused rows
    counts = server.compile_counts()
    assert counts["verify"] == 1 and counts["draft_decode"] == 1
    assert counts["prefill"] <= 4 and counts["draft_prefill"] <= 4
    assert server.watchdog.recompiles == 0


def test_spec_slot_mirror_breakage_fails_loudly(cfg_params):
    """The draft pool must mirror the target's slot indices 1:1; a
    drifted mirror raises instead of silently attending the wrong lane."""
    cfg, params = cfg_params
    server = InferenceServer(params, cfg, n_slots=2,
                             draft_params=params, draft_cfg=cfg, spec_k=2)
    server.spec.draft.engine.pool.allocate()  # steal draft slot 0
    with pytest.raises(RuntimeError, match="mirror"):
        server.generate_batch([Request(prompt=PROMPTS[0], max_new_tokens=2)])


def test_spec_constructor_validation(cfg_params):
    cfg, params = cfg_params
    with pytest.raises(ValueError):  # spec_k without a draft model
        InferenceServer(params, cfg, spec_k=2)
    with pytest.raises(ValueError):  # draft params without its config
        InferenceServer(params, cfg, draft_params=params, spec_k=2)
    with pytest.raises(ValueError):  # k = 0 is "off", not a tiny burst
        InferenceServer(params, cfg, draft_params=params, draft_cfg=cfg,
                        spec_k=0)
    small = dataclasses.replace(cfg, block_size=16)
    with pytest.raises(ValueError):  # draft window can't cover target's
        InferenceServer(params, cfg, draft_params=params, draft_cfg=small,
                        spec_k=2)


# ---------------------------------------------------------------------------
# the decode step reads the cache as it lies (PR 33), the lanes that hold a
# request alone, each in blocks to its own position (PR 45; the walk itself:
# tests/test_lane_walk.py)
# ---------------------------------------------------------------------------

STEP_BLOCK, STEP_ROWS = 16, 64
STEP_FORMS = {
    "per-head": dict(n_kv_head=2),
    "latent": dict(rope=True, rope_interleave=True, rmsnorm=True, swiglu=True,
                   tie_weights=False, kv_lora_rank=16, qk_nope_head_dim=8,
                   qk_rope_head_dim=4, v_head_dim=8),
}


def step_model(form, **over):
    cfg = GPTConfig.make(
        n_layer=2, n_head=4, n_embd=32, vocab_size=50, block_size=STEP_ROWS,
        embd_pdrop=0.0, resid_pdrop=0.0, attn_pdrop=0.0, dtype="float32",
        **{**STEP_FORMS[form], **over})
    return cfg, gpt.init(jax.random.key(1), cfg)


def stale_pool(cfg, lanes, seed=2):
    """A pool whose every row holds something: what a mask or a bound must
    keep out is there to be read."""
    shapes = gen.cache_leaf_shapes(cfg, lanes)
    keys = jax.random.split(jax.random.key(seed), len(shapes))
    return {n: jax.random.normal(k, shapes[n])
            for (n, k) in zip(sorted(shapes), keys)}


def lanes_step(cfg, params, cache, tokens, positions, live):
    """One decode step of all lanes as ``_decode_impl`` runs it, the logits
    kept: (logits (S, V), the cache with the lanes' rows written)."""
    positions, live = jnp.asarray(positions), jnp.asarray(live)

    @jax.jit
    def run(cache, tokens, positions, live):
        return gen._forward_cached(
            params, tokens[:, None], cache, positions, cfg,
            frontier=engine_mod.decode_frontier(positions, live))
    return run(cache, jnp.asarray(tokens), positions, live)


@pytest.mark.parametrize("furthest", [STEP_BLOCK - 1, STEP_BLOCK,
                                      STEP_BLOCK + 1, 2 * STEP_BLOCK + 5],
                         ids=["below-an-edge", "on-an-edge", "above-an-edge",
                              "two-blocks-on"])
@pytest.mark.parametrize("form", sorted(STEP_FORMS))
def test_a_step_under_a_live_mask_is_the_step_under_all_true(
        form, furthest, walk_in_blocks):
    """The walk takes a live lane in blocks to its own position whatever
    the other lanes do: its logits and written rows are those of the step
    with every lane live, bit for bit. A lane that is not live (parked, or
    standing further on) is passed by and is nobody's."""
    walk = walk_in_blocks(STEP_BLOCK)(STEP_ROWS)
    cfg, params = step_model(form)
    cache = stale_pool(cfg, 4)
    assert gen.cache_walk(cfg, cache) == walk
    tokens = np.array([3, 9, 27, 41], np.int32)
    positions = np.array([furthest, 5, STEP_ROWS - 1, 50], np.int32)
    live = np.array([True, True, False, False])
    want_rows = -(-furthest // STEP_BLOCK) * STEP_BLOCK + STEP_BLOCK
    assert engine_mod.decode_rows_read(positions, live, walk) == want_rows
    assert engine_mod.decode_rows_read(
        positions, np.ones(4, bool), walk) == want_rows + 2 * STEP_ROWS
    assert engine_mod.decode_rows_read(positions, None, walk) \
        == want_rows + 2 * STEP_ROWS
    got, got_cache = lanes_step(cfg, params, cache, tokens, positions, live)
    want, want_cache = lanes_step(cfg, params, cache, tokens, positions,
                                  np.ones(4, bool))
    np.testing.assert_array_equal(got[:2], want[:2])
    for name in ("k", "v"):
        for lane in (0, 1):
            np.testing.assert_array_equal(
                got_cache[name][:, lane], want_cache[name][:, lane])
    # the lane at 50 was passed by: it attended its own row alone
    assert not np.array_equal(got[3], want[3])


@pytest.mark.parametrize("form", sorted(STEP_FORMS))
def test_a_live_lane_at_the_last_row_attends_all_its_rows(form, walk_in_blocks):
    """A request's last step stands where free lanes are parked: liveness
    is the mask's to say, not the position's. Beside parked lanes it alone
    is read, the whole slot, and what it computes is what it computes in a
    pool of one block."""
    cfg, params = step_model(form)
    cache = stale_pool(cfg, 3)
    tokens = np.array([7, 0, 0], np.int32)
    positions = np.full(3, STEP_ROWS - 1, np.int32)
    live = np.array([True, False, False])
    one_pass, _ = lanes_step(cfg, params, cache, tokens, positions, live)
    walk = walk_in_blocks(STEP_BLOCK)(STEP_ROWS)
    assert engine_mod.decode_rows_read(positions, live, walk) == STEP_ROWS
    assert engine_mod.decode_rows_read(
        positions, np.zeros(3, bool), walk) == 0
    walked, _ = lanes_step(cfg, params, cache, tokens, positions, live)
    np.testing.assert_allclose(walked[0], one_pass[0], rtol=2e-5, atol=2e-6)
    # a stale row inside the mask does move the lane: all 63 are attended
    moved = {n: a.at[:, 0, STEP_ROWS - 2].add(1.0) for n, a in cache.items()}
    other, _ = lanes_step(cfg, params, moved, tokens, positions, live)
    assert np.abs(np.asarray(other[0] - walked[0])).max() > 1e-4


@pytest.mark.parametrize("form", sorted(STEP_FORMS))
def test_a_lane_that_is_not_live_changes_no_live_lane(form, walk_in_blocks):
    walk_in_blocks(STEP_BLOCK)
    cfg, params = step_model(form)
    cache = stale_pool(cfg, 3)
    live = np.array([True, True, False])
    base, _ = lanes_step(cfg, params, cache, [3, 9, 0],
                         [20, 6, STEP_ROWS - 1], live)
    for token, position in ((0, 45), (31, 2), (31, STEP_ROWS - 1)):
        got, _ = lanes_step(cfg, params, cache, [3, 9, token],
                            [20, 6, position], live)
        np.testing.assert_array_equal(got[:2], base[:2])


@pytest.mark.parametrize("case", [
    dict(), dict(window=5), dict(logit_softcap=3.0),
    dict(window=7, logit_softcap=2.0)],
    ids=["plain", "window", "softcap", "window-and-softcap"])
@pytest.mark.parametrize("kv_heads", [4, 2], ids=["mha", "gqa"])
@pytest.mark.parametrize("walk", [False, True], ids=["one-pass", "walked"])
def test_the_two_part_step_is_the_laid_over_step(case, kv_heads, walk,
                                                 walk_in_blocks):
    """``causal_attend_step`` against what it replaced: the new rows laid
    over the slice (``_lay_rows_over``) and ``causal_attention`` under a
    position a lane. Float32 rounding apart: the same sums in another
    order."""
    keys = jax.random.split(jax.random.key(4), 5)
    lanes, rows, heads, size = 3, 32, 4, 8
    q = jax.random.normal(keys[0], (lanes, 1, heads, size))
    k_cache, v_cache = (jax.random.normal(k, (2, lanes, rows, kv_heads, size))
                        for k in keys[1:3])
    k_new, v_new = (jax.random.normal(k, (lanes, 1, kv_heads, size))
                    for k in keys[3:5])
    positions = jnp.array([0, 13, rows - 1])
    # walked, or by the rule itself: so small a house is read in one pass
    walk = walk_in_blocks(8)(rows) if walk else attn_ops.step_walk(
        (k_cache.shape, v_cache.shape), 4)
    assert walk.block == (8 if walk.row_bytes > 1 << 30 else 0)
    got = attn_ops.causal_attend_step(
        q, k_cache, v_cache, 1, k_new, v_new, positions, walk, **case)
    want = attn_ops.causal_attention(
        q, gen._lay_rows_over(k_cache[1], k_new, positions),
        gen._lay_rows_over(v_cache[1], v_new, positions),
        kv_offset=positions, **case)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-6)


def walked_server(form, walk_in_blocks, **kwargs):
    walk_in_blocks(STEP_BLOCK)
    cfg, params = step_model(form)
    return cfg, params, InferenceServer(
        params, cfg, prefill_buckets=(8, 32), **kwargs)


@pytest.mark.parametrize("form", sorted(STEP_FORMS))
def test_one_decode_program_whatever_the_frontier(form, walk_in_blocks):
    """``live`` is always an argument of the one decode program: rounds
    that walk other lanes to other blocks, the warm-up's round with no
    lane live and a caller that names no mask all run the same executable,
    and the tokens are solo ``generate``'s."""
    cfg, params, server = walked_server(
        form, walk_in_blocks, n_slots=3, warmup=True, recompile_fail=True)
    before = server.compile_counts()
    assert before["decode"] == 1
    prompts = [list(range(1, 4)), list(range(5, 25)), list(range(9, 42))]
    budgets = [12, 6, 3]    # the longest leaves first, then the next
    handles = [server.submit(Request(prompt=p, max_new_tokens=n))
               for p, n in zip(prompts, budgets)]
    seen = set()
    while server.step():
        st = server.slots
        active = st.decoding_slots()
        if active:
            seen.add(int(engine_mod.decode_rows_read(
                st.positions, np.isin(np.arange(3), active),
                server.engine.walk)))
    # three lanes in the first, second and third block; then two; then
    # the first alone, which ends in its own first block
    assert seen == {6 * STEP_BLOCK, 3 * STEP_BLOCK, STEP_BLOCK}
    for h, p, n in zip(handles, prompts, budgets):
        assert h.tokens == solo_greedy(params, cfg, p, n)
    s = server.engine.n_slots
    server.engine.decode_step(
        np.zeros(s, np.int32), np.full(s, STEP_ROWS - 1, np.int32),
        np.ones(s, np.float32), np.zeros(s, np.int32),
        np.ones(s, np.float32), np.zeros(s, bool), np.zeros(s, np.uint32))
    assert server.compile_counts() == before
    fam = server.metrics.registry.counter(
        "mingpt_recompiles_total", labels=("family",))
    assert sum(child.value for _, child in fam.children()) == 0


def test_the_decode_rows_counters_count_what_the_program_reads(
        walk_in_blocks):
    """``decode_rows_read`` over ``decode_rows_reserved``: equal where the
    slices are not walked (a pool of a few KB is read in one pass, and two
    heads of 8 an axis entry each lie positions minor on the chip besides:
    every step reads every slot whole), a known fraction
    where they are: one request in two slots that never leaves the first
    of four blocks reads a quarter of its slot and nothing of the other."""
    cfg, params = step_model("per-head")
    whole = InferenceServer(params, cfg, n_slots=2)
    whole.generate_batch([Request(prompt=[1, 2, 3], max_new_tokens=5)])
    got = whole.summary()
    assert got["decode_rows_read"] == got["decode_rows_reserved"] \
        == 4 * 2 * STEP_ROWS
    _, _, server = walked_server("per-head", walk_in_blocks, n_slots=2,
                                 warmup=True)
    assert server.summary()["decode_rows_reserved"] == 0  # the warm-up's
    server.generate_batch([Request(prompt=[1, 2, 3], max_new_tokens=5)])
    got = server.summary()
    assert got["decode_rows_reserved"] == 4 * 2 * STEP_ROWS
    assert got["decode_rows_read"] == 4 * STEP_BLOCK
    # a second request that stands in the third block: three quarters of
    # its slot
    server.generate_batch([Request(prompt=list(range(1, 36)),
                                   max_new_tokens=3)])
    after = server.summary()
    assert after["decode_rows_read"] - got["decode_rows_read"] \
        == 2 * 3 * STEP_BLOCK
    from mingpt_distributed_tpu.telemetry.export import render_prometheus
    text = render_prometheus(server.metrics.registry)
    assert f"mingpt_serve_decode_rows_read_total {after['decode_rows_read']}" \
        in text
    assert "mingpt_serve_decode_rows_reserved_total " \
        f"{after['decode_rows_reserved']}" in text
