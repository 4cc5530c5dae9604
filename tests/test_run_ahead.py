"""The decode round one step ahead (PR 43): ``InferenceServer.step`` launches
step N+1 before it waits for step N's tokens, which the program takes on the
device (``engine._decode_impl``: ``prev_tokens``, ``from_prev``). Held here,
on the CPU at tiny sizes: every request gets the tokens of the synchronous
order, whatever stops it and whoever takes its slot next; the order itself
(when a round runs ahead, when it syncs first) follows the round's own state;
and there is still one decode program, one trace.

The synchronous order is the same server with launching ahead forbidden by
its own rule: ``InferenceServer._may_launch`` answered by a double that
allows a launch only with nothing in flight. It is no option of the server.
"""

import functools

import jax
import numpy as np
import pytest

from mingpt_distributed_tpu.config import MODEL_PRESETS, GPTConfig
from mingpt_distributed_tpu.models import generate as gen
from mingpt_distributed_tpu.models import gpt
from mingpt_distributed_tpu.serving import InferenceServer, Request
from mingpt_distributed_tpu.serving import engine as engine_mod
from mingpt_distributed_tpu.serving.engine import DecodeEngine

CONFIGS = {
    "dense": dict(
        n_layer=2, n_head=2, n_embd=32, vocab_size=50, block_size=32,
        embd_pdrop=0.0, resid_pdrop=0.0, attn_pdrop=0.0, dtype="float32"),
    # lightning linear attention (a state a slot) beside block-sparse layers
    "hybrid": dict(MODEL_PRESETS["minicpm-sala-tiny"], vocab_size=50,
                   block_size=64),
    # a latent cache, a dense layer before sigmoid-routed dropless experts
    "routed": dict(
        n_layer=3, n_head=4, n_embd=64, vocab_size=50, block_size=32,
        embd_pdrop=0.0, resid_pdrop=0.0, attn_pdrop=0.0, attention="einsum",
        rope=True, rope_theta=500.0, rope_interleave=True, rmsnorm=True,
        swiglu=True, norm_eps=1e-6, tie_weights=False, dtype="float32",
        kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
        v_head_dim=16, n_dense_layers=1, ffn_dim=96, n_experts=8,
        moe_top_k=3, moe_ffn_dim=24, n_shared_experts=2,
        moe_scoring="sigmoid", moe_route_scale=2.448),
}
PROMPTS = [[1, 2, 3, 4, 5, 6, 7, 8, 9], [5, 6, 7], [11, 12, 13, 14, 15],
           [3, 1, 4, 1, 5, 9, 2, 6], [7, 7, 7, 7], [2, 4, 6, 8, 10, 12]]


@functools.lru_cache(maxsize=None)
def model(kind):
    cfg = GPTConfig.make(**CONFIGS[kind])
    return cfg, gpt.init(jax.random.key(7), cfg)


def synchronous(monkeypatch):
    """The server's own rule, answered "only with nothing in flight"."""
    monkeypatch.setattr(InferenceServer, "_may_launch",
                        lambda self, lanes: not self._flight)


def play(server, schedule, max_rounds=400):
    """``schedule``: (round to submit in, Request). Returns the handles in
    the schedule's order, after the server has drained."""
    todo = sorted(enumerate(schedule), key=lambda e: e[1][0])
    handles = {}
    for r in range(max_rounds):
        while todo and todo[0][1][0] <= r:
            i, (_, req) = todo.pop(0)
            handles[i] = server.submit(req)
        if not server.step() and not todo:
            return [handles[i] for i in range(len(schedule))]
    raise AssertionError("not drained")


def staggered(sampled: bool, eos=None):
    """Six requests through fewer slots, admitted while others decode, of
    different lengths: slots are freed and taken again mid-stream."""
    kw = dict(do_sample=True, temperature=0.9, top_k=20, top_p=0.95) \
        if sampled else {}
    rounds = (0, 0, 1, 3, 4, 9)
    lengths = (6, 9, 4, 7, 1, 5)
    return [(r, Request(prompt=p, max_new_tokens=n, seed=100 + i,
                        eos_id=eos, **kw))
            for i, (r, p, n) in enumerate(zip(rounds, PROMPTS, lengths))]


def lane_steps(summary, n_slots) -> int:
    """Lane-steps launched, from the gauge the benchmark reads them from."""
    return round(summary["slot_utilization"] * summary["steps"] * n_slots)


def both_orders(kind, schedule_of, monkeypatch, **server_kw):
    """(handles, summary) of the run-ahead server and of the synchronous
    order, on fresh servers over one set of weights."""
    cfg, params = model(kind)
    out = []
    for order in ("ahead", "synchronous"):
        with monkeypatch.context() as m:
            if order == "synchronous":
                synchronous(m)
            server = InferenceServer(params, cfg, **server_kw)
            out.append((play(server, schedule_of()), server.summary()))
    return out


# -- the same tokens ----------------------------------------------------------

@pytest.mark.parametrize("kind,sampled,server_kw", [
    ("dense", False, dict(n_slots=3)),
    ("dense", True, dict(n_slots=3)),
    ("dense", True, dict(n_slots=2, prefill_chunk=4, prefill_buckets=(4, 8),
                         prefix_cache_mb=1.0)),
    ("dense", False, dict(n_slots=3, kv_dtype="int8")),
    ("hybrid", False, dict(n_slots=3)),
    ("hybrid", True, dict(n_slots=2)),
    ("routed", False, dict(n_slots=3)),
    ("routed", True, dict(n_slots=2)),
], ids=["greedy", "seeded", "seeded-chunked-prefix", "int8-pool",
        "hybrid-greedy", "hybrid-seeded", "routed-greedy", "routed-seeded"])
def test_every_request_gets_the_synchronous_order_s_tokens(
        kind, sampled, server_kw, monkeypatch):
    (ahead, s_ahead), (sync, s_sync) = both_orders(
        kind, lambda: staggered(sampled), monkeypatch, **server_kw)
    for a, b in zip(ahead, sync):
        assert a.finish_reason == b.finish_reason == "length"
        assert a.tokens == b.tokens and len(a.tokens) == a.max_new_effective
    # the double held: the synchronous order never launched ahead
    assert s_sync["decode_rounds_ahead"] == 0 < s_ahead["decode_rounds_ahead"]
    # every stop was by length: no step was launched to be discarded, and
    # the two orders ran the same steps for the same lanes
    assert s_ahead["decode_lane_steps_discarded"] == 0
    assert lane_steps(s_ahead, server_kw["n_slots"]) == lane_steps(
        s_sync, server_kw["n_slots"]) == sum(
        len(h.tokens) - 1 for h in ahead)
    if kind == "routed":
        assert s_ahead["moe_dropped_rows"] == 0
        assert s_ahead["moe_routed_rows"] == s_sync["moe_routed_rows"] > 0


def test_a_stop_by_length_syncs_first_and_frees_the_slot_with_nothing_in_flight():
    """The round in which a request's last token is in flight launches
    nothing: the slot is free with the device idle, so the prefill of the
    next request starts at once (a closed loop's next request)."""
    cfg, params = model("dense")
    server = InferenceServer(params, cfg, n_slots=2)
    short = server.submit(Request(prompt=PROMPTS[1], max_new_tokens=4))
    long = server.submit(Request(prompt=PROMPTS[0], max_new_tokens=12))
    in_flight = []
    while not short.finished:
        server.step()
        in_flight.append(len(server._flight))
    # ahead in every round but the one that freed the slot
    assert in_flight == [1, 1, 0]
    assert short.finish_reason == "length" and len(long.tokens) == 4
    assert server.engine.pool.free_count == 1
    # the round after launches twice, and the other request goes on
    server.step()
    assert len(server._flight) == 1 and len(long.tokens) == 5
    server.run_until_drained()
    s = server.summary()
    assert s["decode_lane_steps_discarded"] == 0
    assert s["decode_launches"] == 11     # long's 11 decoded tokens, no more


# -- stops the host cannot foresee --------------------------------------------

def greedy_alone(kind, prompt, n, **server_kw):
    cfg, params = model(kind)
    server = InferenceServer(params, cfg, n_slots=1, **server_kw)
    [h] = server.generate_batch([Request(prompt=prompt, max_new_tokens=n)])
    return h.tokens


def stops_mid_stream(kind, n=12):
    """(prompt, its greedy tokens, the index of one that none before it
    equals): a request that an ``eos_id`` of that token stops there, past
    its second decode round."""
    for prompt in PROMPTS:
        want = greedy_alone(kind, prompt, n)
        for at in range(2, n - 2):
            if want[at] not in want[:at]:
                return prompt, want, at
    raise AssertionError("these weights repeat themselves everywhere")


@pytest.mark.parametrize("kind", ["dense", "hybrid", "routed"])
def test_an_eos_costs_one_discarded_lane_step_and_the_next_tenant_nothing(
        kind, monkeypatch):
    """One slot: the request stops at an EOS with its next step in flight;
    the queued request takes the slot in the round that step syncs, and
    emits what it emits alone (a hybrid stack's state was stepped once more
    by the discarded lane-step: the prefill starts it afresh)."""
    prompt, want, eos_at = stops_mid_stream(kind)
    tenant = greedy_alone(kind, PROMPTS[3], 6)

    def schedule():
        return [(0, Request(prompt=prompt, max_new_tokens=len(want),
                            eos_id=want[eos_at])),
                (0, Request(prompt=PROMPTS[3], max_new_tokens=6))]

    (ahead, s_ahead), (sync, s_sync) = both_orders(
        kind, schedule, monkeypatch, n_slots=1)
    for (first, second) in (ahead, sync):
        assert first.finish_reason == "eos"
        assert first.tokens == want[:eos_at + 1]
        assert second.finish_reason == "length" and second.tokens == tenant
    assert s_ahead["decode_lane_steps_discarded"] == 1
    assert s_sync["decode_lane_steps_discarded"] == 0
    # the lane-step is counted, once, at its launch
    assert s_ahead["decode_launches"] == s_sync["decode_launches"] + 1
    assert lane_steps(s_ahead, 1) == lane_steps(s_sync, 1) + 1
    if kind == "routed":
        assert s_ahead["moe_dropped_rows"] == s_sync["moe_dropped_rows"] == 0


def test_a_discarded_lane_step_writes_one_row_inside_its_own_slot(monkeypatch):
    """Two slots, one request stops at an EOS: beside the synchronous
    order's pool, the run-ahead pool differs in that slot's one row past the
    EOS (and in the parked row, which every step scribbles on), nowhere in
    the other slot."""
    cfg, params = model("dense")
    prompt, want, eos_at = stops_mid_stream("dense")
    pools = []
    for order in ("ahead", "synchronous"):
        with monkeypatch.context() as m:
            if order == "synchronous":
                synchronous(m)
            server = InferenceServer(params, cfg, n_slots=2)
            other = server.submit(Request(prompt=PROMPTS[2], max_new_tokens=9))
            stops = server.submit(Request(
                prompt=prompt, max_new_tokens=len(want), eos_id=want[eos_at]))
            server.run_until_drained()
            assert stops.finish_reason == "eos" and other.slot is None
            pools.append({n: np.asarray(a) for n, a in
                          server.engine.pool.cache.items()})
    # token i is fed at len(prompt) + i: the EOS itself, by the step ahead
    row_past_eos = len(prompt) + eos_at
    for name in ("k", "v"):
        a, b = (p[name][:, :, :cfg.block_size - 1] for p in pools)
        differs = np.argwhere((a != b).any(axis=(0, 3, 4)))
        assert differs.tolist() == [[1, row_past_eos]], name


def run_two(stop, clock=None, **server_kw):
    """Two requests decoding, the first stopped by ``stop(server, handle,
    round)`` mid-stream with a step in flight; a third waits for its slot.
    Returns (handles, summary)."""
    cfg, params = model("dense")
    if clock is not None:
        server_kw["clock"] = clock
    server = InferenceServer(params, cfg, n_slots=2, **server_kw)
    victim = server.submit(Request(prompt=PROMPTS[0], max_new_tokens=12,
                                   request_id="victim"))
    other = server.submit(Request(prompt=PROMPTS[2], max_new_tokens=12))
    waiting = server.submit(Request(prompt=PROMPTS[3], max_new_tokens=5))
    for r in range(200):
        stop(server, victim, r)
        if clock is not None:
            clock.t += 1.0
        if not server.step():
            break
    return (victim, other, waiting), server.summary()


class _Clock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


@pytest.mark.parametrize("how", ["cancel", "deadline", "on_token-raises"])
def test_a_stop_behind_a_step_in_flight_drops_that_token_alone(how):
    """A cancel, a deadline and a raising callback each stop a request whose
    next token is already being computed: that token is dropped and counted,
    the other request's tokens are the synchronous order's, and the request
    that takes the freed slot gets nothing of the old launch."""
    want_other = greedy_alone("dense", PROMPTS[2], 12)
    want_waiting = greedy_alone("dense", PROMPTS[3], 5)
    clock = _Clock()
    kw = dict(clock=clock)

    def nothing(server, victim, r):
        pass

    stop = nothing
    if how == "cancel":
        def stop(server, victim, r):
            if r == 3:
                assert server.slots.ahead[victim.slot] == 1
                assert server.cancel("victim")
    elif how == "deadline":
        def stop(server, victim, r):
            if r == 0:
                victim.deadline = 3.5      # expires in the sweep of round 3
    else:
        def raising(handle, token):
            if handle.request_id == "victim" and len(handle.tokens) == 4:
                raise RuntimeError("the client went away")
        kw["on_token"] = raising

    (victim, other, waiting), s = run_two(stop, **kw)
    reason = {"cancel": "cancelled", "deadline": "deadline",
              "on_token-raises": "error"}[how]
    assert victim.finish_reason == reason and 0 < len(victim.tokens) < 12
    assert victim.tokens == greedy_alone("dense", PROMPTS[0], 12)[
        :len(victim.tokens)]
    assert other.tokens == want_other and waiting.tokens == want_waiting
    assert s["decode_lane_steps_discarded"] == 1
    assert s["slots_active"] == 0 and s["queue_depth"] == 0


def test_a_slot_taken_again_gets_nothing_from_its_old_tenant_s_launch():
    """The round in which the old launch syncs is the round the new tenant
    is prefilled in and launched for: the record is of (slot, request), so
    the old tenant's token goes nowhere, and the new tenant's first decode
    step feeds the host's token, not the device's."""
    cfg, params = model("dense")
    prompt, want, eos_at = stops_mid_stream("dense")
    tenant = greedy_alone("dense", PROMPTS[4], 6)
    server = InferenceServer(params, cfg, n_slots=1)
    old = server.submit(Request(prompt=prompt, max_new_tokens=len(want),
                                eos_id=want[eos_at]))
    new = server.submit(Request(prompt=PROMPTS[4], max_new_tokens=6))
    while not old.finished:
        server.step()
    [stale] = server._flight            # launched for the request that is gone
    assert stale.lanes == [(0, old)] and server.slots.ahead[0] == 0
    launches = []
    real = server.engine.launch_decode
    server.engine.launch_decode = lambda *a, **k: launches.append(k) or real(
        *a, **k)
    server.step()
    assert new.slot == 0 and not new.prefilling
    # ahead of the stale step, and taking nothing from it
    assert launches[0]["prev"] is stale.step
    assert not launches[0]["from_prev"].any()
    assert stale not in server._flight and len(new.tokens) == 1
    server.run_until_drained()
    assert new.tokens == tenant and old.tokens == want[:eos_at + 1]


def test_what_is_left_in_flight_by_a_cancel_stays_out_of_a_bare_decode_step():
    """The benchmark cancels what is left after its watch and then replays
    ``decode_step`` in the emptied pool: the step left in flight changes
    nothing of what those calls return."""
    cfg, params = model("dense")

    def replay(engine):
        slot = engine.pool.allocate()
        tok, _ = engine.prefill_chunk_call(
            slot, PROMPTS[0], 0, 1.0, None, None, False, 0)
        out, s = [tok], engine.n_slots
        for i in range(4):
            tokens = np.zeros(s, np.int32)
            positions = np.full(s, cfg.block_size - 1, np.int32)
            tokens[slot], positions[slot] = out[-1], len(PROMPTS[0]) + i
            out.append(int(engine.decode_step(
                tokens, positions, np.ones(s, np.float32),
                np.zeros(s, np.int32), np.ones(s, np.float32),
                np.zeros(s, bool), np.zeros(s, np.uint32))[slot]))
        return out

    server = InferenceServer(params, cfg, n_slots=2, warmup=True)
    for i, p in enumerate(PROMPTS[1:3]):
        server.submit(Request(prompt=p, max_new_tokens=20, request_id=f"c{i}"))
    for _ in range(3):
        server.step()
    assert len(server._flight) == 1
    assert server.cancel("c0") and server.cancel("c1")
    assert server.engine.pool.used_count == 0
    assert server.summary()["decode_lane_steps_discarded"] == 2
    assert replay(server.engine) == replay(DecodeEngine(params, cfg, 2))
    assert server.compile_counts()["decode"] == 1


@pytest.mark.parametrize("sampled", [False, True], ids=["greedy", "seeded"])
def test_a_server_that_outlives_a_fault_before_the_emit_computes_again(sampled):
    """The chaos harness's poisoned round raises after the sync and before
    any emit, and the server goes on (serving/fleet.py): the lost tokens and
    the step launched ahead of them are computed again from what was
    emitted, and the stream is the undisturbed one."""
    cfg, params = model("dense")
    kw = dict(do_sample=True, temperature=0.9, top_k=20, seed=5) \
        if sampled else {}
    want = InferenceServer(params, cfg, n_slots=2).generate_batch(
        [Request(prompt=p, max_new_tokens=9, **kw) for p in PROMPTS[:2]])
    calls = []

    def poison(where):
        calls.append(where)
        if len(calls) in (3, 4):
            raise RuntimeError("poisoned round")

    server = InferenceServer(params, cfg, n_slots=2, fault_hook=poison)
    got = [server.submit(Request(prompt=p, max_new_tokens=9, **kw))
           for p in PROMPTS[:2]]
    raised = 0
    for _ in range(40):
        try:
            if not server.step():
                break
        except RuntimeError:
            raised += 1
            assert not server._flight and not server.slots.ahead.any()
    assert raised == 2
    assert [h.tokens for h in got] == [h.tokens for h in want]


# -- speculation keeps today's order ------------------------------------------

def test_rounds_that_speculate_keep_the_synchronous_order():
    cfg, params = model("dense")
    server = InferenceServer(params, cfg, n_slots=2, draft_params=params,
                             draft_cfg=cfg, spec_k=2)
    reqs = [Request(prompt=p, max_new_tokens=8) for p in PROMPTS[:3]]
    handles = server.generate_batch(reqs)
    for h, p in zip(handles, PROMPTS):
        assert h.tokens == greedy_alone("dense", p, 8)
    s = server.summary()
    assert s["spec_rounds"] > 0 and s["decode_rounds_ahead"] == 0
    assert not server._flight


def test_a_lane_that_becomes_eligible_waits_for_what_is_in_flight():
    """Speculation switched on mid-stream (the control plane's gate): the
    round that finds a lane eligible with a step in flight syncs it and
    launches nothing; the next one speculates."""
    cfg, params = model("dense")
    server = InferenceServer(params, cfg, n_slots=1, draft_params=params,
                             draft_cfg=cfg, spec_k=2)
    server.spec_enabled = False
    h = server.submit(Request(prompt=PROMPTS[1], max_new_tokens=12))
    server.step()
    server.step()
    assert len(server._flight) == 1 and server.summary()["spec_rounds"] == 0
    server.spec_enabled = True
    n = len(h.tokens)
    server.step()                       # syncs, launches nothing
    assert not server._flight and len(h.tokens) == n + 1
    assert server.summary()["spec_rounds"] == 0
    server.step()                       # speculates
    assert server.summary()["spec_rounds"] == 1 and not server._flight
    server.run_until_drained()
    assert h.tokens == greedy_alone("dense", PROMPTS[1], 12)


# -- one program --------------------------------------------------------------

@pytest.mark.parametrize("warm", [True, False], ids=["warmed", "cold"])
@pytest.mark.parametrize("committed", [False, True],
                         ids=["uncommitted-weights", "committed-weights"])
def test_one_decode_program_after_a_mixed_run_and_a_bare_decode_step(
        warm, committed, monkeypatch):
    """Launches after nothing, launches ahead, the synchronous order and
    ``decode_step`` alone are one entry of the one jit's cache, whether or
    not the weights are committed to their device (the benchmark's are)."""
    cfg, params = model("dense")
    if committed:
        params = jax.device_put(params, jax.devices()[0])
    server = InferenceServer(params, cfg, n_slots=3, warmup=warm)
    if warm:
        assert server.compile_counts()["decode"] == 1
    play(server, staggered(True))
    play(server, staggered(False, eos=7))
    with monkeypatch.context() as m:
        synchronous(m)
        play(server, staggered(False))
    s = server.engine.n_slots
    server.engine.decode_step(
        np.zeros(s, np.int32), np.full(s, cfg.block_size - 1, np.int32),
        np.ones(s, np.float32), np.zeros(s, np.int32),
        np.ones(s, np.float32), np.zeros(s, bool), np.zeros(s, np.uint32))
    assert server.compile_counts()["decode"] == 1
    assert server.summary()["decode_rounds_ahead"] > 0
    if warm and not committed:
        # (committed weights commit the pool in the warm-up's first prefill,
        # which is then a second entry of the prefill's: PR 29's finding,
        # and no benchmark cell's case)
        assert server.watchdog.recompiles == 0


def test_one_decode_program_under_a_tensor_parallel_mesh():
    """Under ``tp=2`` a step's tokens are whole on every chip and said so
    (``_decode_impl`` pins them), so the launch after nothing, whose tokens
    the engine places that way itself, and the launch ahead are one entry;
    the tokens are the unsharded server's."""
    from mingpt_distributed_tpu.config import MeshConfig
    from mingpt_distributed_tpu.parallel import mesh as mesh_lib

    cfg, params = model("dense")
    mesh = mesh_lib.make_mesh(MeshConfig(tp=2), devices=jax.devices()[:2])
    server = InferenceServer(params, cfg, n_slots=3, warmup=True, mesh=mesh)
    got = play(server, staggered(True))
    assert server.compile_counts()["decode"] == 1
    assert server.watchdog.recompiles == 0
    assert server.summary()["decode_rounds_ahead"] > 0
    want = play(InferenceServer(params, cfg, n_slots=3), staggered(True))
    assert [h.tokens for h in got] == [h.tokens for h in want]


def test_programs_yields_the_decode_program_the_loop_runs():
    """``DecodeEngine.programs()`` states the decode program with the step's
    tokens and the mask among its arguments: lowering it adds no entry, and
    after a run it is the entry the run made."""
    cfg, params = model("dense")
    server = InferenceServer(params, cfg, n_slots=2, warmup=True)
    [(_, _, jitted, args, kwargs)] = [
        p for p in server.engine.programs() if p[0] == "decode"]
    assert jitted is server.engine._decode_jit and len(args) == 13
    assert args[11].shape == args[12].shape == (2,)
    jitted.lower(*args, **kwargs)
    assert server.compile_counts()["decode"] == 1


# -- decode_step alone is what it was -----------------------------------------

@pytest.mark.parametrize("kind", ["dense", "hybrid", "routed"])
def test_the_step_s_tokens_and_their_mask_add_one_select_to_the_program(kind):
    """The decode program with its two new arguments is the program without
    them (the parent's: tests/test_wide_rows.py) and one ``select_n`` over
    the (S,) tokens; nothing else is traced, and no second program merges."""
    cfg, params = model(kind)
    engine = DecodeEngine(params, cfg, 3)
    [(_, _, jitted, args, kwargs)] = [
        p for p in engine.programs() if p[0] == "decode"]
    new = jitted.trace(*args, **kwargs).jaxpr
    old = jitted.trace(*args[:11], **kwargs).jaxpr
    assert len(new.invars) == len(old.invars) + 2
    names = lambda jaxpr: [e.primitive.name for e in jaxpr.eqns]
    assert names(new)[0] == "select_n" and names(new)[1:] == names(old)
    assert new.eqns[0].outvars[0].aval.shape == (3,)


@pytest.mark.parametrize("kind", ["dense", "hybrid", "routed"])
def test_decode_step_alone_returns_the_parent_s_tokens_and_pool(kind):
    """``decode_step`` is a launch whose every lane feeds the host's token:
    its tokens and its pool are, bit for bit, those of the program traced
    without the two new arguments, which is the parent's program
    (tests/test_wide_rows.py holds that trace to the parent's digests)."""
    cfg, params = model(kind)
    rng = np.random.default_rng(0)
    s = 3
    engines = [DecodeEngine(params, cfg, s) for _ in range(2)]
    for slot, prompt in enumerate(PROMPTS[:s]):
        for eng in engines:
            eng.pool.allocate()
            eng.prefill_chunk_call(slot, prompt, 0, 1.0, None, None, False, 0)
    parent = jax.jit(engine_mod.bind_static(
        engine_mod._decode_impl, cfg=cfg), donate_argnums=(1,))
    tokens = rng.integers(0, cfg.vocab_size, s).astype(np.int32)
    positions = np.array([len(p) for p in PROMPTS[:s]], np.int32)
    for step in range(3):
        vectors = (tokens, positions + step, np.full(s, 0.8, np.float32),
                   np.array([0, 5, 0], np.int32),
                   np.array([1.0, 1.0, 0.9], np.float32),
                   np.array([False, True, True]),
                   np.array([1, 2, 3], np.uint32),
                   np.full(s, step, np.int32), np.array([True, True, False]))
        got = engines[0].decode_step(*vectors)
        want, engines[1].pool.cache = parent(
            engines[1].program_params, engines[1].pool.cache, *vectors)
        assert got.tolist() == np.asarray(want).tolist()
        tokens = got
    for name, leaf in engines[0].pool.cache.items():
        assert np.array_equal(np.asarray(leaf),
                              np.asarray(engines[1].pool.cache[name])), name
    assert gen.STATE in engines[0].pool.cache or kind != "hybrid"


def test_a_lane_takes_the_device_s_token_or_the_host_s_by_its_mask():
    """The program's merge itself: two launches back to back, the second
    ahead of the first; a lane under the mask feeds the first step's output,
    a lane outside it the host's vector, and both match the synchronous
    calls that feed those tokens from the host."""
    cfg, params = model("dense")
    s = 2
    ahead, sync = (DecodeEngine(params, cfg, s) for _ in range(2))
    first_tokens = []
    for eng in (ahead, sync):
        for slot, prompt in enumerate(PROMPTS[:s]):
            eng.pool.allocate()
            tok, _ = eng.prefill_chunk_call(
                slot, prompt, 0, 1.0, None, None, False, 0)
            first_tokens.append(tok)
    tokens = np.array(first_tokens[:s], np.int32)
    positions = np.array([len(p) for p in PROMPTS[:s]], np.int32)
    rest = (np.ones(s, np.float32), np.zeros(s, np.int32),
            np.ones(s, np.float32), np.zeros(s, bool), np.zeros(s, np.uint32))
    live = np.ones(s, bool)
    one = ahead.launch_decode(tokens, positions, *rest, np.ones(s, np.int32),
                              live)
    host = np.array([0, 9], np.int32)       # lane 1 feeds the host's 9
    two = ahead.launch_decode(host, positions + 1, *rest,
                              np.full(s, 2, np.int32), live, prev=one,
                              from_prev=np.array([True, False]))
    got_one, got_two = ahead.sync_decode(one), ahead.sync_decode(two)
    want_one = sync.decode_step(tokens, positions, *rest,
                                np.ones(s, np.int32), live)
    want_two = sync.decode_step(
        np.array([want_one[0], 9], np.int32), positions + 1, *rest,
        np.full(s, 2, np.int32), live)
    assert got_one.tolist() == want_one.tolist()
    assert got_two.tolist() == want_two.tolist()
    for name, leaf in ahead.pool.cache.items():
        assert np.array_equal(np.asarray(leaf),
                              np.asarray(sync.pool.cache[name])), name
