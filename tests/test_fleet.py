"""Fleet resilience tests (ISSUE 6) — CPU, tiny config, `not slow` tier,
fully deterministic: seeded fault injector, virtual clocks, zero
wall-clock sleeps (a "slow" replica is slow because its clock says so).

The load-bearing guarantees:
* circuit breakers walk CLOSED -> OPEN -> HALF_OPEN (single probe) ->
  CLOSED/OPEN exactly as documented;
* a replica crash mid-decode retries its in-flight requests on survivors
  with greedy output token-identical to solo generate() and zero
  duplicate tokens in the caller-visible stream;
* overload control sheds with distinct typed/counted reasons
  (watermark, breaker_open, deadline, draining);
* health gating steers routing away from slow replicas; affinity keeps
  shared-prefix prompts on one replica;
* the retry budget is bounded — a fleet that can't serve fails requests
  loudly instead of spinning.
"""

import jax
import pytest

from mingpt_distributed_tpu.config import GPTConfig
from mingpt_distributed_tpu.models import gpt
from mingpt_distributed_tpu.serving import (
    CircuitBreaker,
    ReplicaSupervisor,
    Request,
    Router,
    ShedError,
    VirtualClock,
    default_server_factory,
)
from mingpt_distributed_tpu.training.faults import (
    InjectedServingFault,
    ReplicaCrashed,
    ServingFaultInjector,
)
from oracles import solo_greedy


@pytest.fixture(scope="module")
def cfg_params():
    cfg = GPTConfig.make(
        n_layer=2, n_head=2, n_embd=32, vocab_size=50, block_size=32,
        embd_pdrop=0.0, resid_pdrop=0.0, attn_pdrop=0.0, dtype="float32",
    )
    return cfg, gpt.init(jax.random.key(0), cfg)


def make_fleet(cfg_params, n_replicas=2, spec=None, n_slots=2,
               registry=None, factory_kwargs=None, **router_kw):
    """A small fleet on a virtual clock with fast backoffs, so every
    retry/restart resolves within a few ticks. ``factory_kwargs`` reach
    every replica's InferenceServer (e.g. speculative-decoding knobs)."""
    cfg, params = cfg_params
    injector = ServingFaultInjector(spec) if spec is not None else None
    sup = ReplicaSupervisor(
        default_server_factory(params, cfg, n_slots=n_slots,
                               **(factory_kwargs or {})),
        n_replicas=n_replicas,
        clock=VirtualClock(tick_s=0.001),
        injector=injector,
        registry=registry,
        max_restarts=1,
        restart_backoff_s=0.01,
        itl_slo_s=router_kw.pop("itl_slo_s", 0.1),
    )
    router = Router(sup, max_retries=router_kw.pop("max_retries", 3),
                    retry_backoff_s=0.01, breaker_reset_s=0.05, **router_kw)
    return router


PROMPTS = [[1, 2, 3, 4, 5], [7, 8, 9], [10, 11, 12, 13], [40, 41]]


def prompts_with_affinity(router, index, n, length=3):
    """Deterministically pick n prompts whose affinity hash lands on
    replica ``index`` — chaos specs name replicas, so tests must steer
    work onto the named replica instead of hoping the hash cooperates."""
    out = []
    for start in range(1, 200):
        p = [start + j for j in range(length)]
        if max(p) < 50 and router._affinity_index(p) == index:
            out.append(p)
            if len(out) == n:
                return out
    raise AssertionError(f"no {n} prompts hash to replica {index}")


# ---------------------------------------------------------------------------
# circuit breaker (pure unit — no model)
# ---------------------------------------------------------------------------


def test_breaker_transitions():
    t = {"now": 0.0}
    b = CircuitBreaker(lambda: t["now"], failure_threshold=2,
                       reset_after_s=1.0)
    assert b.state == b.CLOSED and b.allow()
    b.record_failure()
    assert b.state == b.CLOSED  # under threshold
    b.record_failure()
    assert b.state == b.OPEN and not b.allow()
    # reset window elapses -> half-open, exactly one probe
    t["now"] = 1.5
    assert b.allow() and b.state == b.HALF_OPEN
    b.start_probe()
    assert not b.allow()  # probe outstanding
    b.record_success()
    assert b.state == b.CLOSED and b.failures == 0
    # half-open failure re-opens immediately (no threshold accumulation)
    b.trip()
    t["now"] = 3.0
    assert b.allow()
    b.start_probe()
    b.record_failure()
    assert b.state == b.OPEN


def test_breaker_trip_is_immediate():
    b = CircuitBreaker(lambda: 0.0, failure_threshold=5, reset_after_s=1.0)
    b.trip()
    assert b.state == b.OPEN and not b.allow()


# ---------------------------------------------------------------------------
# serving fault injector (pure unit — no model)
# ---------------------------------------------------------------------------


def test_serving_injector_validates_ops():
    with pytest.raises(ValueError, match="serving fault op"):
        ServingFaultInjector("write:every=3")  # I/O op, wrong injector
    inj = ServingFaultInjector("slow:every=1:delay=0.5")
    assert inj.specs[0].mode == "delay"  # slow defaults to delay mode
    assert inj.specs[0].delay_s == 0.5


def test_serving_injector_deterministic_schedule():
    spec = "crash:nth=3:match=replica0;poison:every=2:match=replica1"

    def run():
        inj = ServingFaultInjector(spec)
        events = []
        for i in range(6):
            try:
                inj.step_delay("replica0")
            except ReplicaCrashed:
                events.append(("crash", i))
            hook = inj.round_hook("replica1")
            try:
                hook("decode_round")
            except InjectedServingFault:
                events.append(("poison", i))
        return events

    first, second = run(), run()
    assert first == second
    assert ("crash", 2) in first  # 3rd visit, 0-indexed round 2
    assert [e for e in first if e[0] == "poison"] == [
        ("poison", 1), ("poison", 3), ("poison", 5)]


def test_slow_fault_skews_clock_never_sleeps():
    inj = ServingFaultInjector("slow:every=1:delay=2.0:match=replica1")
    assert inj.step_delay("replica0") == 0.0
    assert inj.step_delay("replica1") == 2.0  # returned, not slept


# ---------------------------------------------------------------------------
# routing + retry (model-backed)
# ---------------------------------------------------------------------------


def test_fleet_plain_traffic_parity(cfg_params):
    cfg, params = cfg_params
    router = make_fleet(cfg_params, n_replicas=2)
    handles = router.generate_batch(
        [Request(prompt=p, max_new_tokens=6) for p in PROMPTS])
    for p, h in zip(PROMPTS, handles):
        assert h.finish_reason == "length"
        assert h.tokens == solo_greedy(params, cfg, p, 6)
        assert h.attempts == 1 and h.duplicates_suppressed == 0
    s = router.summary()
    assert s["requests_by_outcome"]["completed"] == len(PROMPTS)
    assert s["retries_by_reason"] == {"crash": 0, "admit": 0, "error": 0}


def test_affinity_same_prefix_same_replica(cfg_params):
    router = make_fleet(cfg_params, n_replicas=3, affinity_len=4)
    shared = [5, 6, 7, 8]
    a = router.submit(Request(prompt=shared + [1], max_new_tokens=3))
    b = router.submit(Request(prompt=shared + [2], max_new_tokens=3))
    assert a.replica == b.replica  # same prompt head -> same replica
    router.run_until_drained(max_steps=500)
    assert a.finished and b.finished
    routed = router.summary()
    assert routed["requests_by_outcome"]["completed"] == 2


def test_crash_mid_decode_retries_on_survivor(cfg_params):
    """The acceptance core: replica0 dies mid-decode; its in-flight
    requests finish on a survivor, token-identical, zero dup tokens."""
    cfg, params = cfg_params
    streamed = {}
    router = make_fleet(cfg_params, n_replicas=2,
                        spec="crash:nth=3:match=replica0")
    router.on_token = lambda fh, tok: streamed.setdefault(
        fh.request_id, []).append(tok)
    n = 8
    # two prompts pinned on the doomed replica, two on the survivor
    prompts = (prompts_with_affinity(router, 0, 2)
               + prompts_with_affinity(router, 1, 2))
    handles = router.generate_batch(
        [Request(prompt=p, max_new_tokens=n) for p in prompts])
    s = router.summary()
    assert s["replicas"]["replica0"]["crashes"] == 1
    assert s["retries_by_reason"]["crash"] >= 1
    assert s["duplicates_suppressed"] >= 1
    retried = [h for h in handles if h.attempts > 1]
    assert retried, "the crash must have forced at least one retry"
    for p, h in zip(prompts, handles):
        assert h.finish_reason == "length"
        assert h.tokens == solo_greedy(params, cfg, p, n)
        # the caller-visible stream saw every token exactly once
        assert streamed[h.request_id] == h.tokens


def test_crash_mid_decode_with_speculation_never_double_emits(cfg_params):
    """Crash-retry composed with speculative decoding: the decode_round
    fault point fires BEFORE any of a verify round's accepted burst is
    emitted, so a crashed replica loses the whole burst and the
    survivor's re-decode dedups by token index — multi-token bursts
    widen the emission window but cannot double-emit."""
    cfg, params = cfg_params
    streamed = {}
    # nth=2, not 3: bursts retire an 8-token request in ~3 decode rounds,
    # so the crash must land while tokens are genuinely still in flight
    router = make_fleet(
        cfg_params, n_replicas=2, spec="crash:nth=2:match=replica0",
        factory_kwargs=dict(draft_params=params, draft_cfg=cfg, spec_k=3))
    router.on_token = lambda fh, tok: streamed.setdefault(
        fh.request_id, []).append(tok)
    n = 8
    prompts = (prompts_with_affinity(router, 0, 2)
               + prompts_with_affinity(router, 1, 2))
    handles = router.generate_batch(
        [Request(prompt=p, max_new_tokens=n) for p in prompts])
    s = router.summary()
    assert s["replicas"]["replica0"]["crashes"] == 1
    assert s["retries_by_reason"]["crash"] >= 1
    assert [h for h in handles if h.attempts > 1], "crash must force retry"
    for p, h in zip(prompts, handles):
        assert h.finish_reason == "length"
        assert h.tokens == solo_greedy(params, cfg, p, n)
        # every token streamed exactly once, even across the retry
        assert streamed[h.request_id] == h.tokens


def test_crashed_replica_restarts_and_serves_again(cfg_params):
    router = make_fleet(cfg_params, n_replicas=2,
                        spec="crash:nth=1:match=replica0")
    router.generate_batch(
        [Request(prompt=p, max_new_tokens=4)
         for p in prompts_with_affinity(router, 0, 2)])
    # idle rounds still poll the supervisor: the backoff elapses on the
    # virtual clock and the respawn lands
    for _ in range(50):
        router.step()
    s = router.summary()
    assert s["replicas"]["replica0"]["crashes"] == 1
    assert s["replicas"]["replica0"]["state"] == "ready"  # respawned
    # the fresh server accepts traffic again (breaker walked half-open
    # probe -> closed, or remains probe-able)
    h = router.generate_batch([Request(prompt=[9, 9, 9],
                                       max_new_tokens=3)])[0]
    assert h.finish_reason == "length"


def test_admission_fault_retries_elsewhere(cfg_params):
    router = make_fleet(cfg_params, n_replicas=2,
                        spec="admit:every=1:match=replica0")
    # force the affinity-preferred replica to be the one that refuses
    prompt = next(p for p in ([i, i + 1, i + 2] for i in range(1, 40))
                  if router._affinity_index(p) == 0)
    h = router.generate_batch([Request(prompt=prompt, max_new_tokens=4)])[0]
    assert h.finish_reason == "length"
    assert h.replica == "replica1"
    assert router.summary()["retries_by_reason"]["admit"] >= 1


def test_poisoned_round_recomputes_without_double_emit(cfg_params):
    """A poison fault raises after the compiled decode step but before
    emission: the round's tokens are lost, recomputed next round, and
    the stream has no duplicates (greedy parity holds)."""
    cfg, params = cfg_params
    reg_streams = {}
    router = make_fleet(cfg_params, n_replicas=1,
                        spec="poison:nth=2:match=replica0")
    router.on_token = lambda fh, tok: reg_streams.setdefault(
        fh.request_id, []).append(tok)
    p = PROMPTS[0]
    h = router.generate_batch([Request(prompt=p, max_new_tokens=6)])[0]
    assert h.finish_reason == "length"
    assert h.tokens == solo_greedy(params, cfg, p, 6)
    assert reg_streams[h.request_id] == h.tokens
    s = router.summary()
    assert s["duplicates_suppressed"] == 0  # nothing was ever re-emitted
    assert s["replicas"]["replica0"]["crashes"] == 0  # replica survived


def test_retry_budget_exhaustion_fails_loudly(cfg_params):
    """Both replicas crash on every round and the restart budget runs
    out: accepted requests terminate with finish_reason=error instead of
    the router spinning forever."""
    router = make_fleet(cfg_params, n_replicas=2, spec="crash:every=1",
                        max_retries=2)
    handles = [router.submit(Request(prompt=p, max_new_tokens=4))
               for p in PROMPTS[:2]]
    router.run_until_drained(max_steps=5000)
    assert all(h.finished for h in handles)
    assert all(h.finish_reason == "error" for h in handles)
    s = router.summary()
    assert s["requests_by_outcome"]["error"] == 2
    assert s["pending"] == 0 and s["in_flight"] == 0


# ---------------------------------------------------------------------------
# overload control
# ---------------------------------------------------------------------------


def test_watermark_shed(cfg_params):
    router = make_fleet(cfg_params, n_replicas=1, n_slots=1,
                        shed_watermark=2)
    # two queued (nothing stepped yet) reaches the fleet-wide watermark;
    # the next submission is shed before it is accepted
    for p in PROMPTS[:2]:
        router.submit(Request(prompt=p, max_new_tokens=4))
    with pytest.raises(ShedError) as ei:
        router.submit(Request(prompt=[3, 3], max_new_tokens=4))
    assert ei.value.reason == "shed"
    assert router.summary()["rejected_by_reason"]["shed"] == 1
    router.run_until_drained(max_steps=500)


def test_all_breakers_open_sheds(cfg_params):
    router = make_fleet(cfg_params, n_replicas=2)
    for b in router.breakers.values():
        b.trip()
    with pytest.raises(ShedError) as ei:
        router.submit(Request(prompt=[1, 2], max_new_tokens=2))
    assert ei.value.reason == "breaker_open"
    assert ei.value.retry_after_s is not None
    assert router.summary()["rejected_by_reason"]["breaker_open"] == 1


def test_deadline_aware_shed(cfg_params):
    router = make_fleet(cfg_params, n_replicas=1)
    # establish ITL history so the wait estimate is non-zero
    router.generate_batch([Request(prompt=PROMPTS[0], max_new_tokens=6)])
    with pytest.raises(ShedError) as ei:
        router.submit(Request(prompt=PROMPTS[1], max_new_tokens=4,
                              deadline_s=1e-9))
    assert ei.value.reason == "deadline"
    assert router.summary()["rejected_by_reason"]["deadline"] == 1


def test_graceful_drain(cfg_params):
    cfg, params = cfg_params
    router = make_fleet(cfg_params, n_replicas=2)
    handles = [router.submit(Request(prompt=p, max_new_tokens=6))
               for p in PROMPTS[:2]]
    router.step()  # work is in flight
    router.drain()
    with pytest.raises(ShedError) as ei:
        router.submit(Request(prompt=[4, 4], max_new_tokens=2))
    assert ei.value.reason == "draining"
    router.run_until_drained(max_steps=500)
    # drain finished the accepted work, and correctly
    for p, h in zip(PROMPTS, handles):
        assert h.finish_reason == "length"
        assert h.tokens == solo_greedy(params, cfg, p, 6)
    assert router.summary()["rejected_by_reason"]["draining"] == 1


# ---------------------------------------------------------------------------
# health gating
# ---------------------------------------------------------------------------


def test_slow_replica_health_gated(cfg_params):
    """An injected-slow replica accumulates clock skew, its observed ITL
    p99 crosses the SLO, and routing steers new work to the healthy
    replica while the slow one still finishes what it has."""
    router = make_fleet(cfg_params, n_replicas=2,
                        spec="slow:every=1:delay=0.25:match=replica0",
                        itl_slo_s=0.1, affinity_len=4)
    # aim the first request at replica0 so it builds slow-ITL history
    prompt = next(p for p in ([i, i + 1, i + 2] for i in range(1, 40))
                  if router._affinity_index(p) == 0)
    first = router.generate_batch([Request(prompt=prompt,
                                           max_new_tokens=6)])[0]
    assert first.finish_reason == "length"  # slow, not broken
    sup = router.supervisor
    rep0 = sup.replica_by_name("replica0")
    assert rep0.clock.skew_s > 0
    health = rep0.health()
    assert not health.ready and "itl_p99" in health.reasons
    # same-affinity traffic now spills to the healthy replica
    h = router.submit(Request(prompt=prompt, max_new_tokens=3))
    assert h.replica == "replica1"
    router.run_until_drained(max_steps=500)
    assert h.finish_reason == "length"


def test_health_gauges_exported(cfg_params):
    from mingpt_distributed_tpu.telemetry import MetricsRegistry
    from mingpt_distributed_tpu.telemetry.export import render_prometheus

    reg = MetricsRegistry()
    router = make_fleet(cfg_params, n_replicas=2, registry=reg,
                        spec="crash:nth=1:match=replica1")
    router.generate_batch(
        [Request(prompt=p, max_new_tokens=3)
         for p in prompts_with_affinity(router, 1, 2)])
    for _ in range(50):  # let the restart backoff elapse + respawn land
        router.step()
    page = render_prometheus(reg)
    for needle in (
        'mingpt_fleet_replica_up{replica="replica0"} 1',
        'mingpt_fleet_crashes_total{replica="replica1"} 1',
        'mingpt_fleet_restarts_total{replica="replica1"} 1',
        "mingpt_fleet_breaker_state",
        'mingpt_serving_rejected_total{reason="queue_full"} 0',
    ):
        assert needle in page, f"missing {needle!r} in exposition"
