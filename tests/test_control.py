"""Control-plane tests (ISSUE 20) — CPU, tiny config, `not slow` tier,
fully deterministic: every governor decision is a function of
ControlSnapshot fields sampled off the router's injected clock.

The load-bearing guarantees:
* the hysteresis governor never acts on noise — alternating
  breach/comfort ticks accumulate nothing, and the post-action
  cooldown discards observations entirely;
* the trace importer replays a recorded mingpt-trace/1 log exactly —
  rendered arrival times ARE the recorded submit times, seed-free,
  and the ``recorded:`` spec string round-trips;
* the cost model's units are pinned against hand counts;
* an autoscaled sweep is byte-identical across runs — the
  mingpt-traffic/1 report AND every mingpt-control/1 log;
* scale-down drains, never kills: token streams stay exactly equal to
  solo greedy decode with zero duplicates while a replica retires.
"""

import json

import jax
import pytest

from mingpt_distributed_tpu.config import GPTConfig
from mingpt_distributed_tpu.control.controller import (
    CONTROL_SCHEMA,
    ControllerConfig,
    HysteresisGovernor,
    SLOAutoscaler,
    parse_controller_spec,
)
from mingpt_distributed_tpu.control.cost import compute_cost, cost_from_cell
from mingpt_distributed_tpu.control.importer import (
    import_trace_arrivals,
    trace_arrival_times,
)
from mingpt_distributed_tpu.models import gpt
from mingpt_distributed_tpu.serving import (
    ReplicaSupervisor,
    Request,
    Router,
    VirtualClock,
    default_server_factory,
)
from mingpt_distributed_tpu.trafficlab import (
    SweepSpec,
    arrival_times,
    parse_arrival_spec,
    render_traffic_report,
    run_sweep,
    validate_traffic_report,
)
from oracles import solo_greedy

TRACE_SCHEMA = "mingpt-trace/1"


@pytest.fixture(scope="module")
def cfg_params():
    cfg = GPTConfig.make(
        n_layer=2, n_head=2, n_embd=32, vocab_size=50, block_size=32,
        embd_pdrop=0.0, resid_pdrop=0.0, attn_pdrop=0.0, dtype="float32",
    )
    return cfg, gpt.init(jax.random.key(0), cfg)


# ---------------------------------------------------------------------------
# hysteresis governor (pure unit — no model, no fleet)
# ---------------------------------------------------------------------------


def test_governor_alternating_noise_never_acts():
    """Streaks reset on any non-matching tick, so breach/comfort noise
    can flap forever without reaching either threshold."""
    g = HysteresisGovernor(up_after=2, down_after=2, cooldown_s=0.0)
    for i in range(100):
        breach = i % 2 == 0
        assert g.observe(breach, not breach, now=i * 0.01) is None
    assert g.breach_ticks <= 1 and g.comfort_ticks <= 1


def test_governor_sustained_breach_acts_once_then_cooldown():
    g = HysteresisGovernor(up_after=3, down_after=4, cooldown_s=1.0)
    assert g.observe(True, False, now=0.0) is None
    assert g.observe(True, False, now=0.1) is None
    assert g.observe(True, False, now=0.2) == "up"
    # cooldown: observations are DISCARDED, not accumulated — a solid
    # breach streak inside the blackout must not double-trigger
    for i in range(8):
        assert g.observe(True, False, now=0.3 + i * 0.1) is None
    assert g.breach_ticks == 0
    # after expiry the streak starts from scratch
    assert g.observe(True, False, now=1.3) is None
    assert g.observe(True, False, now=1.4) is None
    assert g.observe(True, False, now=1.5) == "up"


def test_governor_comfort_streak_scales_down_and_resets():
    g = HysteresisGovernor(up_after=2, down_after=3, cooldown_s=0.0)
    assert g.observe(False, True, now=0.0) is None
    assert g.observe(False, True, now=0.1) is None
    # one deadband tick (neither breach nor comfort) resets the streak
    assert g.observe(False, False, now=0.2) is None
    assert g.observe(False, True, now=0.3) is None
    assert g.observe(False, True, now=0.4) is None
    assert g.observe(False, True, now=0.5) == "down"
    # acting zeroed both streaks
    assert g.breach_ticks == 0 and g.comfort_ticks == 0


# ---------------------------------------------------------------------------
# controller spec grammar
# ---------------------------------------------------------------------------


def test_parse_controller_spec_static_and_defaults():
    assert parse_controller_spec("static") is None
    cfg = parse_controller_spec("auto")
    assert isinstance(cfg, ControllerConfig)
    assert cfg.metric == "ttft_p99" and cfg.min_replicas == 1


def test_parse_controller_spec_overrides_round_trip():
    cfg = parse_controller_spec(
        "auto:metric=queue_depth:target=2.0:comfort=0.25:up_after=3"
        ":down_after=7:min_replicas=2:max_replicas=3:interval_s=0.01"
        ":cooldown_s=0.1:queue_high=4.0:min_chunk=8")
    assert cfg.metric == "queue_depth"
    assert cfg.target == 2.0 and cfg.comfort == 0.25
    assert (cfg.up_after, cfg.down_after) == (3, 7)
    assert (cfg.min_replicas, cfg.max_replicas) == (2, 3)
    assert cfg.interval_s == 0.01 and cfg.cooldown_s == 0.1
    assert cfg.queue_high == 4.0 and cfg.min_chunk == 8


@pytest.mark.parametrize("bad", [
    "manual",                       # neither static nor auto
    "auto:metric",                  # malformed k=v
    "auto:target=1:target=2",       # duplicate field
    "auto:frobnicate=1",            # unknown field
    "auto:metric=ttft_p50",         # unknown metric
    "auto:target=-1",               # fails validate()
    "auto:min_replicas=3:max_replicas=1",
    "auto:comfort=1.5",
])
def test_parse_controller_spec_rejects(bad):
    with pytest.raises(ValueError):
        parse_controller_spec(bad)


# ---------------------------------------------------------------------------
# cost model units
# ---------------------------------------------------------------------------


def test_compute_cost_hand_counts():
    c = compute_cost({
        "completed": 6, "shed": 2, "expired": 1, "errors": 1,
        "tokens": 100, "deadline_requests": 5, "deadline_hits": 3,
    })
    # demanded = 10, shed_rate = 0.2; misses = 2, miss/tok = 0.02
    assert c["shed_rate"] == pytest.approx(0.2)
    assert c["deadline_miss_per_ktok"] == pytest.approx(20.0)
    assert c["goodput_tokens"] == pytest.approx(80.0)
    assert c["cost"] == pytest.approx(0.02 + 0.2)


def test_compute_cost_edges():
    # nothing demanded at all: every term is exactly zero
    zeros = {k: 0 for k in ("completed", "shed", "expired", "errors",
                            "tokens", "deadline_requests",
                            "deadline_hits")}
    c = compute_cost(zeros)
    assert c == {"deadline_miss_per_ktok": 0.0, "shed_rate": 0.0,
                 "goodput_tokens": 0.0, "cost": 0.0}
    # zero tokens but misses: miss count passes through undivided, so
    # an all-shed cell still grades worse than a serving one
    c = compute_cost(dict(zeros, shed=4, deadline_requests=3))
    assert c["shed_rate"] == 1.0 and c["cost"] == pytest.approx(4.0)
    with pytest.raises(ValueError):
        compute_cost({k: v for k, v in zeros.items() if k != "tokens"})
    with pytest.raises(ValueError):
        compute_cost(dict(zeros, completed=-1))
    with pytest.raises(ValueError):
        compute_cost(dict(zeros, deadline_hits=1))  # hits > requests


def test_cost_from_cell_matches_and_handles_none_rate():
    cell = {"completed": 6, "shed": 2, "expired": 1, "errors": 1,
            "tokens": 100, "deadline_requests": 5,
            "deadline_hit_rate": 3 / 5}
    assert cost_from_cell(cell) == compute_cost({
        "completed": 6, "shed": 2, "expired": 1, "errors": 1,
        "tokens": 100, "deadline_requests": 5, "deadline_hits": 3})
    # no deadline-carrying requests: rate is None, hits are zero
    quiet = dict(cell, deadline_requests=0, deadline_hit_rate=None)
    assert cost_from_cell(quiet)["deadline_miss_per_ktok"] == 0.0


# ---------------------------------------------------------------------------
# trace importer: recorded replay is exact
# ---------------------------------------------------------------------------


def _write_trace(path, stamps, outcomes=None):
    """A minimal valid mingpt-trace/1 file: one request summary per
    arrival, deliberately out of order (the importer sorts)."""
    outcomes = outcomes or ["completed"] * len(stamps)
    with open(path, "w", encoding="utf-8") as fh:
        for i, (ts, outcome) in enumerate(zip(stamps, outcomes)):
            fh.write(json.dumps({
                "schema": TRACE_SCHEMA, "kind": "request",
                "trace_id": f"t{i}", "request_id": f"r{i}",
                "ts": ts, "end_ts": ts + 0.5, "total_s": 0.5,
                # n_tokens=0 keeps the strict validator from demanding
                # matching emit events — arrivals are all we replay
                "outcome": outcome, "n_tokens": 0, "attempts": 1,
            }) + "\n")


def test_importer_roundtrip_exact(tmp_path):
    path = str(tmp_path / "trace.jsonl")
    # shed requests are arrivals too — the fleet refused them, but the
    # load they represent must replay
    _write_trace(path, stamps=[3.5, 1.25, 1.75, 9.0],
                 outcomes=["completed", "completed", "shed", "expired"])
    times = trace_arrival_times(path)
    assert times == (0.0, 0.5, 2.25, 7.75)  # sorted, zero-based

    spec, meta = import_trace_arrivals(path)
    assert meta["n_requests"] == 4
    assert meta["duration_s"] == pytest.approx(7.75)
    assert meta["mean_rate"] == pytest.approx(3 / 7.75)

    # rendered arrivals ARE the recorded gaps — exactly, any seed
    for seed in (0, 1, 12345):
        assert arrival_times(spec, 4, seed) == [0.0, 0.5, 2.25, 7.75]
    assert arrival_times(spec, 2, 0, start=10.0) == [10.0, 10.5]
    with pytest.raises(ValueError):
        arrival_times(spec, 5, 0)  # more than the trace holds

    # spec string round-trips through the arrival grammar
    reparsed = parse_arrival_spec(spec.to_string())
    assert reparsed.times == spec.times


def test_importer_rejects_empty_trace(tmp_path):
    path = str(tmp_path / "empty.jsonl")
    with open(path, "w", encoding="utf-8") as fh:
        fh.write("")
    with pytest.raises(ValueError):
        trace_arrival_times(path)


# ---------------------------------------------------------------------------
# autoscaled sweep determinism (model-backed)
# ---------------------------------------------------------------------------

AUTO_SPEC = ("auto:metric=queue_depth:target=2.0:comfort=0.5"
             ":interval_s=0.002:cooldown_s=0.02:up_after=2:down_after=5"
             ":min_replicas=1:max_replicas=3")


def test_autoscaled_sweep_byte_identical(cfg_params):
    """Two runs of the same autoscaled sweep produce the same report
    bytes AND the same control-log bytes — the controller is on the
    virtual clock, so there is nothing nondeterministic to leak."""
    cfg, params = cfg_params
    spec = SweepSpec(
        arrival="ramp:rate0=1400.0:rate1=4.0:duration=0.04",
        ladder=(1.0,), policies=("fifo",),
        controllers=("static", AUTO_SPEC),
        n_requests=16, seed=0, n_replicas=1, n_slots=2,
        slo="ttft_p95<=0.025,shed_rate<=0.5", prefix_cache_mb=0.5)

    def run_once():
        logs = {}
        report = run_sweep(
            params, cfg, spec,
            control_log_sink=lambda r, label, text:
                logs.__setitem__((r, label), text))
        return report, logs

    report_a, logs_a = run_once()
    report_b, logs_b = run_once()
    validate_traffic_report(report_a)
    assert report_a["policies"] == ["fifo", "fifo+auto"]
    assert render_traffic_report(report_a) == render_traffic_report(report_b)
    assert logs_a == logs_b and (0, "fifo+auto") in logs_a

    cell = report_a["rungs"][0]["policies"]["fifo+auto"]
    assert cell["control"]["spec"] == AUTO_SPEC
    rows = [json.loads(line)
            for line in logs_a[(0, "fifo+auto")].splitlines()]
    assert rows and all(r["schema"] == CONTROL_SCHEMA for r in rows)
    assert cell["control"]["ticks"] == len(rows)
    # the static cell has no control block but still gets a cost grade
    static = report_a["rungs"][0]["policies"]["fifo"]
    assert "control" not in static and "cost" in static


# ---------------------------------------------------------------------------
# scale-down drains, never kills (model-backed)
# ---------------------------------------------------------------------------


def test_scale_down_drains_never_kills(cfg_params):
    """An over-provisioned idle fleet scales down by DRAINING a replica
    — streams stay token-exact vs solo greedy with zero duplicates, no
    replica is ever killed, and post-drain submissions complete on the
    survivor."""
    cfg, params = cfg_params
    sup = ReplicaSupervisor(
        default_server_factory(params, cfg, n_slots=2),
        n_replicas=2, clock=VirtualClock(tick_s=0.001),
        max_restarts=1, restart_backoff_s=0.01)
    router = Router(sup, max_retries=3, retry_backoff_s=0.01)
    ccfg = parse_controller_spec(
        "auto:metric=queue_depth:target=4.0:comfort=0.5"
        ":interval_s=0.002:cooldown_s=0.01:up_after=2:down_after=3"
        ":min_replicas=1:max_replicas=2")
    controller = SLOAutoscaler(router, ccfg)
    router.controller = controller

    prompts = [[1, 2, 3, 4, 5], [7, 8, 9], [10, 11, 12, 13], [40, 41]]
    handles = [router.submit(Request(prompt=p, max_new_tokens=4))
               for p in prompts]
    router.run_until_drained(max_steps=500)
    for h, p in zip(handles, prompts):
        assert h.finished and h.finish_reason == "length"
        assert h.tokens == solo_greedy(params, cfg, p, 4)
        assert h.duplicates_suppressed == 0

    # idle comfort ticks: the controller drains one replica down to
    # min_replicas and retires it once its load hits zero
    for _ in range(200):
        router.step()
        states = [rep.state for rep in sup.replicas]
        if "drained" in states:
            break
    states = [rep.state for rep in sup.replicas]
    assert states.count("drained") == 1
    assert controller.action_counts()["replicas"]["down"] == 1
    # drained by the controller, not killed by the supervisor: nothing
    # restarted, nothing errored, every accepted request completed
    s = router.summary()
    assert s["requests_by_outcome"].get("error", 0) == 0
    assert s["requests_by_outcome"]["completed"] == len(prompts)
    assert s["retries_by_reason"] == {"crash": 0, "admit": 0, "error": 0}

    # the survivor still serves, token-exact, and routing avoids the
    # drained replica
    h = router.submit(Request(prompt=[6, 7, 8], max_new_tokens=3))
    router.run_until_drained(max_steps=500)
    assert h.finished and h.tokens == solo_greedy(params, cfg, [6, 7, 8], 3)
    drained = [rep.name for rep in sup.replicas if rep.state == "drained"]
    assert h.replica not in drained

    # the decision log is valid mingpt-control/1, one row per tick
    rows = [json.loads(line)
            for line in controller.render_log().splitlines()]
    assert rows and all(r["schema"] == CONTROL_SCHEMA for r in rows)
    assert controller.tick == len(rows)
    downs = [r for r in rows if r["action"]["direction"] == "down"]
    assert any(r["action"]["actuator"] == "replicas" for r in downs)
