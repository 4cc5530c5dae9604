"""Request-scoped tracing, flight recorder and SLO engine (ISSUE 10) —
CPU, tiny config, `not slow` tier, fully deterministic: every timestamp
the recorder sees comes from a VirtualClock (the tracing module reads no
clock of its own; graftlint pins that), so span durations in these
assertions are exact, not approximate.

The load-bearing guarantees:
* a crash + retry produces ONE trace per request — the retried attempt
  appears as a second ``fleet.attempt`` span plus a ``retry`` event,
  with zero orphan records and the emit events matching the
  caller-visible stream exactly;
* sampling is deterministic per trace id, and error/shed/retry outcomes
  always export regardless of the probability;
* a drain (the SIGTERM path serve.py runs) dumps a strict-parseable
  flight record through the atomic manifest;
* /healthz carries per-replica breaker + health-gate detail,
  /debug/flight serves a valid snapshot, and /metrics carries
  ``mingpt_build_info``;
* SLO grading uses exact nearest-rank quantiles of the recorded
  durations, not histogram bucket upper bounds.
"""

import json
import urllib.request

import jax
import pytest

from mingpt_distributed_tpu import telemetry
from mingpt_distributed_tpu.config import GPTConfig
from mingpt_distributed_tpu.models import gpt
from mingpt_distributed_tpu.serving import (
    InferenceServer,
    ReplicaSupervisor,
    Request,
    Router,
    VirtualClock,
    default_server_factory,
)
from mingpt_distributed_tpu.telemetry import (
    FlightRecorder,
    MetricsRegistry,
    TraceRecorder,
    evaluate_slos,
    exact_quantile,
    load_flight_dir,
    load_trace_jsonl,
    parse_prometheus,
    parse_slo_spec,
    render_slo_report,
    trace_sink,
    validate_flight_dump,
    validate_trace_records,
)
from mingpt_distributed_tpu.training.faults import ServingFaultInjector
from oracles import solo_greedy


@pytest.fixture(scope="module")
def cfg_params():
    cfg = GPTConfig.make(
        n_layer=2, n_head=2, n_embd=32, vocab_size=50, block_size=32,
        embd_pdrop=0.0, resid_pdrop=0.0, attn_pdrop=0.0, dtype="float32",
    )
    return cfg, gpt.init(jax.random.key(0), cfg)


def make_fleet(cfg_params, n_replicas=2, spec=None, n_slots=2,
               registry=None, **router_kw):
    cfg, params = cfg_params
    injector = ServingFaultInjector(spec) if spec is not None else None
    sup = ReplicaSupervisor(
        default_server_factory(params, cfg, n_slots=n_slots),
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


def prompts_with_affinity(router, index, n, length=3):
    out = []
    for start in range(1, 200):
        p = [start + j for j in range(length)]
        if max(p) < 50 and router._affinity_index(p) == index:
            out.append(p)
            if len(out) == n:
                return out
    raise AssertionError(f"no {n} prompts hash to replica {index}")


# ---------------------------------------------------------------------------
# recorder unit tests (no model)
# ---------------------------------------------------------------------------


def test_recorder_roundtrip_validates(tmp_path):
    path = str(tmp_path / "t.jsonl")
    rec = TraceRecorder(sink=trace_sink(path))
    ctx = rec.start_trace("req-0", now=1.0, baggage={"tenant": "a"})
    rec.add_event(ctx, "queued", 1.0, queue_depth=0)
    rec.add_span(ctx, "serve.queue_wait", ts=1.0, dur_s=0.5)
    attempt = rec.open_span(ctx, "fleet.attempt", 1.5, attempt=1)
    rec.add_span(attempt, "serve.prefill_chunk", ts=1.5, dur_s=0.25)
    rec.add_event(ctx, "emit", 2.0, token_index=0)
    rec.add_event(ctx, "emit", 2.5, token_index=1)
    rec.close_span(attempt, 2.5, outcome="length")
    summary = rec.end_trace(ctx, now=2.5, outcome="length", n_tokens=2)
    rec.close()

    assert summary["ttft_s"] == pytest.approx(1.0)   # 2.0 - 1.0 (submit)
    assert summary["itl_mean_s"] == pytest.approx(0.5)
    assert summary["total_s"] == pytest.approx(1.5)
    assert summary["sampled"] and summary["baggage"]["tenant"] == "a"
    traces = load_trace_jsonl(path)   # strict: raises on any violation
    t = traces["req-0"]
    assert {s["name"] for s in t["spans"]} == {
        "serve.queue_wait", "fleet.attempt", "serve.prefill_chunk"}
    # the attempt's child span parents to the attempt span, not s0
    prefill = next(s for s in t["spans"]
                   if s["name"] == "serve.prefill_chunk")
    attempt_span = next(s for s in t["spans"]
                        if s["name"] == "fleet.attempt")
    assert prefill["parent_id"] == attempt_span["span_id"]
    assert rec.active_traces == 0 and rec.orphan_records == 0


def test_sampling_deterministic_and_forced():
    rec = TraceRecorder(sample=0.0)
    ctx = rec.start_trace("happy", now=0.0)
    s = rec.end_trace(ctx, now=1.0, outcome="length", n_tokens=1)
    assert not s["sampled"] and s["sample_cause"] is None
    # errors always export...
    ctx = rec.start_trace("sad", now=0.0)
    s = rec.end_trace(ctx, now=1.0, outcome="error")
    assert s["sampled"] and s["sample_cause"] == "forced"
    # ...as do retried requests and explicitly-marked traces
    ctx = rec.start_trace("retried", now=0.0)
    s = rec.end_trace(ctx, now=1.0, outcome="length", attempts=2)
    assert s["sampled"]
    ctx = rec.start_trace("marked", now=0.0)
    rec.mark_forced(ctx)
    s = rec.end_trace(ctx, now=1.0, outcome="length")
    assert s["sampled"]
    # unsampled summaries still feed the SLO engine
    assert len(rec.completed_requests()) == 4
    # determinism: same id -> same decision at the same probability
    a = TraceRecorder(sample=0.5)
    b = TraceRecorder(sample=0.5)
    for i in range(32):
        ca = a.start_trace(f"r{i}", now=0.0)
        cb = b.start_trace(f"r{i}", now=0.0)
        sa = a.end_trace(ca, now=1.0, outcome="length")
        sb = b.end_trace(cb, now=1.0, outcome="length")
        assert sa["sampled"] == sb["sampled"]
    assert 0 < a.exported_traces < 32  # both branches actually taken


def test_orphans_counted_and_unclosed_spans_recovered():
    reg = MetricsRegistry()
    rec = TraceRecorder(registry=reg)
    ctx = rec.start_trace("r", now=0.0)
    stale = ctx.child("s99")
    rec.close_span(stale, 1.0)          # never opened -> orphan
    assert rec.orphan_records == 1
    left_open = rec.open_span(ctx, "fleet.attempt", 0.5)
    s = rec.end_trace(ctx, now=2.0, outcome="error")
    assert s is not None
    # the leftover open span was force-closed and flagged, and the
    # resulting record stream still passes strict validation
    rec2 = TraceRecorder(sample=1.0)
    c2 = rec2.start_trace("r2", now=0.0)
    rec2.open_span(c2, "fleet.attempt", 0.5)
    collected = []

    class _Sink:
        schema = telemetry.TRACE_SCHEMA

        def write(self, kind, payload):
            collected.append(dict(payload,
                                  schema=self.schema, kind=kind))

        def close(self):
            pass

    rec2.sink = _Sink()
    rec2.end_trace(c2, now=2.0, outcome="error")
    spans = [r for r in collected if r["kind"] == "span"]
    assert len(spans) == 1 and spans[0]["unclosed"] is True
    validate_trace_records(collected)
    assert left_open.trace_id == "r"  # silence unused-var linters


def test_trace_validation_rejects_orphans_and_bad_totals():
    rec = [
        {"schema": telemetry.TRACE_SCHEMA, "kind": "span", "trace_id": "t",
         "span_id": "s1", "parent_id": "s0", "name": "x", "ts": 0.0,
         "dur_s": 1.0},
        {"schema": telemetry.TRACE_SCHEMA, "kind": "request",
         "trace_id": "t", "ts": 0.0, "end_ts": 1.0, "total_s": 1.0,
         "outcome": "length", "n_tokens": 0, "attempts": 1,
         "request_id": "t"},
    ]
    validate_trace_records(rec)
    bad = [dict(rec[0], parent_id="s42"), rec[1]]
    with pytest.raises(ValueError, match="orphan"):
        validate_trace_records(bad)
    bad = [rec[0], dict(rec[1], total_s=2.0)]
    with pytest.raises(ValueError, match="total_s"):
        validate_trace_records(bad)
    with pytest.raises(ValueError, match="request"):
        validate_trace_records([rec[0]])  # no summary record


# ---------------------------------------------------------------------------
# SLO engine (pure unit)
# ---------------------------------------------------------------------------


def test_exact_quantile_nearest_rank():
    xs = [0.1 * i for i in range(1, 101)]
    assert exact_quantile(xs, 0.50) == pytest.approx(5.0)
    assert exact_quantile(xs, 0.99) == pytest.approx(9.9)
    assert exact_quantile([7.0], 0.99) == 7.0
    assert exact_quantile([], 0.5) is None
    # the motivating difference: an exact p99 of these latencies is NOT
    # a bucket upper bound of the fixed telemetry ladder
    ladder = telemetry.LATENCY_BUCKETS_S
    assert exact_quantile(xs, 0.99) not in ladder


def test_slo_spec_parse_and_grading():
    objs = parse_slo_spec("ttft_p99<=0.5,itl_p50<=0.1,shed_rate<=0.05")
    assert [o.metric for o in objs] == ["ttft_p99", "itl_p50", "shed_rate"]
    assert parse_slo_spec("default")  # the named default set
    for bad in ("ttft_p999<=1", "nonsense<=1", "ttft_p99", ""):
        with pytest.raises(ValueError):
            parse_slo_spec(bad)

    def req(ttft, gaps, outcome="length"):
        return {"outcome": outcome, "ttft_s": ttft, "itl_s": gaps,
                "n_tokens": 1 + len(gaps), "attempts": 1}

    requests = [req(0.1, [0.01, 0.02]) for _ in range(9)]
    requests.append(req(9.0, [5.0]))  # one tail-blowing request
    report = evaluate_slos(
        requests, parse_slo_spec("ttft_p50<=0.2,ttft_p99<=0.5"))
    by_name = {r["name"]: r for r in report["objectives"]}
    assert by_name["ttft_p50"]["pass"] is True
    assert by_name["ttft_p99"]["pass"] is False   # exact p99 sees 9.0
    assert report["attained"] == 1 and report["grade"] == "D"  # 1/2
    # shed traces have no latency but count toward shed_rate
    requests.append(req(None, [], outcome="shed"))
    report = evaluate_slos(requests, parse_slo_spec("shed_rate<=0.05"))
    assert report["objectives"][0]["observed"] == pytest.approx(1 / 11)
    assert report["objectives"][0]["pass"] is False
    assert "FAIL" in render_slo_report(report)
    # no data -> n/a objectives don't count against the grade
    report = evaluate_slos([], parse_slo_spec("ttft_p99<=0.5"))
    assert report["objectives"][0]["pass"] is None
    assert report["grade"] == "n/a"  # nothing evaluable: no letter grade


def test_recovery_tail_slo_metric():
    """ISSUE 17: ``recovery_pNN`` pools the per-request fault->first-
    replacement-token scalars; requests a crash never touched carry no
    sample and don't dilute the tail."""
    objs = parse_slo_spec("recovery_p99<=0.5")
    assert objs[0].metric == "recovery_p99"
    with pytest.raises(ValueError):
        parse_slo_spec("recovery_p999<=1")
    requests = [
        {"outcome": "length", "ttft_s": 0.1, "itl_s": [],
         "recovery_s": 0.2},
        {"outcome": "length", "ttft_s": 0.1, "itl_s": [],
         "recovery_s": 0.9},
        {"outcome": "length", "ttft_s": 0.1, "itl_s": []},  # undisturbed
    ]
    report = evaluate_slos(requests, objs)
    row = report["objectives"][0]
    assert row["observed"] == pytest.approx(0.9)  # exact p99 of 2 samples
    assert row["pass"] is False
    # nothing re-routed -> the objective is n/a, not vacuously green
    report = evaluate_slos(requests[2:], objs)
    assert report["objectives"][0]["pass"] is None


# ---------------------------------------------------------------------------
# flight recorder (pure unit)
# ---------------------------------------------------------------------------


def test_flight_ring_dump_and_manifest(tmp_path):
    reg = MetricsRegistry()
    fl = FlightRecorder(capacity=8, out_dir=str(tmp_path), registry=reg)
    fl.metrics_providers["proc"] = lambda: telemetry.render_prometheus(reg)
    fl.source_providers["dead"] = lambda: 1 / 0  # must not kill a dump
    for i in range(12):
        fl.record("span", {"name": f"s{i}", "ts": float(i)})
    assert fl.dropped == 4  # ring is bounded
    path, doc = fl.dump("crash", replica="replica0")
    assert path is not None
    validate_flight_dump(doc)
    assert len(doc["records"]) == 8 and doc["ring_dropped"] == 4
    assert doc["sources"]["dead"][0]["kind"] == "provider_error"
    fl.dump("sigterm_drain")
    manifest, docs = load_flight_dir(str(tmp_path))
    assert [d["trigger"] for d in docs] == ["crash", "sigterm_drain"]
    assert manifest["latest"].endswith("sigterm_drain.json")
    # snapshots need no out_dir; dumps without one skip the write but
    # still return the document
    fl2 = FlightRecorder(capacity=2)
    fl2.record("event", {"name": "x", "ts": 0.0})
    validate_flight_dump(fl2.snapshot("on_demand"))
    p2, doc2 = fl2.dump("crash")
    assert p2 is None and validate_flight_dump(doc2)


def test_flight_max_dumps_bounded(tmp_path):
    fl = FlightRecorder(out_dir=str(tmp_path), max_dumps=2)
    fl.record("span", {"name": "s", "ts": 0.0})
    assert fl.dump("crash")[0] is not None
    assert fl.dump("crash")[0] is not None
    assert fl.dump("crash")[0] is None   # budget spent: skipped, counted
    assert fl.dumps_skipped == 1
    _, docs = load_flight_dir(str(tmp_path))
    assert len(docs) == 2


# ---------------------------------------------------------------------------
# fleet integration: the chaos acceptance bar
# ---------------------------------------------------------------------------


def test_crash_retry_is_one_trace_with_no_orphans(cfg_params, tmp_path):
    """The ISSUE 10 satellite: a crash + retry yields ONE trace whose
    second attempt is a marked span (not a second trace), with zero
    orphan records and emit events exactly matching the stream."""
    cfg, params = cfg_params
    path = str(tmp_path / "trace.jsonl")
    rec = TraceRecorder(sink=trace_sink(path))
    streamed = {}

    def on_token(fh, tok):
        streamed.setdefault(fh.request_id, []).append(tok)

    router = make_fleet(cfg_params, spec="crash:nth=6:match=replica0",
                        trace_recorder=rec, on_token=on_token)
    prompts = prompts_with_affinity(router, 0, 3)
    handles = router.generate_batch(
        [Request(prompt=p, max_new_tokens=8) for p in prompts])

    assert any(h.attempts > 1 for h in handles)
    for p, h in zip(prompts, handles):
        assert h.finish_reason == "length"
        assert h.tokens == solo_greedy(params, cfg, p, 8)
    assert rec.orphan_records == 0
    assert rec.active_traces == 0

    rec.close()
    traces = load_trace_jsonl(path)  # strict validation built in
    assert set(traces) == {h.request_id for h in handles}
    for h in handles:
        t = traces[h.request_id]
        attempts = [s for s in t["spans"] if s["name"] == "fleet.attempt"]
        retries = [e for e in t["events"] if e["name"] == "retry"]
        emits = [e for e in t["events"] if e["name"] == "emit"]
        assert len(attempts) == h.attempts
        assert len(retries) == h.attempts - 1
        assert [e["token_index"] for e in emits] == list(range(len(h.tokens)))
        assert len(emits) == len(streamed[h.request_id])
        assert t["request"]["retried"] == (h.attempts > 1)
        if h.attempts > 1:
            assert retries[0]["reason"] == "crash"
            assert t["request"]["sample_cause"] == "forced"
        # every attempt span names the replica that served it, and the
        # last one is the replica the handle finished on
        assert all("replica" in s for s in attempts)
        assert attempts[-1]["replica"] == h.replica


def test_scheduler_spans_join_fleet_trace(cfg_params, tmp_path):
    """Queue-wait, prefix-lookup, prefill and decode-round spans
    recorded inside a replica's scheduler parent into the fleet-minted
    trace via the attempt context riding on the attempt Request."""
    path = str(tmp_path / "trace.jsonl")
    rec = TraceRecorder(sink=trace_sink(path))
    router = make_fleet(cfg_params, trace_recorder=rec)
    h = router.generate_batch([Request(prompt=[1, 2, 3],
                                       max_new_tokens=4)])[0]
    rec.close()
    t = load_trace_jsonl(path)[h.request_id]
    names = {s["name"] for s in t["spans"]}
    assert {"fleet.attempt", "serve.queue_wait", "serve.prefix_lookup",
            "serve.prefill_chunk", "serve.decode_round"} <= names
    # in-replica spans parent under the attempt span, not the root
    attempt_id = next(s["span_id"] for s in t["spans"]
                      if s["name"] == "fleet.attempt")
    for s in t["spans"]:
        if s["name"].startswith("serve."):
            assert s["parent_id"] == attempt_id


def test_shed_requests_get_forced_traces(cfg_params):
    rec = TraceRecorder(sample=0.0)  # sheds must export regardless
    router = make_fleet(cfg_params, trace_recorder=rec)
    router.drain()
    with pytest.raises(Exception):
        router.submit(Request(prompt=[1, 2, 3]))
    (summary,) = rec.completed_requests()
    assert summary["outcome"] == "shed"
    assert summary["shed_reason"] == "draining"
    assert summary["sampled"] and summary["sample_cause"] == "forced"


def test_drain_dumps_strict_flight_record(cfg_params, tmp_path):
    """The SIGTERM-drain path serve.py runs: after draining, the flight
    dump must strict-parse through the manifest — on a virtual clock,
    with no wall sleeps."""
    reg = MetricsRegistry()
    fl = FlightRecorder(out_dir=str(tmp_path / "flight"), registry=reg)
    rec = TraceRecorder(registry=reg, flight=fl)
    router = make_fleet(cfg_params, registry=reg,
                        trace_recorder=rec, flight=fl)
    router.generate_batch(
        [Request(prompt=[1, 2, 3], max_new_tokens=4),
         Request(prompt=[9, 8, 7], max_new_tokens=4)])
    router.drain()
    path, doc = fl.dump("sigterm_drain")
    assert path is not None
    manifest, docs = load_flight_dir(str(tmp_path / "flight"))
    assert docs[-1]["trigger"] == "sigterm_drain"
    # the recorder mirrored the request spans into the ring
    kinds = {r["kind"] for r in docs[-1]["records"]}
    assert {"span", "event", "request"} <= kinds
    # per-replica registry snapshots strict-parse (validated already,
    # but assert they are actually per-replica)
    assert any(name.startswith("replica") for name in docs[-1]["metrics"])


def test_crash_triggers_flight_dump(cfg_params, tmp_path):
    fl = FlightRecorder(out_dir=str(tmp_path))
    rec = TraceRecorder(flight=fl)
    router = make_fleet(cfg_params, spec="crash:nth=6:match=replica0",
                        trace_recorder=rec, flight=fl)
    prompts = prompts_with_affinity(router, 0, 3)
    handles = router.generate_batch(
        [Request(prompt=p, max_new_tokens=8) for p in prompts])
    assert all(h.finish_reason == "length" for h in handles)
    _, docs = load_flight_dir(str(tmp_path))
    crash = [d for d in docs if d["trigger"] == "crash"]
    assert crash and crash[0]["attrs"]["replica"] == "replica0"


# ---------------------------------------------------------------------------
# endpoints: /healthz detail, /debug/flight, build info
# ---------------------------------------------------------------------------


def _get_json(tserver, path):
    with urllib.request.urlopen(tserver.url(path), timeout=10) as resp:
        return json.loads(resp.read().decode())


def test_healthz_flight_and_build_info_endpoints(cfg_params):
    reg = MetricsRegistry()
    telemetry.register_build_info(reg)
    fl = FlightRecorder()
    fl.record("event", {"name": "x", "ts": 0.0})
    router = make_fleet(cfg_params, registry=reg, flight=fl)
    tserver = telemetry.TelemetryServer(reg, port=0)
    try:
        tserver.health_provider = router.health_report
        tserver.flight_provider = lambda: fl.snapshot("on_demand")
        health = _get_json(tserver, "/healthz")
        assert health["status"] == "ok"
        reps = health["replicas"]
        assert set(reps) == {"replica0", "replica1"}
        for r in reps.values():
            assert r["breaker"] in ("closed", "half_open", "open")
            assert isinstance(r["reasons"], list)
        snap = _get_json(tserver, "/debug/flight")
        validate_flight_dump(snap)
        assert snap["trigger"] == "on_demand"
        with urllib.request.urlopen(tserver.url("/metrics"),
                                    timeout=10) as resp:
            parsed = parse_prometheus(resp.read().decode())
        assert parsed["types"]["mingpt_build_info"] == "gauge"
        info = [labels for n, labels, v in parsed["samples"]
                if n == "mingpt_build_info"]
        assert info and {"version", "jax", "jaxlib"} <= set(info[0])
    finally:
        tserver.close()


def test_debug_flight_404_without_recorder():
    reg = MetricsRegistry()
    tserver = telemetry.TelemetryServer(reg, port=0)
    try:
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(tserver.url("/debug/flight"), timeout=10)
        assert ei.value.code == 404
    finally:
        tserver.close()


# ---------------------------------------------------------------------------
# solo-server ownership: tracing without a router
# ---------------------------------------------------------------------------


def test_solo_server_owns_its_traces(cfg_params, tmp_path):
    cfg, params = cfg_params
    path = str(tmp_path / "trace.jsonl")
    rec = TraceRecorder(sink=trace_sink(path))
    server = InferenceServer(params, cfg, n_slots=2, trace_recorder=rec)
    handles = server.generate_batch(
        [Request(prompt=[1, 2, 3], max_new_tokens=4),
         Request(prompt=[5, 6, 7], max_new_tokens=4)])
    rec.close()
    traces = load_trace_jsonl(path)
    assert set(traces) == {h.request_id for h in handles}
    for h in handles:
        t = traces[h.request_id]
        emits = [e for e in t["events"] if e["name"] == "emit"]
        assert len(emits) == len(h.tokens)
        assert t["request"]["outcome"] == "length"
        # solo traces have no fleet layer: no attempt spans
        assert not any(s["name"] == "fleet.attempt" for s in t["spans"])
    assert rec.active_traces == 0 and rec.orphan_records == 0
