"""Unified telemetry subsystem (ISSUE 5): registry semantics, RateWindow
edge cases, Prometheus render/parse (strict grammar, not string-contains),
span tracer, JSONL schema, recompile watchdog, and the HTTP endpoint.
"""

import json
import math
import re
import urllib.error
import urllib.request

import pytest

from mingpt_distributed_tpu import telemetry
from mingpt_distributed_tpu.telemetry import programs as program_lib
from mingpt_distributed_tpu.telemetry import (
    LATENCY_BUCKETS_S,
    PEAK_FLOPS,
    PEAK_HBM_BYTES,
    JsonlEventSink,
    MetricsRegistry,
    RateWindow,
    RecompileError,
    RecompileWatchdog,
    SpanTracer,
    TelemetryServer,
    log_event,
    parse_prometheus,
    render_prometheus,
)

# ---------------------------------------------------------------------------
# RateWindow edge cases (ISSUE 5 satellite c)
# ---------------------------------------------------------------------------


def test_rate_window_first_call_returns_none():
    assert RateWindow().observe(10.0) is None


def test_rate_window_marker_not_advancing_returns_none():
    w = RateWindow()
    w.observe(5.0, now=0.0)
    assert w.observe(5.0, now=1.0) is None   # unchanged marker
    assert w.observe(4.0, now=2.0) is None   # regressed marker
    # the window still slides: the next advance rates against t=2
    assert w.observe(8.0, now=4.0) == pytest.approx(2.0)


def test_rate_window_zero_elapsed_guard():
    w = RateWindow()
    w.observe(0.0, now=7.0)
    # marker advanced but zero wall time elapsed: must not divide by zero
    assert w.observe(100.0, now=7.0) is None


def test_rate_window_basic_rate():
    w = RateWindow()
    w.observe(100.0, now=0.0)
    assert w.observe(400.0, now=3.0) == pytest.approx(100.0)


# ---------------------------------------------------------------------------
# Registry semantics
# ---------------------------------------------------------------------------


def test_registry_get_or_create_returns_same_family():
    reg = MetricsRegistry()
    a = reg.counter("mingpt_test_total", help="h")
    b = reg.counter("mingpt_test_total")
    assert a is b


def test_registry_conflicting_redefinition_raises():
    reg = MetricsRegistry()
    reg.counter("mingpt_test_total")
    with pytest.raises(ValueError, match="conflicting"):
        reg.gauge("mingpt_test_total")
    reg.counter("mingpt_labeled_total", labels=("a",))
    with pytest.raises(ValueError, match="conflicting"):
        reg.counter("mingpt_labeled_total", labels=("b",))


def test_registry_invalid_names_rejected():
    reg = MetricsRegistry()
    with pytest.raises(ValueError):
        reg.counter("0bad")
    with pytest.raises(ValueError):
        reg.counter("ok_total", labels=("bad-label",))


def test_counter_monotonic():
    reg = MetricsRegistry()
    c = reg.counter("c_total")
    c.inc()
    c.inc(2.5)
    assert c.value == 3.5
    with pytest.raises(ValueError):
        c.inc(-1)


def test_labeled_family_memoises_children():
    reg = MetricsRegistry()
    fam = reg.counter("req_total", labels=("outcome",))
    fam.labels(outcome="ok").inc(3)
    assert fam.labels(outcome="ok").value == 3
    assert fam.labels(outcome="bad").value == 0
    with pytest.raises(ValueError):
        fam.labels(wrong="x")
    with pytest.raises(ValueError):
        fam.inc()  # label-less proxy refused on a labeled family


def test_histogram_buckets_and_cumulative():
    reg = MetricsRegistry()
    h = reg.histogram("lat_seconds", buckets=(0.1, 1.0))
    for v in (0.05, 0.5, 0.5, 5.0):
        h.observe(v)
    assert h.count == 4
    assert h.sum == pytest.approx(6.05)
    assert h.cumulative() == [(0.1, 1), (1.0, 3), (float("inf"), 4)]
    # boundary value counts into its own bucket (le semantics)
    h.observe(0.1)
    assert h.cumulative()[0] == (0.1, 2)


def test_histogram_rejects_bad_ladders():
    reg = MetricsRegistry()
    with pytest.raises(ValueError):
        reg.histogram("a_seconds", buckets=())
    with pytest.raises(ValueError):
        reg.histogram("b_seconds", buckets=(1.0, 1.0))
    with pytest.raises(ValueError):
        reg.histogram("c_seconds", buckets=(2.0, 1.0))


# ---------------------------------------------------------------------------
# Prometheus exposition: render + strict parse
# ---------------------------------------------------------------------------


def test_render_and_parse_roundtrip_with_label_escaping():
    reg = MetricsRegistry()
    fam = reg.counter("esc_total", help="weird\nhelp \\ text",
                      labels=("path",))
    nasty = 'a"b\\c\nd'
    fam.labels(path=nasty).inc(2)
    text = render_prometheus(reg)
    parsed = parse_prometheus(text)
    assert parsed["types"]["esc_total"] == "counter"
    [(name, labels, value)] = parsed["samples"]
    assert name == "esc_total"
    assert labels == {"path": nasty}  # escape → unescape is lossless
    assert value == 2


def test_render_histogram_triplet_validated_by_parser():
    reg = MetricsRegistry()
    h = reg.histogram("ttft_seconds", help="ttft", buckets=(0.01, 0.1))
    h.observe(0.005)
    h.observe(0.05)
    h.observe(7.0)
    parsed = parse_prometheus(render_prometheus(reg))
    assert parsed["types"]["ttft_seconds"] == "histogram"
    samples = {(n, labels.get("le")): v
               for n, labels, v in parsed["samples"]}
    assert samples[("ttft_seconds_bucket", "0.01")] == 1
    assert samples[("ttft_seconds_bucket", "0.1")] == 2
    assert samples[("ttft_seconds_bucket", "+Inf")] == 3
    assert samples[("ttft_seconds_count", None)] == 3
    assert samples[("ttft_seconds_sum", None)] == pytest.approx(7.055)


def test_empty_labeled_family_still_renders_type_line():
    # the selftest's "recompiles == 0" assertion depends on the family
    # being advertised even when no recompile has ever produced a sample
    reg = MetricsRegistry()
    reg.counter("mingpt_recompiles_total", labels=("family",))
    parsed = parse_prometheus(render_prometheus(reg))
    assert parsed["types"]["mingpt_recompiles_total"] == "counter"
    assert parsed["samples"] == []


@pytest.mark.parametrize("bad", [
    "metric{] 1",
    "metric 1 2 3",
    'metric{le="0.1} 1',
    "# TYPE metric nonsense",
    "0bad_name 1",
])
def test_parse_rejects_malformed_lines(bad):
    with pytest.raises(ValueError):
        parse_prometheus(bad)


def test_parse_rejects_incoherent_histogram():
    bad = "\n".join([
        "# TYPE h seconds".replace("seconds", "histogram"),
        'h_bucket{le="0.1"} 5',
        'h_bucket{le="+Inf"} 3',  # not cumulative
        "h_sum 1.0",
        "h_count 3",
    ])
    with pytest.raises(ValueError, match="cumulative"):
        parse_prometheus(bad)
    bad2 = "\n".join([
        "# TYPE h histogram",
        'h_bucket{le="+Inf"} 3',
        "h_sum 1.0",
        "h_count 4",             # +Inf bucket != count
    ])
    with pytest.raises(ValueError, match="_count"):
        parse_prometheus(bad2)


def test_unified_page_carries_train_and_serve_families():
    """The acceptance shape: MetricsLogger and ServingMetrics registered
    into ONE registry produce a single valid exposition page with TTFT/ITL
    histograms, utilization + prefix gauges, and train loss/MFU gauges —
    asserted through the strict parser, not string matching."""
    from mingpt_distributed_tpu.config import GPTConfig
    from mingpt_distributed_tpu.serving.metrics import ServingMetrics
    from mingpt_distributed_tpu.training.metrics import MetricsLogger

    reg = MetricsRegistry()
    cfg = GPTConfig.make(n_layer=2, n_head=2, n_embd=32, vocab_size=64,
                         block_size=16)
    mlog = MetricsLogger(cfg, registry=reg, enabled=False)
    mlog.log_step(1, 512, 16, {"loss": 3.0})
    mlog.log_step(2, 512, 16, {"loss": 2.5})
    sm = ServingMetrics(n_slots=2, registry=reg)
    sm.on_submit()
    sm.on_prefill(ttft_s=0.02, stall_s=0.01)
    sm.on_prefix_lookup(hit=True, rows=4)
    sm.on_tokens(3)
    sm.on_complete(n_generated=3, gen_span_s=0.02)
    sm.on_step(queue_depth=0, slots_active=1, lanes_used=1)
    parsed = parse_prometheus(render_prometheus(reg))
    types = parsed["types"]
    assert types["mingpt_serve_ttft_seconds"] == "histogram"
    assert types["mingpt_serve_itl_seconds"] == "histogram"
    assert types["mingpt_serve_slot_utilization"] == "gauge"
    assert types["mingpt_serve_prefix_hit_rate"] == "gauge"
    assert types["mingpt_train_loss"] == "gauge"
    assert types["mingpt_train_mfu"] == "gauge"
    values = {(n, tuple(sorted(l.items()))): v
              for n, l, v in parsed["samples"]}
    assert values[("mingpt_train_loss", ())] == 2.5
    assert values[("mingpt_serve_prefix_hit_rate", ())] == 1.0
    assert values[("mingpt_serve_requests_total",
                   (("outcome", "completed"),))] == 1
    # TTFT histogram coherence was already enforced by parse_prometheus;
    # spot-check the ladder is the shared default
    les = sorted(float(l["le"]) for n, l, _ in parsed["samples"]
                 if n == "mingpt_serve_ttft_seconds_bucket"
                 and l["le"] != "+Inf")
    assert les == sorted(LATENCY_BUCKETS_S)


def test_serving_metrics_backcompat_surface():
    """The attribute surface pre-existing tests and serve.py read must
    survive the move onto registry instruments."""
    from mingpt_distributed_tpu.serving.metrics import ServingMetrics

    m = ServingMetrics(n_slots=4)
    m.on_submit()
    m.on_submit()
    m.on_reject()
    m.on_prefill_chunk(n_tokens=5, bucket=8, seconds=0.01)
    m.on_prefill_chunk(n_tokens=3, bucket=4, seconds=0.01)
    m.on_prefill(ttft_s=0.1, stall_s=0.05)
    m.on_tokens(2)
    m.on_complete(n_generated=2, gen_span_s=0.1)
    m.on_step(queue_depth=1, slots_active=2, lanes_used=1)
    assert m.requests_submitted == 2
    assert m.requests_rejected == 1
    assert m.requests_completed == 1
    assert m.prefill_chunks == 2
    assert m.prefill_tokens == 8
    assert m.prefill_padded_tokens == 12
    assert m.bucket_histogram == {8: 1, 4: 1}
    assert m.bucket_histogram.get(4) == 1
    assert m.ttft_mean_s == pytest.approx(0.1)
    assert m.itl_mean_s == pytest.approx(0.1)
    assert m.admission_stall_mean_s == pytest.approx(0.05)
    assert m.prefill_pad_overhead == pytest.approx(12 / 8)
    assert m.slot_utilization == pytest.approx(0.25)
    assert m.queue_depth == 1 and m.slots_active == 2
    s = m.summary()
    assert s["requests_submitted"] == 2
    assert s["bucket_histogram"] == {"4": 1, "8": 1}
    json.dumps(s)  # summary must stay JSON-serializable


# ---------------------------------------------------------------------------
# JSONL event schema
# ---------------------------------------------------------------------------


def test_jsonl_sink_schema(tmp_path):
    p = tmp_path / "events.jsonl"
    sink = JsonlEventSink(str(p))
    sink.write("train_step", {"step": 1, "loss": 3.0})
    sink.write("custom", {"ts": 123.0, "x": "y"})
    sink.close()
    recs = [json.loads(l) for l in p.read_text().splitlines()]
    assert all(r["schema"] == telemetry.SCHEMA_VERSION for r in recs)
    assert recs[0]["kind"] == "train_step"
    assert recs[0]["loss"] == 3.0          # legacy flat keys preserved
    assert isinstance(recs[0]["ts"], float)
    assert recs[1]["ts"] == 123.0          # caller timestamps win


def test_metrics_logger_jsonl_is_versioned(tmp_path):
    from mingpt_distributed_tpu.config import GPTConfig
    from mingpt_distributed_tpu.training.metrics import MetricsLogger

    cfg = GPTConfig.make(n_layer=2, n_head=2, n_embd=32, vocab_size=64,
                         block_size=16)
    p = tmp_path / "m.jsonl"
    log = MetricsLogger(cfg, jsonl_path=str(p))
    log.log_step(1, 512, 16, {"loss": 3.0})
    log.close()
    [rec] = [json.loads(l) for l in p.read_text().splitlines()]
    assert rec["schema"] == telemetry.SCHEMA_VERSION
    assert rec["kind"] == "train_step"
    assert rec["step"] == 1 and rec["loss"] == 3.0


# ---------------------------------------------------------------------------
# Span tracer
# ---------------------------------------------------------------------------


def test_spans_nest_and_record_depth():
    tr = SpanTracer()
    with tr.span("train.step", step=3):
        with tr.span("train.snapshot"):
            pass
    inner, outer = tr.records()  # inner exits (and records) first
    assert inner["name"] == "train.snapshot" and inner["depth"] == 1
    assert outer["name"] == "train.step" and outer["depth"] == 0
    assert outer["step"] == 3
    assert outer["dur_s"] >= inner["dur_s"] >= 0
    assert outer["kind"] == "span"


def test_span_ids_and_parents_nest_per_thread():
    """``parent`` is the id of the span open around this one on the SAME
    thread; ids are unique across threads."""
    import threading

    tr = SpanTracer()
    inside = threading.Event()
    release = threading.Event()

    def worker():
        with tr.span("w.outer"):
            inside.set()
            release.wait(5)
            with tr.span("w.inner"):
                pass

    with tr.span("m.outer"):
        t = threading.Thread(target=worker)
        t.start()
        assert inside.wait(5)
        # opened while the worker's span is open: still a child of m.outer
        with tr.span("m.inner"):
            tr.event("m.mark")
        release.set()
        t.join()
    by = {r["name"]: r for r in tr.records()}
    assert by["m.outer"]["parent"] is None and by["w.outer"]["parent"] is None
    assert by["m.inner"]["parent"] == by["m.outer"]["id"]
    assert by["w.inner"]["parent"] == by["w.outer"]["id"]
    assert by["m.inner"]["depth"] == by["w.inner"]["depth"] == 1
    assert by["m.mark"]["depth"] == 2 and "id" not in by["m.mark"]
    ids = [r["id"] for r in tr.records() if r["kind"] == "span"]
    assert len(set(ids)) == 4
    # self time: a parent's duration covers its children's
    assert by["m.outer"]["dur_s"] >= by["m.inner"]["dur_s"]


def test_add_span_files_a_finished_wait_under_the_open_span():
    tr = SpanTracer()
    with tr.span("serve.admit", request_id="r"):
        tr.add_span("serve.queue_wait", 0.5, request_id="r")
    wait, admit = tr.records()
    assert wait["kind"] == "span" and wait["dur_s"] == 0.5
    assert wait["parent"] == admit["id"] and wait["depth"] == 1
    assert wait["ts"] == pytest.approx(admit["ts"] - 0.5, abs=0.05)
    assert wait["request_id"] == "r" and wait["id"] != admit["id"]


def test_span_attrs_can_be_set_inside_the_span():
    tr = SpanTracer()
    with tr.span("serve.prefill_chunk", pos=0) as sp:
        sp.set(padded=64)
    [rec] = tr.records()
    assert rec["pos"] == 0 and rec["padded"] == 64
    with SpanTracer(enabled=False).span("x") as noop:
        noop.set(padded=64)      # the shared no-op takes it and keeps nothing


def test_enabled_span_holds_a_profiler_annotation_open(monkeypatch):
    from mingpt_distributed_tpu.telemetry import spans as spans_mod

    log = []

    class Ann:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            log.append(("open", self.name))

        def __exit__(self, *exc):
            log.append(("close", self.name))

    monkeypatch.setattr(spans_mod, "_annotation", Ann)
    tr = SpanTracer()
    with tr.span("a"):
        with tr.span("b", k=1):
            assert log == [("open", "a"), ("open", "b")]
    assert log[2:] == [("close", "b"), ("close", "a")]
    # a disabled tracer opens none, through span(), add_span() or event()
    del log[:]
    off = SpanTracer(enabled=False)
    with off.span("a"):
        off.add_span("w", 1.0)
        off.event("e")
    assert log == [] and off.records() == [] and off.emitted == 0


def test_span_ring_is_bounded():
    tr = SpanTracer(capacity=8)
    for i in range(20):
        tr.event("e", i=i)
    assert len(tr.records()) == 8
    assert tr.emitted == 20
    assert tr.dropped == 12
    assert [r["i"] for r in tr.records()] == list(range(12, 20))


def test_disabled_tracer_is_noop_and_allocation_free():
    tr = SpanTracer(enabled=False)
    a = tr.span("x")
    b = tr.span("y")
    assert a is b  # one shared no-op context manager
    with a:
        pass
    tr.event("e")
    assert tr.records() == []


def test_tracer_streams_to_jsonl(tmp_path):
    p = tmp_path / "spans.jsonl"
    tr = SpanTracer()
    tr.attach_jsonl(str(p))
    with tr.span("serve.decode_round", lanes=2):
        pass
    tr.event("recompile", family="decode")
    tr.close()
    recs = [json.loads(l) for l in p.read_text().splitlines()]
    assert [r["kind"] for r in recs] == ["span", "event"]
    assert recs[0]["schema"] == telemetry.SCHEMA_VERSION
    assert recs[0]["name"] == "serve.decode_round"
    assert recs[0]["lanes"] == 2
    assert recs[1]["family"] == "decode"


def test_log_event_prefixes_and_mirrors(capsys):
    tr = SpanTracer()
    log_event("Snapshot not found. Training model from scratch", tracer=tr)
    out = capsys.readouterr().out
    assert re.match(r"^\[p\d+\] Snapshot not found", out)
    assert "from scratch" in out  # the substring existing tests rely on
    [rec] = tr.records()
    assert rec["kind"] == "event" and rec["name"] == "log"
    assert "from scratch" in rec["message"]


# ---------------------------------------------------------------------------
# Recompile watchdog
# ---------------------------------------------------------------------------


def _counts_fn(box):
    return lambda: dict(box)


def test_watchdog_unarmed_is_dormant():
    box = {"prefill": 0, "decode": 0}
    wd = RecompileWatchdog(_counts_fn(box), registry=MetricsRegistry())
    box["decode"] = 5  # pre-warmup compiles are free
    assert wd.check() == 0
    assert not wd.armed and wd.recompiles == 0


def test_watchdog_counts_each_trace_once():
    box = {"prefill": 2, "decode": 1}
    reg = MetricsRegistry()
    tr = SpanTracer()
    wd = RecompileWatchdog(_counts_fn(box), registry=reg, tracer=tr)
    wd.arm()
    assert wd.check() == 0
    box["prefill"] = 4
    assert wd.check() == 2       # growth reported...
    assert wd.check() == 0       # ...exactly once (baseline advanced)
    assert wd.recompiles == 2
    fam = reg.counter("mingpt_recompiles_total", labels=("family",))
    assert fam.labels(family="prefill").value == 2
    assert any(r["name"] == "recompile" for r in tr.records())


def test_watchdog_hard_fail_raises():
    box = {"decode": 1}
    wd = RecompileWatchdog(_counts_fn(box), registry=MetricsRegistry(),
                           hard_fail=True)
    wd.arm()
    box["decode"] = 2
    with pytest.raises(RecompileError, match="decode"):
        wd.check()


def test_watchdog_hard_fail_via_env(monkeypatch):
    monkeypatch.setenv("MINGPT_RECOMPILE_FATAL", "1")
    box = {"decode": 0}
    wd = RecompileWatchdog(_counts_fn(box), registry=MetricsRegistry())
    wd.arm()
    box["decode"] = 1
    with pytest.raises(RecompileError):
        wd.check()


# ---------------------------------------------------------------------------
# HTTP endpoint
# ---------------------------------------------------------------------------


def test_telemetry_server_serves_metrics_and_healthz():
    reg = MetricsRegistry()
    reg.counter("mingpt_test_requests_total").inc(4)
    srv = TelemetryServer(reg, port=0)  # ephemeral: parallel-test safe
    try:
        with urllib.request.urlopen(srv.url("/metrics"), timeout=10) as r:
            assert r.status == 200
            assert r.headers["Content-Type"].startswith("text/plain")
            parsed = parse_prometheus(r.read().decode())
        assert ("mingpt_test_requests_total", {}, 4.0) in parsed["samples"]
        with urllib.request.urlopen(srv.url("/healthz"), timeout=10) as r:
            health = json.loads(r.read().decode())
        assert health["status"] == "ok"
        assert health["uptime_s"] >= 0
        with pytest.raises(urllib.error.HTTPError) as exc:
            urllib.request.urlopen(srv.url("/nope"), timeout=10)
        assert exc.value.code == 404
    finally:
        srv.close()


def test_telemetry_server_scrape_reflects_live_updates():
    reg = MetricsRegistry()
    g = reg.gauge("mingpt_test_live")
    srv = TelemetryServer(reg, port=0)
    try:
        for want in (1.5, -2.0):
            g.set(want)
            with urllib.request.urlopen(srv.url("/metrics"), timeout=10) as r:
                parsed = parse_prometheus(r.read().decode())
            assert ("mingpt_test_live", {}, want) in parsed["samples"]
    finally:
        srv.close()


# ---------------------------------------------------------------------------
# Roofline peaks (satellite a)
# ---------------------------------------------------------------------------


def test_peak_tables_share_keys_and_prefix_order():
    assert set(PEAK_FLOPS) == set(PEAK_HBM_BYTES)
    for table in (PEAK_FLOPS, PEAK_HBM_BYTES):
        keys = list(table)
        # longest-prefix-wins depends on dict order: every key must come
        # before any strict prefix of itself ("TPU v5 lite" < "TPU v5")
        for i, k in enumerate(keys):
            for j, other in enumerate(keys):
                if k != other and k.startswith(other):
                    assert i < j, f"{k!r} shadowed by earlier {other!r}"
        assert all(v > 0 and math.isfinite(v) for v in table.values())
    # the new generations ride along with sane monotonic-ish growth
    assert PEAK_FLOPS["TPU v6e"] > PEAK_FLOPS["TPU v5p"]
    assert PEAK_FLOPS["TPU v7"] > PEAK_FLOPS["TPU v6e"]


def test_training_metrics_reexports_peaks():
    # pre-existing imports keep working after the dedupe
    from mingpt_distributed_tpu.training import metrics as tm

    assert tm.PEAK_FLOPS is PEAK_FLOPS
    assert tm.PEAK_HBM_BYTES is PEAK_HBM_BYTES
    assert tm.RateWindow is RateWindow
    assert tm.peak_flops_per_chip is telemetry.peak_flops_per_chip


def test_get_registry_and_tracer_are_process_singletons():
    assert telemetry.get_registry() is telemetry.get_registry()
    assert telemetry.get_tracer() is telemetry.get_tracer()


# ---------------------------------------------------------------------------
# programs that file themselves (ISSUE 55)
# ---------------------------------------------------------------------------


def test_a_filing_program_files_itself_once_and_is_the_jit_otherwise():
    import jax
    import jax.numpy as jnp

    index = program_lib._Index(capacity=4)

    def f(x, scale=2.0):
        with jax.named_scope("ffn"):
            return x * scale

    step = program_lib.Filing(jax.jit(f), "f", "v", index)
    # a trace of the wrapper is no call of the program
    jax.make_jaxpr(step)(jnp.ones(3))
    assert len(index) == 0
    assert step(jnp.ones(3), scale=3.0).tolist() == [3.0, 3.0, 3.0]
    assert step(jnp.ones(3), scale=4.0).tolist() == [4.0, 4.0, 4.0]
    assert len(index) == 1
    (family, variant, jitted, args, kwargs), _ = index._entries[step]
    assert (family, variant, jitted) == ("f", "v", step.jitted)
    assert isinstance(args[0], jax.ShapeDtypeStruct) and args[0].shape == (3,)
    assert kwargs["scale"].shape == ()          # abstract too: no array kept
    # everything else is the jitted program's
    assert "mul" in step.lower(jnp.ones(3)).as_text()
    assert step.trace(jnp.ones(3)).jaxpr is not None
    [record] = index.records()
    assert record["kind"] == "program" and record["name"] == "jit_f"
    assert record["lowered_scopes"] == ["ffn"] and "stale_scopes" not in record
    assert "ffn" in set(record["scopes"].values())
    json.dumps(record)                          # plain data


def test_the_index_is_bounded_and_keeps_the_newest():
    import jax
    import jax.numpy as jnp

    index = program_lib._Index(capacity=2)
    steps = [program_lib.Filing(jax.jit(lambda x, k=k: x + k), "f", str(k),
                                index) for k in range(3)]
    for step in steps:
        assert float(step(jnp.ones(()))) == 1.0 + int(step.variant)
        step(jnp.ones(()))                  # a second call files nothing
    assert len(index) == 2
    assert [r["variant"] for r in index.records()] == ["1", "2"]
    assert index.record_of(steps[0]) is None
    # an owner that still holds the evicted program files it again
    again = steps[0].record((jnp.ones(()),), {})
    assert again["variant"] == "0" and len(index) == 2
    assert [r["variant"] for r in index.records()] == ["2", "0"]
