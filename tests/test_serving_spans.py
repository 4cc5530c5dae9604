"""The spans of a scheduling round (ISSUE 23): one span system, read three
ways: the tracer's ring, the per-request traces, the profiler's host plane.
CPU, tiny width, `not slow` tier."""

import glob
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mingpt_distributed_tpu.config import GPTConfig
from mingpt_distributed_tpu.models import gpt
from mingpt_distributed_tpu.serving import InferenceServer, Request
from mingpt_distributed_tpu.serving.engine import DecodeEngine
from mingpt_distributed_tpu.telemetry import SpanTracer, TraceRecorder

CHILDREN = ["serve.fold_keys", "serve.decode_launch", "serve.decode_sync",
            "serve.emit"]
GOLDEN = os.path.join(os.path.dirname(__file__), "data",
                      "request_trace_golden.json")
PROMPTS = [[1, 2, 3, 4, 5, 6, 7, 8, 9], [5, 6, 7],
           [1, 2, 3, 4, 5, 6, 7, 8, 9, 10, 11]]


@pytest.fixture(scope="module")
def cfg_params():
    cfg = GPTConfig.make(
        n_layer=2, n_head=2, n_embd=32, vocab_size=50, block_size=32,
        embd_pdrop=0.0, resid_pdrop=0.0, attn_pdrop=0.0, dtype="float32",
    )
    return cfg, gpt.init(jax.random.key(0), cfg)


def spans_of(tracer, name=None):
    return [r for r in tracer.records() if r["kind"] == "span"
            and (name is None or r["name"] == name)]


def served(cfg_params, tracer, **kw):
    cfg, params = cfg_params
    return InferenceServer(params, cfg, n_slots=2, tracer=tracer, **kw)


def kids_of(tracer, round_):
    kids = [r for r in spans_of(tracer) if r["parent"] == round_["id"]]
    # in the order the round runs them, all one level down
    assert sorted(kids, key=lambda r: r["ts"]) == kids
    assert all(r["depth"] == 1 for r in kids)
    assert 0 < sum(r["dur_s"] for r in kids) <= round_["dur_s"]
    return kids


def test_one_round_has_one_of_each_child_under_one_decode_round(cfg_params):
    """A steady round, one with a step in flight that may be run ahead of:
    the launch of the next step, the sync of the last, the emit."""
    tracer = SpanTracer()
    srv = served(cfg_params, tracer)
    for i, p in enumerate(PROMPTS[:2]):
        srv.submit(Request(prompt=p, max_new_tokens=5, request_id=f"r{i}"))
    srv.step()          # nothing in flight yet: its own case, below
    srv.step()
    first, round_ = spans_of(tracer, "serve.decode_round")
    assert round_["parent"] is None and round_["lanes"] == 2
    kids = kids_of(tracer, round_)
    # one of each a round (never one a lane)
    assert [r["name"] for r in kids] == CHILDREN
    assert kids[0]["lanes"] == 2
    # a third round adds exactly one more of each
    before = {name: len(spans_of(tracer, name)) for name in CHILDREN}
    srv.step()
    for name in CHILDREN:
        assert len(spans_of(tracer, name)) == before[name] + 1, name
    assert len(spans_of(tracer, "serve.decode_round")) == 3


def test_a_round_with_nothing_in_flight_launches_twice(cfg_params):
    """The first decode round, and the round after one that synced first:
    the step it syncs and the step ahead of it are both its own launches."""
    tracer = SpanTracer()
    srv = served(cfg_params, tracer)
    for i, p in enumerate(PROMPTS[:2]):
        srv.submit(Request(prompt=p, max_new_tokens=5, request_id=f"r{i}"))
    srv.step()
    [round_] = spans_of(tracer, "serve.decode_round")
    assert round_["parent"] is None and round_["lanes"] == 2
    assert [r["name"] for r in kids_of(tracer, round_)] == [
        "serve.fold_keys", "serve.decode_launch"] * 2 + [
        "serve.decode_sync", "serve.emit"]
    s = srv.summary()
    assert (s["decode_launches"], s["decode_rounds_ahead"]) == (2, 1)


def test_a_round_that_may_not_run_ahead_syncs_and_emits_alone(cfg_params):
    """The token in flight is its request's last by length: the round
    launches nothing, and leaves nothing in flight."""
    tracer = SpanTracer()
    srv = served(cfg_params, tracer)
    h = srv.submit(Request(prompt=PROMPTS[1], max_new_tokens=3))
    srv.step()          # the prompt's token and one more; the last in flight
    assert len(h.tokens) == 2 and len(srv._flight) == 1
    assert not srv.step()
    assert h.finish_reason == "length" and len(h.tokens) == 3
    assert not srv._flight
    _, last = spans_of(tracer, "serve.decode_round")
    assert [r["name"] for r in kids_of(tracer, last)] == [
        "serve.decode_sync", "serve.emit"]
    s = srv.summary()
    assert (s["decode_launches"], s["decode_rounds_ahead"],
            s["decode_lane_steps_discarded"]) == (2, 1, 0)


def test_queue_wait_and_prefill_spans_need_no_trace_recorder(cfg_params):
    tracer = SpanTracer()
    srv = served(cfg_params, tracer, prefill_chunk=4, prefill_buckets=(4, 8))
    assert srv.trace_recorder is None
    srv.submit(Request(prompt=PROMPTS[0], max_new_tokens=2, request_id="q"))
    srv.run_until_drained()
    [wait] = spans_of(tracer, "serve.queue_wait")
    [admit] = spans_of(tracer, "serve.admit")
    [lookup] = spans_of(tracer, "serve.prefix_lookup")
    assert wait["request_id"] == "q" and wait["dur_s"] >= 0
    assert wait["parent"] == admit["id"] == lookup["parent"]
    assert lookup["hit_rows"] == 0
    chunks = spans_of(tracer, "serve.prefill_chunk")
    assert [(c["pos"], c["tokens"], c["padded"]) for c in chunks] == [
        (0, 4, 4), (4, 4, 4), (8, 1, 4)]
    assert all(c["request_id"] == "q" and c["parent"] is None for c in chunks)
    assert "trace_id" not in admit        # the ring carries no per-trace ids


def test_disabled_tracer_records_nothing_through_the_server(cfg_params):
    srv = served(cfg_params, None)
    assert not srv.tracer.enabled and srv.engine.tracer is srv.tracer
    srv.submit(Request(prompt=PROMPTS[1], max_new_tokens=3))
    srv.run_until_drained()
    assert srv.tracer.records() == [] and srv.tracer.emitted == 0


def test_the_engine_alone_has_a_disabled_tracer(cfg_params):
    cfg, params = cfg_params
    eng = DecodeEngine(params, cfg, 2)
    assert not eng.tracer.enabled


class _Sink:
    schema = "mingpt-trace/1"

    def __init__(self):
        self.out = []

    def write(self, kind, payload):
        self.out.append(dict(payload, kind=kind))

    def close(self):
        pass


class _RoundClock:
    """Moves only between rounds: every stamp of a round is the same."""

    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


@pytest.mark.parametrize("mode", ["plain", "spec", "ahead"])
def test_request_traces_are_field_for_field_what_they_were(
        cfg_params, mode, monkeypatch):
    """The per-request records of three requests through two slots, against
    the same run of the commit before the call sites were merged (PR 22:
    chunked prefill with a prefix hit, and speculation). ``plain`` is the
    synchronous order (no step is launched ahead of one in flight: the
    server's own rule, answered by a double) and ``spec`` keeps it by the
    rule; ``ahead`` is the same traffic one step ahead (PR 43): the same
    records, but that a request which starts decoding behind a step in
    flight has its second token a round later, and a round that syncs
    first counts the lanes it holds."""
    cfg, params = cfg_params
    if mode == "plain":
        monkeypatch.setattr(InferenceServer, "_may_launch",
                            lambda self, lanes: not self._flight)
    sink, clock = _Sink(), _RoundClock()
    rec = TraceRecorder(sink=sink, sample=1.0)
    kw = (dict(draft_params=params, draft_cfg=cfg, spec_k=2) if mode == "spec"
          else dict(prefill_chunk=4, prefix_cache_mb=1.0,
                    prefill_buckets=(4, 8)))
    tracer = SpanTracer()
    srv = InferenceServer(params, cfg, n_slots=2, clock=clock,
                          trace_recorder=rec, tracer=tracer, **kw)
    for i, p in enumerate(PROMPTS):
        srv.submit(Request(prompt=p, max_new_tokens=3 + i,
                           request_id=f"g{i}"))
    while True:
        clock.t += 0.25
        if not srv.step():
            break
    with open(GOLDEN, encoding="utf-8") as f:
        want = json.load(f)[mode]
    assert len(sink.out) == len(want)
    for got, exp in zip(sink.out, want):
        assert got == exp
    # and the ring saw the same phases, once each
    n = lambda name, recs: sum(r.get("name") == name for r in recs)
    for name in ("serve.queue_wait", "serve.prefix_lookup",
                 "serve.prefill_chunk"):
        assert n(name, spans_of(tracer)) == n(name, want), name
    if mode == "spec":
        # a round of speculating lanes only: the shared phases and no more
        names = {r["name"] for r in spans_of(tracer)}
        assert {"serve.decode_round", "serve.fold_keys", "serve.emit"} <= names


def test_a_profile_of_two_rounds_holds_the_span_names(cfg_params, tmp_path):
    """Spans are profiler annotations too: a capture shows them in a host
    plane, on the profiler's clock."""
    from jax.profiler import ProfileData

    tracer = SpanTracer()
    srv = served(cfg_params, tracer, warmup=True)
    for i, p in enumerate(PROMPTS[:2]):
        srv.submit(Request(prompt=p, max_new_tokens=4, request_id=f"p{i}"))
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        srv.step()
        srv.step()
    finally:
        jax.profiler.stop_trace()
    [path] = glob.glob(os.path.join(
        str(tmp_path), "plugins", "profile", "*", "*.xplane.pb"))
    seen = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("serve."):
                        seen[e.name] = seen.get(e.name, 0) + 1
    # the first round launches twice (nothing was in flight), the second once
    for name in CHILDREN + ["serve.decode_round"]:
        assert seen.get(name) == 2 + (
            name in ("serve.fold_keys", "serve.decode_launch")), (name, seen)
    assert seen.get("serve.admit") == 2 and seen.get("serve.prefill_chunk") == 2
    # a wait filed at its end is a ring record and no annotation
    assert "serve.queue_wait" not in seen
    assert len(spans_of(tracer, "serve.queue_wait")) == 2


@pytest.mark.parametrize("attr,part", [
    ("_decode_jit", "decode_impl"), ("_prefill_jit", "prefill_impl"),
    ("_extract_jit", "extract_prefix_impl"),
    ("_install_jit", "install_prefix_impl")])
def test_engine_programs_are_jitted_under_their_own_names(cfg_params, attr, part):
    cfg, params = cfg_params
    eng = DecodeEngine(params, cfg, 2, prefix_cache_mb=1.0)
    fn = getattr(eng, attr)
    assert part in fn.__name__ and "unknown" not in fn.__name__
    s = eng.n_slots
    cache = eng.pool.cache
    args = {
        "_decode_jit": lambda: (eng.params, cache, jnp.zeros(s, jnp.int32),
                                jnp.zeros(s, jnp.int32), jnp.ones(s),
                                jnp.zeros(s, jnp.int32), jnp.ones(s),
                                jnp.zeros(s, bool), jnp.zeros(s, jnp.uint32),
                                jnp.zeros(s, jnp.int32)),
        "_prefill_jit": lambda: (eng.params, cache, jnp.zeros(32, jnp.int32),
                                 np.int32(3), np.int32(0), np.int32(0),
                                 np.float32(1), np.int32(0), np.float32(1),
                                 np.bool_(False), np.uint32(0)),
        "_extract_jit": lambda: (cache, np.int32(0)),
        "_install_jit": lambda: (
            cache, {n: a[:, :1, :32] for n, a in cache.items()}, np.int32(0)),
    }[attr]()
    kwargs = {"rows": 32} if attr == "_extract_jit" else {}
    text = fn.lower(*args, **kwargs).as_text()
    assert f"module @jit_{fn.__name__}" in text


def test_the_verify_program_is_named_too(cfg_params):
    cfg, params = cfg_params
    srv = InferenceServer(params, cfg, n_slots=2, draft_params=params,
                          draft_cfg=cfg, spec_k=2)
    assert srv.spec._verify_jit.__name__ == "_verify_impl"


def test_decode_step_reads_typed_keys_as_request_keys(cfg_params):
    """What ``benchmarks/harness/check.py`` still passes: the (S,) typed
    keys ``key(seed)`` where the seeds go, and no token index. They run the
    one decode program, as the seeds they were made from."""
    cfg, params = cfg_params
    eng = DecodeEngine(params, cfg, 2)
    outs = []
    for given in (np.array([3, 4], np.uint32),
                  jnp.stack([jax.random.key(3), jax.random.key(4)])):
        outs.append(eng.decode_step(
            np.array([1, 2], np.int32), np.array([0, 0], np.int32),
            np.ones(2, np.float32), np.zeros(2, np.int32),
            np.ones(2, np.float32), np.ones(2, bool), given))
    assert outs[0].tolist() == outs[1].tolist()
    assert eng.compile_counts()["decode"] == 1
    tok, _ = eng.prefill_chunk_call(0, [1, 2, 3], 0, 1.0, None, None, True, 9)
    again, _ = eng.prefill_chunk_call(
        0, [1, 2, 3], 0, 1.0, None, None, True, jax.random.key(9))
    assert tok == again and eng.compile_counts()["prefill"] == 1
