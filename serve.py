#!/usr/bin/env python
"""Continuous-batching inference server entry point.

Where sample.py decodes ONE prompt per process, this CLI drives the
serving/ subsystem: a slot-based KV scheduler over the compiled decode
path that admits new prompts mid-decode, streams tokens per request as
they are produced, and reports serving metrics (tokens/sec, queue depth,
slot utilization, TTFT, inter-token latency).

Modes (checkpoint restore is shared with sample.py —
training.checkpoint.restore_inference_params):

  REPL (default)      read prompts from stdin one line at a time, stream
                      the completion as it decodes:
                        python serve.py [--config gpt2_config.yaml]
  offline batch       drain a file of prompts (one per line) through the
                      scheduler concurrently, print the completions:
                        python serve.py --prompts-file prompts.txt
  self-test           no checkpoint needed: random-init tiny model, three
                      canned prompts through 2 slots (forces queueing),
                      greedy outputs verified token-identical to solo
                      generate() and the no-recompile guarantee asserted —
                      the CI end-to-end gate (run_tests.sh):
                        python serve.py --selftest

Common knobs: --slots N, --max-new-tokens, --temperature, --top-k,
--top-p, --greedy, --eos-text STR (stop when the encoded token appears),
--metrics-json PATH, --log-every N, plus section.key=value config
overrides as in train.py/sample.py.

Telemetry (ISSUE 5): --metrics-port P exposes Prometheus /metrics and
/healthz from the process-wide telemetry registry (0 = ephemeral port,
printed to stderr); the selftest additionally self-scrapes the page,
validates it with the strict exposition parser, and asserts the
recompile watchdog counted zero post-warmup traces.

Robustness knobs (ISSUE 2): --queue-limit N bounds the request queue
(over-limit submissions are rejected with a clean error instead of
growing without bound); --deadline-s S expires requests that exceed
their deadline, queued or mid-decode, so an abandoned request can't pin
a KV slot. One failing prompt (encode error, validation error, queue
rejection) is reported and skipped — the engine keeps serving.

Prefill knobs (ISSUE 3): --prefill-buckets "64,128,..." compiles a
bounded ladder of prefill lengths (default: powers of two from 64) so a
short prompt pays a short forward instead of a block_size² one;
--prefill-chunk N prefills long prompts in N-token chunks between decode
steps, bounding co-tenant inter-token latency by one chunk;
--prefix-cache-mb M keeps an LRU of shared-prefix KV rows so a request
repeating a cached prompt head (system prompts) copies rows instead of
recomputing them; --warmup pre-traces the whole ladder at start.

Fleet knobs (ISSUE 6): --replicas N serves through N supervised
in-process engine replicas behind the health/affinity Router
(serving/fleet.py) — crashed replicas restart with backoff, their
requests retry idempotently on survivors; --shed-watermark D sheds new
requests once the fleet-wide queue depth reaches D; --chaos-spec (or
MINGPT_SERVING_FAULTS) injects deterministic serving faults
(crash/poison/slow/admit, same grammar as training/faults.py). Graceful
shutdown everywhere: SIGTERM (or one SIGINT) stops admission, drains
in-flight requests, flushes metrics and exits 75 (EX_TEMPFAIL, the
trainer's requeue convention; a second SIGINT aborts hard). The
--selftest-chaos gate (run_tests.sh) runs canned prompts through 3
replicas under an injected crash-mid-decode + slow replica and asserts
greedy parity with solo generate(), zero duplicate streamed tokens and
the breaker/retry/shed counters on a strict-parsed /metrics scrape.

Control plane (ISSUE 20): --autoscale SPEC attaches the SLO autoscaler
(mingpt_distributed_tpu/control) to the fleet router — it watches live
TTFT/ITL quantiles and queue depth each scheduling round and actuates
replica count (spawn / drain-then-retire), speculation gating, prefill
chunking and the shed watermark under hysteresis + cooldown;
--slo-target X is shorthand for --autoscale auto:target=X;
--control-log PATH appends each mingpt-control/1 decision row live.
Either flag implies the fleet path even at --replicas 1.

Observability knobs (ISSUE 10): --trace-jsonl PATH exports one
``mingpt-trace/1`` record stream per request (spans + emit events + a
request summary), --trace-sample P samples the happy path (errors,
sheds and retries always export); --flight-dir DIR arms the crash
flight recorder — recent spans/events/metrics dumped atomically on
crash, breaker trip, watchdog recompile and SIGTERM drain, and
on-demand via GET /debug/flight on the telemetry server; --slo [SPEC]
prints a graded SLO report (TTFT/ITL percentiles + shed rate from
exact per-request trace durations) at shutdown; --slo-json PATH writes
the same report as machine-readable mingpt-slo/1 JSON, diffable with
tools/trace_summary.py --compare. With tracing on, the
chaos gate additionally strict-validates the exported trace stream
(one trace per request, attempt spans matching the retry count, zero
orphan spans) and the dumped flight records.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys


def build_argparser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--config", default="gpt2_config.yaml")
    p.add_argument("--slots", type=int, default=4)
    p.add_argument("--max-new-tokens", type=int, default=200)
    p.add_argument("--temperature", type=float, default=1.0)
    p.add_argument("--top-k", type=int, default=None)
    p.add_argument("--top-p", type=float, default=None)
    p.add_argument("--greedy", action="store_true",
                   help="argmax decoding (default: sample)")
    p.add_argument("--eos-text", default=None,
                   help="stop a request when this (single-token) text is "
                        "produced")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--prompts-file", default=None,
                   help="offline batch mode: one prompt per line")
    p.add_argument("--selftest", action="store_true",
                   help="random-init tiny model + canned prompts; verifies "
                        "greedy parity with generate() and exits")
    p.add_argument("--metrics-json", default=None,
                   help="write the serving metrics summary JSON here")
    p.add_argument("--log-every", type=int, default=20,
                   help="scheduler steps between metric log lines (0 = off)")
    p.add_argument("--queue-limit", type=int, default=None,
                   help="bound the request queue; over-limit submissions "
                        "are rejected (backpressure) instead of queueing "
                        "without bound")
    p.add_argument("--deadline-s", type=float, default=None,
                   help="per-request deadline in seconds; expired requests "
                        "free their KV slot (finish_reason=deadline)")
    p.add_argument("--prefill-buckets", default=None,
                   help="comma-separated ladder of compiled prefill "
                        "lengths (default: powers of two from 64 up to "
                        "block_size); prompts pad to the smallest "
                        "covering bucket")
    p.add_argument("--prefill-chunk", type=int, default=None,
                   help="prefill long prompts in chunks of this many "
                        "tokens between decode steps (default: whole "
                        "prompt in one call)")
    p.add_argument("--prefix-cache-mb", type=float, default=0.0,
                   help="LRU budget (MiB) for shared-prefix KV reuse; "
                        "0 disables the prefix store")
    p.add_argument("--kv-dtype", choices=("fp32", "int8", "fp8"),
                   default="fp32",
                   help="KV-cache storage dtype (ISSUE 18): int8 stores "
                        "quantized K/V payloads + fp32 scale planes "
                        "(~0.27x the pool bytes at head_dim>=64 — ~4x "
                        "the decode lanes per chip); fp32 is the "
                        "byte-identical default path")
    p.add_argument("--selftest-quant", action="store_true",
                   help="ISSUE 18 gate: int8 KV pool with chunked "
                        "prefill + prefix store + speculation composed "
                        "— greedy token parity within tolerance vs the "
                        "fp32 server, identical compile_counts per "
                        "dtype, zero post-warmup recompiles, pool payload "
                        "+ scale planes <= 0.27x the fp32 pool bytes, and "
                        "a sampled max-abs-logit-error gauge; then "
                        "exits")
    p.add_argument("--warmup", action="store_true",
                   help="pre-trace the prefill bucket ladder and decode "
                        "step before serving (no first-request compile "
                        "stall)")
    p.add_argument("--draft-config", default=None,
                   help="speculative decoding draft model: 'self' (draft "
                        "= target weights), 'self:N' (first N layers of "
                        "the target), or comma-separated GPTConfig "
                        "overrides like 'n_layer=2,n_embd=64' (random "
                        "init); requires --spec-k >= 1")
    p.add_argument("--spec-k", type=int, default=0,
                   help="draft tokens proposed per verify round (0 = "
                        "speculation off); eligible greedy lanes then "
                        "emit 1..k+1 tokens per round, token-exact with "
                        "the plain greedy path")
    p.add_argument("--selftest-spec", action="store_true",
                   help="random-init tiny model: speculative decode must "
                        "be token-identical to the plain greedy path "
                        "(identical-draft and truncated-draft variants, "
                        "incl. chunked prefill + prefix reuse) with O(1) "
                        "verify executables; exits non-zero on mismatch")
    p.add_argument("--mesh", default=None, metavar="SPEC",
                   help="run the engine across a device mesh (ISSUE 14): "
                        "'axis=N' clauses joined by ',', e.g. 'tp=2' "
                        "shards params (megatron rules) and the KV pool's "
                        "heads over 2 devices so per-device KV bytes are "
                        "total/2; testable off-TPU via XLA_FLAGS="
                        "--xla_force_host_platform_device_count=N")
    p.add_argument("--selftest-sharded", action="store_true",
                   help="ISSUE 14 gate (run under forced host devices): "
                        "tp=2 server must be greedy token-identical to "
                        "tp=1 (incl. chunked prefill, prefix hits and "
                        "speculation), with identical compile_counts(), "
                        "zero watchdog recompiles, head-sharded prefix "
                        "entries and per-device pool bytes = total/2; "
                        "then exits")
    p.add_argument("--metrics-port", type=int, default=None,
                   help="serve Prometheus /metrics + /healthz on this port "
                        "(0 = ephemeral port, printed at start); default: "
                        "no endpoint")
    p.add_argument("--replicas", type=int, default=1,
                   help="serve through N supervised in-process engine "
                        "replicas behind the health/affinity router "
                        "(default 1: single server, no fleet layer)")
    p.add_argument("--autoscale", default=None, metavar="SPEC",
                   help="attach the SLO autoscaler to the fleet router: "
                        "'auto[:k=v...]' (control/controller.py grammar), "
                        "e.g. auto:metric=ttft_p99:target=0.05:"
                        "max_replicas=4; implies the fleet path even at "
                        "--replicas 1")
    p.add_argument("--slo-target", type=float, default=None, metavar="X",
                   help="shorthand for --autoscale auto:target=X (TTFT "
                        "p99 seconds the controller defends)")
    p.add_argument("--control-log", default=None, metavar="PATH",
                   help="append each mingpt-control/1 autoscaler "
                        "decision row to this JSONL file as it is made")
    p.add_argument("--shed-watermark", type=int, default=None,
                   help="fleet mode: shed new requests once the fleet-wide "
                        "queue depth reaches this watermark")
    p.add_argument("--isolation", choices=("thread", "process"),
                   default="thread",
                   help="fleet replica isolation (with --replicas > 1): "
                        "'thread' = in-process engine replicas (default, "
                        "back-compat); 'process' = each replica is a "
                        "spawned worker subprocess behind the mingpt-rpc/1 "
                        "socket surface, SIGKILL-able and independently "
                        "requeued (exit 75) on drain")
    p.add_argument("--spill-dir", default=None,
                   help="process isolation: root directory for per-worker "
                        "spill state (spec.json, stderr.log, flight dumps "
                        "collected by the supervisor on process death); "
                        "default: a temp directory")
    p.add_argument("--standby", type=int, default=0, metavar="N",
                   help="process isolation: keep N pre-warmed spare "
                        "workers (fully spawned, params restored, program "
                        "family warm); a crashed replica adopts a hot "
                        "spare instead of paying a cold respawn, and the "
                        "pool backfills off the recovery critical path "
                        "(default 0: cold respawns only)")
    p.add_argument("--hang-deadline", type=float, default=None,
                   metavar="SECONDS",
                   help="process isolation: arm the liveness escalation "
                        "ladder — a replica holding work that completes "
                        "no round for this long gets SIGTERM, then "
                        "SIGKILL after a grace window if it ignored the "
                        "term (wedged worker); default: no ladder")
    p.add_argument("--chaos-spec", default=None,
                   help="deterministic serving fault spec, e.g. "
                        "'crash:nth=6:match=replica0;slow:every=1:"
                        "delay=0.25:match=replica1' (default: "
                        "MINGPT_SERVING_FAULTS env; ops crash|poison|"
                        "slow|admit)")
    p.add_argument("--trace-jsonl", default=None,
                   help="export request-scoped traces (mingpt-trace/1 "
                        "JSONL: spans, emit events, one request summary "
                        "per trace) to this path")
    p.add_argument("--trace-sample", type=float, default=1.0,
                   help="happy-path trace sampling probability in [0, 1]; "
                        "errors, sheds and retried requests always export "
                        "(default 1.0)")
    p.add_argument("--flight-dir", default=None,
                   help="arm the flight recorder: dump recent "
                        "spans/events/metrics here (mingpt-flight/1, "
                        "atomic write + manifest) on crash, breaker trip, "
                        "recompile and SIGTERM drain; also enables GET "
                        "/debug/flight on --metrics-port")
    p.add_argument("--slo", nargs="?", const="default", default=None,
                   metavar="SPEC",
                   help="print a graded SLO report at shutdown from exact "
                        "per-request trace durations; SPEC is "
                        "'metric<=threshold' clauses (ttft_pNN, itl_pNN, "
                        "shed_rate, error_rate) joined by ','; bare --slo "
                        "uses the default objectives")
    p.add_argument("--slo-json", default=None, metavar="PATH",
                   help="write the shutdown SLO report as machine-readable "
                        "JSON (mingpt-slo/1, the same shape mingpt-traffic/1 "
                        "embeds) to PATH; two runs diff with "
                        "tools/trace_summary.py --compare a.json b.json. "
                        "Objectives come from --slo, or the defaults when "
                        "only --slo-json is given")
    p.add_argument("--selftest-chaos", action="store_true",
                   help="random-init tiny model through 3 replicas under "
                        "injected crash + slow faults; verifies greedy "
                        "parity, zero duplicate tokens and fleet metrics, "
                        "then exits")
    p.add_argument("--selftest-procfleet", action="store_true",
                   help="ISSUE 16 gate: 2 real replica subprocesses behind "
                        "the mingpt-rpc/1 socket surface; kill -9 one "
                        "mid-decode and verify crash-retry parity with "
                        "zero duplicate tokens, then drain-with-migration "
                        "and verify the migrated streams are bit-identical "
                        "with mingpt-trace/1 timelines spanning both "
                        "replicas; then exits")
    p.add_argument("--selftest-standby", action="store_true",
                   help="ISSUE 17 gate: real replica subprocesses again — "
                        "kill -9 under a warm-standby pool and verify the "
                        "adoption recovers strictly faster than the cold "
                        "respawn on the same fault; wedge a worker inside "
                        "the step RPC and verify the SIGTERM->SIGKILL "
                        "escalation ladder clears it; migrate a "
                        "speculative request and verify the peer resumes "
                        "proposing from shipped draft rows; then exits")
    p.add_argument("--hosts", type=int, default=1, metavar="N",
                   help="cross-host roster size for the hostplane drills "
                        "(ISSUE 19): each host runs a HostAgent owning "
                        "its own process-isolated replica fleet")
    p.add_argument("--fleet-secret", default=None, metavar="SECRET",
                   help="shared fleet secret: HMAC-sign every cross-host "
                        "control envelope over its canonical bytes; "
                        "unsigned/tampered/replayed frames are rejected "
                        "with typed errors and counted on "
                        "mingpt_fleet_auth_rejects_total. Default off — "
                        "single-host paths stay byte-identical")
    p.add_argument("--selftest-crosshost", action="store_true",
                   help="ISSUE 19 gate: two real localhost host agents, "
                        "each supervising real replica subprocesses — "
                        "SIGKILL a whole host mid-decode and verify the "
                        "peer adopts its requests with zero duplicate or "
                        "lost stream tokens; live-migrate cross-host "
                        "through the paced channel under a slow_link "
                        "spec and verify the wall-clock transfer "
                        "respects the bandwidth budget; post a tampered "
                        "control frame and verify the typed reject plus "
                        "auth counter; then exits")
    p.add_argument("overrides", nargs="*")
    return p


class _ShutdownGuard:
    """SIGTERM/SIGINT → stop admission, drain, flush, exit 75 — the same
    contract as trainer.py's preemption path. The first signal only sets
    the flag (the serving loop finishes in-flight work); a second SIGINT
    raises KeyboardInterrupt for a hard abort."""

    def __init__(self):
        self.stop_requested = False

    def install(self) -> "_ShutdownGuard":
        import signal

        def handler(signum, frame):
            if self.stop_requested and signum == signal.SIGINT:
                raise KeyboardInterrupt
            self.stop_requested = True
            print(f"[serve] caught signal {signum}: admission stopped, "
                  f"draining in-flight requests (SIGINT again to abort)",
                  file=sys.stderr, flush=True)

        signal.signal(signal.SIGTERM, handler)
        signal.signal(signal.SIGINT, handler)
        return self


def _parse_buckets(spec):
    if spec is None:
        return None
    try:
        return tuple(int(b) for b in str(spec).split(",") if b.strip())
    except ValueError:
        raise SystemExit(f"--prefill-buckets must be comma-separated ints, "
                         f"got {spec!r}")


def _server_kwargs(args) -> dict:
    """The prefill-overhaul knobs, shared by every server construction."""
    return dict(
        prefill_buckets=_parse_buckets(args.prefill_buckets),
        prefill_chunk=args.prefill_chunk,
        prefix_cache_mb=args.prefix_cache_mb,
        warmup=args.warmup,
        kv_dtype=getattr(args, "kv_dtype", "fp32"),
    )


def _mesh_kwargs(args) -> dict:
    """Resolve --mesh 'axis=N,...' into InferenceServer mesh kwargs
    (empty dict = single-device serving, byte-identical to before the
    flag existed). Builds the named mesh over the first prod(N) local
    devices — serving shards one model replica, it does not claim the
    whole host's device set the way training does."""
    if args.mesh is None:
        return {}
    import math

    from mingpt_distributed_tpu.parallel.mesh import (
        AXES,
        MeshConfig,
        make_mesh,
    )

    overrides = {}
    for clause in str(args.mesh).split(","):
        k, sep, v = clause.partition("=")
        k = k.strip()
        if not sep or k not in AXES:
            raise SystemExit(f"--mesh clause {clause!r} is not axis=N "
                             f"(axes: {', '.join(AXES)})")
        try:
            overrides[k] = int(v)
        except ValueError:
            raise SystemExit(f"--mesh {k}={v!r}: extent must be an int")
    import jax

    need = math.prod(overrides.values())
    have = len(jax.devices())
    if need > have:
        raise SystemExit(
            f"--mesh {args.mesh!r} needs {need} devices, have {have} "
            f"(off-TPU: XLA_FLAGS=--xla_force_host_platform_device_count"
            f"={need})")
    mesh = make_mesh(MeshConfig(**overrides), devices=jax.devices()[:need])
    return dict(mesh=mesh)


def _draft_from(spec, params, cfg):
    """Resolve --draft-config into (draft_params, draft_cfg).

    'self' shares the target weights outright (accept rate 1.0 — the
    plumbing-proof configuration); 'self:N' takes the first N layers of
    the target (blocks are stacked on a leading layer axis, so the draft
    is a prefix-slice sharing embeddings/head); 'k=v,...' builds a
    separate random-init config off the target's dims."""
    import jax

    from mingpt_distributed_tpu.models import gpt

    if spec == "self":
        return params, cfg
    if spec.startswith("self:"):
        try:
            n = int(spec.split(":", 1)[1])
        except ValueError:
            raise SystemExit(f"--draft-config self:N needs an int, "
                             f"got {spec!r}")
        if not 1 <= n <= cfg.n_layer:
            raise SystemExit(f"--draft-config {spec!r}: N outside "
                             f"[1, {cfg.n_layer}]")
        dcfg = dataclasses.replace(cfg, n_layer=n)
        dparams = dict(params)
        dparams["blocks"] = jax.tree.map(lambda a: a[:n], params["blocks"])
        return dparams, dcfg
    overrides = {}
    for clause in spec.split(","):
        k, sep, v = clause.partition("=")
        if not sep or not k.strip():
            raise SystemExit(f"--draft-config clause {clause!r} is not "
                             f"k=v (or 'self' / 'self:N')")
        try:
            overrides[k.strip()] = int(v)
        except ValueError:
            try:
                overrides[k.strip()] = float(v)
            except ValueError:
                overrides[k.strip()] = v.strip()
    try:
        dcfg = dataclasses.replace(cfg, **overrides).resolved()
    except Exception as e:
        raise SystemExit(f"--draft-config {spec!r}: {e}")
    return gpt.init(jax.random.key(1), dcfg), dcfg


def _spec_kwargs(args, params, cfg) -> dict:
    """Speculative-decoding kwargs for InferenceServer (empty dict = off).
    --draft-config and --spec-k only make sense together."""
    if args.spec_k <= 0 and args.draft_config is None:
        return {}
    if args.spec_k <= 0 or args.draft_config is None:
        raise SystemExit(
            "--draft-config and --spec-k (>= 1) must be given together")
    dparams, dcfg = _draft_from(args.draft_config, params, cfg)
    return dict(draft_params=dparams, draft_cfg=dcfg, spec_k=args.spec_k)


def _start_telemetry(args):
    """(registry, TelemetryServer | None) for this process. With
    --metrics-port the process-wide registry is exposed on /metrics (0
    binds an ephemeral port, printed so callers/CI can scrape it);
    without it the registry still unifies the in-process metrics."""
    from mingpt_distributed_tpu import telemetry

    reg = telemetry.get_registry()
    telemetry.register_build_info(reg)
    if args.metrics_port is None:
        return reg, None
    tserver = telemetry.TelemetryServer(reg, port=args.metrics_port)
    print(f"[serve] telemetry: /metrics and /healthz on {tserver.url('')}",
          file=sys.stderr)
    return reg, tserver


def _make_observability(args, reg):
    """(TraceRecorder | None, FlightRecorder | None) from the ISSUE 10
    flags. The flight recorder samples the process registry and the
    span tracer's ring at dump time; the trace recorder mirrors every
    span/event it records into the flight ring, so a crash dump carries
    the requests that were in flight when it happened. --slo needs the
    per-request summaries, so it forces a recorder even without an
    export path."""
    from mingpt_distributed_tpu import telemetry

    flight = None
    if args.flight_dir is not None:
        flight = telemetry.FlightRecorder(
            out_dir=args.flight_dir, registry=reg)
        flight.source_providers["tracer"] = telemetry.get_tracer().records
        flight.metrics_providers["process"] = (
            lambda: telemetry.render_prometheus(reg))
    recorder = None
    if (args.trace_jsonl is not None or args.slo is not None
            or args.slo_json is not None or flight is not None):
        if not 0.0 <= args.trace_sample <= 1.0:
            raise SystemExit(
                f"--trace-sample must be in [0, 1], got {args.trace_sample}")
        sink = (telemetry.trace_sink(args.trace_jsonl)
                if args.trace_jsonl is not None else None)
        recorder = telemetry.TraceRecorder(
            sink=sink, sample=args.trace_sample, registry=reg, flight=flight)
    return recorder, flight


def _slo_report(args, recorder):
    """Evaluate SLO objectives over the recorder's completed-request
    summaries: print the graded report with --slo, write the report dict
    as sorted-key JSON with --slo-json (diffable via trace_summary.py
    --compare). Returns the report dict (None when neither flag is set)."""
    import json as _json

    from mingpt_distributed_tpu import telemetry

    if (args.slo is None and args.slo_json is None) or recorder is None:
        return None
    objectives = telemetry.parse_slo_spec(args.slo or "default")
    report = telemetry.evaluate_slos(recorder.completed_requests(),
                                     objectives)
    if args.slo is not None:
        print(telemetry.render_slo_report(report))
    if args.slo_json is not None:
        with open(args.slo_json, "w") as f:
            f.write(_json.dumps(report, sort_keys=True, indent=2) + "\n")
        print(f"[serve] SLO report (mingpt-slo/1) written to "
              f"{args.slo_json}", file=sys.stderr)
    return report


def _request_for(args, tokens, eos_id=None):
    from mingpt_distributed_tpu.serving import Request

    return Request(
        prompt=tokens,
        max_new_tokens=args.max_new_tokens,
        temperature=args.temperature,
        top_k=args.top_k,
        top_p=args.top_p,
        do_sample=not args.greedy,
        eos_id=eos_id,
        seed=args.seed,
        deadline_s=args.deadline_s,
    )


def selftest(args) -> int:
    """Offline batch over canned prompts with a random-init tiny model:
    greedy server output must be token-identical to solo generate(), with
    the compiled-program family bounded by the bucket ladder. CI runs
    this twice via run_tests.sh — once with defaults (single-bucket
    ladder: exactly one prefill + one decode trace) and once with
    --prefill-chunk/--prefill-buckets/--prefix-cache-mb so chunked +
    bucketed admission and prefix reuse are exercised end-to-end without
    a checkpoint."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from mingpt_distributed_tpu.config import GPTConfig
    from mingpt_distributed_tpu.models import generate as gen
    from mingpt_distributed_tpu.models import gpt
    from mingpt_distributed_tpu.serving import InferenceServer, Request
    from mingpt_distributed_tpu.training.metrics import MetricsLogger

    cfg = GPTConfig.make(
        n_layer=2, n_head=2, n_embd=32, vocab_size=96, block_size=48,
        embd_pdrop=0.0, resid_pdrop=0.0, attn_pdrop=0.0, dtype="float32",
    )
    params = gpt.init(jax.random.key(0), cfg)
    canned = ["O God, O God!", "Once more unto", "All the world's"]
    prompts = [[ord(c) % cfg.vocab_size for c in s] for s in canned]
    if args.prefix_cache_mb > 0:
        # two prompts sharing a long head: the second must hit the store
        canned += ["Once more unto the breach", "Once more unto the wall!"]
        prompts += [[ord(c) % cfg.vocab_size for c in s] for s in canned[-2:]]
    max_new = 12

    # one registry for the whole page: serving instruments + the trainer
    # gauge families (a silent MetricsLogger registers mingpt_train_*, so
    # the scrape asserts the unified exposition, not just serving's half)
    reg, tserver = _start_telemetry(args)
    MetricsLogger(cfg, enabled=False, registry=reg)
    server = InferenceServer(params, cfg, n_slots=2,
                             log_every=args.log_every,
                             registry=reg,
                             **_server_kwargs(args))
    handles = server.generate_batch(
        [Request(prompt=p, max_new_tokens=max_new) for p in prompts])

    rc = 0
    for text, p, h in zip(canned, prompts, handles):
        want = np.asarray(
            gen.generate(params, cfg, jnp.asarray(p, jnp.int32)[None],
                         max_new))[0, len(p):].tolist()
        ok = h.tokens == want
        print(f"selftest {h.request_id} ({text!r}): "
              + ("OK" if ok else f"MISMATCH server={h.tokens} solo={want}"))
        if not ok:
            rc = 1
    counts = server.compile_counts()
    ladder = len(server.engine.buckets)
    if counts["decode"] != 1 or counts["prefill"] > ladder:
        print(f"selftest FAIL: unbounded compilation: {counts} "
              f"(ladder size {ladder})")
        rc = 1
    if args.prefix_cache_mb > 0 and server.metrics.prefix_hits < 1:
        print("selftest FAIL: prefix store enabled but no hit recorded")
        rc = 1
    # recompile watchdog: armed by --warmup; any post-warmup trace is a
    # bounded-program-family regression
    wd = server.watchdog
    if args.warmup and not wd.armed:
        print("selftest FAIL: --warmup set but watchdog not armed")
        rc = 1
    if wd.recompiles:
        print(f"selftest FAIL: watchdog counted {wd.recompiles} "
              f"post-warmup recompile(s)")
        rc = 1
    print(f"selftest watchdog: armed={wd.armed} recompiles={wd.recompiles}")
    if tserver is not None:
        rc |= _selftest_scrape(tserver)
        tserver.close()
    summary = server.summary()
    print("selftest metrics:", json.dumps(summary))
    if args.metrics_json:
        server.metrics.write_json(args.metrics_json)
    if summary["requests_completed"] != len(canned):
        print("selftest FAIL: not all requests completed")
        rc = 1
    print("selftest", "PASSED" if rc == 0 else "FAILED")
    return rc


def _selftest_scrape(tserver) -> int:
    """Scrape our own /metrics over HTTP and validate it with the strict
    exposition parser (grammar + histogram-triplet coherence — not
    string-contains): the unified page must carry serving latency
    histograms, utilization/prefix gauges, the trainer gauge families and
    a zero recompile count."""
    import urllib.request

    from mingpt_distributed_tpu.telemetry import parse_prometheus

    rc = 0
    with urllib.request.urlopen(tserver.url("/healthz"), timeout=10) as resp:
        health = json.loads(resp.read().decode())
    if health.get("status") != "ok":
        print(f"selftest FAIL: /healthz says {health}")
        rc = 1
    with urllib.request.urlopen(tserver.url("/metrics"), timeout=10) as resp:
        text = resp.read().decode()
    try:
        parsed = parse_prometheus(text)
    except ValueError as e:
        print(f"selftest FAIL: /metrics is not valid exposition text: {e}")
        return 1
    required = {
        "mingpt_serve_ttft_seconds": "histogram",
        "mingpt_serve_itl_seconds": "histogram",
        "mingpt_serve_slot_utilization": "gauge",
        "mingpt_serve_prefix_hit_rate": "gauge",
        "mingpt_train_loss": "gauge",
        "mingpt_train_mfu": "gauge",
        "mingpt_recompiles_total": "counter",
        "mingpt_build_info": "gauge",
    }
    for name, kind in required.items():
        got = parsed["types"].get(name)
        if got != kind:
            print(f"selftest FAIL: /metrics lacks {kind} {name} (got {got})")
            rc = 1
    recompiles = sum(v for n, _labels, v in parsed["samples"]
                     if n == "mingpt_recompiles_total")
    if recompiles:
        print(f"selftest FAIL: /metrics reports {recompiles} recompile(s)")
        rc = 1
    n = len(parsed["samples"])
    print(f"selftest scrape: {n} samples, recompiles_total {recompiles:g}")
    return rc


def selftest_spec(args) -> int:
    """ISSUE 11 acceptance gate: speculative decode must be token-exact
    with the non-speculative greedy path, with ONE verify executable for
    the server's lifetime.

    Two variants run, both against solo generate():

    * **identical draft** (``--draft-config self`` semantics): every
      proposal matches, so acceptance is always k+1 — the full-burst
      emission path, the draft backfill row, and the accept-rate/tokens-
      per-verify metrics are all exercised at their ceiling (accept rate
      must be exactly 1.0, tokens/verify exactly k+1);
    * **truncated 1-layer draft + chunked prefill + prefix reuse**: real
      rejections exercise cache rollback on both engines, combined with
      6-token prefill chunks, a multi-bucket ladder and a shared-prefix
      store hit — the combined-machinery parity the plain selftest runs
      without speculation.

    Both servers warm up and must show zero post-warmup recompiles with
    the verify/draft families inside the watched counts."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from mingpt_distributed_tpu.config import GPTConfig
    from mingpt_distributed_tpu.models import generate as gen
    from mingpt_distributed_tpu.models import gpt
    from mingpt_distributed_tpu.serving import InferenceServer, Request

    cfg = GPTConfig.make(
        n_layer=2, n_head=2, n_embd=32, vocab_size=96, block_size=48,
        embd_pdrop=0.0, resid_pdrop=0.0, attn_pdrop=0.0, dtype="float32",
    )
    params = gpt.init(jax.random.key(0), cfg)
    k = args.spec_k if args.spec_k > 0 else 3
    max_new = 12

    def solo(p):
        return np.asarray(
            gen.generate(params, cfg, jnp.asarray(p, jnp.int32)[None],
                         max_new))[0, len(p):].tolist()

    def check_parity(tag, canned, prompts, handles) -> int:
        bad = 0
        for text, p, h in zip(canned, prompts, handles):
            want = solo(p)
            ok = h.tokens == want
            print(f"selftest-spec [{tag}] {h.request_id} ({text!r}): "
                  + ("OK" if ok
                     else f"MISMATCH spec={h.tokens} solo={want}"))
            if not ok:
                bad = 1
        return bad

    canned = ["O God, O God!", "Once more unto", "All the world's"]
    prompts = [[ord(c) % cfg.vocab_size for c in s] for s in canned]
    rc = 0

    # -- variant A: identical draft (always-accept ceiling) ------------
    srv = InferenceServer(params, cfg, n_slots=2, warmup=True,
                          draft_params=params, draft_cfg=cfg, spec_k=k)
    handles = srv.generate_batch(
        [Request(prompt=p, max_new_tokens=max_new) for p in prompts])
    rc |= check_parity("self", canned, prompts, handles)
    m = srv.metrics
    if m.spec_accept_rate != 1.0:
        print(f"selftest-spec FAIL: identical draft accept rate "
              f"{m.spec_accept_rate} != 1.0")
        rc = 1
    if m.spec_tokens_per_verify_mean != k + 1:
        print(f"selftest-spec FAIL: identical draft emitted "
              f"{m.spec_tokens_per_verify_mean} tokens/verify, want {k + 1}")
        rc = 1
    counts = srv.compile_counts()
    if counts["verify"] != 1 or counts["draft_decode"] != 1:
        print(f"selftest-spec FAIL: unbounded speculation programs: "
              f"{counts}")
        rc = 1
    if srv.watchdog.recompiles:
        print(f"selftest-spec FAIL: {srv.watchdog.recompiles} post-warmup "
              f"recompile(s) (spec families are watched)")
        rc = 1
    print(f"selftest-spec [self] accept "
          f"{m.spec_accepted}/{m.spec_proposed}, "
          f"tokens/verify {m.spec_tokens_per_verify_mean:.3g}, "
          f"counts {counts}")

    # -- variant B: truncated draft + chunked prefill + prefix reuse ---
    dcfg = dataclasses.replace(cfg, n_layer=1)
    dparams = dict(params)
    dparams["blocks"] = jax.tree.map(lambda a: a[:1], params["blocks"])
    canned_b = canned + ["Once more unto the breach",
                         "Once more unto the wall!"]
    prompts_b = [[ord(c) % cfg.vocab_size for c in s] for s in canned_b]
    srv2 = InferenceServer(
        params, cfg, n_slots=2, warmup=True,
        prefill_chunk=6, prefill_buckets=(4, 6, 8, 16, 32, 48),
        prefix_cache_mb=4.0,
        draft_params=dparams, draft_cfg=dcfg, spec_k=k)
    handles_b = srv2.generate_batch(
        [Request(prompt=p, max_new_tokens=max_new) for p in prompts_b])
    rc |= check_parity("self:1+chunk+prefix", canned_b, prompts_b, handles_b)
    m2 = srv2.metrics
    counts2 = srv2.compile_counts()
    ladder = len(srv2.engine.buckets)
    if counts2["verify"] != 1:
        print(f"selftest-spec FAIL: verify family grew: {counts2}")
        rc = 1
    if counts2["prefill"] > ladder or counts2["draft_prefill"] > ladder:
        print(f"selftest-spec FAIL: prefill families exceed the "
              f"{ladder}-bucket ladder: {counts2}")
        rc = 1
    if m2.prefix_hits < 1:
        print("selftest-spec FAIL: prefix store enabled but no hit")
        rc = 1
    if m2.spec_rounds < 1:
        print("selftest-spec FAIL: no verify rounds ran in variant B")
        rc = 1
    if srv2.watchdog.recompiles:
        print(f"selftest-spec FAIL: {srv2.watchdog.recompiles} post-warmup "
              f"recompile(s) in the combined variant")
        rc = 1
    print(f"selftest-spec [self:1+chunk+prefix] accept "
          f"{m2.spec_accepted}/{m2.spec_proposed}, "
          f"prefix_hits {m2.prefix_hits}, counts {counts2}")
    print("selftest-spec metrics:", json.dumps(srv2.summary()))
    print("selftest-spec", "PASSED" if rc == 0 else "FAILED")
    return rc


def selftest_quant(args) -> int:
    """The ISSUE 18 acceptance gate: an int8 KV pool with chunked
    prefill + prefix store + speculation composed must track the fp32
    server within tolerance while paying ~0.27x the pool bytes.

    Geometry note: the scale planes cost 4 bytes per (row, kv_head)
    against head_dim payload bytes, so the <= 0.27 bytes ratio needs
    head_dim >= 64 — this gate runs n_embd=256 / n_head=4 (head_dim 64)
    rather than the other selftests' head_dim-16 tiny config.

    Checks: greedy token parity within tolerance (>= 90% of emitted
    tokens on the common prefix per request, across chunked prefill,
    prefix hits and speculative bursts); ``compile_counts()`` identical
    per dtype (the dtype rides the compile key, it never adds
    executables); zero post-warmup recompiles on both servers;
    pool payload + scale planes <= 0.27x the fp32 pool's bytes; the
    ``mingpt_serve_kv_dtype`` build-info gauge and a sampled
    ``mingpt_serve_quant_logit_err_max``; and that ``fp8`` resolves."""
    import jax

    from mingpt_distributed_tpu.config import GPTConfig
    from mingpt_distributed_tpu.models import gpt
    from mingpt_distributed_tpu.parallel.zero import per_device_bytes
    from mingpt_distributed_tpu.serving import InferenceServer, Request
    from mingpt_distributed_tpu.serving import quant as quant_lib
    from mingpt_distributed_tpu.telemetry import (
        MetricsRegistry,
        parse_prometheus,
        render_prometheus,
    )

    cfg = GPTConfig.make(
        n_layer=2, n_head=4, n_embd=256, vocab_size=96, block_size=48,
        embd_pdrop=0.0, resid_pdrop=0.0, attn_pdrop=0.0, dtype="float32",
    )
    params = gpt.init(jax.random.key(0), cfg)
    canned = ["O God, O God!", "Once more unto", "All the world's",
              "Once more unto the breach", "Once more unto the wall!"]
    prompts = [[ord(c) % cfg.vocab_size for c in s] for s in canned]
    max_new = 12

    def run_once(kv_dtype):
        reg = MetricsRegistry()
        srv = InferenceServer(
            params, cfg, n_slots=2, registry=reg,
            prefill_buckets=(8, 48), prefill_chunk=6,
            prefix_cache_mb=0.5, warmup=True,
            draft_params=params, draft_cfg=cfg, spec_k=2,
            kv_dtype=kv_dtype,
        )
        handles = srv.generate_batch(
            [Request(prompt=p, max_new_tokens=max_new) for p in prompts])
        return srv, reg, [h.tokens for h in handles]

    rc = 0
    srv32, _, toks32 = run_once("fp32")
    srv8, reg8, toks8 = run_once("int8")

    # tolerance-gated greedy parity: int8 KV storage may flip a late
    # token on a near-tie, so the gate is a common-prefix ratio, not
    # exact equality (the fp32 path keeps the exact-parity selftests)
    agree = total = 0
    for text, a, b in zip(canned, toks32, toks8):
        lcp = 0
        for x, y in zip(a, b):
            if x != y:
                break
            lcp += 1
        agree += lcp
        total += len(a)
        print(f"selftest-quant ({text!r}): "
              + ("OK" if lcp == len(a) else
                 f"prefix {lcp}/{len(a)} fp32={a} int8={b}"))
    if total == 0 or agree / total < 0.9:
        print(f"selftest-quant FAIL: parity {agree}/{total} below the "
              f"0.9 tolerance gate")
        rc = 1

    c32, c8 = srv32.compile_counts(), srv8.compile_counts()
    if c32 != c8:
        print(f"selftest-quant FAIL: compile_counts diverge by dtype: "
              f"fp32={c32} int8={c8}")
        rc = 1
    for name, srv in (("fp32", srv32), ("int8", srv8)):
        if srv.watchdog.recompiles:
            print(f"selftest-quant FAIL: {name} watchdog counted "
                  f"{srv.watchdog.recompiles} post-warmup recompile(s)")
            rc = 1
    if srv8.metrics.prefix_hits < 1:
        print("selftest-quant FAIL: no prefix hit on the int8 server")
        rc = 1
    if srv8.metrics.spec_rounds < 1:
        print("selftest-quant FAIL: no speculative rounds on int8")
        rc = 1

    # the hard bytes gate: payload + scale planes vs the fp32 pool, as
    # the busiest device holds them
    pool32, pool8 = srv32.engine.pool.cache, srv8.engine.pool.cache
    bytes32, bytes8 = per_device_bytes(pool32), per_device_bytes(pool8)
    ratio = bytes8 / bytes32
    if per_device_bytes(quant_lib.split_scales(pool8)[1]) <= 0:
        print("selftest-quant FAIL: no scale planes in the int8 pool")
        rc = 1
    if sorted(pool32) != ["k", "v"]:
        print(f"selftest-quant FAIL: fp32 pool grew leaves beyond k/v: "
              f"{sorted(pool32)}")
        rc = 1
    if ratio > 0.27:
        print(f"selftest-quant FAIL: kv_pool+kv_scales ratio {ratio:.4f} "
              f"> 0.27")
        rc = 1

    # quantization quality, sampled into the gauge + asserted sane
    err = quant_lib.max_abs_logit_error(
        params, cfg, prompts[0], quant_lib.resolve_kv_dtype("int8"))
    srv8.observe_quant_logit_error(err)
    if not (0.0 < err < 0.5):
        print(f"selftest-quant FAIL: max |dlogit| {err} out of range")
        rc = 1
    page = parse_prometheus(render_prometheus(reg8))
    dtype_val = gerr = None
    for n, labels, v in page["samples"]:
        if n == "mingpt_serve_kv_dtype" and labels.get("kv_dtype") == "int8":
            dtype_val = v
        if n == "mingpt_serve_quant_logit_err_max":
            gerr = v
    if dtype_val != 1.0:
        print("selftest-quant FAIL: mingpt_serve_kv_dtype{kv_dtype=int8} "
              "!= 1 in the scrape")
        rc = 1
    if gerr is None or abs(gerr - err) > 1e-12:
        print(f"selftest-quant FAIL: quant err gauge {gerr} != sampled "
              f"{err}")
        rc = 1

    q = quant_lib.resolve_kv_dtype("fp8")
    if q is None or q.name != "fp8":
        print(f"selftest-quant FAIL: fp8 resolved to {q!r}")
        rc = 1

    print(f"selftest-quant bytes: int8 payload+scales={bytes8} "
          f"fp32 pool={bytes32} ratio={ratio:.4f}")
    print(f"selftest-quant err={err:.6f} counts={c8}")
    print("selftest-quant", "PASSED" if rc == 0 else "FAILED")
    return rc


def selftest_chaos(args) -> int:
    """The ISSUE 6 acceptance gate, CPU-only and fully deterministic
    (virtual clock, seeded injector, zero wall sleeps): canned prompts
    through 3 supervised replicas while the injector crashes replica0
    mid-decode and makes replica1 slow. Every request must finish on a
    surviving replica with greedy output token-identical to solo
    generate(), the caller-visible stream must contain zero duplicate
    tokens, and the breaker/retry/shed/crash counters must appear on a
    strict-parsed /metrics scrape."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from mingpt_distributed_tpu.config import GPTConfig
    from mingpt_distributed_tpu.models import generate as gen
    from mingpt_distributed_tpu.models import gpt
    from mingpt_distributed_tpu.serving import (
        ReplicaSupervisor,
        Request,
        Router,
        ShedError,
        VirtualClock,
        default_server_factory,
    )
    from mingpt_distributed_tpu.training.faults import ServingFaultInjector

    cfg = GPTConfig.make(
        n_layer=2, n_head=2, n_embd=32, vocab_size=96, block_size=48,
        embd_pdrop=0.0, resid_pdrop=0.0, attn_pdrop=0.0, dtype="float32",
    )
    params = gpt.init(jax.random.key(0), cfg)
    canned = ["O God, O God!", "Once more unto", "All the world's",
              "Now is the winter", "Friends, Romans", "To be, or not"]
    prompts = [[ord(c) % cfg.vocab_size for c in s] for s in canned]
    max_new = 12
    spec = args.chaos_spec or (
        "crash:nth=6:match=replica0;slow:every=1:delay=0.25:match=replica1")
    n_replicas = args.replicas if args.replicas > 1 else 3

    if args.metrics_port is None:
        args.metrics_port = 0  # the scrape assertions are part of the gate
    reg, tserver = _start_telemetry(args)
    recorder, flight = _make_observability(args, reg)
    if tserver is not None and flight is not None:
        tserver.flight_provider = lambda: flight.snapshot("on_demand")
    injector = ServingFaultInjector(spec)
    supervisor = ReplicaSupervisor(
        default_server_factory(params, cfg, n_slots=2, **_server_kwargs(args)),
        n_replicas=n_replicas,
        clock=VirtualClock(tick_s=0.001),
        injector=injector,
        registry=reg,
        max_restarts=1,
        restart_backoff_s=0.01,
        itl_slo_s=0.1,
    )
    streamed = {}

    def on_token(fh, tok):
        streamed.setdefault(fh.request_id, []).append(tok)

    router = Router(
        supervisor, on_token=on_token, max_retries=3, retry_backoff_s=0.01,
        breaker_reset_s=0.05, shed_watermark=args.shed_watermark,
        trace_recorder=recorder, flight=flight)
    if tserver is not None:
        tserver.health_provider = router.health_report
    handles = [router.submit(Request(prompt=p, max_new_tokens=max_new))
               for p in prompts]
    router.run_until_drained(max_steps=5000)
    summary = router.summary()
    print("selftest-chaos fleet:", json.dumps(summary))

    rc = 0
    for text, p, h in zip(canned, prompts, handles):
        want = np.asarray(
            gen.generate(params, cfg, jnp.asarray(p, jnp.int32)[None],
                         max_new))[0, len(p):].tolist()
        ok = h.finish_reason == "length" and h.tokens == want
        seen = streamed.get(h.request_id, [])
        if seen != h.tokens:
            print(f"selftest-chaos FAIL {h.request_id}: streamed {seen} != "
                  f"handle {h.tokens} (duplicate or lost emission)")
            rc = 1
        print(f"selftest-chaos {h.request_id} ({text!r}): "
              f"attempts={h.attempts} replica={h.replica} "
              f"dups_suppressed={h.duplicates_suppressed} "
              + ("OK" if ok else
                 f"MISMATCH reason={h.finish_reason} "
                 f"server={h.tokens} solo={want}"))
        if not ok:
            rc = 1

    reps = summary["replicas"]
    checks = [
        ("replica0 crashed at least once",
         reps["replica0"]["crashes"] >= 1),
        ("crashed replica was restarted",
         summary["requests_by_outcome"]["completed"] == len(canned)
         and reps["replica0"]["state"] == "ready"),
        ("crash retries were counted",
         summary["retries_by_reason"]["crash"] >= 1),
        ("re-emitted tokens were suppressed, not double-streamed",
         summary["duplicates_suppressed"] >= 1),
        ("slow replica accumulated injected clock skew",
         reps["replica1"]["clock_skew_s"] > 0),
        ("slow replica is health-gated on ITL p99",
         "itl_p99" in reps["replica1"]["health_reasons"]),
    ]
    for what, ok in checks:
        if not ok:
            print(f"selftest-chaos FAIL: {what}")
            rc = 1

    # drain semantics: admission stops with a typed, counted rejection
    router.drain()
    try:
        router.submit(Request(prompt=prompts[0], max_new_tokens=2))
        print("selftest-chaos FAIL: draining fleet accepted a request")
        rc = 1
    except ShedError as e:
        if e.reason != "draining":
            print(f"selftest-chaos FAIL: drain shed reason {e.reason!r}")
            rc = 1
    if router.summary()["rejected_by_reason"]["draining"] < 1:
        print("selftest-chaos FAIL: draining rejection not counted")
        rc = 1

    if flight is not None:
        flight.dump("sigterm_drain")  # the artifact shutdown() writes
    if recorder is not None:
        rc |= _chaos_observability_checks(args, recorder, flight, handles)

    if tserver is not None:
        rc |= _chaos_scrape(tserver, has_flight=flight is not None)
        tserver.close()
    if args.metrics_json:
        with open(args.metrics_json, "w") as f:
            json.dump(router.summary(), f, indent=2)
            f.write("\n")
    print("selftest-chaos", "PASSED" if rc == 0 else "FAILED")
    return rc


def _chaos_observability_checks(args, recorder, flight, handles) -> int:
    """The ISSUE 10 acceptance bar, run inside the chaos gate whenever
    tracing is enabled: every completed request yields exactly ONE
    strict-valid trace whose attempt spans match the retry count and
    whose emit events match the streamed tokens; crash- and
    drain-triggered flight dumps strict-parse via the manifest; the
    --slo report grades from the exact trace durations."""
    from mingpt_distributed_tpu import telemetry

    rc = 0
    if recorder.active_traces:
        print(f"selftest-chaos FAIL: {recorder.active_traces} trace(s) "
              f"still open after drain")
        rc = 1
    if recorder.orphan_records:
        print(f"selftest-chaos FAIL: {recorder.orphan_records} orphan "
              f"trace record(s)")
        rc = 1
    report = _slo_report(args, recorder)
    if args.slo is not None and (report is None or not report.get("grade")):
        print("selftest-chaos FAIL: --slo produced no graded report")
        rc = 1
    recorder.close()  # flush the JSONL sink before strict-loading it

    if args.trace_jsonl is not None:
        try:
            traces = telemetry.load_trace_jsonl(args.trace_jsonl)
        except ValueError as e:
            print(f"selftest-chaos FAIL: trace stream invalid: {e}")
            return 1
        retried_traces = 0
        for h in handles:
            t = traces.get(h.request_id)
            if t is None:
                print(f"selftest-chaos FAIL: no trace for {h.request_id}")
                rc = 1
                continue
            emits = [e for e in t["events"] if e["name"] == "emit"]
            attempts = [s for s in t["spans"]
                        if s["name"] == "fleet.attempt"]
            retries = [e for e in t["events"] if e["name"] == "retry"]
            checks = [
                ("one emit event per streamed token",
                 len(emits) == len(h.tokens)),
                ("one attempt span per attempt",
                 len(attempts) == h.attempts),
                ("retry events mark every extra attempt",
                 len(retries) == h.attempts - 1),
                ("summary agrees with the handle",
                 t["request"]["attempts"] == h.attempts
                 and t["request"]["n_tokens"] == len(h.tokens)
                 and t["request"]["outcome"] == h.finish_reason),
                ("scheduler spans joined the fleet trace",
                 {"serve.queue_wait", "serve.prefix_lookup",
                  "serve.decode_round"}
                 <= {s["name"] for s in t["spans"]}),
            ]
            for what, ok in checks:
                if not ok:
                    print(f"selftest-chaos FAIL {h.request_id}: {what}")
                    rc = 1
            retried_traces += h.attempts > 1
        if not retried_traces:
            print("selftest-chaos FAIL: no retried request in the trace "
                  "stream (crash did not land?)")
            rc = 1
        shed = [t for t in traces.values()
                if t["request"]["outcome"] == "shed"]
        if len(shed) != 1:
            print(f"selftest-chaos FAIL: expected 1 forced shed trace, "
                  f"got {len(shed)}")
            rc = 1
        print(f"selftest-chaos traces: {len(traces)} trace(s), "
              f"{retried_traces} retried, {len(shed)} shed")

    if flight is not None and flight.out_dir is not None:
        try:
            manifest, docs = telemetry.load_flight_dir(flight.out_dir)
        except (OSError, ValueError) as e:
            print(f"selftest-chaos FAIL: flight dir invalid: {e}")
            return 1
        triggers = [d["trigger"] for d in docs]
        for want in ("crash", "sigterm_drain"):
            if want not in triggers:
                print(f"selftest-chaos FAIL: no {want!r} flight dump "
                      f"(got {triggers})")
                rc = 1
        print(f"selftest-chaos flight: {len(docs)} dump(s) {triggers}, "
              f"latest {manifest['latest']}")
    return rc


def _chaos_scrape(tserver, has_flight: bool = False) -> int:
    """Strict-parse our own /metrics and assert the fleet resilience
    families are present — breaker state, retries, crashes, restarts,
    per-reason rejections, duplicate-token suppression. /healthz must
    carry the per-replica breaker + health-gate detail (ISSUE 10) and,
    with the flight recorder armed, /debug/flight must serve a
    strict-valid snapshot."""
    import urllib.request

    from mingpt_distributed_tpu.telemetry import (
        parse_prometheus,
        validate_flight_dump,
    )

    rc = 0
    with urllib.request.urlopen(tserver.url("/healthz"), timeout=10) as resp:
        health = json.loads(resp.read().decode())
    reps = health.get("replicas")
    if not isinstance(reps, dict) or not all(
            "breaker" in v and "reasons" in v for v in reps.values()):
        print(f"selftest-chaos FAIL: /healthz lacks per-replica breaker "
              f"state + health reasons: {health}")
        rc = 1
    if has_flight:
        with urllib.request.urlopen(tserver.url("/debug/flight"),
                                    timeout=10) as resp:
            snap = json.loads(resp.read().decode())
        try:
            validate_flight_dump(snap)
        except ValueError as e:
            print(f"selftest-chaos FAIL: /debug/flight snapshot "
                  f"invalid: {e}")
            rc = 1

    with urllib.request.urlopen(tserver.url("/metrics"), timeout=10) as resp:
        text = resp.read().decode()
    try:
        parsed = parse_prometheus(text)
    except ValueError as e:
        print(f"selftest-chaos FAIL: /metrics is not valid exposition "
              f"text: {e}")
        return 1
    required = {
        "mingpt_serving_rejected_total": "counter",
        "mingpt_fleet_retries_total": "counter",
        "mingpt_fleet_crashes_total": "counter",
        "mingpt_fleet_restarts_total": "counter",
        "mingpt_fleet_breaker_state": "gauge",
        "mingpt_fleet_replica_up": "gauge",
        "mingpt_fleet_replica_healthy": "gauge",
        "mingpt_fleet_duplicate_tokens_suppressed_total": "counter",
    }
    for name, kind in required.items():
        got = parsed["types"].get(name)
        if got != kind:
            print(f"selftest-chaos FAIL: /metrics lacks {kind} {name} "
                  f"(got {got})")
            rc = 1
    crashes = sum(v for n, _l, v in parsed["samples"]
                  if n == "mingpt_fleet_crashes_total")
    retries = sum(v for n, _l, v in parsed["samples"]
                  if n == "mingpt_fleet_retries_total")
    if crashes < 1 or retries < 1:
        print(f"selftest-chaos FAIL: scrape shows crashes={crashes:g} "
              f"retries={retries:g} (expected >= 1 each)")
        rc = 1
    print(f"selftest-chaos scrape: {len(parsed['samples'])} samples, "
          f"crashes_total {crashes:g}, retries_total {retries:g}")
    return rc


def selftest_sharded(args) -> int:
    """The ISSUE 14 acceptance gate, CPU-only via forced host devices.

    Two servers over identical random-init weights and canned prompts —
    one single-device, one tp=2 across a mesh — must produce identical
    greedy tokens (placement is invisible to sampling: attention is
    head-parallel and the megatron param split reassembles exactly),
    with identical ``compile_counts()`` (the mesh rides the compile key,
    it never adds executables), zero post-warmup recompiles, prefix-hit
    parity, head-sharded prefix entries, and per-device pool bytes =
    total / 2 on the mesh."""
    import jax

    from mingpt_distributed_tpu.config import GPTConfig
    from mingpt_distributed_tpu.models import gpt
    from mingpt_distributed_tpu.parallel.mesh import MeshConfig, make_mesh
    from mingpt_distributed_tpu.parallel.zero import per_device_bytes
    from mingpt_distributed_tpu.serving import InferenceServer, Request

    if len(jax.devices()) < 2:
        print("selftest-sharded FAIL: needs >= 2 devices (run under "
              "XLA_FLAGS=--xla_force_host_platform_device_count=2)")
        return 1

    cfg = GPTConfig.make(
        n_layer=2, n_head=2, n_embd=32, vocab_size=96, block_size=48,
        embd_pdrop=0.0, resid_pdrop=0.0, attn_pdrop=0.0, dtype="float32",
    )
    params = gpt.init(jax.random.key(0), cfg)
    canned = ["O God, O God!", "Once more unto", "All the world's"]
    if args.prefix_cache_mb > 0:
        canned += ["Once more unto the breach", "Once more unto the wall!"]
    prompts = [[ord(c) % cfg.vocab_size for c in s] for s in canned]
    max_new = 10

    def run_once(mesh):
        srv = InferenceServer(params, cfg, n_slots=2,
                              mesh=mesh, **_server_kwargs(args))
        handles = srv.generate_batch(
            [Request(prompt=p, max_new_tokens=max_new) for p in prompts])
        return srv, [h.tokens for h in handles]

    rc = 0
    srv1, toks1 = run_once(None)
    mesh = make_mesh(MeshConfig(tp=2), devices=jax.devices()[:2])
    srv2, toks2 = run_once(mesh)

    for text, a, b in zip(canned, toks1, toks2):
        ok = a == b
        print(f"selftest-sharded ({text!r}): "
              + ("OK" if ok else f"MISMATCH tp1={a} tp2={b}"))
        if not ok:
            rc = 1

    c1, c2 = srv1.compile_counts(), srv2.compile_counts()
    if c1 != c2:
        print(f"selftest-sharded FAIL: compile_counts diverge under "
              f"sharding: tp1={c1} tp2={c2}")
        rc = 1
    ladder = len(srv2.engine.buckets)
    if c2["decode"] != 1 or c2["prefill"] > ladder:
        print(f"selftest-sharded FAIL: unbounded compilation: {c2} "
              f"(ladder size {ladder})")
        rc = 1
    for name, srv in (("tp1", srv1), ("tp2", srv2)):
        if srv.watchdog.recompiles:
            print(f"selftest-sharded FAIL: {name} watchdog counted "
                  f"{srv.watchdog.recompiles} post-warmup recompile(s)")
            rc = 1
    if args.warmup and not srv2.watchdog.armed:
        print("selftest-sharded FAIL: --warmup set but watchdog not armed")
        rc = 1

    if srv2.engine.kv_shard_count != 2:
        print(f"selftest-sharded FAIL: tp=2 pool is split over "
              f"{srv2.engine.kv_shard_count} device(s), expected 2")
        rc = 1
    if args.prefix_cache_mb > 0:
        for name, srv in (("tp1", srv1), ("tp2", srv2)):
            if srv.metrics.prefix_hits < 1:
                print(f"selftest-sharded FAIL: no prefix hit on {name}")
                rc = 1
        # stored entries must carry the pool's head-sharding — a prefix
        # hit is a chip-local row copy, never a gather
        for key, entry in srv2.engine.prefix_store.entries():
            for arr in entry.values():
                shard = arr.sharding.shard_shape(arr.shape)
                if shard[3] * 2 != arr.shape[3]:
                    print(f"selftest-sharded FAIL: prefix entry "
                          f"(rows={len(key)}) not head-sharded: "
                          f"{arr.shape} -> {shard}")
                    rc = 1

    # the sharded pool's per-device residency is total/2, read from the
    # shards the devices hold, and sharding leaves the total unchanged
    pool1, pool2 = srv1.engine.pool.cache, srv2.engine.pool.cache
    total1 = sum(a.nbytes for a in pool1.values())
    total2 = sum(a.nbytes for a in pool2.values())
    per_dev = per_device_bytes(pool2)
    if per_dev * 2 != total2:
        print(f"selftest-sharded FAIL: pool per-device bytes {per_dev} "
              f"!= total {total2} / 2")
        rc = 1
    if total2 != total1:
        print(f"selftest-sharded FAIL: sharding changed the pool's total "
              f"bytes: tp1={total1} tp2={total2}")
        rc = 1

    print(f"selftest-sharded compile_counts: {c2}")
    print(f"selftest-sharded pool bytes: total={total2} "
          f"per_device={per_dev}")
    print("selftest-sharded", "PASSED" if rc == 0 else "FAILED")
    return rc


def selftest_procfleet(args) -> int:
    """The ISSUE 16 acceptance gate, against REAL subprocesses: two
    replica workers behind the mingpt-rpc/1 socket surface.

    Phase A — ``kill -9`` one worker mid-decode: every request must
    finish on the survivor (and the respawned worker) with greedy output
    token-identical to solo generate() and a caller-visible stream with
    zero duplicate or lost tokens; the supervisor must have reaped exit
    code -9 and collected the dead worker's flight spill.

    Phase B — drain-with-migration: the source ships its KV/prefix state
    to the peer and retires with exit 75 (the requeue contract, now per
    replica process); every in-flight request completes bit-identical to
    an undisturbed run, and its strict-validated mingpt-trace/1 timeline
    spans both replicas (emits on the source, a migrate event, emits on
    the destination).

    Phase C — the chunked /rpc/stream endpoint replays one request's
    token stream over the real socket, byte-equal to the handle."""
    import signal
    import tempfile
    import threading

    import jax
    import jax.numpy as jnp
    import numpy as np

    from mingpt_distributed_tpu.config import GPTConfig
    from mingpt_distributed_tpu.models import generate as gen
    from mingpt_distributed_tpu.models import gpt
    from mingpt_distributed_tpu.serving import (
        ProcRouter,
        ProcessSupervisor,
        Request,
        WallClock,
        process_backend_factory,
    )
    from mingpt_distributed_tpu.telemetry import parse_prometheus
    from mingpt_distributed_tpu.telemetry.tracing import (
        TRACE_SCHEMA,
        TraceRecorder,
        validate_trace_records,
    )

    cfg_kw = dict(
        n_layer=2, n_head=2, n_embd=32, vocab_size=96, block_size=48,
        embd_pdrop=0.0, resid_pdrop=0.0, attn_pdrop=0.0, dtype="float32",
    )
    cfg = GPTConfig.make(**cfg_kw)
    params = gpt.init(jax.random.key(0), cfg)
    canned = ["O God, O God!", "Once more unto", "All the world's",
              "Now is the winter", "Friends, Romans", "To be, or not"]
    prompts = [[ord(c) % cfg.vocab_size for c in s] for s in canned]
    max_new = 12

    def solo(p, n):
        out = gen.generate(params, cfg, jnp.asarray(p, jnp.int32)[None], n)
        return np.asarray(out)[0, len(p):].tolist()

    class _ListSink:
        def __init__(self):
            self.records = []

        def write(self, kind, rec):
            self.records.append({"schema": TRACE_SCHEMA, "kind": kind,
                                 **rec})

        def close(self):
            pass

    spill_root = args.spill_dir or tempfile.mkdtemp(prefix="procfleet-")
    spec = {
        "cfg": cfg_kw,
        "init_seed": 0,
        "server": {"n_slots": 2, "prefill_chunk": 8,
                   "prefix_cache_mb": 4.0},
    }
    sink = _ListSink()
    recorder = TraceRecorder(sink=sink)
    supervisor = ProcessSupervisor(
        process_backend_factory(spec, spill_root, rpc_timeout_s=120.0),
        n_replicas=2,
        clock=WallClock(),
        max_restarts=1,
        restart_backoff_s=0.05,
    )
    streamed = {}

    def on_token(fh, tok):
        streamed.setdefault(fh.request_id, []).append(tok)

    router = ProcRouter(supervisor, on_token=on_token, max_retries=3,
                        retry_backoff_s=0.01, breaker_reset_s=0.05,
                        trace_recorder=recorder)
    pids = {rep.name: rep.backend.pid for rep in supervisor.replicas}
    print(f"selftest-procfleet workers: {pids} (spill: {spill_root})")
    rc = 0

    # -- Phase A: kill -9 mid-decode ----------------------------------
    handles = [router.submit(Request(prompt=p, max_new_tokens=max_new))
               for p in prompts]

    def mid_decode_replica():
        """A ready replica currently decoding a request that has emitted
        at least one token — killing it re-derives those tokens on the
        retry, which is exactly what the dedup layer must absorb."""
        for (name, _), (fh, rh) in router._attempts.items():
            rep = supervisor.replica_by_name(name)
            if (rep.state == "ready" and not rh.finished
                    and len(rh.tokens) >= 1):
                return rep
        return None

    victim = None
    for _ in range(2000):
        router.step()
        victim = mid_decode_replica()
        if victim is not None:
            break
    if victim is None:
        print("selftest-procfleet FAIL: no replica ever mid-decode")
        return 1
    os.kill(victim.backend.pid, signal.SIGKILL)
    print(f"selftest-procfleet kill -9 {victim.name} "
          f"(pid {victim.backend.pid}) mid-decode")
    router.run_until_drained(max_steps=20000)
    for _ in range(2000):
        # the restart backoff is wall-time; idle-step until poll_restarts
        # respawns the victim (phase B needs both replicas up)
        if supervisor.replica_by_name(victim.name).state == "ready":
            break
        router.step()

    for text, p, h in zip(canned, prompts, handles):
        want = solo(p, max_new)
        ok = h.finish_reason == "length" and h.tokens == want
        seen = streamed.get(h.request_id, [])
        if seen != h.tokens:
            print(f"selftest-procfleet FAIL {h.request_id}: streamed "
                  f"{seen} != handle {h.tokens} (duplicate or lost "
                  f"emission)")
            rc = 1
        print(f"selftest-procfleet {h.request_id} ({text!r}): "
              f"attempts={h.attempts} replica={h.replica} "
              + ("OK" if ok else f"MISMATCH reason={h.finish_reason} "
                                 f"fleet={h.tokens} solo={want}"))
        if not ok:
            rc = 1
    summary = router.summary()
    crash = next((c for c in supervisor.crash_reports
                  if c["replica"] == victim.name), None)
    checks_a = [
        ("crash retries were counted",
         summary["retries_by_reason"].get("crash", 0) >= 1),
        ("re-derived tokens were suppressed, not double-streamed",
         summary["duplicates_suppressed"] >= 1),
        ("supervisor reaped exit code -9",
         crash is not None and crash["exit_code"] == -signal.SIGKILL),
        ("dead worker's flight spill was collected",
         crash is not None and len(crash["spill_dumps"]) >= 1),
        ("killed worker was respawned as a new process",
         supervisor.replica_by_name(victim.name).state == "ready"
         and supervisor.replica_by_name(victim.name).backend.pid
         != pids[victim.name]),
    ]
    for what, ok in checks_a:
        if not ok:
            print(f"selftest-procfleet FAIL (phase A): {what}")
            rc = 1

    # -- Phase B: drain-with-migration --------------------------------
    handles_b = [router.submit(Request(prompt=p, max_new_tokens=max_new))
                 for p in prompts]
    src = None
    for _ in range(2000):
        router.step()
        src = mid_decode_replica()
        if src is not None:
            break
    if src is None:
        print("selftest-procfleet FAIL: phase B never reached mid-decode")
        return 1
    report = router.migrate_and_drain(src.name)
    print(f"selftest-procfleet migration: {json.dumps(report)}")
    router.run_until_drained(max_steps=20000)
    for text, p, h in zip(canned, prompts, handles_b):
        want = solo(p, max_new)
        ok = (h.finish_reason == "length" and h.tokens == want
              and streamed.get(h.request_id, []) == h.tokens)
        if not ok:
            print(f"selftest-procfleet FAIL (phase B) {h.request_id} "
                  f"({text!r}): reason={h.finish_reason} "
                  f"fleet={h.tokens} solo={want}")
            rc = 1
    moved = set(report["requests_moved"])
    spanning = 0
    for h in handles_b:
        if h.request_id not in moved:
            continue
        events = [r for r in sink.records
                  if r["kind"] == "event" and r["trace_id"] == h.request_id]
        migrates = [e for e in events if e["name"] == "migrate"]
        emit_replicas = {e["replica"] for e in events
                        if e["name"] == "emit"}
        if not migrates:
            print(f"selftest-procfleet FAIL: migrated {h.request_id} has "
                  f"no migrate event")
            rc = 1
        if len(emit_replicas) > 1:
            spanning += 1
    checks_b = [
        ("migration shipped state (outcome=ok)",
         report["outcome"] == "ok"),
        ("drained worker exited with the requeue code (75)",
         report["src_exit_code"] == 75),
        ("prefix/KV entries were installed on the peer",
         report["entries_installed"] >= 1),
        ("at least one in-flight request was migrated",
         len(moved) >= 1),
        ("a migrated request's timeline spans both replicas",
         spanning >= 1),
    ]
    for what, ok in checks_b:
        if not ok:
            print(f"selftest-procfleet FAIL (phase B): {what}")
            rc = 1

    # -- Phase C: chunked token stream over the real socket -----------
    h = router.submit(Request(prompt=prompts[0], max_new_tokens=max_new))
    router.step()
    attempt = next(((name, aid) for (name, aid), (fh, _)
                    in router._attempts.items()
                    if fh.request_id == h.request_id), None)
    if attempt is None:
        print("selftest-procfleet FAIL: phase C request not in flight")
        rc = 1
    else:
        name, aid = attempt
        transport = supervisor.replica_by_name(name).backend.transport
        got = []

        def consume():
            for doc in transport.stream(f"/rpc/stream?request_id={aid}"):
                got.append(doc)

        t = threading.Thread(target=consume, daemon=True)
        t.start()
        router.run_until_drained(max_steps=20000)
        t.join(timeout=60.0)
        toks = [d["token"] for d in got if d["kind"] == "stream_token"]
        ends = [d for d in got if d["kind"] == "stream_end"]
        if t.is_alive() or toks != h.tokens or not ends \
                or ends[0]["finish_reason"] != "length":
            print(f"selftest-procfleet FAIL (phase C): stream endpoint "
                  f"gave tokens={toks} ends={ends} vs handle={h.tokens}")
            rc = 1

    # -- fleet observability over the socket --------------------------
    page = router.fleet_metrics_page()
    parsed = parse_prometheus(page)  # strict: one TYPE line per family
    by_name = {}
    for sname, labels, value in parsed["samples"]:
        by_name.setdefault(sname, []).append((labels, value))
    migr_ok = any(labels.get("outcome") == "ok" and value >= 1
                  for labels, value in
                  by_name.get("mingpt_fleet_migrations_total", []))
    restarts_ok = any(value >= 1 for _, value in
                      by_name.get("mingpt_fleet_process_restarts_total",
                                  []))
    replica_labelled = any("replica" in labels for labels, _ in
                           by_name.get("mingpt_serve_steps_total", []))
    for what, ok in [
        ("merged page counts the migration", migr_ok),
        ("merged page counts the process restart", restarts_ok),
        ("worker pages merged under the replica label",
         replica_labelled),
    ]:
        if not ok:
            print(f"selftest-procfleet FAIL: {what}")
            rc = 1

    recorder.close()
    if recorder.active_traces:
        print(f"selftest-procfleet FAIL: {recorder.active_traces} "
              f"trace(s) still open")
        rc = 1
    try:
        validate_trace_records(sink.records)
    except ValueError as e:
        print(f"selftest-procfleet FAIL: trace validation: {e}")
        rc = 1

    exits = supervisor.shutdown_all()
    bad_exits = {n: c for n, c in exits.items()
                 if c not in (75, -signal.SIGKILL)}
    if bad_exits:
        print(f"selftest-procfleet FAIL: unexpected worker exit codes "
              f"{bad_exits} (want 75 for drained, -9 for killed)")
        rc = 1
    print(f"selftest-procfleet worker exits: {exits}")
    print("selftest-procfleet", "PASSED" if rc == 0 else "FAILED")
    return rc


def selftest_standby(args) -> int:
    """The ISSUE 17 acceptance gate, against REAL subprocesses.

    Phase A — cold vs standby on the same fault: kill -9 a mid-decode
    worker twice, once over a plain supervisor and once with a warm
    spare. Both runs must stay token-exact with zero duplicate or lost
    stream tokens; the standby run must record a strictly smaller
    crash->serving recovery time, label it ``path="standby"``, and
    backfill the pool after the adoption.

    Phase B — hang escalation: a worker wedges inside the step RPC (the
    ``stuck_step`` process fault, worker-side) and refuses SIGTERM; the
    liveness ladder must escalate SIGTERM -> SIGKILL within the
    configured deadline, the crash path recovers through standby
    adoption, and every stream stays exact.

    Phase C — speculative-state-complete migration: workers run
    self-speculation; ``migrate_and_drain`` must ship draft-pool rows
    and the destination must prime the migrated request from them
    (``spec_prime_total{mode="adopted"}``) with output token-identical
    to solo generate()."""
    import signal
    import tempfile

    import jax
    import jax.numpy as jnp
    import numpy as np

    from mingpt_distributed_tpu.config import GPTConfig
    from mingpt_distributed_tpu.models import generate as gen
    from mingpt_distributed_tpu.models import gpt
    from mingpt_distributed_tpu.serving import (
        ProcRouter,
        ProcessSupervisor,
        Request,
        WallClock,
        process_backend_factory,
    )
    from mingpt_distributed_tpu.telemetry import parse_prometheus

    cfg_kw = dict(
        n_layer=2, n_head=2, n_embd=32, vocab_size=96, block_size=48,
        embd_pdrop=0.0, resid_pdrop=0.0, attn_pdrop=0.0, dtype="float32",
    )
    cfg = GPTConfig.make(**cfg_kw)
    params = gpt.init(jax.random.key(0), cfg)
    canned = ["O God, O God!", "Once more unto", "All the world's",
              "Now is the winter"]
    prompts = [[ord(c) % cfg.vocab_size for c in s] for s in canned]
    max_new = 10

    def solo(p, n):
        out = gen.generate(params, cfg, jnp.asarray(p, jnp.int32)[None], n)
        return np.asarray(out)[0, len(p):].tolist()

    spill_root = args.spill_dir or tempfile.mkdtemp(prefix="standby-")
    rc = 0

    def build_fleet(spill, spec, **sup_kwargs):
        streamed = {}

        def on_token(fh, tok):
            streamed.setdefault(fh.request_id, []).append(tok)

        supervisor = ProcessSupervisor(
            process_backend_factory(
                spec, spill,
                rpc_timeout_s=sup_kwargs.pop("rpc_timeout_s", 120.0)),
            n_replicas=2, clock=WallClock(), max_restarts=1,
            restart_backoff_s=0.05, **sup_kwargs)
        router = ProcRouter(supervisor, on_token=on_token, max_retries=3,
                            retry_backoff_s=0.01, breaker_reset_s=0.05)
        return supervisor, router, streamed

    def mid_decode_replica(supervisor, router):
        for (name, _), (fh, rh) in router._attempts.items():
            rep = supervisor.replica_by_name(name)
            if (rep.state == "ready" and not rh.finished
                    and len(rh.tokens) >= 1):
                return rep
        return None

    def check_parity(tag, handles, streamed):
        ok = True
        for p, h in zip(prompts, handles):
            want = solo(p, max_new)
            if h.finish_reason != "length" or h.tokens != want:
                print(f"selftest-standby FAIL ({tag}) {h.request_id}: "
                      f"reason={h.finish_reason} fleet={h.tokens} "
                      f"solo={want}")
                ok = False
            if streamed.get(h.request_id, []) != h.tokens:
                print(f"selftest-standby FAIL ({tag}) {h.request_id}: "
                      f"streamed {streamed.get(h.request_id)} != handle "
                      f"{h.tokens} (duplicate or lost emission)")
                ok = False
        return ok

    # -- Phase A: cold vs standby recovery on the same fault ----------
    def run_kill(tag, standby):
        spec = {"cfg": cfg_kw, "init_seed": 0,
                "server": {"n_slots": 2, "prefill_chunk": 8}}
        supervisor, router, streamed = build_fleet(
            os.path.join(spill_root, tag), spec, standby=standby)
        handles = [router.submit(Request(prompt=p, max_new_tokens=max_new))
                   for p in prompts]
        victim = None
        for _ in range(2000):
            router.step()
            victim = mid_decode_replica(supervisor, router)
            if victim is not None:
                break
        if victim is None:
            print(f"selftest-standby FAIL ({tag}): never mid-decode")
            return None, False, supervisor
        os.kill(victim.backend.pid, signal.SIGKILL)
        router.run_until_drained(max_steps=20000)
        for _ in range(2000):
            if supervisor.replica_by_name(victim.name).state == "ready":
                break
            router.step()
        ok = check_parity(tag, handles, streamed)
        rec = supervisor.recovery_info(victim.name)
        return rec, ok, supervisor

    rec_cold, ok_cold, sup_cold = run_kill("cold", standby=0)
    rec_stby, ok_stby, sup_stby = run_kill("standby", standby=1)
    pool_refilled = (sup_stby.standby_pool is not None
                     and sup_stby.standby_pool.available() == 1)
    sup_cold.shutdown_all()
    sup_stby.shutdown_all()
    checks_a = [
        ("cold run stayed token-exact", ok_cold),
        ("standby run stayed token-exact", ok_stby),
        ("cold respawn recorded path=cold",
         rec_cold is not None and rec_cold["path"] == "cold"),
        ("standby respawn recorded path=standby",
         rec_stby is not None and rec_stby["path"] == "standby"),
        ("a spare was adopted by name",
         rec_stby is not None
         and str(rec_stby["adopted"]).startswith("standby")),
        ("standby recovery strictly beat cold on the same fault",
         rec_cold is not None and rec_stby is not None
         and rec_stby["recovery_s"] < rec_cold["recovery_s"]),
        ("the pool was backfilled after adoption", pool_refilled),
    ]
    if rec_cold and rec_stby:
        print(f"selftest-standby recovery: cold="
              f"{rec_cold['recovery_s']:.3f}s standby="
              f"{rec_stby['recovery_s']:.3f}s "
              f"(adopted {rec_stby['adopted']})")
    for what, ok in checks_a:
        if not ok:
            print(f"selftest-standby FAIL (phase A): {what}")
            rc = 1

    # -- Phase B: stuck_step -> SIGTERM -> SIGKILL ladder -------------
    spec_b = {"cfg": cfg_kw, "init_seed": 0,
              "server": {"n_slots": 2, "prefill_chunk": 8},
              "process_faults": "stuck_step:nth=3:match=replica0"}
    supervisor, router, streamed = build_fleet(
        os.path.join(spill_root, "hang"), spec_b, standby=1,
        hang_deadline_s=1.0, hang_kill_grace_s=1.0, rpc_timeout_s=2.0)
    # the initial workers (and the spare) already read their specs;
    # respawns and backfills must come up clean, or the replacement
    # wedges again on ITS third step
    spec_b.pop("process_faults")
    first_pid = supervisor.replica_by_name("replica0").backend.pid
    handles = [router.submit(Request(prompt=p, max_new_tokens=max_new))
               for p in prompts]
    router.run_until_drained(max_steps=20000)
    for _ in range(2000):
        if supervisor.replica_by_name("replica0").state == "ready":
            break
        router.step()
    ok_b = check_parity("hang", handles, streamed)
    page = router.fleet_metrics_page()
    esc = {}
    for sname, labels, value in parse_prometheus(page)["samples"]:
        if sname == "mingpt_fleet_hang_escalations_total":
            esc[labels.get("signal")] = value
    crash = next((c for c in supervisor.crash_reports
                  if c["replica"] == "replica0"), None)
    rep0 = supervisor.replica_by_name("replica0")
    checks_b = [
        ("streams stayed exact through the wedge", ok_b),
        ("the ladder fired SIGTERM first", esc.get("term", 0) >= 1),
        ("SIGTERM was refused, SIGKILL followed", esc.get("kill", 0) >= 1),
        ("the wedged worker died of SIGKILL",
         crash is not None and crash["exit_code"] == -signal.SIGKILL),
        ("the replacement is a new, serving process",
         rep0.state == "ready" and rep0.backend.pid != first_pid),
    ]
    for what, ok in checks_b:
        if not ok:
            print(f"selftest-standby FAIL (phase B): {what}")
            rc = 1
    print(f"selftest-standby escalations: {esc} "
          f"(exit={None if crash is None else crash['exit_code']})")
    supervisor.shutdown_all()

    # -- Phase C: draft rows ride the migration -----------------------
    spec_c = {"cfg": cfg_kw, "init_seed": 0, "draft": "self", "spec_k": 3,
              "server": {"n_slots": 2, "prefill_chunk": 8,
                         "prefix_cache_mb": 4.0}}
    supervisor, router, streamed = build_fleet(
        os.path.join(spill_root, "spec"), spec_c)
    handles = [router.submit(Request(prompt=p, max_new_tokens=max_new))
               for p in prompts]
    src = None
    for _ in range(2000):
        router.step()
        src = mid_decode_replica(supervisor, router)
        if src is not None:
            break
    if src is None:
        print("selftest-standby FAIL (phase C): never mid-decode")
        rc = 1
        report = {}
    else:
        report = router.migrate_and_drain(src.name)
        print(f"selftest-standby migration: {json.dumps(report)}")
        router.run_until_drained(max_steps=20000)
    ok_c = check_parity("spec", handles, streamed)
    adopted_primes = 0.0
    dst = (supervisor.replica_by_name(report["to"])
           if report.get("to") else None)
    if dst is not None and dst.backend is not None:
        page = dst.backend.transport.fetch_text("/metrics")
        for sname, labels, value in parse_prometheus(page)["samples"]:
            if (sname == "mingpt_serve_spec_prime_total"
                    and labels.get("mode") == "adopted"):
                adopted_primes = value
    checks_c = [
        ("migrated speculative streams stayed token-exact", ok_c),
        ("migration shipped state (outcome=ok)",
         report.get("outcome") == "ok"),
        ("draft-pool rows rode the transfer channel",
         report.get("draft_rows_installed", 0) >= 1),
        ("the peer primed from shipped rows, not a re-prefill",
         adopted_primes >= 1),
    ]
    for what, ok in checks_c:
        if not ok:
            print(f"selftest-standby FAIL (phase C): {what}")
            rc = 1
    exits = supervisor.shutdown_all()
    print(f"selftest-standby worker exits: {exits}")
    print("selftest-standby", "PASSED" if rc == 0 else "FAILED")
    return rc


def selftest_crosshost(args) -> int:
    """The ISSUE 19 acceptance gate, against REAL subprocesses.

    Two (or ``--hosts``) localhost HostAgents on the wall clock, each
    owning a ProcessSupervisor of real replica worker subprocesses
    behind the mingpt-rpc/1 socket surface, exchanging HMAC-signed
    control envelopes. Quorum is 1 for a two-host drill — a majority of
    two is two, which no single-failure drill can survive.

    Leg A — host death: SIGKILL every worker on host0 while one of its
    requests is mid-decode and stop its agent (the machine died). The
    peer's heartbeat ladder must quarantine it, the frontend must
    declare it failed and adopt its requests, and every caller stream
    must stay token-exact with zero duplicate or lost emissions
    (``recovery_log`` path ``crosshost`` on the adopting host).

    Leg B — paced migration under ``slow_link``: live-migrate a
    mid-decode replica host0 -> host1 through the PacedChannel with
    real sleeps; the measured wall transfer time must be at least the
    token-bucket budget (bytes/rate plus injected per-chunk latency)
    and the migrated streams must stay token-exact.

    Leg C — a control frame tampered after signing is rejected with the
    typed ``BadSignature`` error and a distinct
    ``mingpt_fleet_auth_rejects_total{reason="bad_mac"}`` count."""
    import tempfile
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np

    from mingpt_distributed_tpu.config import GPTConfig
    from mingpt_distributed_tpu.models import generate as gen
    from mingpt_distributed_tpu.models import gpt
    from mingpt_distributed_tpu.serving import (
        ProcRouter,
        ProcessSupervisor,
        Request,
        WallClock,
        process_backend_factory,
    )
    from mingpt_distributed_tpu.serving.procfleet import (
        CrossHostRouter,
        HostAgent,
        LoopbackHostLink,
        PacedChannel,
        envelope,
    )
    from mingpt_distributed_tpu.telemetry import (
        MetricsRegistry,
        parse_prometheus,
    )
    from mingpt_distributed_tpu.training.faults import NetworkFaultInjector

    cfg_kw = dict(
        n_layer=2, n_head=2, n_embd=32, vocab_size=96, block_size=48,
        embd_pdrop=0.0, resid_pdrop=0.0, attn_pdrop=0.0, dtype="float32",
    )
    cfg = GPTConfig.make(**cfg_kw)
    params = gpt.init(jax.random.key(0), cfg)
    canned = ["O God, O God!", "Once more unto", "All the world's",
              "Now is the winter", "Friends, Romans", "To be, or not"]
    prompts = [[ord(c) % cfg.vocab_size for c in s] for s in canned]
    max_new = 12
    secret = args.fleet_secret or "crosshost-drill-secret"
    n_hosts = max(2, args.hosts)
    spill_root = args.spill_dir or tempfile.mkdtemp(prefix="crosshost-")
    rc = 0

    def solo(p, n):
        out = gen.generate(params, cfg, jnp.asarray(p, jnp.int32)[None], n)
        return np.asarray(out)[0, len(p):].tolist()

    def build_mesh(tag, net_faults="", paced_bytes_per_s=None):
        clock = WallClock()
        net = NetworkFaultInjector(net_faults)
        roster = [f"host{i}" for i in range(n_hosts)]
        spec = {"cfg": cfg_kw, "init_seed": 0,
                "server": {"n_slots": 2, "prefill_chunk": 8,
                           "prefix_cache_mb": 4.0}}
        agents = {}
        for host in roster:
            sup = ProcessSupervisor(
                process_backend_factory(
                    spec, os.path.join(spill_root, f"{tag}-{host}"),
                    rpc_timeout_s=120.0),
                n_replicas=2, clock=clock, max_restarts=1,
                restart_backoff_s=0.05, registry=MetricsRegistry())
            router = ProcRouter(sup, max_retries=3, retry_backoff_s=0.01,
                                breaker_reset_s=0.05)
            agents[host] = HostAgent(host, router, roster, clock,
                                     secret=secret,
                                     heartbeat_interval_s=0.05, quorum=1)
        for src in roster:
            agents[src].connect({
                dst: LoopbackHostLink(src, dst, agents[dst], net=net)
                for dst in roster if dst != src})
        streamed = {}
        frontend = CrossHostRouter(
            agents, clock, net=net,
            on_token=lambda c, t: streamed.setdefault(
                c.request_id, []).append(t))
        # real waits: the drill paces against the wall clock
        frontend.paced = PacedChannel(clock,
                                      bytes_per_s=paced_bytes_per_s,
                                      registry=frontend.registry,
                                      sleep=time.sleep)
        return frontend, agents, streamed

    def check_parity(tag, handles, streamed):
        ok = True
        for p, h in zip(prompts, handles):
            want = solo(p, max_new)
            if h.finish_reason != "length" or h.tokens != want:
                print(f"selftest-crosshost FAIL ({tag}) {h.request_id}: "
                      f"reason={h.finish_reason} fleet={h.tokens} "
                      f"solo={want}")
                ok = False
            if streamed.get(h.request_id, []) != h.tokens:
                print(f"selftest-crosshost FAIL ({tag}) {h.request_id}: "
                      f"streamed {streamed.get(h.request_id)} != handle "
                      f"{h.tokens} (duplicate or lost emission)")
                ok = False
        return ok

    def mid_decode_on(frontend, host):
        for c in frontend.handles.values():
            if (c.current_host == host and not c.finished
                    and len(c.tokens) >= 1):
                return c
        return None

    def shutdown(agents):
        for host in sorted(agents):
            try:
                agents[host].router.supervisor.shutdown_all()
            except Exception as e:  # dead hosts already reaped
                print(f"selftest-crosshost: {host} shutdown: {e!r}")

    def samples(page, family):
        return {tuple(sorted(labels.items())): value
                for name, labels, value in parse_prometheus(page)["samples"]
                if name == family}

    # -- Leg A: SIGKILL a whole host mid-decode -----------------------
    frontend, agents, streamed = build_mesh("kill")
    pids = {h: [r.backend.pid for r in a.router.supervisor.replicas]
            for h, a in agents.items()}
    print(f"selftest-crosshost workers: {pids} (spill: {spill_root})")
    handles = [frontend.submit(Request(prompt=p, max_new_tokens=max_new))
               for p in prompts]
    victim = None
    for _ in range(20000):
        frontend.step()
        victim = mid_decode_on(frontend, "host0")
        if victim is not None:
            break
    if victim is None:
        print("selftest-crosshost FAIL (kill): nothing mid-decode on "
              "host0")
        rc = 1
    else:
        agents["host0"].kill_host()  # SIGKILLs every host0 worker
        try:
            frontend.run_until_drained(max_steps=200000)
        except RuntimeError as e:
            print(f"selftest-crosshost FAIL (kill): {e}")
            rc = 1
        ok_kill = check_parity("kill", handles, streamed)
        rows = [row for a in agents.values()
                for row in a.router.supervisor.recovery_log
                if row.get("path") == "crosshost"]
        fo = samples(frontend.fleet_metrics_page(),
                     "mingpt_fleet_crosshost_failovers_total")
        fo_host0 = fo.get((("from_host", "host0"),), 0)
        checks_a = [
            ("streams stayed exact across the host death", ok_kill),
            ("the frontend declared host0 failed",
             "host0" in frontend.summary()["declared_failed"]),
            ("the victim request failed over cross-host",
             victim.recovery_s is not None
             and len(set(victim.hosts)) >= 2),
            ("the adopting host logged path=crosshost recovery rows",
             bool(rows) and all(r["recovery_s"] > 0 for r in rows)),
            ("the failover counter names host0", fo_host0 >= 1),
        ]
        for what, ok in checks_a:
            if not ok:
                print(f"selftest-crosshost FAIL (kill): {what}")
                rc = 1
        if victim.recovery_s is not None:
            print(f"selftest-crosshost host-death recovery: "
                  f"{victim.recovery_s:.3f}s over hosts {victim.hosts}")
    shutdown(agents)

    # -- Leg B: paced migration under slow_link -----------------------
    bytes_per_s = 1e6
    link_delay = 0.02
    frontend, agents, streamed = build_mesh(
        "paced",
        net_faults=f"slow_link:every=1:match=host0->host1:"
                   f"delay={link_delay}",
        paced_bytes_per_s=bytes_per_s)
    handles = [frontend.submit(Request(prompt=p, max_new_tokens=max_new))
               for p in prompts]
    for _ in range(20000):
        frontend.step()
        if mid_decode_on(frontend, "host0") is not None:
            break
    t0 = time.monotonic()
    report = frontend.migrate_crosshost("host0", "host1")
    elapsed = time.monotonic() - t0
    print(f"selftest-crosshost migration: {json.dumps(report)}")
    try:
        frontend.run_until_drained(max_steps=200000)
    except RuntimeError as e:
        print(f"selftest-crosshost FAIL (paced): {e}")
        rc = 1
    ok_paced = check_parity("paced", handles, streamed)
    budget = report["bytes"] / bytes_per_s + link_delay * report["chunks"]
    xb = samples(frontend.fleet_metrics_page(),
                 "mingpt_fleet_xfer_bytes_total")
    shipped = xb.get((("paced", "true"),), 0)
    checks_b = [
        ("migration shipped state (outcome=ok)",
         report["outcome"] == "ok"),
        ("migrated streams stayed token-exact", ok_paced),
        ("the source replica retired with the requeue exit code",
         report["src_exit_code"] == 75),
        ("the wall transfer respected the bandwidth budget "
         f"(transfer_s={report['transfer_s']:.3f}s budget="
         f"{budget:.3f}s wall={elapsed:.3f}s)",
         report["transfer_s"] >= 0.95 * budget
         and elapsed >= 0.95 * budget),
        ("pacing waited, not stalled (within 2s of budget)",
         report["transfer_s"] <= budget + 2.0),
        ("the paced byte counter saw the transfer",
         shipped >= report["bytes"]),
    ]
    for what, ok in checks_b:
        if not ok:
            print(f"selftest-crosshost FAIL (paced): {what}")
            rc = 1

    # -- Leg C: tampered frame -> typed reject + counter --------------
    doc = envelope("heartbeat", host="host0", epoch=0, seq=10_000)
    agents["host0"].auth.sign(doc)
    doc["seq"] = 10_001  # tampered after signing
    resp = json.loads(agents["host1"].handle_host(
        "/host/heartbeat", json.dumps(doc, sort_keys=True).encode()))
    rejects = samples(agents["host1"].router.fleet_metrics_page(),
                      "mingpt_fleet_auth_rejects_total")
    bad_mac = sum(v for labels, v in rejects.items()
                  if dict(labels).get("reason") == "bad_mac")
    checks_c = [
        ("tampered frame rejected with the typed error",
         resp.get("kind") == "error"
         and resp.get("error") == "BadSignature"),
        ("the bad_mac reject counter incremented", bad_mac >= 1),
    ]
    for what, ok in checks_c:
        if not ok:
            print(f"selftest-crosshost FAIL (auth): {what}")
            rc = 1
    print(f"selftest-crosshost auth: reject={resp.get('error')} "
          f"bad_mac={bad_mac}")
    shutdown(agents)
    print("selftest-crosshost", "PASSED" if rc == 0 else "FAILED")
    return rc


def _autoscale_spec(args):
    """Resolve --autoscale / --slo-target into one controller spec (or
    None), failing fast on a malformed spec. ``--autoscale static`` is
    an explicit no-op so scripts can parameterize the flag."""
    spec = args.autoscale
    if spec is None and args.slo_target is not None:
        spec = f"auto:target={args.slo_target}"
    if spec is None:
        return None
    from mingpt_distributed_tpu.control.controller import (
        parse_controller_spec,
    )
    try:
        if parse_controller_spec(spec) is None:
            return None
    except ValueError as e:
        raise SystemExit(f"bad --autoscale spec: {e}")
    return spec


def _check_isolation(isolation: str, platform: str) -> None:
    """Refuse ``--isolation process`` where it cannot work. A chip belongs
    to one process at a time: this parent takes it the moment it puts the
    restored parameters on the device, and every replica worker it then
    spawns inherits its environment and asks for the same chip."""
    if isolation == "process" and platform == "tpu":
        raise SystemExit(
            "--isolation process cannot run on a TPU backend: a chip "
            "belongs to one process, this parent holds it once the "
            "snapshot is on the device, and each spawned replica worker "
            "would then fail or hang asking for the same chip — use "
            "--isolation thread.")


def main(argv=None) -> int:
    args = build_argparser().parse_args(argv)
    if args.selftest_procfleet:
        return selftest_procfleet(args)
    if args.selftest_standby:
        return selftest_standby(args)
    if args.selftest_crosshost:
        return selftest_crosshost(args)
    if args.selftest_sharded:
        return selftest_sharded(args)
    if args.selftest_chaos:
        return selftest_chaos(args)
    if args.selftest_spec:
        return selftest_spec(args)
    if args.selftest_quant:
        return selftest_quant(args)
    if args.selftest:
        return selftest(args)

    import jax

    from mingpt_distributed_tpu.config import load_config
    from mingpt_distributed_tpu.data.token_dataset import make_dataset
    from mingpt_distributed_tpu.serving import InferenceServer
    from mingpt_distributed_tpu.training import checkpoint as ckpt_lib
    from mingpt_distributed_tpu.utils import startup

    startup.enable_compile_cache()
    print(f"[serve] {startup.device_line()}", file=sys.stderr)
    _check_isolation(args.isolation, jax.default_backend())

    cfg = load_config(args.config, args.overrides)
    dataset = make_dataset(cfg.data_config)
    gpt_cfg = dataclasses.replace(
        cfg.gpt_config,
        vocab_size=dataset.vocab_size,
        block_size=dataset.block_size,
        embd_pdrop=0.0, resid_pdrop=0.0, attn_pdrop=0.0,
    )
    path = cfg.trainer_config.snapshot_path or ckpt_lib.DEFAULT_SNAPSHOT_PATH
    snap = ckpt_lib.restore_inference_params(path, gpt_cfg)
    if snap is None:
        print(f"no snapshot at {path}; train first (python train.py)",
              file=sys.stderr)
        return 1
    params = jax.device_put(snap.params)
    print(f"loaded snapshot step {snap.step} from {path}", file=sys.stderr)

    eos_id = None
    if args.eos_text is not None:
        eos = dataset.encode(args.eos_text)
        if len(eos) != 1:
            print(f"--eos-text must encode to one token, got {len(eos)}",
                  file=sys.stderr)
            return 1
        eos_id = int(eos[0])

    # stream tokens as they decode: print the newly-decoded text suffix of
    # each request (decode-accumulated-and-diff is tokenizer-agnostic)
    printed = {}

    def on_token(handle, _tok) -> None:
        text = dataset.decode(handle.tokens)
        sys.stdout.write(text[len(printed.get(handle.request_id, "")):])
        printed[handle.request_id] = text
        sys.stdout.flush()

    guard = _ShutdownGuard().install()
    reg, tserver = _start_telemetry(args)
    recorder, flight = _make_observability(args, reg)
    spec_kw = _spec_kwargs(args, params, gpt_cfg)
    mesh_kw = _mesh_kwargs(args)
    autoscale = _autoscale_spec(args)
    if tserver is not None and flight is not None:
        tserver.flight_provider = lambda: flight.snapshot("on_demand")

    def attach_controller(router):
        """Hang the SLO autoscaler off the router; the control tick
        rides router.step(), so no extra thread is needed."""
        if not autoscale:
            return
        from mingpt_distributed_tpu.control.controller import (
            SLOAutoscaler,
            parse_controller_spec,
        )
        router.controller = SLOAutoscaler(
            router, parse_controller_spec(autoscale),
            log_path=args.control_log)
        print("[serve] SLO autoscaler attached (" + autoscale + ")"
              + (f"; decisions -> {args.control_log}"
                 if args.control_log else ""), file=sys.stderr)

    def build_backend(stream_cb):
        """One InferenceServer by default; --replicas N puts the fleet
        router in front of N supervised replicas (--isolation process
        moves each replica into its own subprocess behind the
        mingpt-rpc/1 socket surface). All expose submit /
        run_until_drained / summary with the same handle surface."""
        if args.isolation == "process":
            import tempfile

            from mingpt_distributed_tpu.serving import (
                ProcRouter,
                ProcessSupervisor,
                WallClock,
                process_backend_factory,
            )
            from mingpt_distributed_tpu.training.faults import (
                ProcessFaultInjector,
            )
            cfg_doc = dataclasses.asdict(gpt_cfg)
            if cfg_doc.get("n_layer") is not None:
                # make() wants model_type XOR explicit dims; asdict
                # carries both once a preset has been resolved
                cfg_doc.pop("model_type", None)
            spec = {
                "cfg": cfg_doc,
                "snapshot": path,  # workers restore the trained params
                "server": {"n_slots": args.slots,
                           "max_queue": args.queue_limit,
                           "default_deadline_s": args.deadline_s,
                           **_server_kwargs(args)},
                "serving_faults": args.chaos_spec,
            }
            spill_root = args.spill_dir or tempfile.mkdtemp(
                prefix="procfleet-")
            # process-level faults (kill/hang/slow_socket) come from
            # MINGPT_PROCESS_FAULTS; serving faults ride in the spec
            pinj = ProcessFaultInjector()
            supervisor = ProcessSupervisor(
                process_backend_factory(spec, spill_root),
                n_replicas=max(1, args.replicas),
                clock=WallClock(),
                process_injector=pinj if pinj.specs else None,
                registry=reg,
                standby=max(0, args.standby),
                hang_deadline_s=args.hang_deadline,
            )
            router = ProcRouter(supervisor, on_token=stream_cb,
                                shed_watermark=args.shed_watermark,
                                trace_recorder=recorder, flight=flight)
            attach_controller(router)
            if tserver is not None:
                tserver.health_provider = router.health_report
                # fleet scrape over RPC: worker /metrics pages merged
                # under the replica label
                tserver.metrics_provider = router.fleet_metrics_page
            return router
        if args.replicas > 1 or autoscale:
            from mingpt_distributed_tpu.serving import (
                ReplicaSupervisor,
                Router,
                WallClock,
                default_server_factory,
            )
            from mingpt_distributed_tpu.training.faults import (
                ServingFaultInjector,
            )
            injector = ServingFaultInjector(args.chaos_spec)
            supervisor = ReplicaSupervisor(
                default_server_factory(
                    params, gpt_cfg, n_slots=args.slots,
                    max_queue=args.queue_limit,
                    default_deadline_s=args.deadline_s,
                    **spec_kw,
                    **mesh_kw,
                    **_server_kwargs(args)),
                n_replicas=args.replicas,
                clock=WallClock(),
                injector=injector if injector.specs else None,
                registry=reg,
            )
            router = Router(supervisor, on_token=stream_cb,
                            shed_watermark=args.shed_watermark,
                            trace_recorder=recorder, flight=flight)
            attach_controller(router)
            if tserver is not None:
                tserver.health_provider = router.health_report
                # fleet-wide observability (ISSUE 13): union scrape page
                tserver.metrics_provider = router.fleet_metrics_page
            return router
        server = InferenceServer(params, gpt_cfg, n_slots=args.slots,
                                 on_token=stream_cb,
                                 log_every=(0 if stream_cb
                                            else args.log_every),
                                 max_queue=args.queue_limit,
                                 default_deadline_s=args.deadline_s,
                                 registry=reg,
                                 trace_recorder=recorder,
                                 **spec_kw,
                                 **mesh_kw,
                                 **_server_kwargs(args))
        if flight is not None:
            server.watchdog.on_recompile = (
                lambda grown: flight.dump("watchdog_recompile",
                                          families=grown))
        return server

    def shutdown(backend) -> int:
        """Common exit path: drain in-flight work, flush metrics, close
        the telemetry endpoint; exit 75 after a signal so schedulers
        requeue instead of failing the job. Under the flight recorder a
        signalled drain also dumps a flight record (the crash-adjacent
        evidence a preemption would otherwise discard); --slo prints
        its graded report from the completed-request traces."""
        if guard.stop_requested and hasattr(backend, "drain"):
            backend.drain()
        backend.run_until_drained()
        if guard.stop_requested and flight is not None:
            flight.dump("sigterm_drain")
        _slo_report(args, recorder)
        if recorder is not None:
            recorder.close()
        if args.metrics_json:
            if hasattr(backend, "metrics"):
                backend.metrics.write_json(args.metrics_json)
            else:
                with open(args.metrics_json, "w") as f:
                    json.dump(backend.summary(), f, indent=2)
                    f.write("\n")
        if tserver is not None:
            tserver.close()
        if guard.stop_requested:
            from mingpt_distributed_tpu.serving.fleet import REQUEUE_EXIT_CODE

            print(f"[serve] drained after signal; exiting "
                  f"{REQUEUE_EXIT_CODE} (requeue)", file=sys.stderr)
            return REQUEUE_EXIT_CODE
        return 0

    if args.prompts_file:
        with open(args.prompts_file, encoding="utf-8") as f:
            lines = [ln.rstrip("\n") for ln in f if ln.strip()]
        server = build_backend(None)
        # per-request isolation: one bad prompt (encode failure, validation
        # error, queue rejection) is reported and skipped — the batch keeps
        # draining instead of the whole engine tearing down
        handles = []
        for ln in lines:
            if guard.stop_requested:
                print(f"[serve] admission stopped by signal; "
                      f"{len(lines) - len(handles)} prompt(s) not admitted",
                      file=sys.stderr)
                break
            try:
                handles.append(
                    (ln, server.submit(_request_for(
                        args, dataset.encode(ln), eos_id))))
            except Exception as e:
                print(f"=== skipped ({type(e).__name__}: {e}) ===\n{ln}",
                      file=sys.stderr)
            server.step()  # drain as we go so a bounded queue makes progress
        rc = shutdown(server)
        for ln, h in handles:
            print(f"=== {h.request_id} ({h.finish_reason}) ===")
            print(ln + dataset.decode(h.tokens))
        print(json.dumps(server.summary()))
        return rc

    # REPL: one prompt per stdin line, streamed as it decodes
    server = build_backend(on_token)
    interactive = sys.stdin.isatty()
    if interactive:
        print("prompt> ", end="", flush=True)
    try:
        for line in sys.stdin:
            prompt = line.rstrip("\n")
            if guard.stop_requested:
                break
            if not prompt:
                if interactive:
                    print("prompt> ", end="", flush=True)
                continue
            # one failing request must not tear down the REPL: report,
            # reprompt
            try:
                sys.stdout.write(prompt)
                server.submit(
                    _request_for(args, dataset.encode(prompt), eos_id))
                server.run_until_drained()
                print()
            except KeyboardInterrupt:
                raise
            except Exception as e:
                print(f"\n[serve] request failed ({type(e).__name__}: {e}); "
                      "still serving", file=sys.stderr)
            if guard.stop_requested:
                break
            if interactive:
                print("prompt> ", end="", flush=True)
    except KeyboardInterrupt:
        # second SIGINT: skip further admission, still drain + flush below
        print("\n[serve] interrupted again — draining and exiting",
              file=sys.stderr)
    return shutdown(server)


if __name__ == "__main__":
    sys.exit(main())
