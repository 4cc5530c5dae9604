"""Continuous-batching scheduler: queue → slots → decode-step boundaries.

The loop the server runs (``step()`` = one scheduling round):

1. **Admit** — while the queue is non-empty and the pool has a free slot,
   pop the request the :class:`AdmissionPolicy` selects (FIFO by
   default; serving/admission.py, trafficlab/policies.py for EDF /
   fair-share), claim the slot, and try a shared-prefix cache hit (device
   row copy — the prompt's cached head costs no FLOPs, only the tail is
   prefilled).
2. **Prefill** — every slot still prefilling advances by at most ONE
   chunk of <= ``prefill_chunk`` tokens (padded to the smallest covering
   bucket of the engine's compiled ladder). Short prompts finish in the
   same round they were admitted — identical latency to the old
   whole-prompt admission — while a long prompt spreads its chunks
   across rounds so co-tenant inter-token latency is bounded by one
   chunk, not one full prompt. The final chunk samples the request's
   first token and flips the slot to decoding.
3. **Decode** — one shared compiled step advances every *decoding* slot
   one token (per-slot positions and sampling params; prefilling and
   free lanes ride along parked at position block_size-1, a row the
   stale-row invariant makes unobservable until its legitimate writer
   fills it). With speculation on (``draft_params`` + ``spec_k``,
   serving/speculative.py), eligible greedy lanes instead run
   propose→verify→accept-n and emit a burst of 1..k+1 tokens per round
   — every one of them still the target model's own greedy choice, so
   parity, retry idempotence and token-index dedup are untouched.
   The round runs one step ahead: it launches step N+1, which takes
   step N's tokens on the device, before it waits for step N and emits
   its tokens (``step``'s docstring has when it does not); what a round
   emits is the step launched a round before.
4. **Retire** — requests hitting a stop condition (per-request
   ``max_new_tokens`` or EOS token) finish, free their slot, and the next
   round's admissions reuse it. Mid-decode admission is the whole point:
   new prompts join while others are half-way through decoding.

Determinism: policy-ordered admission (FIFO default; every shipped
policy tie-breaks by queue position), lowest-free-slot placement, and per-request
PRNG keys ``fold_in(key(seed), token_index)`` — a sampled request's
output depends only on (params, prompt, sampling params, seed), never on
which other requests share the batch. The keys are derived inside the
compiled program that samples with them (``engine.lane_keys``): the host
keeps a request's seed in its slot and hands each round the vector of
seeds and the vector of token indices, so no ``jax.random`` call runs
eagerly between admission and the emitted token. Greedy requests are
token-identical to solo ``generate()`` on the same prompt under every
combination of bucketing, chunking and prefix reuse (asserted in
tests/test_serving.py): chunked prefill is row-equivalent to the
one-shot forward, and prefix rows are bit-identical to what recomputing
them would produce. The same property is what makes fleet-level retry
idempotent (serving/fleet.py): a crashed replica's request re-prefills
from the original prompt on a survivor and produces the same greedy
token at every index, so already-streamed tokens dedup by position.

Prompt bounds: prompts longer than ``prefill_len`` are cropped to their
last ``prefill_len`` tokens (the server has no sliding-window decode path
— unlike solo ``generate()``'s overflow semantics, positions restart at 0
for the cropped prompt), and ``max_new_tokens`` is clamped so decode
positions never leave the ``block_size`` window. ``strict_window=True``
rejects instead of cropping/clamping (``Request.validate`` with the
engine's bounds).

Request state vs slot state (ISSUE 6 split): :class:`Request`,
:class:`RequestHandle` and the backpressure errors live in
``serving/requests.py`` — a request outlives the replica serving it.
:class:`SlotTable` below owns everything that dies with this engine:
the handle↔slot binding and the per-slot decode-state arrays.

Robustness under sustained traffic (ISSUE 2):

* **bounded queue** — ``max_queue`` caps waiting requests; beyond it,
  ``submit`` raises :class:`QueueFullError` carrying the observed depth
  and a suggested retry-after (backpressure the caller can act on)
  instead of growing the deque without bound;
* **deadlines** — a per-request ``deadline_s`` (or the server-wide
  ``default_deadline_s``) expires requests at step boundaries, whether
  still queued, mid-prefill or mid-decode, so an abandoned request can
  never pin a KV slot forever (``finish_reason="deadline"``);
* **callback isolation** — a raising ``on_token`` callback retires the
  request and frees its slot (``finish_reason="error"``, the exception
  on ``handle.error``) instead of leaking the slot or tearing down the
  scheduling loop for every other tenant.
"""

from __future__ import annotations

import itertools
import time
from collections import deque
from typing import (
    Any,
    Callable,
    Deque,
    Dict,
    List,
    NamedTuple,
    Optional,
    Sequence,
    Tuple,
)

import numpy as np

from mingpt_distributed_tpu.config import ConfigError, GPTConfig
from mingpt_distributed_tpu.serving.admission import AdmissionPolicy, FifoPolicy
from mingpt_distributed_tpu.serving.engine import (
    DecodeEngine,
    DecodeLaunch,
    decode_rows_read,
    ring_rows,
    sampler_orders,
)
from mingpt_distributed_tpu.serving.metrics import ServingMetrics
from mingpt_distributed_tpu.serving.requests import (  # noqa: F401  (re-export)
    QueueFullError,
    Request,
    RequestHandle,
    ShedError,
)
from mingpt_distributed_tpu.serving.speculative import SpeculativeDecoder
from mingpt_distributed_tpu.telemetry import (
    MetricsRegistry,
    RecompileWatchdog,
    SpanTracer,
    log_event,
)
from mingpt_distributed_tpu.telemetry.programs import program_records
from mingpt_distributed_tpu.telemetry.tracing import (
    TraceRecorder,
    trace_baggage,
)


class _Phase:
    """One phase of a scheduling round, timed at one call site.

    Entering opens a span of the server's :class:`SpanTracer` (and with it
    a profiler annotation). Where a :class:`TraceRecorder` is wired and a
    request has a trace, the same phase is filed into that trace: at the
    end for the request the phase was opened for, or by :meth:`file` for
    each request of a phase that serves many. The recorder's times come
    from the server's injectable ``clock``, read here and nowhere else in
    the phase, so on a virtual clock the per-request records stay exact;
    ``dur_s`` is on that clock too, for the metrics that want the phase's
    length."""

    __slots__ = ("_server", "name", "_handle", "fields", "_span", "_t0",
                 "dur_s")

    def __init__(self, server: "InferenceServer", name: str,
                 handle: Optional[RequestHandle], fields: Dict[str, Any]):
        self._server = server
        self.name = name
        self._handle = handle
        self.fields = fields
        if handle is not None:
            fields = {"request_id": handle.request_id, **fields}
        self._span = server.tracer.span(name, **fields)
        self.dur_s = 0.0

    def __enter__(self) -> "_Phase":
        self._span.__enter__()
        self._t0 = self._server.clock()
        return self

    def __exit__(self, *exc) -> bool:
        self.lap()
        self._span.__exit__(*exc)
        if self._handle is not None and exc[0] is None:
            self.file(self._handle)
        return False

    def set(self, **fields: Any) -> None:
        """Fields learned inside the phase (a chunk's padded length)."""
        self.fields.update(fields)
        self._span.set(**fields)

    def lap(self) -> None:
        """Fix ``dur_s`` at the phase's length up to now."""
        self.dur_s = self._server.clock() - self._t0

    def file(self, handle: RequestHandle, name: Optional[str] = None,
             **fields: Any) -> None:
        """The phase, from its start to the last :meth:`lap`, as a span of
        ``handle``'s trace."""
        rec = self._server.trace_recorder
        if rec is not None and handle.trace is not None:
            rec.add_span(handle.trace, name or self.name, ts=self._t0,
                         dur_s=self.dur_s, **self.fields, **fields,
                         request_id=handle.request_id)


class _InFlight(NamedTuple):
    """A decode step the device has been handed: the engine's handle on its
    tokens, and each lane it was launched for as (slot, request)."""

    step: DecodeLaunch
    lanes: List[Tuple[int, RequestHandle]]


class SlotTable:
    """Slot-side state of one engine replica: the handle occupying each
    KV lane plus the per-slot decode-state arrays fed whole to the shared
    compiled decode step.

    Non-decoding lanes (free or still prefilling) are PARKED at position
    ``block_size - 1``: the decode program writes one row per slot
    unconditionally, and that row is the only one a later legitimate
    writer is guaranteed to refill before any query can attend it —
    parking anywhere lower could clobber rows a chunked prefill has
    already written.

    The decode round runs one step ahead (``InferenceServer.step``), so a
    decoding lane's ``positions`` entry is where its *next launch* feeds a
    token: it moves at the launch, not at the emit, and ``ahead`` counts
    the lane's tokens the device has been handed and the host has not
    seen. ``tokens`` holds what the host last emitted; a lane with a token
    on its way feeds that one instead, on the device.
    """

    def __init__(self, n_slots: int, block_size: int):
        self.n_slots = n_slots
        self.parked = block_size - 1
        self.handles: List[Optional[RequestHandle]] = [None] * n_slots
        self.tokens = np.zeros(n_slots, np.int32)
        self.positions = np.full(n_slots, self.parked, np.int32)
        self.temps = np.ones(n_slots, np.float32)
        self.top_ks = np.zeros(n_slots, np.int32)
        self.top_ps = np.ones(n_slots, np.float32)
        self.do_sample = np.zeros(n_slots, bool)
        # request seeds, the low 32 bits (all jax.random.key keeps of a
        # seed); free lanes hold 0
        self.seeds = np.zeros(n_slots, np.uint32)
        # tokens of the slot's request the device has been handed and the
        # host has not seen: 1 while a step launched for it is in flight (2
        # between the launch of the next and the sync of that one)
        self.ahead = np.zeros(n_slots, np.int32)

    def bind(self, slot: int, handle: RequestHandle, seed: int) -> None:
        handle.slot = slot
        self.handles[slot] = handle
        self.seeds[slot] = seed & 0xFFFFFFFF

    def release(self, slot: int) -> None:
        """Back to a free lane's state: parked, and asking the sampler for
        nothing (a tenant's ``do_sample`` left behind would keep the
        decode program sorting for nobody: engine.sampler_orders)."""
        self.handles[slot] = None
        self.seeds[slot] = 0
        self.ahead[slot] = 0
        self.positions[slot] = self.parked
        self.temps[slot], self.top_ks[slot], self.top_ps[slot] = 1.0, 0, 1.0
        self.do_sample[slot] = False

    def start_decode(self, slot: int, token: int, position: int,
                     req: Request) -> None:
        """Flip a freshly-prefilled slot to decoding: the first generated
        token is fed at ``position`` (= len(prompt)) next round."""
        self.tokens[slot] = token
        self.positions[slot] = position
        self.temps[slot] = req.temperature
        self.top_ks[slot] = 0 if req.top_k is None else req.top_k
        self.top_ps[slot] = 1.0 if req.top_p is None else req.top_p
        self.do_sample[slot] = req.do_sample

    def token_indices(self, slots: Sequence[int]) -> np.ndarray:
        """(n_slots,) int32: for each of ``slots`` the index of the token
        its request samples next (how many it has emitted, and how many
        more are on their way: ``ahead``), 0 elsewhere. With ``seeds`` this
        is all the decode program needs to derive the round's keys."""
        index = np.zeros(self.n_slots, np.int32)
        for s in slots:
            index[s] = len(self.handles[s].tokens) + self.ahead[s]
        return index

    def live_handles(self) -> List[RequestHandle]:
        return [h for h in self.handles if h is not None]

    def decoding_slots(self) -> List[int]:
        return [s for s, h in enumerate(self.handles)
                if h is not None and not h.prefilling]

    @property
    def occupied(self) -> int:
        return sum(h is not None for h in self.handles)


class InferenceServer:
    """Slot-scheduled continuous-batching server over a DecodeEngine."""

    def __init__(
        self,
        params,
        cfg: GPTConfig,
        n_slots: int = 4,
        prefill_len: Optional[int] = None,
        metrics: Optional[ServingMetrics] = None,
        on_token: Optional[Callable[[RequestHandle, int], None]] = None,
        log_every: int = 0,
        max_queue: Optional[int] = None,
        default_deadline_s: Optional[float] = None,
        clock: Callable[[], float] = time.perf_counter,
        prefill_buckets: Optional[Sequence[int]] = None,
        prefill_chunk: Optional[int] = None,
        prefix_cache_mb: float = 0.0,
        warmup: bool = False,
        registry: Optional[MetricsRegistry] = None,
        tracer: Optional[SpanTracer] = None,
        recompile_fail: bool = False,
        strict_window: bool = False,
        fault_hook: Optional[Callable[[str], None]] = None,
        trace_recorder: Optional[TraceRecorder] = None,
        draft_params=None,
        draft_cfg: Optional[GPTConfig] = None,
        spec_k: int = 0,
        admission_policy: Optional[AdmissionPolicy] = None,
        mesh=None,
        tp_axis: str = "tp",
        kv_dtype: Optional[str] = None,
    ):
        self.cfg = cfg
        # disabled-by-default tracer: span() returns a shared no-op, so the
        # scheduling loop pays nothing unless telemetry is wired in. The
        # engine gets the same one: the two halves of its decode step are
        # children of this server's serve.decode_round
        self.tracer = tracer if tracer is not None else SpanTracer(enabled=False)
        # mesh passes through untouched: the scheduler owns slots
        # (ownership), the engine's sharding owns placement — the two
        # never interact, so every scheduling decision below is
        # mesh-oblivious.
        self.engine = DecodeEngine(
            params, cfg, n_slots, prefill_len,
            prefill_buckets=prefill_buckets, prefill_chunk=prefill_chunk,
            prefix_cache_mb=prefix_cache_mb,
            mesh=mesh, tp_axis=tp_axis, kv_dtype=kv_dtype,
            tracer=self.tracer,
        )
        # speculative decoding (serving/speculative.py): a draft model +
        # spec_k >= 1 turn the decode round into propose→verify→accept-n.
        # Off by default — with it off the decode round is byte-identical
        # to the plain path (compile_counts reports no spec families).
        if draft_params is not None:
            if draft_cfg is None:
                raise ValueError("draft_params given without draft_cfg")
            if cfg.mixer_types is not None or draft_cfg.mixer_types is not None:
                raise ConfigError(
                    "speculation rolls rejected tokens back by position, and "
                    "a hybrid stack's state has none: neither the target "
                    "nor the draft may set mixer_types")
            if cfg.layer_types is not None \
                    or draft_cfg.layer_types is not None:
                raise ConfigError(
                    "speculation writes the rows of the tokens it proposes "
                    "and rolls the rejected ones back by position: in a "
                    "window layer's ring they have overwritten the rows of "
                    "the window's far end, which no roll-back restores; "
                    "neither the target nor the draft may set layer_types")
            if cfg.n_passes > 1 or draft_cfg.n_passes > 1:
                raise ConfigError(
                    "speculation (spec_k) is not built for a looped stack "
                    "(n_passes > 1): the verify program's rows of passes x "
                    "layers planes and their roll-back are untested, for "
                    "the target and for the draft")
            if spec_k < 1:
                raise ValueError(
                    "draft model given but spec_k < 1: pass spec_k >= 1 "
                    "to enable speculation (or drop the draft)")
            self.spec: Optional[SpeculativeDecoder] = SpeculativeDecoder(
                self.engine, draft_params, draft_cfg, spec_k)
        elif spec_k >= 1:
            raise ValueError("spec_k >= 1 requires draft_params/draft_cfg")
        else:
            self.spec = None
        # control-plane gate (ISSUE 20): round-level speculation on/off.
        # Gating is token-exact — verify guarantees parity, and a gated
        # round's draft rows merely go stale (advisory state), so the
        # autoscaler can trade draft compute for aggregate throughput
        # mid-stream without touching emitted tokens.
        self.spec_enabled = True
        self.metrics = metrics or ServingMetrics(
            n_slots, log_every=log_every, registry=registry)
        self.metrics.engine_built(
            self.engine.program_param_bytes, self.engine.n_cast_leaves,
            self.engine.kv_bytes_per_row, self.engine.moe_rows,
            self.engine.state_bytes_per_slot, self.engine.sparse_rows,
            self.engine.loop_passes, self.engine.pool.row_width,
            self.engine.pool.row_tiles, self.engine.head_boundaries,
            self.engine.ring_bytes_per_slot, self.engine.ring_rows_per_slot,
            self.engine.kernel_walk_layers, self.engine.ring_planes)
        # post-warmup recompile watchdog over the compiled program families
        # (the merged server-level counts, so draft/verify traces are
        # watched too; armed after warmup(); checked every round)
        self.watchdog = RecompileWatchdog(
            self.compile_counts,
            registry=self.metrics.registry if registry is None else registry,
            tracer=self.tracer,
            hard_fail=recompile_fail,
        )
        # KV storage dtype as a build-info-style gauge (ISSUE 18): one
        # labeled child set to 1, so a scrape (and the fleet-merged
        # scrape, per-replica) states which dtype this server runs
        # without needing a registry schema change per dtype. A second
        # gauge carries the quantization quality number the selftest
        # samples (max |Δlogit| of a KV round trip) — quantized servers
        # only; the fp32 scrape is byte-identical to pre-quant builds.
        _reg = self.metrics.registry if registry is None else registry
        self._quant_err_gauge = None
        if _reg is not None:
            _reg.gauge(
                "mingpt_serve_kv_dtype",
                help="KV-cache storage dtype (build-info style: the "
                     "labeled child is 1)",
                labels=("kv_dtype",),
            ).labels(kv_dtype=self.engine.kv_dtype).set(1)
            if self.engine.kv_quant is not None:
                self._quant_err_gauge = _reg.gauge(
                    "mingpt_serve_quant_logit_err_max",
                    help="max |logit delta| of a KV quantize/dequantize "
                         "round trip, as sampled by the quant selftest",
                )
        self.on_token = on_token
        if max_queue is not None and max_queue < 1:
            raise ValueError(f"max_queue must be >= 1, got {max_queue}")
        self.max_queue = max_queue
        self.default_deadline_s = default_deadline_s
        self.clock = clock  # injectable for deterministic deadline tests
        self.strict_window = strict_window
        # chaos-harness hook (serving/fleet.py): called with a fault-point
        # name at scheduling-loop boundaries; an injector raising here
        # models a replica failing mid-round. "decode_round" fires after
        # the compiled step returned but BEFORE any token is emitted —
        # the computed tokens are lost, never streamed, so retry-on-a-
        # survivor cannot double-emit.
        self.fault_hook = fault_hook
        # request-scoped tracing (ISSUE 10). Settable attribute: the
        # fleet router pushes its recorder onto every replica server
        # (including respawned ones) after construction. A request
        # arriving with a TraceContext (a router attempt) parents into
        # that trace; one without gets a trace minted here (solo mode),
        # and then this server also owns emit events + end_trace.
        self.trace_recorder = trace_recorder
        # admission ordering (ISSUE 12): which queued request takes the
        # next free slot. The default FifoPolicy selects index 0 —
        # identical to the historical popleft() — so existing behavior
        # is preserved unless a policy is injected.
        self.admission_policy = (admission_policy if admission_policy
                                 is not None else FifoPolicy())
        self.queue: Deque[RequestHandle] = deque()
        self.slots = SlotTable(n_slots, cfg.block_size)
        # decode steps launched and not yet synced, oldest first: one
        # between rounds at most, two inside a round that runs ahead
        self._flight: Deque[_InFlight] = deque()
        self._ids = itertools.count()
        if warmup:
            self.engine.warmup()
            if self.spec is not None:
                self.spec.warmup()
            self.watchdog.arm()
        # which named scope each instruction of each program came from: made
        # when somebody first reads the tracer, never with it disabled
        self.tracer.pin("program", lambda: program_records(
            [*self.engine.programs(),
             *(self.spec.programs() if self.spec is not None else ())]))

    def observe_quant_logit_error(self, err: float) -> None:
        """Record a sampled quantization quality number (max |Δlogit| of
        a KV round trip, ``quant.max_abs_logit_error``) into the
        ``mingpt_serve_quant_logit_err_max`` gauge. No-op on fp32
        servers or when no registry is wired in."""
        if self._quant_err_gauge is not None:
            self._quant_err_gauge.set(float(err))

    # -- submission ----------------------------------------------------
    def submit(self, request: Request) -> RequestHandle:
        if self.strict_window:
            request.validate(block_size=self.cfg.block_size,
                             prefill_len=self.engine.prefill_len)
        else:
            request.validate()
        if self.max_queue is not None and len(self.queue) >= self.max_queue:
            depth = len(self.queue)
            self.metrics.on_reject(reason="queue_full")
            # suggested retry-after: roughly how long the queue takes to
            # move one slot's worth of work — depth × observed ITL, with
            # a floor so a cold server still suggests a sane backoff
            itl = self.metrics.itl_mean_s
            retry_after = max(0.05, depth * (itl if itl else 0.02))
            raise QueueFullError(
                f"request queue full ({depth}/{self.max_queue} waiting, "
                f"{self.engine.pool.used_count} decoding) — shed load or "
                f"retry in ~{retry_after:.2f}s",
                queue_depth=depth,
                retry_after_s=retry_after,
            )
        pl = self.engine.prefill_len
        prompt = list(request.prompt)[-pl:]
        # decode feeds generated tokens at positions len(prompt) ..
        # len(prompt)+n-2 (the last token is never fed), all < block_size
        max_new = min(request.max_new_tokens,
                      self.cfg.block_size - len(prompt) + 1)
        now = self.clock()
        deadline_s = (request.deadline_s if request.deadline_s is not None
                      else self.default_deadline_s)
        handle = RequestHandle(
            request=request,
            request_id=request.request_id or f"req-{next(self._ids)}",
            prompt_used=prompt,
            max_new_effective=max_new,
            submit_time=now,
            deadline=None if deadline_s is None else now + deadline_s,
        )
        rec = self.trace_recorder
        if request.trace is not None:
            handle.trace = request.trace
        elif rec is not None:
            handle.trace = rec.start_trace(
                handle.request_id, now=now, baggage=trace_baggage(request))
            handle.trace_owner = True
        if rec is not None and handle.trace is not None:
            rec.add_event(handle.trace, "queued", now,
                          request_id=handle.request_id,
                          queue_depth=len(self.queue))
        self.queue.append(handle)
        self.metrics.on_submit()
        return handle

    # -- scheduling ----------------------------------------------------
    def _check_stop(self, handle: RequestHandle, token: int) -> bool:
        if (handle.request.eos_id is not None
                and token == handle.request.eos_id):
            handle.finish_reason = "eos"
            return True
        if len(handle.tokens) >= handle.max_new_effective:
            handle.finish_reason = "length"
            return True
        return False

    def _emit(self, handle: RequestHandle, token: int) -> bool:
        """Record a decoded token and stream it. Returns False when the
        user's on_token callback raised — the caller must retire the
        request (freeing its slot) instead of leaking it."""
        now = self.clock()
        if handle.first_token_time is None:
            handle.first_token_time = now
        handle.last_token_time = now
        handle.tokens.append(token)
        self.metrics.on_tokens(1)
        # emit events are recorded by whoever minted the trace — the
        # router under a fleet (its clock, dedup-aware across retries),
        # this server in solo mode — so each visible token is exactly
        # one event even when a retried attempt replays a prefix
        if (self.trace_recorder is not None and handle.trace is not None
                and handle.trace_owner):
            self.trace_recorder.add_event(
                handle.trace, "emit", now,
                token_index=len(handle.tokens) - 1)
        if self.on_token is not None:
            try:
                self.on_token(handle, token)
            except Exception as e:  # the callback is user code: isolate it
                handle.error = e
                log_event(
                    f"[serve] on_token callback raised for "
                    f"{handle.request_id}: {e!r} — retiring request, "
                    f"freeing its slot",
                    tracer=self.tracer, request_id=handle.request_id,
                )
                return False
        return True

    def _release_slot(self, handle: RequestHandle) -> None:
        slot = handle.slot
        if slot is not None:
            handle.slot = None
            handle.prefilling = False
            # lane-steps launched for a request that has now stopped
            self.metrics.on_lane_steps_discarded(int(self.slots.ahead[slot]))
            self.slots.release(slot)
            self.engine.pool.free(slot)
            if self.spec is not None:
                self.spec.release(slot)

    def _retire(self, handle: RequestHandle) -> None:
        assert handle.slot is not None
        handle.finished = True
        self._release_slot(handle)
        span = (handle.last_token_time or 0.0) - (handle.first_token_time or 0.0)
        self.metrics.on_complete(len(handle.tokens), span)
        self._end_owned_trace(handle)

    def _end_owned_trace(self, handle: RequestHandle) -> None:
        if (self.trace_recorder is not None and handle.trace is not None
                and handle.trace_owner):
            extra: Dict[str, Any] = {}
            if self.spec is not None:
                # per-request speculation outcome rides the summary dict:
                # accept-rate = spec_accepted / spec_proposed
                extra = dict(spec_proposed=handle.spec_proposed,
                             spec_accepted=handle.spec_accepted)
            self.trace_recorder.end_trace(
                handle.trace, now=self.clock(),
                outcome=handle.finish_reason or "error",
                n_tokens=len(handle.tokens), attempts=1, **extra)

    def _fail(self, handle: RequestHandle, reason: str) -> None:
        """Terminal non-success: deadline expiry (queued, mid-prefill or
        mid-decode) or a raising callback. Frees the slot so it can never
        stay pinned."""
        handle.finished = True
        handle.finish_reason = reason
        self._release_slot(handle)
        if reason == "deadline":
            self.metrics.on_expire()
        else:
            self.metrics.on_error()
        self._end_owned_trace(handle)

    def _expire_if_due(self, handle: RequestHandle, now: float) -> bool:
        if handle.deadline is not None and now >= handle.deadline:
            self._fail(handle, "deadline")
            return True
        return False

    def cancel(self, request_id: str) -> bool:
        """Terminate one accepted-but-unfinished request (ISSUE 16: the
        procfleet RPC cancel endpoint): a queued request leaves the queue,
        an in-flight one frees its slot. Either way the handle finishes
        with reason "cancelled" and the error counter ticks — a cancel is
        a non-success outcome, not a completion. Returns False when no
        live request carries the id (already finished, or never here)."""
        for h in list(self.queue):
            if h.request_id == request_id and not h.finished:
                self.queue.remove(h)
                self._fail(h, "cancelled")
                return True
        for h in self.slots.live_handles():
            if h.request_id == request_id and not h.finished:
                self._fail(h, "cancelled")
                return True
        return False

    def _phase(self, name: str, handle: Optional[RequestHandle] = None,
               **fields: Any) -> _Phase:
        return _Phase(self, name, handle, fields)

    def _queue_wait(self, handle: RequestHandle) -> None:
        """``serve.queue_wait``: submit to admit, a wait that began in
        another call, so it is filed whole at its end: into the tracer's
        ring always, into the request's trace where there is one."""
        wait = handle.admit_time - handle.submit_time
        self.tracer.add_span("serve.queue_wait", wait,
                             request_id=handle.request_id)
        rec = self.trace_recorder
        if rec is not None and handle.trace is not None:
            rec.add_span(handle.trace, "serve.queue_wait",
                         ts=handle.submit_time, dur_s=wait,
                         request_id=handle.request_id)

    def _admit(self, handle: RequestHandle) -> None:
        """Claim a slot and start admission: a shared-prefix hit installs
        its rows now (device copy); prompt tokens beyond it prefill in the
        chunk phase — same round for short prompts, spread over rounds
        for long ones."""
        slot = self.engine.pool.allocate()
        assert slot is not None
        if self.spec is not None:
            # mirrored draft lane: both pools allocate lowest-free-index
            # and free together, so the indices coincide (bind asserts it)
            self.spec.bind(slot)
        handle.prefilling = True
        handle.admit_time = self.clock()
        self._queue_wait(handle)
        self.slots.bind(slot, handle, handle.request.seed)
        with self._phase("serve.prefix_lookup", handle) as ph:
            hit = self.engine.try_load_prefix(slot, handle.prompt_used)
            ph.set(hit_rows=hit)
        self.metrics.on_prefix_lookup(
            hit > 0, hit, enabled=self.engine.prefix_store is not None)
        handle.prefix_rows = hit
        handle.prefill_pos = hit

    def _prefill_one_chunk(self, handle: RequestHandle) -> None:
        """Advance a prefilling slot by one chunk; the final chunk samples
        the request's first token and flips the slot to decoding."""
        req = handle.request
        slot = handle.slot
        prompt = handle.prompt_used
        n_total = len(prompt)
        pos = handle.prefill_pos
        take = min(n_total - pos, self.engine.chunk_size)
        end = pos + take
        last = end == n_total
        off = pos
        bucket = self.engine.bucket_for(take)
        if off + bucket > self.cfg.block_size:
            # the final bucket would overrun the cache window: shift the
            # chunk window back and re-prefill the overlap. Rewriting rows
            # with the values they already hold is exact (the forward is
            # deterministic and row-wise), so parity is unaffected — we
            # trade a few redundant row-FLOPs for a bounded program count.
            off = self.cfg.block_size - bucket
        with self._phase("serve.prefill_chunk", handle,
                         pos=pos, tokens=end - pos) as ph:
            tok, padded = self.engine.prefill_chunk_call(
                slot, prompt[off:end], off,
                req.temperature, req.top_k, req.top_p, req.do_sample,
                self.slots.seeds[slot],
            )
            ph.set(padded=padded)
        self.metrics.on_prefill_chunk(end - pos, padded, ph.dur_s)
        handle.prefill_pos = end
        if not last:
            return
        handle.prefilling = False
        if self.engine.prefix_store is not None:
            self.engine.save_prefix(slot, prompt)
        if self.spec is not None:
            # draft prime: a full prefill of the prompt, or — when
            # migration parked this prompt's draft rows on us — a
            # device-side row install plus at most a tail chunk
            mode = self.spec.prime(slot, prompt, self.slots.seeds[slot])
            self.metrics.on_spec_prime(mode)
        ok = self._emit(handle, tok)
        now = self.clock()
        self.metrics.on_prefill(
            handle.ttft_s or 0.0, now - (handle.admit_time or now))
        self.slots.start_decode(slot, tok, n_total, req)
        if not ok:
            self._fail(handle, "error")
        elif self._check_stop(handle, tok):
            self._retire(handle)

    def _fire_fault(self, where: str) -> None:
        if self.fault_hook is not None:
            self.fault_hook(where)

    def _may_launch(self, lanes: List[int]) -> bool:
        """Whether the round may hand the device a step for ``lanes`` now.
        With nothing in flight, always. Ahead of a step in flight, unless
        one of that step's tokens is its request's last by length, the one
        stop the host knows before it sees the token: the round then syncs
        first, the slot is freed with nothing in flight and the prefill of
        the request that takes it starts at once (a closed loop sends that
        request the moment the reply ends), and no step is launched to be
        discarded."""
        st = self.slots
        return not self._flight or not any(
            st.ahead[s] and len(st.handles[s].tokens) + st.ahead[s]
            >= st.handles[s].max_new_effective for s in lanes)

    def _launch(self, lanes: List[int]) -> None:
        """Hand the device one decode step for ``lanes`` and move the host's
        state on as far as it can without the tokens: a lane with a token
        on its way (``SlotTable.ahead``) feeds that one, taken on the
        device from the step in flight, and samples under the index after
        it; its position and its count of tokens ahead go up by one. The
        launch is filed with the *requests* it was made for: the slot of
        one that stops meanwhile may have another tenant by the sync."""
        st = self.slots
        # the keys themselves are folded inside the program; what the host
        # builds of them is the index vector
        with self.tracer.span("serve.fold_keys", lanes=len(lanes)):
            index = st.token_indices(lanes)
        # the lanes this step is run for: the program reads a long slot
        # only as far as the furthest of them stands; every other lane is
        # parked (a speculating one too: the verify program writes its rows)
        live = np.zeros(st.n_slots, bool)
        live[lanes] = True
        pos = np.where(live, st.positions, st.parked)
        last = self._flight[-1].step if self._flight else None
        step = self.engine.launch_decode(
            st.tokens, pos, st.temps, st.top_ks, st.top_ps, st.do_sample,
            st.seeds, index, live, prev=last, from_prev=live & (st.ahead > 0))
        self.metrics.on_decode_launch(ahead=last is not None)
        # the program's own rules, on the vectors it got
        if sampler_orders(st.do_sample, st.top_ks, st.top_ps):
            self.metrics.on_sampler_sorted()
        self.metrics.on_decode_rows(
            int(decode_rows_read(pos, live, self.engine.walk)),
            st.n_slots * self.cfg.block_size)
        if self.engine.ring_walk is not None:
            read, inside = ring_rows(pos, live, self.engine.ring_walk)
            self.metrics.on_ring_rows(
                int(read) * self.engine.ring_planes,
                int(inside) * self.engine.ring_planes)
        self._flight.append(
            _InFlight(step, [(s, st.handles[s]) for s in lanes]))
        st.positions[lanes] += 1
        st.ahead[lanes] += 1

    def _sync(self) -> Dict[int, List[int]]:
        """Wait for the oldest step in flight. Returns its token for every
        lane whose request is still its slot's; the token of a request that
        has stopped since the launch (an EOS, a cancel, a deadline, a
        raising callback: ``_release_slot`` counted the lane-step as
        discarded) is dropped, whoever holds the slot now."""
        st = self.slots
        done = self._flight.popleft()
        nxt = self.engine.sync_decode(done.step)
        burst: Dict[int, List[int]] = {}
        for s, handle in done.lanes:
            if st.handles[s] is handle:
                st.ahead[s] -= 1
                burst[s] = [int(nxt[s])]
        return burst

    def _forget_flight(self) -> None:
        """Back to what the host has seen: every step in flight is let go
        (its rows are written again, the same, when its tokens are computed
        again) and each decoding lane stands where its last emitted token
        is fed."""
        st = self.slots
        self._flight.clear()
        st.ahead[:] = 0
        for s in st.decoding_slots():
            handle = st.handles[s]
            st.positions[s] = len(handle.prompt_used) + len(handle.tokens) - 1

    def step(self) -> bool:
        """One scheduling round (expire → admit → prefill chunks → decode
        → retire). Returns True while any request is queued or in flight.

        The decode round runs one step ahead: before it waits for step N's
        tokens it launches step N+1, which feeds them on the device, so the
        device goes from one step into the next while the host wakes, emits
        and returns to its caller. The depth (0 or 1) follows from what the
        round holds and from nothing else: a round syncs first where a
        token in flight is a request's last by length or a lane
        speculates, and the round after one that left nothing in flight
        launches twice. A stop the host cannot foresee (EOS, cancel,
        deadline, a raising ``on_token``) leaves one lane-step in flight
        for a request that is gone: it writes a row inside the request's
        own slot, which the stale-row invariant hides from the slot's next
        tenant, its token is dropped, and ``decode_lane_steps_discarded``
        counts it. A request's tokens, and the keys they were sampled
        under, are those of the synchronous order."""
        # deadline sweep first: expired queued requests never take a slot,
        # expired in-flight requests release theirs before admission
        now = self.clock()
        expired_queued = [h for h in self.queue
                          if self._expire_if_due(h, now)]
        if expired_queued:
            self.queue = deque(h for h in self.queue if not h.finished)
        for h in self.slots.live_handles():
            self._expire_if_due(h, now)

        while self.queue and self.engine.pool.free_count:
            idx = self.admission_policy.select(self.queue, now)
            h = self.queue[idx]
            del self.queue[idx]
            self.admission_policy.on_admit(h)
            with self.tracer.span("serve.admit", request_id=h.request_id):
                self._admit(h)

        # one chunk per prefilling slot per round: a long prompt's
        # admission cost is spread out, so co-tenant inter-token latency
        # is bounded by one chunk forward, not one full-prompt forward
        for h in self.slots.live_handles():
            if h.prefilling:
                self._prefill_one_chunk(h)

        active = self.slots.decoding_slots()
        stepped = 0
        if active or self._flight:
            # serve.decode_round and its four children, one of each in a
            # steady round: serve.fold_keys and serve.decode_launch (the
            # next step, _launch), serve.decode_sync (the step before it,
            # _sync) and serve.emit. A round that may not run ahead has the
            # last two alone; the round after it launches twice
            with self._phase("serve.decode_round",
                             lanes=len(active)) as round_:
                st = self.slots
                # speculation split: greedy lanes with k+1 rows of window
                # headroom run propose→verify→accept-n; sampled lanes and
                # near-window tails keep the plain one-token step (parity
                # and key-folding semantics unchanged on both paths)
                eligible: List[int] = []
                if self.spec is not None and self.spec_enabled:
                    eligible = [s for s in active if self.spec.eligible(
                        bool(st.do_sample[s]), int(st.positions[s]))]
                plain = [s for s in active if s not in eligible]
                # how many tokens a burst accepts is the host's to find, so
                # a round with a lane that speculates keeps nothing in
                # flight past its own sync, and speculates only once
                # nothing is
                spec_slots = [] if self._flight else eligible
                depth = 0 if eligible else 1
                # one step ahead, where the round's own state allows
                # (_may_launch): the next step is launched before the host
                # waits for the last one, whose tokens it takes on the
                # device
                while (plain and len(self._flight) <= depth
                       and self._may_launch(plain)):
                    self._launch(plain)
                    stepped += len(plain)
                burst = self._sync() if self._flight else {}
                if spec_slots:
                    stepped += len(spec_slots)
                    with self.tracer.span("serve.fold_keys",
                                          lanes=len(spec_slots)):
                        index = st.token_indices(spec_slots)
                    smask = np.zeros(st.n_slots, bool)
                    smask[spec_slots] = True
                    proposals = self.spec.propose(
                        st.tokens, st.positions, smask, st.seeds, index)
                    fill_mask = np.zeros(st.n_slots, bool)
                    fill_toks = np.zeros(st.n_slots, np.int32)
                    fill_pos = np.zeros(st.n_slots, np.int32)
                    for s in spec_slots:
                        rows = [int(st.tokens[s])] + \
                            [int(t) for t in proposals[s]]
                        g = self.spec.verify(
                            s, rows, int(st.positions[s]),
                            float(st.temps[s]), int(st.top_ks[s]),
                            float(st.top_ps[s]), st.seeds[s], index[s])
                        n_acc = self.spec.accept_len(proposals[s], g)
                        burst[s] = [int(t) for t in g[:n_acc]]
                        if n_acc == self.spec.k + 1:
                            # full acceptance: the draft row pos+k was
                            # never written — backfill d_k there so the
                            # next propose round attends a real row
                            fill_mask[s] = True
                            fill_toks[s] = int(proposals[s][-1])
                            fill_pos[s] = int(st.positions[s]) + self.spec.k
                    self.spec.backfill(
                        fill_toks, fill_pos, fill_mask, st.seeds, index)
                # per-request decode-round spans cover the compiled
                # step(s) and are recorded BEFORE emission: a retiring
                # emit ends its (solo-owned) trace, and a later-arriving
                # span would be dropped as an orphan
                if self.trace_recorder is not None:
                    round_.lap()
                    for s in sorted(burst):
                        if s in spec_slots:
                            round_.file(st.handles[s], "serve.spec_round",
                                        proposed=self.spec.k,
                                        accepted=len(burst[s]) - 1)
                        else:
                            round_.file(st.handles[s])
                # chaos fault point: a raise here loses the synced step's
                # tokens (the whole accepted burst included) before any of
                # them is emitted, and those of the step launched ahead
                # with them — the crash-mid-decode case the fleet retry
                # must survive without double-emission
                try:
                    self._fire_fault("decode_round")
                except BaseException:
                    # a server that outlives the fault (a poisoned round)
                    # computes the lost tokens again: from what it emitted
                    self._forget_flight()
                    raise
                with self.tracer.span("serve.emit"):
                    for s in sorted(burst):
                        handle = st.handles[s]
                        toks = burst[s]
                        if s in spec_slots:
                            handle.spec_proposed += self.spec.k
                            handle.spec_accepted += len(toks) - 1
                            self.metrics.on_spec_round(self.spec.k, len(toks))
                        for token in toks:
                            ok = self._emit(handle, token)
                            st.tokens[s] = token
                            if s in spec_slots:
                                # a plain lane moved on at its launch
                                st.positions[s] += 1
                            if not ok:
                                self._fail(handle, "error")
                                break
                            if self._check_stop(handle, token):
                                self._retire(handle)
                                break
                            # mid-burst deadline: a burst is the new round
                            # granularity, so expiry is enforced between
                            # tokens too — the tail of the burst is dropped
                            # and both the target and draft slots free now
                            if (handle.deadline is not None
                                    and self.clock() >= handle.deadline):
                                self._fail(handle, "deadline")
                                break

        occupied = self.slots.occupied
        self.metrics.on_step(len(self.queue), occupied, lanes_used=stepped)
        self.watchdog.check()
        return bool(self.queue) or occupied > 0

    def unfinished(self) -> List[RequestHandle]:
        """Every accepted-but-unfinished request — queued, prefilling or
        decoding — in FIFO-ish order (queue first). The fleet router uses
        this to re-admit a crashed replica's requests on survivors."""
        live = [h for h in self.slots.live_handles() if not h.finished]
        return list(self.queue) + live

    def run_until_drained(self, max_steps: Optional[int] = None) -> None:
        steps = 0
        while self.step():
            steps += 1
            if max_steps is not None and steps >= max_steps:
                raise RuntimeError(
                    f"server not drained after {max_steps} steps "
                    f"(queued={len(self.queue)}, "
                    f"active={self.engine.pool.used_count})"
                )

    # -- offline convenience -------------------------------------------
    def generate_batch(self, requests: Sequence[Request]) -> List[RequestHandle]:
        """Submit everything, drain, return handles in submission order."""
        handles = [self.submit(r) for r in requests]
        self.run_until_drained()
        return handles

    def compile_counts(self) -> Dict[str, int]:
        """Engine program families, plus the verify/draft families when
        speculation is on (absent otherwise, so the plain server's counts
        are unchanged by this feature existing)."""
        counts = self.engine.compile_counts()
        if self.spec is not None:
            counts.update(self.spec.compile_counts())
        return counts

    def summary(self) -> Dict[str, Any]:
        return self.metrics.summary()
