"""Serving observability, in the style of training/metrics.py.

Counters (requests submitted/completed, prefills, tokens generated),
per-step gauges (queue depth, slot utilization), and per-request latency
(time-to-first-token, mean inter-token latency). Tokens/sec is computed
over log windows with the same ``RateWindow`` the training MetricsLogger
uses, so the two subsystems report rates with identical semantics.

ISSUE 5: every number here is now a typed instrument registered in a
:class:`~..telemetry.MetricsRegistry` under ``mingpt_serve_*`` — no
private accumulator dicts. TTFT / ITL / admission-stall / prefill-chunk
latencies are fixed-ladder histograms (``LATENCY_BUCKETS_S``), request
outcomes are one labeled counter family, and the padded-bucket fit is a
``bucket``-labeled counter. The pre-existing attribute surface
(``metrics.requests_completed``, ``metrics.bucket_histogram``, ...) is
preserved as read-only views over the instruments, and ``summary()`` /
``log_line()`` emit the same shapes as before.

Output surfaces: a periodic one-line log (``log_every`` scheduler steps,
process-stdout, same pipe-separated shape as the trainer's step line),
an on-demand JSON summary (``summary()`` / ``write_json()``) for offline
batch runs and the serve.py ``--selftest`` gate, and — when the process
registry is injected — the shared Prometheus ``/metrics`` page.
"""

from __future__ import annotations

import json
from typing import Any, Dict, Optional

from mingpt_distributed_tpu.telemetry import (
    LATENCY_BUCKETS_S,
    MetricsRegistry,
    RateWindow,
    log_event,
)


class ServingMetrics:
    def __init__(
        self,
        n_slots: int,
        log_every: int = 0,
        enabled: bool = True,
        registry: Optional[MetricsRegistry] = None,
    ):
        self.n_slots = max(n_slots, 1)
        self.log_every = log_every
        self.enabled = enabled
        self.registry = registry if registry is not None else MetricsRegistry()
        r = self.registry
        # counters
        self._requests = r.counter(
            "mingpt_serve_requests_total",
            help="requests by outcome (submitted counts admissions to the "
                 "queue; rejected = bounded-queue refusals; expired = "
                 "deadline hits; failed = on_token callback raised)",
            labels=("outcome",),
        )
        self._prefills = r.counter(
            "mingpt_serve_prefills_total", help="admissions fully prefilled")
        self._tokens = r.counter(
            "mingpt_serve_tokens_generated_total",
            help="decode tokens emitted")
        # fleet-facing rejection family (ISSUE 6): every refused admission
        # lands here with WHY it was refused — queue_full (bounded queue),
        # shed (global depth watermark), breaker_open (no replica's
        # circuit breaker admits traffic), deadline (cannot be met),
        # draining (graceful shutdown). The legacy outcome="rejected"
        # counter keeps aggregating them all.
        self._rejected = r.counter(
            "mingpt_serving_rejected_total",
            help="refused admissions by reason (queue_full | shed | "
                 "breaker_open | deadline | draining)",
            labels=("reason",),
        )
        for _reason in ("queue_full", "shed", "breaker_open",
                        "deadline", "draining"):
            # pre-touch so every reason is scrape-visible at zero
            self._rejected.labels(reason=_reason).inc(0)
        self._steps = r.counter(
            "mingpt_serve_steps_total", help="scheduler rounds executed")
        self._sampler_sorted = r.counter(
            "mingpt_serve_sampler_sorted_rounds_total",
            help="decode rounds whose program ordered the vocabulary: a "
                 "lane sampled under top-k or top-p "
                 "(engine.sampler_orders); 0 under greedy or "
                 "plain-temperature traffic")
        self._decode_rows_read = r.counter(
            "mingpt_serve_decode_rows_read_total",
            help="rows of the pool's slots the decode steps read, a layer: "
                 "each live lane's slot in blocks as far as its own "
                 "position (engine.decode_rows_read)")
        self._decode_rows_reserved = r.counter(
            "mingpt_serve_decode_rows_reserved_total",
            help="rows the slots of those decode steps reserve "
                 "(n_slots x block_size a step)")
        self._ring_rows_read = r.counter(
            "mingpt_serve_ring_rows_read_total",
            help="rows of the window layers' rings the decode steps read, "
                 "all window layers: each live lane's ring in blocks as far "
                 "as it has written (engine.ring_rows)")
        self._ring_rows_live = r.counter(
            "mingpt_serve_ring_rows_live_total",
            help="of those, the rows inside their lanes' windows")
        self._decode_launches = r.counter(
            "mingpt_serve_decode_launches_total",
            help="decode steps handed to the device")
        self._decode_rounds_ahead = r.counter(
            "mingpt_serve_decode_rounds_ahead_total",
            help="decode steps launched before the sync of the step "
                 "before them, whose tokens they take on the device")
        self._lane_steps_discarded = r.counter(
            "mingpt_serve_decode_lane_steps_discarded_total",
            help="lane-steps computed for a request that had stopped by "
                 "their sync (an EOS, a cancel, a deadline or a raising "
                 "callback behind a step launched ahead)")
        # prefill accounting (ISSUE 3): real prompt tokens forwarded, the
        # padded bucket fit (how well the ladder matches the traffic), and
        # wall time inside prefill calls — the decode-stall budget
        # admissions consume
        self._prefill_chunks = r.counter(
            "mingpt_serve_prefill_chunks_total",
            help="padded prefill calls issued")
        self._prefill_tokens = r.counter(
            "mingpt_serve_prefill_tokens_total",
            help="real (unpadded) prompt tokens prefilled")
        self._prefill_padded = r.counter(
            "mingpt_serve_prefill_padded_tokens_total",
            help="bucket lengths actually forwarded (incl. padding and "
                 "shifted-final-chunk overlap)")
        self._prefill_seconds = r.counter(
            "mingpt_serve_prefill_seconds_total",
            help="wall seconds spent inside prefill calls")
        self._bucket_counter = r.counter(
            "mingpt_serve_prefill_bucket_total",
            help="prefill chunks by padded bucket length",
            labels=("bucket",),
        )
        # shared-prefix store
        self._prefix_lookups = r.counter(
            "mingpt_serve_prefix_lookups_total",
            help="prefix-cache lookups at admission")
        self._prefix_hits = r.counter(
            "mingpt_serve_prefix_hits_total", help="prefix-cache hits")
        self._prefix_rows = r.counter(
            "mingpt_serve_prefix_rows_reused_total",
            help="KV rows restored from the prefix cache instead of "
                 "recomputed")
        # latency histograms (fixed ladder — comparable across scrapes)
        self._ttft = r.histogram(
            "mingpt_serve_ttft_seconds",
            help="time to first token per admission",
            buckets=LATENCY_BUCKETS_S,
        )
        self._itl = r.histogram(
            "mingpt_serve_itl_seconds",
            help="mean inter-token latency per completed request",
            buckets=LATENCY_BUCKETS_S,
        )
        self._stall = r.histogram(
            "mingpt_serve_admission_stall_seconds",
            help="slot claim to first token — decode stall an admission "
                 "costs its co-tenants",
            buckets=LATENCY_BUCKETS_S,
        )
        self._chunk_hist = r.histogram(
            "mingpt_serve_prefill_chunk_seconds",
            help="wall time of one padded prefill call",
            buckets=LATENCY_BUCKETS_S,
        )
        # speculative decoding (serving/speculative.py): proposal volume,
        # acceptance, and the emitted-tokens-per-verify distribution — the
        # number that says what speculation actually bought per compiled
        # target forward (1 = draft useless, k+1 = full acceptance)
        self._spec_rounds = r.counter(
            "mingpt_serve_spec_rounds_total",
            help="verify rounds executed (one batched target forward each)")
        self._spec_proposed = r.counter(
            "mingpt_serve_spec_proposed_total",
            help="draft tokens proposed across verify rounds")
        self._spec_accepted = r.counter(
            "mingpt_serve_spec_accepted_total",
            help="draft tokens accepted (matched the target's greedy "
                 "choice)")
        self._spec_tokens_per_verify = r.histogram(
            "mingpt_serve_spec_tokens_per_verify",
            help="tokens emitted per verify round (accepted prefix + the "
                 "bonus token)",
            buckets=(1, 2, 3, 4, 6, 8, 12, 16),
        )
        self._spec_accept_rate = r.gauge(
            "mingpt_serve_spec_accept_rate",
            help="cumulative accepted/proposed draft tokens")
        self._spec_prime = r.counter(
            "mingpt_serve_spec_prime_total",
            help="draft primes by path: full = paid a draft prefill, "
                 "adopted = resumed from migrated draft rows (ISSUE 17)",
            labels=("mode",))
        for mode in ("full", "adopted"):
            self._spec_prime.labels(mode=mode).inc(0)
        # gauges sampled at step boundaries
        self._queue_depth = r.gauge(
            "mingpt_serve_queue_depth", help="queued requests after the "
            "last scheduler round")
        self._slots_active = r.gauge(
            "mingpt_serve_slots_active", help="occupied slots after the "
            "last scheduler round")
        self._util = r.gauge(
            "mingpt_serve_slot_utilization",
            help="mean fraction of decode lanes doing useful work")
        self._tps = r.gauge(
            "mingpt_serve_tokens_per_sec",
            help="decode tokens/sec over the last log window")
        self._prefill_tps = r.gauge(
            "mingpt_serve_prefill_tokens_per_sec",
            help="real prompt tokens/sec over the last prefill window")
        self._hit_rate = r.gauge(
            "mingpt_serve_prefix_hit_rate",
            help="prefix-cache hits / lookups so far")
        # set once, by the server that owns the engine (engine_built)
        self._program_weight_bytes = r.gauge(
            "mingpt_serve_program_weight_bytes",
            help="bytes of the parameter tree the compiled programs read")
        self._program_weights_cast = r.gauge(
            "mingpt_serve_program_weights_cast",
            help="leaves of that tree cast to the compute dtype at "
                 "construction (0: the programs read the tree handed in)")
        self._kv_bytes_per_row = r.gauge(
            "mingpt_serve_kv_bytes_per_row",
            help="bytes one cached token costs in the pool, all layers")
        self._state_bytes_per_slot = r.gauge(
            "mingpt_serve_state_bytes_per_slot",
            help="bytes of recurrent state a slot holds beside its rows")
        self._ring_bytes_per_slot = r.gauge(
            "mingpt_serve_ring_bytes_per_slot",
            help="bytes of the window layers' rings a slot holds beside "
                 "its rows")
        self._ring_rows_per_slot = r.gauge(
            "mingpt_serve_ring_rows_per_slot",
            help="rows one ring of a slot holds (the window); 0: no ring")
        self._ring_planes = r.gauge(
            "mingpt_serve_ring_planes",
            help="window layers, each with a ring of keys and one of "
                 "values; 0: no ring")
        self._kv_row_width = r.gauge(
            "mingpt_serve_kv_row_width",
            help="last axis of the pool's k leaf: one head's size, or a "
                 "position's heads side by side")
        self._kv_row_tiles = r.gauge(
            "mingpt_serve_kv_row_tiles",
            help="lane tiles a lane's row write touches in the k leaf, "
                 "all planes")
        # the device-side counters, read only by summary()
        self._moe_rows_source: Optional[Callable[[], Any]] = None
        self._sparse_rows_source: Optional[Callable[[], Any]] = None
        self._loop_passes_source: Optional[Callable[[], Any]] = None
        self._head_boundaries_source: Optional[Callable[[], int]] = None
        self._kernel_walk_layers_source: Optional[Callable[[], int]] = None
        self._util_sum = 0.0
        self._prefill_rate = RateWindow()
        self._prefill_tokens_per_sec: Optional[float] = None
        self._rate = RateWindow()
        self._tokens_per_sec: Optional[float] = None

    # -- back-compat attribute views over the instruments ---------------
    @property
    def requests_submitted(self) -> int:
        return int(self._requests.labels(outcome="submitted").value)

    @property
    def requests_completed(self) -> int:
        return int(self._requests.labels(outcome="completed").value)

    @property
    def requests_rejected(self) -> int:
        return int(self._requests.labels(outcome="rejected").value)

    @property
    def requests_expired(self) -> int:
        return int(self._requests.labels(outcome="expired").value)

    @property
    def requests_failed(self) -> int:
        return int(self._requests.labels(outcome="failed").value)

    @property
    def prefills(self) -> int:
        return int(self._prefills.value)

    @property
    def tokens_generated(self) -> int:
        return int(self._tokens.value)

    @property
    def steps(self) -> int:
        return int(self._steps.value)

    @property
    def sampler_sorted_rounds(self) -> int:
        return int(self._sampler_sorted.value)

    @property
    def decode_rows_read(self) -> int:
        return int(self._decode_rows_read.value)

    @property
    def decode_rows_reserved(self) -> int:
        return int(self._decode_rows_reserved.value)

    @property
    def prefill_chunks(self) -> int:
        return int(self._prefill_chunks.value)

    @property
    def prefill_tokens(self) -> int:
        return int(self._prefill_tokens.value)

    @property
    def prefill_padded_tokens(self) -> int:
        return int(self._prefill_padded.value)

    @property
    def bucket_histogram(self) -> Dict[int, int]:
        return {
            int(labels["bucket"]): int(child.value)
            for labels, child in self._bucket_counter.children()
        }

    @property
    def prefix_lookups(self) -> int:
        return int(self._prefix_lookups.value)

    @property
    def prefix_hits(self) -> int:
        return int(self._prefix_hits.value)

    @property
    def prefix_rows_reused(self) -> int:
        return int(self._prefix_rows.value)

    @property
    def queue_depth(self) -> int:
        return int(self._queue_depth.value)

    @property
    def slots_active(self) -> int:
        return int(self._slots_active.value)

    # -- event hooks (called by the scheduler) -------------------------
    def on_submit(self) -> None:
        self._requests.labels(outcome="submitted").inc()

    def on_reject(self, reason: str = "queue_full") -> None:
        self._requests.labels(outcome="rejected").inc()
        self._rejected.labels(reason=reason).inc()

    def on_expire(self) -> None:
        self._requests.labels(outcome="expired").inc()

    def on_error(self) -> None:
        self._requests.labels(outcome="failed").inc()

    def on_prefill(self, ttft_s: float, stall_s: float = 0.0) -> None:
        """One admission finished prefilling. ``stall_s`` is the wall time
        from slot claim to first token — what this admission cost its
        co-tenants in decode stall."""
        self._prefills.inc()
        self._ttft.observe(ttft_s)
        self._stall.observe(stall_s)

    def on_prefill_chunk(self, n_tokens: int, bucket: int, seconds: float) -> None:
        """One prefill call: ``n_tokens`` real prompt tokens forwarded as
        a ``bucket``-length padded chunk."""
        self._prefill_chunks.inc()
        self._prefill_tokens.inc(n_tokens)
        self._prefill_padded.inc(bucket)
        self._bucket_counter.labels(bucket=bucket).inc()
        self._prefill_seconds.inc(seconds)
        self._chunk_hist.observe(seconds)
        rate = self._prefill_rate.observe(self.prefill_tokens)
        if rate is not None:
            self._prefill_tokens_per_sec = rate
            self._prefill_tps.set(rate)

    def on_prefix_lookup(self, hit: bool, rows: int, enabled: bool = True) -> None:
        if not enabled:
            return
        self._prefix_lookups.inc()
        if hit:
            self._prefix_hits.inc()
            self._prefix_rows.inc(rows)
        self._hit_rate.set(self.prefix_hits / self.prefix_lookups)

    def on_tokens(self, n: int) -> None:
        self._tokens.inc(n)

    def on_spec_prime(self, mode: str) -> None:
        """One draft prime: ``mode`` is ``"full"`` (paid a prefill) or
        ``"adopted"`` (resumed from migrated draft rows)."""
        self._spec_prime.labels(mode=mode).inc()

    def on_spec_round(self, proposed: int, emitted: int) -> None:
        """One verify round on one slot: ``proposed`` = k draft tokens
        offered, ``emitted`` = accepted prefix + bonus token (>= 1), so
        accepted draft tokens = emitted - 1."""
        self._spec_rounds.inc()
        self._spec_proposed.inc(proposed)
        self._spec_accepted.inc(emitted - 1)
        self._spec_tokens_per_verify.observe(emitted)
        if self.spec_proposed:
            self._spec_accept_rate.set(
                self.spec_accepted / self.spec_proposed)

    def on_complete(self, n_generated: int, gen_span_s: float) -> None:
        """gen_span_s: first-token to last-token wall time."""
        self._requests.labels(outcome="completed").inc()
        if n_generated > 1:
            self._itl.observe(gen_span_s / (n_generated - 1))

    def on_sampler_sorted(self) -> None:
        """A decode round whose program ordered the vocabulary (the
        scheduler calls it, beside its ``decode_step``)."""
        self._sampler_sorted.inc()

    def on_decode_rows(self, read: int, reserved: int) -> None:
        """A decode step's rows, a layer, over all slots: those it read
        and those the slots reserve (the scheduler calls it, beside its
        ``decode_step``, with the program's own rule)."""
        self._decode_rows_read.inc(read)
        self._decode_rows_reserved.inc(reserved)

    def on_ring_rows(self, read: int, live: int) -> None:
        """A decode step's rows of the window layers' rings, all window
        layers: those it read and, of them, those inside their lanes'
        windows (the scheduler calls it beside ``on_decode_rows``, with
        the program's own rule: ``engine.ring_rows``)."""
        self._ring_rows_read.inc(read)
        self._ring_rows_live.inc(live)

    def on_decode_launch(self, ahead: bool) -> None:
        """A decode step handed to the device; ``ahead``: before the sync
        of the step before it."""
        self._decode_launches.inc()
        if ahead:
            self._decode_rounds_ahead.inc()

    def on_lane_steps_discarded(self, n: int) -> None:
        """``n`` lane-steps in flight for a request that has just stopped."""
        self._lane_steps_discarded.inc(n)

    def on_step(
        self, queue_depth: int, slots_active: int, lanes_used: Optional[int] = None
    ) -> None:
        """queue_depth/slots_active: end-of-round gauges (occupancy after
        retirement). lanes_used: lane-steps the round launched (a lane-step
        is counted once, at its launch) — what utilization of the shared
        decode batch means."""
        self._steps.inc()
        self._queue_depth.set(queue_depth)
        self._slots_active.set(slots_active)
        used = slots_active if lanes_used is None else lanes_used
        self._util_sum += used / self.n_slots
        self._util.set(self._util_sum / self.steps)
        rate = self._rate.observe(self.tokens_generated)
        if rate is not None:
            self._tokens_per_sec = rate
            self._tps.set(rate)
        if self.enabled and self.log_every and self.steps % self.log_every == 0:
            log_event(self.log_line())

    # -- read-out ------------------------------------------------------
    @property
    def ttft_mean_s(self) -> Optional[float]:
        return self._ttft.sum / self._ttft.count if self._ttft.count else None

    @property
    def itl_mean_s(self) -> Optional[float]:
        return self._itl.sum / self._itl.count if self._itl.count else None

    @property
    def ttft_p99_s(self) -> Optional[float]:
        """Ladder-resolution p99 (upper bound) — the health-gate signal."""
        return self._ttft.quantile(0.99)

    @property
    def itl_p99_s(self) -> Optional[float]:
        """Ladder-resolution p99 (upper bound) — the health-gate signal."""
        return self._itl.quantile(0.99)

    @property
    def rejected_by_reason(self) -> Dict[str, int]:
        return {
            labels["reason"]: int(child.value)
            for labels, child in self._rejected.children()
        }

    @property
    def spec_rounds(self) -> int:
        return int(self._spec_rounds.value)

    @property
    def spec_proposed(self) -> int:
        return int(self._spec_proposed.value)

    @property
    def spec_accepted(self) -> int:
        return int(self._spec_accepted.value)

    @property
    def spec_accept_rate(self) -> Optional[float]:
        if not self.spec_proposed:
            return None
        return self.spec_accepted / self.spec_proposed

    @property
    def spec_tokens_per_verify_mean(self) -> Optional[float]:
        h = self._spec_tokens_per_verify
        return h.sum / h.count if h.count else None

    @property
    def admission_stall_mean_s(self) -> Optional[float]:
        return self._stall.sum / self.prefills if self.prefills else None

    @property
    def prefix_hit_rate(self) -> Optional[float]:
        if not self.prefix_lookups:
            return None
        return self.prefix_hits / self.prefix_lookups

    @property
    def prefill_pad_overhead(self) -> Optional[float]:
        """Padded-to-real token ratio — 1.0 means the ladder fits the
        traffic perfectly; the redundant-overlap rows of shifted final
        chunks count as padding here too."""
        if not self.prefill_tokens:
            return None
        return self.prefill_padded_tokens / self.prefill_tokens

    @property
    def slot_utilization(self) -> Optional[float]:
        return self._util_sum / self.steps if self.steps else None

    def log_line(self) -> str:
        parts = [
            f"serve step {self.steps}",
            f"active {self.slots_active}/{self.n_slots}",
            f"queued {self.queue_depth}",
            f"done {self.requests_completed}/{self.requests_submitted}",
            f"tokens {self.tokens_generated}",
        ]
        dropped = (self.requests_rejected + self.requests_expired
                   + self.requests_failed)
        if dropped:
            parts.append(
                f"dropped {dropped} (rej {self.requests_rejected} / exp "
                f"{self.requests_expired} / err {self.requests_failed})"
            )
        if self._tokens_per_sec is not None:
            parts.append(f"tokens/sec {self._tokens_per_sec:.4g}")
        if self._prefill_tokens_per_sec is not None:
            parts.append(f"prefill_tok/s {self._prefill_tokens_per_sec:.4g}")
        if self.ttft_mean_s is not None:
            parts.append(f"ttft_ms {self.ttft_mean_s * 1e3:.4g}")
        if self.itl_mean_s is not None:
            parts.append(f"itl_ms {self.itl_mean_s * 1e3:.4g}")
        if self.prefix_lookups:
            parts.append(
                f"prefix_hit {self.prefix_hits}/{self.prefix_lookups}")
        if self.spec_rounds:
            parts.append(
                f"spec_accept {self.spec_accepted}/{self.spec_proposed}")
        return " | ".join(parts)

    def engine_built(self, program_weight_bytes: int,
                     program_weights_cast: int, kv_bytes_per_row: int = 0,
                     moe_rows_source: Optional[Callable[[], Any]] = None,
                     state_bytes_per_slot: int = 0,
                     sparse_rows_source: Optional[Callable[[], Any]] = None,
                     loop_passes_source: Optional[Callable[[], Any]] = None,
                     kv_row_width: int = 0, kv_row_tiles: int = 0,
                     head_boundaries_source: Optional[Callable[[], int]] = None,
                     ring_bytes_per_slot: int = 0, ring_rows_per_slot: int = 0,
                     kernel_walk_layers_source: Optional[
                         Callable[[], int]] = None,
                     ring_planes: int = 0) -> None:
        """What the engine's programs read and what a cached token and a
        slot's state cost, known once it is built; ``kv_row_width`` and
        ``kv_row_tiles`` say which way the pool keeps a row
        (``SlotKVPool.row_width``, ``row_tiles``). ``moe_rows_source``
        fetches a routed model's (expert layers, E + 4) counter of routed
        rows from the device (``DecodeEngine.moe_rows``) and
        ``sparse_rows_source`` a hybrid stack's (2,) counter of the rows its
        sparse layers' decode steps attended (``DecodeEngine.sparse_rows``),
        ``loop_passes_source`` a looped stack's (2 + n_passes,) counter of
        its passes (``DecodeEngine.loop_passes``);
        ``head_boundaries_source`` reads, off the decode program's trace,
        the projections whose product stands behind a boundary
        (``DecodeEngine.head_boundaries``) and ``kernel_walk_layers_source``
        the layers whose walk over the pool is the Pallas kernel's
        (``DecodeEngine.kernel_walk_layers``: how that kernel engages,
        beside ``decode_rows_read`` and ``ring_rows_read``, which count by
        its rule where it does); only ``summary()`` calls them."""
        self._program_weight_bytes.set(program_weight_bytes)
        self._program_weights_cast.set(program_weights_cast)
        self._kv_bytes_per_row.set(kv_bytes_per_row)
        self._state_bytes_per_slot.set(state_bytes_per_slot)
        self._ring_bytes_per_slot.set(ring_bytes_per_slot)
        self._ring_rows_per_slot.set(ring_rows_per_slot)
        self._ring_planes.set(ring_planes)
        self._kv_row_width.set(kv_row_width)
        self._kv_row_tiles.set(kv_row_tiles)
        self._moe_rows_source = moe_rows_source
        self._sparse_rows_source = sparse_rows_source
        self._loop_passes_source = loop_passes_source
        self._head_boundaries_source = head_boundaries_source
        self._kernel_walk_layers_source = kernel_walk_layers_source

    def _loop_summary(self) -> Dict[str, Any]:
        """A looped stack's passes since the server was built
        (``generate.LOOP_PASSES``), over the tokens of requests in every
        prefill and decode program: token-passes run, tokens counted (their
        quotient is the pass count at the one exit threshold that is built)
        and, a pass, the mean exit mass ``p_t`` a token (a list, which sums
        to 1 where the stack has a gate). None where the layers run once
        and no gate is read."""
        got = self._loop_passes_source() if self._loop_passes_source else None
        if got is None:
            return dict.fromkeys((
                "loop_token_passes", "loop_tokens", "loop_exit_mass"))
        return {"loop_token_passes": float(got[0]),
                "loop_tokens": float(got[1]),
                "loop_exit_mass": [float(m) / max(float(got[1]), 1.0)
                                   for m in got[2:]]}

    def _ring_summary(self) -> Dict[str, Any]:
        """The window layers' rings of a stack of ``layer_types``: what a
        slot holds of them (its bytes, a ring's rows, the window layers),
        and the rows the decode steps read of them since the server was
        built, all window layers, beside those that were inside their
        lanes' windows. None where no layer keeps a ring."""
        rows = int(self._ring_rows_per_slot.value)
        if not rows:
            return dict.fromkeys((
                "ring_bytes_per_slot", "ring_rows_per_slot", "ring_planes",
                "ring_rows_read", "ring_rows_live"))
        return {"ring_bytes_per_slot": int(self._ring_bytes_per_slot.value),
                "ring_rows_per_slot": rows,
                "ring_planes": int(self._ring_planes.value),
                "ring_rows_read": int(self._ring_rows_read.value),
                "ring_rows_live": int(self._ring_rows_live.value)}

    def _sparse_summary(self) -> Dict[str, Any]:
        """The sparse layers' decode steps since the server was built: the
        rows they attended and the rows at or before their queries, summed
        over sparse layers and live lanes (a layer's KV heads averaged).
        None where no layer selects."""
        rows = self._sparse_rows_source() if self._sparse_rows_source else None
        if rows is None:
            return {"sparse_rows_attended": None, "sparse_rows_live": None}
        return {"sparse_rows_attended": float(rows[0]),
                "sparse_rows_live": float(rows[1])}

    def _moe_summary(self) -> Dict[str, Any]:
        """The routed-rows counter since the server was built
        (``generate.MOE_ROWS``, all expert layers): rows the experts
        computed, routes asked for and not computed (0: the route drops
        nothing, and this is where it would show), the busiest expert's
        rows over the mean, the worst layer's, and the blocks the experts'
        loop took through an expert beside the blocks its layouts had (only
        the blocks that hold a request's route are run), and the experts
        that held at least one row, a call and layer, summed: the fewest
        reads of an expert's weights those blocks can cost. None where the
        model routes nothing this way."""
        rows = self._moe_rows_source() if self._moe_rows_source else None
        if rows is None:
            return dict.fromkeys((
                "moe_routed_rows", "moe_dropped_rows",
                "moe_load_max_over_mean", "moe_blocks_run",
                "moe_blocks_laid", "moe_expert_runs"))
        computed, (asked, ran, laid, held) = rows[:, :-4], rows[:, -4:].sum(0)
        mean = computed.mean(axis=1)
        return {
            "moe_routed_rows": int(computed.sum()),
            "moe_dropped_rows": int(asked - computed.sum()),
            "moe_load_max_over_mean": float(
                (computed.max(axis=1) / mean)[mean > 0].max())
            if (mean > 0).any() else None,
            "moe_blocks_run": int(ran),
            "moe_blocks_laid": int(laid),
            "moe_expert_runs": int(held),
        }

    def summary(self) -> Dict[str, Any]:
        return {
            "program_weight_bytes": int(self._program_weight_bytes.value),
            "program_weights_cast": int(self._program_weights_cast.value),
            "kv_bytes_per_row": int(self._kv_bytes_per_row.value),
            "state_bytes_per_slot": int(self._state_bytes_per_slot.value),
            **self._ring_summary(),
            "kv_row_width": int(self._kv_row_width.value),
            "kv_row_tiles": int(self._kv_row_tiles.value),
            "decode_head_boundaries": self._head_boundaries_source()
            if self._head_boundaries_source else None,
            "decode_kernel_walk_layers": self._kernel_walk_layers_source()
            if self._kernel_walk_layers_source else None,
            **self._moe_summary(),
            **self._sparse_summary(),
            **self._loop_summary(),
            "requests_submitted": self.requests_submitted,
            "requests_completed": self.requests_completed,
            "requests_rejected": self.requests_rejected,
            "requests_expired": self.requests_expired,
            "requests_failed": self.requests_failed,
            "prefills": self.prefills,
            "prefill_chunks": self.prefill_chunks,
            "prefill_tokens": self.prefill_tokens,
            "prefill_padded_tokens": self.prefill_padded_tokens,
            "prefill_pad_overhead": self.prefill_pad_overhead,
            "prefill_time_s": self._prefill_seconds.value,
            "prefill_tokens_per_sec": self._prefill_tokens_per_sec,
            "bucket_histogram": {
                str(k): v for k, v in sorted(self.bucket_histogram.items())
            },
            "admission_stall_mean_s": self.admission_stall_mean_s,
            "prefix_lookups": self.prefix_lookups,
            "prefix_hits": self.prefix_hits,
            "prefix_hit_rate": self.prefix_hit_rate,
            "prefix_rows_reused": self.prefix_rows_reused,
            "tokens_generated": self.tokens_generated,
            "steps": self.steps,
            "sampler_sorted_rounds": self.sampler_sorted_rounds,
            "decode_rows_read": self.decode_rows_read,
            "decode_rows_reserved": self.decode_rows_reserved,
            "decode_launches": int(self._decode_launches.value),
            "decode_rounds_ahead": int(self._decode_rounds_ahead.value),
            "decode_lane_steps_discarded": int(
                self._lane_steps_discarded.value),
            "queue_depth": self.queue_depth,
            "slots_active": self.slots_active,
            "slot_utilization": self.slot_utilization,
            "tokens_per_sec": self._tokens_per_sec,
            "ttft_mean_s": self.ttft_mean_s,
            "itl_mean_s": self.itl_mean_s,
            "spec_rounds": self.spec_rounds,
            "spec_proposed": self.spec_proposed,
            "spec_accepted": self.spec_accepted,
            "spec_accept_rate": self.spec_accept_rate,
            "spec_tokens_per_verify_mean": self.spec_tokens_per_verify_mean,
        }

    def write_json(self, path: str) -> None:
        with open(path, "w") as f:
            json.dump(self.summary(), f, indent=2)
            f.write("\n")
