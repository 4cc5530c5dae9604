"""A serving cell: ``InferenceServer`` built as ``serve.py`` builds it, fed
by the benchmark's own load generator on the wall clock.

One thread does everything, as ``serve.py``'s own loops do: before each
scheduling round it submits what is due (open loop) or what a client whose
last request has completed sends next (closed loop), then calls ``step()``.
Every token is stamped in ``on_token`` with ``time.perf_counter``, the
clock the server stamps ``first_token_time`` with; the server's own
``ServingMetrics`` latencies are not used (they start at ``submit()``, not
at the due time, and their percentiles come from a histogram ladder).

Timeline of a run, in seconds after the origin: traffic starts at 0, the
measured window is [ramp_s, ramp_s + seconds), and the run watches for at
most ``drain_s`` more. A request is attempted if it is due (open) or sent
(closed) inside the window. It fails if it is refused, errors, or, when the
watch ends, is unfinished and has had no token for ``stall_s``: starved or
stuck. A request that is still streaming then is neither failed nor
finished; what is left in the server is cancelled before the check.
(Waiting for every request to finish would cost a whole request's life after
every window: 70 s for 512 tokens at the gaps PR 22 measured.)
A traced run starts the profiler ``trace_after_s`` into the window and stops
it ``trace_s`` later; each holds this thread for seconds, and the loop's clock
stands still meanwhile (``play``), so the traced rounds are the window's own.

A closed-loop mix whose lengths are dealt in a cycle (``traffic``: ``order``,
``cycle_length``) comes in blocks of that length. The window then opens in the
round that sends the first request of a block, and ``serve_tok_s`` is the
median, over the whole blocks sent inside the window, of a block's tokens
over the time from its first request's sending to the next block's. Every
block is the same work in the same order and the loop is always full, so in
the cycle the loop settles into, each block's rate is the server's; the
median of seven of them does not move when the host is held for half a
second once in a window, which the window's own tokens over its length
(``serve_tok_s_window`` in the notes, and the metric itself where a mix has no
blocks) does by the whole length of the stall.
"""

from __future__ import annotations

import dataclasses
import os
import time
from typing import Dict, List, Optional

import numpy as np

from benchmarks.harness import check, spec, trace, traffic


def init_params(gpt_cfg, seed: int):
    """Seeded weights on the device in one jitted call, in the type the
    configuration states for its parameters (``gpt.init`` draws them in
    ``cfg.param_dtype``: float32 for the GPT-2 cells, which the engine casts
    once for its programs; bfloat16, in one copy, for kanana and minicpm).
    Where the architecture has a learned position
    table the program zero-initialises it; here it is drawn like the other
    embeddings, or no check could see a position that is looked up wrongly.
    A rotary model has no table and keeps ``gpt.init``'s parameters as they
    are, under the same split of the key."""
    import jax
    from mingpt_distributed_tpu.models import gpt

    def make(key):
        k_model, k_pos = jax.random.split(key)
        params = gpt.init(k_model, gpt_cfg)
        if "wpe" in params:
            params["wpe"] = 0.02 * jax.random.normal(
                k_pos, params["wpe"].shape, params["wpe"].dtype)
        return params

    return jax.jit(make)(jax.random.key(seed))


def params_digest(params) -> str:
    """A digest of every bit of a parameter tree, of any types: a leaf is
    reduced on the device to the wrapping sum of its words, each times an
    odd weight that grows with its place, and the leaves' sums are hashed on
    the host under their names. Two trees with one digest hold the same
    weights."""
    import hashlib

    import jax
    import jax.numpy as jnp

    def words(a):
        if a.dtype == jnp.bool_ or jax.dtypes.itemsize_bits(a.dtype) < 8:
            a = a.astype(jnp.float32)       # exact for all that is so small
        bits = jax.lax.bitcast_convert_type(
            a, jnp.dtype(f"uint{8 * a.dtype.itemsize}"))
        if bits.dtype.itemsize == 8:        # both halves of a long word
            bits = (bits >> 32).astype(jnp.uint32) * jnp.uint32(0x9E3779B1) \
                + bits.astype(jnp.uint32)
        # the word's place in row-major order, from one iota an axis: no
        # reshape, so nothing of a leaf's size is ever laid out anew
        place, stride = jnp.zeros((), jnp.uint32), 1
        for axis in reversed(range(a.ndim)):
            place = place + jnp.uint32(stride % (1 << 32)) * \
                jax.lax.broadcasted_iota(jnp.uint32, a.shape, axis)
            stride *= a.shape[axis]
        return jnp.sum(bits.astype(jnp.uint32) * (2 * place + 1),
                       dtype=jnp.uint32)

    sums = jax.device_get(jax.jit(
        lambda tree: jax.tree.map(words, tree))(params))
    h = hashlib.sha256()
    for path, value in jax.tree_util.tree_flatten_with_path(sums)[0]:
        h.update(f"{jax.tree_util.keystr(path)}={int(value)};".encode())
    return h.hexdigest()[:16]


@dataclasses.dataclass
class Sent:
    """One request as the generator saw it."""
    req: traffic.Req
    handle: object
    t_ref: float            # due time (open loop) or send time (closed)
    late_s: float           # how long after t_ref submit() was called


class Driver:
    """The load generator and its records. ``play`` can run more than once
    on one server (the rate sweep does), each time from an idle server."""

    def __init__(self, cell: spec.Cell, seed: int, traced: bool):
        from mingpt_distributed_tpu.serving import InferenceServer
        from mingpt_distributed_tpu.telemetry import SpanTracer

        self.cell = cell
        self.gpt_cfg = spec.gpt_config(cell, training=False)
        self.stamps: Dict[str, List[float]] = {}
        self.tracer = SpanTracer(capacity=1 << 16, enabled=traced)
        self.server = InferenceServer(
            init_params(self.gpt_cfg, seed), self.gpt_cfg,
            on_token=self._on_token, warmup=True, tracer=self.tracer,
            **spec.server_options(cell))

    def _on_token(self, handle, _token) -> None:
        self.stamps[handle.request_id].append(time.perf_counter())

    def _submit(self, req: traffic.Req, t_ref: float) -> Sent:
        from mingpt_distributed_tpu.serving import Request

        rid = f"r{req.index}"
        self.stamps[rid] = []
        handle = self.server.submit(Request(
            prompt=req.prompt.tolist(), max_new_tokens=req.max_new_tokens,
            do_sample=False, request_id=rid))
        return Sent(req, handle, t_ref, time.perf_counter() - t_ref)

    def play(self, reqs: List[traffic.Req], seconds: float, *,
             compiles=None, traced: bool = False) -> "Play":
        mix, server = self.cell.mix, self.server
        closed = mix["loop"] == "closed"
        block = traffic.cycle_length(mix)
        ramp_s, drain_s = float(mix["ramp_s"]), float(mix["drain_s"])
        parked = self.gpt_cfg.block_size - 1
        trace_dir = os.path.join(spec.WORK, self.cell.name, "trace")
        self.stamps.clear()
        sent: List[Sent] = []
        nxt = 0                             # next request of the list
        clients: List[Optional[Sent]] = (
            [None] * int(mix.get("clients") or server.engine.n_slots)
            if closed else [])
        play = Play(n_slots=server.engine.n_slots,
                    block_size=self.gpt_cfg.block_size, block_requests=block)
        tracing = None          # the profiler's capture, once it has started
        traced_now = False      # inside the traced window
        t_trace = (ramp_s + float(mix["trace_after_s"]),
                   ramp_s + float(mix["trace_after_s"]) + float(mix["trace_s"])
                   ) if traced else (float("inf"), float("inf"))

        origin = time.perf_counter()
        play.w0, play.w1 = origin + ramp_s, origin + ramp_s + seconds
        while True:
            now = time.perf_counter()
            # opens between rounds and, where the traffic comes in blocks,
            # in the round that sends the first request of one
            if play.open_counters is None and now >= play.w0 and (
                    not block or (nxt % block == 0 and any(
                        c is None or c.handle.finished for c in clients))):
                play.w0, play.w1 = now, now + seconds
                play.open_counters = self._counters()
                if compiles is not None:
                    compiles.open_window()
            if play.close_counters is None and now >= play.w1:
                play.close_counters = self._counters()
                if compiles is not None:
                    play.compiled_in_window = compiles.close_window()
            # The profiler holds this thread for seconds when it starts and
            # when it stops (12.9-18.8 s in all on the chip, PR 36). The loop's
            # clock stands still meanwhile: the traffic's origin and the
            # window's end move by what each took, so no arrivals pile up
            # behind the instrument and the traced rounds see the house the
            # untraced window sees. Requests in flight see one long gap, in
            # a run whose end-to-end numbers nobody reads.
            if tracing is None and now - origin >= t_trace[0]:
                tracing = trace.capture(trace_dir)
                tracing.__enter__()
                traced_now = True
                play.trace_open = self._counters()
                held = time.perf_counter() - now
            elif traced_now and now - origin >= t_trace[1]:
                tracing.__exit__(None, None, None)
                traced_now = False
                play.trace_close = self._counters()
                held = time.perf_counter() - now
            else:
                held = 0.0
            if held:
                origin, now = origin + held, now + held
                if play.close_counters is None:     # the window's end moves,
                    play.w1 += held
                    if play.open_counters is None:  # and an unopened one whole
                        play.w0 += held
                    else:
                        play.profiler_held_s += held
            if play.close_counters is not None and not traced_now:
                attempted = [s for s in sent if play.w0 <= s.t_ref < play.w1]
                if all(s.handle.finished for s in attempted) \
                        or now >= play.w1 + drain_s:
                    break

            with trace.annotate("submit"):
                if closed:
                    for c, last in enumerate(clients):
                        if (last is None or last.handle.finished) \
                                and now < play.w1:
                            if nxt >= len(reqs):
                                raise RuntimeError(
                                    f"the mix's pool of {len(reqs)} requests "
                                    "ran out: make it larger")
                            clients[c] = self._submit(reqs[nxt], now)
                            sent.append(clients[c])
                            nxt += 1
                else:
                    while nxt < len(reqs) and origin + reqs[nxt].due_s <= now:
                        sent.append(self._submit(
                            reqs[nxt], origin + reqs[nxt].due_s))
                        nxt += 1
            with trace.annotate("round"):
                busy = server.step()
            if play.open_counters is not None and play.close_counters is None:
                play.rounds += 1
            if traced_now:
                # rows of the pool that hold a token, after this round
                pos = server.slots.positions
                play.trace_rounds += 1
                play.trace_live_rows += int(pos[pos != parked].sum())
            if not busy:
                due = origin + reqs[nxt].due_s \
                    if not closed and nxt < len(reqs) else now + 1e-3
                time.sleep(max(0.0, min(due - time.perf_counter(), 1e-3)))

        play.drained_at = time.perf_counter()
        play.stall_s = float(mix["stall_s"])
        # the check needs an empty pool: cancel what is still in the server
        for s in sent:
            if not s.handle.finished:
                server.cancel(s.handle.request_id)
        play.sent = sent
        play.stamps = {k: np.asarray(v) for k, v in self.stamps.items()}
        play.watchdog_recompiles = server.watchdog.recompiles
        return play

    def _counters(self) -> Dict[str, Optional[float]]:
        """One reading of the program's own counters: every field of one
        ``ServingMetrics.summary()`` that is a number, or None (a mean or a
        rate the program has not formed yet), under the program's name for
        it, so a counter a later PR adds reaches a reader in a new file with
        no edit here. The counts are differenced over a window; a level, a
        mean or a rate is read at one end (``benchmarks/README.md`` says
        which is which). Beside them three the harness makes: ``lanes``
        (lane-rounds of decode so far) and the queue and the occupied slots
        at this moment."""
        s = self.server.metrics.summary()
        return {
            **{k: v for k, v in s.items() if v is None or (
                isinstance(v, (int, float)) and not isinstance(v, bool))},
            "lanes": (s["slot_utilization"] or 0.0) * s["steps"]
            * self.server.metrics.n_slots,
            "queued_now": len(self.server.queue),
            "slots_now": self.server.slots.occupied,
        }


@dataclasses.dataclass
class Play:
    n_slots: int
    block_size: int
    block_requests: int = 0     # closed loop in a cycle: requests a block
    w0: float = 0.0
    w1: float = 0.0
    drained_at: float = 0.0
    stall_s: float = 0.0
    sent: List[Sent] = dataclasses.field(default_factory=list)
    stamps: Dict[str, np.ndarray] = dataclasses.field(default_factory=dict)
    rounds: int = 0             # scheduling rounds inside the window
    trace_rounds: int = 0       # and inside the traced window, with the rows
    trace_live_rows: int = 0    # of the pool that held a token after each
    open_counters: Optional[Dict] = None
    close_counters: Optional[Dict] = None
    trace_open: Optional[Dict] = None
    trace_close: Optional[Dict] = None
    compiled_in_window: int = 0
    watchdog_recompiles: int = 0
    profiler_held_s: float = 0.0    # a traced run: the clock stood, in [w0, w1)

    def summary(self) -> Dict:
        """The window's numbers, from the benchmark's own stamps."""
        pct = lambda a, q: float(np.percentile(a, q)) if len(a) else float("nan")
        w0, w1 = self.w0, self.w1
        seconds = w1 - w0 - self.profiler_held_s
        attempted = [s for s in self.sent if w0 <= s.t_ref < w1]
        ok = lambda s: s.handle.finish_reason in ("length", "eos")

        def streaming(s) -> bool:
            """Cancelled by the benchmark at the watch's end while tokens
            were still coming."""
            stamps = self.stamps[s.handle.request_id]
            return (s.handle.finish_reason == "cancelled" and len(stamps) > 0
                    and stamps[-1] >= self.drained_at - self.stall_s)

        failed = [s for s in attempted if not ok(s) and not streaming(s)]
        failed_ids = {id(s) for s in failed}
        # a failed request counts as the worst wait seen: until the watch's end
        ttft = np.asarray([
            (self.drained_at if id(s) in failed_ids
             else s.handle.first_token_time) - s.t_ref for s in attempted])
        gaps = np.concatenate([np.zeros(0)] + [
            np.diff(t)[(t[1:] >= w0) & (t[1:] < w1)]
            for t in self.stamps.values() if len(t) > 1])
        done_in = [s for s in self.sent if ok(s)
                   and w0 <= s.handle.last_token_time < w1]
        tokens_done = sum(len(s.handle.prompt_used) + len(s.handle.tokens)
                          for s in done_in)
        late = np.asarray([s.late_s for s in attempted] or [0.0])
        c0, c1 = self.open_counters, self.close_counters
        block_tok_s = self.block_rates()
        return {
            "attempted": len(attempted), "failed": len(failed),
            "streaming_at_end": sum(bool(streaming(s)) for s in attempted),
            "ttft_p50_ms": 1e3 * pct(ttft, 50),
            "ttft_p90_ms": 1e3 * pct(ttft, 90),
            "ttft_p99_ms": 1e3 * pct(ttft, 99),
            "itl_p50_ms": 1e3 * pct(gaps, 50),
            "itl_p90_ms": 1e3 * pct(gaps, 90),
            "itl_gaps": int(len(gaps)),
            "serve_tok_s": float(np.median(block_tok_s))
            if len(block_tok_s) else tokens_done / seconds,
            "serve_tok_s_window": tokens_done / seconds,
            "blocks": int(len(block_tok_s)),
            "block_tok_s": [float(r) for r in block_tok_s],
            "completed_req_s": len(done_in) / seconds,
            "offered_req_s": len(attempted) / seconds,
            "generator_late_ms_p50": 1e3 * pct(late, 50),
            "generator_late_ms_p99": 1e3 * pct(late, 99),
            "queue_open": c0["queued_now"], "queue_close": c1["queued_now"],
            "slots_open": c0["slots_now"],
            "slots_close": c1["slots_now"],
            "rounds": self.rounds, "profiler_held_s": self.profiler_held_s,
            # how well the bucket ladder fits the traffic: padded over real
            "prefill_pad_ratio": (
                (c1["prefill_padded_tokens"] - c0["prefill_padded_tokens"])
                / max(c1["prefill_tokens"] - c0["prefill_tokens"], 1)),
            "round_ms_mean": 1e3 * seconds / max(self.rounds, 1),
        }

    def block_rates(self) -> np.ndarray:
        """Tokens a second of each whole block sent inside the window: the
        tokens of its requests (prompt and generated, all of which have to
        have finished) over the time from the sending of its first request
        to the sending of the next block's first."""
        k = self.block_requests
        if not k:
            return np.zeros(0)
        by_index = {s.req.index: s for s in self.sent}
        firsts = sorted(i for i, s in by_index.items()
                        if i % k == 0 and s.t_ref >= self.w0)
        rates = []
        for i in firsts:
            members = [by_index.get(j) for j in range(i, i + k)]
            if i + k not in by_index or not all(
                    s.handle.finish_reason in ("length", "eos")
                    for s in members):
                continue
            tokens = sum(len(s.handle.prompt_used) + len(s.handle.tokens)
                         for s in members)
            rates.append(tokens / (by_index[i + k].t_ref - by_index[i].t_ref))
        return np.asarray(rates)


def run(cell: spec.Cell, seed: int, seconds: float, traced: bool,
        devices: List, t_process: float, compiles) -> Dict:
    """One run of a serving cell: the end-to-end metrics, the evidence the
    per-layer readers take their numbers from, and the verdict."""
    mix, found = cell.mix, cell.found
    reference = spec.load_reference(cell.config)
    driver = Driver(cell, seed, traced)
    rate = found.get("rate_req_s")
    reqs = traffic.requests(
        mix, driver.gpt_cfg.vocab_size, seed, rate=rate,
        horizon_s=float(mix["ramp_s"]) + seconds + 1.0,
        warm_inflight=int(found.get("warm_inflight", 0)))
    t_origin = time.perf_counter()
    play = driver.play(reqs, seconds, compiles=compiles, traced=traced)
    memory = [d.memory_stats() for d in devices]    # before the check's own
    summary = play.summary()
    weights_digest = params_digest(driver.server.engine.params)

    # -- outside the window: the engine's own programs against the reference
    prompts = check.pick_prompts(reqs, driver.server.engine.buckets,
                                 int(mix["check_prompts"]))
    if driver.server.engine.pool.used_count:
        verdict = {"ok": False, "cases": [],
                   "why": "the server did not drain: no empty pool to check in"}
    else:
        verdict = check.serve_verdict(
            reference, cell.config, driver.server, prompts,
            int(mix["check_decode_steps"]),
            twin_ratio=float(found.get("twin_ratio", 1.0)))
    recompiled = play.compiled_in_window + play.watchdog_recompiles
    verdict["compiled_in_window"] = recompiled
    verdict["ok"] = verdict["ok"] and recompiled == 0

    tr = trace.load(os.path.join(spec.WORK, cell.name, "trace"), devices) \
        if traced else None
    evidence = {
        "kind": "serve", "cell": cell, "chips": len(devices),
        "device_kind": devices[0].device_kind,
        "trace": tr, "play": play,
        "program_spans": driver.tracer.records() if traced else [],
    }
    end_to_end = {k: summary[k] for k in
                  ("serve_tok_s", "ttft_p50_ms", "itl_p50_ms")}
    return {
        "attempted": summary["attempted"], "failed": summary["failed"],
        # the ramp is part of what it takes to reach the window
        "setup_s": play.w0 - t_process,
        "end_to_end": end_to_end,
        "evidence": evidence, "verdict": verdict,
        "memory_stats": memory,
        "notes": {**summary, "traffic_digest": traffic.digest(reqs),
                  "weights_digest": weights_digest,
                  "requests_generated": len(reqs),
                  "warm_s": t_origin - t_process},
    }
