"""The span primitive: nested wall-time spans, also profiler annotations.

``SpanTracer.span(name, **attrs)`` times a phase of the host's work. The
trainer wraps its step / snapshot / eval phases; the serving scheduler and
engine wrap admission, prefill chunks and the decode round with its four
children (the names, parents and attributes are listed in
``docs/architecture.md``, "Telemetry"). A closed span goes two ways:

* into a bounded ring (a deque with ``maxlen``: a stuck exporter can never
  grow host memory) and, where a sink is attached, the versioned JSONL
  stream. ``benchmarks/`` reads the ring (``evidence["program_spans"]``) for
  its per-layer span metrics and to label the device's idle gaps;
* while it is open, a ``jax.profiler.TraceAnnotation`` of the same name is
  held open, so any profile taken meanwhile (the benchmark's capture, the
  trainer's ``profile_dir``) shows the span in the host plane on the
  profiler's own clock, beside the device lines. Outside a capture an
  annotation is one flag test.

Record layout (also the JSONL ``kind: "span"`` payload):
``{"name", "ts" (epoch s, start), "dur_s" (perf_counter), "depth", "id",
"parent", <attrs...>}``. ``id`` counts up per tracer; ``parent`` is the
``id`` of the span open around this one on the same thread, or None: self
time is a span's duration minus its children's. Point events
(``tracer.event``) carry ``{"name", "ts", "depth", <attrs...>}``. A pinned
record (``tracer.pin``) is its owner's: the ``program`` record of a compiled
program is laid out in ``telemetry/programs.py``.

Overhead discipline: a disabled tracer returns one shared no-op context
manager (no allocation per call, no annotation), and an enabled span costs
three clock reads, one dict, one deque append and one annotation: no
locks on the hot path beyond the deque's internal one. Multi-process runs
gate the *default* tracer to process 0 (``telemetry.get_tracer()``), the
same single-writer convention as MetricsLogger. Nothing here initialises a
backend: an annotation only talks to the profiler.
"""

from __future__ import annotations

import itertools
import sys
import threading
import time
from collections import deque
from typing import Any, Callable, Dict, List, Optional

from mingpt_distributed_tpu.telemetry.export import JsonlEventSink

__all__ = ["SpanTracer", "log_event", "process_index"]


def process_index() -> int:
    """jax.process_index() when a backend is up, else 0 — telemetry must
    never be the thing that initialises (or crashes on) a backend."""
    try:
        import jax

        return jax.process_index()
    except Exception:
        return 0


_annotation = None  # jax.profiler.TraceAnnotation, or False where jax is absent


def _open_annotation(name: str):
    """A host span in the profiler's own trace, open until its ``__exit__``
    is called; None where there is no JAX to ask."""
    global _annotation
    if _annotation is None:
        try:
            from jax.profiler import TraceAnnotation

            _annotation = TraceAnnotation
        except Exception:
            _annotation = False
    if not _annotation:
        return None
    ann = _annotation(name)
    ann.__enter__()
    return ann


class _NoopSpan:
    __slots__ = ()

    def __enter__(self) -> "_NoopSpan":
        return self

    def __exit__(self, *exc) -> bool:
        return False

    def set(self, **attrs: Any) -> None:
        pass


_NOOP = _NoopSpan()


class _Span:
    __slots__ = ("_tracer", "name", "attrs", "id", "parent", "_stack",
                 "_ann", "_t0", "_ts")

    def __init__(self, tracer: "SpanTracer", name: str, attrs: Dict[str, Any]):
        self._tracer = tracer
        self.name = name
        self.attrs = attrs

    def set(self, **attrs: Any) -> None:
        """Attributes learned inside the span (a chunk's padded length)."""
        self.attrs.update(attrs)

    def __enter__(self) -> "_Span":
        self._stack = stack = self._tracer._open_spans()
        self.parent = stack[-1] if stack else None
        self.id = next(self._tracer._ids)
        stack.append(self.id)
        self._ann = _open_annotation(self.name)
        self._ts = time.time()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> bool:
        dur = time.perf_counter() - self._t0
        if self._ann is not None:
            self._ann.__exit__(*exc)
        self._stack.pop()
        rec = {"name": self.name, "ts": self._ts, "dur_s": dur,
               "depth": len(self._stack), "id": self.id,
               "parent": self.parent}
        if self.attrs:
            rec.update(self.attrs)
        self._tracer._record("span", rec)
        return False


class SpanTracer:
    """Nested spans + point events in a bounded ring, optional JSONL."""

    def __init__(
        self,
        capacity: int = 4096,
        sink: Optional[JsonlEventSink] = None,
        enabled: bool = True,
    ):
        if capacity < 1:
            raise ValueError(f"capacity must be >= 1, got {capacity}")
        self.enabled = enabled
        self.capacity = capacity
        self.sink = sink
        self.emitted = 0  # total ever recorded; ring keeps the newest
        self._ring: deque = deque(maxlen=capacity)
        self._pinned: List[Dict[str, Any]] = []
        self._pins: List[tuple] = []    # (kind, make) not yet made
        self._ids = itertools.count(1)
        self._tls = threading.local()

    def _open_spans(self) -> List[int]:
        """Ids of the spans open on this thread, outermost first."""
        try:
            return self._tls.stack
        except AttributeError:
            self._tls.stack = []
            return self._tls.stack

    def span(self, name: str, **attrs: Any):
        """Context manager timing a nested phase. Near-free when the
        tracer is disabled (one shared no-op object)."""
        if not self.enabled:
            return _NOOP
        return _Span(self, name, attrs)

    def add_span(self, name: str, dur_s: float, **attrs: Any) -> None:
        """A span that ends now and lasted ``dur_s`` by the caller's own
        clock: a wait that began in another call (a request's time in the
        queue), which no ``with`` block can wrap. It is a child of whatever
        span is open here, and has no profiler annotation."""
        if not self.enabled:
            return
        stack = self._open_spans()
        rec = {"name": name, "ts": time.time() - dur_s, "dur_s": dur_s,
               "depth": len(stack), "id": next(self._ids),
               "parent": stack[-1] if stack else None}
        rec.update(attrs)
        self._record("span", rec)

    def event(self, name: str, **attrs: Any) -> None:
        """A point-in-time event (no duration) — watchdog firings, log
        lines, phase markers."""
        if not self.enabled:
            return
        rec = {"name": name, "ts": time.time(),
               "depth": len(self._open_spans())}
        rec.update(attrs)
        self._record("event", rec)

    def pin(self, kind: str,
            make: Callable[[], List[Dict[str, Any]]]) -> None:
        """Records the ring's eviction never drops, made only when somebody
        reads: ``make`` returns them, and is called once, by the first
        ``records()`` or, where a JSONL sink is or gets attached, then. It
        is how the owner of a compiled program files its ``program`` record
        (``telemetry/programs.py``) at no cost to a run nobody profiles. A
        disabled tracer ignores the call."""
        if not self.enabled:
            return
        self._pins.append((kind, make))
        if self.sink is not None:
            self._make_pinned()

    def _make_pinned(self) -> None:
        while self._pins:
            kind, make = self._pins.pop(0)
            for rec in make():
                rec["kind"] = kind
                self._pinned.append(rec)
                self._to_sink(rec)

    def _to_sink(self, rec: Dict[str, Any]) -> None:
        if self.sink is not None:
            payload = dict(rec)
            self.sink.write(payload.pop("kind"), payload)

    def _record(self, kind: str, rec: Dict[str, Any]) -> None:
        rec["kind"] = kind
        self._ring.append(rec)
        self.emitted += 1
        self._to_sink(rec)

    @property
    def dropped(self) -> int:
        return self.emitted - len(self._ring)

    def records(self) -> List[Dict[str, Any]]:
        """The pinned records, then a snapshot of the ring, oldest first."""
        self._make_pinned()
        return self._pinned + list(self._ring)

    def attach_jsonl(self, path: str) -> None:
        """Start streaming spans/events to a JSONL file (idempotent for
        the same tracer: replaces any previous sink). The pinned records
        lead the file."""
        if self.sink is not None:
            self.sink.close()
        self.sink = JsonlEventSink(path)
        for rec in self._pinned:
            self._to_sink(rec)
        self._make_pinned()

    def close(self) -> None:
        if self.sink is not None:
            self.sink.close()
            self.sink = None


def log_event(
    message: str,
    *,
    tracer: Optional[SpanTracer] = None,
    file=None,
    **attrs: Any,
) -> None:
    """Replacement for bare ``print()`` in multi-process code paths: the
    line is prefixed with the process index (so interleaved pod output
    stays attributable) and mirrored into the tracer's event ring/JSONL.
    Callers keep their own process-0 gating where they want single-writer
    output; this helper makes whatever IS printed attributable.
    """
    print(f"[p{process_index()}] {message}", file=file or sys.stdout,
          flush=True)
    t = tracer
    if t is None:
        from mingpt_distributed_tpu import telemetry

        t = telemetry.get_tracer()
    t.event("log", message=message, **attrs)
