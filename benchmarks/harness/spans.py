"""The program's own spans, as the per-layer span metrics read them.

``evidence["program_spans"]`` is the ring of the server's ``SpanTracer``
(``mingpt_distributed_tpu/telemetry/spans.py``): each span a dict with
``name``, ``ts`` (epoch seconds at its start), ``dur_s``, ``id`` and
``parent``, the ``id`` of the span that was open around it. A scheduling
round that decodes is one ``serve.decode_round`` with four children, one of
each: ``serve.fold_keys``, ``serve.decode_launch``, ``serve.decode_sync``,
``serve.emit``. A duration is the program's own ``perf_counter`` pair; the
epoch stamp, set against the trace's ``profile_start_time``, only decides
which rounds lie inside the traced window.

A program from before the spans had ``id`` and ``parent`` yields no round
here, every reader returns None, and the line leaves the metric out.
"""

from __future__ import annotations

import bisect
import statistics
from typing import Dict, List, Optional, Tuple

from benchmarks.harness import trace

ROUND = "serve.decode_round"

Round = Tuple[dict, List[dict]]     # the round's span and its children


def on_trace_clock(tr: trace.Trace, record: dict) -> trace.Interval:
    """A ring span as an interval in nanoseconds on the profiler's clock."""
    lo = record["ts"] * 1e9 - tr.start_unix_ns
    return (lo, lo + record["dur_s"] * 1e9)


def _spans(ev) -> List[dict]:
    return [r for r in ev.get("program_spans") or []
            if r.get("kind") == "span" and "id" in r]


def rounds(ev) -> List[Round]:
    """The ``serve.decode_round`` spans that lie inside the traced window,
    each with the spans whose ``parent`` it is."""
    tr = ev.get("trace")
    if tr is None or tr.start_unix_ns is None:
        return []
    w0, w1 = trace.window_of(tr)
    spans = _spans(ev)
    children: Dict[int, List[dict]] = {}
    for r in spans:
        if r.get("parent") is not None:
            children.setdefault(r["parent"], []).append(r)
    out = []
    for r in spans:
        if r["name"] == ROUND:
            lo, hi = on_trace_clock(tr, r)
            if lo >= w0 and hi <= w1:
                out.append((r, children.get(r["id"], [])))
    return out


def child_ms_per_round(ev, name: str) -> Optional[float]:
    """Mean over the traced rounds of the milliseconds the round spent in
    its children called ``name`` (a round without one counts as zero)."""
    found = rounds(ev)
    if not found:
        return None
    return 1e3 * statistics.fmean(
        sum(c["dur_s"] for c in kids if c["name"] == name)
        for _, kids in found)


def self_ms_per_round(ev) -> Optional[float]:
    """Mean over the traced rounds of the round's length minus its
    children's: what no child span covers."""
    found = rounds(ev)
    if not found:
        return None
    return 1e3 * statistics.fmean(
        r["dur_s"] - sum(c["dur_s"] for c in kids) for r, kids in found)


def ended_in_window_ms(ev, name: str) -> List[float]:
    """Milliseconds of each span called ``name`` that ended inside the
    traced window."""
    tr = ev.get("trace")
    if tr is None or tr.start_unix_ns is None:
        return []
    w0, w1 = trace.window_of(tr)
    return [1e3 * r["dur_s"] for r in _spans(ev)
            if r["name"] == name and w0 <= on_trace_clock(tr, r)[1] <= w1]


def programs_per_round(ev, skip: Tuple[str, ...]) -> Optional[float]:
    """Mean over the traced rounds of the program executions the first
    device started inside the round, those whose jit name holds one of
    ``skip`` left out."""
    found = rounds(ev)
    tr = ev.get("trace")
    if not found or not tr.devices:
        return None
    dev = tr.devices[min(tr.devices)]
    starts = sorted(
        e.start_ns for e, name in zip(dev.modules, trace.program_names(tr, dev))
        if not any(part in name for part in skip))
    counts = []
    for r, _ in found:
        lo, hi = on_trace_clock(tr, r)
        counts.append(bisect.bisect_right(starts, hi)
                      - bisect.bisect_left(starts, lo))
    return statistics.fmean(counts)


def flash_ms_per_step(ev, name_part: str) -> Optional[float]:
    """Device milliseconds a training step spends in the Mosaic calls whose
    own instruction name holds ``name_part`` (``flash_fwd``, ``flash_bwd``:
    the ``name=`` of each ``pallas_call`` in ``ops/flash_attention.py``),
    averaged over the devices. The name is the instruction's, not its
    text: a backward call names the forward's outputs among its operands."""
    tr = ev.get("trace")
    if tr is None or not ev.get("trace_steps"):
        return None
    seconds = trace.class_s(
        tr, trace.window_of(tr),
        lambda e: trace.is_mosaic(e) and name_part in e.base)
    return 1e3 * seconds / ev["trace_steps"] if seconds else None
