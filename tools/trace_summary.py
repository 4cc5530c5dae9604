#!/usr/bin/env python
"""Summarise a jax.profiler trace: top ops by total duration, per lane.

Input: a profile directory written by ``jax.profiler.trace`` (e.g. from
``trainer_config.profile_dir``) — it contains
``plugins/profile/<run>/<host>.trace.json.gz`` in Chrome trace-event
format, which this tool aggregates without needing TensorBoard: for each
process/thread lane, complete events ("ph": "X") are summed by name.

Usage: python tools/trace_summary.py DIR [--top N]
       python tools/trace_summary.py SPANS.jsonl [--top N]
       python tools/trace_summary.py TRACE.jsonl [--slo [SPEC]]
       python tools/trace_summary.py CONTROL.jsonl [--top N]
       python tools/trace_summary.py --compare A.json B.json

A ``.jsonl`` file argument is treated as a telemetry span stream instead
(``mingpt-telemetry/1`` records with ``kind: "span"``, as written by
``TrainerConfig.spans_jsonl`` or ``SpanTracer.attach_jsonl``): spans are
converted to the same trace-event shape — one lane per span-name prefix
(``train``, ``serve``) — and summarised by the same aggregation.

A ``.jsonl`` whose records carry the ``mingpt-trace/1`` schema (written
by ``serve.py --trace-jsonl``, ISSUE 10) is a *request-scoped* trace
stream: the file is strict-validated and rendered as one timeline per
request — queue wait, prefix lookup, prefill chunks, decode rounds and
the emitted-token window in submit-relative time, with retry attempts
flagged. ``--slo [SPEC]`` additionally grades the request summaries
against named objectives (exact quantiles, telemetry.slo) and prints
the attainment report.

A ``.jsonl`` whose records carry the ``mingpt-control/1`` schema
(written by ``serve.py --control-log`` or collected from a trafficlab
autoscaled cell, ISSUE 20) is an SLO-autoscaler decision log: rendered
as the per-actuator action table (ups/downs per lever), the actuation
timeline in virtual time, and the grouped reason mix — what the
controller saw (values elided) and how often, holds included.

``--compare A.json B.json`` (ISSUE 12) takes two ``mingpt-slo/1``
reports (written by ``serve.py --slo-json``) and prints a per-objective
delta table — observed values, deltas (negative = B better) and
pass/fail transitions — so two serving runs (e.g. before/after a
change, or two admission policies) diff mechanically.

The "what are the top-3 time sinks" question (VERDICT r2 next #2) is
answered by the busiest device lane's table; host-side Python/dispatch
lanes appear separately so device idle time is visible as the gap between
the lane's busy total and the trace span.
"""

from __future__ import annotations

import argparse
import glob
import gzip
import json
import os
import re
import sys
from collections import defaultdict


TRACE_SCHEMA = "mingpt-trace/1"
CONTROL_SCHEMA = "mingpt-control/1"


def _telemetry():
    """Import the repo's telemetry package (the strict mingpt-trace/1
    loader + SLO engine live there, not here). Running this file
    directly puts tools/ — not the repo root — on sys.path, so fall
    back to the tool's parent directory."""
    try:
        from mingpt_distributed_tpu import telemetry
    except ImportError:
        sys.path.insert(
            0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
        from mingpt_distributed_tpu import telemetry
    return telemetry


def sniff_jsonl_schema(path: str):
    """The ``schema`` field of the first JSON record (None if the first
    line isn't JSON) — how a request-trace stream is told apart from a
    plain span stream without reading the whole file."""
    with open(path) as f:
        for line in f:
            if not line.strip():
                continue
            try:
                rec = json.loads(line)
            except ValueError:
                return None
            return rec.get("schema") if isinstance(rec, dict) else None
    return None


def summarize_requests(traces: dict) -> list[str]:
    """One timeline per request from a validated mingpt-trace/1 stream
    (``load_trace_jsonl`` output). Offsets are relative to the trace's
    submit timestamp; spans recorded by a skew-injected replica clock
    may land outside the fleet-clock window — that is the skew being
    *visible*, not a rendering bug."""
    out = [f"request traces: {len(traces)}"]
    order = sorted(traces.items(), key=lambda kv: kv[1]["request"]["ts"])
    for tid, t in order:
        r = t["request"]
        ttft = f"{r['ttft_s']:.4f}s" if r.get("ttft_s") is not None else "-"
        itl = (f"{r['itl_mean_s']:.4f}s"
               if r.get("itl_mean_s") is not None else "-")
        out.append(
            f"\n== {tid}: outcome={r['outcome']} tokens={r['n_tokens']} "
            f"attempts={r['attempts']} ttft={ttft} itl_mean={itl} "
            f"total={r['total_s']:.4f}s"
            + (" RETRIED" if r.get("retried") else ""))
        t0 = float(r["ts"])
        rows = []
        for s in t["spans"]:
            extra = "".join(
                f" {k}={s[k]}" for k in
                ("attempt", "replica", "pos", "tokens", "hit_rows", "lanes")
                if k in s)
            rows.append((
                float(s["ts"]),
                f"  +{float(s['ts']) - t0:9.4f}s {float(s['dur_s']):9.4f}s  "
                f"{s['name']}{extra}"))
        emits = [e for e in t["events"] if e.get("name") == "emit"]
        for e in t["events"]:
            if e.get("name") == "emit":
                continue
            flag = "RETRY " if e.get("name") == "retry" else ""
            extra = "".join(
                f" {k}={e[k]}" for k in
                ("reason", "attempt", "queue_depth", "shed_reason")
                if k in e)
            rows.append((
                float(e["ts"]),
                f"  +{float(e['ts']) - t0:9.4f}s          -  "
                f"{flag}{e['name']}{extra}"))
        if emits:
            first = min(float(e["ts"]) for e in emits)
            last = max(float(e["ts"]) for e in emits)
            rows.append((
                first,
                f"  +{first - t0:9.4f}s {last - first:9.4f}s  "
                f"emit x{len(emits)} (first..last token)"))
        rows.sort(key=lambda kv: kv[0])
        out.extend(line for _, line in rows)
    return out


def load_control_jsonl(path: str) -> list[dict]:
    """Strict-load a ``mingpt-control/1`` decision log (one JSON row
    per evaluated controller tick)."""
    rows = []
    with open(path) as f:
        for i, line in enumerate(f):
            if not line.strip():
                continue
            try:
                rec = json.loads(line)
            except ValueError as e:
                raise ValueError(f"line {i + 1}: not JSON ({e})")
            if rec.get("schema") != CONTROL_SCHEMA:
                raise ValueError(
                    f"line {i + 1}: schema {rec.get('schema')!r}, "
                    f"want {CONTROL_SCHEMA!r}")
            missing = [k for k in ("tick", "now", "action", "reason")
                       if k not in rec]
            if missing:
                raise ValueError(f"line {i + 1}: missing keys {missing}")
            rows.append(rec)
    if not rows:
        raise ValueError(f"no {CONTROL_SCHEMA} rows in {path}")
    return rows


def _reason_key(reason: str) -> str:
    """Group controller reasons by shape: the observed values vary per
    tick, the comparison they triggered doesn't — elide the numbers so
    the mix table counts regimes, not floats."""
    return re.sub(r"-?\d+(?:\.\d+)?(?:[eE][+-]?\d+)?", "*",
                  reason.split(";", 1)[0].strip())


def summarize_control(rows: list[dict], top: int = 12) -> list[str]:
    """Render one autoscaler decision log: action table per actuator,
    the actuation timeline in virtual time, and the grouped reason
    mix (holds included — what the controller saw and declined on)."""
    t0, t1 = float(rows[0]["now"]), float(rows[-1]["now"])
    metric = rows[0].get("metric", "?")
    acted = [r for r in rows
             if r["action"].get("direction") != "hold"]
    out = [
        f"control log ({CONTROL_SCHEMA}): {len(rows)} ticks over "
        f"{t1 - t0:.3f}s, metric={metric}",
        f"actions: {len(acted)} (holds: {len(rows) - len(acted)})",
    ]
    counts: dict = defaultdict(lambda: defaultdict(int))
    for r in acted:
        counts[r["action"]["actuator"]][r["action"]["direction"]] += 1
    for actuator in sorted(counts):
        for direction in sorted(counts[actuator]):
            out.append(f"  {actuator:<16} {direction:<5} "
                       f"{counts[actuator][direction]:>4}")
    if acted:
        out.append("\ntimeline:")
        for r in acted:
            out.append(
                f"  tick {r['tick']:>4} +{float(r['now']) - t0:8.3f}s  "
                f"{r['action']['actuator']:<14} "
                f"{r['action']['direction']:<4} {r['reason']}")
    out.append("\nreason mix:")
    mix: dict = defaultdict(int)
    for r in rows:
        mix[_reason_key(r["reason"])] += 1
    ranked = sorted(mix.items(), key=lambda kv: kv[1], reverse=True)
    for key, n in ranked[:top]:
        out.append(f"  {n:>5}x  {key}")
    if len(ranked) > top:
        out.append(f"  (+{len(ranked) - top} more reason shapes)")
    return out


def load_trace(profile_dir: str) -> dict:
    """Merge every *.trace.json.gz found (multi-host runs write one per
    host; profiling a dir twice leaves several runs) — summarising only
    one would silently hide the other hosts' lanes."""
    pats = [
        os.path.join(profile_dir, "plugins", "profile", "*", "*.trace.json.gz"),
        os.path.join(profile_dir, "*.trace.json.gz"),
    ]
    paths = [p for pat in pats for p in sorted(glob.glob(pat))]
    if not paths:
        raise FileNotFoundError(
            f"no *.trace.json.gz under {profile_dir} (expected "
            "plugins/profile/<run>/<host>.trace.json.gz)"
        )
    merged: dict = {"traceEvents": []}
    for i, path in enumerate(paths):
        print(f"loading [{i + 1}/{len(paths)}] {path}", file=sys.stderr)
        with gzip.open(path, "rt") as f:
            t = json.load(f)
        # namespace pids per file so different hosts' lanes can't collide
        prefix = os.path.basename(path).split(".")[0]
        for e in t.get("traceEvents", []):
            if len(paths) > 1 and "pid" in e:
                e["pid"] = f"{prefix}:{e['pid']}"
            merged["traceEvents"].append(e)
    return merged


def load_span_jsonl(path: str) -> dict:
    """Telemetry span JSONL -> Chrome trace-event dict for summarize().

    Each ``kind: "span"`` record becomes a complete ("X") event; the lane
    (tid) is the span name's subsystem prefix (``train.step`` -> lane
    ``train``), so trainer and serving phases summarise as separate lanes
    the way device/host lanes do for profiler traces. Non-span records
    (point events, logs) carry no duration and are skipped."""
    events = []
    with open(path) as f:
        for line in f:
            if not line.strip():
                continue
            rec = json.loads(line)
            if rec.get("kind") != "span":
                continue
            events.append({
                "ph": "X",
                "name": rec.get("name", "?"),
                "ts": float(rec.get("ts", 0.0)) * 1e6,     # s -> us
                "dur": float(rec.get("dur_s", 0.0)) * 1e6,
                "pid": "spans",
                "tid": str(rec.get("name", "?")).split(".", 1)[0],
            })
    if not events:
        raise FileNotFoundError(
            f"no span records (kind == \"span\") in {path}"
        )
    return {"traceEvents": events}


def summarize(trace: dict, top: int = 12) -> list[str]:
    events = trace.get("traceEvents", [])
    # pid/tid -> human-readable lane names from metadata events
    pids: dict = {}
    tids: dict = {}
    for e in events:
        if e.get("ph") == "M" and e.get("name") == "process_name":
            pids[e["pid"]] = e["args"].get("name", str(e["pid"]))
        if e.get("ph") == "M" and e.get("name") == "thread_name":
            tids[(e["pid"], e.get("tid"))] = e["args"].get("name", "")

    lanes: dict = defaultdict(lambda: defaultdict(float))
    lane_spans: dict = defaultdict(list)
    t_min, t_max = float("inf"), 0.0
    for e in events:
        if e.get("ph") != "X":
            continue
        dur = float(e.get("dur", 0.0))  # microseconds
        ts = float(e.get("ts", 0.0))
        t_min = min(t_min, ts)
        t_max = max(t_max, ts + dur)
        key = (
            pids.get(e.get("pid"), str(e.get("pid"))),
            tids.get((e.get("pid"), e.get("tid")), str(e.get("tid"))),
        )
        lanes[key][e.get("name", "?")] += dur  # inclusive, like trace viewers
        lane_spans[key].append((ts, ts + dur))

    # busy = UNION of the lane's intervals (events nest — e.g. python call
    # stacks — so a plain sum over-counts; union gives honest utilisation)
    lane_busy: dict = {}
    for key, spans in lane_spans.items():
        spans.sort()
        total, cur_s, cur_e = 0.0, None, None
        for s, e in spans:
            if cur_e is None or s > cur_e:
                if cur_e is not None:
                    total += cur_e - cur_s
                cur_s, cur_e = s, e
            else:
                cur_e = max(cur_e, e)
        if cur_e is not None:
            total += cur_e - cur_s
        lane_busy[key] = total

    span_ms = (t_max - t_min) / 1e3 if t_max > t_min else 0.0
    out = [f"trace span: {span_ms:.2f} ms, lanes: {len(lanes)}"]
    # busiest lanes first — the device lanes are what matter for MFU
    for key in sorted(lane_busy, key=lane_busy.get, reverse=True):
        pname, tname = key
        busy_ms = lane_busy[key] / 1e3
        out.append(
            f"\n== lane {pname} / {tname}: busy {busy_ms:.2f} ms"
            + (f" ({100 * busy_ms / span_ms:.0f}% of span)" if span_ms else "")
        )
        ops = sorted(lanes[key].items(), key=lambda kv: kv[1], reverse=True)
        for name, dur in ops[:top]:
            pct = 100 * dur / lane_busy[key] if lane_busy[key] else 0
            out.append(f"  {dur / 1e3:9.2f} ms  {pct:5.1f}%  {name[:90]}")
        if len(ops) > top:
            rest = sum(d for _, d in ops[top:])
            out.append(f"  {rest / 1e3:9.2f} ms         (+{len(ops) - top} more)")
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("profile_dir", nargs="?", default=None,
                    help="profiler output dir, a telemetry span .jsonl, "
                         "a mingpt-trace/1 request-trace .jsonl, or a "
                         "mingpt-control/1 autoscaler decision .jsonl "
                         "(omitted with --compare)")
    ap.add_argument("--top", type=int, default=12)
    ap.add_argument("--compare", nargs=2, default=None,
                    metavar=("A.json", "B.json"),
                    help="diff two mingpt-slo/1 reports (serve.py "
                         "--slo-json output): per-objective observed "
                         "values, deltas and pass/fail transitions")
    ap.add_argument("--slo", nargs="?", const="default", default=None,
                    metavar="SPEC",
                    help="request-trace input only: grade the request "
                         "summaries against 'metric<=threshold' "
                         "objectives (default: the standard set) and "
                         "print the attainment report")
    args = ap.parse_args(argv)
    if args.compare is not None:
        tel = _telemetry()
        reports = []
        for path in args.compare:
            try:
                with open(path) as f:
                    reports.append(json.load(f))
            except (OSError, ValueError) as e:
                print(f"cannot read SLO report {path}: {e}",
                      file=sys.stderr)
                return 1
        try:
            diff = tel.diff_slo_reports(reports[0], reports[1])
        except ValueError as e:
            print(str(e), file=sys.stderr)
            return 1
        print(tel.render_slo_diff(diff))
        return 0
    if args.profile_dir is None:
        ap.error("profile_dir is required unless --compare is given")
    span_input = (os.path.isfile(args.profile_dir)
                  and args.profile_dir.endswith(".jsonl"))
    if span_input and sniff_jsonl_schema(args.profile_dir) == TRACE_SCHEMA:
        tel = _telemetry()
        try:
            traces = tel.load_trace_jsonl(args.profile_dir)
        except ValueError as e:
            print(f"invalid {TRACE_SCHEMA} stream: {e}", file=sys.stderr)
            return 1
        print("\n".join(summarize_requests(traces)))
        if args.slo is not None:
            report = tel.evaluate_slos(
                [t["request"] for t in traces.values()],
                tel.parse_slo_spec(args.slo))
            print(tel.render_slo_report(report))
        return 0
    if span_input and sniff_jsonl_schema(args.profile_dir) == CONTROL_SCHEMA:
        # fourth input kind (ISSUE 20): an SLO-autoscaler decision log
        try:
            rows = load_control_jsonl(args.profile_dir)
        except (OSError, ValueError) as e:
            print(f"invalid {CONTROL_SCHEMA} stream: {e}", file=sys.stderr)
            return 1
        print("\n".join(summarize_control(rows, args.top)))
        return 0
    if args.slo is not None:
        print("--slo needs a mingpt-trace/1 request-trace .jsonl input",
              file=sys.stderr)
        return 1
    try:
        trace = (load_span_jsonl(args.profile_dir) if span_input
                 else load_trace(args.profile_dir))
    except FileNotFoundError as e:
        print(str(e), file=sys.stderr)
        return 1
    print("\n".join(summarize(trace, args.top)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
