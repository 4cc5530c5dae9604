"""Device time by named scope: the program's own instruction -> scope table
joined to the trace.

The owner of each compiled program files a ``program`` record in its tracer
(``mingpt_distributed_tpu/telemetry/programs.py``): ``{"kind": "program",
"name": <jit name, "jit__decode_impl">, "family", "variant", "scopes":
{instruction name: scope, "" where none}}``, one for each program (each
prefill bucket has its own). It reaches a reader among
``evidence["program_spans"]``. On the chip an operation's event is named
after its whole instruction (``%fusion.12 = bf16[..] fusion(..), kind=..``),
so its instruction name is the token before the first space, and a program's
runs are found by jit name (``trace.program_names``).

A program from before the record (the parent commit), a cell without a
trace, or a window without a run of the program yields None, every reader
built on it returns None, and the line leaves the metric out.
"""

from __future__ import annotations

import bisect
from typing import Dict, Optional, Sequence

from benchmarks.harness import trace

#: the decode program, by a part of its jit name
DECODE = "decode_impl"


def ms_by_scope(ev, program_part: str) -> Optional[dict]:
    """Device milliseconds a run of the programs whose jit name holds
    ``program_part`` spends under each scope, the mean over the runs inside
    the traced window on the first device: ``{"runs", "by_scope": {scope:
    ms}, "unscoped_ms" (instructions the table knows under no scope),
    "unmatched_ms" (instructions it lacks)}``. Container operations
    (``while``, ``conditional``, ``call``) never count: their time is their
    body's. Where several records match (one a prefill bucket), a run is
    read with the table that knows the most of its device time. A cell's
    readers all read one reduction: it is kept in the evidence, under the
    trace and the records it was made from."""
    tr, records = ev.get("trace"), ev.get("program_spans") or []
    key = (program_part, id(tr), id(records))
    kept = ev.setdefault("ms_by_scope", {})
    if key not in kept:
        kept[key] = _ms_by_scope(tr, records, program_part)
    return kept[key]


def _ms_by_scope(tr, records, program_part: str) -> Optional[dict]:
    tables = [r["scopes"] for r in records
              if r.get("kind") == "program"
              and program_part in r.get("name", "")]
    if tr is None or not tr.devices or not tables:
        return None
    dev = tr.devices[min(tr.devices)]
    lo, hi = trace.window_of(tr)
    runs = [e for e, name in zip(dev.modules, trace.program_names(tr, dev))
            if program_part in name and e.start_ns >= lo and e.end_ns <= hi]
    if not runs:
        return None
    ops = [e for e in dev.ops if not trace.is_container(e)]
    starts = [e.start_ns for e in ops]
    total: Dict[Optional[str], float] = {}
    for run in runs:
        inside = [(e.name.lstrip("%").split(" ")[0], e.dur_ns) for e in
                  ops[bisect.bisect_left(starts, run.start_ns):
                      bisect.bisect_right(starts, run.end_ns)]]
        table = max(tables, key=lambda t: sum(
            ns for name, ns in inside if name in t))
        for name, ns in inside:
            scope = table.get(name)         # None: not of this program
            total[scope] = total.get(scope, 0.0) + ns
    per_run = {k: v / len(runs) / 1e6 for k, v in total.items()}
    return {
        "runs": len(runs),
        "unmatched_ms": per_run.pop(None, 0.0),
        "unscoped_ms": per_run.pop("", 0.0),
        "by_scope": per_run,
    }


def decode_ms(ev, scopes: Sequence[str]) -> Optional[float]:
    """Device milliseconds a decode step spends under ``scopes`` together
    (``""`` is what no scope covers); 0 where the program has none of them."""
    got = ms_by_scope(ev, DECODE)
    if got is None:
        return None
    return sum(got["unscoped_ms"] if s == "" else got["by_scope"].get(s, 0.0)
               for s in scopes)


def join_share(ev, program_part: str = DECODE) -> Optional[float]:
    """Of the device time of the program's runs, the percentage that fell on
    instructions the table knows: near 100, and far less where the table is
    of another program than the one that ran."""
    got = ms_by_scope(ev, program_part)
    if got is None:
        return None
    matched = got["unscoped_ms"] + sum(got["by_scope"].values())
    whole = matched + got["unmatched_ms"]
    return 100.0 * matched / whole if whole > 0 else None
