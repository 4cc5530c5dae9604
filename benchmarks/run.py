#!/usr/bin/env python3
"""Run one cell of the benchmark once, in this process, on this machine.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell's name is looked up in ``BENCHMARK.json``; its configuration, its
traffic mix, what was found once on the chip and its per-layer readers are
files under ``benchmarks/`` found by name (``benchmarks/README.md``).

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (with ``--trace 0`` the cell's
end-to-end metrics, with ``--trace 1`` its per-layer metrics), ``device``,
traced, ``breakdown``, and last ``compared``: each number the verdict
compared beside its limit (``harness/check.compared``), which are also the
last lines of standard error. ``device`` holds, beside ``memory_peak_bytes``, the
runtime's memory statistics it is made of (``harness/device.memory``). Lines
before it are notes for a reader; nothing parses them. Without a TPU of a kind the table of peaks knows, or with fewer
chips than the cell asks for, the exit code is 2 and no result is printed:
there is no CPU fallback. A number from any other machine is not a result.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()     # set-up is counted from here

import argparse     # noqa: E402
import json         # noqa: E402
import os           # noqa: E402
import sys          # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def note(label: str, doc) -> None:
    print(f"[bench] {label}: {json.dumps(doc, default=str)}", flush=True)


def per_layer_metrics(cell, evidence) -> dict:
    """Each per-layer metric of the cell from its own reader. A reader that
    finds nothing to read returns None and the metric is left out."""
    from benchmarks.harness import spec

    out = {}
    for entry in cell.per_layer:
        value = spec.load_reader(entry["name"]).read(evidence)
        if value is not None:
            out[entry["name"]] = {"value": float(value), "unit": entry["unit"]}
    return out


def traced_device_fields(evidence) -> tuple:
    """``busy_s`` and ``window_s`` for ``device`` and the ``breakdown``."""
    from benchmarks.harness import trace

    tr = evidence["trace"]
    window = trace.window_of(tr)
    spans = trace.annotation_spans(tr)
    program = trace.program_spans(tr, evidence.get("program_spans", []))
    note("trace", {
        "devices": sorted(tr.devices), "window_s": (window[1] - window[0]) / 1e9,
        "device_ops": sum(len(d.ops) for d in tr.devices.values()),
        "annotations": len(tr.annotations),
        "program_spans_used": len(program)})
    fields = {"busy_s": trace.busy_s(tr, window),
              "window_s": (window[1] - window[0]) / 1e9}
    breakdown = {
        "device_ops": trace.top_device_ops(tr, window),
        "idle_gaps": trace.idle_gaps_by_span(tr, window, spans + program),
    }
    return fields, breakdown


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    args = ap.parse_args(argv)

    from benchmarks.harness import check
    from benchmarks.harness import compiles as compiles_lib
    from benchmarks.harness import device, serve_cell, spec, train_cell

    cell = spec.load_cell(args.workload)
    try:
        devices, cache_dir = device.open_chip(cell.chips)
    except device.NoChip as e:
        print(f"benchmarks/run.py: {e}", file=sys.stderr)
        return 2
    os.makedirs(spec.WORK, exist_ok=True)
    counter = compiles_lib.CompileCounter()
    note("start", {"cell": cell.name, "seed": args.seed,
                   "seconds": args.seconds, "trace": args.trace,
                   "cache_dir": cache_dir, "found": cell.found,
                   "device": device.describe(devices)})

    runner = train_cell if cell.kind == "train" else serve_cell
    result = runner.run(cell, args.seed, args.seconds, bool(args.trace),
                        devices, T_PROCESS, counter)
    evidence = result["evidence"]
    note("notes", result["notes"])
    note("verdict", result["verdict"])
    note("compiles", {"lowered": counter.lowered,
                      "cache_hits": counter.cache_hits,
                      "cache_misses": counter.cache_misses})

    dev = dict(device.describe(devices),
               **device.memory(result["memory_stats"]))
    line = {"correct": bool(result["verdict"]["ok"]),
            "attempted": int(result["attempted"]),
            "failed": int(result["failed"])}
    if args.trace:
        fields, breakdown = traced_device_fields(evidence)
        dev.update(fields)
        line["metrics"] = per_layer_metrics(cell, evidence)
        line["breakdown"] = breakdown
    else:
        values = dict(result["end_to_end"], setup_s=result["setup_s"])
        line["metrics"] = {
            m["name"]: {"value": float(values[m["name"]]), "unit": m["unit"]}
            for m in cell.end_to_end}
    line["device"] = dev
    # each number the verdict compared beside its limit: last in the line
    # and last on standard error
    line["compared"] = check.compared(result["verdict"])
    note("end_to_end", dict(result["end_to_end"], setup_s=result["setup_s"],
                            total_s=time.perf_counter() - T_PROCESS))
    print(json.dumps(line), flush=True)
    for name, (value, limit) in line["compared"].items():
        print(f"compared {name}: {value!r} limit {limit!r}", file=sys.stderr)
    sys.stderr.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
