#!/usr/bin/env python3
"""Searches on the chip that fix what a serving cell's file holds, and
what-ifs on its traffic. One process builds the server once and plays the
cell's open-loop mix several times in turn, from an idle server each time.

    python3 benchmarks/sweep.py --workload <cell> --seconds 15 \\
        --plays '[{"rate_req_s": 2.0}, {"rate_req_s": 2.3, "warm_inflight": 55}]'

A play is a JSON object; each key is optional: ``rate_req_s`` and
``warm_inflight`` (default: the cell's), ``seed`` and ``seconds`` (default:
the command's), ``mix`` (keys that replace the mix's, each whole: another
arrival process, unstratified lengths, ``arrivals`` or ``warm_start`` with or
without ``"order": "cycle"`` and an ``order_seed``). The server's own options are the cell's, or
``--override``'s, for every play.

Prints one JSON line a play: offered and completed requests a second, the
tails, and the queue and occupied slots at both ends of the window. The knee
is the highest rate at which the queue is as short at the close as at the
opening and slots stay free; the cell's file then fixes ``rate_req_s`` at
four fifths of it, and the sweep as run belongs in PERF.md. This is a
search, not a result: the driver never runs it.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--plays", required=True, metavar="JSON",
                    help="a list of objects, one a play")
    ap.add_argument("--seconds", type=float, default=15.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--override", default=None, metavar="JSON",
                    help="replace keys of the cell's own file")
    args = ap.parse_args(argv)

    from benchmarks.harness import device, serve_cell, spec, traffic

    cell = spec.load_cell(args.workload,
                          json.loads(args.override) if args.override else None)
    if cell.kind != "serve" or cell.mix["loop"] != "open":
        raise SystemExit("the sweep is for open-loop serving cells")
    try:
        devices, _ = device.open_chip(cell.chips)
    except device.NoChip as e:
        print(f"benchmarks/sweep.py: {e}", file=sys.stderr)
        return 2
    os.makedirs(spec.WORK, exist_ok=True)
    driver = serve_cell.Driver(cell, args.seed, traced=False)
    for one in json.loads(args.plays):
        mix = dict(cell.mix, **one.get("mix", {}))
        rate = float(one.get("rate_req_s", cell.found["rate_req_s"]))
        inflight = int(one.get("warm_inflight",
                                 cell.found.get("warm_inflight", 0)))
        seed = int(one.get("seed", args.seed))
        seconds = float(one.get("seconds", args.seconds))
        driver.cell = dataclasses.replace(cell, mix=mix)
        reqs = traffic.requests(
            mix, driver.gpt_cfg.vocab_size, seed, rate=rate,
            horizon_s=float(mix["ramp_s"]) + seconds + 1.0,
            warm_inflight=inflight)
        play = driver.play(reqs, seconds)
        keep = ("attempted", "failed", "streaming_at_end", "offered_req_s",
                "completed_req_s", "serve_tok_s",
                "ttft_p50_ms", "ttft_p90_ms", "ttft_p99_ms", "itl_p50_ms",
                "itl_p90_ms", "queue_open", "queue_close", "slots_open",
                "slots_close", "round_ms_mean", "generator_late_ms_p99")
        summary = play.summary()
        print(json.dumps({**one, "rate_req_s": rate,
                          "warm_inflight": inflight, "seed": seed,
                          "seconds": seconds, "n_slots": play.n_slots,
                          **{k: summary[k] for k in keep}}), flush=True)
    print(json.dumps({"device": dict(
        device.describe(devices),
        **device.memory([d.memory_stats() for d in devices]))}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
