#!/usr/bin/env python3
"""Record a piece of a profiler trace for ``test_trace.py``: the device
planes' events (name, start, duration, string statistics) of the first
``--ms`` milliseconds and every ``bench.`` annotation, as JSON.

    python3 benchmarks/tests/record_trace.py <dir with plugins/profile/*/*.xplane.pb> OUT.json

``data/train_trace_v5e_40ms.json.gz`` was made so from a traced run of
``gpt2-124m.train-1chip`` on the v5e (PR 22); a new shape of trace that the
reduction has to read gets its own recording and its own known answers.
"""

import argparse
import glob
import json
import os
import sys


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("directory")
    ap.add_argument("out")
    ap.add_argument("--ms", type=float, default=50.0)
    args = ap.parse_args(argv)
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    from jax.profiler import ProfileData

    path = sorted(glob.glob(os.path.join(
        args.directory, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    recorded = []
    for plane in ProfileData.from_file(path).planes:
        rec = {"name": plane.name, "lines": [], "stats": [
            [k, v] for k, v in plane.stats if isinstance(v, (int, float, str))]}
        for line in plane.lines:
            events = list(line.events)
            ours = [e for e in events if e.name.startswith("bench.")]
            if not events or not (plane.name.startswith("/device:") or ours):
                continue
            first = min(e.start_ns for e in events)
            rec["lines"].append({"name": line.name, "events": [
                [e.name, e.start_ns, e.duration_ns,
                 [[k, v] for k, v in e.stats if isinstance(v, str)]]
                for e in events
                if e.name.startswith("bench.")
                or e.start_ns - first <= args.ms * 1e6]})
        if rec["lines"] or plane.name == "Task Environment":
            recorded.append(rec)
    with open(args.out, "w") as f:
        json.dump(recorded, f)
    print("wrote", args.out, os.path.getsize(args.out), "bytes")
    return 0


if __name__ == "__main__":
    sys.exit(main())
