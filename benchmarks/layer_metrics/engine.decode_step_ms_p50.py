"""Layer ``engine``: median device milliseconds of one run of the decode
program (``serving/engine.py`` ``_decode_impl``, found by its jit name among
the trace's program executions)."""

import statistics

from benchmarks.harness import trace


def read(ev):
    tr = ev.get("trace")
    if tr is None:
        return None
    runs = trace.program_runs(tr, trace.window_of(tr), "decode_impl")
    return 1e3 * statistics.median(runs) if runs else None
