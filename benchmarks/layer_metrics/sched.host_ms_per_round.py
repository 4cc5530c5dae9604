"""Layer ``scheduler``: host milliseconds a scheduling round costs, as the
mean over the traced rounds of the round's length minus the time the device
was busy inside it. The round is the benchmark's own annotation around
``InferenceServer.step()``; the device time is the trace's, on the same
clock. What is left is admission, per-slot Python, launches and the wait on
the token transfer."""

import statistics

from benchmarks.harness import trace


def read(ev):
    tr = ev.get("trace")
    if tr is None:
        return None
    rounds = trace.host_s_per_round(tr, trace.window_of(tr))
    return 1e3 * statistics.fmean(rounds) if rounds else None
