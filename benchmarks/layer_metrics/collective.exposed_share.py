"""Layer ``collective``: the share of the traced window, in percent, in which
a collective operation ran on a device while no compute operation did:
parameter gathers and gradient reduce-scatters that the step waits for.
Source: the profiler trace, averaged over the chips. Nothing to read on one
chip."""

from benchmarks.harness import trace


def read(ev):
    tr = ev.get("trace")
    if tr is None or ev["chips"] < 2:
        return None
    window = trace.window_of(tr)
    return 100.0 * trace.exposed_collective_s(tr, window) \
        / ((window[1] - window[0]) / 1e9)
