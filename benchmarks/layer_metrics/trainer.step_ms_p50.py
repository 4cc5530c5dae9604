"""Layer ``trainer``: median milliseconds between step boundaries on the host
clock. Two steps are in flight, so a boundary is the moment the host saw a
step finished; in steady state the difference is the device's step time."""

import statistics


def read(ev):
    steps = ev.get("step_s")
    return 1e3 * statistics.median(steps) if steps else None
