"""Layer ``kernel``: the share of device busy time spent in the flash
attention Mosaic calls (``ops/flash_attention.py``), in percent. Source: the
profiler trace, operations that carry a Pallas or Mosaic mark. Serving never
runs the kernel, so the metric belongs to training cells only."""

from benchmarks.harness import trace


def read(ev):
    tr = ev.get("trace")
    if tr is None:
        return None
    window = trace.window_of(tr)
    busy = trace.busy_s(tr, window)
    flash = trace.class_s(tr, window, trace.is_mosaic)
    return 100.0 * flash / busy if busy and flash else None
