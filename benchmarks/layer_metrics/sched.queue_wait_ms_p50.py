"""Layer ``scheduler``: median milliseconds from ``submit()`` to admission,
over the requests admitted inside the traced window: the program's
``serve.queue_wait`` span (``serving/scheduler.py`` ``_admit``)."""

import statistics

from benchmarks.harness import spans


def read(ev):
    waits = spans.ended_in_window_ms(ev, "serve.queue_wait")
    return statistics.median(waits) if waits else None
