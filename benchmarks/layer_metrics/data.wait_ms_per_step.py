"""Layer ``data``: host milliseconds a step spends getting its batch, from
the prefetch queue (``data/prefetch.py``) through ``_put_batch`` to the
device. Source: the benchmark's own clock around those two calls, every step
of the window. Moves ``train_tok_s_chip`` only once it outlasts a step."""

import statistics


def read(ev):
    waits = ev.get("data_wait_s")
    return 1e3 * statistics.fmean(waits) if waits else None
