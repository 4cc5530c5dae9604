"""Layer ``engine``: milliseconds a decode round has the host blocked in the
``device_get`` of the step's tokens, as the mean over the traced rounds of
the program's ``serve.decode_sync`` span (``serving/engine.py``
``decode_step``). The device runs the decode program meanwhile, so this is
device time the host waits out, not host work."""

from benchmarks.harness import spans


def read(ev):
    return spans.child_ms_per_round(ev, "serve.decode_sync")
