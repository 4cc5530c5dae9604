"""Layer ``kv_pool``: device milliseconds a run of the decode program spends
writing the lanes' new rows into the donated pool (scope ``kv_layout``), the
mean over the traced window. Source: the program's ``program`` record joined
to the trace (``harness/scopes.py``)."""

from benchmarks.harness import scopes


def read(ev):
    return scopes.decode_ms(ev, ("kv_layout",))
