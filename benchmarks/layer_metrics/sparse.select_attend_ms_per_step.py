"""Layer ``attention``: device milliseconds a run of the decode program spends
choosing the blocks of the block-sparse layers and attending them (scopes
``sparse_select`` and ``sparse_attend``), the mean over the traced window.
Source: the program's ``program`` record joined to the trace
(``harness/scopes.py``)."""

from benchmarks.harness import scopes


def read(ev):
    return scopes.decode_ms(ev, ("sparse_select", "sparse_attend"))
