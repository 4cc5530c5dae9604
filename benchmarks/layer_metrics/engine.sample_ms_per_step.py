"""Layer ``engine``: device milliseconds a run of the decode program spends in
the sampler (scope ``sample``), the mean over the traced window: the guard
that the vocabulary's sorts stay out of a greedy round. Source: the program's
``program`` record joined to the trace (``harness/scopes.py``)."""

from benchmarks.harness import scopes


def read(ev):
    return scopes.decode_ms(ev, ("sample",))
