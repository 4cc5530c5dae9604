"""Layer ``engine``: device milliseconds a run of the decode program spends in
operations no named scope covers (trunk matmuls, norms, head, embedding,
copies), the mean over the traced window. With the scoped metrics of the cell
it sums to the mean device-operation time of a decode run. Source: the
program's ``program`` record joined to the trace (``harness/scopes.py``)."""

from benchmarks.harness import scopes


def read(ev):
    return scopes.decode_ms(ev, ("",))
