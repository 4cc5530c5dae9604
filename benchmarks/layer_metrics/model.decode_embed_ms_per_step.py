"""Layer ``model``: device milliseconds a run of the decode program spends in a
decode step's embedding lookup (``wte[tokens]`` and, without rope, ``wpe``);
scopes ``embed``, the mean over the traced window. Source: the program's
``program`` record joined to the trace (``harness/model_scopes.py``)."""

from benchmarks.harness import model_scopes


def read(ev):
    return model_scopes.decode_ms(ev, ("embed",))
