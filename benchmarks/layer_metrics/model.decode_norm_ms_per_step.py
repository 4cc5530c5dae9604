"""Layer ``model``: device milliseconds a run of the decode program spends in a
decode step's norms: each sublayer's input norm, post-sublayer norms, the final
norm (a looped stack's, once a pass); scopes ``norm``, the mean over the traced
window. Source: the program's ``program`` record joined to the trace
(``harness/model_scopes.py``)."""

from benchmarks.harness import model_scopes


def read(ev):
    return model_scopes.decode_ms(ev, ("norm",))
