"""Layer ``model``: device milliseconds a run of the decode program spends in a
decode step's MLPs with their residual sums (a routed layer's router and
combine; the experts themselves are ``moe.experts_ms_per_step``'s); scopes
``ffn``, the mean over the traced window. Source: the program's ``program``
record joined to the trace (``harness/model_scopes.py``)."""

from benchmarks.harness import model_scopes


def read(ev):
    return model_scopes.decode_ms(ev, ("ffn",))
