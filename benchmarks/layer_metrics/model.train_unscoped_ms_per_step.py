"""Layer ``model``: device milliseconds a training step spends in the embedding
(lookup, dropout, its gradient's scatter) and what no scope covers (the
gradients' norm, copies, collectives outside a scope); scopes ``embed``, no
scope, the mean over the traced window. Source: the table the step filed of
itself (``telemetry.programs.filed_records``) joined to the trace
(``harness/model_scopes.py``)."""

from benchmarks.harness import model_scopes


def read(ev):
    return model_scopes.train_ms(ev, ("embed", ""))
