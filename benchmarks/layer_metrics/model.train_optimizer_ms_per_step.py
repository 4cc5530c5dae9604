"""Layer ``model``: device milliseconds a training step spends in the
optimizer's update (clipping, AdamW, the parameters' write); scopes
``optimizer``, the mean over the traced window. Source: the table the step
filed of itself (``telemetry.programs.filed_records``) joined to the trace
(``harness/model_scopes.py``)."""

from benchmarks.harness import model_scopes


def read(ev):
    return model_scopes.train_ms(ev, ("optimizer",))
