"""Layer ``model``: device milliseconds a training step spends in what is left
of the attention sublayers once projections and norms are named: the flash
kernels, the residual sums, dropout; scopes ``attn``, the mean over the
traced window. Source: the table the step filed of itself
(``telemetry.programs.filed_records``) joined to the trace
(``harness/model_scopes.py``)."""

from benchmarks.harness import model_scopes


def read(ev):
    return model_scopes.train_ms(ev, ("attn",))
