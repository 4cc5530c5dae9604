"""Layer ``model``: device milliseconds a training step spends in the attention
sublayers' projections, forward and backward: q/k/v with their bias and the
output projection; scopes ``qkv``, ``attn_out``, the mean over the traced
window. Source: the table the step filed of itself
(``telemetry.programs.filed_records``) joined to the trace
(``harness/model_scopes.py``)."""

from benchmarks.harness import model_scopes


def read(ev):
    return model_scopes.train_ms(ev, ("qkv", "attn_out"))
