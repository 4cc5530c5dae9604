"""Layer ``model``: device milliseconds a training step spends in the chunked
cross-entropy: the head's matmuls and the softmax, forward, recomputed and
backward; scopes ``ce``, the mean over the traced window. Source: the table the
step filed of itself (``telemetry.programs.filed_records``) joined to the trace
(``harness/model_scopes.py``)."""

from benchmarks.harness import model_scopes


def read(ev):
    return model_scopes.train_ms(ev, ("ce",))
