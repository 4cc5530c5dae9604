"""Layer ``model``: device milliseconds a run of the decode program spends in
the projections of a decode step: queries, keys and values (their bias,
per-head norm, rotation, the absorbed latent products) and the output
projection with its gate and residual sum; scopes ``qkv``, ``attn_out``, the
mean over the traced window. Source: the program's ``program`` record joined to the
trace (``harness/model_scopes.py``)."""

from benchmarks.harness import model_scopes


def read(ev):
    return model_scopes.decode_ms(ev, ("qkv", "attn_out"))
