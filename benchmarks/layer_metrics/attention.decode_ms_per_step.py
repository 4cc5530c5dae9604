"""Layer ``attention``: device milliseconds a run of the decode program spends
in the mixers' steps over the cache, the mean over the traced window: the
operations under the scopes ``cached_attn`` (a per-head cache), ``latent_attn``
(a latent cache), ``lightning_step`` (a recurrent state), ``sparse_select`` and
``sparse_attend`` (a block-sparse layer). Source: the program's ``program``
record joined to the trace (``harness/scopes.py``)."""

from benchmarks.harness import scopes


def read(ev):
    return scopes.decode_ms(ev, ("cached_attn", "latent_attn", "lightning_step",
                                 "sparse_select", "sparse_attend"))
