"""Layer ``scheduler``: host milliseconds a decode round spends on the
lanes' sampling keys, as the mean over the traced rounds of the program's
``serve.fold_keys`` span (``serving/scheduler.py``). Since PR 24 the keys
are folded inside the decode program and the span holds only the NumPy
build of the token-index vector (``SlotTable.token_indices``); before it,
one eager ``fold_in`` dispatch a lane."""

from benchmarks.harness import spans


def read(ev):
    return spans.child_ms_per_round(ev, "serve.fold_keys")
