"""Layer ``scheduler``: host milliseconds a decode round spends deriving the
lanes' sampling keys (``SlotTable.fold_key``, one ``fold_in`` dispatch a
lane), as the mean over the traced rounds of the program's
``serve.fold_keys`` span (``serving/scheduler.py``)."""

from benchmarks.harness import spans


def read(ev):
    return spans.child_ms_per_round(ev, "serve.fold_keys")
