"""Layer ``scheduler``: host milliseconds a decode round spends emitting the
tokens, checking stop conditions and retiring requests, as the mean over the
traced rounds of the program's ``serve.emit`` span
(``serving/scheduler.py``). The benchmark's ``on_token`` stamp is inside."""

from benchmarks.harness import spans


def read(ev):
    return spans.child_ms_per_round(ev, "serve.emit")
