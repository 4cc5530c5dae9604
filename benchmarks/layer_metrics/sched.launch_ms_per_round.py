"""Layer ``scheduler``: host milliseconds a decode round spends stacking the
keys, staging the arguments and calling the jitted decode program up to its
return (the enqueue), as the mean over the traced rounds of the program's
``serve.decode_launch`` span (``serving/engine.py`` ``decode_step``)."""

from benchmarks.harness import spans


def read(ev):
    return spans.child_ms_per_round(ev, "serve.decode_launch")
