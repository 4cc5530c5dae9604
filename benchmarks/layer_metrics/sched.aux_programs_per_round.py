"""Layer ``scheduler``: program executions the device starts inside a decode
round that are neither the decode nor a prefill program: the small programs
the host dispatches one by one (``fold_in`` a lane, the stack of the keys).
Mean over the traced rounds; the rounds are the program's
``serve.decode_round`` spans, the executions the trace's, by jit name."""

from benchmarks.harness import spans


def read(ev):
    return spans.programs_per_round(ev, ("decode_impl", "prefill_impl"))
