"""Layer ``scheduler``: program executions the device starts inside a decode
round that are neither the decode nor a prefill program: whatever small
programs the host dispatches one by one beside them. Since PR 24 there are
none (before it: a ``fold_in`` and a cast a lane, and the stack of the keys),
so a reading above 0 says that an eager dispatch has come back into the
round. Mean over the traced rounds; the rounds are the program's
``serve.decode_round`` spans, the executions the trace's, by jit name."""

from benchmarks.harness import spans


def read(ev):
    return spans.programs_per_round(ev, ("decode_impl", "prefill_impl"))
