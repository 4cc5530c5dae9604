"""Layer ``scheduler``: milliseconds of a decode round that none of its
child spans covers: ``serve.decode_round`` minus the spans whose ``parent``
it is, mean over the traced rounds. With ``sched.fold_keys_ms_per_round``,
``sched.launch_ms_per_round``, ``engine.sync_wait_ms_per_round`` and
``sched.emit_ms_per_round`` it sums to the mean round by construction; it is
the check that the children cover the round."""

from benchmarks.harness import spans


def read(ev):
    return spans.self_ms_per_round(ev)
