"""Layer ``engine``: device milliseconds of the prefill programs
(``_prefill_impl``, by jit name) per thousand real prompt tokens prefilled in
the traced window (``ServingMetrics.prefill_tokens``). Padding to the bucket
is in the numerator and not in the denominator, so a ladder that fits the
traffic badly shows here."""

from benchmarks.harness import trace


def read(ev):
    tr, play = ev.get("trace"), ev.get("play")
    if tr is None or play is None or play.trace_close is None:
        return None
    runs = trace.program_runs(tr, trace.window_of(tr), "prefill_impl")
    tokens = play.trace_close["prefill_tokens"] \
        - play.trace_open["prefill_tokens"]
    return 1e3 * sum(runs) / (tokens / 1e3) if runs and tokens else None
