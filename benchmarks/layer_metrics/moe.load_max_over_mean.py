"""Layer ``experts``: the busiest expert's routed rows over the mean expert's,
in the traced window, the worst expert layer's. 1 is a level load; the
dropless route computes every row whatever this reads, so a high value costs
time (one expert's blocks run one after another) and never a token. Source:
the program's device-side counter of routed rows, through
``ServingMetrics.summary()``'s ``moe_load_max_over_mean``, which is since the
server was built: read at the traced window's close (a level, not a
difference; the warm-up's rows are a thousandth of a run's)."""


def read(ev):
    play = ev.get("play")
    if play is None or play.trace_close is None:
        return None
    return play.trace_close.get("moe_load_max_over_mean")
