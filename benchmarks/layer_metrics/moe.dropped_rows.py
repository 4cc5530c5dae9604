"""Layer ``experts``: routes asked for and not computed, in the traced
window: the program's device-side counter of the rows each expert's blocks
computed against the routes its tokens asked for
(``ServingMetrics.summary()``'s ``moe_dropped_rows``, differenced over the
window). The route drops nothing, so this reads 0; a capacity would show
here first."""


def read(ev):
    play = ev.get("play")
    if play is None or play.trace_close is None:
        return None
    closed = play.trace_close.get("moe_dropped_rows")
    opened = play.trace_open.get("moe_dropped_rows")
    if closed is None or opened is None:
        return None
    return closed - opened
