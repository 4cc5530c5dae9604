"""Layer ``scheduler``: lane-steps the decode program computed, over the
traced window, for a request that had stopped by their sync: what launching a
step ahead costs where the host cannot foresee a stop (an EOS, a cancel, a
deadline, a raising callback; a stop by length is foreseen, and that round
syncs first). 0 under traffic whose every request ends by its length. Source:
the scheduler's own counter (``ServingMetrics.summary()``'s
``decode_lane_steps_discarded``), differenced over the window. A program
without the counter reports nothing."""


def read(ev):
    play = ev.get("play")
    if play is None or play.trace_close is None or play.trace_open is None:
        return None
    closed, opened = (c.get("decode_lane_steps_discarded")
                      for c in (play.trace_close, play.trace_open))
    if closed is None or opened is None:
        return None
    return float(closed - opened)
