"""Layer ``kv_pool``: bytes of the pool over the rows that hold a token, the
mean of the traced window's rounds: what a cached token costs here, dead rows
counted (a decode round reads every row of every slot). Source: the program's
``kv_bytes_per_row`` gauge (``ServingMetrics.summary()``) times the rows the
pool reserves, over the scheduler's per-slot positions sampled after every
round."""


def read(ev):
    play = ev.get("play")
    if play is None or play.trace_close is None or not play.trace_live_rows:
        return None
    row_bytes = play.trace_close.get("kv_bytes_per_row")
    if not row_bytes:
        return None
    live = play.trace_live_rows / play.trace_rounds
    return row_bytes * play.n_slots * play.block_size / live
