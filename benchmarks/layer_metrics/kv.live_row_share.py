"""Layer ``kv_pool``: rows of the pool that hold a token over rows allocated
(``n_slots`` x ``block_size``), in percent, sampled after every round of the
traced window from the scheduler's per-slot positions: memory in use against
memory reserved."""


def read(ev):
    play = ev.get("play")
    if play is None or not play.trace_rounds:
        return None
    return 100.0 * play.trace_live_rows / (
        play.trace_rounds * play.n_slots * play.block_size)
