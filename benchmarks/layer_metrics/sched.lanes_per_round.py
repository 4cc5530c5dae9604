"""Layer ``scheduler``: decode lanes that did useful work, a round. Source:
the program's own ``ServingMetrics`` (``slot_utilization`` is the running
mean of lanes used over slots, ``steps`` the rounds), read at both ends of
the traced window."""


def read(ev):
    play = ev.get("play")
    if play is None or play.trace_close is None:
        return None
    rounds = play.trace_close["steps"] - play.trace_open["steps"]
    lanes = play.trace_close["lanes"] - play.trace_open["lanes"]
    return lanes / rounds if rounds else None
