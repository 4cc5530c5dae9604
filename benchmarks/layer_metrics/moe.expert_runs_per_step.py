"""Layer ``experts``: experts that held at least one routed row, a call of the
experts' blocks and expert layer, summed over the traced window and divided
by its scheduling rounds (a prefill's calls included: a prompt of thousands
of tokens holds every expert): how many experts' weights a round must read at
the least, each once. With few lanes it stands near ``lanes x k`` a layer,
with many near ``E``: the level at which adding lanes stops costing expert
reads. Source: the program's device-side counter
(``ServingMetrics.summary()``'s ``moe_expert_runs``) and ``steps``,
differenced over the window. A program without the counter, or a window
without a round, reports nothing."""


def read(ev):
    play = ev.get("play")
    if play is None or play.trace_close is None or play.trace_open is None:
        return None
    moved = []
    for field in ("moe_expert_runs", "steps"):
        closed, opened = (c.get(field) for c in (play.trace_close,
                                                 play.trace_open))
        if closed is None or opened is None:
            return None
        moved.append(closed - opened)
    runs, rounds = moved
    return runs / rounds if rounds > 0 else None
