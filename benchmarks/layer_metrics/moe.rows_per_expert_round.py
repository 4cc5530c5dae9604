"""Layer ``experts``: routed rows an expert computes in one scheduling round,
averaged over the experts of all expert layers and the rounds of the traced
window (prefill chunks' rows included): how many tokens amortise one read of
an expert's weights. A level of the traffic and the slots, not a cost.
Source: ``ServingMetrics.summary()``'s ``moe_routed_rows`` and ``steps``,
differenced over the window; the experts a layer from the cell's
configuration."""


def read(ev):
    play = ev.get("play")
    if play is None or play.trace_close is None:
        return None
    closed, opened = play.trace_close, play.trace_open
    if closed.get("moe_routed_rows") is None \
            or opened.get("moe_routed_rows") is None:
        return None
    rounds = closed["steps"] - opened["steps"]
    config = ev["cell"].config
    layers = config["num_hidden_layers"] - config["first_k_dense_replace"]
    if not rounds:
        return None
    return (closed["moe_routed_rows"] - opened["moe_routed_rows"]) / (
        rounds * layers * config["n_routed_experts"])
