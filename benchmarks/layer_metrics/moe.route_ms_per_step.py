"""Layer ``experts``: device milliseconds a run of the decode program spends
making the routes of a router that reads the attention's input (scope
``moe_route``: the router's float32 matmul, the k largest, the gates), the
mean over the traced window. The experts themselves are
``moe.experts_ms_per_step``'s; a router that reads the MLP's input has no
such scope (its route stands under ``ffn``), and a program without the scope
reports nothing. Source: the program's ``program`` record joined to the trace
(``harness/scopes.py``)."""

from benchmarks.harness import scopes


def read(ev):
    got = scopes.ms_by_scope(ev, scopes.DECODE)
    if got is None or "moe_route" not in got["by_scope"]:
        return None
    return got["by_scope"]["moe_route"]
