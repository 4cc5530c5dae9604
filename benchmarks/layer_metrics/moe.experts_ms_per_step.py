"""Layer ``experts``: device milliseconds a run of the decode program spends in
the routed experts' grouped matmuls and the shared experts (scopes
``moe_experts`` and ``moe_shared``), the mean over the traced window. Source:
the program's ``program`` record joined to the trace (``harness/scopes.py``)."""

from benchmarks.harness import scopes


def read(ev):
    return scopes.decode_ms(ev, ("moe_experts", "moe_shared"))
