"""Layer ``attention``: device milliseconds a run of the decode program spends
in the window layers' steps over their rings, the mean over the traced
window: the operations under the scope ``ring_attn`` (a stack of
``layer_types``' sliding layers, each lane's last ``sliding_window`` rows
read as they lie). ``attention.decode_ms_per_step`` reads ``cached_attn``,
the full layers' steps over a row a position: the two add up to the mixers'
time. Source: the program's ``program`` record joined to the trace
(``harness/scopes.py``). A program without the scope reports nothing."""

from benchmarks.harness import scopes


def read(ev):
    return scopes.decode_ms(ev, ("ring_attn",))
