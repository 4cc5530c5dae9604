"""Layer ``engine``: of the device time of the decode program's runs in the
traced window, the percentage that fell on instructions its ``program`` record
knows. Near 100; a table of another program than the one that ran shows here,
and the scoped metrics are then not to be believed (``harness/scopes.py``)."""

from benchmarks.harness import scopes


def read(ev):
    return scopes.join_share(ev)
