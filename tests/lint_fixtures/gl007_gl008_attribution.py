"""GL007/GL008 fixtures — wall-clock and naming temptations in code
that times compiled programs and registers gauges for them.

A report built on a VirtualClock is byte-identical across two runs only
while every compile/device timestamp is read from the injected clock,
never the wall. These are the shapes that would quietly break that,
plus the gauge-family naming contract.

Positives: timing an AOT compile with ``time.perf_counter()``;
sampling a device interval through an imported ``perf_counter``
alias; an off-convention gauge name.
Suppressed: one wall-clock headroom probe and one bad name, inline
disable.
Negatives: the injected-clock compile timer, a ``wall_ts`` report
stamp, an injectable clock default, and conforming ``mingpt_attrib_*``
registrations.
"""
import time
from time import perf_counter


class _Reg:
    """Stand-in with the MetricsRegistry registration surface."""

    def counter(self, name, help="", labels=()):
        return name

    def gauge(self, name, help="", labels=()):
        return name


REG = _Reg()


def timed_compile_bad(jit_fn, args):
    t0 = time.perf_counter()  # expect: GL007
    compiled = jit_fn.lower(*args).compile()
    return compiled, time.perf_counter() - t0  # expect: GL007


def observe_bad(ledger, family, started):
    ledger.observe(family, perf_counter() - started)  # expect: GL007


def hbm_probe_wall_suppressed():
    return time.monotonic()  # graftlint: disable=GL007


def timed_compile(jit_fn, args, clock):
    t0 = clock()  # clean: injected clock
    compiled = jit_fn.lower(*args).compile()
    return compiled, clock() - t0


def stamp_report(report):
    wall_ts = time.time()  # clean: epoch stamp on the exported report
    report["wall_ts"] = wall_ts
    return report


def make_ledger_clock(clock=time.perf_counter):  # clean: injectable ref
    return clock


FLOPS = REG.gauge("mingpt_attrib_flops", labels=("family", "variant"))
CALLS = REG.counter("mingpt_attrib_calls_total")  # clean: conforming
HBM = REG.gauge("mingpt_attrib_hbm_bytes", labels=("owner",))
BAD_NAME = REG.gauge("attrib_mfu")  # expect: GL008
BAD_SUPPRESSED = REG.gauge("hbm_bytes")  # graftlint: disable=GL008
