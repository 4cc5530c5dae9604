"""From a profiler trace to numbers: the benchmark's own reduction.

``capture`` records a window with ``jax.profiler``; ``load`` reads the
``.xplane.pb`` it leaves with ``jax.profiler.ProfileData`` (nothing but JAX).
The rest is plain arithmetic over intervals in nanoseconds on the
profiler's one clock, which host annotations (``jax.profiler.
TraceAnnotation``) and device operations share:

  busy       union of the intervals in which an operation ran on a device
  idle gaps  the window minus that union, each gap named after the host
             annotation open at the time
  sums       device time of the operations of one class (the flash Mosaic
             calls, the collectives) or of one program (by its jit name)
  exposed    collective time during which no other operation ran

``from_planes`` takes any objects shaped like ``ProfileData.planes``, which
is how ``benchmarks/tests`` checks the arithmetic on a trace with known
answers.
"""

from __future__ import annotations

import bisect
import contextlib
import dataclasses
import glob
import os
import re
import shutil
from typing import Callable, Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
#: host annotations the benchmark writes itself all start with this
ANNOTATION_PREFIX = "bench."
#: JAX's host event around a call of a jitted function: PjitFunction(<name>)
LAUNCH = re.compile(r"^PjitFunction\((.+)\)$")
#: the program name XLA gives a jitted functools.partial
UNNAMED = "jit__unknown"

COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute", "collective-broadcast", "send", "recv")
#: operations whose event spans the operations of their body
CONTAINERS = ("while", "conditional", "call")
#: how a Mosaic (Pallas) kernel shows: on the chip an operation's name is
#: its whole HLO instruction, and only the kernel's own custom call carries
#: this attribute (its consumers merely name it among their operands)
MOSAIC_MARK = 'custom_call_target="tpu_custom_call"'
#: the host's and the device's timelines usually agree to some tens of
#: microseconds, and were seen a millisecond apart: a program may seem to
#: start that much before the call that launched it
CLOCK_SLACK_NS = 1e5
MAX_CLOCK_SKEW_NS = 5e6


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start_ns: float
    dur_ns: float
    text: str = ""        # name and string statistics, lower case

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns

    @property
    def base(self) -> str:
        """``%all-gather-start.12`` -> ``all-gather``."""
        n = self.name.lstrip("%").split(" ")[0]
        n = re.sub(r"[.\d]+$", "", n)
        return re.sub(r"-(start|done)$", "", n)


@dataclasses.dataclass
class Device:
    ops: List[Event]
    modules: List[Event]
    busy_cache: Dict[Interval, List[Interval]] = dataclasses.field(
        default_factory=dict)


@dataclasses.dataclass
class Trace:
    devices: Dict[int, Device]
    annotations: List[Event]          # the benchmark's own, from host planes
    start_unix_ns: Optional[int]      # epoch of the profiler clock's zero
    launches: List[Event] = dataclasses.field(default_factory=list)
    #                                 ^ JAX's own host event around each call
    #                                   of a jitted function, with its name


# ---------------------------------------------------------------------------
# capture and load
# ---------------------------------------------------------------------------


def annotate(name: str):
    """A host span on the profiler's clock; near free when nothing traces."""
    import jax

    return jax.profiler.TraceAnnotation(ANNOTATION_PREFIX + name)


@contextlib.contextmanager
def capture(directory: str):
    """Trace what runs inside, under one ``bench.window`` annotation that
    ``window_of`` finds again. The Python tracer is off (a scheduler that is
    mostly Python would drown in its own trace) and no HLO is attached."""
    import jax

    shutil.rmtree(directory, ignore_errors=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    options.enable_hlo_proto = False
    jax.profiler.start_trace(directory, profiler_options=options)
    try:
        with annotate("window"):
            yield
    finally:
        jax.profiler.stop_trace()


def load(directory: str, devices: Sequence) -> Optional[Trace]:
    """The trace ``capture`` left under ``directory``. On a TPU it has to
    hold a plane for each of ``devices``; on any other backend (a rehearsal)
    there are no device planes to reduce, and the answer is None."""
    from jax.profiler import ProfileData

    found = glob.glob(os.path.join(directory, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    tr = from_planes(ProfileData.from_file(sorted(found)[-1]).planes)
    if devices[0].platform != "tpu":
        return None
    missing = {d.id for d in devices} - set(tr.devices)
    if missing or not all(d.ops for d in tr.devices.values()):
        raise RuntimeError(
            f"the trace under {directory} has no operations for device(s) "
            f"{sorted(missing) or sorted(tr.devices)}")
    return tr


def _event(ev) -> Event:
    stats = []
    for key, val in ev.stats:
        if isinstance(val, (str, bytes)):
            stats.append(val.decode() if isinstance(val, bytes) else val)
    return Event(name=ev.name, start_ns=float(ev.start_ns),
                 dur_ns=float(ev.duration_ns),
                 text=" ".join([ev.name] + stats).lower())


def from_planes(planes: Iterable) -> Trace:
    devices: Dict[int, Device] = {}
    annotations: List[Event] = []
    launches: List[Event] = []
    start_unix_ns = None
    for plane in planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = Device(ops=[], modules=[])
            for line in plane.lines:
                if line.name == OPS_LINE:
                    dev.ops = sorted((_event(e) for e in line.events),
                                     key=lambda e: e.start_ns)
                elif line.name == MODULES_LINE:
                    dev.modules = sorted((_event(e) for e in line.events),
                                         key=lambda e: e.start_ns)
            devices[int(m.group(1))] = dev
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(ANNOTATION_PREFIX):
                        annotations.append(_event(e))
                    elif LAUNCH.match(e.name):
                        launches.append(_event(e))
        elif plane.name == "Task Environment":
            stats = dict(plane.stats)
            if "profile_start_time" in stats:
                start_unix_ns = int(stats["profile_start_time"])
    annotations.sort(key=lambda e: e.start_ns)
    launches.sort(key=lambda e: e.start_ns)
    return Trace(devices=devices, annotations=annotations,
                 start_unix_ns=start_unix_ns, launches=launches)


# ---------------------------------------------------------------------------
# classes of operations
# ---------------------------------------------------------------------------


def is_collective(ev: Event) -> bool:
    return ev.base in COLLECTIVES


def is_container(ev: Event) -> bool:
    return ev.base in CONTAINERS


def is_mosaic(ev: Event) -> bool:
    return MOSAIC_MARK in ev.text


def is_compute(ev: Event) -> bool:
    return not is_collective(ev) and not is_container(ev)


# ---------------------------------------------------------------------------
# interval arithmetic
# ---------------------------------------------------------------------------


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Merged, sorted, non-overlapping; empty intervals dropped."""
    out: List[Interval] = []
    for lo, hi in sorted(i for i in intervals if i[1] > i[0]):
        if out and lo <= out[-1][1]:
            if hi > out[-1][1]:
                out[-1] = (out[-1][0], hi)
        else:
            out.append((lo, hi))
    return out


def total(intervals: Iterable[Interval]) -> float:
    return sum(hi - lo for lo, hi in intervals)


def clip(intervals: Iterable[Interval], window: Interval) -> List[Interval]:
    lo, hi = window
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def subtract(a: Sequence[Interval], b: Sequence[Interval]) -> List[Interval]:
    """``a`` minus ``b``; both already merged and sorted."""
    out: List[Interval] = []
    j = 0
    for lo, hi in a:
        cur = lo
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < hi:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < hi:
            out.append((cur, hi))
    return out


def spans_of(events: Iterable[Event],
             keep: Callable[[Event], bool] = lambda e: True) -> List[Interval]:
    return [(e.start_ns, e.end_ns) for e in events if keep(e)]


# ---------------------------------------------------------------------------
# the numbers
# ---------------------------------------------------------------------------


def window_of(trace: Trace) -> Interval:
    """The traced window: ``capture``'s ``bench.window`` annotation, or, if
    the host plane lost it, the extent of the device operations."""
    for ev in trace.annotations:
        if ev.name == ANNOTATION_PREFIX + "window":
            return (ev.start_ns, ev.end_ns)
    ops = [e for d in trace.devices.values() for e in d.ops]
    if not ops:
        raise ValueError("the trace holds no device operation")
    return (min(e.start_ns for e in ops), max(e.end_ns for e in ops))


def busy(dev: Device, window: Interval) -> List[Interval]:
    if window not in dev.busy_cache:
        dev.busy_cache[window] = union(clip(spans_of(dev.ops), window))
    return dev.busy_cache[window]


def busy_s(trace: Trace, window: Interval) -> float:
    """Seconds in which an operation ran, averaged over the devices."""
    per_dev = [total(busy(d, window)) for d in trace.devices.values()]
    return sum(per_dev) / len(per_dev) / 1e9 if per_dev else 0.0


def class_s(trace: Trace, window: Interval,
            keep: Callable[[Event], bool]) -> float:
    """Device seconds of the operations ``keep`` admits (containers never
    count: their time is their body's), averaged over the devices."""
    per_dev = [
        total(clip(spans_of(d.ops, lambda e: keep(e) and not is_container(e)),
                   window))
        for d in trace.devices.values()
    ]
    return sum(per_dev) / len(per_dev) / 1e9 if per_dev else 0.0


def exposed_collective_s(trace: Trace, window: Interval) -> float:
    """Collective time during which no compute operation ran on the same
    device, averaged over the devices."""
    per_dev = []
    for d in trace.devices.values():
        coll = union(clip(spans_of(d.ops, is_collective), window))
        comp = union(clip(spans_of(d.ops, is_compute), window))
        per_dev.append(total(subtract(coll, comp)))
    return sum(per_dev) / len(per_dev) / 1e9 if per_dev else 0.0


def program_names(trace: Trace, dev: Device) -> List[str]:
    """The jit name of each program run on ``dev``. A program that was
    jitted from a ``functools.partial`` (the serving engine's are) reaches
    XLA unnamed; it takes the name of the host call that launched it. The
    host waits for each such program before it calls the next (every engine
    call ends in a transfer of its tokens), so the unnamed programs and the
    calls of functions no program is named after pair off one to one, in
    order: a program belongs to the last such call before it started, or,
    if that one is taken (the device's clock can run a millisecond ahead of
    the host's), to the next."""
    named = {e.name.split("(")[0] for e in dev.modules}
    calls = [(e.start_ns, LAUNCH.match(e.name).group(1))
             for e in trace.launches
             if "jit_" + LAUNCH.match(e.name).group(1) not in named]
    # a call shows twice, once for each layer of JAX's dispatch
    calls = [c for k, c in enumerate(calls)
             if k == 0 or c[1] != calls[k - 1][1]
             or c[0] - calls[k - 1][0] > CLOCK_SLACK_NS]
    starts = [t for t, _ in calls]
    out, taken = [], -1
    for e in dev.modules:
        name = e.name.split("(")[0]
        if name == UNNAMED:
            k = bisect.bisect_right(starts, e.start_ns + CLOCK_SLACK_NS) - 1
            if k <= taken and taken + 1 < len(calls) \
                    and starts[taken + 1] <= e.start_ns + MAX_CLOCK_SKEW_NS:
                k = taken + 1
            if k >= 0:
                name, taken = "jit_" + calls[k][1], max(taken, k)
        out.append(name)
    return out


def program_runs(trace: Trace, window: Interval, name_part: str) -> List[float]:
    """Device durations in seconds of each run, inside the window, of the
    programs whose jit name holds ``name_part``, on the first device."""
    if not trace.devices:
        return []
    dev = trace.devices[min(trace.devices)]
    lo, hi = window
    return [e.dur_ns / 1e9
            for e, name in zip(dev.modules, program_names(trace, dev))
            if name_part in name and e.start_ns >= lo and e.end_ns <= hi]


def innermost_labels(spans: Sequence[Tuple[str, float, float]]
                     ) -> List[Tuple[float, float, str]]:
    """Flatten possibly nested named spans into disjoint segments, each
    labelled with the span that started last among those open."""
    marks = sorted({t for _, lo, hi in spans for t in (lo, hi)})
    starts = sorted(spans, key=lambda s: s[1])
    out: List[Tuple[float, float, str]] = []
    open_: List[Tuple[str, float, float]] = []
    i = 0
    for lo, hi in zip(marks, marks[1:]):
        while i < len(starts) and starts[i][1] <= lo:
            open_.append(starts[i])
            i += 1
        open_ = [s for s in open_ if s[2] > lo]
        if open_:
            label = max(open_, key=lambda s: s[1])[0]
            if out and out[-1][2] == label and out[-1][1] == lo:
                out[-1] = (out[-1][0], hi, label)
            else:
                out.append((lo, hi, label))
    return out


def idle_gaps_by_span(trace: Trace, window: Interval,
                      spans: Sequence[Tuple[str, float, float]],
                      top: int = 10) -> List[List]:
    """The first device's idle time inside the window, split by what the
    host was doing: ``[[span name, seconds], ...]``, longest first."""
    if not trace.devices:
        return []
    dev = trace.devices[min(trace.devices)]
    gaps = subtract([window], busy(dev, window))
    segments = innermost_labels(spans)
    seg_lo = [s[0] for s in segments]
    by_name: Dict[str, float] = {}
    for lo, hi in gaps:
        covered = 0.0
        k = max(bisect.bisect_right(seg_lo, lo) - 1, 0)
        while k < len(segments) and segments[k][0] < hi:
            a, b, label = segments[k]
            part = min(b, hi) - max(a, lo)
            if part > 0:
                by_name[label] = by_name.get(label, 0.0) + part
                covered += part
            k += 1
        if hi - lo - covered > 0:
            by_name["(no span)"] = by_name.get("(no span)", 0.0) \
                + (hi - lo - covered)
    ranked = sorted(by_name.items(), key=lambda kv: -kv[1])[:top]
    return [[name, ns / 1e9] for name, ns in ranked]


def op_label(ev: Event) -> str:
    if is_mosaic(ev):
        return "mosaic:" + ev.base
    return ev.base


def top_device_ops(trace: Trace, window: Interval, top: int = 10) -> List[List]:
    """``[[operation, seconds], ...]``: device time by operation, averaged
    over the devices, largest first."""
    by_name: Dict[str, float] = {}
    lo, hi = window
    for d in trace.devices.values():
        for e in d.ops:
            if is_container(e):
                continue
            part = min(e.end_ns, hi) - max(e.start_ns, lo)
            if part > 0:
                key = op_label(e)
                by_name[key] = by_name.get(key, 0.0) + part
    n = max(len(trace.devices), 1)
    ranked = sorted(by_name.items(), key=lambda kv: -kv[1])[:top]
    return [[name, ns / n / 1e9] for name, ns in ranked]


def annotation_spans(trace: Trace) -> List[Tuple[str, float, float]]:
    return [(e.name[len(ANNOTATION_PREFIX):], e.start_ns, e.end_ns)
            for e in trace.annotations]


def program_spans(trace: Trace, records: Sequence[dict]
                  ) -> List[Tuple[str, float, float]]:
    """The program's own ``SpanTracer`` spans on the profiler's clock. They
    are stamped with ``time.time()``; the trace says at which epoch its clock
    is zero. The two clocks are only as close as the host keeps them, so a
    span is kept only where it falls inside one of the benchmark's own
    ``bench.round`` annotations, which is where the code puts it."""
    if trace.start_unix_ns is None:
        return []
    rounds = sorted((e.start_ns, e.end_ns) for e in trace.annotations
                    if e.name == ANNOTATION_PREFIX + "round")
    starts = [r[0] for r in rounds]
    out = []
    for r in records:
        if r.get("kind") != "span":
            continue
        lo = r["ts"] * 1e9 - trace.start_unix_ns
        hi = lo + r["dur_s"] * 1e9
        k = bisect.bisect_right(starts, lo) - 1
        if k >= 0 and hi <= rounds[k][1]:
            out.append((r["name"], lo, hi))
    return out


def host_s_per_round(trace: Trace, window: Interval,
                     name: str = "round") -> List[float]:
    """For each ``bench.<name>`` annotation inside the window: its length
    minus the time the first device was busy inside it, in seconds. That is
    the round's host time: scheduling, Python, launches and waiting on
    transfers, with the device idle."""
    if not trace.devices:
        return []
    merged = busy(trace.devices[min(trace.devices)], window)
    starts = [lo for lo, _ in merged]
    out = []
    for ev in trace.annotations:
        if ev.name != ANNOTATION_PREFIX + name:
            continue
        if ev.start_ns < window[0] or ev.end_ns > window[1]:
            continue
        near = merged[max(bisect.bisect_right(starts, ev.start_ns) - 1, 0):
                      bisect.bisect_left(starts, ev.end_ns)]
        inside = total(clip(near, (ev.start_ns, ev.end_ns)))
        out.append((ev.dur_ns - inside) / 1e9)
    return out
