"""Layer ``kernel``: the decode walk's kernel's share of its memory roofline,
in percent: the bytes of cached rows the decode steps of the traced window
read, over the HBM peak of the table (``harness/device.py``), over the device
time of the Mosaic calls whose own instruction name holds ``rows_attend`` (the
``name=`` of the ``pallas_call`` in ``ops/attention.py``, one a layer whose
pool the kernel walks) in that window.

The bytes, from the program's own counters differenced over the window and
``summary()``'s gauges, by the rule the program runs (``attention.
step_rows_read`` on the kernel's ``StepWalk``: whole blocks up to each live
lane's reach, every lane alone):

  the full layers': ``decode_rows_read`` (rows of the slots, a plane) x
      ``kv_bytes_per_row`` (all planes' keys and values of one row)
  + the rings': ``ring_rows_read`` (rows of the rings, all window layers)
      x ``ring_bytes_per_slot`` / (``ring_rows_per_slot`` x ``ring_planes``)
      (one ring's keys and values of one row); nothing where the stack
      keeps no ring

The queries, the lanes' own new rows and what the calls write are not
counted, nor is any operation (a step is bound by the bytes it reads: some
tens of query heads a row): a bound from below, so a reading over 100 says
the time leaves out part of the calls or a counter counts too much. A trace
without such a call (the XLA walk in its place: the parent's program, a
latent pool, slices read in one pass), a program without the counters, or a
window in which no row was read reports nothing."""

from benchmarks.harness import device, trace


def rows_bytes(opened, closed):
    """Bytes of cached rows the decode steps read between two readings of
    ``summary()``; None where a counter or a gauge it needs is missing."""
    def moved(field):
        first, last = opened.get(field), closed.get(field)
        return None if first is None or last is None else last - first

    full, a_row = moved("decode_rows_read"), closed.get("kv_bytes_per_row")
    if full is None or not a_row:
        return None
    ring = moved("ring_rows_read")
    if ring is None:        # no layer keeps a ring
        return full * a_row
    a_slot, rows, planes = (closed.get(g) for g in (
        "ring_bytes_per_slot", "ring_rows_per_slot", "ring_planes"))
    if not a_slot or not rows or not planes:
        return None
    return full * a_row + ring * a_slot / (rows * planes)


def read(ev):
    tr, play = ev.get("trace"), ev.get("play")
    if tr is None or play is None:
        return None
    if play.trace_close is None or play.trace_open is None:
        return None
    read_bytes = rows_bytes(play.trace_open, play.trace_close)
    if read_bytes is None or read_bytes <= 0:
        return None
    if not any(d.ops for d in tr.devices.values()):
        return None
    seconds = trace.class_s(
        tr, trace.window_of(tr),
        lambda e: trace.is_mosaic(e) and "rows_attend" in e.base)
    if not seconds:
        return None
    least = read_bytes / device.peaks_for(ev["device_kind"])["hbm_bytes_s"]
    return 100.0 * least / seconds
