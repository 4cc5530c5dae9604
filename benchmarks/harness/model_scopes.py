"""The ``model`` layer by scope: device time of a program's runs under the
marks the parts of a layer carry (``qkv``, ``attn_out``, ``ffn``, ``norm``,
``head``, ``embed``; ``mingpt_distributed_tpu/telemetry/programs.py``
``SCOPES``), through the one join of ``harness/scopes.py``.

A serving cell's records ride ``evidence["program_spans"]``. A training
cell's evidence holds none (its loop drives the trainer's step and drops
the trainer), so they come from the program itself:
``telemetry.programs.filed_records()``, the tables of the programs that
filed themselves in this process at their first call. The join gets a copy
of the evidence with those records in ``program_spans``' place.

Every reader built on this returns None, and the line leaves its metric
out, where the program is from before the marks: no ``filed_records``, no
record of the program, a record that does not say what its lowering carried
(``lowered_scopes``: without it a table cannot say it is not stale), a
record whose executable is of another lowering (``stale_scopes``), a table
in which none of the reader's scopes occurs; and, as for every reader of
the join, where there is no trace or no run of the program in the window.
"""

from __future__ import annotations

import sys
import traceback
from typing import List, Optional, Sequence

from benchmarks.harness import scopes

#: the trainer's step, by a part of its jit name
TRAIN = "train_step"
#: the layer's marks: a table that holds none of them is from before them
MARKS = ("qkv", "attn_out", "ffn", "norm", "head", "embed")


def _filed_records() -> List[dict]:
    """What the process's programs filed of themselves; nothing from a
    program that cannot say, and nothing, with the reason on standard
    error, where making a record fails: a result line without the layer's
    metrics is still the run's result."""
    from mingpt_distributed_tpu.telemetry import programs

    make = getattr(programs, "filed_records", None)
    if make is None:
        return []
    try:
        return make()
    except Exception:       # the reader's boundary: the line must be printed
        traceback.print_exc(file=sys.stderr)
        return []


def train_evidence(ev) -> dict:
    """``ev`` with the filed records where a serving cell's records are;
    made once a run (the copy keeps the join's reduction for the cell's
    other readers)."""
    if "model_train_evidence" not in ev:
        ev["model_train_evidence"] = dict(
            ev, program_spans=_filed_records(), ms_by_scope={})
    return ev["model_train_evidence"]


def _believed(ev, program_part: str, wanted: Sequence[str]) -> bool:
    """Whether the evidence's records of the program can be read for the
    named scopes among ``wanted``."""
    records = [r for r in ev.get("program_spans") or []
               if r.get("kind") == "program"
               and program_part in r.get("name", "")]
    named = {s for s in wanted if s}
    return bool(records) and all(
        "lowered_scopes" in r and not r.get("stale_scopes") for r in records
    ) and any(named & set(r["scopes"].values()) for r in records)


def ms(ev, program_part: str, wanted: Sequence[str]) -> Optional[float]:
    """Device milliseconds a run of the program spends under ``wanted``
    together (``""``: what no scope covers), the mean over the traced
    window; None where the table cannot be read for them."""
    if not _believed(ev, program_part, wanted):
        return None
    got = scopes.ms_by_scope(ev, program_part)
    if got is None:
        return None
    return sum(got["unscoped_ms"] if s == "" else got["by_scope"].get(s, 0.0)
               for s in wanted)


def decode_ms(ev, wanted: Sequence[str]) -> Optional[float]:
    """``ms`` of the decode step, from the server's own records."""
    return ms(ev, scopes.DECODE, wanted)


def train_ms(ev, wanted: Sequence[str]) -> Optional[float]:
    """``ms`` of the trainer's step, from the records the step filed."""
    return ms(train_evidence(ev), TRAIN, wanted)


def train_join_share(ev) -> Optional[float]:
    """Of the device time of the step's runs, the percentage on instructions
    the filed table knows."""
    tev = train_evidence(ev)
    if not _believed(tev, TRAIN, MARKS):
        return None
    return scopes.join_share(tev, TRAIN)
