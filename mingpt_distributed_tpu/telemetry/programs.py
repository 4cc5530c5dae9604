"""The ``program`` record: which named scope each instruction of a
compiled program came from.

A device profile names an operation after its HLO instruction
(``%fusion.2160 = ...`` on the chip) and a program after its jit name
(``jit__decode_impl(<fingerprint>)``); the ``jax.named_scope`` marks of the
package reach only the compiled text, as ``op_name`` metadata. The owner of
each compiled program (``DecodeEngine``, ``SpeculativeDecoder``,
``GPTTrainer``) already states it as data (``programs()``), so the owner can
hand over the join: ``program_records`` compiles each program ahead of time
and files one record a program, ``{"name", "ts", "family", "variant",
"scopes": {instruction: scope}}``, which the owner pins in its ``SpanTracer``
(``SpanTracer.pin``: made on the first read, never evicted). Any reader of
the ring or the spans JSONL sums a profile's device time by scope with it
(``benchmarks/harness/scopes.py`` does; ``docs/architecture.md``,
"Telemetry").

``jitted.lower(...).compile()`` never inserts into the jit call cache, so
``compile_counts()`` and an armed recompile watchdog are untouched. For a
program a call has already built, given the call's own abstract values
(``abstract``), the lowering and the executable come back from the jit's own
caches: the table is of the executable that runs and nothing is lowered
again; a program no call has built yet is compiled here.
"""

from __future__ import annotations

import re
import time
from typing import Any, Dict, Iterable, Iterator, List, Tuple

__all__ = ["SCOPES", "abstract", "compile_programs", "program_records",
           "scope_table"]

#: every ``jax.named_scope`` name of the package
#: (``tests/test_program_scopes.py`` holds the two lists equal)
SCOPES = (
    "attn", "mlp", "ce", "optimizer", "sample", "kv_layout", "cached_attn",
    "latent_attn", "moe_experts", "moe_shared", "lightning_scan",
    "lightning_step", "sparse_select", "sparse_attend", "exit_gate",
)

Program = Tuple[str, str, Any, tuple, dict]

_MODULE = re.compile(r"^HloModule\s+([\w.\-]+)", re.M)
# `[ENTRY] %name (params) -> shape {`: a computation opens at column 0
_COMPUTATION = re.compile(r"^(ENTRY\s+)?%?([\w.\-]+)\s*\(.*\{\s*$")
# `  [ROOT] %name = shape opcode(`: layouts hold `T(8,128)` and `S(1)`, never
# a lower-case word before a parenthesis, so the first such word is the opcode
_INSTRUCTION = re.compile(
    r"^\s+(?:ROOT\s+)?%?([\w.\-]+)\s*=\s.*?[\s)]([a-z][a-z0-9\-]*)\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
# the computations a container runs as they stand (a fusion's `calls=` and a
# reducer's `to_apply=` are the instruction's own insides)
_CONTAINERS = ("while", "conditional", "call")
_RUNS = re.compile(
    r"\b(?:body|condition|to_apply|true_computation|false_computation"
    r"|branch_computations)=(\{[^}]*\}|%?[\w.\-]+)")


def abstract(tree):
    """``tree`` with every array replaced by its shape, dtype and, where the
    array is committed to one, its sharding: what ``jitted.lower`` needs to
    build the program a call with the live arrays runs, holding none of them
    (a donated buffer may be gone by the time a pinned record is made). An
    uncommitted array's sharding is left out as the call leaves it out, so
    the lowering is the call's own, found again in the jit's cache."""
    import jax

    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(
            jax.numpy.shape(x), jax.numpy.result_type(x),
            sharding=x.sharding if getattr(x, "committed", False) else None),
        tree)


def compile_programs(
        programs: Iterable[Program]) -> Iterator[Tuple[str, str, Any]]:
    """``(family, variant, compiled)`` for every ``(family, variant, jitted,
    args, kwargs)`` of an owner's ``programs()``: the one lowering loop
    (``analysis/hlo_audit.lower_programs`` audits what it yields)."""
    for family, variant, jitted, args, kwargs in programs:
        yield family, variant, jitted.lower(*args, **kwargs).compile()


def _scope_of(op_name: str) -> str:
    """The innermost component of an ``op_name`` path that is a scope.
    Transforms wrap the components they cover (``transpose(jvp(attn))/mul``),
    so the path is split at parentheses too."""
    for part in reversed(re.split(r"[/()]", op_name)):
        if part in SCOPES:
            return part
    return ""


def scope_table(hlo_text: str) -> Dict[str, str]:
    """Instruction name -> scope (``""``: none) for every instruction of the
    entry computation and of every computation a ``while``, ``conditional``
    or ``call`` runs, however deep. The insides of a fused computation are
    left out (the fusion instruction carries its root's metadata and is what
    a profile shows). An instruction without a scope is in the table all the
    same: a reader tells "no scope" from "not of this program"."""
    computations: Dict[str, List[Tuple[str, str, str]]] = {}
    entry, current = None, None
    for line in hlo_text.splitlines():
        if current is None:
            m = _COMPUTATION.match(line)
            if m:
                current = computations.setdefault(m.group(2), [])
                if m.group(1):
                    entry = m.group(2)
        elif line.startswith("}"):
            current = None
        else:
            m = _INSTRUCTION.match(line)
            if m:
                current.append((m.group(1), m.group(2), line))
    table: Dict[str, str] = {}
    todo, seen = [entry], {entry}
    while todo:
        for name, opcode, line in computations.get(todo.pop(), ()):
            m = _OP_NAME.search(line)
            table[name] = _scope_of(m.group(1)) if m else ""
            if opcode in _CONTAINERS:
                for ref in re.findall(r"[\w.\-]+",
                                      " ".join(_RUNS.findall(line))):
                    if ref not in seen:
                        seen.add(ref)
                        todo.append(ref)
    return table


def program_records(programs: Iterable[Program]) -> List[Dict[str, Any]]:
    """One ``program`` record for each of an owner's ``programs()``: the jit
    name a profile shows (the text's own ``HloModule`` line), when it was
    made, the owner's family and variant, and the scope table."""
    records = []
    for family, variant, compiled in compile_programs(programs):
        text = compiled.as_text()
        records.append({
            "name": _MODULE.search(text).group(1), "ts": time.time(),
            "family": family, "variant": variant,
            "scopes": scope_table(text),
        })
    return records
