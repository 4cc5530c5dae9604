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

A program whose owner is gone by the time somebody reads (a caller that
drives the trainer's step itself and drops the trainer) files itself:
``filing`` wraps a jitted program in a callable that, at its first call,
puts the program and that call's abstract values in a process-wide, bounded
index; ``filed_records`` makes the records of what the index holds, on
demand, through ``program_records``.

A record knows when it is stale. JAX's persistent cache keys an executable
on its program less the debug information, and a scope is debug
information: a program that differs from an earlier lowering only in its
marks is served that lowering's executable, whose text carries the earlier
names. So each record also holds the scopes its *lowering* carries, and
where they are not the compiled text's it says which (``stale_scopes``): a
reader takes nothing from such a table.
"""

from __future__ import annotations

import collections
import re
import threading
import time
from typing import Any, Dict, Iterable, Iterator, List, Tuple

__all__ = ["SCOPES", "Filing", "abstract", "compile_programs", "filed_records",
           "filing", "program_records", "scope_table"]

#: every ``jax.named_scope`` name of the package
#: (``tests/test_program_scopes.py`` holds the two lists equal)
SCOPES = (
    "attn", "mlp", "ce", "optimizer", "sample", "kv_layout", "cached_attn",
    "ring_attn", "latent_attn", "moe_experts", "moe_shared", "moe_route",
    "lightning_scan", "lightning_step", "sparse_select", "sparse_attend",
    "exit_gate",
    # the parts of a layer and the stack's two ends, in every program that
    # runs them (``models/gpt.py``, ``models/generate.py``): with them
    # ``attn`` and ``mlp`` are what is left of a training sublayer (the
    # kernels, the residual sums, dropout)
    "qkv", "attn_out", "ffn", "norm", "head", "embed",
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
# the name stack of a lowering's operation, as ``as_text(debug_info=True)``
# prints it: `loc("jit(f)/attn/qkv/dot_general"(#loc7))`
# (a file's location is `loc("gpt.py":12:3)`, no parenthesis after the quote,
# and a traceback's frame is named after its function: no slash)
_LOCATION = re.compile(r'loc\("([^"]*/[^"]*)"\(')
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


def _scopes_in(names: Iterable[str]) -> set:
    return {_scope_of(name) for name in names} - {""}


def program_records(programs: Iterable[Program]) -> List[Dict[str, Any]]:
    """One ``program`` record for each of an owner's ``programs()``: the jit
    name a profile shows (the text's own ``HloModule`` line), when it was
    made, the owner's family and variant, the scope table, and the scopes
    the program's lowering carries (``lowered_scopes``). Where those are not
    the scopes of the compiled text, fused computations' insides included,
    the executable is of another lowering (one the persistent cache served)
    and the record names the scopes the two disagree on
    (``stale_scopes``)."""
    records = []
    for family, variant, jitted, args, kwargs in programs:
        lowered = jitted.lower(*args, **kwargs)
        text = lowered.compile().as_text()
        ours = _scopes_in(_LOCATION.findall(lowered.as_text(debug_info=True)))
        record = {
            "name": _MODULE.search(text).group(1), "ts": time.time(),
            "family": family, "variant": variant,
            "scopes": scope_table(text), "lowered_scopes": sorted(ours),
        }
        stale = ours ^ _scopes_in(_OP_NAME.findall(text))
        if stale:
            record["stale_scopes"] = sorted(stale)
        records.append(record)
    return records


class Filing:
    """A jitted program that files itself at its first call: ``(family,
    variant, jitted, the call's abstract values)`` goes into ``index``, and
    every call is the jit's own. It holds no owner and no array, and costs a
    step one flag test. Everything else (``lower``, ``trace``,
    ``_cache_size``) is the jitted program's."""

    def __init__(self, jitted, family: str, variant: str, index: "_Index"):
        self.jitted, self.family, self.variant = jitted, family, variant
        self._index, self._unfiled = index, True

    def __call__(self, *args, **kwargs):
        if self._unfiled:
            self.file(args, kwargs)
        return self.jitted(*args, **kwargs)

    def __getattr__(self, name):
        return getattr(self.jitted, name)

    def file(self, args: tuple, kwargs: dict) -> None:
        """File the program as a call with ``args`` builds it (arrays, or
        their abstract values). A call under a trace (``jax.make_jaxpr`` of
        the wrapper) is not a call of the program and files nothing."""
        import jax

        if any(isinstance(x, jax.core.Tracer)
               for x in jax.tree.leaves((args, kwargs))):
            return
        self._unfiled = False
        self._index.put(self, (self.family, self.variant, self.jitted,
                               abstract(args), abstract(kwargs)))

    def record(self, args: tuple, kwargs: dict) -> Dict[str, Any]:
        """The program's record, out of the index: filed with ``args`` (an
        owner's own statement of its program) where no call has filed it or
        the index has since let it go."""
        found = self._index.record_of(self)
        if found is None:
            self.file(args, kwargs)
            found = self._index.record_of(self)
        return found


class _Index:
    """The programs that filed themselves, oldest first, at most
    ``capacity`` of them (a process may build hundreds of trainers): each
    with its record once somebody has read it. An entry holds the jitted
    program (its traces and executables, which is where a record comes
    from) and abstract values; never an array."""

    def __init__(self, capacity: int):
        self.capacity = capacity
        self._lock = threading.Lock()
        self._entries: "collections.OrderedDict[Filing, list]" = \
            collections.OrderedDict()

    def put(self, key: Filing, program: Program) -> None:
        with self._lock:
            self._entries.pop(key, None)
            self._entries[key] = [program, None]
            while len(self._entries) > self.capacity:
                self._entries.popitem(last=False)

    def record_of(self, key: Filing):
        with self._lock:
            entry = self._entries.get(key)
        if entry is None:
            return None
        if entry[1] is None:
            entry[1] = dict(program_records([entry[0]])[0], kind="program")
        return dict(entry[1])

    def records(self) -> List[Dict[str, Any]]:
        with self._lock:
            keys = list(self._entries)
        return [r for r in map(self.record_of, keys) if r is not None]

    def __len__(self) -> int:
        return len(self._entries)


#: the process's index; 8 programs are more than one process runs at a time
FILED = _Index(capacity=8)


def filing(jitted, family: str, variant: str = "") -> Filing:
    """``jitted`` as a callable that files itself in the process's index at
    its first call (:class:`Filing`)."""
    return Filing(jitted, family, variant, FILED)


def filed_records() -> List[Dict[str, Any]]:
    """The ``program`` records of the programs that have filed themselves
    in this process, oldest first: made at the first read and kept (nothing
    is lowered or compiled until somebody reads, and then lowering and
    executable are the jit's own, found by the call's abstract values). A
    reader that holds a device profile sums it by scope with them, whoever
    owned the programs and whether or not that owner still exists."""
    return FILED.records()
