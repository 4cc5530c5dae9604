"""graftaudit — static verification of lowered program families
(ISSUE 15 tentpole).

graftlint (``analysis/core.py``) checks the *Python* the repo authors;
this module checks the *programs XLA actually built* from it. The
incidents that cost hardware rounds all lived below the AST: sharding
specs that compare unequal after jit normalization (PR 12), GSPMD
quietly inserting collectives into a "single-device" hot path, and
donation falling back to copies that double HBM. Each of those is
visible in the lowered artifact — the post-optimization HLO text, the
executable's ``input_output_alias`` table and ``output_shardings`` — so
each becomes a statically checkable contract.

The auditor never executes the model. Each owner of compiled programs
(engine, speculative decoder, trainer) states them as data — its
``programs()`` yields ``(family, variant, jitted, args, kwargs)`` —
and :func:`lower_programs` lowers and compiles each ahead of time into
a :class:`ProgramArtifact`. A family that is enumerated without an
audit contract is itself a finding (no silent audit gaps).

Three checks per (family, variant) artifact, against the plain-dict
contracts the owning subsystems declare (``DecodeEngine
.audit_contracts`` et al. — serving code never imports this module):

* **collectives** — every collective instruction in the optimized HLO
  (``all-gather`` / ``all-reduce`` / ``all-to-all`` /
  ``collective-permute`` / ``reduce-scatter``, async ``-start/-done``
  forms normalized) must be declared in the contract's
  ``allowed_collectives``; host transfers are never allowed; and no
  collective result may be as large as one KV pool buffer
  (``pool_leaf_elems``) — reducing a per-token activation over tp is
  the design, gathering the pool is the regression.
* **donation** — the executable's ``input_output_alias`` entry count
  must equal the contract's ``donated`` (or be >= ``donated_min``):
  "donation requested but copied" fails the audit instead of doubling
  HBM at 3am.
* **sharding** — every K/V leaf of ``output_shardings`` must equal the
  contract's ``kv_output_sharding`` (the runtime-normalized
  NamedSharding); the contract spec itself must carry no trailing
  ``None`` (the PR 12 gotcha, also linted at the AST level by GL011).

Output mirrors graftlint's conventions: a versioned ``graftaudit/1``
JSON envelope (sorted keys — two runs against the same jaxlib are
byte-identical), a human rendering, exit 0 clean / 1 findings / 2
usage.
"""

from __future__ import annotations

import json
import re
from dataclasses import dataclass
from typing import Any, Dict, Iterable, List, Tuple

from mingpt_distributed_tpu.analysis.core import (
    EXIT_CLEAN,
    EXIT_FINDINGS,
)
from mingpt_distributed_tpu.telemetry.programs import compile_programs

__all__ = [
    "AUDIT_SCHEMA",
    "AuditFinding",
    "ProgramArtifact",
    "audit_programs",
    "build_audit_report",
    "collective_inventory",
    "donated_alias_count",
    "dump_audit_report",
    "lower_programs",
    "render_audit_human",
    "validate_audit_report",
]

AUDIT_SCHEMA = "graftaudit/1"

#: collective op base names (async -start/-done forms normalize to these)
COLLECTIVE_OPS = (
    "all-gather",
    "all-reduce",
    "all-to-all",
    "collective-broadcast",
    "collective-permute",
    "reduce-scatter",
)

#: ops that move data between host and device — never allowed in a
#: serving/training hot path, whatever the contract says
HOST_TRANSFER_OPS = ("infeed", "outfeed", "recv", "send")

# An HLO instruction *definition*: `  [ROOT] %name = <shape> opcode(...`
# — anchoring on the `= shape opcode(` triple so operand references
# inside a line (which repeat opcode-like names) never count.
_INSTR_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?[\w.\-]+\s*=\s*"
    r"(\([^)]*\)|\S+)"        # result shape: tuple or single token
    r"\s+([a-z][\w\-]*)\("    # opcode
)

# Element counts inside a shape string: every `[d0,d1,...]` group
# (`f32[]` is a scalar: empty dims, one element).
_DIMS_RE = re.compile(r"[a-z]\d*\[([\d,]*)\]")

# One input_output_alias table entry: `{out_idx...}: (arg, {sub}, kind)`.
# The inner `{}` of the entry body is followed by `,`, not `:`, so it
# can never match.
_ALIAS_ENTRY_RE = re.compile(r"\{[\d,\s]*\}:\s*\(\d+")


# ---------------------------------------------------------------------
# lowered-artifact capture
# ---------------------------------------------------------------------


@dataclass
class ProgramArtifact:
    """Everything the audit needs from one compiled program family
    member (the lowered/compiled objects themselves are not retained)."""

    family: str
    variant: str
    hlo_text: str
    output_shardings: Any


def lower_programs(
    programs: Iterable[Tuple[str, str, Any, tuple, dict]],
) -> Dict[Tuple[str, str], ProgramArtifact]:
    """Lower and compile every ``(family, variant, jitted, args,
    kwargs)`` of an owner's ``programs()`` ahead of time and keep what
    the checks read. ``jitted.lower(...).compile()`` never inserts into
    the jit call cache, so the owner's ``compile_counts()`` and an armed
    recompile watchdog are untouched by an audit."""
    artifacts: Dict[Tuple[str, str], ProgramArtifact] = {}
    for family, variant, compiled in compile_programs(programs):
        artifacts[(family, variant)] = ProgramArtifact(
            family=family,
            variant=variant,
            hlo_text=compiled.as_text(),
            output_shardings=compiled.output_shardings,
        )
    return artifacts


# ---------------------------------------------------------------------
# HLO text parsing
# ---------------------------------------------------------------------


def _shape_elems(shape: str) -> int:
    """Max element count over the (possibly tuple) result shape — the
    size of the largest buffer the instruction materializes."""
    best = 1
    for dims in _DIMS_RE.findall(shape):
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        best = max(best, n)
    return best


def _base_op(op: str) -> str:
    for suffix in ("-start", "-done"):
        if op.endswith(suffix):
            return op[: -len(suffix)]
    return op


def collective_inventory(hlo_text: str) -> List[Dict[str, Any]]:
    """Every collective / host-transfer instruction definition in the
    HLO text: ``[{"op", "elems", "line"}, ...]`` with async forms
    normalized to their base op (so an ``all-gather-start`` audits as an
    ``all-gather``, counted once — the ``-done`` carries no shape of its
    own worth double-counting)."""
    out: List[Dict[str, Any]] = []
    for lineno, line in enumerate(hlo_text.splitlines(), start=1):
        m = _INSTR_RE.match(line)
        if not m:
            continue
        shape, op = m.group(1), m.group(2)
        base = _base_op(op)
        is_collective = base in COLLECTIVE_OPS and not op.endswith("-done")
        is_host = base in HOST_TRANSFER_OPS or "is_host_transfer=true" in line
        if not (is_collective or is_host):
            continue
        out.append({
            "op": base if is_collective else op,
            "host_transfer": bool(is_host),
            "elems": _shape_elems(shape),
            "line": lineno,
        })
    return out


def donated_alias_count(hlo_text: str) -> int:
    """Number of ``input_output_alias`` entries in the executable — one
    per donated leaf XLA actually aliased. 0 when the header is absent
    (nothing donated, or everything silently copied)."""
    idx = hlo_text.find("input_output_alias=")
    if idx < 0:
        return 0
    # the alias table lives on the (single-line) HloModule header
    segment = hlo_text[idx:hlo_text.find("\n", idx)]
    return len(_ALIAS_ENTRY_RE.findall(segment))


def _kv_output_shardings(output_shardings: Any) -> List[Tuple[str, Any]]:
    """(path, sharding) for every K/V cache leaf of a program's output
    pytree — the leaves reached through a dict key ``"k"`` or ``"v"``
    (the ``Cache`` container every pool/prefix program returns), plus
    the ``k_scale``/``v_scale`` planes a quantized pool carries (their
    sharded axis is kv_heads too, so the same authored sharding must
    hold — a scale plane that gathered would silently replicate)."""
    import jax  # lazy: parsing-only callers never need a backend

    flat = jax.tree_util.tree_flatten_with_path(output_shardings)[0]
    out = []
    for path, shard in flat:
        keys = [p.key for p in path
                if isinstance(p, jax.tree_util.DictKey)]
        if any(k in ("k", "v", "k_scale", "v_scale") for k in keys):
            out.append((jax.tree_util.keystr(path), shard))
    return out


def _spec_has_trailing_none(sharding: Any) -> bool:
    spec = getattr(sharding, "spec", None)
    return bool(spec) and len(spec) > 0 and spec[-1] is None


# ---------------------------------------------------------------------
# findings + checks
# ---------------------------------------------------------------------


@dataclass(frozen=True)
class AuditFinding:
    """One contract violation in one lowered program."""

    family: str
    variant: str
    check: str      # contract | collectives | donation | sharding
    message: str

    @property
    def sort_key(self):
        return (self.family, self.variant, self.check, self.message)

    def to_dict(self) -> Dict[str, str]:
        return {
            "family": self.family,
            "variant": self.variant,
            "check": self.check,
            "message": self.message,
        }

    def render(self) -> str:
        where = f"{self.family}:{self.variant}" if self.variant \
            else self.family
        return f"{where} [{self.check}] {self.message}"


def _audit_one(art: ProgramArtifact, contract: Dict[str, Any],
               ) -> List[AuditFinding]:
    f: List[AuditFinding] = []

    def finding(check: str, message: str) -> None:
        f.append(AuditFinding(art.family, art.variant, check, message))

    # (a) collectives inventory
    allowed = set(contract.get("allowed_collectives", ()))
    pool_elems = contract.get("pool_leaf_elems")
    for item in collective_inventory(art.hlo_text):
        if item["host_transfer"]:
            finding("collectives",
                    f"host transfer {item['op']!r} at HLO line "
                    f"{item['line']} — never allowed in a compiled "
                    f"hot path")
            continue
        if item["op"] not in allowed:
            finding("collectives",
                    f"undeclared collective {item['op']!r} at HLO line "
                    f"{item['line']} (allowed: "
                    f"{sorted(allowed) or 'none'})")
        elif pool_elems is not None and item["elems"] >= pool_elems:
            finding("collectives",
                    f"{item['op']!r} at HLO line {item['line']} moves "
                    f"{item['elems']} elements — at least one whole KV "
                    f"pool buffer ({pool_elems}); collectives may touch "
                    f"activations, never the pool")

    # (b) donation verification
    got = donated_alias_count(art.hlo_text)
    want = contract.get("donated")
    want_min = contract.get("donated_min")
    if want is not None and got != want:
        finding("donation",
                f"executable aliases {got} input-output pairs, contract "
                f"requires exactly {want} — donation "
                + ("silently fell back to copies" if got < want
                   else "aliases more than the contract declares"))
    elif want_min is not None and got < want_min:
        finding("donation",
                f"executable aliases {got} input-output pairs, contract "
                f"requires at least {want_min} — donation silently fell "
                f"back to copies")

    # (c) sharding-spec drift
    if "kv_output_sharding" in contract:
        expected = contract["kv_output_sharding"]
        if expected is not None and _spec_has_trailing_none(expected):
            finding("sharding",
                    f"contract sharding spec {expected.spec} has a "
                    f"trailing None — not the runtime-normalized form "
                    f"(PR 12: equality-keyed executables would see a "
                    f"novel layout)")
        for path, shard in _kv_output_shardings(art.output_shardings):
            if expected is None:
                n_dev = len(getattr(shard, "device_set", ())) or 1
                if n_dev > 1:
                    finding("sharding",
                            f"output {path} is partitioned over {n_dev} "
                            f"devices on a single-device engine")
            elif shard != expected:
                finding("sharding",
                        f"output {path} sharding {shard} != authored "
                        f"normalized sharding {expected}")

    return f


def audit_programs(
    artifacts: Dict[Tuple[str, str], ProgramArtifact],
    contracts: Dict[str, Dict[str, Any]],
) -> List[AuditFinding]:
    """Run checks (a)-(c) for every captured artifact against its
    family's contract. A family with no contract is a finding (check
    ``contract``): audit coverage is part of the suite, so a new program
    family cannot land unaudited."""
    findings: List[AuditFinding] = []
    for (family, variant) in sorted(artifacts):
        art = artifacts[(family, variant)]
        contract = contracts.get(family)
        if contract is None:
            findings.append(AuditFinding(
                family, variant, "contract",
                f"program family {family!r} is listed by its owner's "
                f"programs() but declares no audit contract — add one "
                f"next to its jit definition"))
            continue
        findings.extend(_audit_one(art, contract))
    return sorted(findings, key=lambda x: x.sort_key)


# ---------------------------------------------------------------------
# graftaudit/1 report
# ---------------------------------------------------------------------


def _contract_row(contract: Dict[str, Any]) -> Dict[str, Any]:
    row: Dict[str, Any] = {
        "allowed_collectives":
            sorted(contract.get("allowed_collectives", ())),
    }
    for k in ("donated", "donated_min", "pool_leaf_elems"):
        if k in contract:
            row[k] = contract[k]
    if "kv_output_sharding" in contract:
        sh = contract["kv_output_sharding"]
        row["kv_output_spec"] = None if sh is None else str(sh.spec)
    return row


def build_audit_report(
    sweep: Dict[str, Any],
    artifacts: Dict[Tuple[str, str], ProgramArtifact],
    contracts: Dict[str, Dict[str, Any]],
    findings: List[AuditFinding],
) -> Dict[str, Any]:
    """Assemble the versioned envelope. Everything in it is a property
    of the lowered programs (never a clock or a live-buffer readout), so
    two consecutive runs against the same jaxlib serialize
    byte-identically — the run_tests.sh gate ``cmp``s them."""
    programs = []
    for (family, variant) in sorted(artifacts):
        art = artifacts[(family, variant)]
        counts: Dict[str, int] = {}
        largest = 0
        for item in collective_inventory(art.hlo_text):
            counts[item["op"]] = counts.get(item["op"], 0) + 1
            largest = max(largest, item["elems"])
        programs.append({
            "family": family,
            "variant": variant,
            "collectives": dict(sorted(counts.items())),
            "largest_collective_elems": largest,
            "donated": donated_alias_count(art.hlo_text),
        })
    by_check: Dict[str, int] = {}
    for f in findings:
        by_check[f.check] = by_check.get(f.check, 0) + 1
    return {
        "schema": AUDIT_SCHEMA,
        "sweep": dict(sweep),
        "programs": programs,
        "contracts": {fam: _contract_row(c)
                      for fam, c in sorted(contracts.items())},
        "findings": [f.to_dict() for f in findings],
        "summary": {
            "programs": len(programs),
            "findings": len(findings),
            "by_check": dict(sorted(by_check.items())),
        },
    }


_PROGRAM_KEYS = ("family", "variant", "collectives",
                 "largest_collective_elems", "donated")
_FINDING_KEYS = ("family", "variant", "check", "message")


def validate_audit_report(report: Dict[str, Any]) -> None:
    """Strict structural validation (raises ValueError), so readers of
    a report never defend."""
    if report.get("schema") != AUDIT_SCHEMA:
        raise ValueError(
            f"not a {AUDIT_SCHEMA} report: schema={report.get('schema')!r}")
    if not isinstance(report.get("sweep"), dict):
        raise ValueError("sweep must be an object")
    progs = report.get("programs")
    if not isinstance(progs, list):
        raise ValueError("programs must be a list")
    seen = set()
    for i, row in enumerate(progs):
        missing = set(_PROGRAM_KEYS) - set(row)
        if missing:
            raise ValueError(f"programs[{i}] missing {sorted(missing)}")
        key = (row["family"], row["variant"])
        if key in seen:
            raise ValueError(f"duplicate program row {key}")
        seen.add(key)
        if row["donated"] < 0 or row["largest_collective_elems"] < 0:
            raise ValueError(f"programs[{i}] has negative accounting")
    finds = report.get("findings")
    if not isinstance(finds, list):
        raise ValueError("findings must be a list")
    for i, row in enumerate(finds):
        missing = set(_FINDING_KEYS) - set(row)
        if missing:
            raise ValueError(f"findings[{i}] missing {sorted(missing)}")
    summary = report.get("summary")
    if not isinstance(summary, dict):
        raise ValueError("summary must be an object")
    if summary.get("programs") != len(progs):
        raise ValueError("summary.programs != len(programs)")
    if summary.get("findings") != len(finds):
        raise ValueError("summary.findings != len(findings)")


def dump_audit_report(report: Dict[str, Any]) -> str:
    """Canonical serialization: sorted keys, fixed separators — the
    byte-identity contract of the run_tests.sh double-run gate."""
    return json.dumps(report, sort_keys=True, indent=2)


def render_audit_human(report: Dict[str, Any]) -> str:
    sweep = report["sweep"]
    lines = [f"graftaudit ({report['schema']}): "
             f"{report['summary']['programs']} programs audited, "
             f"tp={sweep.get('tp')} over {sweep.get('devices')} device(s)"]
    lines.append(
        f"  {'family':<16} {'variant':<8} {'collectives':<28} "
        f"{'donated':>7}")
    for row in report["programs"]:
        colls = ",".join(f"{op}x{n}"
                         for op, n in row["collectives"].items()) or "-"
        lines.append(
            f"  {row['family']:<16} {row['variant']:<8} {colls:<28} "
            f"{row['donated']:>7}")
    if report["findings"]:
        lines.append(f"{report['summary']['findings']} finding(s):")
        for row in report["findings"]:
            where = (f"{row['family']}:{row['variant']}"
                     if row["variant"] else row["family"])
            lines.append(f"  {where} [{row['check']}] {row['message']}")
    else:
        lines.append("clean: every lowered program honours its contract")
    return "\n".join(lines)


def audit_exit_code(findings: List[AuditFinding]) -> int:
    return EXIT_FINDINGS if findings else EXIT_CLEAN
