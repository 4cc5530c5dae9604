"""graftaudit tests (ISSUE 15): HLO parsing on synthetic text, the
checks against real lowered programs, contract coverage of the tiny
engine's full family set, and report validation + byte-determinism.

The run_tests.sh gate runs the full CLI sweeps (tp=1 and forced-2-device
tp=2, byte-identical double run); these tests pin the pieces those
sweeps are assembled from, so a unit regression names the broken part
instead of "the gate went red".
"""

import json
import textwrap

import jax
import jax.numpy as jnp
import pytest

from mingpt_distributed_tpu.analysis.hlo_audit import (
    AUDIT_SCHEMA,
    ProgramArtifact,
    audit_programs,
    build_audit_report,
    collective_inventory,
    donated_alias_count,
    dump_audit_report,
    lower_programs,
    validate_audit_report,
)
from mingpt_distributed_tpu.config import GPTConfig
from mingpt_distributed_tpu.models import gpt
from mingpt_distributed_tpu.serving.engine import DecodeEngine


# ---------------------------------------------------------------------
# HLO text parsing (synthetic fixtures — no backend)
# ---------------------------------------------------------------------

SYNTH_HLO = textwrap.dedent("""\
    HloModule audit_fixture, input_output_alias={ {0}: (0, {}, may-alias), {1}: (2, {}, must-alias) }, entry_computation_layout={(f32[8,16]{1,0})->f32[16,16]{1,0}}

    %add_helper (a: f32[], b: f32[]) -> f32[] {
      %a = f32[] parameter(0)
      %b = f32[] parameter(1)
      ROOT %sum = f32[] add(%a, %b)
    }

    ENTRY %main (p0: f32[8,16]) -> f32[16,16] {
      %p0 = f32[8,16]{1,0} parameter(0)
      %ag = f32[16,16]{1,0} all-gather(%p0), dimensions={0}
      %ars = f32[16,16]{1,0} all-reduce-start(%ag), to_apply=%add_helper
      %ard = f32[16,16]{1,0} all-reduce-done(%ars)
      %fused = f32[16,16]{1,0} fusion(%ard), kind=kLoop, calls=%all_reduce_like_name
      ROOT %cp = f32[16,16]{1,0} collective-permute(%fused), source_target_pairs={{0,1}}
    }
    """)


def test_collective_inventory_synthetic():
    inv = collective_inventory(SYNTH_HLO)
    ops = [item["op"] for item in inv]
    # the async pair counts ONCE (start carries the shape, done is
    # skipped) and the fusion whose *operand metadata* mentions an
    # all-reduce-like name does not count at all
    assert ops == ["all-gather", "all-reduce", "collective-permute"]
    assert all(not item["host_transfer"] for item in inv)
    assert [item["elems"] for item in inv] == [256, 256, 256]
    # line numbers point into the text (1-based)
    lines = SYNTH_HLO.splitlines()
    for item in inv:
        assert item["op"].split("-")[0] in lines[item["line"] - 1]


def test_host_transfer_always_flagged():
    hlo = (
        "ENTRY %main {\n"
        "  %tok = token[] after-all()\n"
        '  %s = (f32[4]{0}, u32[], token[]) send(%x, %tok), channel_id=1,'
        " is_host_transfer=true\n"
        "}\n"
    )
    inv = collective_inventory(hlo)
    assert len(inv) == 1
    assert inv[0]["host_transfer"]
    # a host transfer is a finding no matter what the contract allows
    art = ProgramArtifact("decode", "", hlo, [])
    findings = audit_programs(
        {("decode", ""): art},
        {"decode": {"allowed_collectives": ("send",), "donated": 0}})
    assert [f.check for f in findings] == ["collectives"]
    assert "host transfer" in findings[0].message


def test_donated_alias_count_synthetic():
    assert donated_alias_count(SYNTH_HLO) == 2
    assert donated_alias_count("HloModule nothing_donated\n") == 0
    # three entries, including a multi-index output tuple path
    hdr = ("HloModule m, input_output_alias={ {0}: (0, {}, may-alias), "
           "{1, 0}: (1, {}, may-alias), {1, 1}: (2, {}, must-alias) }\n")
    assert donated_alias_count(hdr) == 3


def test_undeclared_collective_is_finding():
    art = ProgramArtifact("decode", "", SYNTH_HLO, [])
    contract = {"allowed_collectives": ("all-gather", "all-reduce"),
                "donated": 2}
    findings = audit_programs({("decode", ""): art}, {"decode": contract})
    assert [f.check for f in findings] == ["collectives"]
    assert "collective-permute" in findings[0].message


def test_pool_sized_collective_is_finding():
    # all ops declared, but the all-gather result (256 elems) reaches
    # the pool-buffer size => moving the pool, not an activation
    art = ProgramArtifact("decode", "", SYNTH_HLO, [])
    contract = {"allowed_collectives":
                ("all-gather", "all-reduce", "collective-permute"),
                "donated": 2, "pool_leaf_elems": 256}
    findings = audit_programs({("decode", ""): art}, {"decode": contract})
    assert findings and all(f.check == "collectives" for f in findings)
    assert "KV" in findings[0].message and "256" in findings[0].message


def test_missing_contract_is_finding():
    art = ProgramArtifact("mystery", "b8", "HloModule m\n", [])
    findings = audit_programs({("mystery", "b8"): art}, {})
    assert [(f.family, f.check) for f in findings] == [("mystery",
                                                        "contract")]
    assert "no audit contract" in findings[0].message


# ---------------------------------------------------------------------
# donation check against REAL lowered programs
# ---------------------------------------------------------------------


def _artifact_from_jit(fn, args, family="fam"):
    return lower_programs([(family, "", fn, args, {})])[(family, "")]


def test_donation_verified_in_lowered_hlo():
    x = jnp.arange(64, dtype=jnp.float32).reshape(8, 8)
    donated = jax.jit(lambda a: a * 2.0 + 1.0, donate_argnums=0)
    art = _artifact_from_jit(donated, (x,))
    assert donated_alias_count(art.hlo_text) == 1
    assert audit_programs(
        {("fam", ""): art},
        {"fam": {"allowed_collectives": (), "donated": 1}}) == []


def test_silent_donation_fallback_is_finding():
    """The 3am failure mode: the jit stopped donating (someone dropped
    donate_argnums) but nothing crashes — only HBM doubles. The audit
    names it."""
    x = jnp.arange(64, dtype=jnp.float32).reshape(8, 8)
    undonated = jax.jit(lambda a: a * 2.0 + 1.0)
    art = _artifact_from_jit(undonated, (x,))
    findings = audit_programs(
        {("fam", ""): art},
        {"fam": {"allowed_collectives": (), "donated": 1}})
    assert [f.check for f in findings] == ["donation"]
    assert "silently fell back to copies" in findings[0].message


# ---------------------------------------------------------------------
# the tiny engine end-to-end: full family coverage, clean audit,
# byte-identical reports
# ---------------------------------------------------------------------


@pytest.fixture(scope="module")
def engine():
    cfg = GPTConfig.make(
        n_layer=2, n_head=2, n_embd=32, vocab_size=50, block_size=32,
        embd_pdrop=0.0, resid_pdrop=0.0, attn_pdrop=0.0, dtype="float32",
    )
    params = gpt.init(jax.random.key(0), cfg)
    return DecodeEngine(params, cfg, n_slots=2, prefill_buckets=(4, 32),
                        prefix_cache_mb=0.5)


def test_every_engine_family_has_a_contract(engine):
    """Audit-coverage gate (satellite): a family the engine's
    ``programs()`` lists without a contract fails the SUITE, not just
    the CLI — so a new jit program cannot land unaudited."""
    artifacts = lower_programs(engine.programs())
    contracts = engine.audit_contracts()
    families = {family for (family, _) in artifacts}
    assert families == {"prefill", "decode", "prefix_save", "prefix_load"}
    assert families <= set(contracts), (
        f"families without an audit contract: "
        f"{sorted(families - set(contracts))}")
    assert not [f for f in audit_programs(artifacts, contracts)
                if f.check == "contract"]


def test_tiny_engine_audits_clean(engine):
    before = engine.compile_counts()
    artifacts = lower_programs(engine.programs())
    # ahead-of-time lowering never enters the jit call caches: an audit
    # next to an armed recompile watchdog cannot trip it
    assert engine.compile_counts() == before
    findings = audit_programs(artifacts, engine.audit_contracts())
    assert findings == [], [f.render() for f in findings]
    # single-device sweep: zero collectives anywhere, donation as
    # contracted (2 cache leaves for prefill/decode/load, 0 for save)
    for (family, variant), art in artifacts.items():
        assert collective_inventory(art.hlo_text) == [], (family, variant)
        want = engine.audit_contracts()[family]["donated"]
        assert donated_alias_count(art.hlo_text) == want, (family, variant)


def test_audit_report_byte_identical_across_runs(engine):
    """The envelope holds only properties of the lowered programs —
    rebuilding from a fresh lowering serializes byte-identically
    (the run_tests.sh tp=2 gate cmp's two full CLI runs; this pins the
    same property in-process)."""
    sweep = {"tp": 1, "devices": 1}

    def one():
        artifacts = lower_programs(engine.programs())
        contracts = engine.audit_contracts()
        findings = audit_programs(artifacts, contracts)
        return dump_audit_report(build_audit_report(
            sweep, artifacts, contracts, findings))

    a, b = one(), one()
    assert a == b
    report = json.loads(a)
    validate_audit_report(report)
    assert report["schema"] == AUDIT_SCHEMA
    assert report["summary"]["findings"] == 0


def test_validate_audit_report_rejects_tampering(engine):
    contracts = engine.audit_contracts()
    report = build_audit_report({"tp": 1, "devices": 1},
                                lower_programs(engine.programs()),
                                contracts, [])
    validate_audit_report(report)
    bad = json.loads(dump_audit_report(report))
    bad["summary"]["programs"] += 1
    with pytest.raises(ValueError, match="summary.programs"):
        validate_audit_report(bad)
    bad2 = json.loads(dump_audit_report(report))
    del bad2["programs"][0]["donated"]
    with pytest.raises(ValueError, match="missing"):
        validate_audit_report(bad2)
    with pytest.raises(ValueError, match="schema"):
        validate_audit_report({"schema": "nope/1"})
