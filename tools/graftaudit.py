#!/usr/bin/env python
"""graftaudit CLI — static audit of every lowered program family
(ISSUE 15 tentpole; checks live in ``analysis/hlo_audit.py``).

Usage: python tools/graftaudit.py [--tp {1,2}] [--json]

Builds the canonical tiny serving + speculation stack (the
``serve.py --selftest-sharded`` config) — and, on the tp=1 sweep, the
tiny trainer — then AOT-lowers every program family their
``programs()`` enumerate
(:func:`~mingpt_distributed_tpu.analysis.hlo_audit.lower_programs`) and
checks the lowered artifacts against the families' declared contracts:
collectives inventory, donation aliasing and output-sharding drift.
Nothing is ever executed on the model (params are initialised,
programs are only lowered + compiled).

Sweeps: ``--tp 1`` is the single-device audit (every family must lower
with zero collectives); ``--tp 2`` runs the same serving stack across a
forced-2-device mesh (``XLA_FLAGS=--xla_force_host_platform_device_count=2``
on CPU) and proves the tp contracts: reduce-family ops only, no
gathered KV pool, donation aliasing intact, normalized sharding specs.

Exit codes mirror graftlint: 0 clean, 1 findings, 2 usage/build error.
The ``--json`` envelope (``graftaudit/1``) is byte-identical across
consecutive runs — run_tests.sh ``cmp``s two tp=2 runs.
"""

from __future__ import annotations

import argparse
import contextlib
import os
import sys
import tempfile


def _repo_import():
    """Running this file directly puts tools/ on sys.path; make the
    repo root importable."""
    try:
        import mingpt_distributed_tpu  # noqa: F401
    except ImportError:
        sys.path.insert(
            0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _build_serving(tp: int):
    """The canonical audit config: the selftest-sharded tiny GPT, a
    2-slot engine with a {8, 48} prefill ladder and the prefix store on
    (so the copy families register), plus a k=2 speculative decoder
    whose draft is the same tiny model. Returns the fp32 stack AND its
    int8 twin (ISSUE 18): same geometry, ``kv_dtype="int8"`` — its
    families audit under the ``q8_`` prefix, proving dequant adds no
    collectives and donation aliasing survives the dtype change."""
    import jax

    from mingpt_distributed_tpu.config import GPTConfig, MeshConfig
    from mingpt_distributed_tpu.models import gpt
    from mingpt_distributed_tpu.parallel.mesh import make_mesh
    from mingpt_distributed_tpu.serving.engine import DecodeEngine
    from mingpt_distributed_tpu.serving.speculative import SpeculativeDecoder

    cfg = GPTConfig.make(
        n_layer=2, n_head=2, n_embd=32, vocab_size=96, block_size=48,
        embd_pdrop=0.0, resid_pdrop=0.0, attn_pdrop=0.0, dtype="float32",
    )
    params = gpt.init(jax.random.key(0), cfg)
    mesh = (make_mesh(MeshConfig(tp=tp), devices=jax.devices()[:tp])
            if tp > 1 else None)
    engine = DecodeEngine(
        params, cfg, n_slots=2, prefill_buckets=(8, 48),
        prefix_cache_mb=0.5, mesh=mesh,
    )
    spec = SpeculativeDecoder(engine, params, cfg, k=2)
    q8_engine = DecodeEngine(
        params, cfg, n_slots=2, prefill_buckets=(8, 48),
        prefix_cache_mb=0.5, mesh=mesh, kv_dtype="int8",
    )
    q8_spec = SpeculativeDecoder(q8_engine, params, cfg, k=2)
    return engine, spec, q8_engine, q8_spec


def _build_trainer(tmpdir: str):
    """Tiny single-device trainer so the train_step family is audited
    on the tp=1 sweep (dense variant; the zero/dp forms need a multi-dp
    mesh and stay covered by their own selftests)."""
    import jax
    import numpy as np  # noqa: F401  (kept: trainer deps import numpy)

    from mingpt_distributed_tpu.config import (
        DataConfig,
        GPTConfig,
        MeshConfig,
        OptimizerConfig,
        TrainerConfig,
    )
    from mingpt_distributed_tpu.data.char_dataset import CharDataset
    from mingpt_distributed_tpu.parallel import mesh as mesh_lib
    from mingpt_distributed_tpu.training.trainer import GPTTrainer

    corpus = ("graftaudit lowers the train step to audit collectives "
              "and aliasing; it never runs it. " * 24)
    ds = CharDataset(
        DataConfig(path="<inline>", block_size=16, train_split=0.9),
        text=corpus)
    train, test = ds.split()
    gcfg = GPTConfig.make(
        n_layer=2, n_head=2, n_embd=32, vocab_size=ds.vocab_size,
        block_size=16, embd_pdrop=0.0, resid_pdrop=0.0, attn_pdrop=0.0,
        dtype="float32",
    )
    tcfg = TrainerConfig.make(
        max_epochs=1, batch_size=16, grad_norm_clip=1.0, save_every=100,
        log_every=1000, seed=7,
        snapshot_path=os.path.join(tmpdir, "snap.msgpack"),
    )
    mesh = mesh_lib.make_mesh(
        MeshConfig(pp=1, dp=1, fsdp=1, ep=1, tp=1, sp=1),
        devices=jax.devices()[:1])
    return GPTTrainer(
        tcfg, gcfg, OptimizerConfig(learning_rate=1e-2), train, test,
        mesh=mesh)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="graftaudit", description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--tp", type=int, default=1, choices=(1, 2),
                    help="tensor-parallel extent of the audited mesh "
                         "(2 needs >= 2 devices)")
    ap.add_argument("--json", action="store_true",
                    help="emit the graftaudit/1 envelope instead of the "
                         "human rendering")
    args = ap.parse_args(argv)

    _repo_import()
    from mingpt_distributed_tpu.analysis.hlo_audit import (
        audit_exit_code,
        audit_programs,
        build_audit_report,
        dump_audit_report,
        lower_programs,
        render_audit_human,
        validate_audit_report,
    )

    import jax

    if args.tp > 1 and len(jax.devices()) < args.tp:
        print(f"graftaudit: --tp {args.tp} needs >= {args.tp} devices, "
              f"found {len(jax.devices())} (on CPU run under XLA_FLAGS="
              f"--xla_force_host_platform_device_count={args.tp})",
              file=sys.stderr)
        return 2

    # Build chatter (log_event, sharding telemetry) goes to stderr so
    # --json stdout stays a single parseable document.
    with contextlib.redirect_stdout(sys.stderr), \
            tempfile.TemporaryDirectory() as tmpdir:
        engine, spec, q8_engine, q8_spec = _build_serving(args.tp)
        programs = [
            *engine.programs(), *spec.programs(),
            *q8_engine.programs(family_prefix="q8_"),
            *q8_spec.programs(family_prefix="q8_"),
        ]
        contracts = {
            **engine.audit_contracts(), **spec.audit_contracts(),
            **q8_engine.audit_contracts(family_prefix="q8_"),
            **q8_spec.audit_contracts(family_prefix="q8_"),
        }
        if args.tp == 1:
            trainer = _build_trainer(tmpdir)
            programs += trainer.programs()
            contracts.update(trainer.audit_contracts())
        artifacts = lower_programs(programs)

    findings = audit_programs(artifacts, contracts)
    report = build_audit_report(
        {"tp": args.tp, "devices": args.tp},
        artifacts, contracts, findings)
    validate_audit_report(report)
    print(dump_audit_report(report) if args.json
          else render_audit_human(report))
    return audit_exit_code(findings)


if __name__ == "__main__":
    sys.exit(main())
