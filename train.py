#!/usr/bin/env python
"""Training entry point — the reference's mingpt/train.py re-done TPU-first.

Reference flow (/root/reference/mingpt/train.py:30-58): hydra main -> NCCL
process group -> unpack 4 config dataclasses -> build dataset/model/optimizer
(get_resources, train.py:11-27) -> GPTTrainer -> train() -> teardown.

Same flow here, with the TPU-native mechanisms: YAML + dotted CLI overrides
(no Hydra run-dir games), jax.distributed for multi-host, a named device mesh
instead of DDP, and vocab/block-size overridden from the dataset exactly as
the reference does (train.py:23-24 — fixing its b13/b14 import and split bugs).

Usage:
  python train.py                               # gpt2_config.yaml
  python train.py --config my.yaml trainer_config.max_epochs=2
  python train.py gpt_config.model_type=gpt-mini data_config.path=in.txt

Run the SAME command on every TPU worker host (launch/tpu_pod_run.sh does
this) — process topology comes from the environment, like torchrun's env
contract (SURVEY §1-L0: launcher-sets-env / app-reads-env, preserved).

Preemption contract (ISSUE 2): SIGTERM/SIGINT stop the loop at the next
step boundary, commit a snapshot, and exit with code 75 (EX_TEMPFAIL) so
a scheduler/wrapper can requeue the job; the requeued run resumes from
that snapshot. ``--selftest-faults`` runs the fault-injected checkpoint
save/restore smoke (no dataset or config needed) — the CI gate for the
durability layer, and with ``MINGPT_FAULTS`` + a ``faulty://`` snapshot
path the same injector doubles as a manual chaos knob for real runs.
"""

from __future__ import annotations

import argparse
import dataclasses
import sys

import jax


def selftest_faults() -> int:
    """Injected-failure save/restore roundtrip on a tmpdir: every 3rd
    object write fails transiently (retries must absorb it), the latest
    blob is then truncated on disk (restore must fall back to the
    previous digest-verified checkpoint, never load the torn one)."""
    import tempfile

    import fsspec
    import numpy as np

    from mingpt_distributed_tpu.training import checkpoint as ckpt
    from mingpt_distributed_tpu.training import durability as dur
    from mingpt_distributed_tpu.training import faults  # noqa: F401 — registers faulty://

    rc = 0
    like = {"w": np.zeros(16, np.float32)}
    with tempfile.TemporaryDirectory() as d:
        fs = fsspec.filesystem("faulty")
        fs.set_faults("write:every=3")
        try:
            path = f"faulty://{d}/snap.msgpack"
            for step in (1, 2):
                ckpt.save_snapshot(
                    path,
                    ckpt.Snapshot(
                        params={"w": np.full(16, float(step), np.float32)},
                        opt_state={}, step=step, epoch=0,
                    ),
                    retry=dur.NO_WAIT,
                )
            writes = fs.specs[0].count
            if writes <= 4:  # 2 commits * 2 PUTs + at least one retry
                print(f"selftest-faults FAIL: no injected write observed "
                      f"({writes} writes)")
                rc = 1
            with open(f"{d}/snap.msgpack.step-00000002", "r+b") as f:
                f.truncate(32)  # tear the latest checkpoint
            snap = ckpt.load_snapshot(path, like, {}, retry=dur.NO_WAIT)
            if snap is None or snap.step != 1:
                print(f"selftest-faults FAIL: expected fallback to step 1, "
                      f"got {None if snap is None else snap.step}")
                rc = 1
            elif not np.array_equal(snap.params["w"],
                                    np.full(16, 1.0, np.float32)):
                print("selftest-faults FAIL: fallback params corrupt")
                rc = 1
        finally:
            fs.clear_faults()
    print("selftest-faults", "PASSED" if rc == 0 else "FAILED")
    return rc


def selftest_zero() -> int:
    """ZeRO weight-update-sharding parity gate (ISSUE 9): on a dp=2
    host-platform mesh, the sharded update (reduce-scatter grads ->
    local 1/dp clip/Adam/decay/lr -> allgather params) must match the
    replicated baseline's losses and parameters within fp32 tolerance,
    at grad_accum=1 AND grad_accum=2, and the optimizer moments must be
    physically ~1/dp per device.

    Hermetic by construction (the dryrun_multichip recipe): the work runs
    in a subprocess whose env forces ``JAX_PLATFORMS=cpu`` with 8 virtual
    host devices. This parent has imported JAX but never initialised a
    backend, so it holds no device the child could want."""
    import os
    import subprocess

    if os.environ.get("_MINGPT_SELFTEST_ZERO_INNER") != "1":
        env = dict(os.environ)
        env["_MINGPT_SELFTEST_ZERO_INNER"] = "1"
        env["JAX_PLATFORMS"] = "cpu"
        flags = [
            f for f in env.get("XLA_FLAGS", "").split()
            if "xla_force_host_platform_device_count" not in f
        ]
        flags.append("--xla_force_host_platform_device_count=8")
        env["XLA_FLAGS"] = " ".join(flags)
        here = os.path.dirname(os.path.abspath(__file__))
        return subprocess.run(
            [sys.executable, os.path.join(here, "train.py"),
             "--selftest-zero"],
            env=env, cwd=here,
        ).returncode

    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from mingpt_distributed_tpu.config import (
        GPTConfig, MeshConfig, OptimizerConfig,
    )
    from mingpt_distributed_tpu.models import gpt
    from mingpt_distributed_tpu.parallel import mesh as mesh_lib
    from mingpt_distributed_tpu.parallel import zero as zero_lib
    from mingpt_distributed_tpu.training.optimizer import (
        lr_schedule, make_optimizer,
    )
    from mingpt_distributed_tpu.training.trainer import (
        make_train_step, state_shardings,
    )

    rc = 0
    cfg = GPTConfig.make(
        n_layer=2, n_head=4, n_embd=64, vocab_size=256, block_size=32,
        embd_pdrop=0.0, resid_pdrop=0.0, attn_pdrop=0.0, dtype="float32",
    )
    opt_cfg = OptimizerConfig()
    optimizer = make_optimizer(
        opt_cfg, grad_norm_clip=1.0, schedule=lr_schedule(opt_cfg)
    )
    mesh = mesh_lib.make_mesh(MeshConfig(dp=2), devices=jax.devices()[:2])
    batch_sharding = mesh_lib.batch_sharding(mesh)
    repl = NamedSharding(mesh, P())

    params_shape = jax.eval_shape(lambda: gpt.init(jax.random.key(0), cfg))
    plan = zero_lib.make_plan(mesh, params_shape)

    rng = np.random.default_rng(0)
    steps = 4
    batches = [
        (
            rng.integers(0, 256, (8, 32), dtype=np.int32),
            rng.integers(0, 256, (8, 32), dtype=np.int32),
        )
        for _ in range(steps)
    ]

    def run(zero_plan, grad_accum):
        def init_state():
            params = gpt.init(jax.random.key(0), cfg)
            if zero_plan is not None:
                opt_state = optimizer.init(
                    zero_lib.update_view(params, zero_plan)
                )
            else:
                opt_state = optimizer.init(params)
            return {
                "params": params, "opt_state": opt_state,
                "step": jax.numpy.asarray(0, dtype=jax.numpy.int32),
            }

        shardings = state_shardings(
            mesh, jax.eval_shape(init_state), zero_plan=zero_plan
        )
        state = jax.jit(init_state, out_shardings=shardings)()
        step_fn = jax.jit(
            make_train_step(cfg, optimizer, mesh, grad_accum=grad_accum,
                            zero_plan=zero_plan),
            in_shardings=(shardings, (batch_sharding,) * 2, repl),
            out_shardings=(shardings, repl),
        )
        losses, update_norms = [], []
        for x, y in batches:
            xb = jax.device_put(x, batch_sharding)
            yb = jax.device_put(y, batch_sharding)
            state, m = step_fn(state, (xb, yb), jax.random.key(0))
            losses.append(float(jax.device_get(m["loss"])))
            update_norms.append(float(jax.device_get(m["update_norm"])))
        return state, losses, update_norms

    for ga in (1, 2):
        base_state, base_losses, base_un = run(None, ga)
        zero_state, zero_losses, zero_un = run(plan, ga)
        if not np.allclose(base_losses, zero_losses, rtol=2e-4, atol=2e-4):
            print(f"selftest-zero FAIL: grad_accum={ga} loss mismatch "
                  f"base={base_losses} zero={zero_losses}")
            rc = 1
        if not all(np.isfinite(v) and v > 0 for v in zero_un):
            print(f"selftest-zero FAIL: bad update_norm {zero_un}")
            rc = 1
        base_params = jax.device_get(base_state["params"])
        zero_params = jax.device_get(zero_state["params"])
        mismatched = []

        def cmp(path, a, b):
            if not np.allclose(a, b, rtol=2e-4, atol=2e-4):
                mismatched.append(jax.tree_util.keystr(path))
            return None

        jax.tree_util.tree_map_with_path(cmp, base_params, zero_params)
        if mismatched:
            print(f"selftest-zero FAIL: grad_accum={ga} param mismatch "
                  f"after {steps} steps: {mismatched}")
            rc = 1
        if ga == 1:
            base_bytes = zero_lib.per_device_bytes(base_state["opt_state"])
            zero_bytes = zero_lib.per_device_bytes(zero_state["opt_state"])
            ratio = zero_bytes / max(base_bytes, 1)
            print(f"selftest-zero: opt_state bytes/device "
                  f"{base_bytes} -> {zero_bytes} (ratio {ratio:.3f}, dp=2)")
            if ratio > 0.7:
                print(f"selftest-zero FAIL: opt state not sharded "
                      f"(ratio {ratio:.3f} > 0.7 at dp=2)")
                rc = 1
    print("selftest-zero", "PASSED" if rc == 0 else "FAILED")
    return rc


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument(
        "--config", default="gpt2_config.yaml", help="YAML config file"
    )
    parser.add_argument(
        "--selftest-faults", action="store_true",
        help="fault-injected checkpoint save/restore smoke; no config "
             "or dataset needed",
    )
    parser.add_argument(
        "--selftest-zero", action="store_true",
        help="ZeRO dp update-sharding parity + memory smoke on a "
             "host-platform dp=2 mesh; no config or dataset needed",
    )
    parser.add_argument(
        "overrides", nargs="*", help="dotted overrides: section.key=value"
    )
    args = parser.parse_args(argv)
    if args.selftest_faults:
        return selftest_faults()
    if args.selftest_zero:
        return selftest_zero()

    from mingpt_distributed_tpu.parallel import distributed
    from mingpt_distributed_tpu.utils import startup

    startup.enable_compile_cache()
    distributed.initialize()  # init_process_group analogue (no-op single host)
    if jax.process_index() == 0:
        print(startup.device_line())

    from mingpt_distributed_tpu.config import load_config
    from mingpt_distributed_tpu.data.token_dataset import make_dataset
    from mingpt_distributed_tpu.training.trainer import GPTTrainer

    cfg = load_config(args.config, args.overrides)

    # get_resources (reference train.py:11-27): dataset -> split -> override
    # model vocab/block from the data -> trainer owns model+optimizer configs.
    # make_dataset dispatches on data_config.tokenizer: char (reference
    # semantics) or bpe (the upstream bpe.py capability, README.md:10-15).
    dataset = make_dataset(cfg.data_config)
    train_view, test_view = dataset.split()
    gpt_cfg = dataclasses.replace(
        cfg.gpt_config,
        vocab_size=dataset.vocab_size,
        block_size=dataset.block_size,
    )
    if jax.process_index() == 0:
        unit = "tokens" if cfg.data_config.tokenizer == "bpe" else "chars"
        print(
            f"data: {len(dataset.data)} {unit}, vocab {dataset.vocab_size}, "
            f"{len(train_view)} train / {len(test_view)} test windows"
        )

    trainer = GPTTrainer(
        cfg.trainer_config,
        gpt_cfg,
        cfg.optimizer_config,
        train_view,
        test_view,
        experiment_config=cfg,
    )
    try:
        trainer.train()
    finally:
        trainer.close()  # metric sinks, span JSONL, /metrics endpoint
        distributed.shutdown()  # destroy_process_group analogue
    if trainer.preempted:
        # stopped on SIGTERM/SIGINT with a committed snapshot: tell the
        # scheduler to requeue us; the restarted run resumes at this step
        from mingpt_distributed_tpu.training.trainer import REQUEUE_EXIT_CODE

        if jax.process_index() == 0:
            print(
                f"preempted at step {trainer.step}; snapshot committed — "
                f"exiting {REQUEUE_EXIT_CODE} for requeue"
            )
        return REQUEUE_EXIT_CODE
    return 0


if __name__ == "__main__":
    sys.exit(main())
