"""The training loop — SPMD re-design of the reference's GPTTrainer
(/root/reference/mingpt/trainer.py:40-183).

What the reference does per batch — H2D copy, forward, backward (DDP
all-reduce), clip, step, then a blocking ``loss.item()`` D2H sync
(trainer.py:118-133, SURVEY §3.1's hot loop) — compiles here into ONE XLA
program: ``train_step`` = forward + backward + psum(grads over the batch axes)
+ clip + AdamW update, jitted with donated state, so the chip never waits on
the host inside the loop and metrics are fetched only every ``log_every``
steps (the per-batch sync is SURVEY §3.1's flagged throughput bug — not
reproduced).

Parallelism is carried by NamedShardings on the state/batch pytrees
(parallel/mesh.py): dp/fsdp shard the batch (gradient all-reduce appears as
XLA collectives exactly where DDP's bucketed NCCL all-reduce sat), fsdp/tp
additionally shard params — the DDP wrap at trainer.py:71 has no analogue
because the *data layout* is the parallelism.

Kept reference semantics: construction order load-snapshot-then-wrap
(trainer.py:66-71 — here: restore before device placement), epoch loop with
eval pass (trainer.py:169-183), save cadence every ``save_every`` epochs,
missing snapshot => fresh start. Fixed: single global writer (B9),
step-granular resume (data iterator + RNG in the snapshot), reduced loss in
logs (B11).
"""

from __future__ import annotations

import dataclasses
import json
import os
import signal
import threading
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import NamedSharding, PartitionSpec as P

from mingpt_distributed_tpu.config import (
    ConfigError,
    ExperimentConfig,
    GPTConfig,
    OptimizerConfig,
    TrainerConfig,
)
from mingpt_distributed_tpu.data.char_dataset import (
    CharView,
    IteratorState,
    ShardedBatchIterator,
)
from mingpt_distributed_tpu.models import gpt
from mingpt_distributed_tpu.parallel import mesh as mesh_lib
from mingpt_distributed_tpu.parallel import zero as zero_lib
from mingpt_distributed_tpu.training import checkpoint as ckpt_lib
from mingpt_distributed_tpu.training.durability import RetryPolicy
from mingpt_distributed_tpu.training.metrics import MetricsLogger
from mingpt_distributed_tpu.training.optimizer import lr_schedule, make_optimizer
from mingpt_distributed_tpu.telemetry import (
    SpanTracer,
    TelemetryServer,
    log_event,
)
from mingpt_distributed_tpu.telemetry import programs as program_lib

TrainState = Dict[str, Any]  # {"params", "opt_state", "step"}

# Exit code train.py returns after a preemption-triggered stop+snapshot:
# EX_TEMPFAIL, the conventional "transient, requeue me" code — cluster
# schedulers and wrapper scripts can restart the job, which then resumes
# from the just-committed snapshot.
REQUEUE_EXIT_CODE = 75

# canonical implementation lives with the other sharding rules
state_shardings = mesh_lib.state_shardings


def make_train_step(
    cfg: GPTConfig,
    optimizer: optax.GradientTransformation,
    mesh=None,
    grad_accum: int = 1,
    lr_fn=None,  # step -> learning rate, for the metrics line (SURVEY §5.5)
    zero_plan=None,  # parallel/zero.py ZeroPlan: dp-sharded weight update
):
    """forward+backward+update as one pure function of (state, batch, rng).

    ``grad_accum > 1`` splits the step's batch into that many micro-batches
    and accumulates gradients over a ``lax.scan`` before the single
    optimizer update — activation memory scales with B/grad_accum while the
    effective batch (and the loss/update semantics) stay the whole B.
    Micro-batch losses/grads are averaged with equal weight (the standard
    mean-of-means convention; exact whenever ignore_index masking is evenly
    distributed, and exactly equal to grad_accum=1 when no -1 targets).

    With a ``zero_plan`` the update phase runs ZeRO weight-update sharding
    (arXiv 2004.13336): grads are reduce-scattered over dp (the sharding
    constraint on the grads' update view turns the dp all-reduce into
    all-reduce+shard, which GSPMD fuses), clip/Adam/decay/lr run on the
    local 1/dp shard only, and the updated params are allgathered back to
    their canonical sharding by the output constraint. Composes with
    ``grad_accum`` unchanged — accumulation happens before the sharded
    update phase.

    ``attention="flash"`` is a promise that the step runs the Pallas
    kernel. The op itself routes calls it cannot tile, and calls under
    attention dropout, to the einsum oracle (its contract for decode and
    odd shapes) — so a training config that can only ever get the oracle
    is refused here, by name, instead of training slowly under the wrong
    label: attention dropout at build time, an untileable T at trace time.
    """
    if cfg.attention == "flash" and cfg.attn_pdrop > 0.0:
        raise ConfigError(
            f"gpt_config.attention=flash with attn_pdrop={cfg.attn_pdrop}: "
            "the Pallas kernel has no attention dropout, so every training "
            "step would run the einsum oracle instead. Set "
            "gpt_config.attn_pdrop=0.0 (embd_pdrop and resid_pdrop do not "
            "gate the kernel) or gpt_config.attention=einsum."
        )

    def loss_and_grads(params, x, y, rng, deterministic):
        def loss_fn(p):
            _, loss = gpt.forward(
                p, x, cfg, targets=y,
                rng=None if deterministic else rng,
                deterministic=deterministic,
                mesh=mesh,
                return_logits=False,  # loss-only: enables the chunked head
            )
            return loss

        return jax.value_and_grad(loss_fn)(params)

    def train_step(state: TrainState, batch, base_rng):
        x, y = batch
        if cfg.attention == "flash":
            from mingpt_distributed_tpu.ops import flash_attention

            if flash_attention.supported_block(x.shape[1]) is None:
                raise ConfigError(
                    f"gpt_config.attention=flash with sequence length "
                    f"T={x.shape[1]}: the Pallas kernel needs T to be a "
                    "multiple of 128, or at most 128 and a multiple of 8, "
                    "so every training step would run the einsum oracle "
                    "instead. Pick such a data_config.block_size or "
                    "gpt_config.attention=einsum."
                )
        rng = jax.random.fold_in(base_rng, state["step"])
        deterministic = (
            cfg.embd_pdrop == 0.0 and cfg.resid_pdrop == 0.0 and cfg.attn_pdrop == 0.0
        )

        if grad_accum > 1:
            b = x.shape[0]
            if b % grad_accum:
                raise ValueError(
                    f"batch {b} not divisible by grad_accum={grad_accum}"
                )
            xs = x.reshape(grad_accum, b // grad_accum, *x.shape[1:])
            ys = y.reshape(grad_accum, b // grad_accum, *y.shape[1:])

            def acc(carry, mb):
                loss_sum, g_sum, i = carry
                x_mb, y_mb = mb
                mb_rng = jax.random.fold_in(rng, i)
                loss_i, g_i = loss_and_grads(
                    state["params"], x_mb, y_mb, mb_rng, deterministic
                )
                g_sum = jax.tree.map(
                    lambda a, bb: a + bb.astype(jnp.float32), g_sum, g_i
                )
                return (loss_sum + loss_i, g_sum, i + 1), None

            g0 = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), state["params"]
            )
            (loss_sum, g_sum, _), _ = jax.lax.scan(
                acc,
                (jnp.zeros((), jnp.float32), g0, jnp.asarray(0, jnp.int32)),
                (xs, ys),
            )
            loss = loss_sum / grad_accum
            grads = jax.tree.map(lambda g: g / grad_accum, g_sum)
        else:
            loss, grads = loss_and_grads(
                state["params"], x, y, rng, deterministic
            )

        with jax.named_scope("optimizer"):
            if zero_plan is not None:
                # ZeRO update phase: shard grads+params into the update view,
                # step the optimizer on the local 1/dp shard, gather back.
                gview = zero_lib.constrain(
                    zero_lib.update_view(grads, zero_plan), zero_plan
                )
                pview = zero_lib.constrain(
                    zero_lib.update_view(state["params"], zero_plan), zero_plan
                )
                updates, new_opt = optimizer.update(
                    gview, state["opt_state"], pview
                )
                # allgather happens here: from_view restores canonical shapes
                # and the step's out_shardings pin the canonical param layout
                new_params = zero_lib.from_view(
                    optax.apply_updates(pview, updates), zero_plan
                )
            else:
                updates, new_opt = optimizer.update(
                    grads, state["opt_state"], state["params"]
                )
                new_params = optax.apply_updates(state["params"], updates)
        metrics = {
            "loss": loss,
            # pre-clip gradient norm (global: GSPMD psums sharded leaves)
            "grad_norm": optax.global_norm(grads),
            # post-clip/applied update norm — grad_norm alone can't show
            # whether clipping actually bit (flat-mode pad slots are zero
            # and contribute nothing)
            "update_norm": optax.global_norm(updates),
        }
        if lr_fn is not None:
            metrics["lr"] = lr_fn(state["step"])
        return (
            {"params": new_params, "opt_state": new_opt, "step": state["step"] + 1},
            metrics,
        )

    return train_step


def make_eval_step(cfg: GPTConfig, mesh=None):
    def eval_step(state: TrainState, batch):
        x, y = batch
        _, loss = gpt.forward(
            state["params"], x, cfg, targets=y, mesh=mesh,
            return_logits=False,
        )
        return loss

    return eval_step


class GPTTrainer:
    """Drives training of a GPT over a device mesh.

    Mirrors the reference constructor contract
    GPTTrainer(config, model, optimizer, train_dataset, test_dataset)
    (trainer.py:46-52) with the model/optimizer passed as *configs* — the
    model is data (a pytree), so the trainer owns materialisation, placement
    and restore.
    """

    def __init__(
        self,
        config: TrainerConfig,
        gpt_config: GPTConfig,
        optimizer_config: OptimizerConfig,
        train_dataset: CharView,
        test_dataset: Optional[CharView] = None,
        mesh=None,
        experiment_config: Optional[ExperimentConfig] = None,
    ):
        self.config = config
        self.gpt_config = gpt_config
        if config.debug_nans:
            jax.config.update("jax_debug_nans", True)
        self.mesh = mesh if mesh is not None else mesh_lib.make_mesh(config.mesh)
        self.process_index = jax.process_index()
        self.process_count = jax.process_count()
        self.is_writer = self.process_index == 0  # B9 fix: GLOBAL process 0
        self.experiment_config = experiment_config

        # --- telemetry (ISSUE 5): spans + optional /metrics endpoint ------
        # Tracer enabled on the writer only (single-writer convention, same
        # as MetricsLogger); spans cover step dispatch, eval and snapshots.
        self.tracer = SpanTracer(enabled=self.is_writer)
        if config.spans_jsonl and self.is_writer:
            self.tracer.attach_jsonl(config.spans_jsonl)
        self.telemetry_server: Optional[TelemetryServer] = None
        metrics_registry = None
        if config.metrics_port and self.is_writer:
            from mingpt_distributed_tpu import telemetry

            metrics_registry = telemetry.get_registry()
            self.telemetry_server = TelemetryServer(
                metrics_registry, port=config.metrics_port
            )
            log_event(
                f"telemetry: serving /metrics and /healthz on "
                f"{self.telemetry_server.url()}",
                tracer=self.tracer,
            )

        batch_ways = int(
            np.prod([self.mesh.shape[a] for a in mesh_lib.BATCH_AXES])
        )
        if config.batch_size % batch_ways != 0:
            axes = "*".join(mesh_lib.BATCH_AXES)
            raise ValueError(
                f"trainer_config.batch_size={config.batch_size} must be "
                f"divisible by {axes}={batch_ways} (mesh "
                f"{dict(self.mesh.shape)})"
            )

        # ONE schedule object feeds both the optax chain and the metrics
        # line, so the logged lr is the applied lr by construction
        self._lr_fn = lr_schedule(optimizer_config)
        self.optimizer = make_optimizer(
            optimizer_config, config.grad_norm_clip, schedule=self._lr_fn
        )
        # How the global batch's ROWS split across processes is a property
        # of the batch SHARDING, not always of process_count: when a
        # non-batch axis spans hosts (e.g. sequence parallelism over DCN,
        # mesh sp across processes) every process addresses all rows and
        # must feed the full batch.
        self._feed_count, self._feed_index = self._data_feed_shards(
            config.batch_size, train_dataset.block_size
        )
        self.train_iter = ShardedBatchIterator(
            train_dataset,
            config.batch_size,
            shuffle=True,
            seed=config.seed,
            process_index=self._feed_index,
            process_count=self._feed_count,
        )
        self.test_iter = (
            ShardedBatchIterator(
                test_dataset,
                config.batch_size,
                shuffle=False,
                seed=config.seed,
                process_index=self._feed_index,
                process_count=self._feed_count,
            )
            if test_dataset is not None and len(test_dataset) >= config.batch_size
            else None
        )

        self.snapshot_path = config.snapshot_path or ckpt_lib.DEFAULT_SNAPSHOT_PATH
        # backend: .msgpack = single-blob (reference contract, host gather);
        # anything else = Orbax directory (sharded, collective, no gather)
        self.ckpt_backend = (
            "msgpack" if self.snapshot_path.endswith(".msgpack") else "orbax"
        )
        # durability: transient-I/O retry policy shared by save and load
        # (jitter seeded from config.seed for reproducible schedules)
        self._retry = RetryPolicy(
            attempts=config.io_retries,
            base_delay_s=config.io_retry_delay_s,
            seed=config.seed,
        )
        # preemption state: the SIGTERM/SIGINT handler flips
        # _stop_requested; the step loop honours it at the next boundary
        self._stop_requested = False
        self._stop_signal: Optional[int] = None
        self.preempted = False
        if config.async_save and self.ckpt_backend == "orbax":
            # refuse rather than silently run sync (VERDICT r4 #6): the
            # user asked for overlap they would not be getting
            raise ConfigError(
                "async_save=True only applies to the msgpack backend; Orbax "
                "sharded saves run synchronously (collective write). Set "
                "async_save=False, or use a .msgpack snapshot_path."
            )
        # --- ZeRO weight-update sharding over dp (opt-in, ISSUE 9) --------
        # The plan is static per (mesh, model): dp<=1 means the view would
        # be the identity, so the plan stays None and the step compiles the
        # exact replicated baseline program.
        self.zero_plan = None
        if config.zero_dp:
            if self.ckpt_backend == "orbax":
                # refuse rather than save the dp-local update view: the
                # Orbax backend writes device shards as-is, so a zero_dp
                # checkpoint would bake in this run's dp extent (and flat
                # padding) instead of the canonical resharding layout.
                raise ConfigError(
                    "zero_dp=True requires the msgpack backend (a "
                    ".msgpack snapshot_path): its save path canonicalises "
                    "the dp-sharded optimizer state so checkpoints restore "
                    "at any dp extent. Orbax would persist the view layout."
                )
            if int(self.mesh.shape["dp"]) > 1:
                params_shape = jax.eval_shape(
                    lambda: gpt.init(jax.random.key(config.seed), gpt_config)
                )
                self.zero_plan = zero_lib.make_plan(self.mesh, params_shape)
        self.base_rng = jax.random.key(config.seed)

        # --- abstract state + shardings, then materialise on-mesh ---------
        init_fn = lambda: self._fresh_state(jax.random.key(config.seed))
        state_shape = jax.eval_shape(init_fn)
        self.shardings = state_shardings(
            self.mesh, state_shape, zero_plan=self.zero_plan
        )
        self.batch_sharding = mesh_lib.batch_sharding(self.mesh)
        self.repl = NamedSharding(self.mesh, P())

        if self.ckpt_backend == "orbax":
            from mingpt_distributed_tpu.training import checkpoint_orbax

            restored = checkpoint_orbax.load_snapshot(
                self.snapshot_path,
                state_shape["params"],
                state_shape["opt_state"],
                shardings=self.shardings,
                retry=self._retry,
            )
        else:
            # Checkpoints store the opt state in CANONICAL layout (original
            # leaf shapes, no dp padding) regardless of zero_dp — restore
            # into the canonical skeleton, then re-view for THIS mesh's
            # plan. That is the whole reshard-on-restore mechanism: a
            # snapshot written at dp=4 localises cleanly at dp=2 or dp=1.
            opt_like = state_shape["opt_state"]
            if self.zero_plan is not None:
                opt_like = zero_lib.canonical_opt_shape(
                    opt_like, self.zero_plan
                )
            restored = ckpt_lib.load_snapshot(
                self.snapshot_path,
                state_shape["params"],
                opt_like,
                retry=self._retry,
            )
            if restored is not None and self.zero_plan is not None:
                restored = dataclasses.replace(
                    restored,
                    opt_state=zero_lib.localize_opt_state(
                        restored.opt_state, self.zero_plan
                    ),
                )
        if restored is None:
            if self.is_writer:
                log_event("Snapshot not found. Training model from scratch",
                          tracer=self.tracer)
            self.state = jax.jit(init_fn, out_shardings=self.shardings)()
            self.start_epoch = 0
        else:
            host_state = {
                "params": restored.params,
                "opt_state": restored.opt_state,
                "step": jnp.asarray(restored.step, dtype=jnp.int32),
            }
            placed = jax.tree.map(
                lambda x, s: (
                    x  # orbax restores already placed with the right sharding
                    if getattr(x, "sharding", None) == s
                    else jax.make_array_from_callback(
                        np.shape(x), s, lambda idx: np.asarray(x)[idx]
                    )
                ),
                host_state,
                self.shardings,
            )
            # Launder the restored buffers through one compiled (undonated)
            # copy so the donated train step only ever sees executable-owned
            # buffers: donating externally-created arrays into an executable
            # deserialised from the persistent compilation cache corrupts
            # the heap on the CPU backend (resume-then-train segfault; the
            # fresh-init path was immune because jit(init_fn) outputs are
            # executable-owned).
            self.state = jax.jit(
                lambda s: jax.tree.map(jnp.copy, s),
                out_shardings=self.shardings,
            )(placed)
            self.start_epoch = restored.epoch
            self.train_iter.state = IteratorState.from_dict(
                restored.data_state
            ) if restored.data_state else self.train_iter.state
            if restored.prng is not None:
                # continue the saved RNG stream, not config.seed's
                self.base_rng = jax.random.wrap_key_data(
                    jnp.asarray(restored.prng)
                )
            if self.is_writer:
                log_event(
                    f"Resuming training from snapshot at epoch "
                    f"{restored.epoch}, step {restored.step}",
                    tracer=self.tracer,
                    epoch=restored.epoch, step=restored.step,
                )

        # --- compiled steps ----------------------------------------------
        self._train_step = jax.jit(
            make_train_step(gpt_config, self.optimizer, self.mesh,
                            grad_accum=config.grad_accum_steps,
                            lr_fn=self._lr_fn,
                            zero_plan=self.zero_plan),
            in_shardings=(self.shardings, (self.batch_sharding,) * 2, self.repl),
            out_shardings=(self.shardings, self.repl),
            donate_argnums=(0,),
        )
        # the step files itself at its first call, whoever makes it
        # (telemetry/programs.py): a caller that drives it and drops the
        # trainer leaves the step's table behind for whoever holds a profile
        # (wrapped in a statement of its own: graftlint's donation rule
        # reads the ``jax.jit(.., donate_argnums=..)`` assignment above)
        self._train_step = program_lib.filing(
            self._train_step, "train_step",
            "zero" if self.zero_plan is not None else "dense")
        self._eval_step = jax.jit(
            make_eval_step(gpt_config, self.mesh),
            in_shardings=(self.shardings, (self.batch_sharding,) * 2),
            out_shardings=self.repl,
        )

        self.metrics = MetricsLogger(
            gpt_config,
            jsonl_path=config.metrics_jsonl if self.is_writer else None,
            tensorboard_dir=(
                config.tensorboard_dir if self.is_writer else None
            ),
            n_chips=len(jax.devices()),
            enabled=self.is_writer,
            registry=metrics_registry,
        )
        if self.is_writer:
            log_event(gpt.model_size_report(self.state["params"], gpt_config),
                      tracer=self.tracer)
        # which named scope each instruction of the step came from: made
        # when somebody first reads the tracer (or with ``spans_jsonl``,
        # now), and the one record the step's own filing keeps
        self.tracer.pin("program", lambda: [
            step.record(args, kwargs)
            for _, _, step, args, kwargs in self.programs()])

    # ------------------------------------------------------------------
    def _fresh_state(self, rng) -> TrainState:
        params = gpt.init(rng, self.gpt_config)
        if self.zero_plan is not None:
            # moments live in the update view (flat-mode leaves padded +
            # flattened) so they can be physically 1/dp under the plan's
            # shardings; Adam init on pad zeros is zeros, so the view is
            # exactly the localised canonical state
            opt_state = self.optimizer.init(
                zero_lib.update_view(params, self.zero_plan)
            )
        else:
            opt_state = self.optimizer.init(params)
        return {
            "params": params,
            "opt_state": opt_state,
            "step": jnp.asarray(0, dtype=jnp.int32),
        }

    # -- compiled programs, as data ------------------------------------
    def programs(self):
        """Yield ``(family, variant, jitted, args, kwargs)`` for the
        compiled train step, against abstract state/batch avals —
        donation binds at execution, not lowering, so lowering these
        consumes no live buffer. The state's avals carry the live arrays'
        shardings; the jit's own ``in_shardings`` already fix the layout,
        so the text is the program that runs either way. Family
        ``train_step``, variant ``zero`` (dp-sharded update, ISSUE 9) or
        ``dense``."""
        state_abs = program_lib.abstract(self.state)
        block = self.train_iter.view.block_size
        tok = jax.ShapeDtypeStruct(
            (self.config.batch_size, block), jnp.int32)
        rng_abs = jax.eval_shape(lambda: self.base_rng)
        yield (self._train_step.family, self._train_step.variant,
               self._train_step, (state_abs, (tok, tok), rng_abs), {})

    def audit_contracts(self) -> dict:
        """Audit contract (ISSUE 15) for the ``train_step`` family
        ``programs`` yields. On a one-device mesh the lowered
        step must contain no collectives at all; on a real mesh the data/
        tensor/zero parallel forms all appear (psum grads, zero's
        reduce-scatter + all-gather, megatron gathers), so every reduce-
        family op is declared. Donation is ``donate_argnums=(0,)`` over
        the whole train state: the executable must alias at least one
        output per params leaf (``donated_min`` — opt-state leaves alias
        too, but their count depends on the optimizer/zero layout, so the
        params floor is the invariant worth pinning)."""
        n_dev = int(np.prod(self.mesh.devices.shape))
        allowed = (() if n_dev == 1 else
                   ("all-gather", "all-reduce", "collective-permute",
                    "reduce-scatter"))
        return {
            "train_step": {
                "allowed_collectives": allowed,
                "donated_min": len(jax.tree.leaves(self.state["params"])),
            },
        }

    def _data_feed_shards(self, global_batch: int, seq_len: int):
        """(n_shards, my_shard) for host data feeding.

        Derived from ``batch_sharding``'s device->index map: the rows this
        process's local devices address. Pure dp/fsdp/ep over hosts gives
        the usual equal contiguous split; a mesh whose batch rows are NOT
        cleanly process-partitioned (sp spanning hosts, or exotic layouts)
        degrades to every host feeding the full batch, which
        make_array_from_process_local_data accepts (host data may match the
        global shape).
        """
        if self.process_count == 1:
            return 1, 0
        rows: set = set()
        m = mesh_lib.batch_sharding(self.mesh).devices_indices_map(
            (global_batch, seq_len)
        )
        for d, idx in m.items():
            if d.process_index == jax.process_index():
                rows.update(range(*idx[0].indices(global_batch)))
        my = sorted(rows)
        n_rows = len(my)
        contiguous = my == list(range(my[0], my[0] + n_rows))
        if (
            n_rows == global_batch
            or not contiguous
            or global_batch % n_rows
            or my[0] % n_rows
        ):
            return 1, 0  # feed the full batch on every host
        return global_batch // n_rows, my[0] // n_rows

    def _put_batch(self, xy: Tuple[np.ndarray, np.ndarray]):
        """Per-host local shard -> global device array under batch sharding."""
        x, y = xy
        gshape = (x.shape[0] * self._feed_count, x.shape[1])
        if self.process_count == 1:
            put = lambda a: jax.device_put(a, self.batch_sharding)
        else:
            put = lambda a: jax.make_array_from_process_local_data(
                self.batch_sharding, a, gshape
            )
        return put(x), put(y)

    @property
    def step(self) -> int:
        return int(jax.device_get(self.state["step"]))

    # -- preemption ----------------------------------------------------
    def request_stop(self, signum: Optional[int] = None) -> None:
        """Ask the loop to stop at the next step boundary (callable from a
        signal handler or programmatically). Idempotent."""
        self._stop_requested = True
        self._stop_signal = signum

    def _on_signal(self, signum, frame) -> None:
        if self._stop_requested and signum == signal.SIGINT:
            # second Ctrl-C: the user really means now
            raise KeyboardInterrupt
        name = signal.Signals(signum).name
        if self.is_writer:
            log_event(
                f"[trainer] {name} received — stopping at the next step "
                f"boundary, snapshotting, then exiting with code "
                f"{REQUEUE_EXIT_CODE} (requeue)",
                tracer=self.tracer, signal=name,
            )
        self.request_stop(signum)

    def _install_signal_handlers(self):
        """SIGTERM (the preemption notice TPU spot VMs deliver) and SIGINT
        request a graceful stop+snapshot. Returns the handlers to restore,
        or None when not applicable (off, or not the main thread —
        python only delivers signals to the main thread)."""
        if not self.config.handle_signals:
            return None
        if threading.current_thread() is not threading.main_thread():
            return None
        prev = {}
        for sig in (signal.SIGTERM, signal.SIGINT):
            prev[sig] = signal.signal(sig, self._on_signal)
        return prev

    # ------------------------------------------------------------------
    def train(self) -> Dict[str, Any]:
        """Epoch loop (reference train(), trainer.py:169-183): resume at
        start_epoch, train, periodic eval + snapshot. Returns final metrics.

        Preemption-safe: a SIGTERM/SIGINT during the loop stops at the
        next step boundary, snapshots, joins any async save, and sets
        ``self.preempted`` so the entry point can exit with
        REQUEUE_EXIT_CODE instead of losing the run.
        """
        prev_handlers = self._install_signal_handlers()
        try:
            return self._train_loop()
        finally:
            if prev_handlers is not None:
                for sig, h in prev_handlers.items():
                    signal.signal(sig, h)

    def _train_loop(self) -> Dict[str, Any]:
        cfg = self.config
        last: Dict[str, Any] = {}
        tokens_per_step = cfg.batch_size * self.train_iter.view.block_size
        stop = False
        # host-side step mirror: no per-batch D2H sync (the reference's
        # per-batch loss.item() stall, SURVEY §3.1, is what this avoids).
        # prev_metrics bounds the async pipeline to 2 in-flight steps: the
        # host waits on step N-1 while N executes — free on TPU (compute
        # overlaps), and it keeps per-device dispatch queues from skewing
        # past the collective-rendezvous timeout on oversubscribed hosts.
        py_step = self.step
        prev_metrics = None
        for epoch in range(self.start_epoch, cfg.max_epochs):
            # the prefetch thread advances the iterator's internal state
            # ahead of consumption; `consumed` is the truth for resume
            consumed = self.train_iter.state.step_in_epoch
            source = self.train_iter.epoch_batches()
            if cfg.prefetch > 0:
                from mingpt_distributed_tpu.data.prefetch import PrefetchIterator

                source = PrefetchIterator(source, depth=cfg.prefetch)
            for xy in source:
                batch = self._put_batch(xy)
                # the span measures host-visible step time: dispatch of step
                # N plus the wait on step N-1 (the two-in-flight cap below)
                with self.tracer.span("train.step", step=py_step + 1):
                    self.state, m = self._train_step(
                        self.state, batch, self.base_rng
                    )
                    if prev_metrics is not None:
                        jax.block_until_ready(prev_metrics)
                    prev_metrics = m
                py_step = step = py_step + 1
                consumed += 1
                # jax.profiler trace window (SURVEY §5.1: the reference has
                # no profiler at all; xplane output feeds Perfetto/XProf)
                if cfg.profile_dir and self.is_writer:
                    if step == cfg.profile_steps[0]:
                        jax.profiler.start_trace(cfg.profile_dir)
                        self._tracing = True
                    elif step == cfg.profile_steps[1] and getattr(
                        self, "_tracing", False
                    ):
                        jax.block_until_ready(m)
                        jax.profiler.stop_trace()
                        self._tracing = False
                        # the tables of the programs that ran, beside the
                        # trace: the profile can be summed by scope as it
                        # stands, with or without ``spans_jsonl``
                        with open(os.path.join(cfg.profile_dir,
                                               "programs.json"), "w") as f:
                            json.dump(program_lib.filed_records(), f)
                        log_event(
                            f"profiler trace written to {cfg.profile_dir}",
                            tracer=self.tracer, step=step,
                        )
                if step % cfg.log_every == 0 or (
                    cfg.max_steps and step >= cfg.max_steps
                ):
                    scalars = {k: float(jax.device_get(v)) for k, v in m.items()}
                    scalars["epoch"] = epoch
                    last = self.metrics.log_step(
                        step, tokens_per_step, self.train_iter.view.block_size,
                        scalars,
                    )
                if self._stop_requested:
                    # preemption: get off the chip at this step boundary —
                    # snapshot below, skip eval, requeue-friendly exit
                    self.preempted = True
                    stop = True
                if cfg.max_steps and step >= cfg.max_steps:
                    stop = True
                if stop:
                    break
            if stop:
                # stop the producer thread BEFORE touching iterator state:
                # it mutates train_iter.state ahead of consumption, and a
                # write landing after the re-sync below would persist a data
                # position beyond what was trained (resume would skip batches)
                if cfg.prefetch > 0:
                    source.close()
                # re-sync iterator state to the batches actually trained on
                # (prefetch ran ahead); resume continues at exactly here
                self.train_iter.state = IteratorState(
                    epoch=epoch, step_in_epoch=consumed,
                    seed=self.train_iter.state.seed,
                )
            epoch_done = epoch + (0 if stop else 1)
            if self.test_iter is not None and not self.preempted and (
                stop or (epoch + 1) % cfg.eval_every == 0
            ):
                last["eval_loss"] = self.evaluate()
                if self.is_writer:
                    log_event(
                        f"epoch {epoch} | eval_loss {last['eval_loss']:.4f}",
                        tracer=self.tracer, epoch=epoch,
                    )
            if stop or (epoch + 1) % cfg.save_every == 0:
                self.save_snapshot(epoch_done)
            if stop:
                break
        self._join_pending_save()  # async_save: flush before returning
        return last

    def _join_pending_save(self) -> None:
        """Wait for an in-flight async snapshot write; re-raise its failure
        (a swallowed write error would mean silently resuming from a stale
        checkpoint after the next restart)."""
        t = getattr(self, "_save_thread", None)
        if t is not None:
            t.join()
            self._save_thread = None
        exc = getattr(self, "_save_exc", None)
        if exc is not None:
            self._save_exc = None
            raise RuntimeError(
                f"async snapshot write to {self.snapshot_path} failed"
            ) from exc

    def evaluate(self) -> float:
        """Mean loss over the eval set.

        Losses stay on device; the loop only *blocks* on the step two
        iterations back (the train loop's two-in-flight cap) instead of
        fetching every batch — on a pod a per-batch device_get costs a full
        host round-trip per batch and stalls the dispatch pipeline
        (VERDICT r2 weak #7). Values are fetched once at the end.
        """
        assert self.test_iter is not None
        losses = []
        self.test_iter.state = IteratorState(seed=self.config.seed)
        with self.tracer.span("train.eval"):
            for i, xy in enumerate(self.test_iter.epoch_batches()):
                if self.config.eval_batches and i >= self.config.eval_batches:
                    break
                losses.append(self._eval_step(self.state, self._put_batch(xy)))
                if len(losses) >= 2:
                    jax.block_until_ready(losses[-2])
            return float(np.mean([float(v) for v in jax.device_get(losses)]))

    def save_snapshot(self, epoch: int) -> None:
        """Single-writer (global process 0 — the B9 fix) snapshot.

        ALL processes must call this (it is called from train() on every
        process): with fsdp/tp sharding some shards live on other hosts, so
        the state is first gathered to every host with a collective
        (process_allgather); only process 0 then writes.
        """
        with self.tracer.span("train.snapshot", epoch=epoch):
            self._save_snapshot(epoch)

    def _save_snapshot(self, epoch: int) -> None:
        common = dict(
            step=self.step,
            epoch=epoch,
            prng=np.asarray(jax.random.key_data(self.base_rng)),
            data_state=self.train_iter.state.to_dict(),
            config=(
                self.experiment_config.to_dict() if self.experiment_config else {}
            ),
        )
        if self.ckpt_backend == "orbax":
            # collective sharded save: every process writes its shards
            from mingpt_distributed_tpu.training import checkpoint_orbax

            checkpoint_orbax.save_snapshot(
                self.snapshot_path,
                ckpt_lib.Snapshot(
                    params=self.state["params"],
                    opt_state=self.state["opt_state"],
                    **common,
                ),
                retry=self._retry,
            )
        else:
            if self.process_count > 1:
                # refuse the doomed gather: allgathering a pod-scale state
                # to every host OOMs long after the run invested hours —
                # fail at save time with the fix in hand (VERDICT r4 #6)
                state_mb = sum(
                    x.size * x.dtype.itemsize
                    for x in jax.tree.leaves(
                        {"params": self.state["params"],
                         "opt_state": self.state["opt_state"]}
                    )
                ) / 2**20
                limit_mb = self.config.msgpack_gather_limit_mb
                if state_mb > limit_mb:
                    raise RuntimeError(
                        f"multi-host msgpack save would allgather "
                        f"{state_mb:.0f} MB of state to every host "
                        f"(limit {limit_mb} MB). Use the Orbax backend — a "
                        f"snapshot_path without the .msgpack suffix — for "
                        f"sharded collective writes with no gather, or "
                        f"raise trainer_config.msgpack_gather_limit_mb if "
                        f"your hosts have the RAM."
                    )
                from jax.experimental import multihost_utils

                params = multihost_utils.process_allgather(
                    self.state["params"], tiled=True
                )
                opt_state = multihost_utils.process_allgather(
                    self.state["opt_state"], tiled=True
                )
            else:
                params = self.state["params"]
                opt_state = self.state["opt_state"]
            if self.zero_plan is not None:
                # snapshots always store the CANONICAL layout (original
                # shapes, no dp padding) so they restore at any dp extent
                opt_state = zero_lib.canonical_opt_state(
                    jax.device_get(opt_state), self.zero_plan
                )
            # shard the checkpoint data objects with the update shards:
            # per-shard writes/digests keep save cost ~per-host-state
            n_shards = self.zero_plan.dp if self.zero_plan is not None else 1
            if not self.is_writer:
                return
            if self.config.async_save:
                # overlap serialization + IO (the slow part for object
                # stores) with training. The host copy happens HERE, before
                # the thread starts: the device buffers are donated to the
                # next step and would be invalidated under the writer.
                host_snap = ckpt_lib.Snapshot(
                    params=jax.device_get(params),
                    opt_state=jax.device_get(opt_state),
                    **common,
                )
                self._join_pending_save()  # re-raises a prior failed write
                import threading

                path, step = self.snapshot_path, self.step
                keep, retry = self.config.keep_snapshots, self._retry

                def _write():
                    try:
                        ckpt_lib.save_snapshot(
                            path, host_snap, keep=keep, retry=retry,
                            shards=n_shards,
                        )
                        log_event(
                            f"Snapshot saved to {path} "
                            f"(epoch {epoch}, step {step}, msgpack, async)",
                            tracer=self.tracer, epoch=epoch, step=step,
                        )
                    except BaseException as e:  # re-raised at next join
                        self._save_exc = e

                self._save_thread = threading.Thread(target=_write)
                self._save_thread.start()
                return
            else:
                ckpt_lib.save_snapshot(
                    self.snapshot_path,
                    ckpt_lib.Snapshot(
                        params=params, opt_state=opt_state, **common
                    ),
                    keep=self.config.keep_snapshots,
                    retry=self._retry,
                    shards=n_shards,
                )
        if self.is_writer:
            log_event(
                f"Snapshot saved to {self.snapshot_path} "
                f"(epoch {epoch}, step {self.step}, {self.ckpt_backend})",
                tracer=self.tracer, epoch=epoch, step=self.step,
            )

    def close(self) -> None:
        """Release telemetry resources: metric sinks, the span JSONL, and
        the /metrics endpoint (idempotent)."""
        self.metrics.close()
        self.tracer.close()
        if self.telemetry_server is not None:
            self.telemetry_server.close()
            self.telemetry_server = None
