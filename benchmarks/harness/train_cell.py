"""A training cell: ``GPTTrainer`` built as ``train.py`` builds it, driven
by the benchmark's own timed loop.

The loop is the trainer's (``training/trainer.py`` ``_train_loop``): the
prefetching iterator, ``_put_batch``, the jitted donated step, two steps in
flight. It is driven from here because the trainer's own loop ends in an
eval pass and a snapshot of the whole state, which no run may pay, and
because the window has to end on a step boundary that the benchmark times.

Time is the host clock between two ``block_until_ready``: the window opens
when every warm-up step has finished and closes when the last step
dispatched inside it has. Nothing compiles inside it.

``train_tok_s_chip`` is the tokens of a step over the window's median step
time, not the window's tokens over its length. With a step queued behind the
one that runs, the host has a whole step of slack, and the device's steps
repeat to 0.01%; but the driver's first check (PR 22) read runs 0.6% under
the rest, which only a host held for longer than a step (half a second) can
do to this loop. One such stall moves the window's mean by its whole length
and the median not at all. The price: a stall the program itself caused in
fewer than half the steps would not show either; the mean stands beside it
in the notes (``window_tok_s_chip``) for a reader to compare.
"""

from __future__ import annotations

import os
import statistics
import time
from typing import Dict, List

import numpy as np

from benchmarks.harness import check, flops, spec, tokens, trace


def build_trainer(cell: spec.Cell, seed: int, devices: List):
    """Dataset from the seed, configs from the cell's files, then the
    program's own constructor; no snapshot exists to restore. The constructor
    bakes its seed into the program that makes the weights, so a new seed
    would be a new program and a compile in every run's set-up: it is built
    with seed 0, and weights, dropout stream and data order are then set from
    ``--seed``, the weights in one jitted call of the trainer's own
    ``_fresh_state`` that takes the key as an argument."""
    import jax
    from mingpt_distributed_tpu.config import (
        MeshConfig, OptimizerConfig, TrainerConfig)
    from mingpt_distributed_tpu.data.char_dataset import CharView, IteratorState
    from mingpt_distributed_tpu.parallel import mesh as mesh_lib
    from mingpt_distributed_tpu.training.trainer import GPTTrainer

    mix, found = cell.mix, cell.found
    gpt_cfg = spec.gpt_config(cell, training=True)
    if int(mix["seq_len"]) != gpt_cfg.block_size:
        raise spec.SpecError("the mix trains at the model's own context")
    stream = tokens.TokenStream(
        tokens.token_stream(seed, int(mix["stream_tokens"]),
                            gpt_cfg.vocab_size, mix["zipf_exponent"]),
        block_size=gpt_cfg.block_size, vocab_size=gpt_cfg.vocab_size)
    trainer_cfg = TrainerConfig.make(
        batch_size=int(mix["global_batch"]),
        grad_norm_clip=float(mix["grad_norm_clip"]),
        grad_accum_steps=int(found.get("grad_accum_steps", 1)),
        prefetch=int(mix["prefetch"]),
        seed=0,
        # a path that never exists: "missing snapshot = train from scratch"
        snapshot_path=os.path.join(spec.WORK, "no_snapshot.msgpack"),
        handle_signals=False,
    )
    mesh = mesh_lib.make_mesh(MeshConfig.make(**found["mesh"]), devices=devices)
    trainer = GPTTrainer(trainer_cfg, gpt_cfg,
                         OptimizerConfig.make(**mix["optimizer"]),
                         CharView(stream, 0, len(stream.data)), None, mesh=mesh)
    # free seed 0's state before the next is made, whoever else holds it:
    # two of them do not fit where one nearly fills the chips
    for leaf in jax.tree.leaves(trainer.state):
        leaf.delete()
    trainer.state = jax.jit(trainer._fresh_state,
                            out_shardings=trainer.shardings)(jax.random.key(seed))
    trainer.base_rng = jax.random.key(seed)
    trainer.train_iter.state = IteratorState(seed=seed)
    return trainer


def run(cell: spec.Cell, seed: int, seconds: float, traced: bool,
        devices: List, t_process: float, compiles) -> Dict:
    """One run of a training cell: the end-to-end metrics, the evidence the
    per-layer readers take their numbers from, and the verdict."""
    import jax
    from mingpt_distributed_tpu.data.prefetch import PrefetchIterator

    mix = cell.mix
    trainer = build_trainer(cell, seed, devices)
    t_built = time.perf_counter()
    tokens_per_step = int(mix["global_batch"]) * int(mix["seq_len"])
    batches = PrefetchIterator(trainer.train_iter.epoch_batches(),
                               depth=int(mix["prefetch"]))
    reference = spec.load_reference(cell.config)
    data_wait: List[float] = []

    def dispatch(xy=None):
        t0 = time.perf_counter()
        with trace.annotate("data"):
            batch = trainer._put_batch(next(batches) if xy is None else xy)
        data_wait.append(time.perf_counter() - t0)
        with trace.annotate("dispatch"):
            trainer.state, m = trainer._train_step(
                trainer.state, batch, trainer.base_rng)
        return m

    try:
        # -- outside the window: the program's forward against the reference
        # on the first rows of the first batch, dropout off ------------------
        x0, y0 = next(batches)
        rows = int(mix["check_rows"])
        y_few = np.where(np.arange(len(y0))[:, None] < rows, y0, -1)
        eval_loss = float(trainer._eval_step(
            trainer.state, trainer._put_batch((x0, y_few.astype(y0.dtype)))))
        ref_loss = check.train_reference_loss(
            reference, cell.config, trainer.state["params"], x0[:rows],
            y0[:rows])

        t_checked = time.perf_counter()
        warm = [dispatch((x0, y0))]
        for _ in range(int(mix["warmup_steps"]) - 1):
            warm.append(dispatch())
        jax.block_until_ready(warm[-1])

        # -- the measured window ---------------------------------------------
        ms: List = []               # device scalars of each step in the window
        done: List[float] = []      # host clock when step k was seen finished
        del data_wait[:]
        trace_dir = os.path.join(spec.WORK, cell.name, "trace")
        t_first = int(mix["trace_after_steps"]) if traced else -1
        t_last = t_first + int(mix["trace_steps"]) if traced else -1
        tracing = None

        def drain():
            if len(done) < len(ms):
                with trace.annotate("wait"):
                    jax.block_until_ready(ms[-1])
                done.append(time.perf_counter())

        compiles.open_window()
        t_open = time.perf_counter()
        while True:
            k = len(ms)
            if k == t_first:
                drain()
                tracing = trace.capture(trace_dir)
                tracing.__enter__()
            elif k == t_last:
                drain()
                tracing.__exit__(None, None, None)
            m = dispatch()
            drain()                 # waits for step k-1 while step k runs
            ms.append(m)
            if done and done[-1] - t_open >= seconds and k >= t_last:
                break
        drain()
        compiled_in_window = compiles.close_window()
        memory = [d.memory_stats() for d in devices]
    finally:
        batches.close()

    elapsed = done[-1] - t_open
    marks = [t_open] + done
    # the steps dispatched right after start_trace and stop_trace carry the
    # profiler's own time and a refill of the pipeline: not step times
    step_s = [b - a for k, (a, b) in enumerate(zip(marks, marks[1:]))
              if k not in (t_first, t_last)]
    losses = [float(m["loss"]) for m in jax.device_get(warm + ms)]
    verdict = check.train_verdict(eval_loss, ref_loss, losses)
    verdict["compiled_in_window"] = compiled_in_window
    verdict["ok"] = verdict["ok"] and compiled_in_window == 0

    chips = len(devices)
    tok_s_chip = tokens_per_step / statistics.median(step_s) / chips
    evidence = {
        "kind": "train", "cell": cell, "chips": chips,
        "device_kind": devices[0].device_kind,
        "tokens_per_step": tokens_per_step,
        "step_s": step_s,
        "data_wait_s": data_wait,
        "trace": trace.load(trace_dir, devices) if traced else None,
        "trace_steps": int(mix["trace_steps"]),
        "flash_flops_per_step_chip": flops.flash_train_flops(
            cell.config, int(mix["global_batch"]), int(mix["seq_len"])) / chips,
        "flash_bytes_per_step_chip": flops.flash_train_bytes(
            cell.config, int(mix["global_batch"]), int(mix["seq_len"])) / chips,
        "train_flops_per_token": flops.train_flops_per_token(
            cell.config, int(mix["seq_len"])),
    }
    return {
        "attempted": len(ms), "failed": 0,
        "setup_s": t_open - t_process,
        "end_to_end": {
            "train_tok_s_chip": tok_s_chip,
        },
        "evidence": evidence, "verdict": verdict, "memory_stats": memory,
        "notes": {"steps_in_window": len(ms), "elapsed_s": elapsed,
                  "window_tok_s_chip":
                      len(ms) * tokens_per_step / elapsed / chips,
                  "step_ms_p50": 1e3 * statistics.median(step_s),
                  "step_ms_max": 1e3 * max(step_s),
                  "data_wait_ms_mean": 1e3 * statistics.fmean(data_wait),
                  # where the set-up went: to the trainer built (import,
                  # device, data, weights), then the check, then warm-up
                  "setup_built_s": t_built - t_process,
                  "setup_check_s": t_checked - t_built,
                  "setup_warmup_s": t_open - t_checked},
    }
