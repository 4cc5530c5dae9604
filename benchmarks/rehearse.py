#!/usr/bin/env python3
"""Rehearse the benchmark where there is no chip. Three things, none of
which is a measurement:

  python3 benchmarks/rehearse.py run [--cell NAME]
      every cell's own code path end to end on the CPU at a tiny size (the
      fsdp cell on four virtual devices), traced, with every per-layer reader
      called. Prints counts: steps, requests, tokens, compilations, which
      readers found something to read. Never a result line, and no time or
      rate under a metric's name.

  python3 benchmarks/rehearse.py compile [--cell NAME] [--override JSON]
      each cell's programs at the published widths, compiled by the TPU's own
      compiler for a described v5e:2x2 (nothing runs), with the bytes the
      compiler plans per device: what it refuses here it would refuse on the
      chip, and the sizing searches (slots, remat, accumulation) start here.

  python3 benchmarks/rehearse.py cycle --cell NAME [--override JSON]
      a closed-loop cell whose mix deals its lengths in a cycle: the
      cell's own lengths and slots through the program's own scheduler, at a
      tiny width, with the scheduling rounds each block took. Rounds do not
      depend on the width, so this is the cycle the chip will run: a mix is
      fit for block medians when the counts settle on one number.

All force JAX onto the CPU; a Pallas kernel runs in interpret mode in the
first and is compiled by Mosaic in the second.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("TPU_LOG_DIR", "disabled")
if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=4")

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.harness import compiles, device, spec  # noqa: E402

#: the tiny size, stated once, in the program's own field names; a
#: configuration whose other fields must shrink with it (KV heads, experts)
#: says how under ``program.tiny`` in its file
TINY_PROGRAM = {"n_layer": 2, "n_head": 3, "n_embd": 96, "block_size": 128,
                "vocab_size": 384}
TINY_TRAIN = {"global_batch": 8, "seq_len": 128, "stream_tokens": 1 << 15,
              "check_rows": 2, "trace_after_steps": 1, "trace_steps": 2}
TINY_SERVE = {
    "prompt_len": {"dist": "lognormal", "median": 20, "sigma": 0.5, "min": 4,
                   "max": 60, "stratum": 16},
    "output_len": {"dist": "uniform", "min": 3, "max": 12, "stratum": 16},
    "server": {"prefill_len": 64, "prefill_buckets": [32, 64]},
    "ramp_s": 1, "drain_s": 2,
    "trace_after_s": 0.5, "trace_s": 1, "pool": 512,
}


def tiny(cell: spec.Cell, sizes=None, mix_too: bool = True) -> spec.Cell:
    """The cell at a tiny size. The program's arguments are shrunk by the
    program's names; every published key that ``program.key_map`` ties to a
    field or property of the program's config is then set to what the tiny
    program has there, whatever the key is called, so ``spec.gpt_config``'s
    check of published against run holds as it does at full size."""
    from mingpt_distributed_tpu.config import GPTConfig

    program = cell.config["program"]
    gpt = {k: v for k, v in program["gpt_config"].items() if k != "model_type"}
    gpt.update({**TINY_PROGRAM, **program.get("tiny", {}), **(sizes or {})})
    run = GPTConfig.make(**gpt)
    config = dict(cell.config, program=dict(program, gpt_config=gpt),
                  **{published: getattr(run, field)
                     for published, field in program["key_map"].items()})
    if not mix_too:
        return dataclasses.replace(cell, config=config)
    # the mix's own arrivals and warm start stay, cycles and all
    mix = dict(cell.mix, **(TINY_TRAIN if cell.kind == "train" else TINY_SERVE))
    for key in ("prompt_len", "output_len"):
        # tiny lengths, dealt in the order the cell's own mix deals its own
        order = {k: v for k, v in cell.mix.get(key, {}).items()
                 if k in ("order", "order_seed")}
        if order:
            mix[key] = dict(mix[key], **order)
    found = dict(cell.found)
    if cell.kind == "serve":
        found.update(server={"n_slots": 4}, rate_req_s=6.0, warm_inflight=2)
        # found at the published size on the chip: a tiny program on the
        # CPU is held to its twin itself
        found.pop("twin_ratio", None)
    return dataclasses.replace(cell, config=config, mix=mix, found=found)


def rehearse_run(cell: spec.Cell) -> None:
    import jax

    from benchmarks.harness import serve_cell, train_cell

    cell = tiny(cell)
    devices = jax.devices()[:cell.chips]
    counter = compiles.CompileCounter()
    runner = train_cell if cell.kind == "train" else serve_cell
    result = runner.run(cell, seed=1, seconds=2.0, traced=True,
                        devices=devices, t_process=time.perf_counter(),
                        compiles=counter)
    readers = {}
    for entry in cell.per_layer:
        try:
            value = spec.load_reader(entry["name"]).read(result["evidence"])
            readers[entry["name"]] = "read" if value is not None else "nothing"
        except device.NoChip:
            readers[entry["name"]] = "needs the chip's peak"
    verdict = result["verdict"]
    counts = {k: v for k, v in result["notes"].items() if k in (
        "steps_in_window", "attempted", "failed", "rounds", "itl_gaps",
        "blocks", "requests_generated", "traffic_digest")}
    print(json.dumps({
        "rehearsed": cell.name, "devices": len(devices),
        "attempted": result["attempted"], "failed": result["failed"],
        "counts": counts, "programs_lowered": counter.lowered,
        "compiled_in_window": verdict["compiled_in_window"],
        "agrees_with_reference": verdict["ok"],
        "check": {k: v for k, v in verdict.items() if k != "ok"},
        "readers": readers,
    }, default=str))


def rehearse_cycle(cell: spec.Cell, blocks: int = 12) -> None:
    """The cell's own closed loop in scheduling rounds: its lengths, its
    slots, the program's scheduler, a model of no width to speak of."""
    from benchmarks.harness import serve_cell, traffic

    k = traffic.cycle_length(cell.mix) if cell.kind == "serve" else 0
    if not k:
        raise spec.SpecError(f"{cell.name}: no closed loop dealt in a cycle")
    cell = tiny(cell, mix_too=False, sizes=dict(
        n_layer=1, n_head=2, n_embd=32,
        block_size=spec.gpt_config(cell, training=False).block_size))
    cell = dataclasses.replace(cell, mix=dict(cell.mix, ramp_s=0.0))
    driver = serve_cell.Driver(cell, seed=1, traced=False)
    reqs = traffic.requests(cell.mix, driver.gpt_cfg.vocab_size, 1, rate=None,
                            horizon_s=0.0)[:(blocks + 1) * k]
    rounds, sent_in = [0], {}
    step, submit = driver.server.step, driver._submit

    def counted_step():
        rounds[0] += 1
        return step()

    def counted_submit(req, t_ref):
        sent_in[req.index] = rounds[0]
        return submit(req, t_ref)

    driver.server.step, driver._submit = counted_step, counted_submit
    try:
        driver.play(reqs, seconds=3600.0)
    except RuntimeError:        # the pool ran out: as many blocks as asked
        pass
    starts = [sent_in[i] for i in range(0, len(reqs), k) if i in sent_in]
    lens = reqs[:k]
    print(json.dumps({
        "cycle": cell.name, "n_slots": driver.server.engine.n_slots,
        "block": k,
        "prompt_lens": [len(r.prompt) for r in lens],
        "output_lens": [r.max_new_tokens for r in lens],
        "decode_lane_rounds_a_block": sum(r.max_new_tokens - 1 for r in lens),
        "rounds_a_block": [b - a for a, b in zip(starts, starts[1:])],
        "sent_in_round_of_block": [
            [sent_in[i + j] - sent_in[i] for j in range(k)]
            for i in range((blocks - 2) * k, blocks * k, k)],
    }))


# ---------------------------------------------------------------------------
# compile for a described chip
# ---------------------------------------------------------------------------


def _report(name: str, compiled, seconds: float) -> None:
    m = compiled.memory_analysis()
    live = (m.argument_size_in_bytes + m.output_size_in_bytes
            - m.alias_size_in_bytes + m.temp_size_in_bytes)
    text = compiled.as_text()
    print(json.dumps({
        "program": name, "compile_s": round(seconds, 1),
        "argument_bytes": m.argument_size_in_bytes,
        "output_bytes": m.output_size_in_bytes,
        "alias_bytes": m.alias_size_in_bytes,
        "temp_bytes": m.temp_size_in_bytes,
        "planned_bytes_per_device": live,
        "mosaic_calls": text.count('custom_call_target="tpu_custom_call"'),
        "collectives": {op: text.count(f" {op}(") + text.count(f" {op}-start(")
                        for op in ("all-gather", "all-reduce",
                                   "reduce-scatter", "all-to-all")},
    }), flush=True)


def compile_train(cell: spec.Cell, topo_devices) -> None:
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from mingpt_distributed_tpu.config import MeshConfig, OptimizerConfig
    from mingpt_distributed_tpu.models import gpt
    from mingpt_distributed_tpu.ops import flash_attention
    from mingpt_distributed_tpu.parallel import mesh as mesh_lib
    from mingpt_distributed_tpu.training.optimizer import (
        lr_schedule, make_optimizer)
    from mingpt_distributed_tpu.training.trainer import make_train_step

    # the kernel asks jax.default_backend(), which is the CPU here: steer it
    # to the compiled path, as the guide says a rehearsal may
    flash_attention._interpret = lambda: False
    mix, found = cell.mix, cell.found
    cfg = spec.gpt_config(cell, training=True)
    mesh = mesh_lib.make_mesh(MeshConfig.make(**found["mesh"]),
                              devices=topo_devices[:cell.chips])
    opt_cfg = OptimizerConfig.make(**mix["optimizer"])
    lr_fn = lr_schedule(opt_cfg)
    optimizer = make_optimizer(opt_cfg, float(mix["grad_norm_clip"]),
                               schedule=lr_fn)

    def fresh(key):
        params = gpt.init(key, cfg)
        return {"params": params, "opt_state": optimizer.init(params),
                "step": jnp.asarray(0, jnp.int32)}

    key = jax.eval_shape(lambda: jax.random.key(0))
    shape = jax.eval_shape(fresh, key)
    shardings = mesh_lib.state_shardings(mesh, shape)
    batch_sh, repl = mesh_lib.batch_sharding(mesh), NamedSharding(mesh, P())
    step = jax.jit(
        make_train_step(cfg, optimizer, mesh,
                        grad_accum=int(found.get("grad_accum_steps", 1)),
                        lr_fn=lr_fn),
        in_shardings=(shardings, (batch_sh,) * 2, repl),
        out_shardings=(shardings, repl), donate_argnums=(0,))
    state = jax.tree.map(
        lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
        shape, shardings)
    tok = jax.ShapeDtypeStruct((int(mix["global_batch"]), int(mix["seq_len"])),
                               jnp.int32, sharding=batch_sh)
    key = jax.ShapeDtypeStruct(key.shape, key.dtype, sharding=repl)
    # the program train_cell.build_trainer makes the seeded state with
    t0 = time.perf_counter()
    compiled = jax.jit(fresh, out_shardings=shardings).lower(key).compile()
    _report(f"{cell.name}: fresh_state", compiled, time.perf_counter() - t0)
    t0 = time.perf_counter()
    compiled = step.lower(state, (tok, tok), key).compile()
    _report(f"{cell.name}: train_step remat={cfg.remat} "
            f"grad_accum={found.get('grad_accum_steps', 1)}",
            compiled, time.perf_counter() - t0)


def compile_serve(cell: spec.Cell, topo_devices) -> None:
    """Every program of the engine the cell's server builds, as
    ``DecodeEngine.programs()`` yields them: the jitted function and the
    arguments of a real call, whatever the pool's leaves and the programs'
    vectors are (a latent pool, a state beside rows, the next vector a PR
    adds). The engine is built here on the CPU over weights of zeros and
    nothing of it runs: only shapes, dtypes and the static configuration
    reach the compiler."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from mingpt_distributed_tpu.models import gpt
    from mingpt_distributed_tpu.serving import InferenceServer

    options = spec.server_options(cell)
    cfg = spec.gpt_config(cell, training=False)
    one = SingleDeviceSharding(topo_devices[0])
    on_chip = lambda x: jax.ShapeDtypeStruct(
        jnp.shape(x), jnp.result_type(x), sharding=one)
    params = jax.tree.map(
        lambda a: jnp.zeros(a.shape, a.dtype),
        jax.eval_shape(lambda: gpt.init(jax.random.key(0), cfg)))
    engine = InferenceServer(params, cfg, warmup=False, **options).engine
    for family, variant, jitted, args, kwargs in engine.programs():
        t0 = time.perf_counter()
        compiled = jitted.lower(*jax.tree.map(on_chip, args),
                                **kwargs).compile()
        _report(f"{cell.name}: {family} {variant} n_slots={engine.n_slots}",
                compiled, time.perf_counter() - t0)


def rehearse_compile(cell: spec.Cell) -> None:
    import jax
    from jax.experimental import topologies

    # a compile for a described chip is written to the persistent cache and
    # can never be read back without one: keep it out
    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    (compile_train if cell.kind == "train" else compile_serve)(
        cell, list(topo.devices))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("what", choices=("run", "compile", "cycle"))
    ap.add_argument("--cell", default=None, help="one cell; default: all")
    ap.add_argument("--override", default=None, metavar="JSON")
    args = ap.parse_args(argv)
    names = [args.cell] if args.cell else [
        w["name"] for w in spec.load_manifest()["workloads"]]
    override = json.loads(args.override) if args.override else None
    for name in names:
        cell = spec.load_cell(name, override)
        {"run": rehearse_run, "compile": rehearse_compile,
         "cycle": rehearse_cycle}[args.what](cell)
    return 0


if __name__ == "__main__":
    sys.exit(main())
