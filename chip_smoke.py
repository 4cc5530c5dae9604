#!/usr/bin/env python
"""On-chip smoke: the quickest proof that the system still starts on a TPU.

Three legs, each through the entry point a user would call, at the full
width of GPT-2 124M (12 layers, 12 heads, width 768, T=1024, bf16, vocabulary
50,257) with seeded random weights:

  kernels  ``ops/flash_attention.causal_attention`` (the public dispatch),
           compiled, against the einsum oracle ``ops/attention``: forward
           and dq/dk/dv.
  train    ``train.py``'s ``main()``: a few optimizer steps with
           ``attention=flash`` and the unrolled layer loop, one eval, one
           committed snapshot.
  serve    ``serve.py``'s ``main()`` on that snapshot: prompts of several
           lengths through the continuous-batching server, greedy. Serving
           never runs the Pallas kernel — prefill and decode attend through
           ``ops/attention.py`` whatever ``gpt_config.attention`` says — so
           this leg proves the serving stack on the chip, not the kernel.

Process model: a chip belongs to one process at a time. This parent never
imports jax or the package; it runs each leg as a child (``--leg NAME``),
one after the other, and each child is the only process on the chip while
it lives. The vocabulary follows the data (``train.py`` takes it from the
corpus), so the parent writes a corpus of 50,257 distinct characters from a
seed; that makes the embedding, the LM head and the chunked cross-entropy
the real GPT-2 shapes with no change to the program.

Everything is written under ``chip_smoke_out/`` next to this file, which is
emptied first. Any failed check fails the script: exit code non-zero, no
result line. On success the last line of stdout is

  {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

The seconds and bytes it prints are smoke figures — one cold or warm run,
compilation mixed in — not benchmark results.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import io
import json
import math
import os
import resource
import shutil
import statistics
import subprocess
import sys
import time
import unicodedata

HERE = os.path.dirname(os.path.abspath(__file__))
OUT = os.path.join(HERE, "chip_smoke_out")
# the whole script must end inside 1200 s, compilation included
DEADLINE_S = 1150.0
# bf16 resolution at these magnitudes; gradients relative to their scale
KERNEL_TOL = 2.5e-2
# kernel choices are made from the shape; these variables override them
FLASH_ENV = ("FLASH_LAYOUT", "FLASH_BLOCK")


@dataclasses.dataclass(frozen=True)
class Size:
    """What the legs run at. ``FULL`` is the smoke; a smaller one exists
    only so the legs can be debugged on a CPU (interpret-mode kernels)."""

    model: dict
    vocab: int
    block: int
    corpus_chars: int
    batch_per_device: int
    steps: int
    prompt_lens: tuple
    slots: int
    new_tokens: int
    kernel_shapes: tuple  # (B, T, H, hd)


FULL = Size(
    model={"model_type": "gpt2"},
    vocab=50257,
    block=1024,
    corpus_chars=450_000,
    batch_per_device=16,
    steps=20,
    prompt_lens=(20, 100, 300, 700),
    slots=8,
    new_tokens=64,
    # GPT-2's own geometry (two heads packed per 128-lane cell), and the
    # one-head-per-cell geometry of the hd=128 presets
    kernel_shapes=((2, 1024, 12, 64), (2, 1024, 4, 128)),
)


def fail(msg: str) -> "SystemExit":
    return SystemExit(f"chip_smoke: FAILED: {msg}")


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise fail(msg)


def paths(out: str) -> dict:
    return {k: os.path.join(out, v) for k, v in {
        "corpus": "corpus.txt",
        "config": "config.yaml",
        "prompts": "prompts.txt",
        "snapshot": "gpt_snapshot.msgpack",
        "train_metrics": "train_metrics.jsonl",
        "train_spans": "train_spans.jsonl",
        "ir": "ir",
        "serve_metrics": "serve_metrics.json",
        "serve_traces": "serve_traces.jsonl",
    }.items()}


# ---------------------------------------------------------------------------
# inputs (parent; numpy only)
# ---------------------------------------------------------------------------


def alphabet(n: int) -> list:
    """The first ``n`` printable, non-space code points from '!' upward:
    no controls, separators, surrogates, private-use or unassigned points,
    so the text survives a UTF-8 round trip and a prompt is one line."""
    chars = []
    cp = 0x21
    while len(chars) < n:
        ch = chr(cp)
        if not ch.isspace() and unicodedata.category(ch)[0] != "C":
            chars.append(ch)
        cp += 1
    return chars


def write_inputs(out: str, size: Size, seed: int = 0) -> None:
    """Corpus (every symbol at least once, the rest Zipf-drawn), training
    config, and the prompts file: one prompt per length cut from the
    corpus, each listed twice."""
    import numpy as np

    p = paths(out)
    rng = np.random.default_rng(seed)
    symbols = np.array(alphabet(size.vocab))
    zipf = 1.0 / np.arange(1, size.vocab + 1)
    drawn = rng.choice(size.vocab, size=size.corpus_chars - size.vocab,
                       p=zipf / zipf.sum())
    ids = rng.permutation(np.concatenate([np.arange(size.vocab), drawn]))
    text = "".join(symbols[ids].tolist())
    with open(p["corpus"], "w", encoding="utf-8") as f:
        f.write(text)

    gpt = dict(size.model, dtype="bfloat16", attention="flash",
               attn_pdrop=0.0, unroll_layers=True)
    lines = ["gpt_config:"]
    lines += [f"  {k}: {json.dumps(v)}" for k, v in gpt.items()]
    lines += [
        "optimizer_config:",
        "  learning_rate: 3.0e-4",
        "  weight_decay: 0.1",
        "data_config:",
        f"  path: {json.dumps(p['corpus'])}",
        f"  block_size: {size.block}",
        "  train_split: 0.9",
        "trainer_config:",
        "  max_epochs: 1",
        f"  max_steps: {size.steps}",
        f"  batch_size: {size.batch_per_device}",  # x devices, on argv
        "  grad_norm_clip: 1.0",
        "  log_every: 1",
        "  eval_batches: 2",
        "  save_every: 1",
        f"  snapshot_path: {json.dumps(p['snapshot'])}",
        f"  metrics_jsonl: {json.dumps(p['train_metrics'])}",
        f"  spans_jsonl: {json.dumps(p['train_spans'])}",
        "  mesh:",
        "    dp: -1",
    ]
    with open(p["config"], "w", encoding="utf-8") as f:
        f.write("\n".join(lines) + "\n")

    starts = rng.integers(0, len(text) - max(size.prompt_lens),
                          size=len(size.prompt_lens))
    prompts = [text[s:s + n] for s, n in zip(starts, size.prompt_lens)]
    with open(p["prompts"], "w", encoding="utf-8") as f:
        f.write("\n".join(prompts + prompts) + "\n")


# ---------------------------------------------------------------------------
# legs (children; the only processes that touch jax)
# ---------------------------------------------------------------------------


def require_tpu(leg: str) -> dict:
    """Name the backend, and refuse anything but a TPU before any compile."""
    import importlib.metadata

    import jax
    import jaxlib

    devices = jax.devices()
    dev = {"platform": devices[0].platform, "kind": devices[0].device_kind,
           "count": len(devices)}
    try:
        libtpu = importlib.metadata.version("libtpu")
    except importlib.metadata.PackageNotFoundError:
        libtpu = "not installed"
    print(f"chip_smoke[{leg}]: platform={dev['platform']} "
          f"device_kind={dev['kind']!r} count={dev['count']} "
          f"jax={jax.__version__} jaxlib={jaxlib.__version__} "
          f"libtpu={libtpu}", flush=True)
    if dev["platform"] != "tpu":
        raise fail(
            f"needs a TPU, and JAX found platform {dev['platform']!r} "
            f"({dev['kind']}, {dev['count']} device(s))")
    return dev


def peak_bytes() -> list:
    """Per device, the allocator's high-water mark in this process (None
    where the backend keeps no statistics: a CPU)."""
    import jax

    stats = [d.memory_stats() for d in jax.devices()]
    return [s["peak_bytes_in_use"] if s else None for s in stats]


def leg_kernels(out: str, size: Size) -> dict:
    import jax
    import jax.numpy as jnp

    from mingpt_distributed_tpu.ops import attention as attn_ops
    from mingpt_distributed_tpu.ops import flash_attention as fa

    def sq_loss(fn, q, k, v):
        return jnp.sum(jnp.square(fn(q, k, v).astype(jnp.float32)))

    def max_abs(a, b=None):
        a = a.astype(jnp.float32)
        return float(jnp.max(jnp.abs(a if b is None else a - b.astype(jnp.float32))))

    ref_fwd = jax.jit(attn_ops.causal_attention)
    flash_fwd = jax.jit(fa.causal_attention)
    ref_bwd = jax.jit(jax.grad(
        lambda *a: sq_loss(attn_ops.causal_attention, *a), argnums=(0, 1, 2)))
    flash_bwd = jax.jit(jax.grad(
        lambda *a: sq_loss(fa.causal_attention, *a), argnums=(0, 1, 2)))

    on_tpu = jax.default_backend() == "tpu"
    shapes = []
    for b, t, h, hd in size.kernel_shapes:
        ks = jax.random.split(jax.random.key(hd), 3)
        q, k, v = (jax.random.normal(kk, (b, t, h, hd), jnp.bfloat16)
                   for kk in ks)
        tag = f"B={b} T={t} H={h} hd={hd}"
        # the dispatch may route a call to the oracle (that is its
        # contract); oracle against oracle would prove nothing
        for name, fn in (("forward", flash_fwd), ("backward", flash_bwd)):
            n_calls = fn.lower(q, k, v).as_text().count("tpu_custom_call")
            check(n_calls > 0 or not on_tpu,
                  f"kernels {tag}: the {name} of the public dispatch holds "
                  "no Mosaic custom call — it ran the oracle")
        t0 = time.perf_counter()
        got = jax.block_until_ready(flash_fwd(q, k, v))
        g_got = jax.block_until_ready(flash_bwd(q, k, v))
        first_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        jax.block_until_ready((flash_fwd(q, k, v), flash_bwd(q, k, v)))
        steady_s = time.perf_counter() - t0
        errs = {"fwd": max_abs(got, ref_fwd(q, k, v))}
        for gw, gg, name in zip(ref_bwd(q, k, v), g_got, ("dq", "dk", "dv")):
            errs[name] = max_abs(gg, gw) / (max_abs(gw) or 1.0)
        for name, err in errs.items():
            check(math.isfinite(err) and err <= KERNEL_TOL,
                  f"kernels {tag}: {name} differs from the einsum oracle by "
                  f"{err:.3e} > {KERNEL_TOL:.1e}")
        shapes.append({"shape": tag, "errors": errs,
                       "first_call_s": first_s, "steady_s": steady_s})
        print(f"chip_smoke[kernels]: {tag} " + " ".join(
            f"{n}={e:.2e}" for n, e in errs.items()) + " PASS", flush=True)
    return {"shapes": shapes, "tolerance": KERNEL_TOL,
            "peak_bytes_in_use": peak_bytes()}


def leg_train(out: str, size: Size, overrides: tuple = ()) -> dict:
    import jax

    import train
    from mingpt_distributed_tpu.config import GPTConfig
    from mingpt_distributed_tpu.data import char_dataset
    from mingpt_distributed_tpu.training import durability

    p = paths(out)
    check(not os.path.exists(p["snapshot"])
          and durability.load_manifest(p["snapshot"]) is None,
          f"a snapshot already exists at {p['snapshot']}: the trainer would "
          "resume from it and might take no step at all")
    # every program this process lowers lands here as StableHLO text; that
    # happens before the compile cache is asked, so a warm cache dumps too
    jax.config.update("jax_dump_ir_to", p["ir"])

    n_dev = len(jax.devices())
    argv = ["--config", p["config"],
            f"trainer_config.batch_size={size.batch_per_device * n_dev}",
            *overrides]
    t0 = time.time()
    rc = train.main(argv)
    total_s = time.time() - t0
    check(rc == 0, f"train.main({argv}) returned {rc}")

    with open(p["train_metrics"], encoding="utf-8") as f:
        recs = [json.loads(ln) for ln in f if ln.strip()]
    recs = [r for r in recs if r.get("kind") == "train_step"]
    check([r["step"] for r in recs] == list(range(1, size.steps + 1)),
          f"asked for steps 1..{size.steps}, metrics_jsonl holds "
          f"{[r['step'] for r in recs]}")
    losses = [r["loss"] for r in recs]
    check(all(math.isfinite(x) for x in losses), f"non-finite loss: {losses}")
    want = math.log(size.vocab)
    check(abs(losses[0] - want) <= 0.5,
          f"first loss {losses[0]:.3f} is not ln({size.vocab}) = {want:.2f} "
          "+- 0.5: the full-vocabulary head is not in play")
    check(losses[-1] < losses[0],
          f"loss did not fall: first {losses[0]:.3f}, last {losses[-1]:.3f}")

    with open(p["train_spans"], encoding="utf-8") as f:
        spans = [json.loads(ln).get("name") for ln in f if ln.strip()]
    check(spans.count("train.eval") == 1,
          f"expected one eval pass, spans hold {spans.count('train.eval')}")
    manifest = durability.load_manifest(p["snapshot"])
    check(manifest is not None and manifest.latest.step == size.steps,
          f"no snapshot committed at step {size.steps}: manifest "
          f"{manifest and [e.step for e in manifest.entries]}")

    step_files = [f for f in os.listdir(p["ir"]) if "jit_train_step" in f]
    check(len(step_files) == 1,
          f"expected one lowered train step under {p['ir']}, found "
          f"{step_files}")
    with open(os.path.join(p["ir"], step_files[0]), encoding="utf-8") as f:
        n_mosaic = f.read().count("tpu_custom_call")
    n_layer = GPTConfig.make(**size.model).n_layer
    # forward and backward of every layer's attention, at the least
    check(n_mosaic >= 2 * n_layer or jax.default_backend() != "tpu",
          f"the lowered train step holds {n_mosaic} Mosaic custom calls, "
          f"fewer than 2 per layer ({n_layer} layers): attention=flash is "
          "not running the Pallas kernel")

    ts = [r["ts"] for r in recs]
    gaps = [b - a for a, b in zip(ts[1:], ts[2:])]
    return {
        "steps": size.steps, "global_batch": size.batch_per_device * n_dev,
        "mesh_overrides": list(overrides),
        "batcher": ("native" if char_dataset._native_batcher is not None
                    else "numpy"),
        "first_loss": losses[0], "last_loss": losses[-1],
        "mosaic_calls_in_train_step": n_mosaic,
        "snapshot_bytes": manifest.latest.size,
        # the state is split so that no object passes the file-size limit
        "snapshot_objects": len(manifest.latest.shard_refs()),
        "largest_object_bytes": max(
            ref.size for ref in manifest.latest.shard_refs()),
        # start of main() to the first step's metrics: data, init, compile
        "to_first_step_s": ts[0] - t0,
        "steady_step_s": statistics.median(gaps),
        "total_s": total_s,
        "peak_bytes_in_use": peak_bytes(),
    }


def leg_serve(out: str, size: Size, extra: tuple = ()) -> dict:
    import serve
    from mingpt_distributed_tpu import telemetry

    p = paths(out)
    with open(p["prompts"], encoding="utf-8") as f:
        prompts = f.read().splitlines()
    argv = ["--config", p["config"], "--prompts-file", p["prompts"],
            "--slots", str(size.slots), "--greedy",
            "--max-new-tokens", str(size.new_tokens),
            "--warmup",  # compiles every program up front and arms the
                         # recompile watchdog: any later trace is counted
            "--metrics-json", p["serve_metrics"],
            "--trace-jsonl", p["serve_traces"], *extra]
    stdout = io.StringIO()
    t0 = time.perf_counter()
    with contextlib.redirect_stdout(stdout):  # completions: read, not shown
        rc = serve.main(argv)
    total_s = time.perf_counter() - t0
    check(rc == 0, f"serve.main({argv}) returned {rc}")

    # "=== req-N (reason) ===" then prompt + completion, in file order
    lines = stdout.getvalue().splitlines()
    heads = [i for i, ln in enumerate(lines) if ln.startswith("=== req-")]
    check(len(heads) == len(prompts),
          f"{len(prompts)} prompts in, {len(heads)} completions out: a "
          "prompt was skipped")
    completions = []
    for i, prompt in zip(heads, prompts):
        check(lines[i + 1].startswith(prompt),
              f"{lines[i]}: the echoed prompt is not the one in the file")
        completions.append(lines[i + 1][len(prompt):])
    short = [lines[i] for i, c in zip(heads, completions)
             if len(c) != size.new_tokens]
    check(not short, f"requests without all {size.new_tokens} tokens: {short}")
    half = len(prompts) // 2
    check(prompts[:half] == prompts[half:]
          and completions[:half] == completions[half:],
          "the two greedy completions of a repeated prompt differ")

    with open(p["serve_metrics"], encoding="utf-8") as f:
        summary = json.load(f)
    n = len(prompts)
    check(summary["requests_submitted"] == n
          and summary["requests_completed"] == n
          and summary["tokens_generated"] == n * size.new_tokens
          and not (summary["requests_rejected"] or summary["requests_expired"]
                   or summary["requests_failed"]),
          f"serving summary does not show {n} clean requests: {summary}")
    check(len(summary["bucket_histogram"]) >= 3,
          f"prompts landed in buckets {summary['bucket_histogram']}: fewer "
          "than three prefill programs were exercised")

    # same process as the server: its registry is the page --metrics-port
    # would have served
    page = telemetry.parse_prometheus(
        telemetry.render_prometheus(telemetry.get_registry()))
    check("mingpt_recompiles_total" in page["types"],
          "the recompile watchdog never registered")
    recompiles = sum(v for name, _, v in page["samples"]
                     if name == "mingpt_recompiles_total")
    check(recompiles == 0,
          f"{recompiles:g} compile(s) after a program's first use")

    with open(p["serve_traces"], encoding="utf-8") as f:
        reqs = [json.loads(ln) for ln in f if ln.strip()]
    reqs = [r for r in reqs if r.get("kind") == "request"]
    check(len(reqs) == n and all(r["n_tokens"] == size.new_tokens
                                 for r in reqs),
          f"request traces do not show {n} x {size.new_tokens} tokens")
    first_submit = min(r["ts"] for r in reqs)  # the server's perf_counter
    return {
        "requests": n, "tokens_generated": summary["tokens_generated"],
        "attention": "einsum oracle (ops/attention.py): serving never "
                     "calls the Pallas kernel",
        "mesh": list(extra),
        "prefill_buckets_used": summary["bucket_histogram"],
        "recompiles_after_warmup": recompiles,
        # restore + every program's compile (--warmup), then the traffic
        "to_first_request_s": first_submit - t0,
        "serving_s": max(r["end_ts"] for r in reqs) - first_submit,
        "ttft_mean_s": summary["ttft_mean_s"],
        "itl_mean_s": summary["itl_mean_s"],
        "total_s": total_s,
        "peak_bytes_in_use": peak_bytes(),
    }


LEG_FNS = {"kernels": leg_kernels, "train": leg_train, "serve": leg_serve}


def run_leg(leg: str) -> int:
    dev = require_tpu(leg)
    from mingpt_distributed_tpu.utils import startup

    cache = startup.enable_compile_cache()
    n_before = len(os.listdir(cache)) if os.path.isdir(cache) else 0
    report = LEG_FNS[leg](OUT, FULL)
    report["device"] = dev
    report["compile_cache"] = {
        "dir": cache, "entries_before": n_before,
        "entries_added": len(os.listdir(cache)) - n_before}
    with open(os.path.join(OUT, f"{leg}.json"), "w", encoding="utf-8") as f:
        json.dump(report, f, indent=1)
    return 0


# ---------------------------------------------------------------------------
# parent
# ---------------------------------------------------------------------------


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--leg", choices=tuple(LEG_FNS), help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args.leg:
        return run_leg(args.leg)

    set_flash = [v for v in FLASH_ENV if v in os.environ]
    check(not set_flash,
          f"{', '.join(set_flash)} set in the environment: the smoke proves "
          "the kernels the program picks by itself")
    t_start = time.monotonic()
    shutil.rmtree(OUT, ignore_errors=True)  # stale snapshot, metrics, IR
    os.makedirs(OUT)
    write_inputs(OUT, FULL)
    # what the machine allows: the snapshot is 1.96 GB in all
    fsize = resource.getrlimit(resource.RLIMIT_FSIZE)[0]
    print("chip_smoke: file-size limit "
          + ("none" if fsize == resource.RLIM_INFINITY else f"{fsize} bytes")
          + f", {shutil.disk_usage(OUT).free / 2**30:.1f} GiB free under "
          f"{OUT}", flush=True)

    reports = {}
    for leg in LEG_FNS:
        left = DEADLINE_S - (time.monotonic() - t_start)
        check(left > 0, f"out of time before leg {leg}")
        t0 = time.monotonic()
        try:
            rc = subprocess.run(
                [sys.executable, os.path.abspath(__file__), "--leg", leg],
                cwd=HERE, timeout=left).returncode
        except subprocess.TimeoutExpired:  # run() has killed the child
            raise fail(f"leg {leg} still running at the {DEADLINE_S:.0f} s "
                       "deadline") from None
        check(rc == 0, f"leg {leg} exited with code {rc}")
        with open(os.path.join(OUT, f"{leg}.json"), encoding="utf-8") as f:
            reports[leg] = json.load(f)
        reports[leg]["leg_wall_s"] = time.monotonic() - t0

    devices = [r["device"] for r in reports.values()]
    check(all(d == devices[0] for d in devices),
          f"the legs saw different devices: {devices}")
    print("chip_smoke: all legs passed. Smoke figures (one run, compile "
          "included where it says so) — not benchmark results:")
    for leg, r in reports.items():
        r.pop("device")
        print(f"chip_smoke[{leg}]: " + json.dumps(r))
    print(f"chip_smoke: {time.monotonic() - t_start:.0f} s in all")
    print(json.dumps({"ok": True, "device": devices[0]}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
