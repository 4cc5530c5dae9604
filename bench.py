#!/usr/bin/env python
"""Benchmark: GPT-2 124M training-step throughput + MFU on one chip.

Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": ..., "vs_baseline": N, ...extras}

The reference publishes no numbers (SURVEY §6; BASELINE.json "published": {});
the driver-set north star is >=80% MFU on GPT-2 124M at seq 1024, so
``vs_baseline`` reports measured-MFU / 0.80.

The measured program is the full jitted training step (forward + backward +
AdamW update, donated state) — the same compiled unit the trainer runs, not a
matmul microbench.  Both attention paths are measured (flash Pallas kernel and
the einsum oracle); the headline number is the faster one and both appear in
the record.

Process model: this parent never imports jax. It asks a short-lived child
what backend JAX finds, and only on a TPU does it start the measurement, in a
second child that runs after the first has exited — so exactly one process
holds the chip at a time. Without a TPU there is no record under
``mfu_gpt2_124m_seq1024`` and the exit code is non-zero; a failed measurement
exits non-zero too.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

METRIC = "mfu_gpt2_124m_seq1024"


PROBE_TIMEOUT_S = 240
BENCH_TIMEOUT_S = 2400


def _error_record(msg: str) -> dict:
    return {
        "metric": METRIC,
        "value": None,
        "unit": "fraction",
        "vs_baseline": None,
        "error": msg,
    }


def _probe_backend() -> dict:
    """Check jax.devices() answers within a bound; never imports jax here.

    The subprocess catches its own exception and reports the TYPE, so the
    parent reads a structured record instead of a traceback."""
    code = (
        "import json, sys\n"
        "try:\n"
        "    import jax\n"
        "    d = jax.devices()[0]\n"
        "    print(json.dumps({'platform': d.platform,"
        " 'kind': d.device_kind, 'n': jax.device_count()}))\n"
        "except Exception as e:\n"
        "    print(json.dumps({'error': str(e)[:400] or type(e).__name__,"
        " 'etype': type(e).__name__}))\n"
        "    sys.exit(0)\n"
    )
    try:
        proc = subprocess.run(
            [sys.executable, "-c", code],
            capture_output=True,
            text=True,
            timeout=PROBE_TIMEOUT_S,
        )
    except subprocess.TimeoutExpired:
        return {"error": f"backend probe timed out after {PROBE_TIMEOUT_S}s"}
    if proc.returncode != 0:
        tail = (proc.stderr or "").strip().splitlines()[-3:]
        return {"error": "backend probe failed: " + " | ".join(tail)}
    try:
        return json.loads(proc.stdout.strip().splitlines()[-1])
    except (ValueError, IndexError):
        return {"error": "backend probe produced no JSON"}


def check_throughput_plausible(
    tokens_per_sec: float,
    flops_per_token: float,
    peak_flops: float | None,
    slack: float = 1.2,
) -> None:
    """Honesty guard on the timing: a window that did not wait for the
    device measures the enqueue, the wall-clock collapses and the reported
    throughput becomes physically impossible.  Refuse to report a number
    that implies more than ``slack``× the chip's peak FLOP rate — fail
    loudly instead.
    """
    if peak_flops is None or not tokens_per_sec:
        return
    achieved = tokens_per_sec * flops_per_token
    if achieved > slack * peak_flops:
        raise RuntimeError(
            f"implausible throughput: {achieved / 1e12:.1f} TFLOP/s implied "
            f"> {slack}x chip peak {peak_flops / 1e12:.1f} TFLOP/s — the "
            "timed window did not wait for the device; "
            "refusing to report inflated numbers"
        )


def check_decode_plausible(
    decode_tokens_per_sec: float,
    batch: int,
    param_bytes: float,
    peak_hbm_bytes: float | None,
    slack: float = 1.5,
) -> None:
    """Roofline honesty guard for the decode extra (VERDICT r3 next #8).

    KV-cached decode is memory-bound: every decode step streams the full
    parameter set from HBM once (shared across the batch), so steps/sec
    cannot exceed bandwidth / param-bytes.  Refuse a rate that implies
    more than ``slack``× the chip's HBM bandwidth rather than report it.
    """
    if peak_hbm_bytes is None or not decode_tokens_per_sec:
        return
    required = (decode_tokens_per_sec / batch) * param_bytes
    if required > slack * peak_hbm_bytes:
        raise RuntimeError(
            f"implausible decode rate: {decode_tokens_per_sec:.0f} tok/s at "
            f"batch {batch} implies {required / 1e9:.0f} GB/s of parameter "
            f"streaming > {slack}x chip HBM bandwidth "
            f"{peak_hbm_bytes / 1e9:.0f} GB/s — timing did not synchronize"
        )


def profile_inner(outdir: str) -> int:
    """Capture a jax.profiler device trace of the winning train-step config
    (VERDICT r2 next #2): 3 warmup steps, then 5 traced steps. Analyse with
    TensorBoard's profile plugin / Perfetto on the written xplane files."""
    import jax
    import jax.numpy as jnp

    from mingpt_distributed_tpu.config import GPTConfig, OptimizerConfig
    from mingpt_distributed_tpu.models import gpt
    from mingpt_distributed_tpu.training.optimizer import make_optimizer
    from mingpt_distributed_tpu.training.trainer import make_train_step
    from mingpt_distributed_tpu.utils import startup

    startup.enable_compile_cache()
    model = os.environ.get("BENCH_MODEL", "gpt2")
    seq = int(os.environ.get("BENCH_SEQ", "1024"))
    batch = int(os.environ.get("BENCH_PROFILE_BATCH", "16"))
    attention = os.environ.get("BENCH_PROFILE_ATTENTION", "flash")
    # default to the round-4 winning step config (unrolled layer loop)
    unroll_layers = os.environ.get("BENCH_PROFILE_UNROLL", "1") == "1"
    remat = os.environ.get("BENCH_PROFILE_REMAT", "0") == "1"
    cfg = GPTConfig.make(
        model_type=model,
        embd_pdrop=0.0, resid_pdrop=0.0, attn_pdrop=0.0,
        dtype="bfloat16", attention=attention,
        unroll_layers=unroll_layers, remat=remat,
        block_size=max(seq, 1024),
    )
    optimizer = make_optimizer(OptimizerConfig(), grad_norm_clip=1.0)
    step_fn = jax.jit(make_train_step(cfg, optimizer), donate_argnums=(0,))
    state = jax.jit(
        lambda k: {
            "params": gpt.init(k, cfg),
            "opt_state": optimizer.init(gpt.init(k, cfg)),
            "step": jnp.asarray(0, dtype=jnp.int32),
        }
    )(jax.random.key(0))
    tokens = jax.random.randint(
        jax.random.key(1), (batch, seq), 0, cfg.vocab_size, dtype=jnp.int32
    )
    rng = jax.random.key(2)
    for _ in range(3):
        state, m = step_fn(state, (tokens, tokens), rng)
    jax.block_until_ready(m)
    n = 5
    with jax.profiler.trace(outdir):
        t0 = time.perf_counter()
        for _ in range(n):
            state, m = step_fn(state, (tokens, tokens), rng)
        jax.block_until_ready(m)
        dt = time.perf_counter() - t0
    loss = float(jax.device_get(m["loss"]))
    print(json.dumps({
        "profile_dir": outdir, "batch": batch, "seq": seq,
        "attention": attention, "steps": n,
        "unroll_layers": unroll_layers, "remat": remat,
        "steps_per_sec": round(n / dt, 3), "loss": loss,
        "device": jax.devices()[0].device_kind,
    }))
    return 0


def _last_json_line(text: str) -> dict | None:
    for line in reversed(text.strip().splitlines()):
        try:
            return json.loads(line)
        except ValueError:
            continue
    return None


def _run_child(flag: str, *args: str) -> int:
    """Run one measurement mode of this file in a bounded child and print
    the last JSON line it produced. Exit code 0 only for a record without
    an ``error`` field."""
    try:
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__), flag, *args],
            capture_output=True,
            text=True,
            timeout=BENCH_TIMEOUT_S,
        )
        out, rc = proc.stdout, proc.returncode
        sys.stderr.write(proc.stderr)
    except subprocess.TimeoutExpired as e:
        # the child emits the headline record as soon as the main sweep
        # finishes (before optional extras) — recover it from the partial
        # stdout rather than discarding a completed measurement
        out = e.stdout or b""
        if isinstance(out, bytes):
            out = out.decode(errors="replace")
        rc = 0
        print(f"{flag} timed out after {BENCH_TIMEOUT_S}s", file=sys.stderr)
    record = _last_json_line(out)
    if record is None:
        print(f"{flag} rc={rc}: no JSON record", file=sys.stderr)
        return 1
    print(json.dumps(record))
    return 1 if (rc != 0 or record.get("error")) else 0


def main() -> int:
    probe = _probe_backend()
    if probe.get("platform") != "tpu":
        # no chip, no record: a CPU number is never written under the
        # chip's metric name
        found = probe.get("error") or f"platform {probe.get('platform')!r}"
        print(f"bench.py needs a TPU; JAX found: {found}", file=sys.stderr)
        return 1
    if "--profile" in sys.argv:
        i = sys.argv.index("--profile")
        outdir = (
            sys.argv[i + 1]
            if len(sys.argv) > i + 1 and not sys.argv[i + 1].startswith("-")
            else "profile_trace"
        )
        return _run_child("--profile-inner", outdir)
    return _run_child("--inner")


def inner() -> int:
    import jax
    import jax.numpy as jnp

    from mingpt_distributed_tpu.config import GPTConfig, OptimizerConfig
    from mingpt_distributed_tpu.models import gpt
    from mingpt_distributed_tpu.telemetry import (
        peak_flops_per_chip,
        peak_hbm_bytes_per_chip,
    )
    from mingpt_distributed_tpu.training.metrics import flops_per_token
    from mingpt_distributed_tpu.training.optimizer import make_optimizer
    from mingpt_distributed_tpu.training.trainer import make_train_step
    from mingpt_distributed_tpu.utils import startup

    startup.enable_compile_cache()

    # env overrides exist so the end-to-end bench contract (one JSON line,
    # metric/value/unit/vs_baseline keys) is testable on CPU with a tiny
    # model; the driver's real run uses the defaults
    model = os.environ.get("BENCH_MODEL", "gpt2")
    seq = int(os.environ.get("BENCH_SEQ", "1024"))
    default_batches = tuple(
        int(b)
        for b in os.environ.get("BENCH_BATCHES", "64,32,16,8,4").split(",")
    )

    def bench_attention(
        attention: str, batches=default_batches, scan_unroll: int = 1,
        remat: bool = False, unroll_layers: bool = False,
        loss_chunks: int = 8,
    ) -> tuple[int, float] | None:
        """(batch, steps/sec) at the largest batch that fits, else None."""
        cfg = GPTConfig.make(
            model_type=model,
            embd_pdrop=0.0, resid_pdrop=0.0, attn_pdrop=0.0,
            dtype="bfloat16",
            attention=attention,
            scan_unroll=scan_unroll,
            remat=remat,
            unroll_layers=unroll_layers,
            loss_chunks=loss_chunks,
            block_size=max(seq, 1024),
        )
        optimizer = make_optimizer(OptimizerConfig(), grad_norm_clip=1.0)
        step_fn = jax.jit(make_train_step(cfg, optimizer), donate_argnums=(0,))

        def try_batch(batch: int) -> float:
            state = jax.jit(
                lambda k: {
                    "params": gpt.init(k, cfg),
                    "opt_state": optimizer.init(gpt.init(k, cfg)),
                    "step": jnp.asarray(0, dtype=jnp.int32),
                }
            )(jax.random.key(0))
            tokens = jax.random.randint(
                jax.random.key(1), (batch, seq), 0, cfg.vocab_size,
                dtype=jnp.int32,
            )
            rng = jax.random.key(2)

            for _ in range(3):  # compile + warmup
                state, m = step_fn(state, (tokens, tokens), rng)
            jax.block_until_ready(m)
            n_steps = 20
            t0 = time.perf_counter()
            for _ in range(n_steps):
                state, m = step_fn(state, (tokens, tokens), rng)
            # steps chain through the donated state, so waiting on the last
            # step's metrics bounds the whole loop
            jax.block_until_ready(m)
            dt = time.perf_counter() - t0
            loss = float(jax.device_get(m["loss"]))
            assert loss == loss, "NaN loss in bench"
            return n_steps / dt

        # retry smaller on ANY failure: HBM OOM can surface as an opaque
        # compile error depending on the backend, not just RESOURCE_EXHAUSTED
        for batch in batches:
            try:
                return batch, try_batch(batch)
            except Exception as e:  # noqa: BLE001
                msg = str(e).splitlines()[0] if str(e) else type(e).__name__
                print(f"{attention} batch={batch} failed: {msg}",
                      file=sys.stderr)
                continue
        return None

    results: dict[str, tuple[int, float]] = {}
    unrolls: dict[str, int] = {}
    remats: dict[str, bool] = {}
    layer_unrolls: dict[str, bool] = {}
    ce_chunks: dict[str, int] = {}  # loss_chunks per path (reproducibility)
    # config ladder per attention path, best-first (round-4 on-chip
    # evidence): the unrolled layer loop removes the scan's
    # dynamic-update-slice activation stacking — ~23% of step time on the
    # r4 trace AND the allocation that made batch >= 16 fail to compile —
    # so it both wins on speed (MFU 0.33 -> 0.43) and unlocks larger
    # batches. Scan + remat remains the memory-floor fallback.
    config_ladder = (
        {"unroll_layers": True, "remat": False},
        {"unroll_layers": False, "remat": False},
        {"unroll_layers": False, "remat": True},
    )
    for attention in ("flash", "einsum"):
        r = None
        for knobs in config_ladder:
            r = bench_attention(attention, **knobs)
            if r is not None:
                remats[attention] = knobs["remat"]
                layer_unrolls[attention] = knobs["unroll_layers"]
                break
        if r is not None:
            results[attention] = r
            unrolls[attention] = 1
            ce_chunks[attention] = 8
            print(
                f"{attention}: batch={r[0]} steps/sec={r[1]:.3f}"
                + (" (remat)" if remats[attention] else "")
                + (" (unrolled)" if layer_unrolls[attention] else ""),
                file=sys.stderr,
            )

    flash_block = None  # None = the kernel's default ladder choice
    # record the layout actually taken: the native-(B,T,D) path only
    # applies when the (h, hd) combination packs to 128 lanes (gpt2 12x64
    # does; e.g. gpt2-xl's 25 heads can't pair) — claiming "btd" for a
    # model that routed to the transpose path would misreport the artifact
    _pcfg = GPTConfig.make(model_type=model)
    from mingpt_distributed_tpu.ops import flash_attention as _fa

    flash_layout = (
        "btd"
        if (_fa._btd_applies(_pcfg.n_head, _pcfg.head_dim)
            and os.environ.get("FLASH_LAYOUT", "auto") != "bh")
        else "bh"
    )
    # honor an ambient FLASH_FUSED_BWD=1 (then the whole ladder measures
    # fused and the probe below is skipped) — the record must describe
    # how the headline was actually measured. The flag only acts on the
    # btd path, so it is only recorded there.
    flash_fused_bwd = (flash_layout == "btd"
                       and os.environ.get("FLASH_FUSED_BWD") == "1")
    def try_probe(label, fn):
        """Run an optional tuning probe; a raising probe is logged and
        treated as a miss rather than aborting the bench and losing every
        collected record (ADVICE r5 — bench_attention returning None is the
        expected miss path, but nothing above guarantees it can't raise)."""
        try:
            return fn()
        except Exception as e:  # noqa: BLE001
            msg = str(e).splitlines()[0] if str(e) else type(e).__name__
            print(f"{label} probe raised (skipped): {msg}", file=sys.stderr)
            return None

    def fused_bwd_spot_check() -> bool:
        """Numeric gate for FLASH_FUSED_BWD (ADVICE r5): compile-and-win is
        not parity. Compare the fused dq/dk/dv against the two-kernel
        reference backward on a small btd-path shape; only a match keeps
        the flag. Runs with FLASH_FUSED_BWD=1 already in the env (the
        caller set it); the reference pass flips it off and restores."""
        import numpy as np

        from mingpt_distributed_tpu.ops import flash_attention as fa

        b, t, h, hd = 2, 256, 4, 64
        block = fa._block_sizes(t)
        if block is None or not fa._btd_applies(h, hd):
            print("fused_bwd spot-check shape can't take the btd path; "
                  "refusing the flag", file=sys.stderr)
            return False
        kq, kk, kv, kw = jax.random.split(jax.random.key(0), 4)
        q = jax.random.normal(kq, (b, t, h * hd), jnp.bfloat16)
        k = jax.random.normal(kk, (b, t, h * hd), jnp.bfloat16)
        v = jax.random.normal(kv, (b, t, h * hd), jnp.bfloat16)
        w = jax.random.normal(kw, (b, t, h * hd), jnp.bfloat16)
        scale = 1.0 / (hd ** 0.5)

        def loss(q, k, v):
            out = fa._flash_btd(q, k, v, h, scale, block, None, None)
            return jnp.sum(out.astype(jnp.float32) * w.astype(jnp.float32))

        grad_fn = jax.grad(loss, argnums=(0, 1, 2))
        fused = jax.device_get(grad_fn(q, k, v))
        os.environ["FLASH_FUSED_BWD"] = "0"
        try:
            ref = jax.device_get(grad_fn(q, k, v))
        finally:
            os.environ["FLASH_FUSED_BWD"] = "1"
        for name, gf, gr in zip(("dq", "dk", "dv"), fused, ref):
            gf = np.asarray(gf, np.float32)
            gr = np.asarray(gr, np.float32)
            # both paths accumulate in f32 and emit bf16: anything beyond
            # a few ulps of bf16 on the largest gradient is a real bug
            tol = 3e-2 * max(1.0, float(np.abs(gr).max()))
            err = float(np.abs(gf - gr).max())
            if not np.isfinite(err) or err > tol:
                print(f"fused_bwd spot-check FAILED on {name}: "
                      f"max|Δ|={err:.3e} tol={tol:.3e}", file=sys.stderr)
                return False
        return True

    if "flash" in results:
        # one bounded extra compile: layer-scan unroll at the winning batch
        # (lets XLA fuse across layer boundaries); only meaningful when the
        # scan path won (the unrolled python loop has no scan to unroll)
        b_star, sps_star = results["flash"]
        if not layer_unrolls["flash"]:
            r = try_probe("unroll", lambda: bench_attention(
                "flash", batches=(b_star,), scan_unroll=4,
                remat=remats["flash"]))
            if r is not None and r[1] > sps_star:
                results["flash"] = r
                unrolls["flash"] = 4
                print(f"flash unroll=4: steps/sec={r[1]:.3f} (kept)",
                      file=sys.stderr)
        # flash block-size sweep at the winning batch (VERDICT r2 weak #4:
        # the (512, 256, 128) ladder was never measured) — two bounded
        # extra compiles; keep the override only if it beats the default
        for blk in (256, 128):
            os.environ["FLASH_BLOCK"] = str(blk)
            try:
                r = try_probe(f"block={blk}", lambda: bench_attention(
                    "flash", batches=(results["flash"][0],),
                    scan_unroll=unrolls["flash"], remat=remats["flash"],
                    unroll_layers=layer_unrolls["flash"],
                ))
            finally:
                os.environ.pop("FLASH_BLOCK", None)
            if r is not None and r[1] > results["flash"][1]:
                results["flash"] = r
                flash_block = blk
                print(f"flash block={blk}: steps/sec={r[1]:.3f} (kept)",
                      file=sys.stderr)
        if flash_block is not None:
            os.environ["FLASH_BLOCK"] = str(flash_block)  # for extras below
        # CE chunk-count probe (r4 on-chip: 4 beat 8 by ~1% at batch 16 with
        # the unrolled chunk loop; larger counts lose matmul efficiency) —
        # one bounded extra compile, kept only if faster
        r = try_probe("loss_chunks=4", lambda: bench_attention(
            "flash", batches=(results["flash"][0],),
            scan_unroll=unrolls["flash"], remat=remats["flash"],
            unroll_layers=layer_unrolls["flash"], loss_chunks=4,
        ))
        if r is not None and r[1] > results["flash"][1]:
            results["flash"] = r
            ce_chunks["flash"] = 4
            print(f"flash loss_chunks=4: steps/sec={r[1]:.3f} (kept)",
                  file=sys.stderr)
        # layout probe: the native-(B,T,D) kernels are the default (r5:
        # +10% at b32 on a v5e); one bounded compile checks the transpose
        # path hasn't overtaken it on THIS backend, and the record carries
        # the winner either way. Skipped when the model can't take the btd
        # path at all (probe would compare the transpose path to itself).
        if flash_layout == "btd":
            prior_layout = os.environ.get("FLASH_LAYOUT")
            os.environ["FLASH_LAYOUT"] = "bh"
            try:
                r = try_probe("layout=bh", lambda: bench_attention(
                    "flash", batches=(results["flash"][0],),
                    scan_unroll=unrolls["flash"], remat=remats["flash"],
                    unroll_layers=layer_unrolls["flash"],
                    loss_chunks=ce_chunks["flash"],
                ))
            finally:
                if prior_layout is None:
                    os.environ.pop("FLASH_LAYOUT", None)
                else:
                    os.environ["FLASH_LAYOUT"] = prior_layout
            if r is not None and r[1] > results["flash"][1]:
                results["flash"] = r
                flash_layout = "bh"
                # the kept measurement never ran the fused kernel (it
                # only exists on the btd path) — don't record it
                flash_fused_bwd = False
                os.environ["FLASH_LAYOUT"] = "bh"  # for extras below
                print(f"flash layout=bh: steps/sec={r[1]:.3f} (kept)",
                      file=sys.stderr)
        # fused-backward probe: the dq+dk+dv single-pass kernel is opt-in
        # until chip-validated (interpret-mode parity only — see
        # _flash_bwd_btd's gate note); one bounded compile turns it on
        # only when it compiles, WINS on this backend, and passes the
        # numeric spot-check against the reference backward. The keep
        # decision runs after the probe (never inside a finally:, ADVICE
        # r5 — a raising probe used to mutate results during exception
        # unwind and then abort the whole bench); the env flag ends set
        # iff the kernel is kept.
        if flash_layout == "btd" and not flash_fused_bwd:
            os.environ["FLASH_FUSED_BWD"] = "1"
            r = try_probe("fused_bwd", lambda: bench_attention(
                "flash", batches=(results["flash"][0],),
                scan_unroll=unrolls["flash"], remat=remats["flash"],
                unroll_layers=layer_unrolls["flash"],
                loss_chunks=ce_chunks["flash"],
            ))
            keep_fused = r is not None and r[1] > results["flash"][1]
            if keep_fused and not try_probe("fused_bwd numeric",
                                            fused_bwd_spot_check):
                print("flash fused_bwd: won on speed but failed the "
                      "numeric spot-check; discarding", file=sys.stderr)
                keep_fused = False
            if keep_fused:
                results["flash"] = r
                flash_fused_bwd = True
                print(f"flash fused_bwd: steps/sec={r[1]:.3f} (kept)",
                      file=sys.stderr)
            else:
                os.environ.pop("FLASH_FUSED_BWD", None)

    if not results:
        print(json.dumps(_error_record("all attention paths failed or OOMed")))
        return 1

    cfg = GPTConfig.make(model_type=model)
    fpt = flops_per_token(cfg, seq)
    peak = peak_flops_per_chip()
    peak_source = "chip_table" if peak else None

    def mfu_of(batch: int, sps: float) -> tuple[float, float | None]:
        tps = sps * batch * seq
        return tps, (tps * fpt / peak if peak else None)

    # plausibility-gate EVERY path, not just the eventual headline (ADVICE
    # r3): an implausible per-path record is as dishonest in the artifact
    # as an implausible headline
    per_path = {}
    for attention in list(results):
        batch, sps = results[attention]
        tps, mfu = mfu_of(batch, sps)
        try:
            check_throughput_plausible(tps, fpt, peak)
        except RuntimeError as e:
            print(f"{attention} path refused: {e}", file=sys.stderr)
            del results[attention]
            if attention == "flash":
                # the sweep's winning block was measured by a refused
                # timing — don't report it or let it steer the extras
                flash_block = None
                os.environ.pop("FLASH_BLOCK", None)
            continue
        per_path[attention] = {
            "batch": batch,
            "tokens_per_sec_per_chip": round(tps, 1),
            "mfu": round(mfu, 4) if mfu is not None else None,
            "scan_unroll": unrolls.get(attention, 1),
            "remat": remats.get(attention, False),
            "unroll_layers": layer_unrolls.get(attention, False),
            "loss_chunks": ce_chunks.get(attention, 8),
            # the scan_unroll / FLASH_BLOCK / loss_chunks probes run for the
            # flash path only (ADVICE r4): non-flash records carry the
            # defaults and are slightly understated
            "tuned": attention == "flash",
        }
    if not results:
        print(json.dumps(_error_record(
            "every attention path implied > 1.2x chip peak — the timed "
            "window did not wait for the device; refusing to report")))
        return 1

    best = max(
        results,
        key=lambda a: per_path[a]["mfu"] or per_path[a]["tokens_per_sec_per_chip"],
    )
    batch, sps = results[best]
    tokens_per_sec, mfu = mfu_of(batch, sps)

    def emit(long_ctx):
        dev = jax.devices()[0]
        record = {
            "metric": METRIC,
            "value": round(mfu, 4) if mfu is not None else None,
            "unit": "fraction",
            # north-star target is 0.80 MFU (BASELINE.md) — no reference-
            # published number exists, so the baseline is the target
            "vs_baseline": round(mfu / 0.80, 4) if mfu is not None else None,
            "attention": best,
            "scan_unroll": unrolls.get(best, 1),
            "unroll_layers": layer_unrolls.get(best, False),
            "loss_chunks": ce_chunks.get(best, 8),
            "flash_block": flash_block,  # None = default ladder
            "flash_layout": flash_layout if best == "flash" else None,
            "flash_fused_bwd": flash_fused_bwd if best == "flash" else None,
            "tokens_per_sec_per_chip": round(tokens_per_sec, 1),
            "flops_per_token": fpt,
            "achieved_tflops": round(tokens_per_sec * fpt / 1e12, 2),
            "peak_tflops": round(peak / 1e12, 1) if peak else None,
            "peak_source": peak_source,
            "batch": batch,
            "seq": seq,
            "device": dev.device_kind,
            "n_devices": jax.device_count(),
            "paths": per_path,
            "long_context": long_ctx,
            "decode": decode,  # KV-cached greedy decode extra (TPU only)
            "serving": serving,  # continuous-batching admission probe
        }
        print(json.dumps(record), flush=True)

    # headline record FIRST: if the optional extras below hang or die, the
    # outer process parses the last complete JSON line and the
    # already-measured MFU is never lost
    decode = None
    serving = None
    emit(None)

    # long-context line (SURVEY §5.7): one bounded flash fwd+bwd at T=8192 —
    # the kernel's O(block) VMEM story, measured whenever a chip is up
    long_ctx = None
    try:
        if jax.default_backend() != "tpu":
            raise RuntimeError("long-context extra is TPU-only (interpret "
                               "mode at T=8192 would dominate the bench)")
        import math as _math

        bh, t_lc, hd = 8, 8192, 128
        ks = jax.random.split(jax.random.key(3), 3)
        q = jax.random.normal(ks[0], (bh, t_lc, hd), jnp.bfloat16)
        k = jax.random.normal(ks[1], (bh, t_lc, hd), jnp.bfloat16)
        v = jax.random.normal(ks[2], (bh, t_lc, hd), jnp.bfloat16)

        from mingpt_distributed_tpu.ops import flash_attention as fa

        def attn_loss(q, k, v):
            out = fa.flash_with_lse(q, k, v, 1.0 / _math.sqrt(hd), 512, True)[0]
            return jnp.sum(out.astype(jnp.float32) ** 2)

        def timed_min(gfn, n=5, repeats=5):
            """Min + spread over >= 5 timed windows: single windows were
            noisy (r4: 2.01x and 0.76x window_speedup on identical code the
            same day). The min is the estimator; the per-trial list is
            recorded so the artifact carries the variance, and the speedup
            is only cited when the spread supports it."""
            for _ in range(2):
                r = gfn(q, k, v)
            jax.block_until_ready(r)
            trials = []
            for _ in range(repeats):
                t0 = time.perf_counter()
                for _ in range(n):
                    r = gfn(q, k, v)
                jax.block_until_ready(r)
                trials.append((time.perf_counter() - t0) / n)
            return min(trials), trials

        g = jax.jit(jax.grad(attn_loss, argnums=(0, 1, 2)))
        dt, dt_trials = timed_min(g)
        # causal fwd 2 matmuls: 4*bh*T^2*hd/2 flops; bwd ~2.5x more
        flops = 3.5 * 4 * bh * t_lc * t_lc * hd / 2
        if peak and flops / dt > 1.2 * peak:
            raise RuntimeError(
                f"implausible long-context timing: {flops / dt / 1e12:.0f} "
                f"TFLOP/s > 1.2x peak {peak / 1e12:.0f}")
        long_ctx = {
            "seq": t_lc, "ms_per_iter": round(dt * 1e3, 2),
            "ms_trials": [round(t * 1e3, 2) for t in dt_trials],
            "attn_tflops": round(flops / dt / 1e12, 1),
        }

        # banded variant at the same shapes: the sliding-window kernel
        # skips out-of-band blocks, so wall-clock should scale ~window/T
        win = 1024

        def attn_loss_win(q, k, v):
            # keyword args: _flash's positional nondiff layout has already
            # changed once (softcap appended) — don't depend on it
            out = fa._flash(q, k, v, 1.0 / _math.sqrt(hd), 512, window=win)
            return jnp.sum(out.astype(jnp.float32) ** 2)

        gw = jax.jit(jax.grad(attn_loss_win, argnums=(0, 1, 2)))
        dt_w, dt_w_trials = timed_min(gw)
        # banded rows attend ~window keys vs the causal average T/2, so
        # banded work ~= full * 2*win/T; same 1.2x-peak refusal applies
        flops_w = flops * 2 * win / t_lc
        if peak and flops_w / dt_w > 1.2 * peak:
            print(f"banded extra refused: {flops_w / dt_w / 1e12:.0f} "
                  f"TFLOP/s implied > 1.2x peak", file=sys.stderr)
        else:
            long_ctx["window"] = win
            long_ctx["window_ms_per_iter"] = round(dt_w * 1e3, 2)
            long_ctx["window_ms_trials"] = [
                round(t * 1e3, 2) for t in dt_w_trials
            ]
            # cite the speedup only when the spread supports it: if either
            # set's trials vary more than the claimed effect, the number is
            # noise, not a measurement (r4: 2.01x and 0.76x on identical
            # code)
            spread = max(
                (max(ts) - min(ts)) / min(ts)
                for ts in (dt_trials, dt_w_trials)
            )
            long_ctx["trial_spread"] = round(spread, 3)
            speedup = dt / dt_w
            if abs(speedup - 1.0) > spread:
                long_ctx["window_speedup"] = round(speedup, 2)
            else:
                long_ctx["window_speedup_unstable"] = round(speedup, 2)
    except Exception as e:  # noqa: BLE001 — optional extra, never fatal
        print(f"long-context extra skipped: {e}", file=sys.stderr)

    # decode throughput extra — LAST, so a slow compile here can't starve
    # the longer-standing long-context metric out of the record (SURVEY C9:
    # the reference re-forwards the whole sequence per token; the KV-cached
    # compiled decode is a capability worth a number). The rate is the
    # DIFFERENTIAL between two generation lengths, so the shared prefill
    # forward cancels and pure decode-step throughput is reported.
    try:
        if jax.default_backend() != "tpu":
            raise RuntimeError("decode extra is TPU-only")
        from mingpt_distributed_tpu.models import generate as gen_mod

        dec_cfg = GPTConfig.make(
            model_type=model,
            embd_pdrop=0.0, resid_pdrop=0.0, attn_pdrop=0.0,
            dtype="bfloat16", block_size=max(seq, 1024),
        )
        dec_params = jax.jit(lambda k: gpt.init(k, dec_cfg))(jax.random.key(4))
        db, prompt_len = 8, 128
        n_short, n_long = 256, 512
        prompt = jax.random.randint(
            jax.random.key(5), (db, prompt_len), 0, dec_cfg.vocab_size,
            dtype=jnp.int32,
        )

        def timed(n):
            out = gen_mod.generate(dec_params, dec_cfg, prompt, n)
            jax.block_until_ready(out)  # compile
            t0 = time.perf_counter()
            out = gen_mod.generate(dec_params, dec_cfg, prompt, n)
            jax.block_until_ready(out)
            return time.perf_counter() - t0

        dt_short, dt_long = timed(n_short), timed(n_long)
        if dt_long > dt_short:
            dtps = db * (n_long - n_short) / (dt_long - dt_short)
            # bf16 compute copy of the params is the floor of per-step HBM
            # traffic (KV-cache reads come on top — bound is conservative)
            check_decode_plausible(
                dtps, db, 2 * gpt.param_count(dec_params),
                peak_hbm_bytes_per_chip())
            decode = {
                "batch": db, "prompt_len": prompt_len,
                "new_tokens": n_long,
                "decode_tokens_per_sec": round(dtps, 1),
            }
    except Exception as e:  # noqa: BLE001 — optional extra, never fatal
        print(f"decode extra skipped: {e}", file=sys.stderr)

    # serving-throughput extra (ISSUE 3): the continuous-batching server
    # under a mixed short/long prompt trace with bucketed + chunked prefill
    # and the shared-prefix store on. Records tokens/sec and — the
    # acceptance evidence — per-admission cost scaling: a short prompt's
    # compiled prefill is measurably cheaper than a full-window one, and a
    # prefix-cache hit pays only its tail. A tiny model keeps the extra
    # bounded on every backend (the numbers compare prefill geometries to
    # EACH OTHER, which a tiny model preserves).
    try:
        if os.environ.get("BENCH_SERVING", "1") == "0":
            raise RuntimeError("disabled via BENCH_SERVING=0")
        serving = serving_probe()
        print(f"serving extra: {json.dumps(serving)}", file=sys.stderr)
    except Exception as e:  # noqa: BLE001 — optional extra, never fatal
        print(f"serving extra skipped: {e}", file=sys.stderr)

    if long_ctx is not None or decode is not None or serving is not None:
        emit(long_ctx)  # augmented record supersedes the headline-only one
    return 0


#: generous-by-design objectives for the serving probe: the point of the
#: BENCH block is recording *observed* exact-quantile latencies and the
#: attainment grade over rounds, not gating CI on a tiny-model number
SERVING_SLO_SPEC = "ttft_p99<=2.0,itl_p99<=0.5,shed_rate<=0.0"


def serving_probe() -> dict:
    """Continuous-batching admission/throughput probe on a tiny model.

    Trace: 24 requests, cycling long (100-token) / shared-prefix (48-token
    system prompt + 8) / short (12-token) prompts through 4 slots with a
    (16, 32, 64, 128) bucket ladder, 32-token chunks and the prefix store
    enabled. Also times the compiled prefill at three admission
    geometries after warmup — short bucket, full window, prefix-hit tail
    — which is the prompt-length-proportional-cost claim in one place.

    The run is traced end-to-end (ISSUE 10): a TraceRecorder collects
    per-request timelines and the returned record carries an ``slo``
    block — exact-quantile TTFT/ITL/shed objectives graded by
    telemetry.slo — so BENCH rounds record SLO attainment alongside
    throughput.

    ISSUE 11: a ``speculative`` block replays the same trace through a
    draft/verify server (draft = target weights, accept rate 1.0) and
    records tokens/sec vs the plain path, tokens-per-verify and the
    verify-executable count — token-exactness
    asserted against the non-spec handles.
    """
    import jax
    import numpy as np

    from mingpt_distributed_tpu import telemetry
    from mingpt_distributed_tpu.config import GPTConfig
    from mingpt_distributed_tpu.models import gpt
    from mingpt_distributed_tpu.serving import InferenceServer, Request

    cfg = GPTConfig.make(
        n_layer=2, n_head=2, n_embd=64, vocab_size=256, block_size=128,
        embd_pdrop=0.0, resid_pdrop=0.0, attn_pdrop=0.0, dtype="float32",
    )
    params = gpt.init(jax.random.key(0), cfg)
    recorder = telemetry.TraceRecorder(sample=1.0)
    server = InferenceServer(
        params, cfg, n_slots=4, prefill_buckets=(16, 32, 64, 128),
        prefill_chunk=32, prefix_cache_mb=16.0, warmup=True,
        trace_recorder=recorder,
    )
    rng = np.random.RandomState(0)
    shared = rng.randint(0, cfg.vocab_size, 48).tolist()
    prompts = []
    for i in range(24):
        if i % 3 == 0:
            prompt = rng.randint(0, cfg.vocab_size, 100).tolist()
        elif i % 3 == 1:
            prompt = shared + rng.randint(0, cfg.vocab_size, 8).tolist()
        else:
            prompt = rng.randint(0, cfg.vocab_size, 12).tolist()
        prompts.append(prompt)
    reqs = [Request(prompt=p, max_new_tokens=16) for p in prompts]
    t0 = time.perf_counter()
    handles = server.generate_batch(reqs)
    wall = time.perf_counter() - t0
    m = server.summary()
    assert all(h.finished for h in handles)

    # speculative block (ISSUE 11): the SAME 24-request trace through a
    # second server with draft/verify decoding. Draft = the target's own
    # weights, so acceptance is deterministic (rate 1.0) on every backend
    # — the block measures the propose→verify machinery's throughput
    # against the plain path, not draft quality. Token-exactness is
    # asserted request-by-request against the non-spec run.
    spec_k = 3
    spec_server = InferenceServer(
        params, cfg, n_slots=4, prefill_buckets=(16, 32, 64, 128),
        prefill_chunk=32, prefix_cache_mb=16.0, warmup=True,
        draft_params=params, draft_cfg=cfg, spec_k=spec_k,
    )
    spec_reqs = [Request(prompt=p, max_new_tokens=16) for p in prompts]
    t0 = time.perf_counter()
    spec_handles = spec_server.generate_batch(spec_reqs)
    spec_wall = time.perf_counter() - t0
    sm = spec_server.metrics
    assert [h.tokens for h in spec_handles] == [h.tokens for h in handles], \
        "speculative decode diverged from the plain greedy path"
    spec_tps = sm.tokens_generated / spec_wall
    speculative = {
        "spec_k": spec_k,
        "tokens_per_sec": round(spec_tps, 1),
        "nonspec_tokens_per_sec": round(m["tokens_generated"] / wall, 1),
        "speedup_vs_nonspec": round(
            spec_tps / (m["tokens_generated"] / wall), 3),
        "accept_rate": round(sm.spec_accept_rate, 3),
        "tokens_per_verify_mean": round(sm.spec_tokens_per_verify_mean, 3),
        "verify_rounds": sm.spec_rounds,
        "verify_executables": spec_server.compile_counts()["verify"],
    }

    eng = server.engine

    def prefill_ms(n_tokens: int, offset: int = 0) -> float:
        ids = list(range(1, n_tokens + 1))
        t0 = time.perf_counter()
        for _ in range(5):
            eng.prefill_chunk_call(0, ids, offset, 1.0, None, None, False, 1)
        return (time.perf_counter() - t0) / 5 * 1e3

    short_ms = prefill_ms(16)            # 16-token prompt, bucket 16
    full_ms = prefill_ms(cfg.block_size)  # full-window prompt
    tail_ms = prefill_ms(16, offset=48)  # what a 48-row prefix hit leaves

    # quantized block (ISSUE 18): an int8 twin of the same engine
    # geometry — bytes-per-slot (payload + fp32 scale planes) against
    # the fp32 pool, max admissible slots under a fixed synthetic
    # per-device HBM budget (the slots-per-chip multiplier headline,
    # asserted strictly higher at int8), and the timed compiled decode
    # step at each dtype. Accuracy lives in serve.py --selftest-quant;
    # this block records the capacity arithmetic perf_diff watches.
    from mingpt_distributed_tpu.serving import quant as quant_lib
    from mingpt_distributed_tpu.serving.engine import DecodeEngine

    q_eng = DecodeEngine(
        params, cfg, n_slots=4, prefill_buckets=(16, 32, 64, 128),
        kv_dtype="int8",
    )

    def decode_step_ms(e) -> float:
        n = e.n_slots
        zeros = np.zeros(n, np.int32)
        step = lambda: e.decode_step(  # noqa: E731
            zeros, zeros, np.ones(n, np.float32), zeros,
            np.ones(n, np.float32), np.zeros(n, bool),
            np.full(n, 2, np.uint32), zeros)
        step()  # compile
        t0 = time.perf_counter()
        for _ in range(20):
            step()
        return (time.perf_counter() - t0) / 20 * 1e3

    fp32_slot = sum(
        int(a.nbytes) for a in eng.pool.cache.values()) // eng.n_slots
    q_data, q_scales = quant_lib.split_scales(q_eng.pool.cache)
    int8_slot = (sum(int(a.nbytes) for a in q_data.values())
                 + sum(int(a.nbytes) for a in q_scales.values())
                 ) // q_eng.n_slots
    hbm_budget = 64 * 1024 * 1024  # synthetic per-device KV budget
    max_slots_fp32 = hbm_budget // fp32_slot
    max_slots_int8 = hbm_budget // int8_slot
    assert max_slots_int8 > max_slots_fp32, \
        "int8 KV pool must admit strictly more slots than fp32"
    quantized = {
        "kv_dtype": "int8",
        "bytes_per_slot_fp32": fp32_slot,
        "bytes_per_slot_int8": int8_slot,
        "bytes_ratio": round(int8_slot / fp32_slot, 4),
        "hbm_budget_mb": hbm_budget // (1024 * 1024),
        "max_slots_fp32": max_slots_fp32,
        "max_slots_int8": max_slots_int8,
        "decode_step_fp32_ms": round(decode_step_ms(eng), 3),
        "decode_step_int8_ms": round(decode_step_ms(q_eng), 3),
    }

    slo = telemetry.evaluate_slos(
        recorder.completed_requests(),
        telemetry.parse_slo_spec(SERVING_SLO_SPEC))
    return {
        "tokens_per_sec": round(m["tokens_generated"] / wall, 1),
        "requests": len(reqs),
        "slots": 4,
        "buckets": list(eng.buckets),
        "prefill_chunk": eng.prefill_chunk,
        "prefill_pad_overhead": round(m["prefill_pad_overhead"], 3),
        "prefix_hit_rate": round(m["prefix_hit_rate"], 3),
        "prefix_rows_reused": m["prefix_rows_reused"],
        "admission_stall_mean_ms": round(
            m["admission_stall_mean_s"] * 1e3, 2),
        "prefill_short16_ms": round(short_ms, 2),
        "prefill_full_window_ms": round(full_ms, 2),
        "prefill_prefix_tail_ms": round(tail_ms, 2),
        "short_vs_full_speedup": round(full_ms / short_ms, 2),
        "speculative": speculative,
        "quantized": quantized,
        "slo": slo,
    }


def serving_inner() -> int:
    """``--serving``: the serving probe as a standalone BENCH record —
    one JSON line whose headline is serving throughput and whose
    ``serving.slo`` block is the graded exact-quantile attainment
    report. Runs on any backend (tiny model, CPU included)."""
    from mingpt_distributed_tpu.utils import startup

    startup.enable_compile_cache()
    serving = serving_probe()
    slo = serving["slo"]
    print(json.dumps({
        "metric": "serving_tokens_per_sec",
        "value": serving["tokens_per_sec"],
        "unit": "tokens/sec",
        "slo_grade": slo["grade"],
        "slo_attainment": slo["attainment"],
        "serving": serving,
    }), flush=True)
    return 0


def traffic_inner() -> int:
    """``--traffic``: the traffic-lab sweep as a standalone BENCH record
    — one JSON line whose headline is the knee rung (first offered-load
    rung where the named SLO objective fails) and whose ``traffic``
    block carries per-policy grades and deadline-hit-rates per rung.
    Runs the canned selftest geometry (tiny model, VirtualClock), so it
    works on any backend and adds nothing to existing records."""
    import traffic as traffic_cli
    from mingpt_distributed_tpu.trafficlab import run_sweep

    cfg, params = traffic_cli._tiny_model()
    spec = traffic_cli.selftest_sweep_spec()
    report = run_sweep(params, cfg, spec, mix=traffic_cli.selftest_mix())
    knee = report["knee"]
    rungs = [
        {
            "rung": rung["rung"],
            "offered_rate": rung["offered_rate"],
            "policies": {
                name: {
                    "grade": cell["slo"]["grade"],
                    "attainment": cell["slo"]["attainment"],
                    "deadline_hit_rate": cell["deadline_hit_rate"],
                    "completed": cell["completed"],
                    "shed": cell["shed"],
                    "expired": cell["expired"],
                }
                for name, cell in rung["policies"].items()
            },
        }
        for rung in report["rungs"]
    ]
    print(json.dumps({
        "metric": "traffic_knee_rung",
        "value": None if knee is None else knee["rung"],
        "unit": "rung",
        "knee": knee,
        "traffic": {
            "schema": report["schema"],
            "arrival": report["arrival"]["spec"],
            "ladder": report["ladder"],
            "policies": report["policies"],
            "slo_spec": report["slo_spec"],
            "knee_objective": report["knee_objective"],
            "rungs": rungs,
        },
    }), flush=True)
    return 0


if __name__ == "__main__":
    if "--inner" in sys.argv:
        sys.exit(inner())
    if "--profile-inner" in sys.argv:
        sys.exit(profile_inner(sys.argv[sys.argv.index("--profile-inner") + 1]))
    if "--serving" in sys.argv:
        sys.exit(serving_inner())
    if "--traffic" in sys.argv:
        sys.exit(traffic_inner())
    sys.exit(main())
