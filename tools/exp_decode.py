#!/usr/bin/env python
"""Decode-throughput diagnosis (round-5 lever #4, VERDICT r4).

r4 recorded 1,440 tok/s at batch 8 — a 124M bf16 model at ~819 GB/s HBM
should be several thousand steps/s on a memory-bound roofline, so this
looks ~10x off. Method: SLOPE timing — the whole generate (prefill +
N-step scan) is one program, so t(N2) - t(N1) isolates the per-token scan
cost from prefill and dispatch (the exp_flash chaining discipline).

Hypotheses measured, largest first:
  fp32:   as-shipped — fp32 master params; dense() casts w per use, and
          the cast sits INSIDE the decode scan (500 MB of fp32 HBM reads
          per token if XLA doesn't hoist it).
  bf16:   params pre-cast to the compute dtype once, outside the scan —
          halves the weight traffic and feeds the MXU bf16 directly.
"""
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

from mingpt_distributed_tpu.config import GPTConfig
from mingpt_distributed_tpu.models import generate as gen
from mingpt_distributed_tpu.models import gpt

PROMPT = 128


def _slope_ms(params, cfg, idx, n_lo, n_hi, reps=3):
    """t(n_hi) - t(n_lo) slope: per-token scan cost net of prefill and
    dispatch (the exp_flash chaining discipline), with a real D2H sync."""
    def timed(n_new):
        out = gen.generate(params, cfg, idx, n_new)  # compile
        out.block_until_ready()
        int(jax.device_get(out[0, -1]))
        t0 = time.perf_counter()
        for _ in range(reps):
            out = gen.generate(params, cfg, idx, n_new)
            int(jax.device_get(out[0, -1]))
        return (time.perf_counter() - t0) / reps

    t_lo, t_hi = timed(n_lo), timed(n_hi)
    return (t_hi - t_lo) / (n_hi - n_lo) * 1e3


def run(batch, cast, n_lo=32, n_hi=160):
    cfg = GPTConfig.make(
        model_type="gpt2",
        embd_pdrop=0.0, resid_pdrop=0.0, attn_pdrop=0.0,
        dtype="bfloat16", attention="flash", unroll_layers=True,
        block_size=1024,
    )
    params = jax.jit(lambda k: gpt.init(k, cfg))(jax.random.key(0))
    if cast:
        dt = jnp.dtype(cfg.dtype)
        params = jax.tree.map(
            lambda x: x.astype(dt) if x.dtype == jnp.float32 else x, params
        )
    idx = jax.random.randint(jax.random.key(1), (batch, PROMPT), 0,
                             cfg.vocab_size, dtype=jnp.int32)

    ms_tok = _slope_ms(params, cfg, idx, n_lo, n_hi)
    return {"batch": batch, "params": "bf16" if cast else "fp32",
            "ms_per_step": round(ms_tok, 3),
            "tok_per_sec": round(batch * 1e3 / ms_tok, 1) if ms_tok > 0
            else None}


def main():
    ok = 0
    for batch in (8, 32):
        for cast in (False, True):
            try:
                rec = run(batch, cast)
                ok += 1
            except Exception as e:  # noqa: BLE001
                rec = {"batch": batch, "cast": cast, "error": repr(e)[:200]}
            print(json.dumps(rec), flush=True)
    if not ok:  # all-error output must not read as a pass
        sys.exit(1)


def run_shape(batch, block_size, n_layer, n_lo=32, n_hi=96):
    """Scaling probe: vary cache size (block_size) and layer count to find
    what the per-step decode cost is proportional to."""
    cfg = GPTConfig.make(
        n_layer=n_layer, n_head=12, n_embd=768, vocab_size=50257,
        embd_pdrop=0.0, resid_pdrop=0.0, attn_pdrop=0.0,
        dtype="bfloat16", attention="flash", unroll_layers=True,
        block_size=block_size,
    )
    params = jax.jit(lambda k: gpt.init(k, cfg))(jax.random.key(0))
    idx = jax.random.randint(jax.random.key(1), (batch, PROMPT), 0,
                             cfg.vocab_size, dtype=jnp.int32)

    ms_tok = _slope_ms(params, cfg, idx, n_lo, n_hi)
    return {"batch": batch, "block_size": block_size, "n_layer": n_layer,
            "ms_per_step": round(ms_tok, 3)}


def main_shapes():
    for bs, nl in ((1024, 12), (256, 12), (1024, 6)):
        try:
            rec = run_shape(8, bs, nl)
        except Exception as e:  # noqa: BLE001
            rec = {"block_size": bs, "n_layer": nl, "error": repr(e)[:200]}
        print(json.dumps(rec), flush=True)


if __name__ == "__main__":
    main_shapes() if "--shapes" in sys.argv else main()
