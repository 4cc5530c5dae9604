#!/usr/bin/env python
"""On-chip flash-kernel microbench: N iterations are chained INSIDE one
jit via lax.fori_loop (each iteration depends on the last), so per-dispatch
latency amortises exactly as in the train-step bench.
Reports per-call ms for fwd and fwd+bwd at the bench shape (gpt2: bh=96,
t=1024, hd=64) across block sizes, plus an MXU matmul reference."""
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

from mingpt_distributed_tpu.ops import flash_attention as fa

BH, T, HD = 96, 1024, 64
INNER = 10


def timed(jfn, *args, n=5, warm=2):
    for _ in range(warm):
        out = jfn(*args)
    float(jnp.sum(out))  # real D2H sync
    t0 = time.perf_counter()
    for _ in range(n):
        out = jfn(*args)
    s = float(jnp.sum(out))
    dt = time.perf_counter() - t0
    assert s == s
    return dt / (n * INNER) * 1e3  # ms per inner iteration


def main():
    ks = jax.random.split(jax.random.key(0), 3)
    q = jax.random.normal(ks[0], (BH, T, HD), jnp.bfloat16)
    k = jax.random.normal(ks[1], (BH, T, HD), jnp.bfloat16)
    v = jax.random.normal(ks[2], (BH, T, HD), jnp.bfloat16)
    scale = 1.0 / (HD ** 0.5)
    flops_fwd = 2 * 2 * BH * T * T * HD / 2

    # MXU reference: chained square matmul
    a = jax.random.normal(ks[0], (8192, 2304), jnp.bfloat16)
    w = jax.random.normal(ks[1], (2304, 2304), jnp.bfloat16) * 0.01

    @jax.jit
    def mm_loop(a, w):
        return jax.lax.fori_loop(
            0, INNER, lambda i, x: jnp.tanh(x @ w), a)

    ms = timed(mm_loop, a, w)
    mm_flops = 2 * 8192 * 2304 * 2304
    print(json.dumps({"what": "matmul 8192x2304x2304", "ms": round(ms, 3),
                      "tflops": round(mm_flops / ms / 1e9, 1)}), flush=True)

    for block in (128, 256, 512):
        @jax.jit
        def fwd_loop(q, k, v):
            def body(i, qc):
                o, _ = fa.flash_with_lse(qc, k, v, scale, block, True,
                                         None, None, 0)
                return (qc + o * 1e-6).astype(qc.dtype)
            return jax.lax.fori_loop(0, INNER, body, q)

        ms = timed(fwd_loop, q, k, v)
        print(json.dumps({"what": f"fwd block={block}", "ms": round(ms, 3),
                          "tflops": round(flops_fwd / ms / 1e9, 1)}),
              flush=True)

        def loss(qc, k, v):
            o, _ = fa.flash_with_lse(qc, k, v, scale, block, True, None,
                                     None, 0)
            return jnp.sum(jnp.square(o.astype(jnp.float32)))

        @jax.jit
        def bwd_loop(q, k, v):
            def body(i, qc):
                g = jax.grad(loss)(qc, k, v)
                return (qc + g * 1e-6).astype(qc.dtype)
            return jax.lax.fori_loop(0, INNER, body, q)

        msb = timed(bwd_loop, q, k, v)
        print(json.dumps({"what": f"fwd+bwd block={block}",
                          "ms": round(msb, 3),
                          "tflops": round(4 * flops_fwd / msb / 1e9, 1)}),
              flush=True)


if __name__ == "__main__":
    main()
