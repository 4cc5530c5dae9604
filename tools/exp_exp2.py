#!/usr/bin/env python
"""VPU microbench: exp vs exp2 (round-5 lever #3, BASELINE.md).

Bounds the win of rebasing the flash kernels' online softmax to base 2
BEFORE touching them: log2(e) folds into the attention scale constant, so
the rebase replaces every exp with exp2 at zero extra multiplies — the win
is exactly (cost(exp) - cost(exp2)) per score element, if any.

Method (the tools/exp_flash.py discipline): a Pallas kernel holds a block
in VMEM and applies the op REPS times via fori_loop — chained work inside
one dispatch, so per-dispatch latency and HBM bandwidth both cancel.
exp(-|y|) keeps values in (0, 1] so the chain neither over- nor
underflows.
"""
import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BLOCK = 512
REPS = 64


def _kernel(x_ref, o_ref, *, op):
    y = x_ref[...]

    def body(i, y):
        return op(-jnp.abs(y) - 0.01)

    o_ref[...] = jax.lax.fori_loop(0, REPS, body, y)


def run(op, name, nblocks=64):
    x = jax.random.normal(jax.random.key(0), (nblocks, BLOCK, BLOCK),
                          jnp.float32)
    fn = jax.jit(lambda x: pl.pallas_call(
        functools.partial(_kernel, op=op),
        grid=(nblocks,),
        in_specs=[pl.BlockSpec((1, BLOCK, BLOCK), lambda i: (i, 0, 0))],
        out_specs=pl.BlockSpec((1, BLOCK, BLOCK), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        interpret=jax.default_backend() != "tpu",
    )(x))
    for _ in range(2):
        o = fn(x)
    float(jnp.sum(o))
    n = 5
    t0 = time.perf_counter()
    for _ in range(n):
        o = fn(x)
    s = float(jnp.sum(o))
    assert s == s
    dt = (time.perf_counter() - t0) / n
    elems = nblocks * BLOCK * BLOCK * REPS
    return {"op": name, "ms": round(dt * 1e3, 3),
            "gexp_per_sec": round(elems / dt / 1e9, 2)}


def main():
    recs = [run(jnp.exp, "exp"), run(jnp.exp2, "exp2"),
            run(lambda y: jnp.exp2(y * 1.4426950408889634), "exp2*log2e")]
    for r in recs:
        print(json.dumps(r), flush=True)
    base, reb = recs[0]["ms"], recs[1]["ms"]
    # per-step bound: the r4 trace put ~20 ms/step of flash-kernel time at
    # b8; exp is a fraction of that. Scale the measured ratio onto the
    # kernels' score-element count at the bench config (b16: 12 layers *
    # 16*12 bh * (1024^2/2) scores * 3 kernels fwd+dq+dkv, 2 exps each).
    print(json.dumps({"what": "exp2 vs exp speedup",
                      "ratio": round(base / reb, 3) if reb else None}),
          flush=True)


if __name__ == "__main__":
    main()
