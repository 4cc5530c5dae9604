#!/usr/bin/env python
"""On-chip A/B: native-(B,T,D) flash kernels vs the transpose path.

Round-5 lever #1: the (B,T,H,hd)<->(B*H,T,hd) transposes at the
custom-vjp boundary. FLASH_LAYOUT=bh forces the old path; auto takes the
native-layout kernels. End-to-end wall clock only.
"""
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

from mingpt_distributed_tpu.config import GPTConfig, OptimizerConfig
from mingpt_distributed_tpu.models import gpt
from mingpt_distributed_tpu.training.optimizer import make_optimizer
from mingpt_distributed_tpu.training.trainer import make_train_step

SEQ = 1024
PEAK_TFLOPS = 197.0
FLOPS_TOK = 854438400


def run(batch, layout, loss_chunks=8):
    os.environ["FLASH_LAYOUT"] = layout
    cfg = GPTConfig.make(
        model_type="gpt2",
        embd_pdrop=0.0, resid_pdrop=0.0, attn_pdrop=0.0,
        dtype="bfloat16", attention="flash", unroll_layers=True,
        loss_chunks=loss_chunks, block_size=SEQ,
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
        jax.random.key(1), (batch, SEQ), 0, cfg.vocab_size, dtype=jnp.int32
    )
    rng = jax.random.key(2)
    for _ in range(3):
        state, m = step_fn(state, (tokens, tokens), rng)
    float(jax.device_get(m["loss"]))
    n = 20
    t0 = time.perf_counter()
    for _ in range(n):
        state, m = step_fn(state, (tokens, tokens), rng)
    loss = float(jax.device_get(m["loss"]))
    dt = time.perf_counter() - t0
    assert loss == loss
    sps = n / dt
    tps = sps * batch * SEQ
    return {"batch": batch, "layout": layout, "loss_chunks": loss_chunks,
            "ms_step": round(1e3 / sps, 2),
            "steps_per_sec": round(sps, 3), "tok_per_sec": round(tps, 1),
            "mfu": round(tps * FLOPS_TOK / (PEAK_TFLOPS * 1e12), 4)}


def main():
    for batch in (16, 32):
        for layout in ("bh", "auto"):
            try:
                rec = run(batch, layout)
            except Exception as e:  # noqa: BLE001 — record and continue
                rec = {"batch": batch, "layout": layout,
                       "error": repr(e)[:200]}
            print(json.dumps(rec), flush=True)


def main_ab():
    """Fused-vs-split backward A/B (round-5 chip validation of
    _dqkv_kernel_btd): b32 both ways, then b16 fused. Exits non-zero when
    NO run succeeded, so pure error lines never read as a pass."""
    ok = 0
    for batch, fused in ((32, True), (32, False), (16, True)):
        os.environ["FLASH_FUSED_BWD"] = "1" if fused else "0"
        try:
            rec = run(batch, "auto")
            rec["fused_bwd"] = fused
            ok += 1
        except Exception as e:  # noqa: BLE001
            rec = {"batch": batch, "fused_bwd": fused,
                   "error": repr(e)[:300]}
        print(json.dumps(rec), flush=True)
    os.environ.pop("FLASH_FUSED_BWD", None)
    if not ok:
        sys.exit(1)


if __name__ == "__main__":
    main_ab() if "--ab" in sys.argv else main()
