#!/usr/bin/env python
"""Sampling entry point: load a snapshot, generate text.

The reference exposes generation only as a method (GPT.generate,
/root/reference/mingpt/model.py:322-356) with no driver (upstream minGPT's
chargpt project had one; the fork dropped it). This CLI completes the
train -> sample loop: it rebuilds the dataset (for the char vocab), restores
the snapshot written by train.py, and decodes with the KV-cached compiled
generator.

Usage:
  python sample.py --prompt "O God, O God!" --max-new-tokens 200 \
      [--config gpt2_config.yaml] [--temperature 0.8] [--top-k 40] [--greedy]
      [section.key=value ...]
"""

from __future__ import annotations

import argparse
import dataclasses
import sys


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--config", default="gpt2_config.yaml")
    p.add_argument("--prompt", default="\n")
    p.add_argument("--max-new-tokens", type=int, default=200)
    p.add_argument("--temperature", type=float, default=1.0)
    p.add_argument("--top-k", type=int, default=None)
    p.add_argument("--top-p", type=float, default=None,
                   help="nucleus sampling: keep the smallest set of tokens "
                        "with cumulative probability >= top_p")
    p.add_argument("--greedy", action="store_true",
                   help="argmax decoding (default: sample)")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("overrides", nargs="*")
    args = p.parse_args(argv)

    import jax

    from mingpt_distributed_tpu.config import load_config
    from mingpt_distributed_tpu.data.token_dataset import make_dataset
    from mingpt_distributed_tpu.models import generate as gen
    from mingpt_distributed_tpu.training import checkpoint as ckpt_lib
    from mingpt_distributed_tpu.utils import startup

    startup.enable_compile_cache()
    cfg = load_config(args.config, args.overrides)
    # same tokenizer dispatch as train.py: the snapshot being sampled was
    # trained on this config's vocabulary
    dataset = make_dataset(cfg.data_config)
    gpt_cfg = dataclasses.replace(
        cfg.gpt_config,
        vocab_size=dataset.vocab_size,
        block_size=dataset.block_size,
        # inference: no dropout
        embd_pdrop=0.0, resid_pdrop=0.0, attn_pdrop=0.0,
    )

    path = cfg.trainer_config.snapshot_path or ckpt_lib.DEFAULT_SNAPSHOT_PATH
    # shared restore helper (also used by serve.py): msgpack-vs-Orbax
    # backend dispatch by suffix, params-only
    snap = ckpt_lib.restore_inference_params(path, gpt_cfg)
    if snap is None:
        print(f"no snapshot at {path}; train first (python train.py)",
              file=sys.stderr)
        return 1
    params = jax.device_put(snap.params)
    print(f"loaded snapshot step {snap.step} from {path}", file=sys.stderr)

    idx = dataset.encode(args.prompt)[None, :]
    out = gen.generate(
        params, gpt_cfg, idx, args.max_new_tokens,
        temperature=args.temperature,
        do_sample=not args.greedy,
        top_k=args.top_k,
        top_p=args.top_p,
        rng=jax.random.key(args.seed),
    )
    print(dataset.decode(jax.device_get(out)[0]))
    return 0


if __name__ == "__main__":
    sys.exit(main())
