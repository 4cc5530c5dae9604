"""Operations and bytes computed from shapes: the numerators of MFU and of
a kernel's roofline share. Kept with the benchmark so that no PR which
claims a gain can change what a FLOP is.

All functions take the published sizes (the keys of a configuration file),
not the program's config object.
"""

from __future__ import annotations

from typing import Mapping


def _ffn(config: Mapping) -> int:
    return int(config.get("n_inner") or 4 * config["n_embd"])


def train_flops_per_token(config: Mapping, seq_len: int) -> float:
    """Forward plus backward FLOPs a token, the 6·N model with the attention
    term. Copied from ``training/metrics.flops_per_token`` and kept as it is:
    the attention term is the full T² (12·L·d·T), not the causal half, and the
    LM head counts, so MFU here continues the repo's older figures. Recomputed
    operations (remat, the chunked head's second matmul) do not count.
    854.4 MFLOP for GPT-2 124M and 10.27 GFLOP for GPT-2 XL at T=1024."""
    d, n_layer, vocab = config["n_embd"], config["n_layer"], config["vocab_size"]
    per_layer = 4 * d * d + 2 * d * _ffn(config)   # qkv, out, fc, proj
    n_matmul = n_layer * per_layer + d * vocab     # embeddings are gathers
    return 6.0 * n_matmul + 12.0 * n_layer * d * seq_len


def flash_train_flops(config: Mapping, batch: int, seq_len: int) -> float:
    """FLOPs the flash kernels need for one training step over ``batch``
    sequences: per layer and head, seven T×T×hd matmuls (forward QKᵀ and PV;
    backward the recomputed QKᵀ, dV, dP, dQ, dK), two FLOPs a multiply-add,
    halved for the causal mask: 7·B·H·T²·hd a layer. Only published heads
    count: the zero heads that pad an odd head count are waste. The kernels
    are compute-bound (their bytes are 8 reads and writes of B·T·d)."""
    d = config["n_embd"]                           # = n_head * head_dim
    return 7.0 * config["n_layer"] * batch * d * float(seq_len) ** 2


def flash_train_bytes(config: Mapping, batch: int, seq_len: int) -> float:
    """HBM bytes the same kernels must move: q, k, v, o, do read and dq, dk,
    dv written once each in bf16, per layer."""
    return 8.0 * 2 * config["n_layer"] * batch * seq_len * config["n_embd"]


def kv_row_bytes(config: Mapping, dtype_bytes: int = 2) -> int:
    """Bytes one cached position takes: K and V for every layer."""
    return 2 * config["n_layer"] * config["n_embd"] * dtype_bytes
