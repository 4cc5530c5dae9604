"""Quantized KV-cache / weight-leaf storage (ISSUE 18 tentpole).

Symmetric per-channel quantization for the serving stack: KV rows are
stored as int8 (or fp8, ``float8_e4m3fn``) with an fp32
*scale plane* living beside the data, and dequantized inside the traced
attention block. One design decision carries the whole PR:

**Power-of-two scales make requantization exactly idempotent.** The
prefill/decode/verify programs slice a slot's lane out of the pool,
dequantize it, run the shared fp32 forward, then requantize the whole
lane on the way back in. With an arbitrary ``amax/qmax`` scale the
round trip ``dequantize → quantize`` is *almost* the identity — the
float division ``amax / (amax/qmax)`` lands within an ulp of ``qmax``
and the re-derived scale within an ulp of the original — and "almost"
would mean every decode step drifts untouched rows by a bit, breaking
both greedy determinism and the migrated-rows-resume-bit-identical
contract procfleet relies on. So the scale is snapped to
``2**ceil(log2(amax / qmax))``: multiplying or dividing a float by a
power of two is exact, the element at ``amax`` maps back into
``(qmax/2, qmax]`` so the re-derived exponent is unchanged, and
``round()`` of an exactly-recovered integer is that integer. Untouched
rows therefore survive any number of requantization round trips
bit-identically; the cost is at most one extra bit of quantization
error, which the tolerance-gated parity policy absorbs (see
docs/architecture.md "Quantized KV cache").

Layout: a quantized cache/lane/entry is the plain ``{"k", "v"}`` dict
grown to ``{"k", "v", "k_scale", "v_scale"}``. Scale planes are
``float32``, one scale per (layer, slot, row, kv_head) whichever way
the pool keeps a row (``generate.cache_leaf_shapes``): beside per-head
data ``(..., KV, hd)`` the data's shape except ``head_dim -> 1``; beside
a row of heads side by side, ``(..., 1, KV x hd)``, a scale a head of the
row's ``(KV, hd)`` view, ``(..., 1, KV)``. Either way every rank-5
slicing program and the ``kv_pool_spec`` head-sharding apply to them
unchanged — under tp the scale planes shard over kv_heads exactly like
the data, on the same axis.

The same primitive quantizes weight leaves per output channel
(``quantize_weight``); the serving weights path itself is the next rung
of the ROADMAP ladder and is exercised here only at unit level.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

__all__ = [
    "KV_DTYPES",
    "KVQuant",
    "SCALE_SUFFIX",
    "data_names",
    "dequantize",
    "dequantize_lane",
    "init_quant_cache",
    "max_abs_logit_error",
    "quantize",
    "quantize_lane",
    "quantize_weight",
    "resolve_kv_dtype",
    "scale_bytes",
    "split_scales",
]

#: the --kv-dtype vocabulary (serve.py, InferenceServer, DecodeEngine)
KV_DTYPES = ("fp32", "int8", "fp8")

#: scale planes are always fp32 — exact power-of-two values up to the
#: full float32 exponent range, independent of the payload dtype
SCALE_DTYPE = jnp.float32

#: cache leaf names carrying quantized payload (scales ride beside them
#: as ``<name>_scale``)
DATA_NAMES = ("k", "v")
SCALE_SUFFIX = "_scale"


@dataclasses.dataclass(frozen=True)
class KVQuant:
    """Hashable quantization descriptor — bound into the jitted program
    families as a trace-time constant (exactly like ``cfg`` and
    ``kv_sharding``), so the dtype IS part of the compile key."""

    name: str        # "int8" | "fp8"
    qdtype: Any      # storage dtype of the payload leaves
    qmax: float      # largest magnitude the payload dtype represents

    def __str__(self) -> str:
        return self.name


def resolve_kv_dtype(name: Optional[str]) -> Optional[KVQuant]:
    """None (store fp32, the byte-identical default path) or a KVQuant."""
    if name is None or name in ("fp32", "float32"):
        return None
    if isinstance(name, KVQuant):
        return name
    if name == "int8":
        return KVQuant("int8", jnp.dtype(jnp.int8), 127.0)
    if name == "fp8":
        dt = jnp.float8_e4m3fn
        return KVQuant("fp8", jnp.dtype(dt), float(jnp.finfo(dt).max))
    raise ValueError(f"unknown kv_dtype {name!r} (choose from {KV_DTYPES})")


def _pow2_scale(amax: jax.Array, qmax: float) -> jax.Array:
    """2**ceil(log2(amax/qmax)) in fp32; 0 where amax == 0 (an all-zero
    channel quantizes to zeros and dequantizes to exact zeros)."""
    amax = amax.astype(SCALE_DTYPE)
    exp = jnp.ceil(jnp.log2(amax / jnp.float32(qmax)))
    return jnp.where(amax > 0, jnp.exp2(exp), jnp.float32(0.0))


def _in_parts(x: jax.Array, parts: int) -> jax.Array:
    """``(..., W)`` as ``(..., parts, W / parts)``: a row's heads apart."""
    return x.reshape(*x.shape[:-1], parts, x.shape[-1] // parts)


def quantize(x: jax.Array, q: KVQuant,
             heads: int = 1) -> Tuple[jax.Array, jax.Array]:
    """Symmetric per-channel quantize over the last axis, or over each of
    ``heads`` equal parts of it (a row that holds that many heads side by
    side: a scale a head, as if each had an axis entry of its own).

    Returns ``(payload, scale)`` with ``payload.shape == x.shape`` in
    ``q.qdtype`` and ``scale.shape == x.shape[:-1] + (heads,)`` in fp32.
    """
    parts = _in_parts(x, heads)
    amax = jnp.max(jnp.abs(parts), axis=-1, keepdims=True)
    scale = _pow2_scale(amax, q.qmax)
    safe = jnp.where(scale > 0, scale, jnp.float32(1.0))
    y = parts.astype(SCALE_DTYPE) / safe
    if q.qdtype == jnp.int8:
        payload = jnp.round(jnp.clip(y, -q.qmax, q.qmax)).astype(jnp.int8)
    else:
        payload = y.astype(q.qdtype)
    return payload.reshape(x.shape), scale[..., 0]


def dequantize(payload: jax.Array, scale: jax.Array, dtype=None) -> jax.Array:
    """payload * scale in ``dtype`` (default fp32), the scale's last axis
    saying how many equal parts of the payload's have a scale each
    (:func:`quantize`). Zero-scale channels hold zero payloads, so the
    product needs no guard."""
    dtype = SCALE_DTYPE if dtype is None else dtype
    parts = _in_parts(payload, scale.shape[-1]).astype(SCALE_DTYPE)
    return (parts * scale[..., None]).reshape(payload.shape).astype(dtype)


def quantize_weight(w: jax.Array, q: KVQuant) -> Tuple[jax.Array, jax.Array]:
    """Per-output-channel quantize of a weight leaf: one scale per index
    of the LAST axis (the output features of every matmul leaf in this
    codebase), reducing over all other axes."""
    axes = tuple(range(w.ndim - 1))
    amax = jnp.max(jnp.abs(w), axis=axes, keepdims=True)
    scale = _pow2_scale(amax, q.qmax)
    safe = jnp.where(scale > 0, scale, jnp.float32(1.0))
    y = w.astype(SCALE_DTYPE) / safe
    if q.qdtype == jnp.int8:
        payload = jnp.round(jnp.clip(y, -q.qmax, q.qmax)).astype(jnp.int8)
    else:
        payload = y.astype(q.qdtype)
    return payload, scale


# ---------------------------------------------------------------------------
# lane / cache structure
# ---------------------------------------------------------------------------


def data_names(cache: Dict[str, jax.Array]) -> Tuple[str, ...]:
    """The payload leaf names of a cache/lane/entry dict (scales are
    ``<name>_scale`` siblings; fp32 dicts have no scale leaves)."""
    return tuple(n for n in sorted(cache) if not n.endswith(SCALE_SUFFIX))


def split_scales(
    cache: Dict[str, jax.Array],
) -> Tuple[Dict[str, jax.Array], Dict[str, jax.Array]]:
    """(payload leaves, scale leaves) of a pool's cache dict."""
    data = {n: a for n, a in cache.items() if not n.endswith(SCALE_SUFFIX)}
    scales = {n: a for n, a in cache.items() if n.endswith(SCALE_SUFFIX)}
    return data, scales


def init_quant_cache(cfg, n_slots: int, q: KVQuant) -> Dict[str, jax.Array]:
    """The quantized analogue of ``generate.init_cache``: zeroed payload
    buffers in ``q.qdtype`` plus zeroed fp32 scale planes."""
    from mingpt_distributed_tpu.models.generate import (
        cache_leaf_shapes, row_heads)

    out: Dict[str, jax.Array] = {}
    for n, shape in cache_leaf_shapes(cfg, n_slots).items():
        out[n] = jnp.zeros(shape, q.qdtype)
        out[n + SCALE_SUFFIX] = jnp.zeros(
            shape[:-1] + (row_heads(cfg),), SCALE_DTYPE)
    return out


def quantize_lane(
    lane: Dict[str, jax.Array], q: KVQuant, heads: int = 1,
) -> Dict[str, jax.Array]:
    """fp32 ``{"k", "v"}`` lane -> quantized lane with scale planes;
    ``heads`` the heads a row holds side by side (``generate.row_heads``)."""
    out: Dict[str, jax.Array] = {}
    for n in DATA_NAMES:
        payload, scale = quantize(lane[n], q, heads)
        out[n] = payload
        out[n + SCALE_SUFFIX] = scale
    return out


def dequantize_lane(
    qlane: Dict[str, jax.Array], dtype=None,
) -> Dict[str, jax.Array]:
    """Quantized lane -> fp32 (or ``dtype``) ``{"k", "v"}`` lane the
    shared forward blocks consume."""
    return {
        n: dequantize(qlane[n], qlane[n + SCALE_SUFFIX], dtype)
        for n in DATA_NAMES
    }


def scale_bytes(cfg, n_slots: int) -> int:
    """Bytes the scale planes add for this geometry (both K and V),
    for capacity planning."""
    elems = cfg.n_layer * n_slots * cfg.block_size * cfg.kv_heads
    return 2 * elems * jnp.dtype(SCALE_DTYPE).itemsize


# ---------------------------------------------------------------------------
# quality probe
# ---------------------------------------------------------------------------


def max_abs_logit_error(params, cfg, tokens, q: KVQuant) -> float:
    """Max |logit(fp32 cache) - logit(quantized roundtrip cache)| over a
    prompt — the quantization-quality number the selftest samples into
    the ``mingpt_serve_quant_logit_err_max`` gauge.

    Runs the same single-sequence cached forward twice: once against the
    exact fp32 cache and once against that cache pushed through a
    quantize/dequantize round trip, so the delta isolates KV storage
    precision (weights and activations stay fp32 in both runs)."""
    import numpy as np

    from mingpt_distributed_tpu.models import generate as gen

    ids = jnp.asarray(tokens, jnp.int32)[None]
    length = ids.shape[1]
    cache = gen.init_cache(cfg, 1)
    _, cache = gen._forward_cached_hidden(params, ids, cache, 0, cfg)
    rt = dequantize_lane(quantize_lane(cache, q, gen.row_heads(cfg)))
    rt = {n: rt[n].astype(cache[n].dtype) for n in DATA_NAMES}
    # re-run only the last token against each cache: rows 0..length-2
    # are read (exact vs round-tripped), the rewritten last row is fp32
    # in both runs, so the delta isolates KV storage precision
    last = ids[:, length - 1:length]
    hidden_exact, _ = gen._forward_cached_hidden(
        params, last, {n: cache[n] for n in DATA_NAMES}, length - 1, cfg)
    hidden_rt, _ = gen._forward_cached_hidden(
        params, last, rt, length - 1, cfg)
    exact = gen._head_logits(params, hidden_exact, cfg)
    approx = gen._head_logits(params, hidden_rt, cfg)
    return float(np.max(np.abs(np.asarray(exact) - np.asarray(approx))))
