"""Multi-head causal self-attention — the einsum reference implementation.

Replaces the reference's delegation to torch's fused ``nn.MultiheadAttention``
(/root/reference/mingpt/model.py:147-165). Two deliberate departures:

* **Correct causal masking.** The reference registered a float tril-of-ones
  and passed it as an additive attention mask, which *fails to mask* future
  positions (bug B6, model.py:142-145,164). Here causality is a boolean
  ``query >= key`` comparison materialised lazily inside the kernel — XLA
  fuses it into the softmax; no (T, T) buffer is stored per layer.
* **No fused-QKV opacity.** q/k/v are explicit arrays shaped
  ``(batch, seq, heads, head_dim)``, supporting grouped-query attention
  (n_kv_head < n_head) and RoPE for the Llama retrofit.

This einsum path is the *oracle*: the Pallas flash-attention kernel
(ops/flash_attention.py) and the ring-attention path (parallel/ring_attention.py)
are tested for parity against it.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

NEG_INF = -1e30  # large-finite instead of -inf: keeps softmax NaN-free in bf16


def softcap(x: jax.Array, cap: Optional[float]) -> jax.Array:
    """Gemma-2-style logit soft-capping: cap * tanh(x / cap); identity when
    cap is None/0. One definition shared by the oracle, the LM-head paths
    and decode (the Pallas kernels inline it — kernel code can't call out)."""
    if not cap:
        return x
    return cap * jnp.tanh(x / cap)


def repeat_kv(k: jax.Array, n_rep: int) -> jax.Array:
    """Expand KV heads for grouped-query attention: (B,S,KV,hd)->(B,S,KV*rep,hd)."""
    if n_rep == 1:
        return k
    b, s, kv, hd = k.shape
    return jnp.broadcast_to(k[:, :, :, None, :], (b, s, kv, n_rep, hd)).reshape(
        b, s, kv * n_rep, hd
    )


def causal_attention(
    q: jax.Array,  # (B, T, H, hd)
    k: jax.Array,  # (B, S, KV, hd)
    v: jax.Array,  # (B, S, KV, hd)
    *,
    attn_pdrop: float = 0.0,
    dropout_key: Optional[jax.Array] = None,
    deterministic: bool = True,
    kv_offset: int | jax.Array = 0,
    window: Optional[int] = None,
    logit_softcap: Optional[float] = None,
) -> jax.Array:
    """Causal scaled-dot-product attention, softmax in float32.

    ``kv_offset`` is the absolute position of q[0] relative to k[0] — 0 for
    training (S == T, self-attention), the cache length during incremental
    decoding (so a single query attends to all cached keys). A ``(B,)``
    offset gives every batch row its own (the serving decode step, whose
    lanes stand at different positions): the mask then differs a row, and
    the window and softcap keep their meaning.
    ``window`` enables sliding-window (banded) attention: each query sees
    only the last ``window`` positions, itself included (Mistral-style;
    ``None`` = full causal). ``logit_softcap`` applies Gemma-2-style
    ``cap * tanh(logits / cap)`` to the scores before masking.
    Returns (B, T, H, hd) in q's dtype.
    """
    b, t, h, hd = q.shape
    kv = k.shape[2]
    k = repeat_kv(k, h // kv)
    v = repeat_kv(v, h // kv)

    scale = 1.0 / jnp.sqrt(jnp.asarray(hd, dtype=jnp.float32))
    # (B, H, T, S) logits in float32
    logits = jnp.einsum(
        "bthd,bshd->bhts", q, k, preferred_element_type=jnp.float32
    ) * scale
    logits = softcap(logits, logit_softcap)

    s = k.shape[1]
    # absolute query positions: (T, 1), or (B, T, 1) under a (B,) offset
    q_pos = jnp.arange(t)[:, None] + jnp.asarray(kv_offset)[..., None, None]
    k_pos = jnp.arange(s)[None, :]
    allowed = q_pos >= k_pos  # (T, S) boolean — the B6 fix
    if window is not None:
        allowed = allowed & (q_pos - k_pos < window)
    logits = jnp.where(allowed[..., None, :, :], logits, NEG_INF)

    probs = jax.nn.softmax(logits, axis=-1)
    if not deterministic and attn_pdrop > 0.0:
        assert dropout_key is not None
        keep = 1.0 - attn_pdrop
        mask = jax.random.bernoulli(dropout_key, keep, probs.shape)
        probs = jnp.where(mask, probs / keep, 0.0)

    out = jnp.einsum(
        "bhts,bshd->bthd", probs.astype(v.dtype), v,
        preferred_element_type=jnp.float32,
    )
    return out.astype(q.dtype)


def rope_tables(
    positions: jax.Array, head_dim: int, theta: float = 10000.0
) -> Tuple[jax.Array, jax.Array]:
    """cos/sin tables for rotary embeddings at the given absolute positions,
    (P,) or a row of them a batch entry (B, P).

    Returns (..., P, head_dim/2) float32 each, split-half (rotate-half)
    convention.
    """
    half = head_dim // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    angles = positions.astype(jnp.float32)[..., None] * freqs
    return jnp.cos(angles), jnp.sin(angles)


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """Rotate (B, T, H, hd) by per-position tables (T, hd/2), or by a
    table a batch entry (B, T, hd/2)."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    cos = cos[..., None, :].astype(jnp.float32)  # the heads' axis
    sin = sin[..., None, :].astype(jnp.float32)
    x1f, x2f = x1.astype(jnp.float32), x2.astype(jnp.float32)
    out = jnp.concatenate(
        [x1f * cos - x2f * sin, x2f * cos + x1f * sin], axis=-1
    )
    return out.astype(x.dtype)
