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

import math
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


def as_heads(rows: jax.Array, head_dim: int) -> jax.Array:
    """Rows ``(..., heads, size)`` as the attention reads them, ``(..., KV,
    head_dim)``: what came in where a head has an axis entry of its own,
    and the same numbers where a cache keeps a position's heads side by
    side, ``(..., 1, KV x head_dim)`` (``generate.cache_leaf_shapes``). A
    view taken where the rows are read, on rows already cut out of the
    cache: never of a buffer."""
    if rows.shape[-1] == head_dim:
        return rows
    return rows.reshape(*rows.shape[:-2], -1, head_dim)


def spread_queries(q: jax.Array, kv: int) -> jax.Array:
    """(B, T, H, hd) -> (B, T, H, KV * hd): each query head's values at its
    KV head's place in a row, zeros at the others'."""
    b, t, h, hd = q.shape
    own = jnp.eye(kv, dtype=q.dtype)                        # (KV, KV)
    wide = q.reshape(b, t, kv, h // kv, 1, hd) * own[:, None, :, None]
    return wide.reshape(b, t, h, kv * hd)


def own_part(out: jax.Array, kv: int) -> jax.Array:
    """(B, T, H, KV * hd) -> (B, T, H, hd): of what a query head averaged
    over whole rows, its own KV head's part."""
    b, t, h, e = out.shape
    parts = out.reshape(b, t, kv, h // kv, kv, e // kv)
    return jnp.stack([parts[:, :, k, :, k] for k in range(kv)],
                     2).reshape(b, t, h, e // kv)


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


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array,
               interleave: bool = False) -> jax.Array:
    """Rotate (B, T, H, hd) by per-position tables (T, hd/2), or by a
    table a batch entry (B, T, hd/2). Pair i is the halves' ``(x[i],
    x[i + hd/2])`` or, under ``interleave`` (DeepSeek's ``rope_interleave``),
    the neighbours ``(x[2i], x[2i + 1])``; either way the rotated pair goes
    back where it came from."""
    half = x.shape[-1] // 2
    if interleave:
        x1, x2 = x[..., 0::2], x[..., 1::2]
    else:
        x1, x2 = x[..., :half], x[..., half:]
    cos = cos[..., None, :].astype(jnp.float32)  # the heads' axis
    sin = sin[..., None, :].astype(jnp.float32)
    x1f, x2f = x1.astype(jnp.float32), x2.astype(jnp.float32)
    r1, r2 = x1f * cos - x2f * sin, x2f * cos + x1f * sin
    if interleave:
        out = jnp.stack([r1, r2], axis=-1).reshape(x.shape)
    else:
        out = jnp.concatenate([r1, r2], axis=-1)
    return out.astype(x.dtype)


#: cached rows a step of a walk over a slice attends: ``latent_attention``'s
#: prefill loop, and a decode step's walk to its lanes' frontier
LATENT_KV_BLOCK = 1024


def _walked(s: int) -> bool:
    """Whether an ``s``-row slice is attended a block at a time: more than
    one block, and a whole number of them."""
    return s > LATENT_KV_BLOCK and s % LATENT_KV_BLOCK == 0


def step_rows_read(s: int, frontier):
    """Rows of an ``s``-row slice a decode step reads
    (:func:`causal_attend_step`, :func:`latent_attend_step`) when no lane
    that counts stands past ``frontier``: whole blocks of LATENT_KV_BLOCK
    up to it. A slice of one block or less (or of no whole number of them)
    is read whole, in one pass. The one rule for the program and for
    whoever counts what it read: NumPy or ``jnp`` alike."""
    if not _walked(s):
        return s
    blk = LATENT_KV_BLOCK
    return (frontier + blk - 1) // blk * blk


def _attend_step(take, score, weigh, own, own_value, s: int, frontier,
                 reread: bool = False):
    """One softmax in two parts for a decode step: a query's cached rows,
    read as they lie, and its own new row beside them, which starts the
    running maximum and sum (its score is finite, so a masked row's
    ``exp(NEG_INF - m)`` is 0 exactly and a block of masked rows changes
    nothing). ``take(start, size)`` cuts rows ``[start, start + size)``
    out of the whole cache buffers (inside the loop's body: a layer's
    slice taken before the loop would be copied whole into it);
    ``score(rows, k_pos)`` gives their (..., size) float32 scores, NEG_INF
    where masked; ``weigh(p, rows)`` the (..., d) float32 sum of their
    values under ``p``. ``own`` (...,) float32 and ``own_value`` (..., d)
    float32 (broadcastable) are the new row's. ``reread``: ``score`` and
    ``weigh`` read the same rows (a latent is key and value), so a walk's
    block is made a buffer of its own: small enough for the chip's
    compiler to keep in fast memory, and the cache's rows then leave HBM
    once a step and not twice (PERF.md, PR 33: 6.0 -> 4.5 ms).
    Returns (..., d) float32."""
    if not _walked(s):
        rows = take(0, s)
        z = score(rows, jnp.arange(s))
        m = jnp.maximum(z.max(-1), own)
        p, p_own = jnp.exp(z - m[..., None]), jnp.exp(own - m)
        acc = weigh(p, rows) + p_own[..., None] * own_value
        return acc / (p.sum(-1) + p_own)[..., None]

    blk = LATENT_KV_BLOCK

    def step(i, carry):
        m, l, acc = carry
        rows = take(i * blk, blk)
        if reread:
            rows = jax.lax.optimization_barrier(rows)
        z = score(rows, i * blk + jnp.arange(blk))
        m_new = jnp.maximum(m, z.max(-1))
        p, fix = jnp.exp(z - m_new[..., None]), jnp.exp(m - m_new)
        return (m_new, l * fix + p.sum(-1),
                acc * fix[..., None] + weigh(p, rows))

    _, l, acc = jax.lax.fori_loop(
        0, step_rows_read(s, frontier) // blk, step,
        (own, jnp.ones_like(own),
         jnp.broadcast_to(own_value, own.shape + own_value.shape[-1:])))
    return acc / l[..., None]


def _layer_rows(buf: jax.Array, layer: int, start, size: int) -> jax.Array:
    """Rows ``[start, start + size)`` of layer ``layer`` out of a whole
    ``(L, B, S, heads, size)`` cache buffer: (B, size, heads, size)."""
    return jax.lax.dynamic_slice(
        buf, (layer, 0, start, 0, 0), (1, buf.shape[1], size) + buf.shape[3:]
    )[0]


@jax.named_scope("cached_attn")
def causal_attend_step(
    q: jax.Array,         # (B, 1, H, hd): one query a lane
    k_cache: jax.Array,   # (L, B, S, KV, hd) or (L, B, S, 1, KV x hd): whole,
    v_cache: jax.Array,   #   as it lies, the new rows not yet written
    layer: int,
    k_new: jax.Array,     # (B, 1, ...): each lane's own new row, shaped as
    v_new: jax.Array,     #   the cache's rows
    positions: jax.Array,  # (B,): where each lane's query stands
    *,
    frontier=None,
    window: Optional[int] = None,
    logit_softcap: Optional[float] = None,
) -> jax.Array:
    """:func:`causal_attention` for the serving decode step, the cache read
    as it lies: lane ``b`` attends rows ``[0, positions[b])`` of its slice
    of layer ``layer`` out of the cache, and its own row from ``k_new``,
    ``v_new``, under one softmax in two parts (:func:`_attend_step`).
    Nothing of the slice's size is selected, copied or written. ``window``
    and ``logit_softcap`` as in ``causal_attention`` (the own row is always
    inside the window). The slice is read as far as
    ``step_rows_read(S, frontier)`` rows (``frontier``: the furthest
    position of a lane whose output counts; None, the furthest of all).

    A cache that keeps each head an axis entry of its own is scored a KV
    head at a time, the grouped queries beside their head. A cache that
    keeps a position's heads side by side (``generate.cache_leaf_shapes``:
    heads narrower than a lane tile, whole tiles together) is read whole
    rows at a time, as a
    hybrid stack's sparse rows are (``ops/sparse_attention.py``): the
    queries go to the rows' width (:func:`spread_queries`), one matmul
    scores every head against the rows where they lie, and each head keeps
    its own part of what it averaged (:func:`own_part`): the per-head sums
    with as many zero products again as there are other heads. Viewing the
    rows as ``(KV, hd)`` instead made the chip's compiler lay every slice
    out again, positions minor, before it scored it (compile rehearsal,
    PR 39: a copy and a float32 convert of a layer's slice, each leaf).
    Returns (B, 1, H, hd) in q's dtype."""
    b, _, h, hd = q.shape
    s, kv = k_cache.shape[2], math.prod(k_cache.shape[3:]) // hd
    side_by_side = k_cache.shape[3] != kv
    if frontier is None:
        frontier = jnp.max(positions)
    scale = 1.0 / jnp.sqrt(jnp.asarray(hd, dtype=jnp.float32))
    if side_by_side:
        # the queries to the rows' width: (B, H, KV x hd)
        qg = spread_queries(q, kv)[:, 0]
        by_q, by_rows, by_new, scored = "bhw", "bsw", "bw", "bh"
    else:
        # grouped queries beside their KV head: the cache is never repeated
        qg = q.reshape(b, kv, h // kv, hd)
        by_q, by_rows, by_new, scored = "bkgd", "bskd", "bkd", "bkg"

    def take(start, size):
        rows = (_layer_rows(k_cache, layer, start, size),
                _layer_rows(v_cache, layer, start, size))
        return tuple(r[:, :, 0] for r in rows) if side_by_side else rows

    def score(rows, k_pos):
        z = softcap(jnp.einsum(
            f"{by_q},{by_rows}->{scored}s", qg, rows[0],
            preferred_element_type=jnp.float32) * scale, logit_softcap)
        behind = positions[:, None] - k_pos[None, :]      # (B, S')
        allowed = behind > 0
        if window is not None:
            allowed = allowed & (behind < window)
        allowed = allowed[:, None, :] if side_by_side \
            else allowed[:, None, None, :]
        return jnp.where(allowed, z, NEG_INF)

    def weigh(p, rows):
        return jnp.einsum(f"{scored}s,{by_rows}->{by_q}",
                          p.astype(rows[1].dtype), rows[1],
                          preferred_element_type=jnp.float32)

    own = softcap(jnp.einsum(
        f"{by_q},{by_new}->{scored}", qg,
        k_new[:, 0, 0] if side_by_side else k_new[:, 0],
        preferred_element_type=jnp.float32) * scale, logit_softcap)
    # the own row's value beside the scores' axes: (B, 1, W), (B, KV, 1, hd)
    v_own = v_new[:, 0] if side_by_side else v_new[:, 0, :, None]
    out = _attend_step(take, score, weigh, own, v_own.astype(jnp.float32),
                       s, frontier)
    if side_by_side:
        return own_part(out[:, None], kv).astype(q.dtype)
    return out.reshape(b, 1, h, hd).astype(q.dtype)


@jax.named_scope("latent_attn")
def latent_attend_step(
    q_lat: jax.Array,         # (B, 1, H, r): queries absorbed through W_UK
    q_pe: jax.Array,          # (B, 1, H, e): their rotated rope part
    latent_cache: jax.Array,  # (L, B, S, 1, r): whole, the new rows not yet
    pe_cache: jax.Array,      # (L, B, S, 1, e)   written
    layer: int,
    latent_new: jax.Array,    # (B, 1, 1, r): each lane's own new latent
    pe_new: jax.Array,        # (B, 1, 1, e): and rotated rope key
    positions: jax.Array,     # (B,)
    *,
    frontier=None,
    scale: float,
) -> jax.Array:
    """:func:`latent_attention` for the serving decode step, the cache read
    as it lies, as :func:`causal_attend_step` reads a per-head one: the own
    row's score is ``q_lat . latent_new + q_pe . pe_new``, its value the
    new latent. Returns the heads' averaged latents (B, 1, H, r)."""
    s = latent_cache.shape[2]
    if frontier is None:
        frontier = jnp.max(positions)
    q_lat, q_pe = q_lat[:, 0], q_pe[:, 0]                 # (B, H, .)

    def take(start, size):
        return (_layer_rows(latent_cache, layer, start, size)[:, :, 0],
                _layer_rows(pe_cache, layer, start, size)[:, :, 0])

    def score(rows, k_pos):
        z = jnp.einsum("bhr,bsr->bhs", q_lat, rows[0],
                       preferred_element_type=jnp.float32)
        z = z + jnp.einsum("bhe,bse->bhs", q_pe, rows[1],
                           preferred_element_type=jnp.float32)
        allowed = k_pos[None, :] < positions[:, None]     # (B, S')
        return jnp.where(allowed[:, None, :], z * scale, NEG_INF)

    def weigh(p, rows):
        return jnp.einsum("bhs,bsr->bhr", p.astype(rows[0].dtype), rows[0],
                          preferred_element_type=jnp.float32)

    new, pe = latent_new[:, 0, 0], pe_new[:, 0, 0]        # (B, r), (B, e)
    own = (jnp.einsum("bhr,br->bh", q_lat, new,
                      preferred_element_type=jnp.float32)
           + jnp.einsum("bhe,be->bh", q_pe, pe,
                        preferred_element_type=jnp.float32)) * scale
    out = _attend_step(take, score, weigh, own,
                       new[:, None].astype(jnp.float32), s, frontier,
                       reread=True)
    return out[:, None].astype(q_lat.dtype)


@jax.named_scope("latent_attn")
def latent_attention(
    q_lat: jax.Array,   # (B, T, H, r): queries absorbed through W_UK
    q_pe: jax.Array,    # (B, T, H, e): rotated rope part of the queries
    latent: jax.Array,  # (B, S, 1, r): the cached normed latents
    k_pe: jax.Array,    # (B, S, 1, e): the cached rotated shared rope key
    *,
    kv_offset: int | jax.Array,
    scale: float,
) -> jax.Array:
    """Causal attention against a latent (MLA) cache in its absorbed form:
    a key is ``[latent ; rope key]``, shared by all heads, and the values
    are the latents themselves, so the cache is never expanded to per-head
    keys and values. Returns the heads' averaged latents (B, T, H, r); the
    caller takes them through W_UV. ``kv_offset`` as in
    ``causal_attention``: a scalar, or a position a row (B,).

    One token a row (T == 1, the decode step) attends its whole slice in
    one pass. A chunk of tokens (prefill) walks the slice in blocks of
    ``LATENT_KV_BLOCK`` rows under a running softmax and stops at the last
    block any query can see, so a prompt pays for the rows it has, not
    for the window, and the (H, T, S) scores never exist whole."""
    b, t, h, r = q_lat.shape
    s, out_dtype = latent.shape[1], q_lat.dtype
    q_pos = jnp.arange(t)[:, None] + jnp.asarray(kv_offset)[..., None, None]
    # one key and one value a row for all heads: the heads are rows of the
    # matmuls' left side, (B, T*H, .) against (B, S, .), scores (B, T, H, S)
    q_lat = q_lat.reshape(b, t * h, r)
    q_pe = q_pe.reshape(b, t * h, -1)

    def scores(lat_blk, pe_blk, k_pos):
        z = jnp.einsum("bqr,bsr->bqs", q_lat, lat_blk[:, :, 0],
                       preferred_element_type=jnp.float32)
        z = z + jnp.einsum("bqe,bse->bqs", q_pe, pe_blk[:, :, 0],
                           preferred_element_type=jnp.float32)
        allowed = q_pos >= k_pos[None, :]          # (T, S') or (B, T, S')
        return jnp.where(allowed[..., None, :],
                         z.reshape(b, t, h, -1) * scale, NEG_INF)

    def weigh(p, lat_blk):
        return jnp.einsum(
            "bqs,bsr->bqr", p.astype(latent.dtype).reshape(b, t * h, -1),
            lat_blk[:, :, 0], preferred_element_type=jnp.float32,
        ).reshape(b, t, h, r)

    if t == 1 or not _walked(s):
        p = jax.nn.softmax(scores(latent, k_pe, jnp.arange(s)), axis=-1)
        return weigh(p, latent).astype(out_dtype)

    blk = LATENT_KV_BLOCK

    def step(i, carry):
        m, l, acc = carry
        lat_blk = jax.lax.dynamic_slice_in_dim(latent, i * blk, blk, axis=1)
        pe_blk = jax.lax.dynamic_slice_in_dim(k_pe, i * blk, blk, axis=1)
        z = scores(lat_blk, pe_blk, i * blk + jnp.arange(blk))
        m_new = jnp.maximum(m, z.max(-1))               # (B, T, H)
        p = jnp.exp(z - m_new[..., None])
        fix = jnp.exp(m - m_new)
        return (m_new, l * fix + p.sum(-1),
                acc * fix[..., None] + weigh(p, lat_blk))

    # the last row any query of the chunk sees is its own last position
    n_blocks = jnp.minimum((jnp.max(q_pos) + blk) // blk, s // blk)
    _, l, acc = jax.lax.fori_loop(0, n_blocks, step, (
        jnp.full((b, t, h), NEG_INF, jnp.float32),
        jnp.zeros((b, t, h), jnp.float32),
        jnp.zeros((b, t, h, r), jnp.float32)))
    return (acc / l[..., None]).astype(out_dtype)
