"""InfLLM-v2 block-sparse softmax attention (MiniCPM4): a query past
``dense_len`` attends the rows of a chosen few blocks of its cache.

What a sparse layer keeps for a token is its key and value rows, as any
softmax layer does, and beside them **pooled keys**: entry ``j`` the mean of
the keys of positions ``[stride * j, stride * j + kernel)``
(:func:`pooled_keys`, :func:`pooled_key_at`). A query at position ``t``:

1. scores the pooled keys it can see (those whose whole window lies at or
   before ``t``): per query head a softmax over them of ``q . Kc_j *
   scale``, summed over the query heads of a KV head;
2. max-pools the scores to blocks of ``block`` positions (a block takes the
   pooled keys whose window touches it);
3. chooses, per KV head, ``topk`` blocks in all: the first ``init_blocks``,
   the blocks that cover its last ``window`` positions, and the
   best-scoring others (ties: the earlier block);
4. attends, causally, the rows of the chosen blocks. Below ``dense_len`` it
   attends every row at or before it.

:func:`select_blocks` is steps 1-3 and :func:`sparse_attend` step 4. The
rows are scored whole and the unchosen masked: the mathematics of a gather,
read as a dense cache is read. Prefill runs :func:`sparse_attend` over
chunks of queries and, inside, over the chunks of keys at or before them,
with a running softmax, so no (heads, T, T) array exists.

**Rows are kept with their KV heads side by side**: ``(B, S, 1, KV * hd)``,
head ``k`` at ``[k * hd, (k + 1) * hd)``. Few KV heads is the point of this
mixer (two, of 128), and a ``(2, 128)`` minor pair is tiled on the TPU so
that no matmul can read it: compiled for a v5e, a decode step first re-laid
every layer's whole key and value slab, 268 MB each, to score it (PERF.md,
PR 32). A ``(1, 256)`` row is read as it lies. The queries go to the rows'
width instead (:func:`spread_queries`: query head ``h`` of KV head ``k``
holds its values at ``k``'s place and zeros elsewhere), one matmul scores
all heads against the whole rows, and each head keeps its own KV head's
part of what it averaged (:func:`own_part`): the sums of the per-head form,
with as many products again that are zero.
"""

from __future__ import annotations

import dataclasses
from typing import Tuple

import jax
import jax.numpy as jnp

from mingpt_distributed_tpu.ops.attention import (  # noqa: F401
    NEG_INF, own_part, spread_queries)

#: queries scored against the pooled keys, and keys attended, at one time
QUERY_CHUNK = 512
KEY_CHUNK = 1024


@dataclasses.dataclass(frozen=True)
class SparseSizes:
    """The selection's sizes (``GPTConfig.sparse_*``)."""
    kernel: int
    stride: int
    block: int
    topk: int
    window: int
    init_blocks: int
    dense_len: int

    @classmethod
    def of(cls, cfg) -> "SparseSizes":
        return cls(cfg.sparse_kernel_size, cfg.sparse_kernel_stride,
                   cfg.sparse_block_size, cfg.sparse_topk, cfg.sparse_window,
                   cfg.sparse_init_blocks, cfg.sparse_dense_len)


def pooled_keys(k_rows: jax.Array, sizes: SparseSizes) -> jax.Array:
    """(B, S, 1, E) key rows -> (B, S / stride, 1, E) pooled keys in the
    rows' dtype, entry ``j`` the float32 mean of rows ``[stride * j, stride *
    j + kernel)``. The last ``kernel / stride - 1`` entries reach past the
    rows and are made of zeros there: no query ever sees them."""
    b, s, one, e = k_rows.shape
    per = sizes.kernel // sizes.stride
    strides = k_rows.astype(jnp.float32).reshape(
        b, s // sizes.stride, sizes.stride, one, e).mean(2)
    padded = jnp.pad(strides, ((0, 0), (0, per - 1), (0, 0), (0, 0)))
    n = s // sizes.stride
    pooled = sum(padded[:, i:i + n] for i in range(per)) / per
    return pooled.astype(k_rows.dtype)


def last_pooled_index(positions: jax.Array, sizes: SparseSizes) -> jax.Array:
    """The pooled key whose window ends at or last before ``positions``
    (clipped to 0 where no window has ended yet)."""
    return jnp.maximum((positions - (sizes.kernel - 1)) // sizes.stride, 0)


def pooled_key_at(k_pool: jax.Array, layer: int, new_row: jax.Array,
                  position: jax.Array, index: jax.Array,
                  sizes: SparseSizes) -> jax.Array:
    """The (L, B, S, 1, E) cached rows of all layers, one of them, each
    lane's new row (B, 1, 1, E) for its ``position`` (B,), and (B,) pooled
    indices -> (B, 1, 1, E): each lane's pooled key ``index[b]`` of that
    layer, from its rows with the new one in its place (the cached rows are
    read as they lie: the row at ``position`` is not written yet)."""
    _, b, _, one, e = k_pool.shape
    # a static loop of row-sized slices of the whole buffer, as the decode
    # step's row writes are (generate._write_lane_rows). Batched into a
    # gather, or cut out of a layer's slice, the TPU compiler first copies
    # that whole slice of the pool, 268 MB a layer for 32 rows a lane
    # (compile rehearsals, PR 32)
    windows = jnp.concatenate([jax.lax.dynamic_slice(
        k_pool, (layer, lane, index[lane] * sizes.stride, 0, 0),
        (1, 1, sizes.kernel, one, e))[0] for lane in range(b)])
    at = index[:, None] * sizes.stride + jnp.arange(sizes.kernel)
    windows = jnp.where((at == position[:, None])[:, :, None, None],
                        new_row, windows)
    return windows.astype(jnp.float32).mean(1, keepdims=True).astype(
        k_pool.dtype)


@jax.named_scope("sparse_select")
def select_blocks(
    q: jax.Array,           # (B, T, H, KV * hd): normed, spread
    pooled: jax.Array,      # (B, S / stride, 1, KV * hd)
    q_pos: jax.Array,       # (B, T) absolute positions
    sizes: SparseSizes,
    kv: int,
) -> jax.Array:
    """(B, T, KV, S / block) bool: the blocks each query attends, per KV
    head. A block that starts after the query is never chosen."""
    b, t, h, e = q.shape
    n_pooled = pooled.shape[1]
    per_block = sizes.block // sizes.stride
    per_kernel = sizes.kernel // sizes.stride
    n_blocks = n_pooled // per_block
    scale = (e // kv) ** -0.5

    scores = jnp.einsum("bthe,bje->bthj", q, pooled[:, :, 0],
                        preferred_element_type=jnp.float32) * scale
    ends = jnp.arange(n_pooled) * sizes.stride + sizes.kernel - 1
    seen = (ends <= q_pos[..., None])[:, :, None]           # (B, T, 1, J)
    scores = jnp.where(seen, scores, NEG_INF)
    probs = jnp.where(seen, jax.nn.softmax(scores, axis=-1), 0.0)
    group = probs.reshape(b, t, kv, h // kv, n_pooled).sum(3)   # (B,T,KV,J)
    # a block takes the pooled keys whose window touches it: per_block of
    # its own and the per_kernel - 1 before it
    by_block = jax.lax.reduce_window(
        group, -jnp.inf, jax.lax.max,
        window_dimensions=(1, 1, 1, per_block + per_kernel - 1),
        window_strides=(1, 1, 1, per_block),
        padding=((0, 0), (0, 0), (0, 0), (per_kernel - 1, 0)))

    blocks = jnp.arange(n_blocks)
    current = (q_pos // sizes.block)[..., None]             # (B, T, 1)
    visible = blocks <= current
    forced = (blocks < sizes.init_blocks) | (
        blocks >= ((q_pos - sizes.window + 1) // sizes.block)[..., None])
    rank = jnp.where(forced[:, :, None], jnp.inf, by_block)
    rank = jnp.where(visible[:, :, None], rank, -jnp.inf)
    best = jax.lax.top_k(rank, min(sizes.topk, n_blocks))[1]
    chosen = (best[..., None] == blocks).any(-2)            # (B, T, KV, Nb)
    dense = (q_pos < sizes.dense_len)[..., None, None]
    return (chosen | dense) & visible[:, :, None]


def _masked_scores(q, k, chosen, q_pos, k_pos, sizes: SparseSizes):
    """Scores (B, H, T, S') of spread queries against the ``k`` rows (B, S',
    KV * hd) of whole blocks at positions ``k_pos`` (S',), and which of them
    count (B, KV, 1, T, S'). ``chosen`` (B, T, KV, S' / block) is of those
    blocks."""
    kv = chosen.shape[2]
    scores = jnp.einsum("bthe,bse->bhts", q, k,
                        preferred_element_type=jnp.float32) \
        * (q.shape[-1] // kv) ** -0.5
    in_block = jnp.repeat(chosen, sizes.block, axis=-1)     # (B, T, KV, S')
    allowed = in_block & (k_pos <= q_pos[..., None])[:, :, None]
    return scores, jnp.moveaxis(allowed, 1, 2)[:, :, None]


def _by_group(scores, kv):
    """(B, H, T, S) -> (B, KV, G, T, S)."""
    b, h = scores.shape[:2]
    return scores.reshape(b, kv, h // kv, *scores.shape[2:])


@jax.named_scope("sparse_attend")
def sparse_attend(
    q: jax.Array,           # (B, T, H, KV * hd): spread
    k_rows: jax.Array,      # (B, S, 1, KV * hd)
    v_rows: jax.Array,      # (B, S, 1, KV * hd)
    chosen: jax.Array,      # (B, T, KV, S / block) bool
    q_pos: jax.Array,       # (B, T)
    sizes: SparseSizes,
) -> Tuple[jax.Array, jax.Array]:
    """Causal softmax attention over the rows of the chosen blocks: one
    pass over all rows (a decode step, or a short sequence). Returns ((B,
    T, H, hd) in q's dtype, (B, T) float32 the rows each query attended, the
    mean over its KV heads)."""
    b, t, h, _ = q.shape
    s, kv = k_rows.shape[1], chosen.shape[2]
    scores, allowed = _masked_scores(
        q, k_rows[:, :, 0], chosen, q_pos, jnp.arange(s), sizes)
    probs = jax.nn.softmax(
        jnp.where(allowed, _by_group(scores, kv), NEG_INF), axis=-1)
    out = jnp.einsum("bhts,bse->bhte",
                     probs.reshape(b, h, t, s).astype(v_rows.dtype),
                     v_rows[:, :, 0], preferred_element_type=jnp.float32)
    attended = allowed.sum((1, 2, 4), dtype=jnp.float32) / kv
    return own_part(jnp.moveaxis(out, 1, 2), kv).astype(q.dtype), attended


def sparse_attention_chunked(
    q: jax.Array,           # (B, T, H, KV * hd): spread
    k_rows: jax.Array,      # (B, S, 1, KV * hd): rows 0.. of the cache
    v_rows: jax.Array,
    pooled: jax.Array,      # (B, S / stride, 1, KV * hd)
    q_pos: jax.Array,       # (B, T) absolute positions, rising along T
    sizes: SparseSizes,
    kv: int,
) -> jax.Array:
    """Selection and attention for a long run of queries (prefill): chunks
    of ``QUERY_CHUNK`` queries, each against the chunks of ``KEY_CHUNK``
    rows up to its last position, under a running softmax. The same sums as
    ``sparse_attend(.., select_blocks(..))`` in another order."""
    b, t, h, e = q.shape
    s = k_rows.shape[1]
    qc, kc = min(QUERY_CHUNK, t), min(KEY_CHUNK, s)
    if t % qc or s % kc or kc % sizes.block:
        chosen = select_blocks(q, pooled, q_pos, sizes, kv)
        return sparse_attend(q, k_rows, v_rows, chosen, q_pos, sizes)[0]
    g = h // kv

    def some_queries(_, chunk):
        q_c, pos_c = chunk                      # (B, qc, H, E), (B, qc)
        chosen = select_blocks(q_c, pooled, pos_c, sizes, kv)

        def some_keys(i, carry):
            top, total, acc = carry
            k_c = jax.lax.dynamic_slice_in_dim(k_rows, i * kc, kc, axis=1)
            v_c = jax.lax.dynamic_slice_in_dim(v_rows, i * kc, kc, axis=1)
            here = jax.lax.dynamic_slice_in_dim(
                chosen, i * (kc // sizes.block), kc // sizes.block, axis=-1)
            scores, allowed = _masked_scores(
                q_c, k_c[:, :, 0], here, pos_c, i * kc + jnp.arange(kc),
                sizes)
            scores = jnp.where(allowed, _by_group(scores, kv), NEG_INF)
            new_top = jnp.maximum(top, scores.max(-1))
            # a row that is not allowed adds nothing, also where a query
            # has seen no allowed row yet (its scores all NEG_INF)
            weight = jnp.where(allowed, jnp.exp(scores - new_top[..., None]),
                               0.0)
            keep = jnp.exp(top - new_top)
            acc = keep[..., None] * acc + _by_group(jnp.einsum(
                "bhts,bse->bhte",
                weight.reshape(b, h, qc, kc).astype(v_c.dtype), v_c[:, :, 0],
                preferred_element_type=jnp.float32), kv)
            return new_top, keep * total + weight.sum(-1), acc

        n_chunks = (pos_c.max() // kc + 1).astype(jnp.int32)
        shape = (b, kv, g, qc)
        _, total, acc = jax.lax.fori_loop(
            0, n_chunks, some_keys,
            (jnp.full(shape, NEG_INF, jnp.float32),
             jnp.zeros(shape, jnp.float32),
             jnp.zeros(shape + (e,), jnp.float32)))
        out = (acc / total[..., None]).reshape(b, h, qc, e)
        return None, own_part(jnp.moveaxis(out, 1, 2), kv)

    n = t // qc
    chunks = (jnp.moveaxis(q.reshape(b, n, qc, h, e), 1, 0),
              jnp.moveaxis(q_pos.reshape(b, n, qc), 1, 0))
    _, out = jax.lax.scan(some_queries, None, chunks)
    return jnp.moveaxis(out, 0, 1).reshape(b, t, h, e // kv).astype(q.dtype)


@jax.named_scope("sparse_attend")
def sparse_attend_step(
    q: jax.Array,           # (B, 1, H, KV * hd): spread
    k_rows: jax.Array,      # (B, S, 1, KV * hd): as cached, the query's
    v_rows: jax.Array,      #   own row not yet written
    k_new: jax.Array,       # (B, 1, 1, KV * hd): the query's own row
    v_new: jax.Array,
    chosen: jax.Array,      # (B, 1, KV, S / block) bool
    q_pos: jax.Array,       # (B, 1)
    sizes: SparseSizes,
) -> Tuple[jax.Array, jax.Array]:
    """``sparse_attend`` for a decode step, the cache read as it lies: the
    rows before the query's position out of the cache, the query's own row
    from ``k_new``, ``v_new``, under one softmax in two parts. Laying the
    new row over the cached slice first (a select of the slice's size) cost
    the step a written and re-read copy of every layer's rows (PERF.md, PR
    32)."""
    b, _, h, e = q.shape
    s, kv = k_rows.shape[1], chosen.shape[2]
    scores, allowed = _masked_scores(
        q, k_rows[:, :, 0], chosen, q_pos - 1, jnp.arange(s), sizes)
    scores = jnp.where(allowed, _by_group(scores, kv), NEG_INF)
    own = _by_group(jnp.einsum(
        "bthe,bse->bhts", q, k_new[:, :, 0],
        preferred_element_type=jnp.float32), kv) * (e // kv) ** -0.5
    top = jnp.maximum(scores.max(-1, keepdims=True), own)
    weight = jnp.where(allowed, jnp.exp(scores - top), 0.0)
    weight_own = jnp.exp(own - top)
    total = weight.sum(-1, keepdims=True) + weight_own      # (B,KV,G,1,1)
    out = jnp.einsum("bhts,bse->bhte",
                     weight.reshape(b, h, 1, s).astype(v_rows.dtype),
                     v_rows[:, :, 0], preferred_element_type=jnp.float32)
    out = out + weight_own.reshape(b, h, 1, 1) * v_new[:, :, 0].astype(
        jnp.float32)[:, None]
    out = out / total.reshape(b, h, 1, 1)
    attended = allowed.sum((1, 2, 4), dtype=jnp.float32) / kv + 1.0
    return own_part(jnp.moveaxis(out, 1, 2), kv).astype(q.dtype), attended
