"""Autoregressive generation — KV-cached, fully compiled.

Same contract as the reference's GPT.generate
(/root/reference/mingpt/model.py:322-356): greedy or sampled decoding with
``temperature`` and optional ``top_k``, context bounded by ``block_size``.

The mechanism is deliberately NOT the reference's: the reference re-runs the
full forward over the whole (cropped) sequence for every new token with a
growing ``torch.cat`` — O(T·full-forward), shape-changing every step, which
under jit would recompile per step (SURVEY §3.3 flags this as the idiom not
to translate). Here decoding is two compiled programs:

  1. **prefill** — one batched forward over the prompt that also writes every
     layer's K/V into a preallocated ``(L, B, block_size, heads, size)``
     cache (``cache_leaf_shapes``: a position's heads side by side where
     they are narrower than a lane tile and together fill whole ones);
  2. **decode** — a single ``lax.scan`` over ``max_new_tokens`` steps, each
     step one-token attention against the cache (static shapes throughout,
     cache updated in place via dynamic_update_slice).

Context-window semantics match the reference exactly: generation is
**unbounded** — when prompt+generation no longer fit ``block_size``, decoding
switches to a sliding-window program that re-crops to the last ``block_size``
tokens every step (/root/reference/mingpt/model.py:336-337). The window slide
re-positions every token (learned absolute positions shift), so cached K/V
written at the old positions would be stale — the sliding program therefore
re-forwards the full (static-shape) window per step, exactly the reference's
O(T·forward) semantics, still as one compiled ``lax.scan``. The KV-cached
fast path handles the common fits-the-window case.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from mingpt_distributed_tpu.config import (
    FULL_ATTN, LIGHTNING, SPARSE, WINDOW_ATTN, GPTConfig)
from mingpt_distributed_tpu.models import gpt
from mingpt_distributed_tpu.ops import attention as attn_ops
from mingpt_distributed_tpu.ops import layers as L
from mingpt_distributed_tpu.ops import sparse_attention as sparse_ops

# {"k", "v"}: (planes, B, block_size, heads, size), heads and size each
# leaf's own (``cache_leaf_shapes``); a plane a layer, or a pass and layer
# of a looped stack (``GPTConfig.cache_planes``). A pool that counts a
# routed model's rows carries a third leaf, MOE_ROWS, which is no cache of
# anything.
Cache = Dict[str, jax.Array]

#: the leaf of a serving pool's cache tree in which the cached forward
#: counts an expert model's routed rows: (expert layers, E + 4) int32, the
#: rows each expert computed and then the routes asked for, the blocks the
#: experts' loop ran, the blocks its layout has and the experts that held a
#: row (ops/moe.grouped_swiglu). It rides in the donated tree so that the
#: programs add to it in place and nothing is fetched in a round.
MOE_ROWS = "moe_rows"

#: a hybrid stack's leaves beside the sparse layers' ``"k"``, ``"v"`` rows
#: ((sparse layers, B, block_size, 1, KV * hd): the heads side by side).
#: POOLED: the sparse layers' pooled keys, (sparse layers, B, block_size /
#: stride, 1, KV * hd), rows of a coarser grid. STATE: the lightning layers'
#: state, (lightning layers, B, H, hd, hd) float32: no position axis, so no
#: mask hides a stale one: a sequence's first chunk starts it from zero.
POOLED = "pooled_k"
STATE = "state"
#: and the counter of the rows the sparse layers' decode steps attended:
#: (2,) float32, [rows attended, rows at or before the query], summed over
#: sparse layers and counted lanes, each the mean over a layer's KV heads.
#: It rides in a serving pool's donated tree as MOE_ROWS does.
SPARSE_ROWS = "sparse_rows"
#: and the counter of a looped stack's passes (``GPTConfig.n_passes``,
#: ``exit_gate``): (2 + n_passes,) float32, [token-passes run, tokens, the
#: exit mass ``p_t`` of each pass summed over those tokens], over the
#: ``valid`` tokens of every prefill and decode program. Token-passes over
#: tokens is ``n_passes`` at the one exit threshold that is built: each pass
#: adds its own tokens, so a pass left out would show.
LOOP_PASSES = "loop_passes"
#: leaves of a cache tree that count and hold nothing of a request
COUNTERS = (MOE_ROWS, SPARSE_ROWS, LOOP_PASSES)
#: the window layers' rings of a stack of ``cfg.layer_types``, beside the
#: full layers' ``"k"``, ``"v"`` rows: (window layers, B, ``cfg.ring_rows``,
#: heads, size), the row of position ``p`` at index ``p mod ring_rows``. A
#: ring has no row a position, so what hides a stale row is its age: a
#: reader takes the row at an index for the last position before its own
#: that lies there, and masks it where that position is negative or has
#: left the window (``attn_ops.ring_attend_step``).
RING_K, RING_V = "ring_k", "ring_v"
RINGS = (RING_K, RING_V)

#: lanes of the device's tile: the minor axis of a buffer is laid out in
#: pieces of this many elements
LANE_TILE = attn_ops.LANE_TILE


def cache_leaf_shapes(cfg: GPTConfig, batch: int) -> Dict[str, Tuple[int, ...]]:
    """The shape of each leaf of a ``batch``-lane cache: the one
    description ``init_cache``, the serving pool, its shardings and its
    audits read. Per-head rows: keys and values alike, ``kv_heads`` of
    ``head_dim``, kept one of two ways by the widths alone (no flag, no
    model's name). As a rule a head has an axis entry of its own,
    ``(planes, B, S, kv_heads, head_dim)``. Where a head fills no lane
    tile of its own (``head_dim`` under LANE_TILE) the device keeps such a
    buffer with positions minor, a lane's row scattered over ``kv_heads x
    head_dim / 16`` tiles a plane (PERF.md, PR 39: 4.65 of an 8.43 ms
    decode step at GPT-2 124M was the rows' writes); if a position's heads
    together are whole tiles (``kv_heads x head_dim`` a multiple of
    LANE_TILE) they lie side by side instead, ``(planes, B, S, 1, kv_heads
    x head_dim)``, the numbers and their order unchanged, and a row is
    ``width / 128`` tiles a plane. A width that is no whole number of
    tiles (GPT-2 XL's 25 x 64 = 1,600) stays per-head: the device keeps
    that leaf positions minor too rather than pad a row, so nothing is won
    and the attention pays (PERF.md, PR 39). ``row_heads`` says how many
    heads a row holds; the decode step reads such rows whole
    (``attn_ops.causal_attend_step``), a chunk views its lane's as heads
    (``attn_ops.as_heads``). Latent attention caches two different things
    a token, each shared by all heads: ``"k"`` the rotated rope key and
    ``"v"`` the normed latent (the values the absorbed attention averages,
    and the first part of every key, so stored once). A looped stack has a plane a
    pass and layer (``cfg.cache_planes``): pass t of layer l keeps its own
    keys and values at plane ``t * n_layer + l``."""
    if cfg.layer_types is not None:
        # a row a position for the full layers alone; a window layer keeps
        # its window's rows in a ring. A row holds its KV heads side by
        # side whatever their size: the decode step walks these slices, and
        # out of a per-head leaf of whole tiles a head (8 of 128) the chip's
        # compiler copies the whole pool, heads before positions, at the
        # head of every step (compile rehearsal, PR 59: 2 x 2 GB)
        row = (1, cfg.kv_heads * cfg.head_dim)
        full, ring = (len(cfg.kind_layers(k)) for k in (FULL_ATTN, WINDOW_ATTN))
        shapes = {n: (full, batch, cfg.block_size) + row for n in ("k", "v")}
        if ring:
            shapes.update({n: (ring, batch, cfg.ring_rows) + row
                           for n in RINGS})
        return shapes
    if cfg.mixer_types is not None:
        # rows for the sparse layers alone; the lightning layers keep a
        # state a head and nothing a position
        sparse, lin = (len(cfg.mixer_layers(m)) for m in (SPARSE, LIGHTNING))
        # a row holds its KV heads side by side: a (2, 128) pair is tiled
        # so that the MXU cannot read it (ops/sparse_attention.py)
        width = cfg.kv_heads * cfg.head_dim
        rows = (sparse, batch, cfg.block_size, 1, width)
        shapes = {"k": rows, "v": rows} if sparse else {}
        if sparse:
            shapes[POOLED] = (sparse, batch, cfg.sparse_pooled_len, 1, width)
        if lin:
            shapes[STATE] = (lin, batch, cfg.lightning_heads,
                             cfg.lightning_head_dim, cfg.lightning_head_dim)
        return shapes
    rows = (cfg.cache_planes, batch, cfg.block_size)
    if cfg.kv_lora_rank:
        return {"k": rows + (1, cfg.qk_rope_head_dim),
                "v": rows + (1, cfg.kv_lora_rank)}
    width = cfg.kv_heads * cfg.head_dim
    side_by_side = cfg.head_dim < LANE_TILE and width % LANE_TILE == 0
    row = (1, width) if side_by_side else (cfg.kv_heads, cfg.head_dim)
    return {n: rows + row for n in ("k", "v")}


def row_heads(cfg: GPTConfig) -> int:
    """KV heads that lie side by side in one row of the ``"k"``, ``"v"``
    leaves (``cache_leaf_shapes``): ``kv_heads`` where a row is a
    position's heads, 1 where a head has an axis entry of its own or the
    row is no head's (a latent). What splits a row into heads for whoever
    needs them apart: a quantized pool's scales, a mesh's shards."""
    if cfg.kv_lora_rank:
        return 1
    return cache_leaf_shapes(cfg, 1)["k"][-1] // cfg.head_dim


def row_tiles(cfg: GPTConfig) -> int:
    """Lane tiles one position's row of the ``"k"`` leaf touches over all
    planes, which is what a lane's write of a decode step touches: a row
    whose last axis holds it whole is ``ceil(width / LANE_TILE)`` tiles a
    plane; a per-head leaf of narrow heads is kept with positions minor, a
    head's numbers 16 to a tile's sublanes, so ``kv_heads x head_dim / 16``
    a plane (the arithmetic of PERF.md, PR 39, for any per-head leaf)."""
    planes, _, _, heads, width = cache_leaf_shapes(cfg, 1)["k"]
    if heads == 1:
        return planes * -(-width // LANE_TILE)
    return planes * heads * width // 16


def cache_walk(cfg: GPTConfig, cache,
               whole: bool = True) -> attn_ops.StepWalk:
    """How a decode step walks ``cache``'s slices, from the ``"k"``, ``"v"``
    leaves as the step is handed them (arrays or their
    ``ShapeDtypeStruct``: shape and dtype are all it reads). The leaf whose
    row says how the slices lie goes first: a latent cache's ``"v"`` is the
    latent, ``"k"`` the rope key beside it. The one place that knows which
    leaves those are, for a bare step and for the serving engine alike.
    ``whole``: the leaves are buffers whole on one device as the step gets
    them (a serving pool that is quantized or sharded over a mesh says no,
    and keeps the XLA walk: ``attn_ops.step_walk``)."""
    return attn_ops.step_walk(
        [cache[n].shape for n in ("v", "k")], cache["v"].dtype.itemsize,
        latent=bool(cfg.kv_lora_rank), whole=whole)


def ring_walk(cfg: GPTConfig, cache,
              whole: bool = True) -> Optional[attn_ops.StepWalk]:
    """:func:`cache_walk` for the window layers' rings of ``cache``; None
    where it holds none."""
    if RING_K not in cache:
        return None
    return attn_ops.step_walk([cache[n].shape for n in RINGS],
                              cache[RING_K].dtype.itemsize, whole=whole)


def init_cache(cfg: GPTConfig, batch: int, dtype=None) -> Cache:
    dtype = dtype or jnp.dtype(cfg.dtype)
    # a state is float32 whatever the rows are kept in
    return {n: jnp.zeros(shape, jnp.float32 if n == STATE else dtype)
            for n, shape in cache_leaf_shapes(cfg, batch).items()}


def init_sparse_rows(cfg: GPTConfig) -> Optional[jax.Array]:
    """A zeroed SPARSE_ROWS leaf, or None where no layer selects."""
    if SPARSE not in (cfg.mixer_types or ()):
        return None
    return jnp.zeros((2,), jnp.float32)


def init_loop_passes(cfg: GPTConfig) -> Optional[jax.Array]:
    """A zeroed LOOP_PASSES leaf, or None where the layers run once and no
    gate is read."""
    if cfg.n_passes == 1 and not cfg.exit_gate:
        return None
    return jnp.zeros((2 + cfg.n_passes,), jnp.float32)


def init_moe_rows(cfg: GPTConfig) -> Optional[jax.Array]:
    """A zeroed MOE_ROWS leaf, or None where the model counts nothing (only
    the dropless route does)."""
    if not cfg.dropless:
        return None
    return jnp.zeros((cfg.n_layer - cfg.n_dense_layers, cfg.n_experts + 4),
                     jnp.int32)


def _lay_rows_over(old: jax.Array, rows: jax.Array, positions) -> jax.Array:
    """A layer's cached ``(B, S, heads, size)`` slice as it will read once each
    lane's new row ``rows[b, 0]`` lies at ``positions[b]``: a select of
    the slice's size. For a slice a step reads whole and small (a hybrid
    stack's pooled keys); over a layer's rows it bound the attention's
    read, which therefore takes the cache as it lies and the new rows
    beside it (``attn_ops.causal_attend_step``, ``latent_attend_step``,
    ``sparse_ops.sparse_attend_step``)."""
    at = jnp.arange(old.shape[1])[None, :] == positions[:, None]  # (B, S)
    return jnp.where(at[:, :, None, None], rows, old)


@jax.named_scope("kv_layout")
def _write_lane_rows(cache: Cache, rows, positions) -> Cache:
    """Every plane's new row of lane ``b`` into the full ``(L, B, S, heads,
    size)`` buffers at ``(:, b, positions[b])``: one
    ``dynamic_update_slice`` of ``(L, 1, 1, heads, size)`` a lane and
    buffer, in place, whatever layout the device keeps the buffers in
    (``heads`` and ``size`` are the leaf's own, ``cache_leaf_shapes``: a
    row of heads side by side is ``L x ceil(size / 128)`` tiles, a per-head
    one ``L x heads x size / 16``). ``rows`` is the planes' list of
    ``{"k", "v"}`` rows, ``(B, 1, heads, size)`` each (a hybrid stack's sparse
    layers bring POOLED rows too, which lie on a coarser grid:
    ``positions`` is then a dict of (B,) indices by leaf).

    The lanes are a static python loop on purpose (compile rehearsals for
    the TPU, PR 27): a ``fori_loop`` body is laid out before its caller,
    with the row axes minor, and a scatter runs only in that layout, so
    either made the program copy the whole buffer into that layout and
    back, every call. A chain of slices in the entry computation takes
    the layout the buffer arrives in."""
    out = dict(cache)
    for name in rows[0]:
        buf = cache[name]
        new = jnp.stack([r[name] for r in rows])  # (L, B, 1, heads, size)
        at = positions[name] if isinstance(positions, dict) else positions
        for lane in range(new.shape[1]):
            buf = jax.lax.dynamic_update_slice(
                buf, new[:, lane:lane + 1], (0, lane, at[lane], 0, 0))
        out[name] = buf
    return out


def _ring_chunk(q, rows, cache: Cache, plane: int, offset, valid):
    """A chunk of a window layer against its ring (the form of
    ``_cached_block`` under one ``offset`` for the batch): (the chunk's
    attention (B, T, H, hd), the cache with the ring as the chunk leaves
    it). The ring holds the rows of the ``W`` positions before ``offset``
    (those the sequence has; what else lies there is masked as a negative
    position), the row of ``p`` at ``p mod W``: rolled by ``offset`` they
    stand in the order of their positions, ``offset - W`` first, and the
    chunk attends them and its own rows after them under the band
    (``attn_ops.banded_attention``, which passes by the blocks no query's
    window touches). Afterwards index ``i`` of the ring holds the row of
    the last position before the chunk's end that is ``i`` modulo ``W``:
    the chunk's own where that position is in the chunk, else the ring's as
    it was. The chunk's end is its ``valid`` (B, T) tokens' (None: all
    ``T``): a bucket's padding after them leaves no row in the ring, or a
    decode step would read it as a position before its own."""
    b, t = q.shape[:2]
    w = cache[RING_K].shape[2]
    offset = jnp.asarray(offset)
    old = {n: cache[n][plane] for n in RINGS}                # (B, W, ...)
    in_order = {n: jnp.roll(old[n], -(offset % w), axis=1) for n in RINGS}
    att = attn_ops.banded_attention(
        q, *(jnp.concatenate([in_order[n], rows[n]], 1) for n in RINGS),
        q_start=offset, k_start=offset - w, window=w)
    length = jnp.full((b,), t) if valid is None else valid.sum(-1)
    last = offset + length[:, None] - 1                      # (B, 1)
    held = last - jnp.mod(last - jnp.arange(w)[None, :], w)  # (B, W) positions
    mine = (held >= offset)[:, :, None, None]
    at = jnp.clip(held - offset, 0, t - 1)[:, :, None, None]
    new = {n: jnp.where(mine, jnp.take_along_axis(rows[n], at, axis=1), old[n])
           for n in RINGS}
    return att, {**cache, **{n: jax.lax.dynamic_update_slice(
        cache[n], new[n][None], (plane, 0, 0, 0, 0)) for n in RINGS}}


def _cached_block(
    x: jax.Array,            # (B, T, D) — T = prompt length or 1
    blk: gpt.Params,         # one layer's params (no leading L axis)
    cache: Cache,            # FULL (planes, B, S, heads, size) buffers
    plane: int,              # the block's plane of the cache
    offset: jax.Array,       # absolute position of x[:, 0]: scalar, or (B,)
    cfg: GPTConfig,
    valid: Optional[jax.Array] = None,  # (B, T) bool: tokens of a request
    expert_layer: Optional[int] = None,  # blk's EXPERT_LEAVES are the stack's
    frontier: Optional[jax.Array] = None,  # (B,): how far each lane is read
    walk: Optional[attn_ops.StepWalk] = None,  # and how (``cache_walk``)
    kind: Optional[str] = None,  # the layer's kind in a stack of layer_types
) -> Tuple[jax.Array, Cache, Cache, Optional[jax.Array]]:
    """One pre-LN block against the cache, ``gpt._block`` with another
    middle word: norm, parts, attend the cache, out, add; norm, MLP, add
    (``gpt.attention_parts``, ``attention_out``, ``mlp_branch``: the layer
    is written there, once). Returns (y, cache, rows, counts): the block's
    own (B, T, heads, size) k/v ``rows`` in the cache's dtype and row shape
    (``cache_leaf_shapes``: a position's ``(kv_heads, head_dim)`` as
    computed, or the same numbers side by side, ``(1, kv_heads x
    head_dim)``; the queries stay ``(B, T, H, hd)``, a chunk's attention
    views its lane's rows as heads and the decode step reads them whole),
    and a dropless expert layer's counts of the ``valid`` tokens' routed
    rows (``gpt.mlp_branch``).

    ``blk`` is the layer's weights and ``plane`` where its keys and values
    lie in the cache: the layer's own number where the layers run once, and
    ``pass * n_layer + layer`` in a looped stack, where one layer's weights
    serve several planes.

    ``offset`` is one position for the whole batch (prefill, verify, solo
    ``generate``: rows that advance together): the rows are written into
    the full cache at (plane, :, offset) here and the block attends the
    plane's slice. That update is a small dynamic_update_slice on the big
    buffer, which XLA aliases in place through the unrolled layer chain
    and the decode scan's carry: a decode step costs the rows it writes,
    not the cache's size.

    A ``(B,)`` offset, one a row, is the serving decode step: the batch is
    the pool's slot axis, T is 1 and every lane stands at its own
    position (the form is read from the offset's shape). The rotary
    angles and the causal mask are then a lane's own; the block reads the
    cache as it lies: a lane attends the rows before its position out of
    the cache and its own new row beside them, under one softmax in two
    parts (``attn_ops.causal_attend_step``, ``latent_attend_step``; the
    row a lane attends for its own token is the row as cached), lane ``b``
    in blocks only as far as ``frontier[b]``, its reach: its position
    where its output counts and 0 where it does not (None: every lane to
    its own position; ``attn_ops.step_plan``), by ``walk`` (None:
    :func:`cache_walk` of this cache; the serving engine works its pool's
    out once and hands the same one to the program and to its counter).
    It returns the cache as it came: the caller writes all planes' rows
    after the last (``_write_lane_rows``), and the capacity route takes
    each lane alone (``mlp_branch``'s ``lanes_apart``).

    Latent attention (``cfg.kv_lora_rank``) caches a token's rotated rope
    key and normed latent and attends them absorbed: the queries go
    through W_UK to the latent's size, the heads average latents, and the
    averages go through W_UV. Per-head keys and values of the cache are
    never built, in prefill or in decode.

    In a stack of ``cfg.layer_types`` the layer is of ``kind``, which says
    its query heads, its rotation and where its rows lie. A full layer's
    are plane ``plane`` of ``"k"``, ``"v"``; a chunk attends them in blocks
    (``attn_ops.banded_attention``: a 4k prompt's scores are never whole).
    A window layer's are plane ``plane`` of the rings (RINGS): a chunk
    attends what the ring holds of the positions before it and its own
    rows beside that, under the band, and leaves the ring holding the last
    rows of its ``valid`` tokens (``_ring_chunk``); a decode step
    reads the ring as it lies (``attn_ops.ring_attend_step``, by ``walk``,
    here the rings': ``ring_walk``) and hands its row back under the
    rings' names for the caller to write at ``position mod ring_rows``.
    A kind that rotates nothing (``gpt.layer_rope`` None) caches its keys
    as projected.

    Where the router reads the attention's input (``cfg.moe_router_input``
    "attn") the layer's route is made right after the first norm
    (``gpt.early_route``) and handed to the MLP, whose experts take the
    second norm's output as ever.
    """
    b, t, _ = x.shape
    nh, kv, hd = cfg.kind_heads(kind)
    ring = kind == WINDOW_ATTN
    leaves = RINGS if ring else ("k", "v")
    per_lane = jnp.ndim(offset) == 1
    if per_lane and t != 1:
        raise ValueError(f"a position a lane takes one token a lane, not {t}")

    h = gpt.sublayer_input(x, blk["ln1_scale"], blk.get("ln1_bias"), cfg)
    route = gpt.early_route(h, blk, cfg)
    with jax.named_scope("qkv"):
        rope = gpt.layer_rope(
            cfg, kind, jnp.asarray(offset)[..., None] + jnp.arange(t)) \
            if cfg.rope else None
    if cfg.kv_lora_rank:
        # what is cached: "v" the normed latent, "k" the rotated rope key
        nope = cfg.qk_nope_head_dim
        q_nope, q_pe, v, k = gpt.latent_parts(h, blk, cfg, rope)
        with jax.named_scope("qkv"):    # W_UK, absorbed into the queries
            w_kv_b = blk["w_kv_b"].astype(x.dtype).reshape(
                cfg.kv_lora_rank, nh, nope + cfg.v_head_dim)
            q_lat = jnp.einsum("bthn,rhn->bthr", q_nope, w_kv_b[..., :nope])
    else:
        q, k, v = gpt.attention_parts(h, blk, cfg, (nh, kv, hd), rope)

    # in the cache's row shape: a latent's parts have it, a per-head row
    # takes it here (heads side by side where the leaf keeps them so)
    rows = {n: a.astype(cache[n].dtype).reshape(b, t, *cache[n].shape[3:])
            for n, a in zip(leaves, (k, v))}
    if not per_lane and not ring:
        cache = {**cache, **{n: jax.lax.dynamic_update_slice(
            cache[n], rows[n][None], (plane, 0, offset, 0, 0))
            for n in ("k", "v")}}
        big_k, big_v = cache["k"][plane], cache["v"][plane]
    # attend against the whole cache; kv_offset makes query absolute
    # positions correct, and the causal mask kills both future tokens and
    # never-written (zero) slots beyond offset+t
    if per_lane and walk is None:
        walk = ring_walk(cfg, cache) if ring else cache_walk(cfg, cache)
    if ring and per_lane:
        att = attn_ops.ring_attend_step(
            q, cache[RING_K], cache[RING_V], plane, rows[RING_K],
            rows[RING_V], offset, walk, frontier=frontier,
        ).reshape(b, t, nh * hd)
    elif ring:
        att, cache = _ring_chunk(q, rows, cache, plane, offset, valid)
        att = att.reshape(b, t, nh * hd)
    elif cfg.kv_lora_rank:
        scale = cfg.qk_head_dim ** -0.5
        if per_lane:
            att = attn_ops.latent_attend_step(
                q_lat, q_pe, cache["v"], cache["k"], plane, rows["v"],
                rows["k"], offset, walk, frontier=frontier, scale=scale)
        else:
            att = attn_ops.latent_attention(
                q_lat, q_pe, big_v, big_k, kv_offset=offset, scale=scale)
        with jax.named_scope("qkv"):    # W_UV, on the heads' averages
            att = jnp.einsum("bthr,rhv->bthv", att, w_kv_b[..., nope:]
                             ).reshape(b, t, nh * cfg.v_head_dim)
    elif per_lane:
        att = attn_ops.causal_attend_step(
            q, cache["k"], cache["v"], plane, rows["k"], rows["v"], offset,
            walk, frontier=frontier, window=cfg.kind_window(kind),
            logit_softcap=cfg.attn_logit_softcap,
        ).reshape(b, t, nh * hd)
    elif kind is not None:
        att = attn_ops.banded_attention(
            q, big_k, big_v, q_start=offset).reshape(b, t, nh * hd)
    else:
        att = attn_ops.causal_attention(
            q, attn_ops.as_heads(big_k, hd), attn_ops.as_heads(big_v, hd),
            kv_offset=offset, window=cfg.attention_window,
            logit_softcap=cfg.attn_logit_softcap,
        ).reshape(b, t, nh * hd)
    # a sum stands under the mark of the part it takes in: fused with that
    # part's last matmul, the sum is the fusion's root
    with jax.named_scope("attn_out"):
        x = x + gpt.attention_out(att, blk, cfg, h=h)

    h2 = gpt.sublayer_input(x, blk["ln2_scale"], blk.get("ln2_bias"), cfg)
    m, _, counts = gpt.mlp_branch(h2, blk, cfg, valid=valid,
                                  layer=expert_layer, lanes_apart=per_lane,
                                  route=route)
    with jax.named_scope("ffn"):
        return x + m, cache, rows, counts


def _cached_hybrid_block(
    x: jax.Array,            # (B, T, D)
    blk: gpt.Params,         # one layer's params
    cache: Cache,            # the FULL buffers of ``cache_leaf_shapes``
    kind: str,               # the layer's mixer
    at: int,                 # its place among that mixer's layers
    offset: jax.Array,       # scalar, or (B,): see ``_cached_block``
    cfg: GPTConfig,
    valid: Optional[jax.Array] = None,  # (B, T) bool: real tokens
):
    """One layer of a hybrid stack against the cache, in either form of
    ``_cached_block``. Returns (y, cache, rows, counted): ``rows`` a sparse
    layer's new ``"k"``, ``"v"`` and POOLED rows for the caller to write
    under a position a lane (None else, and for a lightning layer);
    ``counted`` (2,) a sparse decode step's [rows attended, rows at or
    before the query] over the ``valid`` lanes (None else).

    A lightning layer reads its state at ``cache[STATE][at]`` and writes
    the new one there, in place. A sequence's first chunk (``offset`` 0)
    starts from zero whatever the slot held: a stale state, unlike a stale
    row, would be read. Tokens that are not ``valid`` (a bucket's padding,
    a parked lane, a lane still prefilling) leave the state as it was.

    A sparse layer writes rows as any layer does, and keeps its pooled keys
    beside them: a chunk re-pools its lane's rows whole (entries over rows
    not yet written are not yet visible), a decode step re-pools the one
    window that ends at or last before each lane's position, laid over the
    cached ones before the selection reads them. The decode step reads the
    cached rows as they lie and attends its own new row beside them
    (``sparse_ops.sparse_attend_step``), as ``_cached_block``'s step
    does: no rows are laid over a slice of the pool's size.
    """
    b, t, _ = x.shape
    per_lane = jnp.ndim(offset) == 1
    positions = jnp.asarray(offset)[..., None] + jnp.arange(t)  # (T,) | (B,T)
    with jax.named_scope("norm"):
        u = L.rms_norm(x, blk["ln1_scale"], eps=cfg.norm_eps)
    rows = counted = None
    if kind == LIGHTNING:
        state = cache[STATE][at]
        if not per_lane:
            state = jnp.where(jnp.asarray(offset) == 0, 0.0, state)
        mixed, state = gpt.lightning_mixer(
            u, blk, cfg, positions, state, valid, step=per_lane)
        cache = {**cache, STATE: cache[STATE].at[at].set(state)}
    else:
        sizes = sparse_ops.SparseSizes.of(cfg)
        q, k, v = gpt.sparse_rows(
            *gpt.mixer_qkv(u, blk, cfg, SPARSE, positions))
        new = {"k": k.astype(cache["k"].dtype), "v": v.astype(cache["v"].dtype)}
        q_pos = jnp.broadcast_to(positions, (b, t))
        if per_lane:
            old_k, old_v = cache["k"][at], cache["v"][at]
            index = sparse_ops.last_pooled_index(offset, sizes)
            new[POOLED] = sparse_ops.pooled_key_at(
                cache["k"], at, new["k"], offset, index, sizes)
            pooled = _lay_rows_over(cache[POOLED][at], new[POOLED], index)
            chosen = sparse_ops.select_blocks(q, pooled, q_pos, sizes,
                                              cfg.kv_heads)
            mixed, attended = sparse_ops.sparse_attend_step(
                q, old_k, old_v, new["k"], new["v"], chosen, q_pos, sizes)
            live = jnp.ones((b, 1), bool) if valid is None else valid
            counted = jnp.stack([
                jnp.sum(jnp.where(live, attended, 0.0)),
                jnp.sum(jnp.where(live, q_pos + 1.0, 0.0))])
            rows = new
        else:
            cache = {**cache, **{n: jax.lax.dynamic_update_slice(
                cache[n], new[n][None], (at, 0, offset, 0, 0))
                for n in ("k", "v")}}
            big_k, big_v = cache["k"][at], cache["v"][at]
            pooled = sparse_ops.pooled_keys(big_k, sizes)
            cache = {**cache, POOLED: cache[POOLED].at[at].set(pooled)}
            mixed = sparse_ops.sparse_attention_chunked(
                q, big_k, big_v, pooled, q_pos, sizes, cfg.kv_heads)
        mixed = gpt.mixer_out(mixed, u, blk, cfg, SPARSE)
    return gpt.hybrid_mlp(x, mixed, blk, cfg), cache, rows, counted


#: positions of a long chunk a hybrid layer takes at one time: a 32k prompt's
#: MLP and decay matrices whole would be gigabytes each
HYBRID_SEGMENT = 4096


def _hybrid_layer_in_segments(x, params, cache, layer, offset, cfg, valid):
    """Layer ``layer`` of a hybrid stack against the cache
    (``_cached_hybrid_block``). A chunk longer than HYBRID_SEGMENT goes a
    segment at a time in order, the cache carried: each segment is a chunk
    at its own offset, which is what chunked prefill is, so the result is
    the whole chunk's. The layer's parameters are sliced out of their stack
    inside the loop's body: sliced outside, each would be copied whole
    into it."""
    b, t, d = x.shape
    seg = HYBRID_SEGMENT
    kind, blk, at = gpt.hybrid_layer_params(params, cfg, layer)
    if jnp.ndim(offset) == 1 or t <= seg or t % seg:
        return _cached_hybrid_block(x, blk, cache, kind, at, offset, cfg,
                                    valid)
    if valid is None:
        valid = jnp.ones((b, t), bool)
    in_segments = lambda a: jnp.moveaxis(
        a.reshape(b, t // seg, seg, *a.shape[2:]), 1, 0)

    def one(cache, item):
        x_s, valid_s, start = item
        blk = gpt.hybrid_layer_params(params, cfg, layer)[1]
        y, cache, _, _ = _cached_hybrid_block(
            x_s, blk, cache, kind, at, offset + start, cfg, valid_s)
        return cache, y

    cache, y = jax.lax.scan(one, cache, (
        in_segments(x), in_segments(valid), jnp.arange(0, t, seg)))
    return jnp.moveaxis(y, 0, 1).reshape(b, t, d), cache, None, None


def _forward_cached_hybrid(params, x, cache: Cache, offset, cfg: GPTConfig,
                           valid) -> Tuple[jax.Array, Cache]:
    """The layers of a hybrid stack over embedded ``x``, reading and
    writing the cache: ``_forward_cached_hidden``'s loop for a stack whose
    layers differ in kind."""
    rows, counted = [], []
    for layer in range(cfg.n_layer):
        x, cache, new, count = _hybrid_layer_in_segments(
            x, params, cache, layer, offset, cfg, valid)
        if new is not None:
            rows.append(new)
        if count is not None:
            counted.append(count)
    if rows:
        sizes = sparse_ops.SparseSizes.of(cfg)
        cache = _write_lane_rows(cache, rows, {
            "k": offset, "v": offset,
            POOLED: sparse_ops.last_pooled_index(offset, sizes)})
    if SPARSE_ROWS in cache and counted:
        cache = {**cache, SPARSE_ROWS: cache[SPARSE_ROWS] + sum(counted)}
    return x, cache


def _forward_cached_hidden(
    params: gpt.Params, tokens: jax.Array, cache: Cache, offset, cfg: GPTConfig,
    valid: Optional[jax.Array] = None, frontier: Optional[jax.Array] = None,
    walk: Optional[attn_ops.StepWalk] = None,
    rings: Optional[attn_ops.StepWalk] = None,
) -> Tuple[jax.Array, Cache]:
    """Forward (B, T) tokens at absolute position ``offset`` (a scalar, or
    a ``(B,)`` vector of one position a row: see ``_cached_block``) through
    all layers, reading+writing the cache. Returns (final-norm hidden states
    (B, T, D), cache) — the LM head is applied separately (``_head_logits``)
    so callers that need logits at a *dynamic* position (the serving
    prefill reads position ``prompt_len - 1`` of a padded prompt) can slice
    the hidden states before paying the head matmul.

    A cache that carries MOE_ROWS (a serving pool's) gets the expert
    layers' counts of the ``valid`` (B, T) tokens' routed rows added to it
    (None: every token is routed and counts; a prefill's padding and a
    decode lane without a request take no routed expert, and what they
    leave in the cache is nobody's to read). ``frontier`` (B,) bounds what
    a step under a position a lane reads of each lane's slice, walked as
    ``walk`` says, a window layer's ring as ``rings`` does
    (``_cached_block``; None: this cache's own, :func:`cache_walk`,
    :func:`ring_walk`; a hybrid stack's sparse layers read every row and
    take no notice).

    The layers are a static python loop: every layer's body is in the
    program, with its weights sliced out of their stack at a static index
    and its plane of the cache a static one, so each cache update is a
    row-sized write in place (``_cached_block``); under a position a lane
    the planes' rows are written together after the loop
    (``_write_lane_rows``). Program size and compile time grow with
    ``n_layer`` (48 bodies of gpt2-xl: 200 s cold on the chip).

    A looped stack (``cfg.n_passes`` > 1) runs that chain of layers once a
    pass, the passes written out one after another like the layers (192
    bodies for 48 layers run four times: the chip prefers it to a loop
    over the passes with the plane a traced index, by 45.0 against 52.0 ms
    a decode step, at twice the compile time; PERF.md, PR 37). The
    weights are the same in every pass, the final norm closes each and its
    output is what the next starts from, and pass t of layer l reads and
    writes plane ``t * n_layer + l``. The exit gate reads every pass's
    output; at the one threshold that is built its mass moves no logit and
    is counted (LOOP_PASSES, over the ``valid`` tokens, each pass adding
    the tokens it ran).
    """
    b, t = tokens.shape
    with jax.named_scope("embed"):
        x = params["wte"][tokens]
        if not cfg.rope:
            pos = jnp.asarray(offset)[..., None] + jnp.arange(t)  # (T,) | (B, T)
            x = x + jnp.take(params["wpe"], pos, axis=0)
        if cfg.scale_emb != 1.0:
            x = x * cfg.scale_emb
        x = x.astype(cfg.stream_dtype)

    if cfg.mixer_types is not None:
        x, cache = _forward_cached_hybrid(params, x, cache, offset, cfg, valid)
        return gpt._norm(x, params["lnf_scale"], None, cfg), cache

    n_dense = cfg.n_dense_layers
    # a dropless layer's expert leaves stay the stack's: sliced out, they
    # would be copied whole into the route's loop
    whole = gpt.EXPERT_LEAVES if cfg.dropless else ()
    if cfg.layer_types is not None:
        x, cache = _forward_cached_kinds(
            params, x, cache, offset, cfg, valid, frontier, walk, rings,
            whole)
        return gpt._norm(x, params["lnf_scale"], None, cfg), cache
    # the tokens a pass counts (LOOP_PASSES), where the cache counts any
    counting = LOOP_PASSES in cache
    if counting:
        counted = jnp.ones((b, t), bool) if valid is None else valid
        n_counted = jnp.sum(counted, dtype=jnp.float32)
        ran = jnp.zeros((), jnp.float32)
    rows, counts, gates = [], [], []
    for index in range(cfg.n_passes):       # the same weights in every pass
        for layer in range(cfg.n_layer):
            stack, at = (params["dense_blocks"], layer) if layer < n_dense \
                else (params["blocks"], layer - n_dense)
            blk = {n: a if n in whole else a[at] for n, a in stack.items()}
            x, cache, new, routed = _cached_block(
                x, blk, cache, index * cfg.n_layer + layer, offset, cfg, valid,
                expert_layer=at if whole else None, frontier=frontier,
                walk=walk)
            rows.append(new)
            if routed is not None:
                counts.append(routed)
        if cfg.closes_passes:   # the last pass's is the norm this returns
            x = gpt._norm(x, params["lnf_scale"], params.get("lnf_bias"), cfg)
        if cfg.exit_gate:
            gates.append(gpt.exit_gate_logits(params, x))
        if counting:
            ran = ran + n_counted
    if jnp.ndim(offset) == 1:
        cache = _write_lane_rows(cache, rows, offset)
    if MOE_ROWS in cache and counts:
        cache = {**cache, MOE_ROWS: cache[MOE_ROWS] + jnp.stack(counts)}
    if counting:
        with jax.named_scope("exit_gate"):
            mass = jnp.sum(jnp.where(
                counted, gpt.exit_mass(jnp.stack(gates)), 0.0), axis=(1, 2)) \
                if gates else jnp.zeros((cfg.n_passes,), jnp.float32)
            cache = {**cache, LOOP_PASSES: cache[LOOP_PASSES]
                     + jnp.concatenate([jnp.stack([ran, n_counted]), mass])}
    if not cfg.closes_passes:
        x = gpt._norm(x, params["lnf_scale"], params.get("lnf_bias"), cfg)
    return x, cache


def _forward_cached_kinds(params, x, cache: Cache, offset, cfg: GPTConfig,
                          valid, frontier, walk, rings, whole):
    """The layers of a stack of ``cfg.layer_types`` over embedded ``x``,
    reading and writing the cache: ``_forward_cached_hidden``'s loop for a
    stack whose attention layers differ in kind. A layer's attention comes
    out of its kind's stack and its MLP out of the dense or the expert
    stack (``gpt.kind_layer_params``); a full layer reads and writes its
    plane of ``"k"``, ``"v"`` by ``walk``, a window layer its plane of the
    rings by ``rings`` (None: :func:`ring_walk` of this cache, as None for
    ``walk`` is its :func:`cache_walk`), and under a position a lane the
    new rows of each are written together after the last layer, the rings'
    at ``position mod ring_rows``."""
    rows = {FULL_ATTN: [], WINDOW_ATTN: []}
    counts = []
    for layer in range(cfg.n_layer):
        kind, blk, at, mlp_at = gpt.kind_layer_params(
            params, cfg, layer, whole)
        routed = whole and "w_router" in blk
        x, cache, new, counted = _cached_block(
            x, blk, cache, at, offset, cfg, valid,
            expert_layer=mlp_at if routed else None, frontier=frontier,
            walk=rings if kind == WINDOW_ATTN else walk, kind=kind)
        rows[kind].append(new)
        if counted is not None:
            counts.append(counted)
    if jnp.ndim(offset) == 1:
        cache = _write_lane_rows(cache, rows[FULL_ATTN], offset)
        if rows[WINDOW_ATTN]:
            cache = _write_lane_rows(cache, rows[WINDOW_ATTN],
                                     offset % cfg.ring_rows)
    if MOE_ROWS in cache and counts:
        cache = {**cache, MOE_ROWS: cache[MOE_ROWS] + jnp.stack(counts)}
    return x, cache


@jax.named_scope("head")
def _head_logits(params: gpt.Params, x: jax.Array, cfg: GPTConfig) -> jax.Array:
    """LM head over (B, t, D) hidden states -> (B, t, V) fp32 logits
    (with the Gemma-2 final softcap when configured)."""
    w_head = params["wte"].T if cfg.tie_weights else params["head"]
    if cfg.residual_dtype:
        x = x.astype(cfg.dtype)     # the head's matmul runs in the compute dtype
    if cfg.dim_model_base:
        x = (x / cfg.head_divisor).astype(x.dtype)
    logits = jnp.einsum(
        "btd,dv->btv", x, w_head.astype(x.dtype),
        preferred_element_type=jnp.float32,
    )
    return attn_ops.softcap(logits, cfg.final_logit_softcap)


#: leaves the cached forward reads ONLY through ``.astype(<compute dtype>)``:
#: the matmul weights and biases of ``L.dense`` / ``moe.moe_mlp`` and an
#: untied ``head``. Casting one of these first gives the bits casting it at
#: its use gives. Every other leaf is used in float32 somewhere (``wte`` and
#: ``wpe`` are summed before the cast, also when ``wte`` is the tied head;
#: the norms and ``w_router`` compute in float32) and must stay as it is.
_CAST_ONLY_BLOCK_LEAVES = frozenset({
    "wq", "wk", "wv", "wo", "bq", "bk", "bv", "bo",
    "w_fc", "b_fc", "w_proj", "b_proj",
    "w_gate", "w_up", "w_down",
    "w_e1", "w_e2", "w_eg",
    "w_kv_a", "w_kv_b", "w_sg", "w_su", "w_sd", "w_og", "w_hg",
})


def cast_once_params(
    params: gpt.Params, cfg: GPTConfig
) -> Tuple[gpt.Params, int]:
    """The tree a caller that runs the cached forward many times should
    hand it: every leaf of ``_CAST_ONLY_BLOCK_LEAVES`` (and ``head``) stored
    in ``cfg.dtype``, every other leaf the array that came in. Returns (tree,
    leaves cast). ``w.astype(x.dtype)`` on such a leaf is then no operation,
    so a jitted program that takes the tree as an argument (and so cannot
    hoist the cast itself) converts no weight, and computes bit for bit what
    it computes on ``params`` (tests/test_cast_once.py holds every
    architecture to that: a leaf added to the forward is added here only if
    it passes). Where no leaf needs a cast (a float32 model, or parameters
    made in the compute dtype: ``cfg.param_dtype``) the tree returned IS
    ``params``, and the device holds the weights once."""
    dtype = jnp.dtype(cfg.dtype)
    stacks = [s for s in ("dense_blocks", "blocks",
                          *gpt.MIXER_STACKS.values(),
                          *gpt.KIND_STACKS.values()) if s in params]
    picked = {s: {n: a for n, a in params[s].items()
                  if n in _CAST_ONLY_BLOCK_LEAVES and a.dtype != dtype}
              for s in stacks}
    if "head" in params and params["head"].dtype != dtype:
        picked["head"] = params["head"]
    n_cast = len(jax.tree.leaves(picked))
    if not n_cast:
        return params, 0
    # a leaf placed on purpose (an engine's mesh) keeps that sharding, spelled
    # as it was; any other stays uncommitted, as the leaf it came from is: a
    # committed argument would commit the programs' outputs, and a jit call
    # keys on that
    cast = jax.jit(
        lambda t: jax.tree.map(lambda a: a.astype(dtype), t),
        out_shardings=jax.tree.map(
            lambda a: a.sharding if getattr(a, "committed", False) else None,
            picked),
    )(picked)
    cast.update({s: {**params[s], **cast[s]} for s in stacks})
    return {**params, **cast}, n_cast


def _forward_cached(
    params: gpt.Params, tokens: jax.Array, cache: Cache, offset, cfg: GPTConfig,
    valid: Optional[jax.Array] = None, frontier: Optional[jax.Array] = None,
    walk: Optional[attn_ops.StepWalk] = None,
    rings: Optional[attn_ops.StepWalk] = None,
) -> Tuple[jax.Array, Cache]:
    """Forward (B, T) tokens at position ``offset`` through all layers.
    Returns (last-position logits (B, V), cache). Thin composition of
    ``_forward_cached_hidden`` + ``_head_logits`` — the serving engine
    (serving/engine.py) shares the same two pieces."""
    x, cache = _forward_cached_hidden(
        params, tokens, cache, offset, cfg, valid, frontier, walk, rings)
    logits = _head_logits(params, x[:, -1:], cfg)[:, 0]
    return logits, cache


def _select_next(
    logits: jax.Array, rng, temperature: float, do_sample: bool,
    top_k: Optional[int], top_p: Optional[float] = None,
) -> jax.Array:
    """Temperature / top-k / sample-vs-argmax — reference model.py:341-352 —
    plus nucleus (top-p) filtering as a beyond-parity extension."""
    logits = logits / jnp.maximum(temperature, 1e-8)
    if top_k is not None:
        k = min(top_k, logits.shape[-1])
        kth = jax.lax.top_k(logits, k)[0][..., -1:]
        logits = jnp.where(logits < kth, -jnp.inf, logits)
    if top_p is not None and top_p < 1.0:
        desc = jnp.sort(logits, axis=-1)[..., ::-1]
        probs = jax.nn.softmax(desc, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        # keep tokens whose preceding cumulative mass is < top_p; the top
        # token must survive unconditionally (top_p <= 0 would otherwise
        # mask every token and degenerate to token id 0), making top_p→0
        # equivalent to greedy; threshold at the smallest kept logit
        keep = (cum - probs) < top_p
        keep = keep.at[..., 0].set(True)
        kth = jnp.min(
            jnp.where(keep, desc, jnp.inf), axis=-1, keepdims=True
        )
        logits = jnp.where(logits < kth, -jnp.inf, logits)
    if do_sample:
        return jax.random.categorical(rng, logits, axis=-1)
    return jnp.argmax(logits, axis=-1)


@partial(
    jax.jit,
    static_argnames=("cfg", "max_new_tokens", "temperature", "do_sample",
                     "top_k", "top_p"),
)
def _generate_jit(
    params, idx, rng, *, cfg: GPTConfig, max_new_tokens: int,
    temperature: float, do_sample: bool, top_k: Optional[int],
    top_p: Optional[float] = None,
):
    b, t0 = idx.shape
    cache = init_cache(cfg, b)
    step_keys = jax.random.split(rng, max_new_tokens)

    # prefill the prompt, pick the first new token
    logits, cache = _forward_cached(params, idx, cache, 0, cfg)
    first = _select_next(logits, step_keys[0], temperature, do_sample,
                         top_k, top_p)
    if max_new_tokens == 1:  # static
        return jnp.concatenate([idx, first[:, None]], axis=1)

    def step(carry, step_rng):
        tok, cache, pos = carry
        logits, cache = _forward_cached(params, tok[:, None], cache, pos, cfg)
        nxt = _select_next(logits, step_rng, temperature, do_sample,
                           top_k, top_p)
        return (nxt, cache, pos + 1), tok

    (last, _, _), toks = jax.lax.scan(
        step, (first, cache, jnp.asarray(t0)), step_keys[1:]
    )
    new_tokens = jnp.concatenate(
        [jnp.moveaxis(toks, 0, 1), last[:, None]], axis=1
    )
    return jnp.concatenate([idx, new_tokens], axis=1)


@partial(
    jax.jit,
    static_argnames=("cfg", "max_new_tokens", "temperature", "do_sample",
                     "top_k", "top_p"),
)
def _generate_sliding_jit(
    params, idx, rng, *, cfg: GPTConfig, max_new_tokens: int,
    temperature: float, do_sample: bool, top_k: Optional[int],
    top_p: Optional[float] = None,
):
    """Reference-semantics sliding-window decode (model.py:336-337): every
    step forwards the last ``block_size`` tokens with positions 0..len-1.
    Static shapes: the window buffer is always (B, block_size), left-aligned;
    causal masking makes the garbage beyond ``length`` invisible to the
    read-out position. Returns only the (B, max_new_tokens) new tokens."""
    b, t0 = idx.shape  # t0 <= block_size (caller crops)
    bs = cfg.block_size
    window = jnp.zeros((b, bs), jnp.int32)
    window = jax.lax.dynamic_update_slice(window, idx, (0, 0))
    step_keys = jax.random.split(rng, max_new_tokens)

    def step(carry, step_rng):
        window, length = carry
        logits_all, _ = gpt.forward(params, window, cfg)
        logits = jax.lax.dynamic_slice_in_dim(
            logits_all, length - 1, 1, axis=1
        )[:, 0]
        nxt = _select_next(
            logits, step_rng, temperature, do_sample, top_k, top_p
        ).astype(jnp.int32)
        full = length >= bs
        base = jnp.where(full, jnp.roll(window, -1, axis=1), window)
        pos = jnp.where(full, bs - 1, length)
        window = jax.lax.dynamic_update_slice(base, nxt[:, None], (0, pos))
        return (window, jnp.minimum(length + 1, bs)), nxt

    (_, _), toks = jax.lax.scan(
        step, (window, jnp.asarray(t0, jnp.int32)), step_keys
    )
    return jnp.moveaxis(toks, 0, 1)


def generate(
    params: gpt.Params,
    cfg: GPTConfig,
    idx,
    max_new_tokens: int,
    temperature: float = 1.0,
    do_sample: bool = False,
    top_k: Optional[int] = None,
    top_p: Optional[float] = None,
    rng: Optional[jax.Array] = None,
) -> jax.Array:
    """Generate ``max_new_tokens`` continuations of ``idx`` (B, T0).

    Keeps the reference's signature and semantics (model.py:323-328),
    including unbounded generation past the context window; one compiled
    program per (prompt_len, max_new_tokens) pair thereafter. ``top_p``
    (nucleus sampling) is a beyond-parity extension.
    """
    idx = jnp.asarray(idx, dtype=jnp.int32)
    if idx.ndim == 1:
        idx = idx[None]
    if max_new_tokens < 1:
        return idx
    if rng is None:
        rng = jax.random.key(0)
    if idx.shape[1] + max_new_tokens <= cfg.block_size:
        # fits the window: KV-cached fast path (positions never slide)
        return _generate_jit(
            params, idx, rng, cfg=cfg, max_new_tokens=max_new_tokens,
            temperature=float(temperature), do_sample=bool(do_sample),
            top_k=None if top_k is None else int(top_k),
            top_p=None if top_p is None else float(top_p),
        )
    # overflow: reference-exact sliding window over the last block_size
    # tokens; the full prompt still heads the returned sequence
    new = _generate_sliding_jit(
        params, idx[:, -cfg.block_size:], rng, cfg=cfg,
        max_new_tokens=max_new_tokens, temperature=float(temperature),
        do_sample=bool(do_sample),
        top_k=None if top_k is None else int(top_k),
        top_p=None if top_p is None else float(top_p),
    )
    return jnp.concatenate([idx, new], axis=1)
