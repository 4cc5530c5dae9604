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

import functools
import math
from typing import NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

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


def yarn_inv_freq(dim: int, theta: float, factor: float, original: float,
                  beta_fast: float, beta_slow: float) -> np.ndarray:
    """YaRN's blended frequencies for ``dim`` rotated dimensions, (dim/2,)
    float64, as the published ``rope_parameters`` of ``rope_type`` "yarn"
    state them: pair i turns by ``theta^(-2i/dim)`` (extrapolation) where it
    completes more than ``beta_fast`` rotations over the ``original``
    context, by that over ``factor`` (interpolation) where fewer than
    ``beta_slow``, and by a linear blend between the two pairs where those
    counts are met (floor and ceiling, clipped to [0, dim - 1])."""
    i = np.arange(dim // 2, dtype=np.float64)
    extra = theta ** (-2.0 * i / dim)
    inter = extra / factor

    def pair_of(rotations):
        return dim * math.log(original / (rotations * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(pair_of(beta_fast)), 0)
    high = min(math.ceil(pair_of(beta_slow)), dim - 1)
    if low == high:
        high += 0.001       # the published code's guard against 0 / 0
    ramp = np.clip((i - low) / (high - low), 0.0, 1.0)
    return inter * ramp + extra * (1.0 - ramp)


def yarn_rope_tables(positions: jax.Array, dim: int, theta: float,
                     factor: float, original: float, beta_fast: float,
                     beta_slow: float, attention_factor: float,
                     ) -> Tuple[jax.Array, jax.Array]:
    """:func:`rope_tables` under YaRN (:func:`yarn_inv_freq`): the blended
    frequencies, and the cosine and the sine both times
    ``attention_factor``, which is how the published code scales the
    scores of a stretched context."""
    freqs = jnp.asarray(yarn_inv_freq(
        dim, theta, factor, original, beta_fast, beta_slow), jnp.float32)
    angles = positions.astype(jnp.float32)[..., None] * freqs
    return jnp.cos(angles) * attention_factor, \
        jnp.sin(angles) * attention_factor


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array,
               interleave: bool = False) -> jax.Array:
    """Rotate (B, T, H, hd) by per-position tables (T, hd/2), or by a
    table a batch entry (B, T, hd/2). Pair i is the halves' ``(x[i],
    x[i + hd/2])`` or, under ``interleave`` (DeepSeek's ``rope_interleave``),
    the neighbours ``(x[2i], x[2i + 1])``; either way the rotated pair goes
    back where it came from. Tables narrower than half a head turn the
    head's first ``2 x width`` dimensions and the others pass through (a
    ``partial_rotary_factor`` under 1)."""
    if 2 * cos.shape[-1] < x.shape[-1]:
        turned = 2 * cos.shape[-1]
        return jnp.concatenate([
            apply_rope(x[..., :turned], cos, sin, interleave),
            x[..., turned:]], axis=-1)
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


#: queries, and keys, of one step of :func:`banded_attention`'s walk
BAND_BLOCK = 512


def banded_attention(
    q: jax.Array,  # (B, T, H, hd): query i stands at position q_start + i
    k: jax.Array,  # (B, S, KV, hd) or (B, S, 1, KV x hd): key j stands at
    v: jax.Array,  #   position k_start + j
    *,
    q_start, k_start=0, window: Optional[int] = None,
    block: int = BAND_BLOCK,
) -> jax.Array:
    """Causal attention of a chunk of queries against keys that stand at
    positions of their own, the scores never whole: ``causal_attention``
    for a prefill of thousands of positions over per-head rows. A key is
    seen where its position is not negative (``k_start`` may be: rows a
    ring has not written yet), not after the query's and, under
    ``window``, within the query's last ``window`` positions, its own
    included. Queries go a block at a time; a block walks the blocks of
    keys its band touches under a running softmax and no other, so a
    window layer pays ``O(T x window)`` and a full layer stops at the
    diagonal. Grouped queries stand beside their KV head: no key is
    repeated. Rows that keep a position's heads side by side (a cache's:
    ``generate.cache_leaf_shapes``) are viewed as heads a block at a time,
    once the block is cut out (:func:`as_heads`: never of a buffer). Where
    ``T`` or ``S`` is no whole number of blocks, one pass over everything
    under the same mask. Returns (B, T, H, hd) in q's dtype."""
    b, t, h, hd = q.shape
    s, kv = k.shape[1], math.prod(k.shape[2:]) // hd
    g = h // kv
    scale = 1.0 / jnp.sqrt(jnp.asarray(hd, dtype=jnp.float32))
    q_start, k_start = jnp.asarray(q_start), jnp.asarray(k_start)

    def scores(qb, kb, q_pos, k_pos):
        z = jnp.einsum("bqkgd,bskd->bkgqs", qb, kb,
                       preferred_element_type=jnp.float32) * scale
        behind = q_pos[:, None] - k_pos[None, :]
        allowed = (behind >= 0) & (k_pos >= 0)[None, :]
        if window is not None:
            allowed = allowed & (behind < window)
        return jnp.where(allowed, z, NEG_INF)

    def weigh(p, vb):
        return jnp.einsum("bkgqs,bskd->bqkgd", p.astype(vb.dtype), vb,
                          preferred_element_type=jnp.float32)

    if t % block or s % block:
        qg = q.reshape(b, t, kv, g, hd)
        p = jax.nn.softmax(scores(qg, as_heads(k, hd), q_start + jnp.arange(t),
                                  k_start + jnp.arange(s)), axis=-1)
        return weigh(p, as_heads(v, hd)).reshape(b, t, h, hd).astype(q.dtype)

    n_k = s // block
    qg = q.reshape(b, t // block, block, kv, g, hd)

    def one_block(i):
        qb = jax.lax.dynamic_index_in_dim(qg, i, 1, keepdims=False)
        q_pos = q_start + i * block + jnp.arange(block)
        # the keys' indices the band touches: up to the last query's own,
        # from the first query's window's start (and no negative position)
        first = -k_start if window is None else jnp.maximum(
            -k_start, q_pos[0] - window + 1 - k_start)
        lo = jnp.clip(first // block, 0, n_k)
        hi = jnp.clip((q_pos[-1] - k_start) // block + 1, lo, n_k)

        def step(j, carry):
            m, l, acc = carry
            kb, vb = (as_heads(jax.lax.dynamic_slice_in_dim(
                a, j * block, block, axis=1), hd) for a in (k, v))
            z = scores(qb, kb, q_pos, k_start + j * block + jnp.arange(block))
            m_new = jnp.maximum(m, z.max(-1))
            p, fix = jnp.exp(z - m_new[..., None]), jnp.exp(m - m_new)
            fix_acc = jnp.moveaxis(fix, -1, 1)[..., None]   # (B, q, KV, G, 1)
            return m_new, l * fix + p.sum(-1), acc * fix_acc + weigh(p, vb)

        _, l, acc = jax.lax.fori_loop(lo, hi, step, (
            jnp.full((b, kv, g, block), NEG_INF, jnp.float32),
            jnp.zeros((b, kv, g, block), jnp.float32),
            jnp.zeros((b, block, kv, g, hd), jnp.float32)))
        return acc / jnp.moveaxis(l, -1, 1)[..., None]

    out = jax.lax.map(one_block, jnp.arange(t // block))   # (T/blk, B, blk, ..)
    return jnp.moveaxis(out, 0, 1).reshape(b, t, h, hd).astype(q.dtype)


#: cached rows a step of ``latent_attention``'s prefill walk over a slice
#: attends, and of a latent decode step's (``step_block``)
LATENT_KV_BLOCK = 1024

#: lanes of a vector register: a minor dimension narrower than this fills no
#: tile of its own
LANE_TILE = 128

#: what a step of the XLA walk costs whatever it reads (:func:`_attend_step`:
#: six to ten device operations a ``fori_loop`` step), in the bytes the chip
#: reads meanwhile (6 us and more at 726 GB/s: PERF.md, PR 45): a lane's
#: block is no larger there, and lanes are read together where that saves
#: more steps than it adds bytes (``step_block``, ``step_plan``). It governs
#: the XLA walk alone (a latent pool's, a per-head leaf's, every CPU run's)
#: and whether slices are walked at all (ONE_PASS_STEPS); in the kernel's
#: walk a step costs its bytes (KERNEL_STEP_BYTES)
STEP_COST_BYTES = 4 << 20

#: slices that all lanes' together read in the time of this many steps are
#: not walked: that is all a walk of them could save, a layer, and two loops
#: in every layer's body are not free to trace and compile (``step_block``;
#: PERF.md, PR 45: ouro's three slots of 8 MiB a plane in 192 written-out
#: bodies, ``setup_s`` +20%). No crossover of the attention's own time: the
#: chip reads a walked house of 16-32 MiB -60% to +22% of one pass
ONE_PASS_STEPS = 8

#: keys and values a grid step of the kernel's walk takes of one lane
#: (:func:`_rows_attend_pairs`): the pipeline fetches the next pair's while
#: this one's are scored, so a pair costs its bytes and some 0.4 us; larger
#: blocks over-read more of a lane's last block than the fewer pairs save
#: (PERF.md, PR 62: the chip favoured 256 rows of 3 KB, 512 of 2 KB, 128-256
#: of 4 KB)
KERNEL_STEP_BYTES = 1 << 20


def _walked(s: int) -> bool:
    """Whether an ``s``-row slice is attended a block at a time by a chunk
    of queries (``latent_attention``): more than one block, and a whole
    number of them."""
    return s > LATENT_KV_BLOCK and s % LATENT_KV_BLOCK == 0


class StepWalk(NamedTuple):
    """What a decode step's walk knows before it sees a position: a slice's
    rows, the bytes of a cached row (keys and values, a plane), the rows
    a step takes of one lane (0: the slices are not walked, every lane's is
    read whole in one pass) and whether the steps are the kernel's
    (:func:`_rows_attend_pairs`: every lane read alone, no block for all
    lanes) or the XLA walk's (:func:`_attend_step`, :func:`step_plan`'s
    sharing). Worked out once from the leaves the step is handed
    (:func:`step_walk`) and given as it is to the program and to whoever
    counts what it read."""
    s: int
    row_bytes: int
    block: int
    kernel: bool = False


def step_block(s: int, row_bytes: int, lanes: int,
               latent: bool = False) -> int:
    """Rows of one lane's ``s``-row slice a step of the XLA walk takes
    (:func:`_attend_step`), from what the code can see; 0: the ``lanes``
    slices are not walked, by either walk. The largest power of two of rows
    whose bytes do not pass STEP_COST_BYTES, and at most half the slice: a
    block that is the whole slice is no function of a loop's index, and the
    chip's compiler then computes its scores before the loop, whether a
    step runs or not (PERF.md, PR 45). A latent's block is made a buffer of
    its own (``reread``), which all lanes' together must fit in fast
    memory: LATENT_KV_BLOCK, as PR 33 found it. A slice that is no whole
    number of such blocks is one block. Slices that one pass reads, all of
    them whole, in the time of ONE_PASS_STEPS steps are not walked. (Where
    the kernel walks, its own rule gives the block:
    :func:`kernel_block`.)"""
    if lanes * s * row_bytes <= ONE_PASS_STEPS * STEP_COST_BYTES:
        return 0
    rows = LATENT_KV_BLOCK if latent else min(s // 2, 1 << max(
        0, (STEP_COST_BYTES // row_bytes).bit_length() - 1))
    return rows if rows and s % rows == 0 else s


def kernel_block(s: int, row_bytes: int) -> int:
    """Rows of one lane's ``s``-row slice a grid step of the kernel's walk
    takes: the largest power of two of rows whose bytes do not pass
    KERNEL_STEP_BYTES, a function of the row's bytes alone (a pair costs
    its bytes there, so nothing is weighed against a step's cost). 0: the
    slice is no whole number of such blocks, and keeps the XLA walk (a
    block is a tile-aligned window of the leaf as it lies, never a
    remainder)."""
    rows = 1 << max(0, (KERNEL_STEP_BYTES // row_bytes).bit_length() - 1)
    return rows if s % rows == 0 else 0


def _mosaic_compiles() -> bool:
    """Whether a walked pool of rows side by side goes through the Pallas
    kernel: where Mosaic compiles it, as ``moe._mosaic_compiles`` says for
    the experts' blocks. ``flash_attention._interpret`` is asked through
    its module (imported here: that module imports this one), at the time a
    walk is worked out: the one name a compile rehearsal steers."""
    from mingpt_distributed_tpu.ops import flash_attention
    return not flash_attention._interpret()


def step_walk(leaves: Sequence[Tuple[int, ...]], itemsize: int,
              latent: bool = False, whole: bool = True) -> StepWalk:
    """The walk over cache leaves of shapes ``leaves`` ``(L, B, S, heads,
    size)``, ``itemsize`` bytes a number, as the step reads them. A leaf
    that keeps each of several heads narrower than a lane tile an axis
    entry of its own (GPT-2 XL's 25 of 64) lies positions minor on the
    device, and a block cut out of it by a traced index is copied before it
    is read (PERF.md, PR 45: 38 us a 6.5 MB block where the whole slices
    read in 10 us a lane): such slices are not walked.

    Two walks, chosen by what the code can see and by nothing else. Rows
    that hold a position's heads side by side in whole lane tiles, where
    Mosaic compiles (:func:`_mosaic_compiles`) and the leaves are ``whole``
    (the pool's own buffers on one device: no dequantized copy, no mesh's
    shards), are walked by the kernel, a pair at its bytes' cost: small
    private blocks (:func:`kernel_block`), nothing shared. Everything else
    that is walked (a latent pool, a per-head leaf of whole tiles a head,
    any pool on the CPU) keeps the XLA walk, where a step is dear: large
    blocks (:func:`step_block`), shared where enough lanes need them
    (:func:`step_plan`)."""
    lanes, s, heads, size = leaves[0][1:]
    row_bytes = itemsize * sum(math.prod(leaf[3:]) for leaf in leaves)
    block = 0 if heads > 1 and size < LANE_TILE \
        else step_block(s, row_bytes, lanes, latent)
    if block and whole and not latent and heads == 1 \
            and size % LANE_TILE == 0 and _mosaic_compiles():
        alone = kernel_block(s, row_bytes)
        if alone:
            return StepWalk(s, row_bytes, alone, True)
    return StepWalk(s, row_bytes, block)


def step_plan(walk: StepWalk, reach):
    """How a decode step walks its lanes' slices, from their ``reach`` (B,)
    (a lane's position where it holds a request, 0 where it does not):
    ``(shared, need)``. Lane ``b`` is read in blocks of ``walk.block`` rows
    as far as its reach. In the XLA walk block ``j`` of the slices is read
    for all lanes in one step where that is the cheaper, a step costing
    STEP_COST_BYTES beside what it reads: where the lanes that need it,
    each at a step and a block of its own, would cost more than one step
    and ``B`` blocks. Fewer lanes need a later block, so those are the
    first ``shared`` blocks; ``need`` (B,) are the blocks each lane is read
    alone after them. In the kernel's walk (``walk.kernel``) a pair costs
    its bytes and sharing has nothing to win: ``shared`` is 0 and ``need``
    every block up to the lane's reach. NumPy or ``jnp`` alike."""
    s, row_bytes, block = walk[:3]
    blocks = (reach + block - 1) // block
    if walk.kernel:
        return 0, blocks
    own, whole = (STEP_COST_BYTES + n * block * row_bytes
                  for n in (1, len(reach)))
    # the lanes that need block j of their slice, for every j
    wanted = (blocks[None, :] > np.arange(s // block)[:, None]).sum(-1)
    shared = (wanted >= whole / own).sum()
    return shared, (blocks - shared) * (blocks > shared)


def step_rows_read(walk: StepWalk, reach):
    """Rows a decode step reads of its lanes' slices, a plane, summed over
    the lanes (:func:`causal_attend_step`, :func:`ring_attend_step`,
    :func:`latent_attend_step`): by :func:`step_plan`, whole blocks up to
    each lane's reach and, of every lane, the blocks read together (none in
    the kernel's walk: the rows its grid takes); every slice whole where
    they are not walked. The one rule for the program and for whoever
    counts what it read: NumPy or ``jnp`` alike."""
    if not walk.block:
        return len(reach) * walk.s
    shared, need = step_plan(walk, reach)
    return (shared * len(reach) + need.sum()) * walk.block


def _step_pairs(walk: StepWalk, reach, n: int):
    """The (lane, block) pairs of a walked decode step, from
    :func:`step_plan`: ``(shared, ends, lane_of, block_of)``. After the
    ``shared`` blocks read for all lanes, step ``i`` of the ``ends[-1]``
    the step has takes block ``block_of[i]`` of lane ``lane_of[i]``'s
    slice, the lanes in order and each lane's blocks in order: found in
    ``ends`` (B,), the running sum of the blocks the lanes need, for every
    pair the walk can take. The one plan of the XLA walk's second loop
    (:func:`_attend_step`) and of the kernel's grid
    (:func:`_rows_attend_pairs`)."""
    shared, need = step_plan(walk, reach.astype(jnp.int32))
    ends = jnp.cumsum(need)
    at = jnp.arange(n * (walk.s // walk.block))
    lane_of = jnp.minimum((ends[None, :] <= at[:, None]).sum(-1),
                          n - 1).astype(jnp.int32)
    block_of = (shared + at - (ends - need)[lane_of]).astype(jnp.int32)
    return shared, ends, lane_of, block_of


def _attend_step(take, score, weigh, own, own_value, walk: StepWalk, reach,
                 reread: bool = False):
    """One softmax in parts for a decode step, the XLA walk: a query's
    cached rows, read as they lie, and its own new row beside them, which
    starts the running
    maximum and sum (its score is finite, so a masked row's
    ``exp(NEG_INF - m)`` is 0 exactly and a block of masked rows changes
    nothing). The walk is :func:`step_plan`'s: the blocks enough lanes
    need, a step each for all lanes; then (lane, block) pairs, lane ``b``
    in blocks up to ``reach[b]``, so a lane whose reach is 0 (it holds no
    request, or stands at position 0) is passed by and attends its own row
    alone where no block is shared. Two ``fori_loop`` of one body, their
    trip counts the shared blocks and the pairs (``step_rows_read`` is the
    rows they take); step ``i`` of the second finds its lane and block in
    :func:`_step_pairs`' vectors. Slices that are not
    walked (``walk.block`` 0) are read whole in one pass, every lane's.
    (Rows side by side that the kernel walks never come here:
    :func:`_rows_attend_step`.)
    ``take(lane, lanes, start, size)`` cuts rows ``[start, start + size)``
    of ``lanes`` lanes from ``lane`` on out of the whole cache buffers
    (inside the loop's body: a slice taken before the loop would be copied
    whole into it); ``score(rows, lane, lanes, k_pos)`` gives their
    (lanes, ..., size) float32 scores against those lanes' queries, NEG_INF
    where masked; ``weigh(p, rows)`` the (lanes, ..., d) float32 sum of
    their values under ``p``. ``own`` (B, ...) float32 and ``own_value``
    (B, ..., d) float32 (broadcastable) are the new rows'. ``reread``:
    ``score`` and ``weigh`` read the same rows (a latent is key and
    value), so a block is made a buffer of its own: small enough for the
    chip's compiler to keep in fast memory, and the cache's rows then
    leave HBM once a step and not twice (PERF.md, PR 33: 6.0 -> 4.5 ms).
    Returns (B, ..., d) float32."""
    n, s, block = own.shape[0], walk.s, walk.block
    if not block:
        rows = take(0, n, 0, s)
        z = score(rows, 0, n, jnp.arange(s))
        m = jnp.maximum(z.max(-1), own)
        p, p_own = jnp.exp(z - m[..., None]), jnp.exp(own - m)
        acc = weigh(p, rows) + p_own[..., None] * own_value
        return acc / (p.sum(-1) + p_own)[..., None]

    shared, ends, lane_of, block_of = _step_pairs(walk, reach, n)
    start_of = block_of * block

    def step(carry, lane, lanes, start):
        rows = take(lane, lanes, start, block)
        if reread:
            rows = jax.lax.optimization_barrier(rows)
        z = score(rows, lane, lanes, start + jnp.arange(block))
        m, l, acc = (_of_lanes(c, lane, lanes) for c in carry)
        m_new = jnp.maximum(m, z.max(-1))
        p, fix = jnp.exp(z - m_new[..., None]), jnp.exp(m - m_new)
        new = (m_new, l * fix + p.sum(-1),
               acc * fix[..., None] + weigh(p, rows))
        return tuple(jax.lax.dynamic_update_slice_in_dim(c, a, lane, 0)
                     for c, a in zip(carry, new))

    carry = (own, jnp.ones_like(own),
             jnp.broadcast_to(own_value, own.shape + own_value.shape[-1:]))
    carry = jax.lax.fori_loop(
        0, shared, lambda j, c: step(c, 0, n, j * block), carry)
    _, l, acc = jax.lax.fori_loop(
        0, ends[-1], lambda i, c: step(c, lane_of[i], 1, start_of[i]), carry)
    return acc / l[..., None]


def _lane_rows(buf: jax.Array, layer: int, lane, lanes: int, start,
               size: int) -> jax.Array:
    """Rows ``[start, start + size)`` of ``lanes`` lanes from ``lane`` on,
    of layer ``layer``, out of a whole ``(L, B, S, heads, size)`` cache
    buffer: (lanes, size, heads, size)."""
    return jax.lax.dynamic_slice(
        buf, (layer, lane, start, 0, 0), (1, lanes, size) + buf.shape[3:])[0]


def _of_lanes(a: jax.Array, lane, lanes: int) -> jax.Array:
    """(B, ...) -> (lanes, ...): the entries of ``lanes`` lanes from
    ``lane`` on (all of them: ``a`` as it is)."""
    if lanes == a.shape[0]:
        return a
    return jax.lax.dynamic_slice_in_dim(a, lane, lanes, 0)


@jax.named_scope("cached_attn")
def causal_attend_step(
    q: jax.Array,         # (B, 1, H, hd): one query a lane
    k_cache: jax.Array,   # (L, B, S, KV, hd) or (L, B, S, 1, KV x hd): whole,
    v_cache: jax.Array,   #   as it lies, the new rows not yet written
    layer: int,
    k_new: jax.Array,     # (B, 1, ...): each lane's own new row, shaped as
    v_new: jax.Array,     #   the cache's rows
    positions: jax.Array,  # (B,): where each lane's query stands
    walk: StepWalk,       # how the slices are walked (``step_walk``)
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
    inside the window). Lane ``b``'s slice is read in blocks as far as
    ``frontier[b]`` (B,), its reach: its position where it holds a request
    and 0 where it does not (None: every lane to its own position), by
    whichever walk ``walk`` holds (:func:`step_walk`). The XLA walk's
    (:func:`_attend_step`, :func:`step_plan`): large blocks, a block that
    enough lanes need read for all lanes in one step. The kernel's
    (``walk.kernel``: rows side by side, where Mosaic compiles): small
    blocks, every lane alone, the (lane, block) pairs one Pallas kernel a
    layer (:func:`_rows_attend_pairs`). A per-head leaf of heads under a
    lane tile is read whole, every lane's (:func:`step_walk`), and so are
    slices too few and short for a walk to pay (:func:`step_block`).

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
    return _rows_attend_step(
        q, k_cache, v_cache, layer, k_new, v_new, positions, walk,
        positions if frontier is None else frontier,
        (_rows_before, window), logit_softcap)


def _rows_before(pos, index, window: Optional[int]):
    """Which rows of a full layer's slice a lane at ``pos`` sees, by their
    index, which is their position: those before its own and, under
    ``window``, within its last ``window`` positions."""
    behind = pos - index
    seen = behind > 0
    return seen & (behind < window) if window is not None else seen


def _ring_rows_young(pos, index, w: int):
    """Which rows of a ``w``-row ring a lane at ``pos`` sees, by their
    index (:func:`ring_attend_step`): the row at ``index`` is ``1 + (pos -
    1 - index) mod w`` positions behind, and seen where that is inside the
    window and no further behind than the request began."""
    behind = 1 + jnp.mod(pos - 1 - index, w)
    return (behind < w) & (behind <= pos)


def _rows_attend_kernel(layer_ref, lane_ref, block_ref, pos_ref, reach_ref,
                        q_ref, k_new_ref, v_new_ref, k_ref, v_ref, o_ref,
                        m_ref, l_ref, acc_ref, *, block: int, scale,
                        logit_softcap, mask):
    """Grid step ``i``: block ``block_ref[i]`` of lane ``lane_ref[i]``'s
    slice against that lane's queries at the rows' width (H, W),
    :func:`_attend_step`'s ``step`` operand for operand. A lane's first
    block starts the running maximum, sum and accumulator from its own new
    row; they stay in VMEM across the lane's blocks, and its last block
    (the one that holds its reach) writes ``acc / l``."""
    del layer_ref     # the leaves' index maps read it
    i = pl.program_id(0)
    lane, at = lane_ref[i], block_ref[i]
    # as an einsum's operands: both in the wider of the two dtypes
    wide = jnp.promote_types(q_ref.dtype, k_ref.dtype)
    q = q_ref[0].astype(wide)
    capped = lambda z: softcap(z * scale, logit_softcap)

    @pl.when(at == 0)
    def _():
        # the own row's score: one row, so on the VPU, its products exact
        # in float32 as the MXU's are
        m_ref[...] = capped(jnp.sum(
            q.astype(jnp.float32) * k_new_ref[0].astype(jnp.float32), -1,
            keepdims=True))                                        # (H, 1)
        l_ref[...] = jnp.ones_like(l_ref)
        acc_ref[...] = jnp.broadcast_to(
            v_new_ref[0].astype(jnp.float32), acc_ref.shape)

    rule, arg = mask
    index = at * block + jax.lax.broadcasted_iota(jnp.int32, (1, block), 1)
    z = capped(jax.lax.dot_general(
        q, k_ref[...].astype(wide), (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32))                       # (H, blk)
    z = jnp.where(rule(pos_ref[lane], index, arg), z, NEG_INF)
    m = m_ref[...]
    m_new = jnp.maximum(m, z.max(-1, keepdims=True))
    p, fix = jnp.exp(z - m_new), jnp.exp(m - m_new)
    rows = v_ref[...]
    m_ref[...] = m_new
    l_ref[...] = l_ref[...] * fix + p.sum(-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * fix + jnp.dot(
        p.astype(rows.dtype), rows, preferred_element_type=jnp.float32)

    @pl.when((at + 1) * block >= reach_ref[lane])
    def _():
        o_ref[0] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)


# One jitted caller a shape and not one a layer, as ``moe._run_blocks`` is
# and for its reason (serve-decode's program writes out twelve layers); the
# layer is an operand. Its name holds not the kernel's: XLA names
# instructions after it, and the benchmark's reader finds the kernel by
# name. What a walk holds is what ``_interpret()`` said when it was worked
# out; the call asks again, so a test that steers it clears this cache too.
@functools.partial(jax.jit, static_argnames=(
    "block", "scale", "mask", "logit_softcap"))
def _rows_attend_pairs(layer, lane_of, block_of, pairs, positions, reach,
                       qg, k_new, v_new, k_cache, v_cache, *, block: int,
                       scale: float, mask, logit_softcap):
    """The kernel's walk: the first ``pairs`` (lane, block) pairs of
    :func:`_step_pairs`, pair ``i`` block ``block_of[i]`` (``block`` rows)
    of lane ``lane_of[i]`` of layer ``layer`` out of the whole ``(L, B, S,
    1, W)`` leaves as they lie in HBM: the index maps read the prefetched
    vectors, the grid's bound is ``pairs`` itself, and the pipeline fetches
    pair ``i + 1`` while pair ``i`` is scored. ``qg`` (B, H, W) are the
    queries at the rows' width (:func:`spread_queries`), ``scale`` what
    their scores are multiplied by, ``k_new``, ``v_new`` (B, 1, W) the
    lanes' own new rows, ``mask`` a ``(rule, argument)`` pair
    (:func:`_rows_before`, :func:`_ring_rows_young`). Returns (B, H, W) in
    ``qg``'s dtype; the entries of a lane that has no pair (its ``reach``
    is 0) are not written and hold whatever the buffer held."""
    from mingpt_distributed_tpu.ops import flash_attention
    b, h, w = qg.shape
    of_lane = lambda i, layer, lane, *_: (lane[i], 0, 0)
    of_pair = lambda i, layer, lane, at, *_: (layer[0], lane[i], at[i], 0)
    rows = pl.BlockSpec((None, None, block, w), of_pair)
    own = pl.BlockSpec((1, 1, w), of_lane)
    # the leaves less their axis of one head: the device keeps them with
    # the positions next to the width (tiles of rows by lanes), so this
    # views the same bytes and the call reads them where they lie
    k_cache, v_cache = (a.reshape(a.shape[:3] + (w,))
                        for a in (k_cache, v_cache))
    return pl.pallas_call(
        functools.partial(_rows_attend_kernel, block=block, scale=scale,
                          logit_softcap=logit_softcap, mask=mask),
        out_shape=jax.ShapeDtypeStruct(qg.shape, qg.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(pairs,),
            in_specs=[pl.BlockSpec((1, h, w), of_lane), own, own, rows, rows],
            out_specs=pl.BlockSpec((1, h, w), of_lane),
            scratch_shapes=[pltpu.VMEM((h, 1), jnp.float32),
                            pltpu.VMEM((h, 1), jnp.float32),
                            pltpu.VMEM((h, w), jnp.float32)],
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=flash_attention._interpret(),
        name="rows_attend",
    )(layer, lane_of, block_of, positions, reach, qg, k_new, v_new,
      k_cache, v_cache)


def kernel_walks(jaxpr) -> int:
    """How many walks of a traced program are the kernel's: its
    ``pallas_call`` equations named ``rows_attend``, at any depth (a decode
    program holds one a layer whose pool the kernel walks)."""
    found = 0
    for eqn in jaxpr.eqns:
        found += eqn.primitive.name == "pallas_call" \
            and eqn.params["name"] == "rows_attend"
        for inner in jax.core.jaxprs_in_params(eqn.params):
            found += kernel_walks(inner)
    return found


def _kernel_attend_step(q, k_cache, v_cache, layer, k_new, v_new, positions,
                        walk, frontier, mask, logit_softcap):
    """:func:`_rows_attend_step` where the walk is the kernel's: the pairs
    of :func:`_step_pairs` (every lane alone, a pair at its bytes' cost)
    through :func:`_rows_attend_pairs`, the queries at the rows' width and
    each head keeping its own part of what it averaged, as the XLA walk
    scores rows side by side. (Scoring a chunk of the row at a time with
    its own heads' queries alone read no faster and cost 1.5-3 us more a
    pair: PERF.md, PR 62.)"""
    b, _, h, hd = q.shape
    kv = k_cache.shape[-1] // hd
    _, ends, lane_of, block_of = _step_pairs(walk, frontier, b)
    out = _rows_attend_pairs(
        jnp.full((1,), layer, jnp.int32), lane_of, block_of, ends[-1],
        positions.astype(jnp.int32), frontier.astype(jnp.int32),
        spread_queries(q, kv)[:, 0], k_new[:, 0], v_new[:, 0], k_cache,
        v_cache, block=walk.block,
        scale=float(np.float32(1) / np.sqrt(np.float32(hd))), mask=mask,
        logit_softcap=logit_softcap)
    # a lane no pair was taken of attends its own new row alone
    out = jnp.where((frontier > 0)[:, None, None], out,
                    v_new[:, 0].astype(q.dtype))
    return own_part(out[:, None], kv)


def _rows_attend_step(q, k_cache, v_cache, layer, k_new, v_new, positions,
                      walk, frontier, mask, logit_softcap=None):
    """The body of :func:`causal_attend_step` and :func:`ring_attend_step`:
    one query a lane against its slice of per-head rows as they lie and
    its own new row beside them. ``mask`` is ``(rule, argument)``:
    ``rule(pos (n, 1), index (1, s), argument)`` says which rows of a slice
    a lane at ``pos`` sees, by their index in the slice: the one thing the
    two differ in."""
    if walk.kernel:
        return _kernel_attend_step(q, k_cache, v_cache, layer, k_new, v_new,
                                   positions, walk, frontier, mask,
                                   logit_softcap)
    b, _, h, hd = q.shape
    kv = math.prod(k_cache.shape[3:]) // hd
    side_by_side = k_cache.shape[3] != kv
    scale = 1.0 / jnp.sqrt(jnp.asarray(hd, dtype=jnp.float32))
    allowed_rows = lambda pos, index: mask[0](pos, index, mask[1])
    if side_by_side:
        # the queries to the rows' width: (B, H, KV x hd)
        qg = spread_queries(q, kv)[:, 0]
        by_q, by_rows, by_new, scored = "bhw", "bsw", "bw", "bh"
    else:
        # grouped queries beside their KV head: the cache is never repeated
        qg = q.reshape(b, kv, h // kv, hd)
        by_q, by_rows, by_new, scored = "bkgd", "bskd", "bkd", "bkg"


    def take(lane, lanes, start, size):
        rows = (_lane_rows(k_cache, layer, lane, lanes, start, size),
                _lane_rows(v_cache, layer, lane, lanes, start, size))
        return tuple(r[:, :, 0] for r in rows) if side_by_side else rows

    def score(rows, lane, lanes, k_pos):
        z = softcap(jnp.einsum(
            f"{by_q},{by_rows}->{scored}s", _of_lanes(qg, lane, lanes),
            rows[0], preferred_element_type=jnp.float32) * scale,
            logit_softcap)
        allowed = allowed_rows(_of_lanes(positions, lane, lanes)[:, None],
                               k_pos[None, :])
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
                       walk, frontier)
    if side_by_side:
        return own_part(out[:, None], kv).astype(q.dtype)
    return out.reshape(b, 1, h, hd).astype(q.dtype)


@jax.named_scope("ring_attn")
def ring_attend_step(
    q: jax.Array,         # (B, 1, H, hd): one query a lane
    k_ring: jax.Array,    # (L, B, W, KV, hd) or (L, B, W, 1, KV x hd): whole,
    v_ring: jax.Array,    #   as it lies, the new rows not yet written
    layer: int,
    k_new: jax.Array,     # (B, 1, ...): each lane's own new row
    v_new: jax.Array,
    positions: jax.Array,  # (B,): where each lane's query stands
    walk: StepWalk,       # ``step_walk`` of the two rings
    *,
    frontier=None,
) -> jax.Array:
    """:func:`causal_attend_step` for a window layer whose slot keeps its
    last ``W`` rows in a ring, the row of position ``p`` at index ``p mod
    W``: a lane at position ``p`` attends the rows of positions ``(p - W,
    p)`` out of the ring as it lies and its own new row beside them. The
    row at index ``i`` is that of the last position before ``p`` that is
    ``i`` modulo ``W``; it is ``1 + (p - 1 - i) mod W`` positions behind.
    At ``W`` behind (index ``p mod W``: the row the lane's own will
    replace) it has left the window, and a row further behind than ``p``
    was never written by this request: what a slot's last tenant left there
    is masked by age as a stale row of a full layer is by position
    (:func:`_ring_rows_young`). A lane
    younger than the window so attends what it has written and no more.
    ``frontier`` (B,) as in :func:`causal_attend_step`; a lane's reach into
    a ring ends at ``W``. The same two walks as a full layer's, by
    ``walk.kernel``."""
    w = k_ring.shape[2]
    reach = jnp.minimum(positions if frontier is None else frontier, w)
    return _rows_attend_step(q, k_ring, v_ring, layer, k_new, v_new,
                             positions, walk, reach, (_ring_rows_young, w))


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
    walk: StepWalk,           # ``step_walk`` of the two, ``latent``
    *,
    frontier=None,
    scale: float,
) -> jax.Array:
    """:func:`latent_attention` for the serving decode step, the cache read
    as it lies, as :func:`causal_attend_step` reads a per-head one: the own
    row's score is ``q_lat . latent_new + q_pe . pe_new``, its value the
    new latent. Returns the heads' averaged latents (B, 1, H, r)."""
    if frontier is None:
        frontier = positions
    q_lat, q_pe = q_lat[:, 0], q_pe[:, 0]                 # (B, H, .)

    def take(lane, lanes, start, size):
        return tuple(_lane_rows(c, layer, lane, lanes, start, size)[:, :, 0]
                     for c in (latent_cache, pe_cache))

    def score(rows, lane, lanes, k_pos):
        z = jnp.einsum("bhr,bsr->bhs", _of_lanes(q_lat, lane, lanes),
                       rows[0], preferred_element_type=jnp.float32)
        z = z + jnp.einsum("bhe,bse->bhs", _of_lanes(q_pe, lane, lanes),
                           rows[1], preferred_element_type=jnp.float32)
        allowed = k_pos[None, :] < _of_lanes(positions, lane, lanes)[:, None]
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
                       new[:, None].astype(jnp.float32), walk, frontier,
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
