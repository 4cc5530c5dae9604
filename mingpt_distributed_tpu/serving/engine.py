"""Compiled prefill / multi-slot decode for the continuous-batching server.

A bounded family of programs is compiled, once each, for the server's
lifetime:

1. **prefill-at-offset** — one forward over a right-padded token chunk
   through ``generate._forward_cached_hidden`` (the same unrolled
   cached-block chain solo ``generate()`` uses) against the slot's cache
   lane at a *traced* absolute offset, whose updated lane is written back
   into the pool at a *traced* slot index. Logits are read at the *traced*
   position ``length - 1`` and the next token is sampled on device. The
   chunk is padded to the smallest covering **bucket** from a power-of-two
   ladder (``prefill_buckets``), so the executable count is O(log
   block_size) while prefill FLOPs track the chunk length — a 10-token
   prompt no longer pays a block_size² attention forward. The same
   program serves whole short prompts (offset 0), the per-step chunks of
   a long prompt (``prefill_chunk``-token pieces between decode steps),
   and the tail after a prefix-cache hit.

2. **decode-step** — one token for every slot at once: the pool is a solo
   cache whose batch is the slot axis, so the step is one ``(B=S, T=1)``
   call of the same ``_forward_cached`` the solo scan uses, at a ``(S,)``
   vector of absolute positions (per-slot ``kv_offset`` and RoPE /
   learned-position index). Each layer reads its slice of the pool as
   it lies, each lane's new row attended beside it under one softmax: the
   lanes that hold a request alone, each in blocks as far as its own
   position; in the XLA walk a block that enough of them need for all
   lanes at once, in the kernel's walk (rows side by side on the chip)
   none (``attn_ops.step_plan``), by the one ``StepWalk`` the engine works
   out of its pool's leaves (``decode_walk``: the program is built with
   it and ``decode_rows_read`` counts by it); after the last layer
   one row-sized ``dynamic_update_slice`` a lane writes all layers' rows
   into the donated pool where they lie
   (``generate._write_lane_rows``). The
   pool is never transposed, copied or rebuilt inside the program: a
   ``vmap`` over lanes of a batch of one would batch each lane's update
   into a scatter with the slot axis in front, and the TPU compiler then
   rewrites the whole pool four times a step. An expert MLP routes lane
   by lane. Per-slot sampling params ride as traced arrays. A lane's
   input token is the host's, or the step before's output where it lies
   on the device (``prev_tokens`` under the mask ``from_prev``, one
   ``select`` at the program's head): the scheduler launches a step
   before it has seen the last one's tokens (``launch_decode``,
   ``sync_decode``; ``decode_step`` is the two back to back).

3. **prefix extract / install** (only when the prefix store is enabled) —
   device-side row copies between a slot lane and a shared-prefix cache
   entry, one trace per bucket-quantized prefix length.

Padding correctness: the *stale-row invariant*. A cache row only becomes
visible to attention once a query position reaches it, and every writer
(prefill chunk or decode step) writes real K/V to a row *before* the
first query that could attend it — causal masking is positional, not
value-based, so rows past the real-token frontier may hold anything:
pad garbage from a bucket, a previous tenant's K/V, or a parked decode
lane's scribbles at ``block_size - 1``. This is why admission no longer
needs to zero a slot and why chunked prefill can interleave with decode.

Sampling parity: the per-slot sampler mirrors ``generate._select_next``
(temperature → top-k → top-p → sample/argmax) with the params as traced
per-slot arrays instead of static python scalars — which is what keeps one
compiled program serving mixed greedy/sampled tenants. For greedy lanes
the filters cannot move the argmax, so a greedy request's tokens match
solo ``generate()`` exactly (tests/test_serving.py asserts token identity).
What a round computes is chosen inside the program, by ``lax.cond`` on its
own inputs (``_select_next_slots``): every lane's logits are divided by
its temperature; if no lane samples, one argmax and nothing else; if some
do, the S x V draws as well; and only if one of those samples under top-k
or top-p (``sampler_orders``) is the vocabulary ordered, once, for both
filters (top-k masks by value, so the order of the masked logits is the
masked order). A lane without a request asks for nothing: the scheduler
resets a slot's sampling parameters when it frees it
(``SlotTable.release``). Tokens are those of the sampler that sorted twice
every round, bit for bit (tests/test_sampler_order.py keeps that sampler
as its reference), and the scheduler counts the rounds that sorted
(``sampler_sorted_rounds``).
Chunked prefill is exactly row-equivalent to one whole-prompt forward:
attention, MLP and norms are row-wise, and a chunk's queries see the same
keys at the same absolute positions the one-shot forward would.

Weights: parameters arrive in float32 and ``cfg.dtype`` is the
activations'; every matmul takes its weight as ``w.astype(x.dtype)``. The
weights are arguments of the programs, so XLA cannot hoist that cast out
of them: at 1.6B it was 13 ms at the head of every program (PR 29). The
engine therefore hands its programs ``program_params``, in which
``generate.cast_once_params`` has made that cast once, at construction,
for exactly the leaves whose only use it is: same bits, no ``convert`` of
a weight in any program. ``params`` stays the tree that was handed in.

Tensor-parallel sharding (ISSUE 14): the engine optionally runs across a
``jax.sharding.Mesh``. Params shard by ``parallel/mesh.py``'s megatron
rules (column/row-split matmuls over the tp axis); the KV pool and every
prefix-store entry shard their *heads* dimension over the same axis, so
per-device KV bytes are ``total / tp`` and attention — embarrassingly
parallel over heads — never moves K/V between chips. The sharding is
bound into each program as a partial-bound constant (``kv_sharding``
below), making the mesh part of the program's compile identity the same
way ``cfg`` is: one engine = one mesh = still exactly one executable per
family, so ``compile_counts()`` and the recompile watchdog are oblivious
to sharding. ``_pin_kv`` re-asserts the sharding on every program's cache
output, which keeps donation aliasing exact (output layout == input
layout) and stops GSPMD from ever deciding to gather the pool.
"""

from __future__ import annotations

import functools
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from mingpt_distributed_tpu.config import ConfigError, GPTConfig
from mingpt_distributed_tpu.models import generate as gen
from mingpt_distributed_tpu.models import gpt
from mingpt_distributed_tpu.ops import attention as attn_ops
from mingpt_distributed_tpu.parallel import mesh as mesh_lib
from mingpt_distributed_tpu.serving import quant as quant_lib
from mingpt_distributed_tpu.serving.kv_pool import PrefixKVStore, SlotKVPool
from mingpt_distributed_tpu.telemetry import programs as program_lib
from mingpt_distributed_tpu.telemetry.spans import SpanTracer

#: smallest default bucket — prompts below this pay one 64-token forward,
#: which already beats a block_size² prefill by >100x at block_size 1024
DEFAULT_MIN_BUCKET = 64


def bind_static(fn, **static):
    """``functools.partial(fn, **static)`` under ``fn``'s own name. XLA names
    a jitted program after the callable it was given, and a bare partial
    reaches it as ``jit__unknown``: bound this way the module is
    ``jit_<fn.__name__>`` (``jit__decode_impl``), which is how a profile, and
    the benchmark's readers, tell the engine's programs apart."""
    @functools.wraps(fn)
    def bound(*args, **kwargs):
        return fn(*args, **static, **kwargs)

    return bound


def kv_pool_spec(cfg: GPTConfig, tp_axis: str = "tp"):
    """PartitionSpec of a pool's ``"k"``, ``"v"`` leaves (``generate.
    cache_leaf_shapes``) and of the prefix entries they exchange rows
    with: the axis that holds the KV heads shards over the tensor axis,
    every other dimension replicates. That is axis 3 of a per-head leaf
    ``(L, S, block, KV, hd)``, and the last of a leaf that keeps a
    position's heads side by side, ``(L, S, block, 1, KV x hd)``: equal
    parts of such a row are whole heads where ``kv_heads % tp == 0``
    (the engine holds the rule to the head count, not the width). Heads
    are the right axis because attention is independent per head, so a
    head-sharded cache is read and written only by the chip that owns it
    (no collective touches K/V); slots must stay whole per device (the
    traced-slot dynamic slices address the full slot axis). head_dim is
    deliberately NOT spelled as a trailing None: the runtime normalizes
    compiled-output specs by stripping trailing Nones, and executable
    cache keys compare shardings by equality — an unnormalized spec on
    the warmup cache would make the first serving call on a warmed
    bucket compile a second (identical) executable."""
    spec = (None, None, None, tp_axis)
    if gen.row_heads(cfg) > 1:
        spec = (None,) + spec
    return jax.sharding.PartitionSpec(*spec)


def _kv_leaves(cache):
    """Names of the leaves that hold something of a slot, sorted: cached
    rows and, in a hybrid stack's pool, pooled keys and the linear layers'
    state, each with the slot as its second axis. Every leaf but the
    counters (``generate.COUNTERS``), which ride in a pool and are no (L, S,
    ...) buffer."""
    return sorted(n for n in cache if n not in gen.COUNTERS)


def _with_counter(kv, cache):
    """``kv`` (leaves of a slot's or a pool's rows and state) with the
    counters of ``cache``, where it has any: what a program hands back for
    a cache it was handed."""
    return {**kv, **{n: cache[n] for n in gen.COUNTERS if n in cache}}


def _pin_kv(cache, kv_sharding):
    """``with_sharding_constraint`` over a cache (or prefix entry)
    pytree — ``{"k","v"}``, plus the ``*_scale`` planes of a quantized
    pool, which carry the same head-sharding spec (their sharded axis is
    kv_heads, where the data's is: serving/quant.py).
    ``kv_sharding`` reaches every program as a partial-bound constant —
    trace-time static, exactly like ``cfg`` — which is how the mesh
    participates in the compile key without adding executables. ``None``
    (single-device engine) is the identity."""
    if kv_sharding is None:
        return cache
    return _with_counter({
        name: jax.lax.with_sharding_constraint(cache[name], kv_sharding)
        for name in _kv_leaves(cache)
    }, cache)


def bucket_ladder(
    prefill_len: int,
    buckets: Optional[Sequence[int]] = None,
    chunk: Optional[int] = None,
) -> Tuple[int, ...]:
    """The sorted ladder of compiled prefill lengths.

    Default: powers of two from ``min(DEFAULT_MIN_BUCKET, prefill_len)``
    up to ``prefill_len``, always including ``prefill_len`` itself (and
    ``chunk`` when chunked prefill is on, so full chunks never pad) —
    O(log prefill_len) entries.
    """
    if buckets is not None:
        vals = {int(b) for b in buckets}
        for b in vals:
            if not (1 <= b <= prefill_len):
                raise ValueError(
                    f"prefill bucket {b} outside [1, {prefill_len}]")
    else:
        vals = set()
        b = min(DEFAULT_MIN_BUCKET, prefill_len)
        while b < prefill_len:
            vals.add(b)
            b *= 2
    vals.add(prefill_len)
    if chunk is not None:
        vals.add(int(chunk))
    return tuple(sorted(vals))


def sampler_orders(do_sample, top_ks, top_ps):
    """Whether a round has to order the vocabulary: some lane samples
    under a filter (``top_k`` on, or ``top_p`` below 1). NumPy or ``jnp``
    vectors alike: the programs branch on it (:func:`_select_next_slots`)
    and the scheduler counts it, on the host's copy of the same vectors."""
    return (do_sample & ((top_ks > 0) | (top_ps < 1.0))).any()


def decode_frontier(positions, live):
    """Each lane's reach (S,): how far the decode step reads its slot. Its
    position where the step is run for it and 0 where it is not. ``live``
    and not the positions says which lanes those are (a free lane is
    parked at the window's last row, and so is a request's last step);
    ``live`` None: every lane."""
    return positions if live is None else positions * live


def decode_rows_read(positions, live, walk: attn_ops.StepWalk):
    """Rows of the slots a decode step reads, a plane, summed over the
    lanes, by whichever of the two rules ``walk`` holds
    (``attn_ops.step_rows_read`` on :func:`decode_frontier`). The XLA
    walk's: whole blocks up to each live lane's own position, nothing of a
    lane that is not live but the blocks read for all lanes together
    (``attn_ops.step_plan``'s sharing, blocks by STEP_COST_BYTES). The
    kernel's (``walk.kernel``): whole blocks of ``attn_ops.kernel_block``
    rows up to each live lane's own position and nothing else, the rows
    the kernel's grid takes. Or every slot
    whole where ``walk`` walks none (rows the device keeps positions minor;
    a pool one pass reads in a few steps' time, ``attn_ops.step_block``; a
    hybrid stack's sparse layers). ``walk`` is the engine's
    (``DecodeEngine.walk``, :func:`decode_walk`): the one object its decode
    program was built with. NumPy or ``jnp`` vectors alike: the program's
    walk takes this many rows a plane and the scheduler counts it, on the
    host's copy of the same vectors."""
    return attn_ops.step_rows_read(walk, decode_frontier(positions, live))


def ring_rows(positions, live, walk: attn_ops.StepWalk):
    """(rows read, rows inside their lanes' windows) of a window layer's
    rings in a decode step, a plane, summed over the lanes:
    :func:`decode_rows_read` for a ring of ``walk.s`` rows
    (``attn_ops.ring_attend_step``), by the same two rules: the XLA walk
    reads a block that enough lanes need of every lane's ring, the
    kernel's each lane's ring alone, to its own reach. A lane's reach into its ring ends at
    the ring's size, and of the rows it is read, those of the ``ring - 1``
    positions before its own are inside its window (its own new row is
    attended beside the ring and is none of the ring's). ``walk`` is the
    engine's (``DecodeEngine.ring_walk``). NumPy or ``jnp`` vectors alike."""
    reach = decode_frontier(positions, live)
    return (attn_ops.step_rows_read(walk, reach.clip(0, walk.s)),
            reach.clip(0, walk.s - 1).sum())


def decode_walk(cfg: GPTConfig, cache, kv_quant=None,
                sharded: bool = False) -> attn_ops.StepWalk:
    """The decode step's walk over the pool ``cache``, worked out once from
    the pool's own leaves as the step reads them (``generate.cache_walk``
    on their shapes and the dtype they have after :func:`_dequant_lane`: a
    ``cache_dtype`` of the engine's, or ``cfg.dtype`` out of a quantized
    pool). A pool that is quantized (the step reads a dequantized copy) or
    ``sharded`` over a mesh is not whole on one device as it lies, and
    keeps the XLA walk wherever it runs. A hybrid stack's layers read every
    row of every slot and take no walk: its rule walks nothing."""
    if cfg.mixer_types is not None:
        return attn_ops.StepWalk(cfg.block_size, 0, 0)
    return gen.cache_walk(cfg, jax.eval_shape(
        lambda c: _dequant_lane(c, kv_quant, cfg), cache),
        whole=kv_quant is None and not sharded)


@jax.named_scope("sample")
def _select_next_slots(
    logits: jax.Array,      # (S, V) fp32
    keys: jax.Array,        # (S,) typed PRNG keys
    temps: jax.Array,       # (S,) float32
    top_ks: jax.Array,      # (S,) int32, 0 = disabled
    top_ps: jax.Array,      # (S,) float32, >= 1.0 = disabled
    do_sample: jax.Array,   # (S,) bool
) -> jax.Array:
    """generate._select_next with per-slot traced params. Filter order and
    edge semantics (top token always survives top-p; top_k clamped to V)
    match the solo sampler exactly.

    The round does what its lanes need and no more, chosen by two
    conditionals on the program's own inputs: no lane samples, one argmax;
    some do, the draws too; one of them under a filter
    (:func:`sampler_orders`), the vocabulary's one sort and the filters
    for all lanes. A lane's token is the same whichever branch ran (a
    filter cannot move an argmax, and a disabled filter removes nothing)."""
    v = logits.shape[-1]
    logits = logits / jnp.maximum(temps, 1e-8)[:, None]

    def filtered(logits):
        # top-k with per-slot k: threshold at the k-th largest value; k=V
        # is a no-op, so "disabled" rides as k_eff = V
        k_eff = jnp.where(top_ks > 0, jnp.minimum(top_ks, v), v)
        # descending without a reversed copy: negation is exact. Values
        # alone are sorted, so a stable sort would order nothing more, and
        # on the TPU it carries an index operand that doubles its time
        desc = -jnp.sort(-logits, axis=-1, stable=False)
        kth = jnp.take_along_axis(desc, (k_eff - 1)[:, None], axis=-1)
        logits = jnp.where(logits < kth, -jnp.inf, logits)
        # nucleus: smallest prefix of the post-top-k distribution whose
        # preceding cumulative mass is < top_p; top token unconditional.
        # top-k masks by value, so the masked logits in descending order
        # are the descending order masked: no second sort
        desc = jnp.where(desc < kth, -jnp.inf, desc)
        probs = jax.nn.softmax(desc, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        keep = (cum - probs) < top_ps[:, None]
        keep = keep.at[:, 0].set(True)
        kth2 = jnp.min(jnp.where(keep, desc, jnp.inf), axis=-1, keepdims=True)
        nucleus_on = (top_ps < 1.0)[:, None]
        return jnp.where(nucleus_on & (logits < kth2), -jnp.inf, logits)

    def sample(logits):
        logits = jax.lax.cond(
            sampler_orders(do_sample, top_ks, top_ps),
            filtered, lambda l: l, logits)
        sampled = jax.vmap(
            lambda l, k: jax.random.categorical(k, l))(logits, keys)
        return jnp.where(do_sample, sampled, jnp.argmax(logits, axis=-1))

    return jax.lax.cond(
        do_sample.any(), sample,
        lambda l: jnp.argmax(l, axis=-1), logits).astype(jnp.int32)


@jax.named_scope("kv_layout")
def _slot_lane(cache, slot):
    """The (L, 1, S, heads, size) cache lane of one slot, ``heads`` and
    ``size`` each leaf's own (``generate.cache_leaf_shapes``: per-head
    rows, heads side by side, a latent's parts; scale planes, when
    present, slice the same way)."""
    out = {}
    for name in _kv_leaves(cache):
        l, _, s, kv, last = cache[name].shape
        out[name] = jax.lax.dynamic_slice(
            cache[name], (0, slot, 0, 0, 0), (l, 1, s, kv, last))
    return out


@jax.named_scope("kv_layout")
def _install_lane(cache, lane, slot):
    return {
        name: jax.lax.dynamic_update_slice(
            cache[name], lane[name], (0, slot, 0, 0, 0))
        for name in _kv_leaves(cache)
    }


def _dequant_lane(lane, kv_quant, cfg):
    """Quantized lane -> the fp32 ``{"k","v"}`` lane the shared forward
    blocks consume; identity when the engine stores fp32. Static branch:
    ``kv_quant`` is partial-bound, never traced."""
    if kv_quant is None:
        return lane
    return quant_lib.dequantize_lane(lane, jnp.dtype(cfg.dtype))


def _requant_lane(lane, kv_quant, cfg):
    """The write-back half: requantize a forwarded lane before it
    re-enters the pool, a scale a row and head (``generate.row_heads``
    of them side by side in a row). Power-of-two scales make this exactly
    idempotent on rows the forward did not touch (serving/quant.py), which
    is what keeps greedy decode deterministic and migrated rows
    bit-stable."""
    if kv_quant is None:
        return lane
    return quant_lib.quantize_lane(lane, kv_quant, gen.row_heads(cfg))


def lane_keys(seeds: jax.Array, token_index: Optional[jax.Array] = None):
    """(S,) typed keys, ``fold_in(key(seed), token_index)`` a lane: the key
    under which the request of that seed samples its token of that index
    (``token_index`` None is index 0, a request's first token). Called
    inside the traced program that samples with them, so a round's keys
    cost the host two small vectors and the device S threefry hashes —
    not one eager dispatch a lane. ``seeds`` that are typed keys already
    (``benchmarks/rehearse.py`` lowers the programs with them) pass
    through as they are."""
    if jnp.issubdtype(seeds.dtype, jax.dtypes.prng_key):
        return seeds
    if token_index is None:
        token_index = jnp.zeros(seeds.shape, jnp.int32)
    return jax.vmap(
        lambda s, i: jax.random.fold_in(jax.random.key(s), i)
    )(seeds, token_index)


def request_seeds(seeds) -> np.ndarray:
    """Request seeds as the uint32 the programs take: a seed's low 32
    bits, which is all ``jax.random.key`` keeps of it. A caller that
    still holds typed keys made by ``key(seed)`` (the benchmark's
    correctness check does) may pass those: a threefry key's low word is
    its seed."""
    if isinstance(seeds, jax.Array) and jnp.issubdtype(
            seeds.dtype, jax.dtypes.prng_key):
        seeds = np.asarray(jax.random.key_data(seeds))[..., -1]
    return np.asarray(seeds).astype(np.uint32, copy=False)


def _forward_slot_lane(params, cache, tokens, offset, slot, *, cfg,
                       kv_quant, valid=None):
    """Forward ``tokens`` (T,) at absolute position ``offset`` against one
    slot's lane (dequantized for the forward and requantized whole after,
    on a quantized pool) and write the lane back. Returns (hidden states
    (1, T, D), the pool's cache). A routed model's counter rides through
    the forward beside the lane."""
    lane = _with_counter(
        _dequant_lane(_slot_lane(cache, slot), kv_quant, cfg), cache)
    x, lane = gen._forward_cached_hidden(
        params, tokens[None], lane, offset, cfg, valid)
    cache = _with_counter(cache, lane)
    lane = _requant_lane(lane, kv_quant, cfg)
    return x, _with_counter(_install_lane(cache, lane, slot), cache)


def _prefill_impl(
    params, cache, chunk, length, offset, slot,
    temp, top_k, top_p, do_sample, seed,
    *, cfg: GPTConfig, kv_sharding=None, kv_quant=None,
):
    """chunk: (bucket,) right-padded tokens; length/offset/slot traced
    scalars; seed the request's (uint32, traced). Forwards the chunk at
    absolute position ``offset`` against the slot's cache lane (attending
    everything written before it) and writes the lane back. Returns (token
    sampled at within-chunk position ``length - 1`` under
    ``fold_in(key(seed), 0)`` (scalar int32), updated pool cache) — the
    caller only uses the token on the final chunk of a prompt. A quantized
    engine (``kv_quant``) dequantizes the lane before the forward and
    requantizes the whole lane after — both inside this traced program,
    so the dtype rides the compile key and no collective is added."""
    # a routed model's counter counts the chunk's real tokens, not its
    # padding
    x, cache = _forward_slot_lane(
        params, cache, chunk, offset, slot, cfg=cfg, kv_quant=kv_quant,
        valid=(jnp.arange(chunk.shape[0]) < length)[None])
    h_last = jax.lax.dynamic_slice_in_dim(x, length - 1, 1, axis=1)
    logits = gen._head_logits(params, h_last, cfg)[:, 0]  # (1, V)
    tok = _select_next_slots(
        logits, lane_keys(seed[None]), temp[None], top_k[None], top_p[None],
        do_sample[None],
    )[0]
    return tok, _pin_kv(cache, kv_sharding)


def _decode_impl(
    params, cache, tokens, positions, temps, top_ks, top_ps, do_sample,
    seeds, token_index=None, live=None, prev_tokens=None, from_prev=None,
    *, cfg: GPTConfig, kv_sharding=None, kv_quant=None, walk=None,
    ring_walk=None,
):
    """One token for every slot: tokens/positions (S,), sampling arrays
    (S,), request seeds (S,) uint32, the index (S,) of the token each
    lane samples, from which the lanes' keys are derived here
    (:func:`lane_keys`), and ``live`` (S,) bool, the lanes the step is run
    for (None: all). ``prev_tokens`` (S,) are the tokens the step before
    this one returned, still on the device, and ``from_prev`` (S,) bool the
    lanes that feed theirs: a lane launched before the host has seen its
    last token takes it from there, every other lane from ``tokens`` (None:
    all from ``tokens``). Returns (next tokens (S,), updated pool cache).

    The pool is a solo cache whose batch is the slot axis, so the step is
    one ``(B=S, T=1)`` forward through the same cached-block chain solo
    ``generate`` uses, at a ``(S,)`` vector of positions
    (``generate._cached_block``): each layer reads its slice of the pool
    as it lies, under each lane's own causal mask, with the lane's new
    row attended beside it, and all layers' rows are written into the
    donated pool after the last, one row-sized update a lane. Nothing in
    the program has the pool's size but the pool, and nothing a slice's.
    Each lane's reach is worked out here from ``positions`` and ``live``
    (:func:`decode_frontier`) and the attention walks the lanes by it as
    ``walk`` says and a window layer's ring as ``ring_walk`` does, the
    engine's (:func:`decode_walk`, ``generate.ring_walk``; None: the
    cache's own, worked out here), which is what :func:`decode_rows_read`
    and :func:`ring_rows` count by: a ``live`` lane is read in blocks as far as
    its own position, a lane that is not live is passed by and attends its
    own new row (and what a block read for all lanes leaves it), a routed
    model lays none of its
    routes and counts none (``moe.grouped_swiglu``: it takes the shared
    expert alone), a hybrid stack leaves its state as it was, and its
    token is the caller's to discard; a live lane attends every row its
    mask allows and takes every expert it chose. Positions are clipped, so
    a free lane (parked at ``block_size - 1``) writes into its own lane and
    no other.
    A quantized pool is dequantized, stepped and requantized whole
    (idempotent on the rows the step did not touch: serving/quant.py)."""
    if prev_tokens is not None:
        tokens = jax.lax.select(from_prev, prev_tokens, tokens)
    safe_pos = jnp.clip(positions, 0, cfg.block_size - 1)
    # ``live`` and not the position says which lanes hold a request (a
    # request's last step stands where a free lane is parked)
    logits, stepped = gen._forward_cached(
        params, tokens[:, None],
        _with_counter(_dequant_lane(cache, kv_quant, cfg), cache),
        safe_pos, cfg, valid=None if live is None else live[:, None],
        # a hybrid stack's layers read every row and take no reach
        frontier=None if cfg.mixer_types is not None
        else decode_frontier(safe_pos, live), walk=walk, rings=ring_walk)
    cache = _with_counter(_requant_lane(stepped, kv_quant, cfg), stepped)
    nxt = _select_next_slots(logits, lane_keys(seeds, token_index),
                             temps, top_ks, top_ps, do_sample)
    if kv_sharding is not None:
        # whole on every chip, and said so: the next step takes these as
        # they are, under the sharding ``DecodeEngine._idle_tokens`` has
        nxt = jax.lax.with_sharding_constraint(
            nxt, _replicated(kv_sharding.mesh))
    return nxt, _pin_kv(cache, kv_sharding)


def _replicated(mesh) -> jax.sharding.NamedSharding:
    return jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())


def _extract_prefix_impl(cache, slot, *, rows: int, kv_sharding=None):
    """Copy the first ``rows`` K/V rows of a slot lane out of the pool —
    the device-side read half of a prefix-store insert. ``rows`` is static
    (one trace per bucket-quantized prefix length). The entry keeps the
    pool's head-sharding (same spec, smaller row count), so storing a
    prefix never gathers K/V to one chip."""
    out = {}
    for name in _kv_leaves(cache):
        l, _, _, kv, last = cache[name].shape
        out[name] = jax.lax.dynamic_slice(
            cache[name], (0, slot, 0, 0, 0), (l, 1, rows, kv, last))
    return _pin_kv(out, kv_sharding)


def _install_prefix_impl(cache, entry, slot, *, kv_sharding=None):
    """Write a stored (L, 1, P, heads, size) prefix entry (a lane dict: K/V
    payloads plus scale planes on a quantized pool) into rows [0, P) of a
    slot lane — a device-side dynamic_update_slice, no recompute. Entry
    and pool carry the same head-sharding, so a hit is a chip-local row
    copy. For the fp32 ``{"k","v"}`` entry this flattens to the identical
    two-leaf program as before the quantization layer existed."""
    return _pin_kv(_with_counter({
        name: jax.lax.dynamic_update_slice(
            cache[name], entry[name].astype(cache[name].dtype),
            (0, slot, 0, 0, 0))
        for name in _kv_leaves(cache)
    }, cache), kv_sharding)


class DecodeLaunch:
    """A decode step the device has been handed and the host has not waited
    for: ``tokens`` is the step's (S,) result where it lies, on the device.
    ``DecodeEngine.launch_decode`` makes one, ``sync_decode`` fetches it, and
    the launch that follows may take its tokens as they are (``prev``)."""

    __slots__ = ("tokens",)

    def __init__(self, tokens: jax.Array):
        self.tokens = tokens


class DecodeEngine:
    """Owns the slot pool, the bucket ladder, the optional prefix store,
    and the jitted programs.

    The jit wrappers are per-engine objects so their compile caches count
    only this engine's traces — ``compile_counts()`` is how the tests
    assert the bounded-program guarantee: decode stays at 1 trace and
    prefill at <= len(ladder) traces for the engine's lifetime.
    """

    def __init__(
        self,
        params,
        cfg: GPTConfig,
        n_slots: int,
        prefill_len: Optional[int] = None,
        cache_dtype=None,
        prefill_buckets: Optional[Sequence[int]] = None,
        prefill_chunk: Optional[int] = None,
        prefix_cache_mb: float = 0.0,
        mesh: Optional[jax.sharding.Mesh] = None,
        tp_axis: str = "tp",
        kv_dtype: Optional[str] = None,
        tracer: Optional[SpanTracer] = None,
    ):
        self.cfg = cfg
        # the server's tracer, for the two halves of a decode step; alone,
        # the engine has a disabled one and span() is a shared no-op
        self.tracer = tracer if tracer is not None else SpanTracer(enabled=False)
        self.mesh = mesh
        self.tp_axis = tp_axis
        # ISSUE 18: "fp32" (default; byte-identical to the pre-quant
        # engine), "int8", or "fp8" (where the backend dtype exists).
        # Resolved once; the KVQuant descriptor is partial-bound into the
        # program families below, so the dtype IS part of each compile key.
        self.kv_quant = quant_lib.resolve_kv_dtype(kv_dtype)
        self.kv_dtype = "fp32" if self.kv_quant is None else self.kv_quant.name
        if self.kv_quant is not None and cache_dtype is not None:
            raise ValueError(
                "cache_dtype and kv_dtype are mutually exclusive — a "
                "quantized pool's storage dtype comes from kv_dtype")
        tp = 1 if mesh is None else int(mesh.shape.get(tp_axis, 1))
        if cfg.kv_lora_rank and (self.kv_quant is not None or tp > 1):
            raise ConfigError(
                "a latent (MLA) cache is served unquantized on one chip's "
                "engine: its one latent a token has no heads to split over "
                f"tp={tp}, and kv_dtype={self.kv_dtype!r} would scale the "
                "rope key and the latent, which differ in size and range, "
                "by one rule made for per-head rows")
        if cfg.mixer_types is not None and (
                self.kv_quant is not None or mesh is not None
                or prefix_cache_mb > 0):
            raise ConfigError(
                "a hybrid stack (mixer_types) is served on one device with "
                "an unquantized pool and no prefix store: no rule splits a "
                "state's heads or the block selection over a mesh, "
                f"kv_dtype={self.kv_dtype!r} has no scale for a state or a "
                "pooled key, and a stored prefix would have to carry the "
                "state at its last row, which rows copied up to a bucket "
                "do not")
        if cfg.layer_types is not None and (
                self.kv_quant is not None or mesh is not None
                or prefix_cache_mb > 0 or prefill_chunk is not None):
            raise ConfigError(
                "a stack of layer_types is served on one device with an "
                "unquantized pool, whole prompts and no prefix store: no "
                "rule splits two kinds of layer's unlike head counts over a "
                f"mesh, kv_dtype={self.kv_dtype!r} has no scale for a ring, "
                "a stored prefix or a migrated slot would have to carry the "
                "window layers' rings at its last row, which rows copied up "
                "to a bucket do not, and a last chunk shifted back to stay "
                "inside the context would lay rows in a ring that the "
                "chunk before it has already passed")
        if mesh is not None:
            # One placement decision, made once: params follow the megatron
            # column/row rules, the pool shards heads over the tp axis (or
            # downgrades to replication when kv_heads % tp != 0 — counted
            # by shard_by_rule's telemetry, never an error). The rule is
            # held to the heads an axis holds: of a row of heads side by
            # side that is the row's head count, not its width.
            params = jax.device_put(
                params, mesh_lib.param_shardings(mesh, params))
            shape, heads = (gen.cache_leaf_shapes(cfg, n_slots)["k"],
                            gen.row_heads(cfg))
            if heads > 1:
                shape = shape[:-1] + (heads,)
            self.kv_sharding = mesh_lib.shard_by_rule(
                mesh, shape, kv_pool_spec(cfg, tp_axis), name="kv_cache")
        else:
            self.kv_sharding = None
        # what was handed in (placed, under a mesh), and what the programs
        # read (module docstring, "Weights"): one tree on a float32 engine
        self.params = params
        self.program_params, self.n_cast_leaves = gen.cast_once_params(
            params, cfg)
        self.prefill_len = int(prefill_len or cfg.block_size)
        if not (1 <= self.prefill_len <= cfg.block_size):
            raise ValueError(
                f"prefill_len {self.prefill_len} outside [1, "
                f"{cfg.block_size}]"
            )
        if prefill_chunk is not None:
            prefill_chunk = int(prefill_chunk)
            if not (1 <= prefill_chunk <= self.prefill_len):
                raise ValueError(
                    f"prefill_chunk {prefill_chunk} outside [1, "
                    f"{self.prefill_len}]"
                )
        if cfg.mixer_types is not None and prefill_chunk is not None \
                and self.prefill_len + prefill_chunk > cfg.block_size:
            raise ConfigError(
                "a hybrid stack's state takes every token once: a last "
                "chunk shifted back to stay inside the window would "
                f"re-prefill its overlap, so prefill_len {self.prefill_len}"
                f" + prefill_chunk {prefill_chunk} must not pass block_size "
                f"{cfg.block_size}")
        self.prefill_chunk = prefill_chunk
        self.buckets = bucket_ladder(
            self.prefill_len, prefill_buckets, prefill_chunk)
        self.pool = SlotKVPool(
            cfg, n_slots, cache_dtype, sharding=self.kv_sharding,
            quant=self.kv_quant)
        # the pool normalizes the sharding to the runtime's canonical
        # form; the programs must bind THAT object, or executable keys
        # (which compare shardings) would treat warmup inputs and
        # compiled-output caches as different layouts
        self.kv_sharding = self.pool.sharding
        self.prefix_store = (
            PrefixKVStore(int(prefix_cache_mb * (1 << 20)))
            if prefix_cache_mb > 0 else None
        )
        # kv_sharding rides as a partial-bound constant beside cfg: the
        # mesh is compile identity, not a traced input, so each family
        # still owns exactly one jit wrapper (and one executable).
        kv = self.kv_sharding
        kq = self.kv_quant
        self._prefill_jit = jax.jit(
            bind_static(_prefill_impl, cfg=cfg, kv_sharding=kv, kv_quant=kq),
            donate_argnums=(1,))
        # how the decode step walks this pool: the program is built with it
        # and the scheduler counts by it (decode_rows_read)
        self.walk = decode_walk(cfg, self.pool.cache, kq, kv is not None)
        # and a window layer's ring (None: the stack has none)
        self.ring_walk = gen.ring_walk(
            cfg, self.pool.cache, whole=kq is None and kv is None)
        self._decode_jit = jax.jit(
            bind_static(_decode_impl, cfg=cfg, kv_sharding=kv, kv_quant=kq,
                        walk=self.walk, ring_walk=self.ring_walk),
            donate_argnums=(1,))
        self._decode_counts: Optional[Tuple[int, int]] = None
        # prefix copy programs: `rows` is static, so one jit wrapper traces
        # once per bucket-quantized prefix length
        self._extract_jit = jax.jit(
            bind_static(_extract_prefix_impl, kv_sharding=kv),
            static_argnames=("rows",))
        self._install_jit = jax.jit(
            bind_static(_install_prefix_impl, kv_sharding=kv),
            donate_argnums=(0,))
        self._idle_tokens = self._step_like_zeros()

    def _step_like_zeros(self) -> jax.Array:
        """(S,) zeros that the jit call cannot tell from a decode step's own
        tokens: what a launch with no step before it hands the program as
        ``prev_tokens``, so that it and a launch that runs ahead are one
        entry of the jit's cache (a committed array and an uncommitted one
        are two). A step's tokens are committed where any argument is, which
        the vectors never are: under a mesh whole on every chip
        (``_decode_impl`` says so), else where the weights or the pool
        lie."""
        zeros = np.zeros(self.n_slots, np.int32)
        if self.kv_sharding is not None:
            return jax.device_put(zeros, _replicated(self.kv_sharding.mesh))
        for leaf in jax.tree.leaves((self.program_params, self.pool.cache)):
            if getattr(leaf, "committed", False):
                return jax.device_put(zeros, leaf.sharding)
        return jnp.asarray(zeros)

    @property
    def n_slots(self) -> int:
        return self.pool.n_slots

    @property
    def kv_shard_count(self) -> int:
        """Devices one pool buffer is split over (1 = unsharded)."""
        return self.pool.shard_count

    @property
    def program_param_bytes(self) -> int:
        """Bytes of the tree the programs read."""
        return sum(a.nbytes for a in jax.tree.leaves(self.program_params))

    @property
    def kv_bytes_per_row(self) -> int:
        """Bytes one cached token costs in the pool, all planes (a layer's,
        or a pass and layer's of a looped stack) and row
        leaves (a quantized pool's scale planes and a hybrid stack's pooled
        keys too, each over the positions a slot holds; its state, which
        costs a slot the same whatever it holds, is
        ``state_bytes_per_slot``)."""
        return sum(a.nbytes // (a.shape[1] * self.cfg.block_size)
                   for n, a in self.pool.cache.items()
                   if n not in gen.COUNTERS + gen.RINGS and n != gen.STATE)

    @property
    def ring_bytes_per_slot(self) -> int:
        """Bytes of the window layers' rings a slot holds beside its rows
        (a stack of ``layer_types``), whatever the request's length; 0
        where every layer keeps a row a position."""
        return sum(a.nbytes // a.shape[1]
                   for n, a in self.pool.cache.items() if n in gen.RINGS)

    @property
    def ring_rows_per_slot(self) -> int:
        """Rows one ring of a slot holds (the window); 0 with no ring."""
        ring = self.pool.cache.get(gen.RING_K)
        return 0 if ring is None else int(ring.shape[2])

    @property
    def ring_planes(self) -> int:
        """The window layers, each with a ring of keys and one of values."""
        ring = self.pool.cache.get(gen.RING_K)
        return 0 if ring is None else int(ring.shape[0])

    @property
    def state_bytes_per_slot(self) -> int:
        """Bytes of state a slot holds beside its rows (a hybrid stack's
        linear layers); 0 where every layer keeps rows."""
        state = self.pool.cache.get(gen.STATE)
        return 0 if state is None else state.nbytes // state.shape[1]

    def _counter(self, name: str) -> Optional[np.ndarray]:
        counter = self.pool.cache.get(name)
        return None if counter is None else np.asarray(jax.device_get(counter))

    def moe_rows(self) -> Optional[np.ndarray]:
        """The routed-rows counter as it stands, fetched from the device:
        (expert layers, E + 4), or None where the model counts none. The
        one transfer the counter ever costs; no round makes it."""
        return self._counter(gen.MOE_ROWS)

    def sparse_rows(self) -> Optional[np.ndarray]:
        """``generate.SPARSE_ROWS`` as it stands, fetched likewise: (2,)
        [rows attended, rows at or before the query], or None."""
        return self._counter(gen.SPARSE_ROWS)

    def _counted_in_decode(self) -> Tuple[int, int]:
        """(:meth:`head_boundaries`, :meth:`kernel_walk_layers`), counted
        in the decode program's own trace, once: the jit's own where a step
        has run, which ``warmup`` sees to, so a serving loop's first
        ``summary()`` traces nothing."""
        if self._decode_counts is None:
            (_, _, jitted, args, kwargs), = [
                p for p in self.programs() if p[0] == "decode"]
            jaxpr = jitted.trace(*args, **kwargs).jaxpr
            self._decode_counts = (gpt.head_boundaries(jaxpr),
                                   attn_ops.kernel_walks(jaxpr))
        return self._decode_counts

    def head_boundaries(self) -> int:
        """Projections of the decode program whose product stands behind
        ``gpt.head_projection``'s boundary (0: the rule passed the model
        by)."""
        return self._counted_in_decode()[0]

    def kernel_walk_layers(self) -> int:
        """Layers of the decode program whose walk over the pool is the
        kernel's (``attn_ops.kernel_walks``: the ``rows_attend`` calls its
        trace holds, a layer each); 0 where every layer keeps the XLA walk
        or reads its slices in one pass (any CPU run, a latent pool, a
        hybrid stack)."""
        return self._counted_in_decode()[1]

    def loop_passes(self) -> Optional[np.ndarray]:
        """``generate.LOOP_PASSES`` as it stands, fetched likewise: (2 +
        n_passes,) [token-passes run, tokens, each pass's exit mass], or
        None where the layers run once and no gate is read."""
        return self._counter(gen.LOOP_PASSES)

    @property
    def chunk_size(self) -> int:
        """Max tokens one prefill call processes (= prefill_len when
        chunking is off)."""
        return self.prefill_chunk or self.prefill_len

    def bucket_for(self, n: int) -> int:
        """Smallest ladder bucket covering an n-token chunk."""
        for b in self.buckets:
            if b >= n:
                return b
        raise ValueError(
            f"chunk length {n} exceeds largest bucket {self.buckets[-1]}")

    def prefill_chunk_call(
        self,
        slot: int,
        chunk_ids: Sequence[int],
        offset: int,
        temperature: float,
        top_k: Optional[int],
        top_p: Optional[float],
        do_sample: bool,
        seed,
    ) -> Tuple[int, int]:
        """Prefill ``chunk_ids`` into ``slot`` at absolute ``offset``.
        ``seed`` is the request's (:func:`request_seeds`). Returns (sampled
        token at the chunk's last real position — only meaningful on a
        prompt's final chunk — and the padded bucket length actually
        forwarded)."""
        n = len(chunk_ids)
        if n < 1:
            raise ValueError("empty prefill chunk")
        bucket = self.bucket_for(n)
        if offset + bucket > self.cfg.block_size:
            raise ValueError(
                f"chunk bucket {bucket} at offset {offset} overruns the "
                f"{self.cfg.block_size} cache window (the scheduler "
                "shifts the final chunk back to keep buckets in-window)"
            )
        padded = np.zeros(bucket, np.int32)
        padded[:n] = np.asarray(chunk_ids, np.int32)
        tok, cache = self._prefill_jit(
            self.program_params, self.pool.cache, padded,
            np.int32(n), np.int32(offset), np.int32(slot),
            np.float32(temperature),
            np.int32(0 if top_k is None else top_k),
            np.float32(1.0 if top_p is None else top_p),
            np.bool_(do_sample), request_seeds(seed)[()],
        )
        self.pool.cache = cache
        return int(jax.device_get(tok)), bucket

    # -- shared-prefix KV reuse ----------------------------------------
    def quantized_prefix_len(self, prompt_len: int) -> int:
        """Rows worth storing for an n-token prompt: the largest bucket
        <= prompt_len - 1 (a hit must leave >= 1 tail token to prefill,
        because the first sampled token needs the last prompt position's
        logits). 0 = too short to store."""
        best = 0
        for b in self.buckets:
            if b <= prompt_len - 1:
                best = b
        return best

    def try_load_prefix(self, slot: int, prompt_ids: Sequence[int]) -> int:
        """Install the longest stored prefix of ``prompt_ids`` into
        ``slot`` (device-side row copy, no recompute). Returns the number
        of rows installed (0 = miss / store disabled)."""
        if self.prefix_store is None:
            return 0
        hit = self.prefix_store.lookup(tuple(prompt_ids))
        if hit is None:
            return 0
        rows, entry = hit
        self.pool.cache = self._install_jit(
            self.pool.cache, entry, np.int32(slot))
        return rows

    def save_prefix(self, slot: int, prompt_ids: Sequence[int]) -> int:
        """After a slot finished prefilling ``prompt_ids``, copy its
        bucket-quantized leading rows into the prefix store. Returns rows
        stored (0 = skipped: disabled, too short, or already present)."""
        if self.prefix_store is None:
            return 0
        rows = self.quantized_prefix_len(len(prompt_ids))
        if rows == 0:
            return 0
        key = tuple(prompt_ids[:rows])
        if self.prefix_store.contains(key):
            return 0
        lane = self._extract_jit(self.pool.cache, np.int32(slot), rows=rows)
        stored = self.prefix_store.insert(key, lane)
        return rows if stored else 0

    # -- live migration (ISSUE 16) -------------------------------------
    def migratable_rows(self, prompt_len: int, frontier: int) -> int:
        """Rows worth shipping for a slot whose cache holds ``frontier``
        valid leading rows of an ``prompt_len``-token prompt: the largest
        ladder bucket <= min(frontier, prompt_len - 1) — capped at the
        frontier (a mid-prefill slot only has real K/V up to there; the
        stale-row invariant makes everything past it garbage) and one
        short of the prompt (a hit on the peer must leave >= 1 tail token
        to prefill). Collapses to ``quantized_prefix_len`` for a slot
        that finished prefilling. 0 = nothing shippable."""
        if self.cfg.mixer_types is not None \
                or self.cfg.layer_types is not None:
            # a state cannot be cut at a bucket, pooled keys lie on
            # another grid than rows, and a ring holds the rows before a
            # request's last position, not a bucket's: the peer prefills
            # anew
            return 0
        cap = min(frontier, prompt_len - 1)
        best = 0
        for b in self.buckets:
            if b <= cap:
                best = b
        return best

    def _place_entry(self, entry: Dict[str, jax.Array]) -> Dict[str, jax.Array]:
        """Re-place a lane/entry dict (possibly host arrays off the
        transfer channel) under the pool's sharding so adopted rows stay
        head-sharded on device exactly like locally-extracted ones."""
        if self.kv_sharding is not None:
            return {n: jax.device_put(a, self.kv_sharding)
                    for n, a in entry.items()}
        return {n: jnp.asarray(a) for n, a in entry.items()}

    def extract_slot_rows(self, slot: int, rows: int) -> Dict[str, jax.Array]:
        """Pull ``rows`` leading K/V rows out of ``slot`` as a pinned
        (L, 1, rows, heads, size) entry dict (payloads + scale planes on a
        quantized engine — a migrated quantized entry ships ~4x fewer
        bytes) — the extract half of live migration, through the SAME
        row-copy program family ``save_prefix`` uses. ``rows`` must sit
        on the bucket ladder so this never grows the bounded prefix-copy
        family past one trace per bucket."""
        if self.cfg.mixer_types is not None:
            raise ValueError(
                "a slot of a hybrid stack is rows and a state: its leading "
                "rows alone are no request (migratable_rows is 0)")
        if self.cfg.layer_types is not None:
            raise ValueError(
                "a slot of a stack of layer_types is rows and the window "
                "layers' rings: its leading rows alone are no request "
                "(migratable_rows is 0)")
        if rows not in self.buckets:
            raise ValueError(
                f"extract rows {rows} not on the bucket ladder "
                f"{self.buckets} — migration must reuse the compiled "
                f"prefix-copy programs, not mint new ones")
        return self._extract_jit(self.pool.cache, np.int32(slot), rows=rows)

    def install_slot_rows(self, slot: int, entry: Dict[str, jax.Array]) -> int:
        """Copy an extracted (L, 1, rows, heads, size) entry dict straight
        into ``slot``'s leading cache rows — the install half of live
        migration for engines that have no prefix store (the draft
        engine): same compiled row-copy program ``try_load_prefix`` uses.
        Returns the rows installed."""
        rows = int(entry["k"].shape[2])
        if rows not in self.buckets:
            raise ValueError(
                f"install rows {rows} not on the bucket ladder "
                f"{self.buckets} — migration must reuse the compiled "
                f"prefix-copy programs, not mint new ones")
        self.pool.cache = self._install_jit(
            self.pool.cache, self._place_entry(entry), np.int32(slot))
        return rows

    def adopt_prefix_entry(self, key: Sequence[int],
                           entry: Dict[str, jax.Array]) -> bool:
        """Install a migrated prefix entry dict (host arrays off the
        transfer channel) into THIS engine's prefix store, re-placed
        under the pool's sharding so entries stay head-sharded on device
        exactly like locally-saved ones. Returns False when the store is
        disabled, full, or already holds the key."""
        if self.prefix_store is None:
            return False
        key = tuple(int(t) for t in key)
        if self.prefix_store.contains(key):
            return False
        return self.prefix_store.insert(key, self._place_entry(entry))

    # -- warmup --------------------------------------------------------
    def warmup(self) -> None:
        """Pre-trace the full program family so no request pays a compile:
        one prefill per ladder bucket, the decode step, and (when the
        prefix store is on) the copy programs per storable bucket. Safe
        only while the pool has no tenants — warmup scribbles over slot
        0's cache rows, which the stale-row invariant makes harmless."""
        assert self.pool.used_count == 0, "warmup requires an empty pool"
        for b in self.buckets:
            self.prefill_chunk_call(
                0, [0] * b, 0, 1.0, None, None, False, 0)
        s = self.n_slots
        parked = (
            np.zeros(s, np.int32),
            np.full(s, self.cfg.block_size - 1, np.int32),
            np.ones(s, np.float32), np.zeros(s, np.int32),
            np.ones(s, np.float32), np.zeros(s, bool),
            np.zeros(s, np.uint32), np.zeros(s, np.int32),
            np.zeros(s, bool),
        )
        # both ways the serving loop calls the one program: after nothing,
        # and ahead of a step whose tokens are still on the device
        first = self.launch_decode(*parked)
        self.sync_decode(self.launch_decode(
            *parked, prev=first, from_prev=np.zeros(s, bool)))
        # counted now, off the trace the step above made: a serving loop's
        # first summary() then traces nothing
        self._counted_in_decode()
        if self.prefix_store is not None:
            for b in self.buckets:
                if b <= self.prefill_len - 1:
                    lane = self._extract_jit(
                        self.pool.cache, np.int32(0), rows=b)
                    self.pool.cache = self._install_jit(
                        self.pool.cache, lane, np.int32(0))
        # the warm-up's prompts (one token repeated) are no traffic: the
        # device's counters count from here on
        for name, fresh in ((gen.MOE_ROWS, gen.init_moe_rows),
                            (gen.SPARSE_ROWS, gen.init_sparse_rows),
                            (gen.LOOP_PASSES, gen.init_loop_passes)):
            old = self.pool.cache.get(name)
            if old is not None:
                zeroed = fresh(self.cfg)
                self.pool.cache[name] = jax.device_put(
                    zeroed, old.sharding) if old.committed else zeroed

    def launch_decode(
        self,
        tokens: np.ndarray,
        positions: np.ndarray,
        temps: np.ndarray,
        top_ks: np.ndarray,
        top_ps: np.ndarray,
        do_sample: np.ndarray,
        seeds,
        token_index: Optional[np.ndarray] = None,
        live: Optional[np.ndarray] = None,
        prev: Optional[DecodeLaunch] = None,
        from_prev: Optional[np.ndarray] = None,
    ) -> DecodeLaunch:
        """Hand the device one decode step (:meth:`decode_step` has the
        vectors) and return without waiting for it: the span
        ``serve.decode_launch`` is the staging of the arguments and the jit
        call up to its return (the enqueue). ``prev`` is the launch before
        this one, synced or not, and ``from_prev`` (S,) bool the lanes whose
        input token is that step's output, taken on the device; every other
        lane, and every lane where ``prev`` is None, feeds ``tokens``. The
        vectors are copied here: the caller may change its own while the
        step runs. The pool is donated from step to step, so whatever is
        launched after this, a prefill or a row copy too, runs after it."""
        with self.tracer.span("serve.decode_launch"):
            s = len(tokens)
            if token_index is None:
                token_index = np.zeros(s, np.int32)
            if live is None:
                live = np.asarray(positions) < self.cfg.block_size - 1
            if prev is None or from_prev is None:
                from_prev = np.zeros(s, bool)
            nxt, cache = self._decode_jit(
                self.program_params, self.pool.cache,
                np.array(tokens, np.int32),
                np.array(positions, np.int32),
                np.array(temps, np.float32),
                np.array(top_ks, np.int32),
                np.array(top_ps, np.float32),
                np.array(do_sample, bool),
                np.array(request_seeds(seeds)),
                np.array(token_index, np.int32),
                np.array(live, bool),
                self._idle_tokens if prev is None else prev.tokens,
                np.array(from_prev, bool),
            )
            self.pool.cache = cache
        return DecodeLaunch(nxt)

    def sync_decode(self, launch: DecodeLaunch) -> np.ndarray:
        """The (S,) tokens of a launched step, on the host: the span
        ``serve.decode_sync`` is the wait for them. With a later step
        already launched the device works on through the wait."""
        with self.tracer.span("serve.decode_sync"):
            return np.asarray(jax.device_get(launch.tokens))

    def decode_step(
        self,
        tokens: np.ndarray,
        positions: np.ndarray,
        temps: np.ndarray,
        top_ks: np.ndarray,
        top_ps: np.ndarray,
        do_sample: np.ndarray,
        seeds,
        token_index: Optional[np.ndarray] = None,
        live: Optional[np.ndarray] = None,
    ) -> np.ndarray:
        """Advance every slot one token and wait for it: a
        :meth:`launch_decode` whose every lane feeds the host's ``tokens``,
        followed by its :meth:`sync_decode`; caller masks inactive lanes.
        Lane ``s`` samples under ``fold_in(key(seeds[s]), token_index[s])``,
        derived inside the program: ``seeds`` are the (S,) request seeds
        (:func:`request_seeds`), ``token_index`` how many tokens each
        request has emitted (None: 0 a lane). ``live`` (S,) bool names the
        lanes whose tokens the caller will use (None: those not parked at
        the window's last row, where the scheduler keeps a lane without a
        request; a request's last step stands there too, so a caller that
        runs one says so): the pool is read as it lies, and a long slot
        only as far as the furthest live lane stands
        (:func:`decode_rows_read`); a routed model's experts run for the
        live lanes' routes alone, and a lane that is not live keeps its
        recurrent state; it is always an argument of the one program. The
        host vectors go to the one jit call; no other program is
        dispatched. The scheduler calls the two halves itself, and the
        launch of the next step before the sync of this one
        (``InferenceServer.step``)."""
        return self.sync_decode(self.launch_decode(
            tokens, positions, temps, top_ks, top_ps, do_sample, seeds,
            token_index, live))

    def compile_counts(self) -> Dict[str, int]:
        """Distinct traces per program family. After warmup: decode 1,
        prefill <= len(self.buckets), prefix copies <= len(self.buckets)
        each — bounded for the server's lifetime no matter how many
        requests are served."""
        return {
            "prefill": self._prefill_jit._cache_size(),
            "decode": self._decode_jit._cache_size(),
            "prefix_load": self._install_jit._cache_size(),
            "prefix_save": self._extract_jit._cache_size(),
        }

    # -- compiled programs, as data ------------------------------------
    def programs(self, family_prefix: str = ""):
        """Yield ``(family, variant, jitted, args, kwargs)`` for every
        compiled program of this engine, with the arguments of a real
        call: what ``jitted.lower(*args, **kwargs)`` needs to build the
        program the serving loop runs. The weights and the pool go as
        abstract values with the live arrays' shapes, dtypes and shardings,
        taken now: the pool is donated every round, and a caller may lower
        later (``telemetry/programs.py``); the small vectors are NumPy's, as
        the serving loop hands them over, so enumerating dispatches nothing.
        Family names mirror ``compile_counts()`` keys (prefixed for a draft
        engine) and key ``audit_contracts``; prefill/prefix variants are per
        ladder bucket."""
        params = program_lib.abstract(self.program_params)
        cache = program_lib.abstract(self.pool.cache)
        for b in self.buckets:
            yield (family_prefix + "prefill", f"b{b}", self._prefill_jit,
                   (params, cache,
                    np.zeros(b, np.int32),
                    np.int32(b), np.int32(0), np.int32(0),
                    np.float32(1.0), np.int32(0), np.float32(1.0),
                    np.bool_(False), np.uint32(0)), {})
        s = self.n_slots
        yield (family_prefix + "decode", "", self._decode_jit,
               (params, cache,
                np.zeros(s, np.int32), np.zeros(s, np.int32),
                np.ones(s, np.float32), np.zeros(s, np.int32),
                np.ones(s, np.float32), np.zeros(s, bool),
                np.zeros(s, np.uint32), np.zeros(s, np.int32),
                np.ones(s, bool), program_lib.abstract(self._idle_tokens),
                np.zeros(s, bool)), {})
        if self.prefix_store is None:
            return
        for b in self.buckets:
            if b > self.prefill_len - 1:
                continue
            yield (family_prefix + "prefix_save", f"b{b}", self._extract_jit,
                   (cache, np.int32(0)), {"rows": b})
            entry = {}
            for name in _kv_leaves(cache):
                arr = cache[name]
                l, _, _, kv, last = arr.shape
                entry[name] = jax.ShapeDtypeStruct(
                    (l, 1, b, kv, last), arr.dtype)
            yield (family_prefix + "prefix_load", f"b{b}", self._install_jit,
                   (cache, entry, np.int32(0)), {})

    # -- static audit contracts (ISSUE 15) -----------------------------
    def audit_contracts(self, family_prefix: str = "") -> Dict[str, dict]:
        """Per-family contracts for ``analysis/hlo_audit.py`` — plain
        dicts (serving never imports the analysis layer), keyed like
        ``programs`` families. Grammar (docs/static_analysis.md):

        * ``allowed_collectives`` — collective op base names the lowered
          HLO may contain. Model-forwarding families at tp > 1 reduce
          partial matmul products over tp (``all-reduce``) and gather
          small per-token activations (``all-gather``); the prefix copy
          programs are chip-local row moves and allow nothing, at any tp.
        * ``donated`` — exact ``input_output_alias`` entry count the
          executable must carry: one per donated cache leaf (2 on an
          fp32 pool — k and v; 4 on a quantized pool — the scale planes
          alias too) for prefill/decode/prefix_load, 0 for prefix_save
          (extract donates nothing — the pool must survive the read).
        * ``kv_output_sharding`` — the normalized NamedSharding every
          returned cache/entry leaf must carry (None = single device).
        * ``pool_leaf_elems`` — element count of one K/V pool buffer; a
          collective result at least this large is moving the pool, which
          no contract ever allows.
        """
        facts = self.pool.audit_facts()
        tp = (1 if self.mesh is None
              else int(self.mesh.shape.get(self.tp_axis, 1)))
        model = {
            "allowed_collectives":
                ("all-gather", "all-reduce") if tp > 1 else (),
            "donated": len(self.pool.cache),
            "kv_output_sharding": self.kv_sharding,
            "pool_leaf_elems": facts["cache_leaf_elems"],
        }
        copy = dict(model, allowed_collectives=())
        return {
            family_prefix + "prefill": dict(model),
            family_prefix + "decode": dict(model),
            family_prefix + "prefix_save": dict(copy, donated=0),
            family_prefix + "prefix_load": dict(copy),
        }
