"""Mixture-of-experts MLPs. Two routes, chosen by ``GPTConfig.moe_scoring``:

* ``moe_mlp`` ("softmax"): capacity-based dispatch, GShard/Switch style,
  described below. The Mixtral-style presets, the benchmark's fixture
  ``rope-experts`` and every training path take it, as does the ``ep``
  exchange; tokens over an expert's capacity are dropped.
* ``moe_dropless`` ("sigmoid"): the DeepSeek-V3 route (``noaux_tc`` without
  a group limit), which kanana-2-30b-a3b takes: sigmoid scores, the k best
  of score + bias, the chosen scores normalised and scaled as gates, every
  route computed whatever the load, a shared expert added by the caller.
  See the function. Under ``GPTConfig.moe_dropless`` a "softmax" model takes
  it too (Laguna-XS.2: ``softmax_routes``, the k largest logits, their
  softmax probabilities renormalised and scaled as gates). The route
  (``dropless_routes``) and the experts (``grouped_swiglu``) are two calls,
  so a router may read other activations than the experts take
  (``GPTConfig.moe_router_input``), and the experts' gate goes through
  SiLU or ReLU (``GPTConfig.expert_act``).

The capacity route:

Beyond-parity capability (SURVEY §2.2: the reference has a dense MLP only,
model.py:179-184; EP/MoE marked absent). TPU-native design: dispatch and
combine are dense einsums against a static-shape one-hot tensor — no dynamic
shapes, no host control flow — so the whole layer jits into one XLA program.
Expert weights carry a leading expert axis that shards over the mesh's ``ep``
axis (parallel/mesh.py PARAM_RULES); since the token axis is batch-sharded
over dp/fsdp/ep, the dispatch einsum contracts a token-sharded tensor against
expert-sharded weights and **GSPMD inserts the all-to-alls** — the
hand-written NCCL alltoall of GPU MoE stacks becomes a compiler decision
(the framework's ICI/DCN story, SURVEY §2.3).

Tokens are routed in fixed-size **groups** (GShard's trick): the one-hot
dispatch tensor is (G, group, E, cap_per_group), so its memory is
k·factor·group·S — *linear* in sequence length — instead of the k·factor·S²
a single global group would cost (which at block_size 8192 would be GBs per
layer). Capacity is per group; cross-group imbalance can drop slightly more
tokens than global routing, the standard trade-off.

Routing: softmax router, top-k. k=1 (Switch) scales expert output by the
raw router probability — required so the router receives task-loss gradient
(with renormalised gates the k=1 weight is identically 1 and d loss/d router
== 0). k>=2 (GShard) renormalises the chosen gates to sum to 1. Tokens
overflowing an expert's per-group capacity are dropped for that slot (their
residual path still carries them). Load-balancing aux loss is the
Switch-Transformer one: E · Σ_e f_e · P_e over all tokens.

Caveat: when capacity binds, which tokens drop depends on the *set* of
tokens evaluated together — so KV-cached decode (one token at a time) only
reproduces a full re-forward when capacity_factor is high enough that
nothing drops (factor >= E/k guarantees it). Training is unaffected.
"""

from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from mingpt_distributed_tpu.ops import flash_attention

# Max tokens routed as one group; actual group size is the largest divisor
# of S at most this (S itself for small inputs). Groups below MIN_GROUP
# would collapse per-group expert capacity toward 1 and silently drop most
# routes — if S has no divisor in [MIN_GROUP, MAX_GROUP], route it as one
# big group instead (more dispatch memory, correct routing).
MAX_GROUP = 1024
MIN_GROUP = 128


def _group_size(s: int) -> int:
    if s <= MAX_GROUP:
        return s
    for g in range(MAX_GROUP, MIN_GROUP - 1, -1):
        if s % g == 0:
            return g
    return s


def _route_group(probs, *, top_k: int, cap: int):
    """One group's dispatch/combine from (gs, E) router probs.

    Returns (dispatch (gs, E, cap), combine (gs, E, cap), top1 (gs, E))."""
    gs, e = probs.shape
    remaining = probs
    counts = jnp.zeros((e,), jnp.float32)
    dispatch = jnp.zeros((gs, e, cap), jnp.float32)
    combine = jnp.zeros((gs, e, cap), jnp.float32)
    gates, onehots = [], []
    for _ in range(top_k):
        idx = jnp.argmax(remaining, axis=-1)            # (gs,)
        oh = jax.nn.one_hot(idx, e, dtype=jnp.float32)  # (gs, E)
        gates.append(jnp.sum(probs * oh, axis=-1))      # true prob, not masked
        onehots.append(oh)
        remaining = remaining * (1.0 - oh)
    # k=1: scale by the raw prob (Switch) so the router gets task gradient;
    # k>1: renormalise over the chosen k (GShard)
    denom = sum(gates) if top_k > 1 else jnp.ones_like(gates[0])
    for g, oh in zip(gates, onehots):
        # position of each token within its expert's buffer, honouring
        # tokens already placed by earlier slots
        pos = counts[None, :] + jnp.cumsum(oh, axis=0) - oh   # (gs, E)
        keep = oh * (pos < cap)
        counts = counts + jnp.sum(keep, axis=0)
        slot = jax.nn.one_hot(pos.astype(jnp.int32), cap, dtype=jnp.float32)
        sel = keep[..., None] * slot                     # (gs, E, cap)
        dispatch = dispatch + sel
        combine = combine + sel * (g / jnp.maximum(denom, 1e-9))[:, None, None]
    return dispatch, combine, onehots[0]


def moe_mlp(
    x: jax.Array,        # (B, T, D) — post-norm activations
    w_router: jax.Array,  # (D, E)
    w_e1: jax.Array,      # (E, D, F) — E/ep local experts under ep_axis
    w_e2: jax.Array,      # (E, F, D)
    *,
    top_k: int = 2,
    capacity_factor: float = 1.25,
    w_gate: jax.Array = None,  # (E, D, F): SwiGLU experts (Mixtral-style)
    ep_axis: str = None,
) -> Tuple[jax.Array, jax.Array]:
    """Expert-routed MLP: GELU experts, or SwiGLU when ``w_gate`` is given
    (h = silu(x·w_gate) * (x·w_e1), Mixtral-style). Returns
    (out (B, T, D), aux_loss scalar).

    ``ep_axis``: manual expert parallelism for shard_map regions (the
    pipeline — models/gpt.py), where GSPMD can't insert the all-to-alls
    itself. ``x`` is this shard's tokens, ``w_e*`` hold E/ep local experts
    (expert dim sharded by PARAM_RULES), ``w_router`` is replicated with
    all E columns. Routing runs locally against ALL experts; the expert
    FFN is redistributed with two all_to_alls over ``ep_axis`` — the same
    exchange GSPMD derives for the sharded einsum in the non-manual path.
    The aux loss stays a per-shard statistic either way; callers average
    it over the batch-ish axes (pipeline.py pmean includes ep).
    """
    b, t, d = x.shape
    e = w_e1.shape[0]
    ep = 1
    if ep_axis is not None:
        ep = jax.lax.psum(1, ep_axis)
        e = e * ep  # e: GLOBAL expert count; w_e* hold e/ep local rows
    if w_router.shape[1] != e:
        raise ValueError(
            f"router has {w_router.shape[1]} experts, weights imply {e}"
        )
    s = b * t
    gs = _group_size(s)
    ng = s // gs
    xs = x.reshape(ng, gs, d)

    logits = jnp.einsum(
        "gsd,de->gse", xs.astype(jnp.float32), w_router.astype(jnp.float32)
    )
    probs = jax.nn.softmax(logits, axis=-1)  # (G, gs, E) fp32

    cap = max(1, math.ceil(top_k * gs / e * capacity_factor))
    dispatch, combine, top1 = jax.vmap(
        lambda p: _route_group(p, top_k=top_k, cap=cap)
    )(probs)  # (G, gs, E, cap) x2, (G, gs, E)

    # (G, gs, E, cap) x (G, gs, D) -> experts see (E, G*cap, D)
    expert_in = jnp.einsum("gsec,gsd->gecd", dispatch.astype(x.dtype), xs)
    expert_in = expert_in.transpose(1, 0, 2, 3).reshape(e, ng * cap, d)
    if ep_axis is not None:
        # exchange: every shard sends each peer the inputs it routed to
        # that peer's experts, receiving its own experts' tokens from all
        # peers -> (E/ep, ep*n, d); shard i holds global experts
        # [i*E/ep, (i+1)*E/ep) exactly as PARAM_RULES lays them out
        expert_in = jax.lax.all_to_all(
            expert_in, ep_axis, split_axis=0, concat_axis=1, tiled=True
        )
    up = jnp.einsum(
        "end,edf->enf", expert_in, w_e1.astype(x.dtype),
        preferred_element_type=jnp.float32,
    )
    if w_gate is not None:
        gate = jnp.einsum(
            "end,edf->enf", expert_in, w_gate.astype(x.dtype),
            preferred_element_type=jnp.float32,
        )
        h = (jax.nn.silu(gate) * up).astype(x.dtype)
    else:
        h = jax.nn.gelu(up).astype(x.dtype)
    expert_out = jnp.einsum(
        "enf,efd->end", h, w_e2.astype(x.dtype),
        preferred_element_type=jnp.float32,
    )  # (E, G*cap, D) fp32 — (E/ep, ep*n, D) under ep_axis
    if ep_axis is not None:
        # inverse exchange: outputs return to the shards whose tokens they
        # are -> (E, n, d) with the global expert axis restored
        expert_out = jax.lax.all_to_all(
            expert_out, ep_axis, split_axis=1, concat_axis=0, tiled=True
        )
    expert_out = expert_out.reshape(e, ng, cap, d).transpose(1, 0, 2, 3)
    out = jnp.einsum(
        "gsec,gecd->gsd", combine.astype(jnp.float32), expert_out
    ).astype(x.dtype)

    # Switch load-balancing loss on top-1 assignment, over all tokens
    f = jnp.mean(top1.reshape(s, e), axis=0)   # fraction routed per expert
    p = jnp.mean(probs.reshape(s, e), axis=0)  # mean router prob per expert
    aux = e * jnp.sum(f * p)
    return out.reshape(b, t, d), aux


# ---------------------------------------------------------------------------
# The dropless route
# ---------------------------------------------------------------------------


def _row_block(rows: int, e: int) -> int:
    """Rows of one step of the grouped matmul: the power of two nearest
    above an expert's mean load, within [8, 256]. Every expert's rows are
    padded up to a multiple of it, so it trades padding (small loads) for
    steps (large ones)."""
    mean = max(1, -(-rows // e))
    return min(256, max(8, 1 << (mean - 1).bit_length()))


def sigmoid_routes(h, w_router, bias, *, top_k: int, norm_topk: bool,
                   route_scale: float):
    """(N, D) tokens -> (chosen experts (N, k) int32, gates (N, k) float32,
    selection scores (N, E) float32). Scores are ``sigmoid(h w_router)`` in
    float32; the k chosen are the largest of score + bias; the gates are
    the chosen experts' *scores* (the bias moves the choice and never a
    gate), over their sum under ``norm_topk``, times ``route_scale``."""
    z = h.astype(jnp.float32) @ w_router.astype(jnp.float32)
    scores = jax.nn.sigmoid(z)
    select = scores + bias.astype(jnp.float32)
    chosen = jax.lax.top_k(select, top_k)[1]
    gates = jnp.take_along_axis(scores, chosen, axis=-1)
    if norm_topk:
        gates = gates / (gates.sum(-1, keepdims=True) + 1e-20)
    return chosen.astype(jnp.int32), gates * route_scale, select


def softmax_routes(h, w_router, *, top_k: int, route_scale: float):
    """:func:`sigmoid_routes` for a router that scores with a softmax
    (Qwen2-MoE's, Laguna's): (N, D) tokens -> (chosen experts (N, k) int32,
    gates (N, k) float32, the router's logits (N, E) float32). The k chosen
    are the largest logits (ties to the lowest index); the gates are their
    softmax probabilities over all E experts, in float32, over the chosen
    ones' sum (``norm_topk_prob``), times ``route_scale``."""
    z = h.astype(jnp.float32) @ w_router.astype(jnp.float32)
    chosen = jax.lax.top_k(z, top_k)[1]
    gates = jnp.take_along_axis(jax.nn.softmax(z, axis=-1), chosen, axis=-1)
    gates = gates / gates.sum(-1, keepdims=True)
    return chosen.astype(jnp.int32), gates * route_scale, z


def _mosaic_compiles() -> bool:
    """Whether the cached path's blocks go through the Pallas kernel: where
    Mosaic compiles it. ``flash_attention._interpret`` is asked through its
    module, at trace time: it is the one name a compile rehearsal steers
    (``benchmarks/rehearse.py``), so a cell's programs planned for a
    described chip hold this kernel too."""
    return not flash_attention._interpret()


# What of a core's VMEM (128 MiB on a v5e) the two buffers of an expert's
# three weight tiles may take; the call asks for them, the blocks' own and
# the products' room
_WEIGHT_BUFFERS = 64 << 20


def _width_tile(d: int, f: int, itemsize: int) -> int:
    """Columns of ``F`` a grid step takes: all of them where an expert's
    three matrices fit their buffers twice over (9.4 MB an expert at 2,048 x
    768 in bfloat16), else the largest multiple of 128 that divides ``F``
    and does."""
    fits = lambda tf: 2 * 3 * d * tf * itemsize <= _WEIGHT_BUFFERS
    if fits(f):
        return f
    return max((tf for tf in range(128, f, 128) if f % tf == 0 and fits(tf)),
               default=f)


#: what an expert's gate goes through (``GPTConfig.expert_act``), and the
#: name its Pallas kernel is compiled under: a profile's reader finds the
#: kernel by it
GATE_ACTS = {"silu": jax.nn.silu, "relu": jax.nn.relu}
KERNEL_NAMES = {"silu": "grouped_swiglu", "relu": "grouped_reglu"}


def _glu_block_kernel(expert_ref, x_ref, wg_ref, wu_ref, wd_ref, o_ref, *,
                      act: str):
    """Grid step (i, j): block i of the layout through columns j of its
    expert's matrices, ``block()`` of :func:`grouped_swiglu` operand for
    operand, the gate through ``GATE_ACTS[act]``. The down product of a
    later tile of ``F`` adds to the output block, which stays where it is
    while i does."""
    del expert_ref      # the weights' index maps read it
    rows = x_ref[0]
    gate = jnp.dot(rows, wg_ref[0].astype(rows.dtype),
                   preferred_element_type=jnp.float32)
    up = jnp.dot(rows, wu_ref[0].astype(rows.dtype),
                 preferred_element_type=jnp.float32)
    inner = (GATE_ACTS[act](gate) * up).astype(rows.dtype)
    part = jnp.dot(inner, wd_ref[0].astype(rows.dtype),
                   preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(1) == 0)
    def _():
        o_ref[0] = part

    @pl.when(pl.program_id(1) > 0)
    def _():
        o_ref[0] += part


# One jitted caller a shape and not one a layer (kanana's decode program
# writes out five expert layers, each prefill bucket five more), as
# ``flash_attention._native_forward`` is and for its reason. Its name holds
# not the kernel's: XLA names instructions after it, and the benchmark's
# reader finds the kernel by name. What it traced holds what ``_interpret()``
# said then; a test that steers it clears the cache on both sides.
@functools.partial(jax.jit, static_argnames="act")
def _run_blocks(laid, expert, ran, w_gate, w_up, w_down, act="silu"):
    """The first ``ran`` blocks of ``laid`` (n_blocks, bm, D), block i
    through expert ``expert[i]`` of the stacked (L*E, D, F) / (L*E, F, D)
    leaves -> (n_blocks, bm, D) float32, under the kernel name of ``act``
    (KERNEL_NAMES: one body, compiled a gate activation). The leaves stay
    in HBM whole; the grid's first bound is ``ran`` itself, and the pipeline
    fetches block i + 1's matrices (where its expert is another) while
    block i is in the MXU. The blocks past ``ran`` are not written: they
    hold whatever the buffer held."""
    n_blocks, bm, d = laid.shape
    f = w_gate.shape[-1]
    tf = _width_tile(d, f, w_gate.dtype.itemsize)
    vmem = (2 * 3 * d * tf * w_gate.dtype.itemsize       # the weights' tiles
            + 2 * bm * d * (laid.dtype.itemsize + 4)     # a block in and out
            + 4 * bm * (3 * tf + d) * 4 + (4 << 20))     # the products
    return pl.pallas_call(
        functools.partial(_glu_block_kernel, act=act),
        out_shape=jax.ShapeDtypeStruct((n_blocks, bm, d), jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(ran, f // tf),
            in_specs=[
                pl.BlockSpec((1, bm, d), lambda i, j, ex: (i, 0, 0)),
                pl.BlockSpec((1, d, tf), lambda i, j, ex: (ex[i], 0, j)),
                pl.BlockSpec((1, d, tf), lambda i, j, ex: (ex[i], 0, j)),
                pl.BlockSpec((1, tf, d), lambda i, j, ex: (ex[i], j, 0)),
            ],
            out_specs=pl.BlockSpec((1, bm, d), lambda i, j, ex: (i, 0, 0)),
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=vmem),
        interpret=flash_attention._interpret(),
        name=KERNEL_NAMES[act],
    )(expert, laid, w_gate, w_up, w_down)


@jax.named_scope("moe_experts")
def grouped_swiglu(x, chosen, w_gate, w_up, w_down, valid=None, layer=None,
                   act: str = "silu"):
    """The chosen experts on every ``valid`` token: (N, D) tokens and (N, k)
    experts -> ((N, k, D) float32 expert outputs, (E + 4,) int32 counts).
    An expert is ``(act(x W_g) * (x W_u)) W_d``, ``act`` one of GATE_ACTS:
    SiLU (SwiGLU, the name this function has kept) or ReLU (ReGLU).
    The weights are one layer's (E, D, F) / (E, F, D) or, with ``layer``,
    the whole stack's (L, E, ...) of which that layer is taken: the loop
    or the kernel below then reads its one expert a step straight out of
    the stacked leaf. (A layer sliced out first is an operand of the loop,
    and the TPU compiler copies it there whole: 1.1 GB a layer at 128
    experts of 2,048 x 768.)

    Nothing is dropped and no expert computes a token that did not choose
    it: the routes of the tokens of ``valid`` (N,) (None: all) are laid out
    grouped by expert, each group padded to whole blocks of ``_row_block``
    rows. The layout's shape is static, the worst case (at most E - 1
    blocks of padding, whatever the load); the blocks that hold a row are
    its first ``sum_e ceil(size_e / bm)``, and those and no other go
    through their expert's three matrices: a block that holds no row runs
    no dot and reads no weight. One algorithm in three forms, chosen by
    what the call can see:

    * without ``layer`` the call may be differentiated (``gpt.forward``
      trains through it) and a loop of unknown length cannot be: an XLA loop
      over the layout's blocks, a ``lax.cond`` a step passing an empty
      block by (on the chip 2.5-3.5 us at 8 rows a block and 15 at 128,
      where a block's read is 16: PERF.md, PR 35);
    * with ``layer`` (the cached forward: inference only), where Mosaic
      compiles (:func:`_mosaic_compiles`: the chip), one Pallas kernel
      (:func:`_run_blocks`, KERNEL_NAMES of ``act``) whose grid is the blocks
      that hold a row: the next block's expert is fetched while this one is
      in the MXU, which nothing in a ``while`` body can be (PERF.md, PR 60);
    * with ``layer`` elsewhere, the XLA loop with that number as its trip
      count: the same operands, dtypes and products a block, so the CPU's
      serving programs are what they were.

    A route's result depends on its own token alone, so which other tokens
    share the call changes nothing for it. A token that is not ``valid`` (a
    lane without a request, a bucket's padding) has no route laid: its
    outputs are zeros, read from the fill and never from the layout, so the
    blocks the kernel does not write need no zeros.

    ``counts[:E]`` are the rows each expert's blocks computed, counted from
    the layout the blocks that ran read; ``counts[E]`` is the routes the
    ``valid`` tokens asked for: the two agree exactly when nothing was
    dropped. ``counts[E + 1]`` is the blocks taken through an expert,
    ``counts[E + 2]`` the blocks the layout has and ``counts[E + 3]`` the
    experts that hold at least one row: the fewest expert weights this
    call's blocks can read, each expert's three matrices once."""
    n, d = x.shape
    k = chosen.shape[1]
    e, first = w_gate.shape[-3], 0
    if layer is not None:
        first = layer * e
        w_gate, w_up, w_down = (w.reshape((-1,) + w.shape[2:])
                                for w in (w_gate, w_up, w_down))
    rows = n * k
    bm = _row_block(rows, e)
    n_blocks = -(-rows // bm) + e - 1

    flat = chosen.reshape(rows)
    asked = jnp.ones((rows,), bool) if valid is None else jnp.repeat(valid, k)
    onehot = jax.nn.one_hot(flat, e, dtype=jnp.int32) * asked[:, None]  # (R, E)
    sizes = onehot.sum(0)                                         # (E,)
    rank = jnp.take_along_axis(
        jnp.cumsum(onehot, axis=0) - onehot, flat[:, None], axis=1)[:, 0]
    blocks_of = -(-sizes // bm)                                   # (E,)
    last_block = jnp.cumsum(blocks_of)
    # a route nobody asked for lies past the layout: nothing is written
    # there and what is read there is zeros
    dest = jnp.where(asked, (last_block - blocks_of)[flat] * bm + rank,
                     n_blocks * bm)                               # (R,)
    # the block's expert; the blocks from ``last_block[-1]`` on hold no row
    block_expert = jnp.minimum(jnp.searchsorted(
        last_block, jnp.arange(n_blocks), side="right"), e - 1)
    # which route lies at each row of the layout (rows: padding)
    source = jnp.full((n_blocks * bm,), rows, jnp.int32).at[dest].set(
        jnp.arange(rows, dtype=jnp.int32), mode="drop")
    real = source < rows
    token = jnp.where(real, source // k, 0)
    laid = jnp.where(real[:, None], x[token], 0).reshape(n_blocks, bm, d)

    def block(i, out):
        # block i through its expert's three matrices, written where it lies
        rows_in, ex = laid[i], first + block_expert[i]
        gate = jnp.dot(rows_in, w_gate[ex].astype(x.dtype),
                       preferred_element_type=jnp.float32)
        up = jnp.dot(rows_in, w_up[ex].astype(x.dtype),
                     preferred_element_type=jnp.float32)
        inner = (GATE_ACTS[act](gate) * up).astype(x.dtype)
        return jax.lax.dynamic_update_index_in_dim(out, jnp.dot(
            inner, w_down[ex].astype(x.dtype),
            preferred_element_type=jnp.float32), i, 0)

    ran = last_block[-1]
    zeros = jnp.zeros((n_blocks, bm, d), jnp.float32)
    if layer is None:
        # every block a step, those that hold no row passed by
        out = jax.lax.fori_loop(0, n_blocks, lambda i, out: jax.lax.cond(
            i < ran, block, lambda i, out: out, i, out), zeros)
    elif _mosaic_compiles():
        out = _run_blocks(laid, (first + block_expert).astype(jnp.int32),
                          ran, w_gate, w_up, w_down, act)
    else:
        out = jax.lax.fori_loop(0, ran, block, zeros)
    out = out.reshape(n_blocks * bm, d).at[dest].get(
        mode="fill", fill_value=0).reshape(n, k, d)

    in_run = real.reshape(n_blocks, bm).sum(1) * (jnp.arange(n_blocks) < ran)
    computed = (jax.nn.one_hot(block_expert, e, dtype=jnp.int32)
                * in_run[:, None]).sum(0)
    return out, jnp.concatenate([computed, jnp.stack([
        asked.sum().astype(jnp.int32), ran, jnp.int32(n_blocks),
        (computed > 0).sum().astype(jnp.int32)])])


def dropless_routes(tokens, w_router, bias, *, top_k: int,
                    norm_topk: bool = True, route_scale: float = 1.0,
                    scoring: str = "sigmoid"):
    """The route of a dropless expert layer by itself: (N, D) normed
    activations (whichever the router reads: ``GPTConfig.moe_router_input``)
    -> (chosen experts (N, k) int32, gates (N, k) float32): DeepSeek-V3's
    (:func:`sigmoid_routes`) or, under ``scoring`` "softmax",
    :func:`softmax_routes`' (no ``bias``, gates always renormalised)."""
    if scoring == "softmax":
        return softmax_routes(
            tokens, w_router, top_k=top_k, route_scale=route_scale)[:2]
    return sigmoid_routes(
        tokens, w_router, bias, top_k=top_k, norm_topk=norm_topk,
        route_scale=route_scale)[:2]


def moe_dropless(
    x: jax.Array,         # (B, T, D) post-norm activations
    w_router: jax.Array,  # (D, E)
    bias: jax.Array,      # (E,) e_score_correction_bias
    w_gate: jax.Array,    # (E, D, F)
    w_up: jax.Array,      # (E, D, F)
    w_down: jax.Array,    # (E, F, D)
    *,
    top_k: int,
    norm_topk: bool = True,
    route_scale: float = 1.0,
    valid: jax.Array = None,   # (B, T) bool: the tokens that are routed
    layer: int = None,         # the expert weights are the stack's (L, E, ..)
    scoring: str = "sigmoid",
    route: Optional[Tuple[jax.Array, jax.Array]] = None,
    act: str = "silu",
) -> Tuple[jax.Array, jax.Array]:
    """The routed part of a dropless expert layer: ``sum_i g_i
    expert_i(x)`` over each ``valid`` token's k experts (zeros for any
    other). The route and the experts are two calls: ``route`` is
    :func:`dropless_routes`' (chosen, gates) where the caller made it from
    other activations than the experts take (a router that reads the
    attention's input), and made here from ``x`` where it is None. Returns
    (out (B, T, D), counts (E + 4,) int32: ``grouped_swiglu``, which also
    says what ``layer`` and ``act`` are for)."""
    b, t, d = x.shape
    tokens = x.reshape(b * t, d)
    chosen, gates = route if route is not None else dropless_routes(
        tokens, w_router, bias, top_k=top_k, norm_topk=norm_topk,
        route_scale=route_scale, scoring=scoring)
    out, counts = grouped_swiglu(
        tokens, chosen, w_gate, w_up, w_down,
        None if valid is None else valid.reshape(b * t), layer, act)
    out = jnp.einsum("nkd,nk->nd", out, gates)
    return out.astype(x.dtype).reshape(b, t, d), counts
