"""Causal flash attention — Pallas TPU kernel (FlashAttention-2 style).

Replaces, on the hot path, the einsum oracle in ops/attention.py (itself the
intended semantics of the reference's fused torch attention,
/root/reference/mingpt/model.py:147-165): same math, different memory story.
The einsum path materialises the (B, H, T, S) logits in HBM; this kernel
streams K/V blocks through VMEM with an online softmax, so attention memory
is O(T·d) — the property that makes long block_size HBM-feasible
(SURVEY §5.7's prescription for this framework).

Shapes follow ops.attention.causal_attention: q (B, T, H, hd), k/v
(B, S, KV, hd) with GQA handled by broadcasting outside the kernel (autodiff
then sums dk/dv over the query-head group for free).

Tiling: every kernel streams K/V (or Q, for dk/dv) **block-by-block through
the grid** — the per-cell VMEM footprint is O(block·hd + block²) regardless
of sequence length, so the shipped llama presets (block_size 8192) fit VMEM.
The sequential innermost grid dimension carries the online-softmax state
(running max m, denominator l, accumulator acc) in VMEM scratch across k
blocks; causality is enforced at block granularity by skipping cells above
the diagonal, whose index maps clamp to the diagonal so Pallas's revisit
optimisation never re-DMAs a block that won't be used.

Forward: grid (B*H, T/B, T/B) with the k-block index innermost; emits the
log-sum-exp per row for the backward.
Backward: two kernels — dq streams K/V blocks per q block; dk/dv streams
Q/dO blocks per k block — both recomputing probabilities from the saved LSE.
No stored attention matrix anywhere. The native-layout path computes a
cell's probabilities once, in one dq+dk+dv kernel (_dqkv_kernel_btd),
wherever that kernel's dq scratch fits VMEM (_fused_bwd_fits: a rule over
static shapes), and keeps the two-kernel form for the shapes beyond it.

A diagonal cell (qi == kj) of the native-layout forward and fused backward
computes nothing above the diagonal: where the block holds two or more
groups of DIAG_GROUP_ROWS rows and the layer has no window, the cell is
walked as a staircase, group r of the rows against keys [0, (r + 1) * g)
of the cell, with the constant row >= col mask on the last g columns only
(_diag_group_rows: a rule over the static block; 10 of a 512-cell's 16
sub-tiles). At T = 1,024, where two of a sequence's three live cells are
diagonal, that took 17.5% off the forward and 21.7% off the fused backward
on a v5e (PERF.md, PR 54, the A/B). Windowed layers, blocks under 256, the
split pair and the (BH, T, hd) kernels keep the whole-cell body.

Falls back to the einsum oracle when the shape/config doesn't fit the kernel
(attention dropout on, decode-time cross lengths, T not a multiple of the
block) — correctness is never gated on the fast path. On CPU the kernel runs
in Pallas interpret mode, which is how the parity tests exercise it.
"""

from __future__ import annotations

import functools
import math
import os
from typing import Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from mingpt_distributed_tpu.ops import attention as attn_ops

NEG_INF = -1e30

# Base-2 softmax rebase (round-5, measured): the VPU evaluates exp2 ~6%
# faster than exp (72.4 vs 68.2 G/s on the earlier installation), and log2(e) folds
# into the attention scale constant, so every kernel tracks scores, running
# max and alpha in base 2 at ZERO extra per-element ops — exp becomes exp2,
# nothing else changes. The saved log-sum-exp stays in the NATURAL domain
# (one per-row multiply at finalize): ring-attention merging and the dlse
# cotangent contract are unchanged. exp2(x * LOG2E) == exp(x).
LOG2E = 1.4426950408889634
INV_LOG2E = 1.0 / LOG2E

#: The names ``causal_attention``'s forward rules put on the two values only
#: the kernel can make again, its output and its log-sum-exp, where the call
#: sits in a layer under ``jax.checkpoint`` (``keep_pair``). The policy of
#: models/gpt.py saves them (what ``GPTConfig.remat`` means): the backward
#: makes q, k and v again by their matmuls and hands the backward kernel
#: this pair as the forward left it, so the forward kernel runs once a step
#: and not twice.
SAVED_OUT = "flash_out"
SAVED_LSE = "flash_lse"


def _kept(out, lse):
    """The forward's pair as a checkpoint keeps it. What a rule returns as
    the primal output and keeps as residuals is this one value, so the value
    a policy saves is the value the backward rule reads (``_as_written``
    puts the axis back). The log-sum-exp is kept without the kernels'
    trailing 1, which the device pads to 128 lanes: kept as the kernel
    wrote it, XL's 48 layers would hold 5.2 GB a chip for 0.04 GB of
    numbers. The barrier ties that dense copy to the output the layer goes
    on with: left free, the TPU scheduler puts it after the last layer's
    forward and every padded buffer lives until then (XL's step planned at
    15.73 GB so, against 10.43 at the parent and 11.80 tied: compile
    rehearsals, PR 64). The two copies cost a pass over the padded buffer
    each (0.28 ms a copy at the 124M cell's 201 MB: my chip run, PR 64),
    which is why a step without a checkpoint does not make them."""
    out, lse = jax.lax.optimization_barrier((out, lse[..., 0]))
    return checkpoint_name(out, SAVED_OUT), checkpoint_name(lse, SAVED_LSE)


def _as_written(lse, keep_pair):
    return lse[..., None] if keep_pair else lse


def _scores_base2(q, kblk, scale, softcap):
    """The shared per-cell score computation: QK^T -> optional softcap ->
    BASE-2 scores with the rebase constants folded in (see LOG2E note).

    Returns (s, t): s = base-2 scores, t = the raw tanh output when
    softcap is active (the backward's derivative factor is 1 - t*t;
    kept UNMASKED so it stays bounded in [0, 1]), else None. One
    definition for all six kernels — the math must never diverge between
    them.
    """
    s = jax.lax.dot_general(
        q, kblk, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    if softcap is not None:
        t = jnp.tanh(s * (scale / softcap))
        return (softcap * LOG2E) * t, t
    return s * (scale * LOG2E), None


def _btd_applies(h: int, hd: int) -> bool:
    """Whether causal_attention routes (h, hd) to the native-(B,T,D)
    kernels — directly packed, or via odd-head zero padding."""
    return _btd_pack(h, hd) is not None or (hd < 128 and 128 % hd == 0)


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def supported_block(t: int) -> Optional[int]:
    """Public applicability probe: the square block size the kernel would
    tile T with, or None when the kernel doesn't apply (callers — e.g. the
    ring-attention dispatch — must then use an oracle path)."""
    return _block_sizes(t)


def _block_sizes(t: int) -> Optional[int]:
    """Pick a square block size dividing T, or None if the kernel won't fit.

    ``FLASH_BLOCK`` overrides the preference order (the fixed (512, 256,
    128) ladder had no measured justification): the
    override is used when it divides T, else the default ladder applies.
    """
    override = os.environ.get("FLASH_BLOCK")
    if override:
        try:
            ob = int(override)
        except ValueError:
            ob = 0
        # Clamp to the validated ladder range: above 512 the (block, block)
        # fp32 scratch outgrows VMEM and Mosaic compile fails at trace time,
        # and a process-global env var would poison ring-attention dispatch
        # for every caller, not just the sweep that set it.
        if 8 <= ob <= 512 and t % ob == 0:
            return ob
    for b in (512, 256, 128):
        if t % b == 0:
            return b
    if t <= 128 and t % 8 == 0:
        return t
    return None


# ---------------------------------------------------------------------------
# Forward kernel
# ---------------------------------------------------------------------------


def _dispatch_cells(compute, qi, kj, block, active, *, causal, window,
                    q_offset=0):
    """Route one grid cell to ``compute(masked)`` — the ONE definition of
    the masked/full cell classification for all six kernels. Under a
    window every active cell keeps the masked body (band edges cross
    cells); plain causal splits active cells into the masked diagonal
    and the mask-free interior (min q_pos at or past max k_pos, which
    generalises "strictly below the diagonal" to the ring's q_offset
    hops — full cells also cannot hold dead rows, so their p needs no
    structural mask); non-causal is always mask-free.

    What a masked cell then does is its kernel's: the (BH, T, hd) kernels
    and the split native-layout pair mask the whole (block, block) cell by
    position; the native-layout forward and fused backward, whose
    q_offset is 0 (so a masked cell without a window IS the cell on the
    diagonal, its corner the positions' origin), walk it as a staircase
    of row groups that end at the diagonal wherever ``_diag_group_rows``
    says so (PERF.md, PR 54: the A/B)."""
    if causal and window is not None:
        @pl.when(active)
        def _m():
            compute(True)
    elif causal:
        cell_full = (q_offset + qi * block) >= (kj + 1) * block - 1

        @pl.when(active & ~cell_full)
        def _diag():
            compute(True)

        @pl.when(active & cell_full)
        def _full():
            compute(False)
    else:
        @pl.when(active)
        def _nc():
            compute(False)


def _kv_lo(qi, block, window, q_offset=0):
    """First k block a banded-causal q block attends (window in tokens).

    ``q_offset`` shifts the q block's global position: the ring's
    cross-chunk hops (parallel/ring_attention.py) reuse these kernels with
    q sitting ``q_offset`` tokens after k, so the band runs diagonally
    through the (q, k) block grid instead of hugging the main diagonal.
    """
    return jnp.maximum(q_offset + qi * block - (window - 1), 0) // block


def _kv_hi(qi, block, q_offset, nk):
    """Last k block with any causally-visible key for this q block."""
    return jnp.minimum((q_offset + qi * block + block - 1) // block, nk - 1)


def _q_lo(kj, block, q_offset):
    """First q block that causally sees a k block (q_offset as above)."""
    return jnp.maximum(kj * block - q_offset, 0) // block


def _q_hi(kj, block, window, q_offset=0):
    """Last q block that attends a banded-causal k block."""
    return (kj * block + block + window - 2 - q_offset) // block


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr,
                *, scale, block, causal, window=None, softcap=None,
                q_offset=0):
    qi = pl.program_id(1)
    kj = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(kj == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def _compute(masked):
        # matmul inputs stay in the storage dtype (bf16 on the hot path) —
        # the MXU runs bf16 x bf16 -> fp32 at full rate where fp32 x fp32
        # costs several passes; accumulation is fp32 via
        # preferred_element_type, and the softmax math stays fp32.
        q = q_ref[0]  # (BQ, hd)
        kblk = k_ref[0]  # (BK, hd)
        vblk = v_ref[0]
        s, _ = _scores_base2(q, kblk, scale, softcap)  # (BQ, BK)
        if masked:
            q_pos = q_offset + qi * block + jax.lax.broadcasted_iota(
                jnp.int32, (block, block), 0)
            k_pos = kj * block + jax.lax.broadcasted_iota(
                jnp.int32, (block, block), 1)
            ok = q_pos >= k_pos
            if window is not None:
                ok = ok & (q_pos - k_pos < window)
            # INVARIANT (wipe-by-underflow): when window < block, a q-row's
            # first active k-block can be FULLY masked — this tile is then
            # all NEG_INF, so m_new = NEG_INF and p = exp(0) = 1 garbage
            # transiently enters acc/l below. Correctness relies on every
            # q-row's LAST active block holding a live diagonal key, so the
            # later rescale alpha = exp(NEG_INF - m_finite) underflows to
            # exactly 0.0 and wipes the garbage. Changing NEG_INF to a
            # value exp() doesn't flush to zero, or seeding m/l/acc
            # differently, silently breaks banded attention
            # (guard tests: t=384 / window=16 in test_window_attention.py).
            # With q_offset > 0 a row can be dead in EVERY block (the band
            # passed it entirely). Its m then stays NEG_INF through the
            # whole sweep (l accrues exp(0)=1 garbage per masked entry, it
            # does NOT stay 0), so finalize emits lse = m + log(l) ~=
            # NEG_INF and LSE-merging callers fold the garbage `out` away
            # with weight exp(NEG_INF - m_finite) = 0. m, not l, is the
            # dead-row signature.
            s = jnp.where(ok, s, NEG_INF)

        m = m_scr[...]
        m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp2(s - m_new)
        alpha = jnp.exp2(m - m_new)
        m_scr[...] = m_new
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p.astype(vblk.dtype), vblk,
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    if causal and window is not None:
        active = (kj <= _kv_hi(qi, block, q_offset, nk)) & (
            kj >= _kv_lo(qi, block, window, q_offset))
    elif causal:
        active = kj <= _kv_hi(qi, block, q_offset, nk)
    else:
        active = kj >= 0
    _dispatch_cells(_compute, qi, kj, block, active, causal=causal,
                    window=window, q_offset=q_offset)

    @pl.when(kj == nk - 1)
    def _finalize():
        m, l, acc = m_scr[...], l_scr[...], acc_scr[...]
        # max(l, tiny): a dead q BLOCK (no active kj at all, q_offset > 0)
        # reaches here with l = 0 and would emit 0/0 = NaN; dead rows
        # inside an ACTIVE block instead carry l = masked-entry garbage
        # with m = NEG_INF. Both cases emit lse ~= NEG_INF (m + log(l)),
        # which LSE-merging callers weight to exactly zero — `out` for
        # dead rows is garbage by contract, lse is the signal. Live rows
        # have l >= exp2(0) = 1 from their max entry, so values are exact.
        l_safe = jnp.maximum(l, 1e-30)
        o_ref[0] = (acc / l_safe).astype(o_ref.dtype)
        # natural-domain lse (m is base-2): API contract for ring merging
        lse_ref[0] = m * INV_LOG2E + jnp.log(l_safe)  # (BQ, 1)


def _flash_fwd(q, k, v, scale, block, causal=True, window=None, softcap=None,
               q_offset=0):
    """q/k/v: (BH, T, hd) -> (out (BH, T, hd), lse (BH, T, 1))."""
    bh, t, hd = q.shape
    nb = t // block
    grid = (bh, nb, nb)
    # causal: masked (above-diagonal) cells clamp their k index to the
    # diagonal so the pipeline never fetches a block the kernel will skip;
    # with a sliding window the stream is clamped from below too. A
    # q_offset>0 block whose whole band misses this k chunk has lo > hi:
    # clip then returns hi (already in [0, nb-1]) as the
    # fetched-but-skipped placeholder index.
    if causal and window is not None:
        kv_spec = pl.BlockSpec(
            (1, block, hd),
            lambda b, i, j: (b, jnp.clip(
                j, _kv_lo(i, block, window, q_offset),
                _kv_hi(i, block, q_offset, nb)), 0))
    elif causal:
        kv_spec = pl.BlockSpec(
            (1, block, hd),
            lambda b, i, j: (b, jnp.minimum(j, _kv_hi(i, block, q_offset,
                                                      nb)), 0))
    else:
        kv_spec = pl.BlockSpec((1, block, hd), lambda b, i, j: (b, j, 0))
    out, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, block=block,
                          causal=causal, window=window, softcap=softcap,
                          q_offset=q_offset),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block, hd), lambda b, i, j: (b, i, 0)),
            kv_spec,
            kv_spec,
        ],
        out_specs=[
            pl.BlockSpec((1, block, hd), lambda b, i, j: (b, i, 0)),
            # (BH, T, 1) rather than (BH, T): Mosaic requires the last two
            # block dims to be (8k, 128k) or equal to the array dims — a
            # trailing singleton satisfies that where a (1, block) tile can't
            pl.BlockSpec((1, block, 1), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, t, hd), q.dtype),
            jax.ShapeDtypeStruct((bh, t, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block, 1), jnp.float32),
            pltpu.VMEM((block, 1), jnp.float32),
            pltpu.VMEM((block, hd), jnp.float32),
        ],
        # bh and q-block cells are independent; only the k dimension carries
        # the online-softmax state sequentially
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        name="flash_fwd",
        interpret=_interpret(),
    )(q, k, v)
    return out, lse


# ---------------------------------------------------------------------------
# Backward kernels
# ---------------------------------------------------------------------------


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
               dq_scr, *, scale, block, causal, window=None, softcap=None,
               q_offset=0):
    qi = pl.program_id(1)
    kj = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(kj == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    def _compute(masked):
        # bf16 matmul inputs + fp32 accumulate (see _fwd_kernel note);
        # p/ds are computed in fp32 and cast back only to feed the MXU
        q = q_ref[0]
        do = do_ref[0]
        lse = lse_ref[0] * LOG2E  # natural -> base-2 (per-row, cheap)
        delta = delta_ref[0]
        kblk = k_ref[0]
        vblk = v_ref[0]
        s, t = _scores_base2(q, kblk, scale, softcap)
        if masked:
            q_pos = q_offset + qi * block + jax.lax.broadcasted_iota(
                jnp.int32, (block, block), 0)
            k_pos = kj * block + jax.lax.broadcasted_iota(
                jnp.int32, (block, block), 1)
            ok = q_pos >= k_pos
            if window is not None:
                ok = ok & (q_pos - k_pos < window)
            s = jnp.where(ok, s, NEG_INF)
            # mask p structurally, not via exp underflow: a dead row
            # (q_offset > 0, no live key) has lse ~= NEG_INF, making
            # exp2(NEG_INF - lse) = exp2(~0) = 1 garbage rather than 0
            p = jnp.where(ok, jnp.exp2(s - lse), 0.0)
        else:
            # full cells contain no dead rows (every key is live for every
            # row), so lse is finite and p needs no structural mask
            p = jnp.exp2(s - lse)
        dp = jax.lax.dot_general(
            do, vblk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = p * (dp - delta.astype(jnp.float32))
        if softcap is not None:  # chain through d/ds cap*tanh(s/cap)
            ds = ds * (1.0 - t * t)
        ds = ds * scale
        dq_scr[...] += jax.lax.dot_general(
            ds.astype(kblk.dtype), kblk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    if causal and window is not None:
        active = (kj <= _kv_hi(qi, block, q_offset, nk)) & (
            kj >= _kv_lo(qi, block, window, q_offset))
    elif causal:
        active = kj <= _kv_hi(qi, block, q_offset, nk)
    else:
        active = kj >= 0
    _dispatch_cells(_compute, qi, kj, block, active, causal=causal,
                    window=window, q_offset=q_offset)

    @pl.when(kj == nk - 1)
    def _finalize():
        dq_ref[0] = dq_scr[...].astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, dk_scr, dv_scr, *, scale, block, causal,
                window=None, softcap=None, q_offset=0):
    kj = pl.program_id(1)
    qi = pl.program_id(2)
    nq = pl.num_programs(2)

    @pl.when(qi == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    def _compute(masked):
        # bf16 matmul inputs + fp32 accumulate (see _fwd_kernel note)
        kblk = k_ref[0]  # (BK, hd)
        vblk = v_ref[0]
        q = q_ref[0]  # (BQ, hd)
        do = do_ref[0]
        lse = lse_ref[0] * LOG2E  # natural -> base-2
        delta = delta_ref[0]
        s, t = _scores_base2(q, kblk, scale, softcap)
        if masked:
            q_pos = q_offset + qi * block + jax.lax.broadcasted_iota(
                jnp.int32, (block, block), 0)
            k_pos = kj * block + jax.lax.broadcasted_iota(
                jnp.int32, (block, block), 1)
            ok = q_pos >= k_pos
            if window is not None:
                ok = ok & (q_pos - k_pos < window)
            s = jnp.where(ok, s, NEG_INF)
            # structural masking — see _dq_kernel's dead-row note
            p = jnp.where(ok, jnp.exp2(s - lse), 0.0)
        else:
            p = jnp.exp2(s - lse)  # (BQ, BK); no dead rows in full cells
        dv_scr[...] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dp = jax.lax.dot_general(
            do, vblk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = p * (dp - delta.astype(jnp.float32))
        if softcap is not None:
            ds = ds * (1.0 - t * t)
        ds = ds * scale
        dk_scr[...] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    # causal: only q blocks at or below the (offset) diagonal see this k
    # block; a sliding window also bounds how far below
    if causal and window is not None:
        active = (qi >= _q_lo(kj, block, q_offset)) & (
            qi <= _q_hi(kj, block, window, q_offset))
    elif causal:
        active = qi >= _q_lo(kj, block, q_offset)
    else:
        active = qi >= 0
    _dispatch_cells(_compute, qi, kj, block, active, causal=causal,
                    window=window, q_offset=q_offset)

    @pl.when(qi == nq - 1)
    def _finalize():
        dk_ref[0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)


def _flash_bwd(q, k, v, out, lse, do, scale, block, causal=True, dlse=None,
               window=None, softcap=None, q_offset=0):
    """dlse: optional cotangent for the lse output ((BH, T, 1) fp32).

    The lse gradient folds into the existing kernels for free:
    d lse / d s = p (the softmax row), so a dlse cotangent contributes
    ds += p * dlse — the kernels compute ds = p * (dp - delta), so passing
    delta' = delta - dlse is exactly the combined gradient.
    """
    bh, t, hd = q.shape
    delta = jnp.sum(
        out.astype(jnp.float32) * do.astype(jnp.float32), axis=-1,
        keepdims=True,
    )  # (BH, T, 1), same layout as lse
    if dlse is not None:
        delta = delta - dlse.astype(jnp.float32)
    nb = t // block

    # dq: grid (BH, q block, k block), k/v streamed; causal clamps the
    # stream at the diagonal (skipped cells never fetch); a window also
    # clamps from below
    if causal and window is not None:
        # lo > hi (band misses the chunk) resolves to hi via clip — a
        # valid placeholder index; see the fwd kv_spec note
        kv_stream = pl.BlockSpec(
            (1, block, hd),
            lambda b, i, j: (b, jnp.clip(
                j, _kv_lo(i, block, window, q_offset),
                _kv_hi(i, block, q_offset, nb)), 0))
    elif causal:
        kv_stream = pl.BlockSpec(
            (1, block, hd),
            lambda b, i, j: (b, jnp.minimum(j, _kv_hi(i, block, q_offset,
                                                      nb)), 0))
    else:
        kv_stream = pl.BlockSpec((1, block, hd), lambda b, i, j: (b, j, 0))
    q_fixed = pl.BlockSpec((1, block, hd), lambda b, i, j: (b, i, 0))
    vec_fixed = pl.BlockSpec((1, block, 1), lambda b, i, j: (b, i, 0))
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale, block=block,
                          causal=causal, window=window, softcap=softcap,
                          q_offset=q_offset),
        grid=(bh, nb, nb),
        in_specs=[q_fixed, kv_stream, kv_stream, q_fixed, vec_fixed,
                  vec_fixed],
        out_specs=[q_fixed],
        out_shape=[jax.ShapeDtypeStruct((bh, t, hd), q.dtype)],
        scratch_shapes=[pltpu.VMEM((block, hd), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        name="flash_bwd_dq",
        interpret=_interpret(),
    )(q, k, v, do, lse, delta)[0]

    # dk/dv: grid (BH, k block, q block), q/do/lse/delta streamed, clamped
    if causal and window is not None:
        def _q_idx(b, j, i):
            return (b, jnp.clip(jnp.clip(
                i, _q_lo(j, block, q_offset),
                _q_hi(j, block, window, q_offset)), 0, nb - 1), 0)

        q_stream = pl.BlockSpec((1, block, hd), _q_idx)
        vec_stream = pl.BlockSpec((1, block, 1), _q_idx)
    elif causal:
        q_stream = pl.BlockSpec(
            (1, block, hd),
            lambda b, j, i: (b, jnp.maximum(i, _q_lo(j, block, q_offset)), 0))
        vec_stream = pl.BlockSpec(
            (1, block, 1),
            lambda b, j, i: (b, jnp.maximum(i, _q_lo(j, block, q_offset)), 0))
    else:
        q_stream = pl.BlockSpec((1, block, hd), lambda b, j, i: (b, i, 0))
        vec_stream = pl.BlockSpec((1, block, 1), lambda b, j, i: (b, i, 0))
    kv_fixed = pl.BlockSpec((1, block, hd), lambda b, j, i: (b, j, 0))
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, scale=scale, block=block,
                          causal=causal, window=window, softcap=softcap,
                          q_offset=q_offset),
        grid=(bh, nb, nb),
        in_specs=[q_stream, kv_fixed, kv_fixed, q_stream, vec_stream,
                  vec_stream],
        out_specs=[kv_fixed, kv_fixed],
        out_shape=[
            jax.ShapeDtypeStruct((bh, t, hd), k.dtype),
            jax.ShapeDtypeStruct((bh, t, hd), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block, hd), jnp.float32),
            pltpu.VMEM((block, hd), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        name="flash_bwd_dkv",
        interpret=_interpret(),
    )(q, k, v, do, lse, delta)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# custom-vjp wrapper in the model's (B, T, H, hd) layout
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash(q, k, v, scale: float, block: int, window=None, softcap=None,
           keep_pair: bool = False):
    out, _ = _flash_fwd(q, k, v, scale, block, window=window, softcap=softcap)
    return out


def _flash_fwd_rule(q, k, v, scale, block, window, softcap, keep_pair):
    out, lse = _flash_fwd(q, k, v, scale, block, window=window, softcap=softcap)
    if keep_pair:
        out, lse = _kept(out, lse)
    return out, (q, k, v, out, lse)


def _flash_bwd_rule(scale, block, window, softcap, keep_pair, res, do):
    q, k, v, out, lse = res
    dq, dk, dv = _flash_bwd(q, k, v, out, _as_written(lse, keep_pair), do,
                            scale, block, window=window, softcap=softcap)
    return dq, dk, dv


_flash.defvjp(_flash_fwd_rule, _flash_bwd_rule)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def flash_with_lse(q, k, v, scale: float, block: int, causal: bool = True,
                   window: Optional[int] = None,
                   softcap: Optional[float] = None, q_offset: int = 0):
    """(q, k, v) (BH, T, hd) -> (out (BH, T, hd), lse (BH, T, 1) fp32).

    The building block for distributed attention (parallel/ring_attention.py):
    partial results from different K/V chunks merge exactly via their
    log-sum-exp, so a ring hop can run this kernel per chunk and combine —
    differentiable in both outputs (the lse cotangent folds into delta,
    see _flash_bwd).

    ``window``/``softcap`` mirror the square-kernel options; ``q_offset``
    places the q chunk that many tokens after the k chunk (banded ring
    cross-chunk hops). Rows left with no live key under an offset band
    return garbage ``out`` and lse ~= NEG_INF — callers MUST merge by lse
    (the weight underflows to exactly 0), not read ``out`` directly.

    Its pair carries no checkpoint name (SAVED_OUT, SAVED_LSE): a ring would
    save one a hop, no cell runs one and nothing has measured it, so under
    ``remat`` a ring's hops run again in the backward.
    """
    return _flash_fwd(q, k, v, scale, block, causal, window=window,
                      softcap=softcap, q_offset=q_offset)


def _flash_lse_fwd_rule(q, k, v, scale, block, causal, window, softcap,
                        q_offset):
    out, lse = _flash_fwd(q, k, v, scale, block, causal, window=window,
                          softcap=softcap, q_offset=q_offset)
    return (out, lse), (q, k, v, out, lse)


def _flash_lse_bwd_rule(scale, block, causal, window, softcap, q_offset,
                        res, cts):
    q, k, v, out, lse = res
    do, dlse = cts
    dq, dk, dv = _flash_bwd(
        q, k, v, out, lse, do, scale, block, causal=causal, dlse=dlse,
        window=window, softcap=softcap, q_offset=q_offset,
    )
    return dq, dk, dv


flash_with_lse.defvjp(_flash_lse_fwd_rule, _flash_lse_bwd_rule)


# ---------------------------------------------------------------------------
# Native-layout (B, T, D) kernels — no activation transposes
# ---------------------------------------------------------------------------
#
# The square kernels above take (B*H, T, hd): the model's activations are
# (B, T, H*hd), so every call pays a (0, 2, 1, 3) transpose on the way in
# and out — at hd=64 that was the single largest step-time sink left on the
# round-4 trace (~29 ms/step at batch 16).
# These kernels keep the native layout and make the HEAD a grid dimension:
# grid (B, H/pack, nq, nk) where `pack` sub-heads ride one cell so the lane
# dimension stays at Mosaic's 128 minimum (hd=64 -> 2 heads per cell, which
# also halves the grid and builds the causal mask once per PAIR of heads).
# The kernel bodies are the same online-softmax / lse-delta cells as above,
# re-indexed for the 4D grid. Measured on a TPU v5e chip (batch 16, T=1024,
# GPT-2 dims): fwd+bwd 3.82 ms vs 4.46 ms for kernels+transposes per layer
# call — the win that took the step from MFU 0.47 toward 0.55.


def _btd_pack(h: int, hd: int) -> Optional[int]:
    """Sub-heads per grid cell for the native-layout kernels, or None when
    the (h, hd) combination can't keep the lane dimension at 128."""
    if hd >= 128:
        return 1 if hd % 128 == 0 else None
    if 128 % hd == 0:
        p = 128 // hd
        return p if h % p == 0 else None
    return None


#: Query rows a group of a diagonal cell takes (_diag_group_rows). Measured
#: on a v5e (PERF.md, PR 54: jax.grad of causal_attention alone, bfloat16,
#: device us of the Mosaic calls a call; whole cell -> groups of 256 -> of
#: 128), forward and fused backward: (32, 1024, 12, 64), the 124M cell,
#: 2,606 -> 2,413 -> 2,149 and 2,650 -> 2,227 -> 2,074; (32, 512, 12, 64),
#: every cell diagonal, 860 -> 844 -> 606 and 835 -> 651 -> 573;
#: (2, 4096, 32, 128), 8 diagonal cells of 36, 5,185 -> 5,118 -> 5,017 and
#: 5,979 -> 5,649 -> 5,573. 128 won in both kernels at all five shapes
#: probed (10 of 16 sub-tiles against 12), and is a lane tile's width: a
#: narrower group's slices would cut registers. The parent's kernels at a
#: grid block of 256, which skip the same work by grid steps, read 4,095
#: and 4,302.
DIAG_GROUP_ROWS = 128


def _diag_group_rows(block: int) -> Optional[int]:
    """How a native-layout kernel walks a diagonal cell (``qi == kj``,
    causal, no window): in static groups of this many query rows, each
    against the cell's keys up to the end of its own rows only, or None
    for the whole cell at once (a block that holds fewer than two groups,
    or no whole number of them). A rule over the static block alone: no
    shape probed lost (hd 64 and 128, pack 2 and 1, nb 1 to 8), so neither
    ``hd`` nor ``pack`` enters it, and ``FLASH_BLOCK`` does not either.

    The forward takes a group as one pass of its chain over the group's
    own width. The fused backward forms a group's scores and dp out of
    matmuls made a tile of keys at a time against every row at or under
    the tile: written a group at a time (128 rows against each tile of
    keys) the same five matmuls gained nothing over the whole cell
    (2,675 us against 2,650 at the 124M shape), and the forward written a
    tile of keys at a time lost (2,443 against 2,149): PERF.md, PR 54."""
    g = DIAG_GROUP_ROWS
    return g if block >= 2 * g and block % g == 0 else None


def _diag_groups(block: int, group: int):
    """(rows, keys) of each group of a diagonal cell: the slice of its
    query rows and how many of the cell's keys they can see."""
    return [(slice(r * group, (r + 1) * group), (r + 1) * group)
            for r in range(block // group)]


def _diag_tile(group: int):
    """``row >= col`` of a (group, group) tile: on the diagonal the cell's
    corner is the positions' origin, so the mask is a constant."""
    return (jax.lax.broadcasted_iota(jnp.int32, (group, group), 0)
            >= jax.lax.broadcasted_iota(jnp.int32, (group, group), 1))


def _mask_diag_tile(s, tri):
    """Scores of a group, (group, keys): every column but the last
    ``group`` lies under the diagonal; those are wiped above it."""
    keys, group = s.shape[1], tri.shape[0]
    tile = jnp.where(tri, s[:, keys - group:], NEG_INF)
    if keys == group:
        return tile
    return jnp.concatenate([s[:, :keys - group], tile], axis=1)


def _diag_group_of(tall, r: int, group: int):
    """Group ``r``'s (group, (r + 1) * group) out of products made a tile
    of keys at a time: ``tall[c]`` holds key tile ``c`` against the rows
    from its own group down, (block - c * group, group)."""
    tiles = [tall[c][(r - c) * group:(r - c + 1) * group]
             for c in range(r + 1)]
    return tiles[0] if r == 0 else jnp.concatenate(tiles, axis=1)


def _fwd_kernel_btd(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr,
                    acc_scr, *, scale, block, hd, pack, window=None,
                    softcap=None):
    qi = pl.program_id(2)
    kj = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(kj == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def _softmax_step(rows, s, vblk):
        """One online-softmax update of the scratch rows ``rows`` (a
        sub-head, or a sub-head and a slice of its rows) by the scores
        ``s`` of those rows against the keys whose values ``vblk`` holds."""
        m = m_scr[rows]
        m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp2(s - m_new)
        alpha = jnp.exp2(m - m_new)
        m_scr[rows] = m_new
        l_scr[rows] = l_scr[rows] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[rows] = acc_scr[rows] * alpha + jax.lax.dot_general(
            p.astype(vblk.dtype), vblk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    group = None if window is not None else _diag_group_rows(block)

    def _compute(masked):
        q_all = q_ref[0]  # (block, pack*hd)
        k_all = k_ref[0]
        v_all = v_ref[0]
        staircase = masked and group is not None  # qi == kj: see the rule
        if staircase:
            tri = _diag_tile(group)
        elif masked:
            # causal/band mask built ONCE per cell, shared by all sub-heads
            q_pos = qi * block + jax.lax.broadcasted_iota(
                jnp.int32, (block, block), 0)
            k_pos = kj * block + jax.lax.broadcasted_iota(
                jnp.int32, (block, block), 1)
            ok = q_pos >= k_pos
            if window is not None:
                ok = ok & (q_pos - k_pos < window)
        for sh in range(pack):
            lo, hi = sh * hd, (sh + 1) * hd
            q = q_all[:, lo:hi]
            kblk = k_all[:, lo:hi]
            vblk = v_all[:, lo:hi]
            if staircase:
                # each group of rows is one pass of the chain over its own
                # width: its own maximum, exp2, sum and p @ v
                for rows, keys in _diag_groups(block, group):
                    s, _ = _scores_base2(q[rows], kblk[:keys], scale,
                                         softcap)
                    _softmax_step((sh, rows), _mask_diag_tile(s, tri),
                                  vblk[:keys])
                continue
            s, _ = _scores_base2(q, kblk, scale, softcap)
            if masked:
                # wipe-by-underflow invariant holds exactly as in
                # _fwd_kernel (q_offset is always 0 here: every q row owns
                # a live diagonal)
                s = jnp.where(ok, s, NEG_INF)
            _softmax_step(sh, s, vblk)

    # full/masked cell routing shared with every kernel (_dispatch_cells)
    # — a large cut in a kernel that is VPU-bound, not MXU-bound, at hd=64
    if window is not None:
        active = (kj <= _kv_hi(qi, block, 0, nk)) & (
            kj >= _kv_lo(qi, block, window, 0))
    else:
        active = kj <= _kv_hi(qi, block, 0, nk)
    _dispatch_cells(_compute, qi, kj, block, active, causal=True,
                    window=window)

    @pl.when(kj == nk - 1)
    def _finalize():
        l_safe = jnp.maximum(l_scr[...], 1e-30)  # (pack, block, 1)
        o_sub = acc_scr[...] / l_safe  # (pack, block, hd)
        if pack == 1:
            o_ref[0] = o_sub[0].astype(o_ref.dtype)
        else:
            o_ref[0] = jnp.concatenate(
                [o_sub[i] for i in range(pack)], axis=1).astype(o_ref.dtype)
        # natural-domain lse from base-2 m (same contract as _fwd_kernel)
        lse = m_scr[...] * INV_LOG2E + jnp.log(l_safe)  # (pack, block, 1)
        for sh in range(pack):
            lse_ref[0, sh] = lse[sh]


def _dq_kernel_btd(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                   dq_scr, *, scale, block, hd, pack, window=None,
                   softcap=None):
    qi = pl.program_id(2)
    kj = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(kj == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    def _compute(masked):
        q_all = q_ref[0]
        k_all = k_ref[0]
        v_all = v_ref[0]
        do_all = do_ref[0]
        if masked:
            q_pos = qi * block + jax.lax.broadcasted_iota(
                jnp.int32, (block, block), 0)
            k_pos = kj * block + jax.lax.broadcasted_iota(
                jnp.int32, (block, block), 1)
            ok = q_pos >= k_pos
            if window is not None:
                ok = ok & (q_pos - k_pos < window)
        for sh in range(pack):
            lo, hi = sh * hd, (sh + 1) * hd
            q = q_all[:, lo:hi]
            kblk = k_all[:, lo:hi]
            vblk = v_all[:, lo:hi]
            do = do_all[:, lo:hi]
            lse = lse_ref[0, sh] * LOG2E  # natural -> base-2
            delta = delta_ref[0, sh]
            s, t = _scores_base2(q, kblk, scale, softcap)
            if masked:
                s = jnp.where(ok, s, NEG_INF)
                p = jnp.where(ok, jnp.exp2(s - lse), 0.0)
            else:
                p = jnp.exp2(s - lse)
            dp = jax.lax.dot_general(
                do, vblk, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            ds = p * (dp - delta.astype(jnp.float32))
            if softcap is not None:
                ds = ds * (1.0 - t * t)
            ds = ds * scale
            dq_scr[sh] += jax.lax.dot_general(
                ds.astype(kblk.dtype), kblk, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )

    if window is not None:
        active = (kj <= _kv_hi(qi, block, 0, nk)) & (
            kj >= _kv_lo(qi, block, window, 0))
    else:
        active = kj <= _kv_hi(qi, block, 0, nk)
    _dispatch_cells(_compute, qi, kj, block, active, causal=True,
                    window=window)

    @pl.when(kj == nk - 1)
    def _finalize():
        if pack == 1:
            dq_ref[0] = dq_scr[0].astype(dq_ref.dtype)
        else:
            dq_ref[0] = jnp.concatenate(
                [dq_scr[i] for i in range(pack)], axis=1).astype(dq_ref.dtype)


def _dkv_kernel_btd(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_scr, dv_scr, *, scale, block, hd,
                    pack, window=None, softcap=None):
    kj = pl.program_id(2)
    qi = pl.program_id(3)
    nq = pl.num_programs(3)

    @pl.when(qi == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    def _compute(masked):
        q_all = q_ref[0]
        k_all = k_ref[0]
        v_all = v_ref[0]
        do_all = do_ref[0]
        if masked:
            q_pos = qi * block + jax.lax.broadcasted_iota(
                jnp.int32, (block, block), 0)
            k_pos = kj * block + jax.lax.broadcasted_iota(
                jnp.int32, (block, block), 1)
            ok = q_pos >= k_pos
            if window is not None:
                ok = ok & (q_pos - k_pos < window)
        for sh in range(pack):
            lo, hi = sh * hd, (sh + 1) * hd
            q = q_all[:, lo:hi]
            kblk = k_all[:, lo:hi]
            vblk = v_all[:, lo:hi]
            do = do_all[:, lo:hi]
            lse = lse_ref[0, sh] * LOG2E  # natural -> base-2
            delta = delta_ref[0, sh]
            s, t = _scores_base2(q, kblk, scale, softcap)
            if masked:
                s = jnp.where(ok, s, NEG_INF)
                p = jnp.where(ok, jnp.exp2(s - lse), 0.0)
            else:
                p = jnp.exp2(s - lse)
            dv_scr[sh] += jax.lax.dot_general(
                p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            dp = jax.lax.dot_general(
                do, vblk, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            ds = p * (dp - delta.astype(jnp.float32))
            if softcap is not None:
                ds = ds * (1.0 - t * t)
            ds = ds * scale
            dk_scr[sh] += jax.lax.dot_general(
                ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )

    # here the grid streams q per k block: active means qi at or below
    # the diagonal
    if window is not None:
        active = (qi >= _q_lo(kj, block, 0)) & (
            qi <= _q_hi(kj, block, window, 0))
    else:
        active = qi >= _q_lo(kj, block, 0)
    _dispatch_cells(_compute, qi, kj, block, active, causal=True,
                    window=window)

    @pl.when(qi == nq - 1)
    def _finalize():
        if pack == 1:
            dk_ref[0] = dk_scr[0].astype(dk_ref.dtype)
            dv_ref[0] = dv_scr[0].astype(dv_ref.dtype)
        else:
            dk_ref[0] = jnp.concatenate(
                [dk_scr[i] for i in range(pack)], axis=1).astype(dk_ref.dtype)
            dv_ref[0] = jnp.concatenate(
                [dv_scr[i] for i in range(pack)], axis=1).astype(dv_ref.dtype)


def _dqkv_kernel_btd(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                     dq_ref, dk_ref, dv_ref, dq_all_scr, dk_scr, dv_scr,
                     *, scale, block, hd, pack, window=None, softcap=None):
    """FUSED backward: dq + dk + dv in ONE pass over the (kj, qi) grid.

    The split dq / dkv kernels each recompute s, p and dp per active cell
    — 7 matmuls and 2 full VPU softmax chains per cell across the two
    passes, plus double DMA of every q/k/v/do block. Sharing them costs 5
    matmuls and ONE chain, and the backward is VPU-bound at hd=64.
    Measured on a v5e (PERF.md, PR 50), us a sequence and pair of heads at
    T=1024: 13.8 against the pair's 20.8; a third less at every shape
    probed (nb 1 to 16, hd 64 and 128, windowed), the gradients equal bit
    for bit (dq sums over k blocks in ascending order in both forms).
    A diagonal cell is a staircase of row groups (_diag_group_rows): on
    the chip its gradients are the whole cell's bit for bit too (PR 54: a
    group is the 128 rows the MXU accumulates at a time, and what is
    skipped added exact zeros).

    Mechanics: grid (B, H/pack, kj, qi) with qi innermost (the dkv
    ordering). dk/dv accumulate per kj in scratch exactly as before. dq
    accumulates across the OUTER kj sweeps into a (nq, pack, block, hd)
    scratch slab indexed by qi — a dynamic index on the leading
    (untiled) dim, plain address arithmetic (unlike the sublane-dim
    dynamic stores Mosaic rejects). Every qi slab is complete by the last
    kj sweep, which writes it out; the dq out-spec index map parks on
    block 0 until that sweep so the buffer stays resident and is flushed
    exactly once per q block with real contents.
    """
    kj = pl.program_id(2)
    qi = pl.program_id(3)
    nq = pl.num_programs(3)
    nk = pl.num_programs(2)

    @pl.when((kj == 0) & (qi == 0))
    def _init_dq_all():
        dq_all_scr[...] = jnp.zeros_like(dq_all_scr)

    @pl.when(qi == 0)
    def _init_kv():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    def _dp(do, vblk):
        return jax.lax.dot_general(
            do, vblk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    def _grads(kv_rows, dq_rows, p, t, q, kblk, vblk, do, delta, dp=None):
        """What a cell (or a group of a diagonal cell) adds to the three
        accumulators: p and do into ``dv_scr[kv_rows]``, ds and q into
        ``dk_scr[kv_rows]``, ds and k into ``dq_all_scr[dq_rows]``."""
        dv_scr[kv_rows] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        if dp is None:
            dp = _dp(do, vblk)
        ds = p * (dp - delta.astype(jnp.float32))
        if softcap is not None:
            ds = ds * (1.0 - t * t)
        ds = ds * scale
        dk_scr[kv_rows] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dq_all_scr[dq_rows] += jax.lax.dot_general(
            ds.astype(kblk.dtype), kblk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    group = None if window is not None else _diag_group_rows(block)

    def _compute(masked):
        q_all = q_ref[0]
        k_all = k_ref[0]
        v_all = v_ref[0]
        do_all = do_ref[0]
        staircase = masked and group is not None  # qi == kj: see the rule
        if staircase:
            tri = _diag_tile(group)
        elif masked:
            q_pos = qi * block + jax.lax.broadcasted_iota(
                jnp.int32, (block, block), 0)
            k_pos = kj * block + jax.lax.broadcasted_iota(
                jnp.int32, (block, block), 1)
            ok = q_pos >= k_pos
            if window is not None:
                ok = ok & (q_pos - k_pos < window)
        for sh in range(pack):
            lo, hi = sh * hd, (sh + 1) * hd
            q = q_all[:, lo:hi]
            kblk = k_all[:, lo:hi]
            vblk = v_all[:, lo:hi]
            do = do_all[:, lo:hi]
            if staircase:
                # scores and dp a tile of keys at a time, each against all
                # the rows at or under it (the matmul shape the A/B took:
                # _diag_group_rows), then the chain and the three
                # accumulating matmuls a group of rows at a time. No row
                # is dead (q_offset is 0): lse is finite, and a score
                # wiped to NEG_INF gives p = 0 by underflow
                tall = [(slice(c * group, block),
                         slice(c * group, (c + 1) * group))
                        for c in range(block // group)]
                s_t = [_scores_base2(q[rows], kblk[cols], scale, softcap)
                       for rows, cols in tall]
                dp = [_dp(do[rows], vblk[cols]) for rows, cols in tall]
                for r, (rows, keys) in enumerate(
                        _diag_groups(block, group)):
                    of_group = lambda tiles: _diag_group_of(tiles, r, group)
                    s = of_group([s for s, _ in s_t])
                    t = None if softcap is None else of_group(
                        [t for _, t in s_t])
                    lse = lse_ref[0, sh, rows] * LOG2E
                    _grads((sh, slice(0, keys)), (qi, sh, rows),
                           jnp.exp2(_mask_diag_tile(s, tri) - lse), t,
                           q[rows], kblk[:keys], vblk[:keys], do[rows],
                           delta_ref[0, sh, rows], dp=of_group(dp))
                continue
            lse = lse_ref[0, sh] * LOG2E  # natural -> base-2
            delta = delta_ref[0, sh]
            s, t = _scores_base2(q, kblk, scale, softcap)
            if masked:
                s = jnp.where(ok, s, NEG_INF)
                p = jnp.where(ok, jnp.exp2(s - lse), 0.0)
            else:
                p = jnp.exp2(s - lse)
            _grads(sh, (qi, sh), p, t, q, kblk, vblk, do, delta)

    if window is not None:
        active = (qi >= _q_lo(kj, block, 0)) & (
            qi <= _q_hi(kj, block, window, 0))
    else:
        active = qi >= _q_lo(kj, block, 0)
    _dispatch_cells(_compute, qi, kj, block, active, causal=True,
                    window=window)

    @pl.when(kj == nk - 1)
    def _emit_dq():
        slab = dq_all_scr[qi]  # (pack, block, hd)
        if pack == 1:
            dq_ref[0] = slab[0].astype(dq_ref.dtype)
        else:
            dq_ref[0] = jnp.concatenate(
                [slab[i] for i in range(pack)], axis=1).astype(dq_ref.dtype)

    @pl.when(qi == nq - 1)
    def _finalize_kv():
        if pack == 1:
            dk_ref[0] = dk_scr[0].astype(dk_ref.dtype)
            dv_ref[0] = dv_scr[0].astype(dv_ref.dtype)
        else:
            dk_ref[0] = jnp.concatenate(
                [dk_scr[i] for i in range(pack)], axis=1).astype(dk_ref.dtype)
            dv_ref[0] = jnp.concatenate(
                [dv_scr[i] for i in range(pack)], axis=1).astype(dv_ref.dtype)


def _btd_dkv_specs(block, pack, hd, nb, window):
    """Shared BlockSpecs for the (kj, qi)-ordered backward grids: fixed
    k/v blocks per kj, q/do and lse/delta streamed per qi with the band
    clamp — ONE definition for the split dkv kernel and the fused
    dq+dk+dv kernel, so the clamp math cannot diverge."""
    kv_fixed = pl.BlockSpec((1, block, pack * hd),
                            lambda bb, hh, j, i: (bb, j, hh))
    if window is not None:
        def _q_idx(bb, hh, j, i):
            return (bb, jnp.clip(jnp.clip(
                i, _q_lo(j, block, 0), _q_hi(j, block, window, 0)),
                0, nb - 1), hh)

        def _vec_idx(bb, hh, j, i):
            return (bb, hh, jnp.clip(jnp.clip(
                i, _q_lo(j, block, 0), _q_hi(j, block, window, 0)),
                0, nb - 1), 0)
    else:
        def _q_idx(bb, hh, j, i):
            return (bb, jnp.maximum(i, _q_lo(j, block, 0)), hh)

        def _vec_idx(bb, hh, j, i):
            return (bb, hh, jnp.maximum(i, _q_lo(j, block, 0)), 0)
    return (kv_fixed, pl.BlockSpec((1, block, pack * hd), _q_idx),
            pl.BlockSpec((1, pack, block, 1), _vec_idx))


def _flash_fwd_btd(q, k, v, h, scale, block, window=None, softcap=None):
    """q/k/v (B, T, H*hd) -> out (B, T, H*hd), lse (B, H, T, 1) fp32."""
    b, t, d = q.shape
    hd = d // h
    pack = _btd_pack(h, hd)
    nb = t // block
    grid = (b, h // pack, nb, nb)

    if window is not None:
        def kv_idx(bb, hh, i, j):
            return (bb, jnp.clip(j, _kv_lo(i, block, window, 0),
                                 _kv_hi(i, block, 0, nb)), hh)
    else:
        def kv_idx(bb, hh, i, j):
            return (bb, jnp.minimum(j, _kv_hi(i, block, 0, nb)), hh)

    io_spec = pl.BlockSpec((1, block, pack * hd),
                           lambda bb, hh, i, j: (bb, i, hh))
    kv_spec = pl.BlockSpec((1, block, pack * hd), kv_idx)
    # lse layout note (round-5, measured): a (B, H, T, 1) fp32 buffer pads
    # 128x under TPU T(8,128) tiling (trailing singleton -> 128 lanes) —
    # 384 MB of address space per layer at b64, the allocation behind the
    # historic batch>=64 compile failures. A dense
    # (B, H, nq, 8, 128) per-q-block plane layout was built and reverted:
    # the (rows, 128) <-> (block, 1) relayout it needs inside the kernels
    # lowers to an unsupported Mosaic gather ("Only 2D gather is
    # supported"), in both the fwd write and bwd read directions. The
    # padding is address space, not DMA traffic (the kernel only writes
    # real lanes), batch is throughput-saturated by 32 on a v5e, and b64
    # runs with remat — so the padded layout stands until Mosaic grows the
    # relayout.
    lse_shape = jax.ShapeDtypeStruct((b, h, t, 1), jnp.float32)
    lse_spec = pl.BlockSpec((1, pack, block, 1),
                            lambda bb, hh, i, j: (bb, hh, i, 0))
    out, lse = pl.pallas_call(
        functools.partial(_fwd_kernel_btd, scale=scale, block=block, hd=hd,
                          pack=pack, window=window, softcap=softcap),
        grid=grid,
        in_specs=[io_spec, kv_spec, kv_spec],
        out_specs=[io_spec, lse_spec],
        out_shape=[jax.ShapeDtypeStruct((b, t, d), q.dtype), lse_shape],
        scratch_shapes=[
            pltpu.VMEM((pack, block, 1), jnp.float32),
            pltpu.VMEM((pack, block, 1), jnp.float32),
            pltpu.VMEM((pack, block, hd), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        name="flash_fwd",
        interpret=_interpret(),
    )(q, k, v)
    return out, lse


#: VMEM the fused backward may spend on its dq slab, (nq, pack, block, hd)
#: float32 = T x 128 lanes x 4 B: every T up to 8,192. A budget, not a
#: crossover: on a v5e the one kernel took 27-37% less device time than the
#: pair at every shape probed under it (nb 1 to 16, hd 64 and 128, windowed
#: or not: PERF.md, PR 50). What bounds it is the compiler: with a slab of
#: 8 MiB (T = 16,384) the kernel asks 17 MiB of scoped VMEM of the 16 it may
#: have (compile rehearsal, PR 50): 4 MiB is what has compiled and run.
FUSED_DQ_SCRATCH_BYTES = 4 * 2**20


def _fused_bwd_fits(nb: int, pack: int, block: int, hd: int) -> bool:
    """Whether the native-layout backward runs as one dq+dk+dv kernel: its
    dq slab has to stay in VMEM across the outer k sweeps. Static shapes
    only."""
    return nb * pack * block * hd * 4 <= FUSED_DQ_SCRATCH_BYTES


def _btd_delta(out, do, h):
    """delta = rowsum(out * do) per head: (B, T, H) -> the lse's layout,
    (B, H, T, 1) float32 (see _flash_fwd_btd). The transpose is on a
    (B, H, T) fp32 vector — trivial next to the (B, T, D) activation
    transposes this path exists to kill."""
    b, t, d = out.shape
    delta = jnp.sum(
        out.astype(jnp.float32).reshape(b, t, h, d // h)
        * do.astype(jnp.float32).reshape(b, t, h, d // h), axis=-1)
    return delta.transpose(0, 2, 1)[..., None]


def _flash_bwd_btd(q, k, v, out, lse, do, h, scale, block, window=None,
                   softcap=None):
    """Native-layout backward: dq, dk, dv in (B, T, H*hd)."""
    b, t, d = q.shape
    hd = d // h
    pack = _btd_pack(h, hd)
    nb = t // block
    backward = (_flash_bwd_btd_fused if _fused_bwd_fits(nb, pack, block, hd)
                else _flash_bwd_btd_split)
    return backward(q, k, v, do, lse, _btd_delta(out, do, h), b, t, hd, pack,
                    nb, scale, block, window, softcap)


def _flash_bwd_btd_split(q, k, v, do, lse, delta, b, t, hd, pack, nb,
                         scale, block, window, softcap):
    """Two pallas_calls, dq then dk+dv, each recomputing the cell's
    probabilities: the form for a T whose dq slab the fused kernel's
    scratch cannot hold (_fused_bwd_fits)."""
    d = q.shape[2]
    grid = (b, d // (pack * hd), nb, nb)
    io_q = pl.BlockSpec((1, block, pack * hd),
                        lambda bb, hh, i, j: (bb, i, hh))
    if window is not None:
        kv_stream = pl.BlockSpec(
            (1, block, pack * hd),
            lambda bb, hh, i, j: (bb, jnp.clip(
                j, _kv_lo(i, block, window, 0), _kv_hi(i, block, 0, nb)),
                hh))
    else:
        kv_stream = pl.BlockSpec(
            (1, block, pack * hd),
            lambda bb, hh, i, j: (bb, jnp.minimum(
                j, _kv_hi(i, block, 0, nb)), hh))
    vec_q = pl.BlockSpec((1, pack, block, 1),
                         lambda bb, hh, i, j: (bb, hh, i, 0))
    dq = pl.pallas_call(
        functools.partial(_dq_kernel_btd, scale=scale, block=block, hd=hd,
                          pack=pack, window=window, softcap=softcap),
        grid=grid,
        in_specs=[io_q, kv_stream, kv_stream, io_q, vec_q, vec_q],
        out_specs=[io_q],
        out_shape=[jax.ShapeDtypeStruct((b, t, d), q.dtype)],
        scratch_shapes=[pltpu.VMEM((pack, block, hd), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        name="flash_bwd_dq",
        interpret=_interpret(),
    )(q, k, v, do, lse, delta)[0]

    kv_fixed, q_stream, vec_stream = _btd_dkv_specs(
        block, pack, hd, nb, window)
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel_btd, scale=scale, block=block, hd=hd,
                          pack=pack, window=window, softcap=softcap),
        grid=grid,
        in_specs=[q_stream, kv_fixed, kv_fixed, q_stream, vec_stream,
                  vec_stream],
        out_specs=[kv_fixed, kv_fixed],
        out_shape=[jax.ShapeDtypeStruct((b, t, d), k.dtype),
                   jax.ShapeDtypeStruct((b, t, d), v.dtype)],
        scratch_shapes=[pltpu.VMEM((pack, block, hd), jnp.float32),
                        pltpu.VMEM((pack, block, hd), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        name="flash_bwd_dkv",
        interpret=_interpret(),
    )(q, k, v, do, lse, delta)
    return dq, dk, dv


def _flash_bwd_btd_fused(q, k, v, do, lse, delta, b, t, hd, pack, nb,
                         scale, block, window, softcap):
    """One fused pallas_call for dq+dk+dv — see _dqkv_kernel_btd."""
    d = q.shape[2]
    grid = (b, d // (pack * hd), nb, nb)
    kv_fixed, q_stream, vec_stream = _btd_dkv_specs(
        block, pack, hd, nb, window)
    # dq out: park on block 0 until the last kj sweep (when every qi slab
    # is complete) so the buffer is flushed exactly once per q block with
    # real contents — see _dqkv_kernel_btd's docstring
    dq_spec = pl.BlockSpec(
        (1, block, pack * hd),
        lambda bb, hh, j, i: (bb, jnp.where(j == nb - 1, i, 0), hh))
    dq, dk, dv = pl.pallas_call(
        functools.partial(_dqkv_kernel_btd, scale=scale, block=block,
                          hd=hd, pack=pack, window=window, softcap=softcap),
        grid=grid,
        in_specs=[q_stream, kv_fixed, kv_fixed, q_stream, vec_stream,
                  vec_stream],
        out_specs=[dq_spec, kv_fixed, kv_fixed],
        out_shape=[jax.ShapeDtypeStruct((b, t, d), q.dtype),
                   jax.ShapeDtypeStruct((b, t, d), k.dtype),
                   jax.ShapeDtypeStruct((b, t, d), v.dtype)],
        scratch_shapes=[pltpu.VMEM((nb, pack, block, hd), jnp.float32),
                        pltpu.VMEM((pack, block, hd), jnp.float32),
                        pltpu.VMEM((pack, block, hd), jnp.float32)],
        # kj and qi share the dq scratch slab and the parked dq out block:
        # a megacore split over either would break that residency
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary",
                                 "arbitrary")),
        name="flash_bwd_fused",
        interpret=_interpret(),
    )(q, k, v, do, lse, delta)
    return dq, dk, dv


# The model reaches the native-layout kernels through two jitted functions,
# so that a stack whose layers are written out traces and lowers each kernel
# once a shape, not once a layer: the 124M step (12 layers) takes 4.6 s to
# trace and lower here against 9.0 called bare, the staircase bodies having
# five times the whole cell's equations, and 6.3 at PR 54's parent; the
# compiled step is the same program either way (compile rehearsal, PR 54:
# code size, temporaries, every instruction but the kernels' embedded
# source lines). Their names hold neither kernel's: XLA names instructions
# after them, and readers and tests find the kernels by name. What they
# traced holds what _interpret() said then, a process's one answer; a test
# that steers it forgets the traces (clear_cache) on both sides.
@functools.partial(jax.jit, static_argnums=(3, 4, 5, 6, 7))
def _native_forward(q, k, v, h, scale, block, window, softcap):
    return _flash_fwd_btd(q, k, v, h, scale, block, window=window,
                          softcap=softcap)


@functools.partial(jax.jit, static_argnums=(6, 7, 8, 9, 10))
def _native_backward(q, k, v, out, lse, do, h, scale, block, window,
                     softcap):
    return _flash_bwd_btd(q, k, v, out, lse, do, h, scale, block,
                          window=window, softcap=softcap)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _flash_btd(q, k, v, h: int, scale: float, block: int, window=None,
               softcap=None, keep_pair: bool = False):
    out, _ = _native_forward(q, k, v, h, scale, block, window, softcap)
    return out


def _flash_btd_fwd_rule(q, k, v, h, scale, block, window, softcap, keep_pair):
    out, lse = _native_forward(q, k, v, h, scale, block, window, softcap)
    if keep_pair:
        out, lse = _kept(out, lse)
    return out, (q, k, v, out, lse)


def _flash_btd_bwd_rule(h, scale, block, window, softcap, keep_pair, res, do):
    q, k, v, out, lse = res
    return _native_backward(q, k, v, out, _as_written(lse, keep_pair), do, h,
                            scale, block, window, softcap)


_flash_btd.defvjp(_flash_btd_fwd_rule, _flash_btd_bwd_rule)


def causal_attention(
    q: jax.Array,  # (B, T, H, hd)
    k: jax.Array,  # (B, S, KV, hd)
    v: jax.Array,
    *,
    attn_pdrop: float = 0.0,
    dropout_key: Optional[jax.Array] = None,
    deterministic: bool = True,
    kv_offset: int | jax.Array = 0,
    window: Optional[int] = None,
    logit_softcap: Optional[float] = None,
    keep_pair: bool = False,
) -> jax.Array:
    """Drop-in for ops.attention.causal_attention, flash-accelerated.

    Falls back to the einsum oracle whenever the kernel doesn't apply:
    attention dropout active, decode-style q/k length mismatch, or T not
    tileable. The fallback IS the definition of correctness; the kernel is
    tested for parity against it. ``window`` enables sliding-window
    (banded) attention — the kernel skips and never fetches blocks outside
    the band, so compute scales with T*window instead of T^2.
    ``keep_pair`` says the call sits in a layer under ``jax.checkpoint``
    (models/gpt.py sets it from ``cfg.remat``): the forward rule then names
    its output and log-sum-exp (SAVED_OUT, SAVED_LSE; ``_kept``) for the
    checkpoint's policy to save. The fallback names nothing.
    """
    b, t, h, hd = q.shape
    s = k.shape[1]
    block = _block_sizes(t)
    use_flash = (
        block is not None
        and t == s
        and (deterministic or attn_pdrop == 0.0)
        and isinstance(kv_offset, int)
        and kv_offset == 0
    )
    if not use_flash:
        # the fallback is silent perf loss on the training path — warn
        # once when a large training-shaped call degrades
        if t == s and t > 512 and not _interpret():
            import warnings

            warnings.warn(
                f"flash attention fell back to the einsum oracle for T={t} "
                f"(block not tileable or dropout active): O(T^2) HBM "
                f"scores will be materialised",
                stacklevel=2,
            )
        return attn_ops.causal_attention(
            q, k, v, attn_pdrop=attn_pdrop, dropout_key=dropout_key,
            deterministic=deterministic, kv_offset=kv_offset, window=window,
            logit_softcap=logit_softcap,
        )
    kv = k.shape[2]
    k = attn_ops.repeat_kv(k, h // kv)
    v = attn_ops.repeat_kv(v, h // kv)
    scale = 1.0 / math.sqrt(hd)
    win = None if window is None else int(window)
    cap = None if logit_softcap is None else float(logit_softcap)
    # Native-layout path: the model's activations are (B, T, H*hd) under
    # the hood, so the reshape below is free where to_bh pays two real
    # transposes per call (the round-4 trace's biggest remaining sink).
    # FLASH_LAYOUT=bh forces the transpose path (bench A/B escape hatch).
    if (os.environ.get("FLASH_LAYOUT", "auto") != "bh"
            and _btd_applies(h, hd)):
        if _btd_pack(h, hd) is not None:
            out2 = _flash_btd(
                q.reshape(b, t, h * hd), k.reshape(b, t, h * hd),
                v.reshape(b, t, h * hd), h, scale, block, win, cap,
                keep_pair)
            return out2.reshape(b, t, h, hd)
        else:
            # Odd head counts (gpt2-xl's 25) can't pair sub-heads evenly;
            # pad with zero heads up to the pack unit and slice the
            # result. A zero head attends uniformly over zero values —
            # finite lse, zero output and zero gradients, all discarded
            # by the slice (its VJP zero-pads the cotangent). Costs
            # (hp-h)/h extra kernel work (4% at h=25) against the two
            # transposes saved.
            unit = 128 // hd
            hp = -(-h // unit) * unit
            zpad = jnp.zeros((b, t, (hp - h) * hd), q.dtype)
            out2 = _flash_btd(
                jnp.concatenate([q.reshape(b, t, h * hd), zpad], axis=-1),
                jnp.concatenate([k.reshape(b, t, h * hd), zpad], axis=-1),
                jnp.concatenate([v.reshape(b, t, h * hd), zpad], axis=-1),
                hp, scale, block, win, cap, keep_pair)
            return out2[..., :h * hd].reshape(b, t, h, hd)
    # (B, T, H, hd) -> (B*H, T, hd)
    to_bh = lambda x: x.transpose(0, 2, 1, 3).reshape(b * h, t, hd)
    out = _flash(to_bh(q), to_bh(k), to_bh(v), scale, block, win, cap,
                 keep_pair)
    return out.reshape(b, h, t, hd).transpose(0, 2, 1, 3)
