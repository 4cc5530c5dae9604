"""Pipeline parallelism: GPipe microbatch schedule over the ``pp`` mesh axis.

Beyond-parity strategy (SURVEY §2.2 marks PP "absent" in the reference —
blocks run as one nn.Sequential on one device, model.py:245-246). TPU-native
design: the stacked-layer axis of the block parameters (models/gpt.py stacks
all layers along a leading axis for ``lax.scan``) is *sharded* over ``pp``
— each stage holds n_layer/pp contiguous layers — and activations flow
stage-to-stage with ``lax.ppermute`` (point-to-point neighbour exchange, the
cheapest collective: rides a single ICI/DCN link per hop).

Schedule: classic GPipe. The local batch is split into M microbatches; the
loop runs M + pp - 1 ticks. At tick t, stage 0 ingests microbatch t, every
stage applies its layer stack to the microbatch it currently holds, stage
pp-1 banks its finished microbatch (t - pp + 1), and activations rotate one
hop. Bubble fraction (pp-1)/(M+pp-1) — raise ``cfg.pp_microbatches`` to
amortise. The whole schedule is one ``lax.scan`` inside one ``shard_map``,
so it is reverse-differentiable as-is: autodiff transposes ppermute into the
reverse hop and the backward pass runs the mirror-image pipeline.

Two schedules (``schedule=`` / cfg.pp_schedule):

* **"gpipe"** (default): the whole schedule is one plain differentiable
  scan — autodiff transposes ppermute into the reverse hop and derives the
  backward pipeline; combined with ``cfg.remat`` the stored state per tick
  is small, but the scan's saved carries still grow with the microbatch
  count M.
* **"1f1b"**: identical forward; the backward is a hand-written custom-vjp
  that re-runs the forward pipeline and interleaves each stage's transposed
  (backward) application with the recompute in classic 1F1B order — stage s
  transposes microbatch m exactly 2(pp-1-s) ticks after re-stashing its
  input, so the live stage-input stash is a ring buffer of 2(pp-1)+1
  microbatches: O(pp), independent of M. Compute cost is one extra forward
  vs GPipe+remat — 3 forwards + 1 backward per stage-microbatch (primal,
  the stash-rebuilding recompute, and the vjp's own linearization forward;
  the two bwd-tick forwards run on different microbatches so they cannot
  fuse). Choose it when M is large enough that GPipe's O(M) per-tick
  stashes dominate HBM and the ~25% step-FLOP tax is worth the headroom.
  Same bubble fraction either way.

  Measured (XLA memory_analysis/cost_analysis on the compiled pp=2, M=8
  tiny-GPT train step — test_pipeline.py::test_pp_schedule_cost_model_is_
  measured keeps the ordering pinned): gpipe no-remat 14.2 MB temp /
  49 GFLOP; gpipe+remat 1.7 MB / 54 GFLOP (+10%); 1f1b 3.1 MB / 63 GFLOP
  (+29%). So gpipe+remat is the default memory-saver; 1f1b's niche is
  avoiding remat's recompute *latency* inside each tick (its re-forward
  overlaps the pipeline) or models where jax.checkpoint granularity is
  too coarse.

Composition:
- pp x dp/fsdp: batch stays sharded over BATCH_AXES inside the region.
- pp x sp (``seq_sharded=True``): activations stay sequence-sharded inside
  the region too; the caller's ``apply_stack`` runs sequence-parallel
  attention (ring / Ulysses per-shard bodies over the ``sp`` axis — legal
  here because the pipeline's shard_map already manualises every mesh axis).
- pp x MoE: ``apply_stack`` returns a per-stage aux (load-balancing) loss;
  garbage warm-up/drain ticks are masked out, stages sum over ``pp`` and the
  batch-ish axes average, reproducing the single-device aux semantics.
  (Expert weights are gathered at stage entry like the rest of the stage's
  params — ZeRO-style JIT gather — so combine pp with ep=1.)
- pp x tp/fsdp (``xs_specs``): the caller may pass per-leaf PartitionSpecs
  for ``xs`` so stage parameters STAY tp/fsdp-sharded inside the manual
  region instead of being gathered at entry; ``apply_stack`` then owns the
  megatron math (models/gpt.py: per-shard heads/ffn columns, one psum over
  ``tp`` per residual branch, per-layer all_gather over ``fsdp``).
"""

from __future__ import annotations

from typing import Any, Callable, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from mingpt_distributed_tpu.parallel.mesh import BATCH_AXES


def _split_diff(tree):
    """Flatten a pytree and mark which leaves are differentiable (inexact
    dtype). PRNG-key and integer leaves (e.g. per-layer dropout keys riding
    the scanned xs) get float0 cotangents from the custom vjp."""
    flat, treedef = jax.tree_util.tree_flatten(tree)
    mask = [jnp.issubdtype(jnp.asarray(l).dtype, jnp.inexact) for l in flat]
    return flat, treedef, mask


def _rebuild(flat, treedef, mask, diff_vals):
    it = iter(diff_vals)
    return jax.tree_util.tree_unflatten(
        treedef, [next(it) if k else orig for orig, k in zip(flat, mask)]
    )


def _float0_cotangents(flat, treedef, mask, diff_cts):
    from jax import dtypes as jdtypes

    it = iter(diff_cts)
    out = [
        next(it) if k else np.zeros(np.shape(orig), jdtypes.float0)
        for orig, k in zip(flat, mask)
    ]
    return jax.tree_util.tree_unflatten(treedef, out)


def _make_1f1b(tick_scan, apply_stack, pp: int, m: int):
    """Wrap the GPipe forward in a custom vjp whose backward runs the 1F1B
    interleave: one combined scan where every tick does (a) one forward
    recompute tick, stashing the stage's input in a ring buffer, and (b) one
    transposed (backward) application 2(pp-1-stage) ticks behind, consuming
    the stash and rotating the cotangent one hop backwards. Stage pp-1 has
    lag 0 — its backward starts the very tick its forward recompute runs —
    which is what bounds the stash at 2(pp-1)+1 in-flight microbatches."""
    lag = pp - 1
    stash_n = 2 * lag + 1
    fwd_shift = [(i, (i + 1) % pp) for i in range(pp)]
    rev_shift = [(i, (i - 1) % pp) for i in range(pp)]

    @jax.custom_vjp
    def run(mbs, xs, consts):
        return tick_scan(mbs, xs, consts)

    def fwd_rule(mbs, xs, consts):
        return tick_scan(mbs, xs, consts), (mbs, xs, consts)

    def bwd_rule(res, cts):
        mbs, xs, consts = res
        g_outs, g_aux = cts
        act_dtype = mbs.dtype
        xs_flat, xs_tree, xs_mask = _split_diff(xs)
        c_flat, c_tree, c_mask = _split_diff(consts)
        diff_xs = tuple(l for l, k in zip(xs_flat, xs_mask) if k)
        diff_c = tuple(l for l, k in zip(c_flat, c_mask) if k)

        def tick(carry, t):
            fstate, bstate, stash, g_mbs, g_xs, g_c = carry
            stage = jax.lax.axis_index("pp")

            # -- forward recompute (GPipe order), stashing stage INPUTS ----
            inp = jax.lax.dynamic_index_in_dim(
                mbs, jnp.clip(t, 0, m - 1), 0, keepdims=False
            )
            fstate = jnp.where(stage == 0, inp, fstate)
            mb_f = jnp.clip(t - stage, 0, m - 1).astype(jnp.int32)
            fvalid = (t >= stage) & (t - stage < m)
            slot_f = mb_f % stash_n
            old = jax.lax.dynamic_index_in_dim(stash, slot_f, 0, keepdims=False)
            stash = jax.lax.dynamic_update_index_in_dim(
                stash, jnp.where(fvalid, fstate, old), slot_f, 0
            )
            fstate, _ = apply_stack(fstate, xs, consts, mb_f)
            fstate = jax.lax.ppermute(fstate, "pp", fwd_shift)

            # -- backward: transpose stage apply for mb (t - 2*lag + stage) -
            mb_b = t - 2 * lag + stage
            bvalid = (mb_b >= 0) & (mb_b < m)
            mb_bc = jnp.clip(mb_b, 0, m - 1).astype(jnp.int32)
            x_in = jax.lax.dynamic_index_in_dim(
                stash, mb_bc % stash_n, 0, keepdims=False
            )
            g_in = jnp.where(
                stage == lag,
                jax.lax.dynamic_index_in_dim(g_outs, mb_bc, 0, keepdims=False)
                .astype(act_dtype),
                bstate,
            )
            g_y = jnp.where(bvalid, g_in, jnp.zeros_like(g_in))
            # g_aux arrives shaped (1,) (the region-internal aux shape);
            # apply_stack's own aux output is scalar, so its ct must be too
            g_a = jnp.where(bvalid, g_aux.reshape(()), 0.0).astype(jnp.float32)

            def apply_d(x, dxs, dc):
                return apply_stack(
                    x,
                    _rebuild(xs_flat, xs_tree, xs_mask, dxs),
                    _rebuild(c_flat, c_tree, c_mask, dc),
                    mb_bc,
                )

            _, vjp_fn = jax.vjp(apply_d, x_in, diff_xs, diff_c)
            gx, g_dxs, g_dc = vjp_fn((g_y, g_a))
            gx = gx.astype(act_dtype)
            g_xs = jax.tree.map(jnp.add, g_xs, tuple(g_dxs))
            g_c = jax.tree.map(jnp.add, g_c, tuple(g_dc))
            gm_old = jax.lax.dynamic_index_in_dim(g_mbs, mb_bc, 0, keepdims=False)
            g_mbs = jax.lax.dynamic_update_index_in_dim(
                g_mbs, jnp.where((stage == 0) & bvalid, gx, gm_old), mb_bc, 0
            )
            bstate = jax.lax.ppermute(gx, "pp", rev_shift)
            return (fstate, bstate, stash, g_mbs, g_xs, g_c), None

        init = (
            jnp.zeros_like(mbs[0]),                                  # fstate
            jnp.zeros_like(mbs[0]),                                  # bstate
            jnp.zeros((stash_n, *mbs.shape[1:]), act_dtype),         # stash
            jnp.zeros_like(mbs),                                     # g_mbs
            tuple(jnp.zeros_like(l) for l in diff_xs),
            tuple(jnp.zeros_like(l) for l in diff_c),
        )
        (_, _, _, g_mbs, g_dxs, g_dc), _ = jax.lax.scan(
            tick, init, jnp.arange(m + 2 * lag)
        )
        return (
            g_mbs,
            _float0_cotangents(xs_flat, xs_tree, xs_mask, g_dxs),
            _float0_cotangents(c_flat, c_tree, c_mask, g_dc),
        )

    run.defvjp(fwd_rule, bwd_rule)
    return run


def pipeline_blocks(
    x: jax.Array,              # (B, T, D) activations (batch-sharded outside)
    xs: Any,                   # scanned-over pytree, leading global layer axis
    consts: Any,               # replicated extras (e.g. rope tables), pytree
    apply_stack: Callable[[jax.Array, Any, Any, jax.Array], Tuple[jax.Array, jax.Array]],
    mesh: Mesh,
    *,
    n_microbatches: int = 0,
    seq_sharded: bool = False,
    xs_specs: Any = None,
    schedule: str = "gpipe",
) -> Tuple[jax.Array, jax.Array]:
    """Apply all layers to ``x`` across pipeline stages.

    ``apply_stack(x_mb, xs_local, consts, mb_idx) -> (y_mb, aux)`` applies
    one stage's local layer stack (n_layer/pp layers) to one microbatch and
    returns its scalar aux loss (0 for dense MLPs); ``mb_idx`` is the index
    of the microbatch being processed (fold it into any PRNG keys so
    stochastic ops like dropout decorrelate across microbatches).
    ``seq_sharded`` keeps the sequence dim sharded over ``sp`` inside the
    region (apply_stack must then run sequence-parallel attention).
    ``xs_specs`` (a PartitionSpec pytree matching ``xs``) keeps stage params
    sharded over further axes (tp/fsdp) inside the region — apply_stack must
    then run the matching per-shard math; default gathers everything but the
    ``pp`` layer axis at entry.
    Returns (activations, aux) — semantically equivalent to scanning the
    full layer axis on one device.
    """
    pp = mesh.shape.get("pp", 1)
    if pp == 1:
        return apply_stack(x, xs, consts, jnp.asarray(0, jnp.int32))
    m = n_microbatches or pp
    n_layer = jax.tree.leaves(xs)[0].shape[0]
    if n_layer % pp:
        raise ValueError(f"n_layer {n_layer} not divisible by pp={pp}")

    def shard_fn(x_local, xs_local, consts_):
        b = x_local.shape[0]
        if b % m:
            raise ValueError(
                f"local batch {b} not divisible by {m} microbatches "
                f"(global batch / (dp*fsdp) must divide pp_microbatches)"
            )
        mbs = x_local.reshape(m, b // m, *x_local.shape[1:])
        shift = [(i, (i + 1) % pp) for i in range(pp)]

        def tick_scan(mbs_, xs_, consts_in):
            """GPipe forward ticks -> (outs, aux_tot); outs are banked on
            the last stage only (zeros elsewhere; broadcast happens below)."""

            def tick(carry, t):
                state, outs, aux_tot = carry
                stage = jax.lax.axis_index("pp")
                inp = jax.lax.dynamic_index_in_dim(
                    mbs_, jnp.clip(t, 0, m - 1), 0, keepdims=False
                )
                state = jnp.where(stage == 0, inp, state)
                # the microbatch this stage holds at tick t entered at t - stage
                mb_idx = jnp.clip(t - stage, 0, m - 1).astype(jnp.int32)
                state, aux = apply_stack(state, xs_, consts_in, mb_idx)
                # warm-up/drain ticks process zero-padding, not data — mask
                # their aux out (outputs are filtered by the banking below)
                valid = (t >= stage) & (t - stage < m)
                aux_tot = aux_tot + jnp.where(valid, aux, 0.0)
                # bank stage pp-1's finished microbatch (index t - pp + 1)
                oidx = jnp.maximum(t - (pp - 1), 0)
                prev = jax.lax.dynamic_index_in_dim(outs, oidx, 0, keepdims=False)
                bank = jnp.where((stage == pp - 1) & (t >= pp - 1), state, prev)
                outs = jax.lax.dynamic_update_index_in_dim(outs, bank, oidx, 0)
                state = jax.lax.ppermute(state, "pp", shift)
                return (state, outs, aux_tot), None

            # the aux accumulator rides as shape (1,), NOT a scalar: jaxlib
            # 0.4.x's shard_map partial-eval names every linearization
            # residual {0: all_axes}, which is rank-invalid for scalars and
            # makes jit(grad(...)) of the region raise _SpecError — keeping
            # every differentiable intermediate rank >= 1 sidesteps it
            # (scalarised again at the region boundary below).
            (_, outs, aux_tot), _ = jax.lax.scan(
                tick,
                (jnp.zeros_like(mbs_[0]), jnp.zeros_like(mbs_),
                 jnp.zeros((1,), jnp.float32)),
                jnp.arange(m + pp - 1),
            )
            return outs, aux_tot

        if schedule == "1f1b":
            outs, aux_tot = _make_1f1b(tick_scan, apply_stack, pp, m)(
                mbs, xs_local, consts_
            )
        else:
            outs, aux_tot = tick_scan(mbs, xs_local, consts_)
        stage = jax.lax.axis_index("pp")
        # results live on the last stage; broadcast so every stage returns
        # the full activations (head/loss then run replicated over pp).
        # The mask is materialised at rank outs.ndim rather than passed as
        # a scalar `where` condition: jaxlib 0.4.x's shard_map partial
        # eval names every residual {0: all_axes}, which is rank-invalid
        # for a scalar residual and makes jit(grad(...)) of this region
        # die with _SpecError — a rank-1+ residual sidesteps the bug.
        mask = (stage == pp - 1).astype(outs.dtype).reshape((1,) * outs.ndim)
        outs = jax.lax.psum(outs * mask, "pp")
        # aux: sum over stages (each holds different layers), mean over
        # microbatches and over the batch-ish/sequence shards — the same
        # estimator as the single-device full-batch mean
        aux = jax.lax.psum(aux_tot, "pp") / m
        aux = jax.lax.pmean(aux, BATCH_AXES + (("sp",) if seq_sharded else ()))
        return outs.reshape(x_local.shape), aux.reshape(())

    seq_ax = "sp" if seq_sharded else None
    x_spec = P(BATCH_AXES, seq_ax, *([None] * (x.ndim - 2)))
    fn = jax.shard_map(
        shard_fn,
        mesh=mesh,
        in_specs=(x_spec, xs_specs if xs_specs is not None else P("pp"), P()),
        out_specs=(x_spec, P()),
        check_vma=False,
    )
    return fn(x, xs, consts)
