"""ZeRO-style cross-replica weight-update sharding over the ``dp`` axis.

The mesh already gives ZeRO-3-style *parameter* sharding on ``fsdp`` for
free (PARAM_RULES applies to the AdamW moments leaf-for-leaf), but the
pure ``dp`` axis replicates params AND optimizer moments on every
replica: grads are all-reduced and every dp replica redundantly computes
the identical full AdamW update. This module implements the
weight-update-sharding transformation of "Automatic Cross-Replica
Sharding of Weight Update in Data-Parallel Training" (arXiv 2004.13336):

  reduce-scatter grads over dp  ->  clip/Adam/decay/lr on the local
  1/dp shard only  ->  allgather the updated params.

Everything here is driven by a static per-leaf ``ZeroPlan`` built once
from the abstract parameter shapes:

* **dim mode** — the largest dimension whose size the dp extent (times
  any axes already sharding that dimension) divides gets ``dp`` appended
  to its PartitionSpec entry. The leaf keeps its shape; only the layout
  changes.
* **flat mode** — small/indivisible leaves (biases, norm scales) are
  flattened to 1-D, zero-padded to a multiple of dp, and sharded
  ``P("dp")``. Padding is update-invariant: pad grads are zero, so Adam
  moments and updates for pad slots stay zero, and ``from_view`` drops
  the pad before the params are gathered back.

The *update view* (``update_view``/``from_view``) is the layout the
optimizer runs in; optimizer state is initialised from the view, so the
moments are physically 1/dp per device (``mesh.state_shardings`` with a
``zero_plan``). Checkpoints always store moments in the CANONICAL layout
(original shapes, no pad — ``canonical_opt_state``/``localize_opt_state``),
which is what makes a checkpoint written at dp=4 restore cleanly at
dp=2 or dp=1: the view is a function of the *restoring* mesh, not the
saving one.

Inside the jitted step the plan only ever makes static (python-level)
decisions — per-leaf mode, pad amount, spec — so the compiled program
contains no traced branching; the collectives are placed by GSPMD from
``with_sharding_constraint`` alone. ``optax.clip_by_global_norm`` stays
globally correct on the sharded view because GSPMD inserts the psum for
the norm reduction, and the pad zeros contribute nothing to it.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from mingpt_distributed_tpu.parallel import mesh as mesh_lib
from mingpt_distributed_tpu.utils.pytree import leaf_name

# per-leaf plan modes
DIM = "dim"    # append "dp" to the spec of one (dp-divisible) dimension
FLAT = "flat"  # flatten + zero-pad to a multiple of dp, shard P("dp")
NOOP = "noop"  # dp extent 1: the view is the identity


@dataclass(frozen=True)
class LeafPlan:
    """Static update-view layout for one parameter leaf."""

    name: str
    mode: str
    shape: Tuple[int, ...]       # canonical (model) shape
    view_shape: Tuple[int, ...]  # shape inside the update view
    spec: P                      # partition spec of the update view
    dim: int = -1                # sharded dimension (dim mode)
    pad: int = 0                 # zero slots appended (flat mode)


@dataclass(frozen=True)
class ZeroPlan:
    """Whole-tree plan: a pytree of LeafPlan mirroring the params, plus a
    name index for the (name-keyed) optimizer-moment trees."""

    mesh: Mesh
    dp: int
    leaves: Any                       # pytree of LeafPlan
    by_name: Dict[str, LeafPlan]


def _padded_spec(spec: P, ndim: int) -> list:
    """Spec entries as a list, one per dimension (P may be shorter)."""
    entries = list(spec)
    return entries + [None] * (ndim - len(entries))


def make_plan(mesh: Mesh, params_shape: Any) -> ZeroPlan:
    """Build the static per-leaf plan from abstract parameter shapes.

    The base spec is the PARAM_RULES spec after ``shard_by_rule``'s
    divisibility downgrade, so ``dp`` composes with whatever sharding the
    leaf actually gets (fsdp/tp/pp), never with what the rule wished for.
    """
    dp = int(mesh.shape["dp"])
    by_name: Dict[str, LeafPlan] = {}

    def plan_leaf(path, leaf) -> LeafPlan:
        name = leaf_name(path)
        shape = tuple(leaf.shape)
        base = mesh_lib.shard_by_rule(
            mesh, shape, mesh_lib._spec_for(path, leaf), name=name
        ).spec
        entries = _padded_spec(base, len(shape))
        if dp <= 1:
            lp = LeafPlan(name, NOOP, shape, shape, P(*entries))
            by_name[name] = lp
            return lp
        best, best_size = -1, 0
        for i, size in enumerate(shape):
            axes = entries[i]
            ax_tuple = (
                () if axes is None
                else (axes if isinstance(axes, tuple) else (axes,))
            )
            n = math.prod(mesh.shape[a] for a in ax_tuple)
            if size % (n * dp) == 0 and size > best_size:
                best, best_size = i, size
        if best >= 0:
            axes = entries[best]
            ax_tuple = (
                () if axes is None
                else (axes if isinstance(axes, tuple) else (axes,))
            )
            entries[best] = ax_tuple + ("dp",) if ax_tuple else "dp"
            lp = LeafPlan(name, DIM, shape, shape, P(*entries), dim=best)
        else:
            total = math.prod(shape) if shape else 1
            pad = (-total) % dp
            lp = LeafPlan(
                name, FLAT, shape, (total + pad,), P("dp"), pad=pad
            )
        by_name[name] = lp
        return lp

    leaves = jax.tree_util.tree_map_with_path(plan_leaf, params_shape)
    return ZeroPlan(mesh=mesh, dp=dp, leaves=leaves, by_name=by_name)


def _is_plan(x) -> bool:
    return isinstance(x, LeafPlan)


def update_view(tree: Any, plan: ZeroPlan) -> Any:
    """Canonical layout -> update view (jit-safe; shapes only, no layout —
    sharding comes from ``constrain``/``view_shardings``)."""

    def to_view(lp: LeafPlan, leaf):
        if lp.mode != FLAT:
            return leaf
        flat = jnp.reshape(leaf, (-1,))
        if lp.pad:
            flat = jnp.pad(flat, (0, lp.pad))
        return flat

    return jax.tree.map(to_view, plan.leaves, tree, is_leaf=_is_plan)


def from_view(tree: Any, plan: ZeroPlan) -> Any:
    """Update view -> canonical layout (drops flat-mode padding)."""

    def back(lp: LeafPlan, leaf):
        if lp.mode != FLAT:
            return leaf
        flat = leaf[: math.prod(lp.shape) if lp.shape else 1]
        return jnp.reshape(flat, lp.shape)

    return jax.tree.map(back, plan.leaves, tree, is_leaf=_is_plan)


def view_shardings(plan: ZeroPlan) -> Any:
    """NamedSharding pytree for the update view (mirrors the params)."""
    return jax.tree.map(
        lambda lp: NamedSharding(plan.mesh, lp.spec),
        plan.leaves, is_leaf=_is_plan,
    )


def constrain(tree: Any, plan: ZeroPlan) -> Any:
    """Pin the update view's layout inside jit. On the grads view this is
    what GSPMD lowers to a reduce-scatter over dp (all-reduce + slice
    fused); on the params view it is a local slice of the replicated
    copy (no communication)."""
    return jax.lax.with_sharding_constraint(tree, view_shardings(plan))


# ---------------------------------------------------------------------------
# Canonical <-> view optimizer-state layout (host-side, for checkpoints)
# ---------------------------------------------------------------------------

def _named_flat_leaf(plan: ZeroPlan, path, leaf, *, in_view: bool):
    """The FLAT LeafPlan for this opt-state leaf, or None.

    Moments (mu/nu) mirror the params pytree with the same leaf names;
    scalars (Adam's count) and anything else match no plan entry. The
    leaf must be in the transform's SOURCE layout (``in_view`` = view
    shape, else canonical), so a leaf already in the target layout
    passes through untouched (idempotent)."""
    lp = plan.by_name.get(leaf_name(path))
    if lp is None or lp.mode != FLAT:
        return None
    have = tuple(np.shape(leaf))
    source = lp.view_shape if in_view else lp.shape
    return lp if have == source else None


def canonical_opt_state(opt_state: Any, plan: ZeroPlan) -> Any:
    """View layout -> canonical layout (numpy; gathers nothing itself —
    call on host/full arrays). Checkpoints always store this layout, so
    snapshots are identical whether ``zero_dp`` was on or off and restore
    reshards to any dp extent."""

    def back(path, leaf):
        lp = _named_flat_leaf(plan, path, leaf, in_view=True)
        if lp is None:
            return leaf
        flat = np.asarray(leaf).reshape(-1)
        return flat[: math.prod(lp.shape) if lp.shape else 1].reshape(lp.shape)

    return jax.tree_util.tree_map_with_path(back, opt_state)


def localize_opt_state(opt_state: Any, plan: ZeroPlan) -> Any:
    """Canonical layout -> this plan's view layout (numpy, host-side):
    the restore-time half of reshard-on-restore."""

    def to_view(path, leaf):
        lp = _named_flat_leaf(plan, path, leaf, in_view=False)
        if lp is None:
            return leaf
        flat = np.asarray(leaf).reshape(-1)
        if lp.pad:
            flat = np.pad(flat, (0, lp.pad))
        return flat

    return jax.tree_util.tree_map_with_path(to_view, opt_state)


def canonical_opt_shape(opt_state_shape: Any, plan: ZeroPlan) -> Any:
    """Abstract (eval_shape) view-layout opt state -> canonical-layout
    ShapeDtypeStructs: the checkpoint skeleton ``load_snapshot`` pours
    into before ``localize_opt_state`` re-views it."""

    def back(path, leaf):
        # abstract leaves are in VIEW layout here; map view -> canonical
        lp = plan.by_name.get(leaf_name(path))
        if (
            lp is not None and lp.mode == FLAT
            and tuple(leaf.shape) == lp.view_shape
        ):
            return jax.ShapeDtypeStruct(lp.shape, leaf.dtype)
        return leaf

    return jax.tree_util.tree_map_with_path(back, opt_state_shape)


# ---------------------------------------------------------------------------
# Measurement helper (selftests / dryrun)
# ---------------------------------------------------------------------------

def per_device_bytes(tree: Any) -> int:
    """Bytes of ``tree`` held on the busiest addressable device — the
    per-chip memory cost the sharding actually achieves (a replicated
    leaf counts fully on every device; a 1/dp shard counts once)."""
    per: Dict[int, int] = {}
    for leaf in jax.tree.leaves(tree):
        shards = getattr(leaf, "addressable_shards", None)
        if not shards:
            continue
        for s in shards:
            per[s.device.id] = per.get(s.device.id, 0) + s.data.nbytes
    return max(per.values()) if per else 0
