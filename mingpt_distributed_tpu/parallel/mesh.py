"""Device mesh + sharding rules — the framework's distributed backbone.

Replaces the reference's distributed runtime (SURVEY §1-L1/§2.3): where the
reference wraps the model in DDP over NCCL (/root/reference/mingpt/trainer.py:71,
train.py:34) and shards data with DistributedSampler (trainer.py:80), here a
named ``jax.sharding.Mesh`` over all addressable devices carries every
parallelism axis, and XLA compiles the collectives (psum over ICI within a
slice, DCN across hosts) directly into the training step:

  pp    pipeline parallelism (GPipe stages over the stacked layer axis,
        parallel/pipeline.py)
  dp    pure data parallelism (the reference's only axis — grad all-reduce)
  fsdp  data parallelism + ZeRO-style parameter/optimizer sharding
        (BASELINE config #4: "pjit param sharding, DDP->GSPMD/FSDP analogue")
  ep    expert parallelism for MoE (ops/moe.py); also shards the batch
        outside expert layers, GShard-style
  tp    megatron-style tensor parallelism (column/row-split matmuls)
  sp    sequence/context parallelism for ring attention (long-context axis)

The model stays parallelism-unaware (SURVEY §1-L2's separation, preserved):
these rules attach NamedShardings to the *pytree* from outside; forward never
mentions an axis.
"""

from __future__ import annotations

import math
from typing import Any, Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from mingpt_distributed_tpu.config import MeshConfig
from mingpt_distributed_tpu.utils.pytree import leaf_name

# pp outermost: pipeline stages exchange activations point-to-point once per
# microbatch tick — the least bandwidth-hungry axis, so it can cross DCN;
# tp/sp innermost ride ICI.
AXES = ("pp", "dp", "fsdp", "ep", "tp", "sp")
# Batch is split over every data-ish axis; dp, fsdp and ep all shard the
# batch (ep doubles as a data axis outside expert layers, GShard-style),
# sp shards the sequence (ring attention), tp replicates the batch.
BATCH_AXES = ("dp", "fsdp", "ep")


def resolve_mesh_shape(cfg: MeshConfig, n_devices: int) -> tuple[int, ...]:
    """Resolve -1 entries ("absorb remaining devices") and validate."""
    dims = [cfg.pp, cfg.dp, cfg.fsdp, cfg.ep, cfg.tp, cfg.sp]
    if dims.count(-1) > 1:
        raise ValueError(f"at most one mesh axis may be -1, got {dims}")
    known = math.prod(d for d in dims if d != -1)
    if -1 in dims:
        if n_devices % known != 0:
            raise ValueError(
                f"{n_devices} devices not divisible by fixed axes product {known}"
            )
        dims[dims.index(-1)] = n_devices // known
    if math.prod(dims) != n_devices:
        raise ValueError(
            f"mesh {dict(zip(AXES, dims))} needs {math.prod(dims)} devices, "
            f"have {n_devices}"
        )
    return tuple(dims)


def make_mesh(
    cfg: Optional[MeshConfig] = None, devices: Optional[Sequence[jax.Device]] = None
) -> Mesh:
    """Build the named mesh.

    Without an explicit device list, device placement is delegated to
    ``jax.experimental.mesh_utils.create_device_mesh``, which knows the
    physical TPU topology (ICI torus links) and lays the mesh out so the
    fastest-varying axes (tp, sp — tensor/sequence collectives) ride ICI
    while dp/fsdp cross slices/DCN (SURVEY §2.3's ICI/DCN mapping). A naive
    ``jax.devices()`` reshape instead assumes neighbouring ids are ICI
    neighbours, which real multi-host slices violate.

    Passing ``devices`` explicitly is the escape hatch for tests and for the
    driver's virtual-CPU dry run: those devices are used in the given order.
    """
    cfg = cfg or MeshConfig()
    if devices is not None:
        devs = list(devices)
        shape = resolve_mesh_shape(cfg, len(devs))
        arr = np.array(devs).reshape(shape)
        return Mesh(arr, AXES)
    shape = resolve_mesh_shape(cfg, len(jax.devices()))
    from jax.experimental import mesh_utils

    arr = mesh_utils.create_device_mesh(shape)
    return Mesh(arr, AXES)


def dropped_attention_shard_map(shard, mesh: Mesh, spec: P, pdrop: float,
                                head_axis: Optional[str] = None):
    """shard_map wrapper for sequence-parallel attention bodies under
    attention dropout (single-sourced decorrelation policy — used by both
    ring_attention and ulysses public wrappers).

    The dropout key rides in replicated (P()); each shard folds in

      - its batch-shard coordinate over ``BATCH_AXES`` — the dense GSPMD
        path draws masks per *global* row, so dp/fsdp/ep shards holding
        different rows must draw different masks;
      - its ``head_axis`` coordinate, ONLY when the q/k/v specs actually
        shard heads over that axis — tp shards then hold different global
        heads and must draw per-head-independent masks (mirroring the
        k_attn fold in models/gpt._block's manual-tp branch). When heads
        are *replicated* over tp (head_axis=None) every replica must draw
        the SAME mask or the replicas would diverge.

    The shard body then folds finer-grained ids (the ring's global
    (q-chunk, k-chunk) pair id; ulysses' head-group index) on top.
    """

    def dropped(q, k, v, key):
        key = jax.random.fold_in(key, jax.lax.axis_index(BATCH_AXES))
        if head_axis is not None:
            key = jax.random.fold_in(key, jax.lax.axis_index(head_axis))
        return shard(q, k, v, pdrop=pdrop, key=key)

    return jax.shard_map(
        dropped, mesh=mesh, in_specs=(spec, spec, spec, P()),
        out_specs=spec, check_vma=False,
    )


def batch_spec() -> P:
    """(batch, seq) inputs: batch over dp+fsdp, seq over sp."""
    return P(BATCH_AXES, "sp")


def batch_sharding(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, batch_spec())


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


# ---------------------------------------------------------------------------
# Parameter sharding rules
# ---------------------------------------------------------------------------

# name -> PartitionSpec over the *parameter pytree* produced by models/gpt.py.
# Block params carry a leading layer axis (scanned), never sharded.
# Convention (scaling-book megatron recipe):
#   column-parallel (d_model -> wide): input dim fsdp, output dim tp
#   row-parallel   (wide -> d_model): input dim tp,   output dim fsdp
# so a block's tp collectives are one all-gather + one reduce-scatter pair,
# and fsdp gathers params just-in-time per layer (ZeRO-3 analogue via GSPMD).
# Specs are authored in normalized form — no trailing Nones (GL011):
# unmentioned trailing dims replicate, and the runtime strips trailing
# Nones anyway, so the spelled form only breaks sharding-equality keys.
PARAM_RULES: dict[str, P] = {
    "wte": P("fsdp", "tp"),
    "wpe": P(),
    "head": P("tp", "fsdp"),
    "lnf_scale": P(),
    "lnf_bias": P(),
    # blocks (leading layer axis, sharded over pipeline stages; pp=1 = no-op)
    "wq": P("pp", "fsdp", "tp"),
    "wk": P("pp", "fsdp", "tp"),
    "wv": P("pp", "fsdp", "tp"),
    "wo": P("pp", "tp", "fsdp"),
    "w_fc": P("pp", "fsdp", "tp"),
    "w_gate": P("pp", "fsdp", "tp"),
    "w_up": P("pp", "fsdp", "tp"),
    "w_proj": P("pp", "tp", "fsdp"),
    "w_down": P("pp", "tp", "fsdp"),
    "bq": P("pp", "tp"),
    "bk": P("pp", "tp"),
    "bv": P("pp", "tp"),
    "bo": P("pp"),
    "b_fc": P("pp", "tp"),
    "b_proj": P("pp"),
    "ln1_scale": P("pp"),
    "ln1_bias": P("pp"),
    "ln2_scale": P("pp"),
    "ln2_bias": P("pp"),
    # MoE (ops/moe.py): expert axis over ep; expert matrices additionally
    # fsdp/tp-sharded like their dense counterparts
    "w_router": P("pp"),
    "w_e1": P("pp", "ep", "fsdp", "tp"),
    "w_e2": P("pp", "ep", "tp", "fsdp"),
    "w_eg": P("pp", "ep", "fsdp", "tp"),
    "e_bias": P("pp"),
    # the shared expert every token takes: a dense SwiGLU MLP
    "w_sg": P("pp", "fsdp", "tp"),
    "w_su": P("pp", "fsdp", "tp"),
    "w_sd": P("pp", "tp", "fsdp"),
    # latent attention: the down-projection's outputs (one latent and one
    # rope key a token) belong to no head and stay whole; the
    # up-projection's columns are the heads'
    "w_kv_a": P("pp", "fsdp"),
    "kv_norm_scale": P("pp"),
    "w_kv_b": P("pp", "fsdp", "tp"),
    # a hybrid stack's mixers (models/gpt.py MIXER_STACKS): the output
    # gate's columns are the heads', as wq's; the per-head qk-norm weights
    # and the linear mixer's output norm stay whole
    "w_og": P("pp", "fsdp", "tp"),
    "w_hg": P("pp", "fsdp"),
    "q_norm_scale": P("pp"),
    "k_norm_scale": P("pp"),
    "o_norm_scale": P("pp"),
    # the norms after a sublayer (GPTConfig.post_norms) and the exit gate
    # (GPTConfig.exit_gate): small and whole, as every norm is
    "ln1_post_scale": P("pp"),
    "ln2_post_scale": P("pp"),
    "exit_gate_w": P(),
    "exit_gate_b": P(),
}


def _spec_for(path, leaf) -> P:
    name = leaf_name(path)
    try:
        return PARAM_RULES[name]
    except KeyError:
        raise ValueError(
            f"no sharding rule for parameter {jax.tree_util.keystr(path)!r}"
        ) from None


def param_specs(params_shape: Any) -> Any:
    """PartitionSpec pytree for a (possibly abstract) parameter pytree."""
    return jax.tree_util.tree_map_with_path(_spec_for, params_shape)


# Leaf names whose rule has already been observed downgrading on this
# process — each (param, axes) surprise is logged/counted exactly once,
# not once per mesh rebuild or per moment tree that shares the name.
_DOWNGRADES_SEEN: set = set()


def _note_downgrade(name: str, axes, size: int, n: int) -> None:
    key = (name, axes)
    if key in _DOWNGRADES_SEEN:
        return
    _DOWNGRADES_SEEN.add(key)
    from mingpt_distributed_tpu import telemetry

    telemetry.get_registry().counter(
        "mingpt_train_sharding_downgrades_total",
        help="Parameter-sharding rules silently downgraded to replication "
             "because the mesh axis extent does not divide the dimension.",
        labels=("param",),
    ).labels(param=name).inc()
    telemetry.log_event(
        f"sharding downgrade: {name} dim of size {size} not divisible by "
        f"mesh extent {n} of axes {axes!r} — replicating that dimension",
        param=name,
    )


def shard_by_rule(
    mesh: Mesh, shape: Sequence[int], spec: P, name: Optional[str] = None
) -> NamedSharding:
    """NamedSharding for one array, downgrading (replicating) any spec axis
    whose mesh extent doesn't divide the dimension — tiny models on big
    meshes shard what they can instead of failing. When ``name`` is given,
    each downgrade is logged once and counted in
    ``mingpt_train_sharding_downgrades_total{param}`` so surprise
    replication shows up in scrapes instead of only in the memory bill."""
    fixed = []
    for size, axes in zip(shape, spec):
        if axes is None:
            fixed.append(None)
            continue
        ax_tuple = axes if isinstance(axes, tuple) else (axes,)
        n = math.prod(mesh.shape[a] for a in ax_tuple)
        if size % n == 0:
            fixed.append(axes)
        else:
            if name is not None:
                _note_downgrade(name, axes, size, n)
            fixed.append(None)
    return NamedSharding(mesh, P(*fixed))


def param_shardings(mesh: Mesh, params_shape: Any) -> Any:
    """NamedSharding pytree for model params (divisibility-validated)."""
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: shard_by_rule(
            mesh, leaf.shape, _spec_for(path, leaf), name=leaf_name(path)
        ),
        params_shape,
    )


def state_shardings(mesh: Mesh, state_shape: Any, zero_plan=None) -> Any:
    """NamedShardings for a whole TrainState-like pytree.

    Optimizer moments (mu/nu) mirror the params pytree leaf-for-leaf with the
    same leaf names, so PARAM_RULES applies to them unchanged — ZeRO-style
    sharded optimizer state for free (BASELINE config #4). Scalars and
    unrecognised leaves replicate.

    With a ``zero_plan`` (parallel/zero.py), opt-state moment leaves get the
    plan's dp-sharded *update-view* spec instead, so Adam's mu/nu are
    physically 1/dp per device. Only leaves under the ``opt_state`` key
    whose shape matches the plan's view shape are re-routed — the params
    themselves keep their canonical sharding (they are gathered back after
    every update)."""

    def rule(path, leaf):
        if getattr(leaf, "ndim", 0) == 0:
            return NamedSharding(mesh, P())
        name = leaf_name(path)
        if (
            zero_plan is not None
            and path
            and getattr(path[0], "key", None) == "opt_state"
        ):
            lp = zero_plan.by_name.get(name)
            if lp is not None and tuple(leaf.shape) == tuple(lp.view_shape):
                return NamedSharding(mesh, lp.spec)
        if name in PARAM_RULES:
            return shard_by_rule(mesh, leaf.shape, PARAM_RULES[name], name=name)
        return NamedSharding(mesh, P())

    return jax.tree_util.tree_map_with_path(rule, state_shape)
