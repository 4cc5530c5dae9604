"""GPT as pure functions over a parameter pytree.

TPU-first re-design of the reference model (/root/reference/mingpt/model.py:
GPTEmbedding :193-231, Block :171-189, MultiHeadSelfAttention :125-168,
GPT :234-356). The architecture matches the reference's *intent* — pre-LN
decoder-only transformer, learned token + (zero-init) learned positional
embeddings, 4x GELU MLP, final LayerNorm, bias-free LM head, N(0, 0.02) init
with GPT-2 residual-path scaling 0.02/sqrt(2L) — with the reference's latent
model bugs (B3-B6, B16: broken asserts, pos-embedding indexed by token value,
MLP activation after both linears, non-masking float causal mask) fixed by
construction, and the mechanism re-thought for XLA:

* the model is data — a pytree of arrays (float32, or ``cfg.param_dtype``) — and ``forward`` is a pure
  function, so sharding enters from *outside* via NamedSharding on the pytree
  (preserving the reference's parallelism-unaware-model layering, SURVEY §1-L2);
* per-layer parameters are stacked along a leading layer axis and the block
  is applied with ``lax.scan`` — one block compiled once, not n_layer copies
  unrolled, and ``jax.checkpoint`` (cfg.remat) slots in per scan step;
* activations run in cfg.dtype (bfloat16 on the MXU); normalisations, softmax
  and the loss run in float32;
* no (T, T) mask buffer per layer: causality is computed inside attention.

Llama-retrofit toggles (rope/swiglu/rmsnorm/GQA — BASELINE config #5) reuse
the same skeleton.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from mingpt_distributed_tpu.config import (
    FULL_ATTN, LIGHTNING, SPARSE, WINDOW_ATTN, GPTConfig)
from mingpt_distributed_tpu.ops import attention as attn_ops
from mingpt_distributed_tpu.ops import flash_attention
from mingpt_distributed_tpu.ops import lightning as lightning_ops
from mingpt_distributed_tpu.ops import sparse_attention as sparse_ops
from mingpt_distributed_tpu.ops import layers as L
from mingpt_distributed_tpu.ops import moe
from mingpt_distributed_tpu.parallel.mesh import BATCH_AXES

Params = Dict[str, Any]

#: where a hybrid stack's parameters lie: each mixer's layers stacked along
#: a leading axis under its own name, in the order ``cfg.mixer_layers`` gives
MIXER_STACKS = {LIGHTNING: "lightning_blocks", SPARSE: "sparse_blocks"}
#: where the attention of a stack of ``cfg.layer_types`` lies: each kind's
#: layers stacked under its own name, in the order ``cfg.kind_layers`` gives
#: (their query-head counts differ, so ``wq``, ``wo`` and the gate do). The
#: layers' MLPs lie in ``"dense_blocks"`` and ``"blocks"``, as any stack's
KIND_STACKS = {FULL_ATTN: "full_attn_blocks", WINDOW_ATTN: "window_attn_blocks"}


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------


def init(key: jax.Array, cfg: GPTConfig) -> Params:
    """Materialise the parameter pytree, in ``cfg.param_dtype``.

    Init scheme is the reference's (model.py:298-307, 252-256): weights
    N(0, 0.02), biases 0, LayerNorm identity, positional embedding zeros
    (model.py:209-214), residual-path projections N(0, 0.02/sqrt(2L)).
    Runs fine under jit with out_shardings so huge models can be born sharded.

    ``params["blocks"]`` stacks the layers of one kind along a leading
    axis. An expert model with ``n_dense_layers`` leading dense layers has
    two kinds: those lie in ``params["dense_blocks"]``, a stack of their
    own before the expert stack.
    """
    cfg.validate()
    d = cfg.n_embd
    dtype = jnp.dtype(cfg.param_dtype)

    keys = iter(jax.random.split(key, 32))
    std = 0.02
    resid_std = 0.02 / math.sqrt(2 * cfg.n_layer)

    def normal(k, shape, s=std):
        # drawn in float32 whatever the leaf's dtype: a bfloat16 leaf is
        # the float32 draw rounded, not another stream
        return (jax.random.normal(k, shape, dtype=jnp.float32) * s).astype(dtype)

    ones = lambda shape: jnp.ones(shape, dtype)
    zeros = lambda shape: jnp.zeros(shape, dtype)

    def stack(nl: int, experts: bool) -> Params:
        """``nl`` layers of one kind, stacked: the model's attention and a
        dense MLP or, under ``experts``, the routed one. In a stack of
        ``cfg.layer_types`` the attention lies apart (``kind_stack``)."""
        nh = cfg.n_head
        hd, kv = cfg.head_dim, cfg.kv_heads
        ffn = cfg.expert_width if experts else cfg.dense_width
        use_bias = not (cfg.swiglu or cfg.rmsnorm)  # GPT-2 mode has biases everywhere

        blocks: Params = {"ln2_scale": ones((nl, d))}
        if cfg.layer_types is None:
            blocks["ln1_scale"] = ones((nl, d))
        if cfg.post_norms:
            # drawn in [0.05, 0.2], not 1: seeded branches of unit size make
            # a stack of four norms a layer chaotic, and one that runs its
            # layers several times the more (benchmarks/README.md, "What
            # assumed.weights owes a deep or looped stack"); a trained
            # model's are learned
            for name in ("ln1_post_scale", "ln2_post_scale"):
                blocks[name] = jax.random.uniform(
                    next(keys), (nl, d), jnp.float32, 0.05, 0.2).astype(dtype)
        if cfg.kv_lora_rank:
            r, rope_d = cfg.kv_lora_rank, cfg.qk_rope_head_dim
            blocks.update(
                wq=normal(next(keys), (nl, d, nh * cfg.qk_head_dim)),
                # the latent and the shared rotary key, side by side
                w_kv_a=normal(next(keys), (nl, d, r + rope_d)),
                kv_norm_scale=ones((nl, r)),
                # per head [k_nope | v], as the published kv_b_proj lays them
                w_kv_b=normal(next(keys), (
                    nl, r, nh * (cfg.qk_nope_head_dim + cfg.v_head_dim))),
                wo=normal(next(keys), (nl, nh * cfg.v_head_dim, d), resid_std),
            )
        elif cfg.layer_types is None:
            blocks.update(
                wq=normal(next(keys), (nl, d, nh * hd)),
                wk=normal(next(keys), (nl, d, kv * hd)),
                wv=normal(next(keys), (nl, d, kv * hd)),
                wo=normal(next(keys), (nl, nh * hd, d), resid_std),
            )
        if not cfg.rmsnorm:
            blocks["ln1_bias"] = zeros((nl, d))
            blocks["ln2_bias"] = zeros((nl, d))
        if use_bias:
            blocks.update(
                bq=zeros((nl, nh * hd)),
                bk=zeros((nl, kv * hd)),
                bv=zeros((nl, kv * hd)),
                bo=zeros((nl, d)),
            )
        if experts:
            e = cfg.n_experts
            blocks["w_router"] = normal(next(keys), (nl, d, e))
            if cfg.moe_scoring == "sigmoid":
                # e_score_correction_bias: drawn, so that the choice it moves
                # is exercised (a trained model's balances the experts' load)
                blocks["e_bias"] = normal(next(keys), (nl, e))
            blocks.update(
                w_e1=normal(next(keys), (nl, e, d, ffn)),
                w_e2=normal(next(keys), (nl, e, ffn, d), resid_std),
            )
            if cfg.swiglu:  # Mixtral-style SwiGLU experts
                blocks["w_eg"] = normal(next(keys), (nl, e, d, ffn))
            if cfg.n_shared_experts:
                shared = cfg.n_shared_experts * ffn
                blocks.update(
                    w_sg=normal(next(keys), (nl, d, shared)),
                    w_su=normal(next(keys), (nl, d, shared)),
                    w_sd=normal(next(keys), (nl, shared, d), resid_std),
                )
        elif cfg.swiglu:
            blocks.update(
                w_gate=normal(next(keys), (nl, d, ffn)),
                w_up=normal(next(keys), (nl, d, ffn)),
                w_down=normal(next(keys), (nl, ffn, d), resid_std),
            )
        else:
            blocks.update(
                w_fc=normal(next(keys), (nl, d, ffn)),
                w_proj=normal(next(keys), (nl, ffn, d), resid_std),
            )
            if use_bias:
                blocks.update(b_fc=zeros((nl, ffn)), b_proj=zeros((nl, d)))
        return blocks

    def mixer_stack(kind: str) -> Params:
        """The layers of a hybrid stack that take mixer ``kind``, stacked:
        the mixer's projections, qk-norm weights and output gate, and the
        SwiGLU MLP every layer has. Norm weights are 1, also the lightning
        mixer's output norm; the gate is drawn, or it would gate by one
        half whatever its input."""
        nl = len(cfg.mixer_layers(kind))
        nh, kv, hd = cfg.mixer_heads(kind)
        blocks: Params = {
            "ln1_scale": ones((nl, d)), "ln2_scale": ones((nl, d)),
            "wq": normal(next(keys), (nl, d, nh * hd)),
            "wk": normal(next(keys), (nl, d, kv * hd)),
            "wv": normal(next(keys), (nl, d, kv * hd)),
            "wo": normal(next(keys), (nl, nh * hd, d), resid_std),
            "w_gate": normal(next(keys), (nl, d, cfg.dense_width)),
            "w_up": normal(next(keys), (nl, d, cfg.dense_width)),
            "w_down": normal(next(keys), (nl, cfg.dense_width, d), resid_std),
        }
        if cfg.qk_norm:
            blocks.update(q_norm_scale=ones((nl, hd)),
                          k_norm_scale=ones((nl, hd)))
        if cfg.output_gate:
            blocks["w_og"] = normal(next(keys), (nl, d, nh * hd))
        if kind == LIGHTNING:
            blocks["o_norm_scale"] = ones((nl, nh * hd))
        return blocks

    def kind_stack(kind: str) -> Params:
        """The attention of the layers of ``cfg.layer_types`` of ``kind``,
        stacked: the input norm, the projections for the kind's head count
        and the per-head gate, which is drawn (at zero it would gate by
        one half whatever its input, and a gate left out would not show)."""
        nl = len(cfg.kind_layers(kind))
        nh, kv, hd = cfg.kind_heads(kind)
        blocks: Params = {
            "ln1_scale": ones((nl, d)),
            "wq": normal(next(keys), (nl, d, nh * hd)),
            "wk": normal(next(keys), (nl, d, kv * hd)),
            "wv": normal(next(keys), (nl, d, kv * hd)),
            "wo": normal(next(keys), (nl, nh * hd, d), resid_std),
        }
        if cfg.head_gate:
            blocks["w_hg"] = normal(next(keys), (nl, d, nh))
        return blocks

    params: Params = {}
    if cfg.mixer_types is not None:
        for kind in (LIGHTNING, SPARSE):
            if cfg.mixer_layers(kind):
                params[MIXER_STACKS[kind]] = mixer_stack(kind)
    else:
        for kind in (FULL_ATTN, WINDOW_ATTN):
            if cfg.kind_layers(kind):
                params[KIND_STACKS[kind]] = kind_stack(kind)
        if cfg.n_dense_layers:
            params["dense_blocks"] = stack(cfg.n_dense_layers, experts=False)
        params["blocks"] = stack(cfg.n_layer - cfg.n_dense_layers,
                                 experts=bool(cfg.n_experts))
    params["wte"] = normal(next(keys), (cfg.vocab_size, d))
    params["lnf_scale"] = ones((d,))
    if not cfg.rope:
        params["wpe"] = zeros((cfg.block_size, d))
    if not cfg.rmsnorm:
        params["lnf_bias"] = zeros((d,))
    if not cfg.tie_weights:
        params["head"] = normal(next(keys), (d, cfg.vocab_size))
    if cfg.exit_gate:
        # the weight and the bias drawn: at zero the gate would read one
        # half whatever its input, and a gate left out would not show
        params["exit_gate_w"] = normal(next(keys), (d,))
        params["exit_gate_b"] = normal(next(keys), ())
    return params


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------


def _attention_dispatch(cfg: GPTConfig, mesh=None):
    """Select the attention implementation named by cfg.attention.

    "einsum" is the oracle (ops/attention.py). "flash" is the Pallas
    blockwise kernel (ops/flash_attention.py). "ring" is the
    sequence-parallel path (parallel/ring_attention.py) — it needs the mesh,
    which is the one piece of parallelism context that can't stay outside
    the model: the ring's collectives live inside attention itself.
    """
    if cfg.attention == "einsum":
        return attn_ops.causal_attention
    if cfg.attention == "flash":
        # under remat the layer's checkpoint keeps the forward's pair
        # (_remat), so the forward rule has to hand it on under its names
        attend = functools.partial(flash_attention.causal_attention,
                                   keep_pair=cfg.remat)
        if mesh is None:
            return attend

        # A compiled Pallas kernel is a Mosaic custom call, and a custom
        # call has no partitioning rule: left to GSPMD, q/k/v are gathered
        # and every chip runs the kernel over the whole global batch —
        # right answers, n_devices times the attention work. shard_map
        # makes the split explicit instead: each device runs the kernel on
        # its own batch rows (BATCH_AXES), and on its own heads when tp
        # divides them. Attention is independent per row and per head, so
        # the region holds no collective, and each shard is a whole
        # program whose packed-lane cells (ops/flash_attention._btd_pack)
        # the partitioner can never split. Heads tp does not divide stay
        # whole on every tp rank (gathered on entry: correct, redundant).
        from jax.sharding import PartitionSpec as PSpec

        tp = mesh.shape.get("tp", 1)
        heads_split = tp > 1 and cfg.n_head % tp == 0 and cfg.kv_heads % tp == 0
        spec = PSpec(BATCH_AXES, None, "tp" if heads_split else None)

        def flash_sharded(q, k, v, *, attn_pdrop=0.0, dropout_key=None,
                          deterministic=True, **kw):
            if not deterministic and attn_pdrop > 0.0:
                # no kernel under attention dropout: the op routes this
                # call to the einsum oracle, which is plain HLO — GSPMD
                # partitions it and draws the masks per global row
                return attend(
                    q, k, v, attn_pdrop=attn_pdrop, dropout_key=dropout_key,
                    deterministic=False, **kw)
            return jax.shard_map(
                lambda q, k, v: attend(q, k, v, **kw),
                mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
                check_vma=False,
            )(q, k, v)

        return flash_sharded
    if cfg.attention == "ring":
        from mingpt_distributed_tpu.parallel import ring_attention

        return lambda q, k, v, **kw: ring_attention.ring_causal_attention(
            q, k, v, mesh, **kw
        )
    if cfg.attention == "ulysses":
        from mingpt_distributed_tpu.parallel import ulysses

        return lambda q, k, v, **kw: ulysses.ulysses_causal_attention(
            q, k, v, mesh, **kw
        )
    raise NotImplementedError(f"attention={cfg.attention!r}")


def _manual_sp_attention(cfg: GPTConfig):
    """Per-shard sequence-parallel attention for use *inside* an enclosing
    shard_map region (the pipeline): the ring / Ulysses shard bodies run
    directly over the manual ``sp`` axis — their public wrappers would try
    to open a nested shard_map, which JAX forbids."""
    from mingpt_distributed_tpu.parallel import ring_attention, ulysses

    def fn(q, k, v, *, attn_pdrop=0.0, dropout_key=None, deterministic=True,
           window=None, logit_softcap=None):
        # attention dropout composes here too: the
        # shard bodies take (pdrop, key) directly and fold the chunk /
        # head-group index in, so every (pair, head) mask is drawn exactly
        # once. NOTE: under pp the enclosing body_pp has already folded the
        # sp/batch shard indices into the key, so unlike the public
        # wrappers the mask is NOT a pure function of the global pair id —
        # statistically identical dropout, but a dense oracle cannot
        # reproduce the masks blockwise here (it can for the public path,
        # see tests/test_ring_attention.py::..._matches_blockwise_oracle)
        drop = (not deterministic) and attn_pdrop > 0.0 \
            and dropout_key is not None
        h, hd = q.shape[2], q.shape[3]
        k2 = attn_ops.repeat_kv(k, h // k.shape[2])
        v2 = attn_ops.repeat_kv(v, h // v.shape[2])
        if cfg.attention == "ring":
            return ring_attention._ring_shard(
                q, k2, v2, axis_name="sp", scale=1.0 / math.sqrt(hd),
                window=window, softcap=logit_softcap,
                pdrop=attn_pdrop if drop else 0.0,
                key=dropout_key if drop else None,
            )
        return ulysses._ulysses_shard(q, k2, v2, axis_name="sp",
                                      window=window, softcap=logit_softcap,
                                      pdrop=attn_pdrop if drop else 0.0,
                                      key=dropout_key if drop else None)

    return fn


@jax.named_scope("norm")
def _norm(x, scale, bias, cfg: GPTConfig):
    if cfg.rmsnorm:
        return L.rms_norm(x, scale, eps=cfg.norm_eps)
    return L.layer_norm(x, scale, bias, eps=cfg.norm_eps)


@jax.named_scope("norm")
def sublayer_input(x, scale, bias, cfg: GPTConfig):
    """A sublayer's normed input in the compute dtype: the norm of the
    residual stream, rounded to ``cfg.dtype`` where the stream is carried in
    another (``cfg.residual_dtype``), so that every matmul runs in the
    compute dtype; its output then joins the stream by promotion."""
    h = _norm(x, scale, bias, cfg)
    return h.astype(cfg.dtype) if cfg.residual_dtype else h


def head_projection(h, w, b, turned: bool):
    """``L.dense`` of (B, T, D) activations whose (B, T, N) product the
    caller views per head next. In a decode step (one position a sequence,
    fewer rows than ``w`` has) whose caller then turns that view head by
    head (``turned``: a norm, a rotation), the product stands behind an
    ``optimization_barrier``. The TPU compiler's layout assignment wants a
    turned product heads-major, and where ``w`` is a slice of a stack, whose
    layout it may choose as it may not a parameter's, it buys that by
    writing every layer's weight out transposed, every step; behind the
    barrier the layout is the product's to pay for, ``rows / D`` of the
    weight's bytes, and the matmul reads the weight where it lies in the
    stack. A call of more positions (training, a prefill bucket) pays the
    weight's copy once for all its rows and keeps its program; a product
    nothing turns (GPT-2's) was never re-laid, and there a boundary can only
    cost."""
    y = L.dense(h, w, b)
    if turned and h.shape[1] == 1 and h.shape[0] < w.shape[0]:
        y = jax.lax.optimization_barrier(y)
    return y


def head_boundaries(jaxpr) -> int:
    """How many projections of a traced program took ``head_projection``'s
    boundary: the ``optimization_barrier`` equations, at any depth, that
    stand on a matmul's product or on that product plus its bias (a barrier
    over anything else, a block of cached rows, is another mechanism's)."""
    made_by, found = {}, 0
    for eqn in jaxpr.eqns:
        made_by.update(dict.fromkeys(eqn.outvars, eqn))
        if eqn.primitive.name == "optimization_barrier":
            maker = made_by.get(eqn.invars[0])
            if maker is not None and maker.primitive.name == "add":
                maker = made_by.get(maker.invars[0])
            found += maker is not None and maker.primitive.name == "dot_general"
        for inner in jax.core.jaxprs_in_params(eqn.params):
            found += head_boundaries(inner)
    return found


@jax.named_scope("qkv")
def latent_parts(h, blk: Params, cfg: GPTConfig, rope):
    """What latent attention makes of (B, T, D) normed activations before
    it attends, in either form: the queries' nope part (B, T, H, nope) and
    rotated rope part (B, T, H, e), and the two things a token caches, each
    shared by all heads: the normed latent (B, T, 1, r) and the rotated
    rope key (B, T, 1, e)."""
    b, t, _ = h.shape
    r, nope = cfg.kv_lora_rank, cfg.qk_nope_head_dim
    cos, sin = rope
    q = head_projection(h, blk["wq"], None, turned=True).reshape(
        b, t, cfg.n_head, cfg.qk_head_dim)
    q_pe = attn_ops.apply_rope(q[..., nope:], cos, sin, cfg.rope_interleave)
    kv_a = L.dense(h, blk["w_kv_a"])
    latent = L.rms_norm(kv_a[..., None, :r], blk["kv_norm_scale"],
                        eps=cfg.norm_eps)
    k_pe = attn_ops.apply_rope(kv_a[..., None, r:], cos, sin,
                               cfg.rope_interleave)
    return q[..., :nope], q_pe, latent, k_pe


@jax.named_scope("qkv")
def latent_qkv(h, blk: Params, cfg: GPTConfig, rope):
    """Latent attention as published, not absorbed: (B, T, D) normed
    activations -> per-head q, k (B, T, H, nope + rope) and v (B, T, H,
    v_head_dim). The latent is taken up through ``w_kv_b`` to every head's
    own key part and value; the one rotated rope key is shared by all
    heads. The cached forward never builds these (it attends the latents:
    generate._cached_block); this is the form the uncached forward and
    training take, and the one the cached form is held to."""
    b, t, _ = h.shape
    nh, nope = cfg.n_head, cfg.qk_nope_head_dim
    q_nope, q_pe, latent, k_pe = latent_parts(h, blk, cfg, rope)
    kv_b = L.dense(latent[:, :, 0], blk["w_kv_b"]).reshape(
        b, t, nh, nope + cfg.v_head_dim)
    k = jnp.concatenate([kv_b[..., :nope], jnp.broadcast_to(
        k_pe, (b, t, nh, cfg.qk_rope_head_dim))], -1)
    return jnp.concatenate([q_nope, q_pe], -1), k, kv_b[..., nope:]


#: the routed experts' leaves: the cached forward hands ``routed_and_shared``
#: the stack's, unsliced, with the layer's index (ops/moe.grouped_swiglu)
EXPERT_LEAVES = ("w_eg", "w_e1", "w_e2")


def _route_options(cfg: GPTConfig) -> dict:
    return dict(top_k=cfg.moe_top_k, norm_topk=cfg.moe_norm_topk,
                route_scale=cfg.moe_route_scale, scoring=cfg.moe_scoring)


def early_route(h, blk: Params, cfg: GPTConfig):
    """A dropless expert layer's route where the router reads the
    attention's normed input ``h`` (B, T, D) (``cfg.moe_router_input``
    "attn"): (chosen, gates) of ``ops/moe.dropless_routes``, made before the
    attention so that a token's experts are known while it runs, under the
    scope ``moe_route``; None for any other layer, whose route
    ``routed_and_shared`` makes from the MLP's input."""
    if cfg.moe_router_input != "attn" or "w_router" not in blk:
        return None
    with jax.named_scope("moe_route"):
        return moe.dropless_routes(
            h.reshape(-1, h.shape[-1]), blk["w_router"], blk.get("e_bias"),
            **_route_options(cfg))


def routed_and_shared(h2, blk: Params, cfg: GPTConfig, valid=None,
                      layer=None, route=None):
    """The MLP of a dropless expert layer: each token's k routed experts
    (ops/moe.moe_dropless, the choice and the gates ``cfg.moe_scoring``'s,
    the gate activation ``cfg.expert_act``'s) plus the shared expert every
    token takes. ``route``: :func:`early_route`'s, where the router read
    the attention's input; None: made here from ``h2``. Returns (out, the
    route's counts). With ``layer``, the EXPERT_LEAVES of ``blk`` are the
    whole stack's."""
    m, counts = moe.moe_dropless(
        h2, blk["w_router"], blk.get("e_bias"), blk["w_eg"], blk["w_e1"],
        blk["w_e2"], valid=valid, layer=layer, route=route,
        act=cfg.expert_act, **_route_options(cfg))
    if "w_sg" in blk:
        with jax.named_scope("moe_shared"):
            m = m + L.mlp_swiglu(h2, blk["w_sg"], blk["w_su"], blk["w_sd"])
    return m, counts


def rotated(q, k, rope, cfg: GPTConfig):
    """Per-head queries and keys turned by the caller's ``(cos, sin)``."""
    cos, sin = rope
    return (attn_ops.apply_rope(q, cos, sin, cfg.rope_interleave),
            attn_ops.apply_rope(k, cos, sin, cfg.rope_interleave))


def layer_rope(cfg: GPTConfig, kind: Optional[str], positions):
    """The ``(cos, sin)`` tables a layer of ``kind`` rotates by at
    ``positions`` (``cfg.rope_spec``: the dimensions of a head it turns,
    which the tables' width says to ``apply_rope``; theta; YaRN's blend
    where the kind has one); None for a kind that rotates nothing, whose
    queries and keys ``attention_parts`` then leaves as projected."""
    dim, theta, yarn = cfg.rope_spec(kind)
    if not dim:
        return None
    if yarn is not None:
        return attn_ops.yarn_rope_tables(positions, dim, theta, *yarn)
    return attn_ops.rope_tables(positions, dim, theta)


def kind_layer_params(params: Params, cfg: GPTConfig, layer: int, whole=()):
    """A layer of a stack of ``cfg.layer_types``: (its kind of attention,
    its parameters, its place among that kind's layers, its place in its
    MLP's stack). The attention comes out of its kind's stack
    (KIND_STACKS), the MLP out of ``"dense_blocks"`` or ``"blocks"``; the
    leaves named in ``whole`` stay the stack's (``routed_and_shared``)."""
    kind = cfg.layer_types[layer]
    at = cfg.kind_layers(kind).index(layer)
    stack, mlp_at = (params["dense_blocks"], layer) \
        if layer < cfg.n_dense_layers \
        else (params["blocks"], layer - cfg.n_dense_layers)
    blk = {n: a[at] for n, a in params[KIND_STACKS[kind]].items()}
    blk.update({n: a if n in whole else a[mlp_at] for n, a in stack.items()})
    return kind, blk, at, mlp_at


@jax.named_scope("qkv")
def attention_parts(h, blk: Params, cfg: GPTConfig, heads, rope=None):
    """What per-head attention makes of (B, T, D) normed activations before
    it attends, the one place the projections are written (``latent_parts``
    is the latent's form of it): q (B, T, H, hd) and k, v (B, T, KV, hd) for
    the caller's ``heads`` = (H, KV, hd) (a manual-``tp`` shard passes its
    own), queries and keys RMS-normed per head where ``blk`` carries the
    scales (``qk_norm``), then rotated by ``rope`` (None: not rotated)."""
    b, t, _ = h.shape
    nh, kv, hd = heads
    turned = rope is not None or "q_norm_scale" in blk
    q, k, v = (
        head_projection(h, blk[w], blk.get(bias), turned).reshape(b, t, n, hd)
        for w, bias, n in (("wq", "bq", nh), ("wk", "bk", kv),
                           ("wv", "bv", kv)))
    if "q_norm_scale" in blk:
        q = L.rms_norm(q, blk["q_norm_scale"], eps=cfg.norm_eps)
        k = L.rms_norm(k, blk["k_norm_scale"], eps=cfg.norm_eps)
    if rope is not None:
        q, k = rotated(q, k, rope, cfg)
    return q, k, v


def _row_parallel(x, w, b, tp_axis: Optional[str]):
    """``L.dense``, its matmul summed over ``tp_axis`` (the manual megatron
    recipe's one collective a branch) before the bias joins, so that the
    bias is not multiplied by ``tp``."""
    y = L.dense(x, w)
    if tp_axis is not None:
        y = jax.lax.psum(y, tp_axis)
    return y if b is None else y + b.astype(y.dtype)


@jax.named_scope("attn_out")
def attention_out(att, blk: Params, cfg: GPTConfig,
                  tp_axis: Optional[str] = None, h=None):
    """Attention's (B, T, H * hd) output on its way back to the stream:
    where ``blk`` carries a per-head gate (``cfg.head_gate``) each head's
    part times ``sigmoid(h W_g)`` of the layer's normed input ``h``, the
    sigmoid in float32; then through ``wo`` and, under ``cfg.post_norms``,
    its RMS norm."""
    if "w_hg" in blk:
        b, t, _ = att.shape
        gate = jax.nn.sigmoid(jnp.dot(
            h, blk["w_hg"].astype(h.dtype),
            preferred_element_type=jnp.float32))            # (B, T, H)
        att = (gate[..., None] * att.reshape(b, t, gate.shape[-1], -1)
               ).astype(att.dtype).reshape(b, t, -1)
    att = _row_parallel(att, blk["wo"], blk.get("bo"), tp_axis)
    if cfg.post_norms:
        with jax.named_scope("norm"):
            att = L.rms_norm(att, blk["ln1_post_scale"], eps=cfg.norm_eps)
    return att


@jax.named_scope("ffn")
def mlp_branch(h2, blk: Params, cfg: GPTConfig, *, valid=None, layer=None,
               lanes_apart: bool = False, tp_axis: Optional[str] = None,
               ep_axis: Optional[str] = None, route=None):
    """A layer's MLP over (B, T, D) normed activations, whichever it has,
    and its post-norm: (the branch, the capacity route's load-balancing
    term (zero for any other), a dropless route's counts of the ``valid``
    tokens' rows (``routed_and_shared``, which ``layer`` and ``route`` are
    for; None for any other)). ``lanes_apart``: the rows are other users'
    requests, so the capacity route, where a token's room depends on who
    else is routed, takes each alone. ``tp_axis``, ``ep_axis``: ``_block``'s manual forms."""
    aux, counts = jnp.zeros((), jnp.float32), None
    if "w_router" in blk and cfg.dropless:
        m, counts = routed_and_shared(h2, blk, cfg, valid, layer, route)
    elif "w_router" in blk:
        def experts(tokens):
            return moe.moe_mlp(
                tokens, blk["w_router"], blk["w_e1"], blk["w_e2"],
                top_k=cfg.moe_top_k, capacity_factor=cfg.moe_capacity_factor,
                w_gate=blk.get("w_eg"), ep_axis=ep_axis)

        if lanes_apart:
            m = jax.vmap(lambda lane: experts(lane[None])[0][0])(h2)
        else:
            m, aux = experts(h2)
    elif cfg.swiglu and tp_axis is not None:
        # gate and up each rounded to the compute dtype, where L.mlp_swiglu
        # rounds their product once: kept until a cell runs pp x tp
        # (ROADMAP.md, Design)
        inner = jax.nn.silu(L.dense(h2, blk["w_gate"])) \
            * L.dense(h2, blk["w_up"])
        m = _row_parallel(inner, blk["w_down"], None, tp_axis)
    elif cfg.swiglu:
        m = L.mlp_swiglu(h2, blk["w_gate"], blk["w_up"], blk["w_down"])
    elif tp_axis is not None:
        inner = L.gelu(L.dense(h2, blk["w_fc"], blk.get("b_fc")))
        m = _row_parallel(inner, blk["w_proj"], blk.get("b_proj"), tp_axis)
    else:
        m = L.mlp_gelu(h2, blk["w_fc"], blk.get("b_fc"), blk["w_proj"],
                       blk.get("b_proj"))
    if cfg.post_norms:
        with jax.named_scope("norm"):
            m = L.rms_norm(m, blk["ln2_post_scale"], eps=cfg.norm_eps)
    return m, aux, counts


def hybrid_layer_params(params: Params, cfg: GPTConfig, layer: int):
    """(the layer's mixer, its parameters sliced out of its mixer's stack,
    its place among that mixer's layers)."""
    kind = cfg.mixer_types[layer]
    at = cfg.mixer_layers(kind).index(layer)
    return kind, {n: a[at] for n, a in params[MIXER_STACKS[kind]].items()}, at


@jax.named_scope("qkv")
def mixer_qkv(u, blk: Params, cfg: GPTConfig, kind: str, positions):
    """``attention_parts`` for a hybrid layer's mixer and its head counts:
    queries and keys RMS-normed per head (``qk_norm``) and, in a lightning
    layer, rotated to ``positions`` ((T,) or (B, T)) by tables of its own
    head size. A sparse layer's keys are not rotated: it caches them normed."""
    heads = cfg.mixer_heads(kind)
    q, k, v = attention_parts(u, blk, cfg, heads)
    if kind == LIGHTNING:
        q, k = rotated(q, k, attn_ops.rope_tables(
            positions, heads[2], cfg.rope_theta), cfg)
    return q, k, v


def sparse_rows(q, k, v):
    """A sparse layer's per-head q, k, v as ``ops/sparse_attention`` takes
    them and the cache keeps them: keys and values with their KV heads side
    by side, (B, T, 1, KV * hd), and the queries spread to that width."""
    b, t, kv, hd = k.shape
    return (sparse_ops.spread_queries(q, kv), k.reshape(b, t, 1, kv * hd),
            v.reshape(b, t, 1, kv * hd))


@jax.named_scope("attn_out")
def mixer_out(mixed, u, blk: Params, cfg: GPTConfig, kind: str):
    """A mixer's (B, T, H, hd) output to the residual stream's width: the
    lightning mixer's output norm, the gate ``sigmoid(W_g u)``, then W_o."""
    b, t = mixed.shape[:2]
    mixed = mixed.reshape(b, t, -1).astype(u.dtype)
    if kind == LIGHTNING:
        mixed = L.rms_norm(mixed, blk["o_norm_scale"], eps=cfg.norm_eps)
    if cfg.output_gate:
        gate = jnp.dot(u, blk["w_og"].astype(u.dtype),
                       preferred_element_type=jnp.float32)
        mixed = (jax.nn.sigmoid(gate) * mixed).astype(u.dtype)
    return L.dense(mixed, blk["wo"])


def lightning_mixer(u, blk: Params, cfg: GPTConfig, positions, state,
                    valid=None, step: bool = False):
    """A lightning layer's mixer over (B, T, D) normed activations from
    ``state`` (B, H, hd, hd) float32: (the mixer's output (B, T, D), the
    state after the last valid token). ``step``: one position a lane, the
    recurrence as written; else the chunked scan."""
    q, k, v = mixer_qkv(u, blk, cfg, LIGHTNING, positions)
    run = lightning_ops.lightning_step if step else lightning_ops.lightning_scan
    mixed, state = run(q, k, v, state, lightning_ops.slopes(cfg.lightning_heads),
                       cfg.lightning_head_dim ** -0.5, valid)
    return mixer_out(mixed, u, blk, cfg, LIGHTNING), state


def zero_lightning_state(cfg: GPTConfig, batch: int) -> jax.Array:
    return jnp.zeros((batch, cfg.lightning_heads, cfg.lightning_head_dim,
                      cfg.lightning_head_dim), jnp.float32)


def hybrid_mlp(x, mixed, blk: Params, cfg: GPTConfig):
    """The rest of a hybrid layer: the mixer's branch and the SwiGLU MLP's
    onto the residual stream, each times ``cfg.residual_scale``."""
    scale = cfg.residual_scale
    # a sum stands under the mark of the part it takes in: fused with that
    # part's last matmul, the sum is the fusion's root
    with jax.named_scope("attn_out"):
        x = x + (scale * mixed).astype(x.dtype)
    with jax.named_scope("norm"):
        h2 = L.rms_norm(x, blk["ln2_scale"], eps=cfg.norm_eps)
    m, _, _ = mlp_branch(h2, blk, cfg)
    with jax.named_scope("ffn"):
        return x + (scale * m).astype(x.dtype)


def _hybrid_block(x, blk: Params, cfg: GPTConfig, kind: str) -> jax.Array:
    """One layer of a hybrid stack over a whole sequence from its start,
    nothing cached: the form training and the uncached forward take, and
    the one the cached forms are held to."""
    b, t, _ = x.shape
    with jax.named_scope("norm"):
        u = L.rms_norm(x, blk["ln1_scale"], eps=cfg.norm_eps)
    positions = jnp.arange(t)
    if kind == LIGHTNING:
        mixed, _ = lightning_mixer(u, blk, cfg, positions,
                                   zero_lightning_state(cfg, b))
    else:
        sizes = sparse_ops.SparseSizes.of(cfg)
        q, k, v = sparse_rows(*mixer_qkv(u, blk, cfg, SPARSE, positions))
        pad = ((0, 0), (0, -t % sizes.block), (0, 0), (0, 0))
        k, v = jnp.pad(k, pad), jnp.pad(v, pad)   # whole blocks of rows
        q_pos = jnp.broadcast_to(positions, (b, t))
        chosen = sparse_ops.select_blocks(
            q, sparse_ops.pooled_keys(k, sizes), q_pos, sizes, cfg.kv_heads)
        mixed = mixer_out(
            sparse_ops.sparse_attend(q, k, v, chosen, q_pos, sizes)[0],
            u, blk, cfg, SPARSE)
    return hybrid_mlp(x, mixed, blk, cfg)


def _remat(fn):
    """``fn`` under ``cfg.remat``: its backward runs the layer's forward
    again, but for the two values the flash forward names (its output and
    its log-sum-exp: ``_attention_dispatch`` asks it to, ``keep_pair``),
    which are kept: q, k and v come back from one matmul each, and that
    pair only from the kernel, which so runs once a step. A body that names
    nothing (the einsum attention, a hybrid layer, a ring) keeps nothing
    but its inputs."""
    return jax.checkpoint(
        fn, policy=jax.checkpoint_policies.save_only_these_names(
            flash_attention.SAVED_OUT, flash_attention.SAVED_LSE))


def _block(
    x: jax.Array,
    blk: Params,
    cfg: GPTConfig,
    rope: Optional[Tuple[jax.Array, jax.Array]],
    drop_key: Optional[jax.Array],
    deterministic: bool,
    mesh=None,
    attn_fn=None,  # override (e.g. manual sp attention inside the pipeline)
    tp_axis: Optional[str] = None,  # manual megatron-tp inside shard_map
    ep_axis: Optional[str] = None,  # manual expert parallelism in shard_map
    kind: Optional[str] = None,  # the layer's kind in a stack of layer_types
) -> Tuple[jax.Array, jax.Array]:
    """One pre-LN transformer block over a whole sequence: norm, parts,
    attend, out, add; norm, MLP, add. The residual sums, dropout and the
    ``attn`` / ``mlp`` scopes are this body's; the rest is the functions
    ``generate._cached_block`` calls too. Returns (x, aux): aux is the MoE
    load-balancing loss for this layer (zero for dense MLPs), accumulated
    across layers by the caller.

    ``tp_axis`` (inside an enclosing shard_map, e.g. the pipeline) runs the
    megatron recipe manually: this shard's weights hold n_head/tp heads and
    ffn/tp columns (column-parallel in, row-parallel out), activations stay
    replicated over tp, and the only tp collectives are one psum per
    residual branch (``_row_parallel``)."""
    b, t, _ = x.shape
    nh, kv, hd = cfg.kind_heads(kind)
    if tp_axis is not None:
        assert not cfg.n_experts, "tp_axis doesn't compose with MoE blocks"
        tp_n = jax.lax.psum(1, tp_axis)
        nh, kv = nh // tp_n, kv // tp_n
    if drop_key is not None:
        k_attn, k_resid1, k_resid2 = jax.random.split(drop_key, 3)
        if tp_axis is not None:
            # attention dropout acts on this shard's local heads — fold the
            # shard index in so head h of shard j draws a different mask
            # than head h of shard 0 (residual dropout keys must stay
            # replicated: those activations are identical across tp)
            k_attn = jax.random.fold_in(k_attn, jax.lax.axis_index(tp_axis))
    else:
        k_attn = k_resid1 = k_resid2 = None

    with jax.named_scope("attn"):
        h = sublayer_input(x, blk["ln1_scale"], blk.get("ln1_bias"), cfg)
        route = early_route(h, blk, cfg)
        if cfg.kv_lora_rank:
            q, k, v = latent_qkv(h, blk, cfg, rope)
        else:
            q, k, v = attention_parts(h, blk, cfg, (nh, kv, hd), rope)
        # window/softcap compose with every attention impl, including the
        # manual-sp attn_fn override inside pipeline stages
        attn_kw = {}
        if cfg.kind_window(kind):
            attn_kw["window"] = cfg.kind_window(kind)
        if cfg.attn_logit_softcap:
            attn_kw["logit_softcap"] = cfg.attn_logit_softcap
        if kind is not None:
            # a chunk of thousands of positions, its scores never whole
            att = attn_ops.banded_attention(
                q, k, v, q_start=0, **attn_kw).reshape(b, t, -1)
        else:
            att = (attn_fn or _attention_dispatch(cfg, mesh))(
                q, k, v, attn_pdrop=cfg.attn_pdrop, dropout_key=k_attn,
                deterministic=deterministic, **attn_kw).reshape(b, t, -1)
        att = attention_out(att, blk, cfg, tp_axis, h)
        x = x + L.dropout(att, cfg.resid_pdrop, k_resid1, deterministic)

    with jax.named_scope("mlp"):
        h2 = sublayer_input(x, blk["ln2_scale"], blk.get("ln2_bias"), cfg)
        m, aux, _ = mlp_branch(h2, blk, cfg, tp_axis=tp_axis, ep_axis=ep_axis,
                               route=route)
        return x + L.dropout(m, cfg.resid_pdrop, k_resid2, deterministic), aux


@jax.named_scope("exit_gate")
def exit_gate_logits(params: Params, h: jax.Array) -> jax.Array:
    """The exit gate's logits over a pass's normed output: (..., D) ->
    (...,) float32, ``w . h + b`` summed in float32 (the gate is one of the
    things a bfloat16 model keeps there)."""
    return jnp.einsum(
        "...d,d->...", h.astype(jnp.float32),
        params["exit_gate_w"].astype(jnp.float32),
    ) + params["exit_gate_b"].astype(jnp.float32)


@jax.named_scope("exit_gate")
def exit_mass(gate_logits: jax.Array) -> jax.Array:
    """What exits at each pass: ``gate_logits`` (R, ...) float32, a pass
    first -> ``p`` (R, ...), ``p_t = g_t * prod_{j<t}(1 - g_j)`` with ``g =
    sigmoid(logits)``, the last pass taking what is left, so that the passes
    sum to 1."""
    g = jax.nn.sigmoid(gate_logits)
    stay = jnp.cumprod(1.0 - g, axis=0)                  # prod_{j<=t}
    before = jnp.concatenate([jnp.ones_like(stay[:1]), stay[:-1]])
    return jnp.concatenate([(g * before)[:-1], before[-1:]])


def forward(
    params: Params,
    tokens: jax.Array,  # (B, T) int32
    cfg: GPTConfig,
    *,
    targets: Optional[jax.Array] = None,  # (B, T) int32, -1 = ignore
    rng: Optional[jax.Array] = None,
    deterministic: bool = True,
    mesh=None,  # required only for attention="ring" (see _attention_dispatch)
    return_logits: bool = True,
    return_gates: bool = False,
) -> Tuple[Optional[jax.Array], Optional[jax.Array]]:
    """Full forward pass -> (logits (B, T, V) float32, loss or None).

    Same contract as the reference's GPT.forward (model.py:309-320): returns
    logits always, plus mean cross-entropy over targets != -1 when targets
    are given. ``return_logits=False`` (the trainer's loss-only mode)
    returns ``(None, loss)`` and — when ``cfg.loss_chunks`` applies — never
    materialises the (B, T, V) logits at all: the LM head + softmax run per
    sequence chunk, and under differentiation a chunk's gradient is taken
    in the same sweep (see chunked_cross_entropy).

    A looped stack (``cfg.n_passes`` > 1) runs its layers that many times
    over the one set of weights; the final norm closes every pass, and its
    output is what the next pass starts from and what the exit gate and the
    head read. ``return_gates`` adds a third result, the exit gate's
    float32 logits a pass (n_passes, B, T) (``cfg.exit_gate``); at the one
    threshold that is built they move no logit.
    """
    b, t = tokens.shape
    if t > cfg.block_size:  # static shape — checked at trace time (B3 intent)
        raise ValueError(f"sequence length {t} > block_size {cfg.block_size}")
    if not deterministic and rng is None:
        raise ValueError("training-mode forward needs rng for dropout")
    if cfg.n_passes > 1 and (targets is not None or not deterministic):
        raise NotImplementedError(
            "a looped stack (n_passes > 1) is served, not trained: its "
            "family's objective is an expected loss over the exits with an "
            "entropy term, which no published config states, and the last "
            "pass's cross-entropy under its name would be a guess")
    if return_gates and not cfg.exit_gate:
        raise ValueError("return_gates needs cfg.exit_gate")

    with jax.named_scope("embed"):
        x = params["wte"][tokens]  # (B, T, D) fp32 gather
        if not cfg.rope:
            # slice by *position*, add (the B4 fix: reference indexed pos
            # table by token values and called a Parameter)
            x = x + params["wpe"][:t]
        if deterministic:
            emb_key = None
        else:
            rng, emb_key = jax.random.split(rng)
        x = L.dropout(x, cfg.embd_pdrop, emb_key, deterministic)
        if cfg.scale_emb != 1.0:
            x = x * cfg.scale_emb
        x = x.astype(cfg.stream_dtype)

    if cfg.mixer_types is not None:
        if mesh is not None and mesh.shape.get("pp", 1) > 1:
            raise NotImplementedError(
                "pipeline stages split one stack of like layers: a hybrid "
                "stack (mixer_types) has one kind a layer")
        if not deterministic and (cfg.resid_pdrop or cfg.attn_pdrop):
            raise NotImplementedError(
                "a hybrid stack's layers are written without dropout: set "
                "resid_pdrop and attn_pdrop to 0")
        for layer in range(cfg.n_layer):
            kind, blk, _ = hybrid_layer_params(params, cfg, layer)
            step = functools.partial(_hybrid_block, cfg=cfg, kind=kind)
            x = (_remat(step) if cfg.remat else step)(x, blk)
        return _head_and_loss(params, x, cfg, targets, return_logits,
                              jnp.zeros((), jnp.float32), mesh=mesh)

    if cfg.layer_types is not None:
        if mesh is not None and (mesh.shape.get("pp", 1) > 1
                                 or mesh.shape.get("tp", 1) > 1):
            raise NotImplementedError(
                "a stack of layer_types is not split over pp or tp: its "
                "kinds of layer have unlike stacks and head counts, and no "
                "rule shards the two of them")
        if not deterministic:
            raise NotImplementedError(
                "a stack of layer_types is served and evaluated, not "
                "trained: its layers are written without dropout and its "
                "attention walks its band in a loop no backward is written "
                "for")
        positions = jnp.arange(t)
        for layer in range(cfg.n_layer):
            kind, blk, _, _ = kind_layer_params(params, cfg, layer)
            # the dropless route has no load-balancing term
            x, _ = _block(x, blk, cfg, layer_rope(cfg, kind, positions),
                          None, True, kind=kind)
        return _head_and_loss(params, x, cfg, targets, return_logits,
                              jnp.zeros((), jnp.float32), mesh=mesh)

    rope = None
    if cfg.rope:
        rope = attn_ops.rope_tables(jnp.arange(t), cfg.rope_dim, cfg.rope_theta)

    nl = cfg.n_layer
    n_dense = cfg.n_dense_layers
    if deterministic:
        def body(carry, blk):
            xc, aux = carry
            y, a = _block(xc, blk, cfg, rope, None, True, mesh)
            return (y, aux + a), None
        xs = params["blocks"]
        xs_dense = params.get("dense_blocks")
    else:
        layer_keys = jax.random.split(rng, nl)
        def body(carry, scanned):
            blk, key = scanned
            xc, aux = carry
            y, a = _block(xc, blk, cfg, rope, key, False, mesh)
            return (y, aux + a), None
        xs = (params["blocks"], layer_keys[n_dense:])
        xs_dense = (params.get("dense_blocks"), layer_keys[:n_dense])

    step = _remat(body) if cfg.remat else body

    def run_stack(carry, xs, n):
        """``n`` stacked layers of one kind over the carry."""
        if cfg.unroll_layers:
            # statically unrolled layer loop: same body (incl. remat
            # wrapping), but per-layer params/keys are static slices: no
            # scan carry, no dynamic-update-slice stacking of saved
            # activations (see config.unroll_layers)
            for i in range(n):
                carry, _ = step(carry, jax.tree.map(lambda a: a[i], xs))
            return carry
        return jax.lax.scan(step, carry, xs, unroll=cfg.scan_unroll)[0]

    if mesh is not None and mesh.shape.get("pp", 1) > 1 and n_dense:
        raise NotImplementedError(
            "pipeline stages split one stack of like layers: a model with "
            "leading dense layers (n_dense_layers) has two")
    if mesh is not None and mesh.shape.get("pp", 1) > 1 and cfg.closes_passes:
        raise NotImplementedError(
            "pipeline stages run their layers once: a looped stack "
            "(n_passes > 1) or an exit gate is not written for them")
    gates = []
    if mesh is not None and mesh.shape.get("pp", 1) > 1:
        # pipeline stages over the pp axis (parallel/pipeline.py): the same
        # scanned block, applied to each stage's layer shard per microbatch.
        # rope tables travel as explicit replicated consts — shard_map must
        # see every traced value it uses.
        from mingpt_distributed_tpu.parallel import pipeline

        sp = mesh.shape.get("sp", 1)
        seq_sharded = cfg.attention in ("ring", "ulysses") and sp > 1
        if seq_sharded:
            # inside the manual region there is no oracle fallback, so the
            # shard bodies' applicability conditions become hard errors
            # (attention dropout is supported: _manual_sp_attention routes
            # it to the shard bodies' einsum/dense-local dropped paths)
            if t % sp:
                raise ValueError(f"T={t} not divisible by sp={sp} under pp")
            # (ulysses head-divisibility is checked below, tp-aware)
        # ep x pp: expert leaves (w_e*) keep their ep
        # sharding through xs_specs; the MoE runs manual expert parallelism
        # inside the region (two all_to_alls over ep — ops/moe.py ep_axis)
        ep_n = mesh.shape.get("ep", 1)
        ep_manual = bool(cfg.n_experts) and ep_n > 1
        if ep_manual and cfg.n_experts % ep_n:
            raise ValueError(
                f"n_experts={cfg.n_experts} not divisible by ep={ep_n}"
            )
        manual_attn = _manual_sp_attention(cfg) if seq_sharded else None

        # --- keep tp/fsdp sharding LIVE inside the pipeline region --------
        # Megatron-tp is run manually when every split dimension divides;
        # otherwise tp falls back to gathered (replicated) stage params.
        # fsdp stays sharded per-leaf regardless and is all-gathered
        # per *layer* inside the scan (ZeRO-3-style JIT gather: one layer's
        # params live at a time; remat re-gathers in backward).
        tp_n = mesh.shape.get("tp", 1)
        ffn_dim = cfg.dense_width
        tp_manual = (
            tp_n > 1
            and not cfg.n_experts
            and cfg.n_head % tp_n == 0
            and cfg.kv_heads % tp_n == 0
            and ffn_dim % tp_n == 0
        )
        if cfg.attention == "ulysses" and seq_sharded:
            local_heads = cfg.n_head // tp_n if tp_manual else cfg.n_head
            if local_heads % sp:
                raise ValueError(
                    f"ulysses needs (n_head/tp) % sp == 0 "
                    f"(got {local_heads} % {sp})"
                )
        from mingpt_distributed_tpu.parallel import mesh as mesh_lib

        def leaf_spec(path, leaf):
            from jax.sharding import PartitionSpec as PSpec

            rule = mesh_lib.PARAM_RULES[mesh_lib.leaf_name(path)]
            if not tp_manual:  # drop tp: apply_stack runs dense math
                rule = PSpec(*(
                    None if ax == "tp" else ax for ax in rule
                ))
            return mesh_lib.shard_by_rule(mesh, leaf.shape, rule).spec

        blocks_specs = jax.tree_util.tree_map_with_path(
            leaf_spec, params["blocks"]
        )
        name_to_spec = {}
        jax.tree_util.tree_map_with_path(
            lambda path, s: name_to_spec.setdefault(
                mesh_lib.leaf_name(path), s
            ),
            blocks_specs,
        )
        xs_specs = (
            blocks_specs if deterministic
            else (blocks_specs, jax.sharding.PartitionSpec("pp"))
        )

        def gather_fsdp(blk):
            """All-gather ONE layer's params over fsdp at point of use
            (leading layer axis already consumed by the scan)."""

            def g(path, leaf):
                spec = name_to_spec[mesh_lib.leaf_name(path)]
                for dim, ax in enumerate(spec[1:]):  # [0] = layer axis
                    if ax == "fsdp":
                        return jax.lax.all_gather(
                            leaf, "fsdp", axis=dim, tiled=True
                        )
                return leaf

            return jax.tree_util.tree_map_with_path(g, blk)

        def apply_stack(x_mb, xs_local, consts, mb_idx):
            if cfg.rope:
                cos, sin = consts
                if seq_sharded:
                    # this shard's rows of the (global-T) rope tables
                    c = x_mb.shape[1]
                    i0 = jax.lax.axis_index("sp") * c
                    cos = jax.lax.dynamic_slice_in_dim(cos, i0, c)
                    sin = jax.lax.dynamic_slice_in_dim(sin, i0, c)
                rope_c = (cos, sin)
            else:
                rope_c = None

            def run(carry, blk, key):
                xc, aux = carry
                blk = gather_fsdp(blk)
                y, a = _block(xc, blk, cfg, rope_c, key, deterministic,
                              attn_fn=manual_attn,
                              tp_axis="tp" if tp_manual else None,
                              ep_axis="ep" if ep_manual else None)
                return (y, aux + a)

            if deterministic:
                def body_pp(carry, blk):
                    return run(carry, blk, None), None
            else:
                def body_pp(carry, scanned):
                    blk, key = scanned
                    # decorrelate dropout across microbatches: the same
                    # layer key is applied to every microbatch otherwise
                    key = jax.random.fold_in(key, mb_idx)
                    # ...and across batch shards: the pipeline's shard_map
                    # manualises every mesh axis, so dp/fsdp/ep shards hold
                    # DIFFERENT rows of the same microbatch but would draw
                    # identical masks from the replicated layer key (the
                    # dense GSPMD path draws per-global-row)
                    key = jax.random.fold_in(
                        key, jax.lax.axis_index(BATCH_AXES)
                    )
                    if seq_sharded:
                        # ...and across sequence shards: each sp shard
                        # holds different positions of the same tensor
                        key = jax.random.fold_in(
                            key, jax.lax.axis_index("sp")
                        )
                    return run(carry, blk, key), None
            step_pp = _remat(body_pp) if cfg.remat else body_pp
            (y, aux), _ = jax.lax.scan(
                step_pp, (x_mb, jnp.zeros((), jnp.float32)), xs_local,
                unroll=cfg.scan_unroll,
            )
            return y, aux

        # pipeline aux = sum over layers, averaged over microbatches and
        # batch shards — the same quantity the single-device scan carries
        x, moe_aux = pipeline.pipeline_blocks(
            x, xs, rope if cfg.rope else (), apply_stack, mesh,
            n_microbatches=cfg.pp_microbatches,
            seq_sharded=seq_sharded,
            xs_specs=xs_specs,
            schedule=cfg.pp_schedule,
        )
    else:
        moe_aux = jnp.zeros((), jnp.float32)
        for _ in range(cfg.n_passes):       # the same weights in every pass
            carry = (x, moe_aux)
            if n_dense:
                carry = run_stack(carry, xs_dense, n_dense)
            x, moe_aux = run_stack(carry, xs, nl - n_dense)
            if cfg.closes_passes:
                x = _norm(x, params["lnf_scale"], params.get("lnf_bias"), cfg)
            if cfg.exit_gate:
                gates.append(exit_gate_logits(params, x))
    out = _head_and_loss(params, x, cfg, targets, return_logits, moe_aux,
                         normed=cfg.closes_passes, mesh=mesh)
    return (*out, jnp.stack(gates)) if return_gates else out


def _head_and_loss(params: Params, x, cfg: GPTConfig, targets,
                   return_logits: bool, moe_aux, normed: bool = False,
                   mesh=None):
    """The final norm (but for ``normed`` hidden states, which a looped
    stack's last pass hands over), the LM head and the loss of ``forward``:
    (logits or None, loss or None)."""
    t, nl = x.shape[1], cfg.n_layer
    if not normed:
        x = _norm(x, params["lnf_scale"], params.get("lnf_bias"), cfg)
    if cfg.residual_dtype:
        x = x.astype(cfg.dtype)     # the head's matmul runs in the compute dtype
    if cfg.dim_model_base:
        x = (x / cfg.head_divisor).astype(x.dtype)
    w_head = params["wte"].T if cfg.tie_weights else params["head"]
    # snap the chunk count to the largest divisor of T <= loss_chunks, so an
    # awkward block_size degrades to fewer/larger chunks, not silently to
    # the dense (B, T, V) materialisation the feature exists to avoid
    nc = max(
        (d for d in range(1, cfg.loss_chunks + 1) if t % d == 0),
        default=1,
    )
    chunked = targets is not None and not return_logits and nc > 1

    logits = None
    if not chunked:
        logits = jnp.einsum(
            "btd,dv->btv", x, w_head.astype(x.dtype),
            preferred_element_type=jnp.float32,
        )
        logits = attn_ops.softcap(logits, cfg.final_logit_softcap)

    loss = None
    if targets is not None:
        if chunked:
            # loss-only mode: the LM head + softmax run per sequence chunk,
            # so the full (B, T, V) fp32 logits (1.6 GB at B=8/T=1024/
            # V=50257 — the tensor that caps the per-chip batch) never
            # materialises: differentiated, a chunk leaves its dx and its
            # addend to dW behind, not its logits, and the backward holds
            # no head matmul. When logits are requested they exist anyway,
            # so dense CE costs no extra memory — no chunking in that case.
            loss = chunked_cross_entropy(
                x, w_head.astype(x.dtype), targets, nc,
                softcap=cfg.final_logit_softcap,
                unroll=cfg.unroll_layers,
                batch_shards=1 if mesh is None else math.prod(
                    mesh.shape[a] for a in BATCH_AXES),
            )
        else:
            loss = cross_entropy(logits, targets)
        if cfg.n_experts:
            # per-layer-mean load-balancing loss (Switch Transformer)
            loss = loss + cfg.moe_aux_weight * moe_aux / nl
    if not return_logits:
        logits = None
    return logits, loss


def cross_entropy(logits: jax.Array, targets: jax.Array) -> jax.Array:
    """Mean CE over positions with target != -1 (reference model.py:316-319:
    F.cross_entropy(..., ignore_index=-1))."""
    valid = targets != -1
    safe = jnp.where(valid, targets, 0)
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    ll = jnp.take_along_axis(logp, safe[..., None], axis=-1)[..., 0]
    return -(ll * valid).sum() / jnp.maximum(valid.sum(), 1)


@jax.named_scope("ce")
def chunked_cross_entropy(
    x: jax.Array, w_head: jax.Array, targets: jax.Array, n_chunks: int,
    softcap: Optional[float] = None,
    unroll: bool = False,
    batch_shards: int = 1,
) -> jax.Array:
    """Same math as ``cross_entropy(x @ w_head, targets)``, but the head
    matmul + softmax run per sequence chunk: peak logits memory is
    (B, T/n_chunks, V), and nothing of vocabulary width outlives its chunk,
    forward or backward.

    The function is a ``jax.custom_vjp``. Differentiated, a chunk's
    gradient of the logits is taken in the forward sweep, while the chunk's
    logits are still there (the loss is a scalar, so its cotangent only
    scales the result), and turned at once into the chunk's ``dx`` and its
    addend to ``dW``, both kept in float32 until the backward has scaled
    them by ``cotangent / count`` (its whole work) and casts them to the
    primals' dtypes: three head-sized matmuls a chunk, none computed twice.
    Not differentiated (an evaluation loss) it is the plain loop: one matmul
    a chunk and the two reductions. Forward-mode differentiation
    (``jax.jvp``) is what the rule gives up.

    The per-chunk loss is ``sum(lse - logit_target)`` — two reductions
    over the chunk logits — rather than ``log_softmax`` + gather, which
    would materialise a full (B, c, V) log-prob tensor only to read one
    column of it (round-4 trace: the CE machinery cost ~2.6x its matmul
    ideal). The gradient of the logits is ``jax.vjp`` of that same tail,
    so a softcap's derivative is carried and no formula is written twice.

    ``unroll=True`` replaces the chunk lax.scan with a statically unrolled
    python loop over direct slices of ``x`` — no (n, B, c, D) transposed
    copy of the activations, no while-loop overhead (same rationale as
    ``config.unroll_layers``, which the trainer threads through here). An
    ``optimization_barrier`` between a chunk and the next keeps the loop's
    order, which is what keeps one chunk's logits live and not all of them.

    ``batch_shards`` is the number of shards a mesh cuts the batch into
    (``forward`` works it out of its ``mesh``). ``dW`` contracts over the
    batch, so on such a mesh a chunk's addend is a partial sum a shard, and
    a sum that is carried through a loop or a barrier as a (D, V) array is
    reduced there, chunk by chunk (eight all-reduces of 322 MB at XL under
    ``fsdp=4``: compile rehearsal, PR 56). Carried as (batch_shards, D, V),
    a shard's slab stays on its device and the one sum over the leading
    axis, after the last chunk, is the step's one reduction of the head's
    gradient.
    """
    b, t, d = x.shape
    if t % n_chunks:
        # the unrolled slices would silently drop the tail (the scan path's
        # reshape would fail anyway) — forward() snaps nc to a divisor of T
        raise ValueError(f"T={t} not divisible by n_chunks={n_chunks}")
    c = t // n_chunks

    def chunk_logits(xc, w):
        return jnp.einsum(
            "bcd,dv->bcv", xc, w, preferred_element_type=jnp.float32
        )

    def chunk_tail(logits, tc):
        logits = attn_ops.softcap(logits, softcap)
        valid = tc != -1
        safe = jnp.where(valid, tc, 0)
        lse = jax.nn.logsumexp(logits, axis=-1)  # (B, c) fp32
        s_t = jnp.take_along_axis(logits, safe[..., None], axis=-1)[..., 0]
        return ((lse - s_t) * valid).sum()

    def over_chunks(step, carry, xs_full, ts_full):
        """Fold ``step(carry, xc, tc) -> (carry, out)`` over the chunks;
        the chunks' outputs (None, or (B, c, D) each) laid back along T."""
        if unroll:
            outs = []
            for i in range(n_chunks):
                xc = xs_full[:, i * c:(i + 1) * c]
                if i:
                    # a chunk starts when the one before it is done: nothing
                    # else orders the unrolled chunks, and the TPU scheduler
                    # was seen to run all eight logits matmuls first (6.6 GB
                    # of logits live at once at 124M: compile rehearsal,
                    # PR 56)
                    carry, outs[-1], xc = jax.lax.optimization_barrier(
                        (carry, outs[-1], xc))
                carry, out = step(carry, xc, ts_full[:, i * c:(i + 1) * c])
                outs.append(out)
            return carry, jax.tree.map(
                lambda *o: jnp.concatenate(o, axis=1), *outs)
        xs = xs_full.reshape(b, n_chunks, c, d).swapaxes(0, 1)  # (n, B, c, D)
        ts = ts_full.reshape(b, n_chunks, c).swapaxes(0, 1)
        carry, outs = jax.lax.scan(
            lambda cr, xt: step(cr, *xt), carry, (xs, ts))
        return carry, jax.tree.map(
            lambda o: o.swapaxes(0, 1).reshape(b, t, d), outs)

    def count(ts_full):
        return jnp.maximum((ts_full != -1).sum(), 1)

    @jax.custom_vjp
    def mean_loss(x, w, ts_full):
        def step(tot, xc, tc):
            return tot + chunk_tail(chunk_logits(xc, w), tc), None

        tot, _ = over_chunks(step, jnp.zeros((), jnp.float32), x, ts_full)
        return tot / count(ts_full)

    def forward_rule(x, w, ts_full):
        shards = batch_shards if b % batch_shards == 0 else 1
        by_shard = lambda a: a.reshape(shards, b // shards, *a.shape[1:])

        def step(carry, xc, tc):
            tot, dw = carry
            li, pull = jax.vjp(lambda z: chunk_tail(z, tc),
                               chunk_logits(xc, w))
            (dz,) = pull(jnp.ones((), jnp.float32))  # (B, c, V) fp32
            # the two transposes of chunk_logits, as jax.vjp would state
            # them, but for dW's sum staying in float32 and by shard
            dxc = jax.lax.dot_general(
                dz, w, (((2,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            dw = dw + jax.lax.dot_general(
                by_shard(xc), by_shard(dz), (((1, 2), (1, 2)), ((0,), (0,))),
                preferred_element_type=jnp.float32)
            return (tot + li, dw), dxc

        init = (jnp.zeros((), jnp.float32),
                jnp.zeros((shards, *w.shape), jnp.float32))
        (tot, dw), dx = over_chunks(step, init, x, ts_full)
        n = count(ts_full)
        return tot / n, (dx, dw.sum(0), n)

    def backward_rule(res, g):
        dx, dw, n = res
        scale = g / n
        return ((dx * scale).astype(x.dtype),
                (dw * scale).astype(w_head.dtype), None)

    mean_loss.defvjp(forward_rule, backward_rule)
    return mean_loss(x, w_head, targets)


# ---------------------------------------------------------------------------
# Reporting (reference C10: print_model_size, model.py:21-33, 257-259)
# ---------------------------------------------------------------------------


def param_count(params: Params) -> int:
    return sum(int(p.size) for p in jax.tree.leaves(params))


def model_size_report(params: Params, cfg: GPTConfig) -> str:
    n = param_count(params)
    mb = sum(int(p.size) * p.dtype.itemsize for p in jax.tree.leaves(params)) / 2**20
    return (
        f"GPT: {cfg.n_layer}L/{cfg.n_head}H/{cfg.n_embd}d, "
        f"block {cfg.block_size}, vocab {cfg.vocab_size} — "
        f"{n/1e6:.2f}M params, {mb:.1f} MB (fp32 master)"
    )
