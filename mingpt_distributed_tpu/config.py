"""Configuration layer: dataclasses + presets + YAML + CLI overrides.

TPU-native re-design of the reference's config system
(/root/reference/mingpt/model.py:38-59, /root/reference/mingpt/trainer.py:21-29,
/root/reference/mingpt/char_dataset.py:12-17, /root/reference/mingpt/train.py:36-39,
/root/reference/mingpt/gpt2_config.yaml): the same four-section schema
(model / optimizer / data / trainer), with the reference's latent config bugs
fixed by construction:

* one canonical spelling ``n_embd`` everywhere (the reference mixed ``n_embed``
  and ``n_embd`` across dataclass, preset table, and YAML — bugs B2/B15 in
  SURVEY.md §2.9); ``n_embed`` is accepted as an input alias and normalised.
* preset-vs-explicit dims validated as XOR (the reference's condition at
  model.py:267 inverted the check — bug B1), matching upstream minGPT's intent.
* unknown keys are rejected at load time with the valid key set in the error.

No Hydra dependency: a plain YAML file plus dotted ``section.key=value`` CLI
overrides reproduces the Hydra surface actually used by the reference
(/root/reference/mingpt/train.py:30, gpt2_config.yaml), without relocating the
run dir (the reference had to disable that relocation, gpt2_config.yaml:21-23).
"""

from __future__ import annotations

import dataclasses
import io
import warnings
from dataclasses import dataclass, field
from typing import Any, Mapping, Optional, Sequence, Tuple

import yaml

# ---------------------------------------------------------------------------
# Model presets
# ---------------------------------------------------------------------------

# Preset table mirroring /root/reference/mingpt/model.py:269-294 (values are
# public GPT-2/minGPT lore, cf. reference README.md:86-143), plus TPU-era
# additions (llama family for the RoPE/SwiGLU retrofit, BASELINE config #5).
MODEL_PRESETS: dict[str, dict[str, Any]] = {
    # name            layers heads  width   (params)
    "openai-gpt":    dict(n_layer=12, n_head=12, n_embd=768),    # 117M
    "gpt2":          dict(n_layer=12, n_head=12, n_embd=768),    # 124M
    "gpt2-medium":   dict(n_layer=24, n_head=16, n_embd=1024),   # 350M
    "gpt2-large":    dict(n_layer=36, n_head=20, n_embd=1280),   # 774M
    "gpt2-xl":       dict(n_layer=48, n_head=25, n_embd=1600),   # 1558M
    "gopher-44m":    dict(n_layer=8,  n_head=16, n_embd=512),
    "gpt-mini":      dict(n_layer=6,  n_head=6,  n_embd=192),
    "gpt-micro":     dict(n_layer=4,  n_head=4,  n_embd=128),
    "gpt-nano":      dict(n_layer=3,  n_head=3,  n_embd=48),
    # Llama-style presets (rotary + SwiGLU + RMSNorm), beyond-parity targets.
    "llama-tiny":    dict(n_layer=4,  n_head=4,  n_embd=256,  n_kv_head=2,
                          rope=True, swiglu=True, rmsnorm=True, tie_weights=False),
    "llama-3-8b":    dict(n_layer=32, n_head=32, n_embd=4096, n_kv_head=8,
                          rope=True, swiglu=True, rmsnorm=True, tie_weights=False,
                          vocab_size=128256, block_size=8192, ffn_mult=3.5,
                          rope_theta=500000.0),  # Llama 3 base, not the 1e4 default
    # Mistral-style presets: Llama architecture + sliding-window attention
    # (each position attends the last `attention_window` tokens; the flash
    # kernel skips out-of-band blocks so compute is O(T*window)).
    "mistral-tiny":  dict(n_layer=4,  n_head=4,  n_embd=256,  n_kv_head=2,
                          rope=True, swiglu=True, rmsnorm=True, tie_weights=False,
                          attention_window=64),
    "mistral-7b":    dict(n_layer=32, n_head=32, n_embd=4096, n_kv_head=8,
                          rope=True, swiglu=True, rmsnorm=True, tie_weights=False,
                          vocab_size=32000, block_size=8192, ffn_mult=3.5,
                          rope_theta=1000000.0, attention_window=4096),
    # Mixtral-style sparse MoE presets (SwiGLU experts, top-2 routing,
    # expert axis shards over the mesh's ep axis — ops/moe.py).
    "mixtral-tiny":  dict(n_layer=4,  n_head=4,  n_embd=256,  n_kv_head=2,
                          rope=True, swiglu=True, rmsnorm=True, tie_weights=False,
                          n_experts=4, moe_top_k=2),
    "mixtral-8x7b":  dict(n_layer=32, n_head=32, n_embd=4096, n_kv_head=8,
                          rope=True, swiglu=True, rmsnorm=True, tie_weights=False,
                          vocab_size=32000, block_size=8192, ffn_mult=3.5,
                          rope_theta=1000000.0, n_experts=8, moe_top_k=2),
    # kakaocorp/kanana-2-30b-a3b-instruct-2601 (model_type deepseek_v3): a
    # latent (MLA) cache with no query latent, one dense SwiGLU layer, then
    # 128 sigmoid-routed experts of 768 (6 a token, nothing dropped) beside
    # two shared experts. Published and served in bfloat16.
    "kanana-2-30b-a3b-instruct-2601": dict(
        n_layer=48, n_head=32, n_embd=2048, vocab_size=128256,
        block_size=32768, rope=True, rope_theta=1000000.0,
        rope_interleave=True, swiglu=True, rmsnorm=True, norm_eps=1e-6,
        tie_weights=False, kv_lora_rank=512, qk_nope_head_dim=128,
        qk_rope_head_dim=64, v_head_dim=128, n_dense_layers=1, ffn_dim=6144,
        n_experts=128, moe_top_k=6, moe_ffn_dim=768, n_shared_experts=2,
        moe_scoring="sigmoid", moe_route_scale=2.448,
        param_dtype="bfloat16"),
    # openbmb/MiniCPM-SALA (9B): 24 lightning linear-attention layers (a
    # decaying 128x128 state a head, rope) interleaved with 8 InfLLM-v2
    # block-sparse softmax layers (32 query heads over 2 KV heads, no rope),
    # qk-norm, gated outputs, MiniCPM's three scalings. Published in bfloat16.
    "minicpm-sala": dict(
        n_layer=32, n_head=32, n_embd=4096, n_kv_head=2, vocab_size=73448,
        block_size=524288, rope=True, rope_theta=10000.0, swiglu=True,
        rmsnorm=True, norm_eps=1e-6, tie_weights=False, ffn_dim=16384,
        mixer_types=tuple(
            "minicpm4" if i in (0, 9, 16, 17, 22, 29, 30, 31)
            else "lightning-attn" for i in range(32)),
        lightning_heads=32, lightning_head_dim=128, qk_norm=True,
        output_gate=True, scale_emb=12.0, scale_depth=1.4,
        dim_model_base=256, param_dtype="bfloat16"),
    # the same stack at a size the CPU tests run: one period of four layers
    # that ends on a sparse one, a selection that selects within 128 rows
    "minicpm-sala-tiny": dict(
        n_layer=4, n_head=4, n_embd=64, n_kv_head=2, vocab_size=96,
        block_size=128, rope=True, swiglu=True, rmsnorm=True, norm_eps=1e-6,
        tie_weights=False, ffn_dim=128,
        mixer_types=("lightning-attn", "minicpm4", "lightning-attn",
                     "minicpm4"),
        lightning_heads=4, lightning_head_dim=16, qk_norm=True,
        output_gate=True, scale_emb=12.0, scale_depth=1.4,
        scale_depth_layers=32, dim_model_base=16,
        sparse_kernel_size=8, sparse_kernel_stride=4, sparse_block_size=16,
        sparse_topk=4, sparse_window=16, sparse_init_blocks=1,
        sparse_dense_len=48, dtype="float32"),
}

#: the two mixers a hybrid stack (``GPTConfig.mixer_types``) is made of,
#: under their published names
LIGHTNING, SPARSE = "lightning-attn", "minicpm4"
#: the two kinds of softmax attention layer a stack of ``layer_types`` is
#: made of, under their published names: every row of the context, or the
#: last ``attention_window`` rows, kept in a ring
FULL_ATTN, WINDOW_ATTN = "full_attention", "sliding_attention"


class ConfigError(ValueError):
    """Raised for invalid or inconsistent configuration."""


def _reject_unknown(cls, kwargs: Mapping[str, Any]) -> dict[str, Any]:
    valid = {f.name for f in dataclasses.fields(cls)}
    unknown = set(kwargs) - valid
    if unknown:
        raise ConfigError(
            f"{cls.__name__}: unknown key(s) {sorted(unknown)}; "
            f"valid keys: {sorted(valid)}"
        )
    return dict(kwargs)


@dataclass(frozen=True)
class GPTConfig:
    """Model hyperparameters (reference GPTConfig, model.py:38-51).

    Either give ``model_type`` (a preset name) or the explicit dims
    ``n_layer/n_head/n_embd`` — exactly one of the two (upstream minGPT's
    XOR assert; the reference fork broke this, SURVEY.md B1).

    Frozen (hashable): instances are jit static arguments; evolve with
    ``dataclasses.replace``.
    """

    model_type: Optional[str] = None
    n_layer: Optional[int] = None
    n_head: Optional[int] = None
    n_embd: Optional[int] = None
    vocab_size: int = 50257
    block_size: int = 1024
    # Dropout rates (reference: embed_drop/resid_drop/attn_drop, all 0.1).
    embd_pdrop: float = 0.1
    resid_pdrop: float = 0.1
    attn_pdrop: float = 0.1
    # --- TPU-native extensions -------------------------------------------
    # Attention implementation: "einsum" (reference semantics, oracle),
    # "flash" (Pallas blockwise kernel), "ring" (sequence-parallel ring
    # attention over the mesh's `sp` axis).
    attention: str = "einsum"
    # Sliding-window (banded) attention, Mistral-style: each position sees
    # only the last `attention_window` tokens (itself included); None =
    # full causal. Supported by every attention impl: the einsum oracle,
    # the flash kernel (which skips out-of-band blocks: compute
    # O(T*window), not O(T^2)), and the ring/ulysses sequence-parallel
    # paths (the ring turns banded with static hop skipping —
    # test_sp_window_softcap.py).
    attention_window: Optional[int] = None
    # Gemma-2-style logit soft-capping: logits -> cap * tanh(logits / cap).
    # `attn_logit_softcap` applies to attention scores before masking
    # (every impl, incl. ring/ulysses — test_sp_window_softcap.py);
    # `final_logit_softcap` applies to the LM-head logits (loss, chunked
    # loss, and generation alike). None disables.
    attn_logit_softcap: Optional[float] = None
    final_logit_softcap: Optional[float] = None
    # Compute dtype for activations.
    dtype: str = "bfloat16"
    # Dtype ``gpt.init`` makes the parameters in. float32 is the training
    # master copy (the serving engine then keeps a cast copy of the matmul
    # leaves, generate.cast_once_params); a model published and served in
    # bfloat16 is made in bfloat16 and lives on the device once.
    param_dtype: str = "float32"
    # Rematerialise each block in backward (jax.checkpoint) to trade FLOPs
    # for HBM. A layer keeps its input and the two values only the flash
    # kernel can make again, the attention's output and log-sum-exp
    # (gpt._remat): B x T x D in the compute dtype and B x H x T float32 a
    # layer over the input alone, for a backward without the forward kernel.
    remat: bool = False
    # GPipe microbatch count when the mesh has pp > 1 stages; 0 = one
    # microbatch per stage. Bubble fraction is (pp-1)/(M+pp-1), so raise M
    # for efficiency, bounded by batch divisibility and activation memory.
    pp_microbatches: int = 0
    # Pipeline schedule: "gpipe" (plain differentiable scan; autodiff derives
    # the backward pipeline; live activations O(M) microbatches) or "1f1b"
    # (custom-vjp backward that interleaves recompute-forward with backward
    # in 1F1B order, bounding the backward's stage-input stash to O(pp)
    # microbatches at the cost of one extra forward per stage-microbatch
    # vs gpipe+remat — pick it when activation HBM, not FLOPs, binds).
    pp_schedule: str = "gpipe"
    # Tie the LM head to the token embedding (GPT-2 ties; the reference's
    # head is an independent bias-free Linear, model.py:249 — keep that as
    # the default for parity).
    tie_weights: bool = False
    # Llama-retrofit toggles (BASELINE config #5).
    rope: bool = False
    rope_theta: float = 10000.0
    swiglu: bool = False
    rmsnorm: bool = False
    n_kv_head: Optional[int] = None  # grouped-query attention; None = n_head
    ffn_mult: float = 4.0  # MLP expansion factor (reference hardcodes 4x)
    # The MLP width as a number where a model publishes one that is no
    # multiple of the width (``dense_width``); None = ffn_mult * n_embd.
    ffn_dim: Optional[int] = None
    norm_eps: float = 1e-5  # LayerNorm/RMSNorm epsilon
    # Rotate adjacent pairs (2i, 2i+1) instead of the halves (i, i + hd/2).
    rope_interleave: bool = False
    # Multi-head latent attention (DeepSeek-V2/V3, no query latent): > 0
    # replaces wk/wv with a down-projection to one ``kv_lora_rank`` latent
    # and one shared rotary key of ``qk_rope_head_dim`` a token (all that
    # is cached) and an up-projection to per-head keys (``qk_nope_head_dim``)
    # and values (``v_head_dim``). Needs rope and rmsnorm.
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # Mixture-of-experts (ops/moe.py): 0 = dense MLP (reference semantics);
    # E > 0 replaces every block's MLP with E GELU experts, top-k routed,
    # expert axis sharded over the mesh's `ep` axis.
    n_experts: int = 0
    moe_top_k: int = 2
    moe_capacity_factor: float = 1.25
    moe_aux_weight: float = 0.01  # load-balancing loss weight
    # An expert's width (``expert_width``); None = the dense MLP's.
    moe_ffn_dim: Optional[int] = None
    # Which route (ops/moe.py). "softmax": GShard dispatch by capacity,
    # gates renormalised for k > 1. "sigmoid": the DeepSeek-V3 route:
    # sigmoid scores, the k best of score + a bias leaf, gates the chosen
    # scores (normalised to sum to 1 under ``moe_norm_topk``) times
    # ``moe_route_scale``, nothing dropped whatever the load, no aux loss.
    moe_scoring: str = "softmax"
    moe_norm_topk: bool = True
    moe_route_scale: float = 1.0
    # SwiGLU experts every token takes beside its routed ones, as one MLP
    # of n_shared_experts * expert_width (sigmoid route only).
    n_shared_experts: int = 0
    # Leading layers that keep a dense MLP in an expert model: a stack of
    # their own (params["dense_blocks"]) before the expert stack.
    n_dense_layers: int = 0
    # A hybrid stack (MiniCPM-SALA): one mixer a layer, under its published
    # name. LIGHTNING is linear attention with a decaying (head_dim,
    # head_dim) float32 state a head and no rows (ops/lightning.py); SPARSE
    # is InfLLM-v2 block-sparse softmax attention over ``n_head`` query and
    # ``n_kv_head`` KV heads of ``head_dim`` (ops/sparse_attention.py).
    # None: every layer is the stack's one attention. Needs rope (no
    # position table), rmsnorm and swiglu; the lightning layers rotate their
    # queries and keys, the sparse ones do not.
    mixer_types: Optional[Tuple[str, ...]] = None
    lightning_heads: int = 0
    lightning_head_dim: int = 0
    # RMS-norm every head's queries and keys, with a learned weight a mixer.
    qk_norm: bool = False
    # The mixer's output times sigmoid(W_g u) before the output projection.
    output_gate: bool = False
    # MiniCPM's scalings: the embeddings times ``scale_emb``; every residual
    # branch times ``scale_depth / sqrt(scale_depth_layers or n_layer)`` (a
    # cut in depth keeps the published depth here); the head's input divided
    # by ``n_embd / dim_model_base``. 1, 0 and 0 switch each off.
    scale_emb: float = 1.0
    scale_depth: float = 0.0
    scale_depth_layers: Optional[int] = None
    dim_model_base: int = 0
    # The sparse mixer's selection: keys mean-pooled over windows of
    # ``sparse_kernel_size`` every ``sparse_kernel_stride``; a query below
    # ``sparse_dense_len`` attends every row, one at or above it the rows of
    # ``sparse_topk`` blocks of ``sparse_block_size``: the first
    # ``sparse_init_blocks``, those that cover its last ``sparse_window``
    # positions, and the best-scoring others.
    sparse_kernel_size: int = 32
    sparse_kernel_stride: int = 16
    sparse_block_size: int = 64
    sparse_topk: int = 64
    sparse_window: int = 2048
    sparse_init_blocks: int = 1
    sparse_dense_len: int = 8192
    # A looped stack (Ouro): the ``n_layer`` layers run ``n_passes`` times
    # over one set of weights. The final norm closes every pass and its
    # output is what the next pass starts from; pass t of layer l caches and
    # attends its own keys and values (cache plane ``t * n_layer + l``:
    # ``cache_planes`` planes over ``n_layer`` layers of weights).
    n_passes: int = 1
    # An RMSNorm after each sublayer as well as before it: x + N2(Attn(N1 x)),
    # then a + N4(MLP(N3 a)) (leaves ``ln1_post_scale``, ``ln2_post_scale``).
    post_norms: bool = False
    # One Linear(n_embd, 1) read on every pass's normed output: g_t =
    # sigmoid(w . h_t + b), and p_t = g_t * prod_{j<t}(1 - g_j) the mass
    # that exits at pass t (the last pass takes what is left). A token
    # leaves at the first pass whose cumulative p reaches
    # ``exit_threshold``; at 1, the only value built, every token runs every
    # pass and the gate moves no logit (the serving programs count it:
    # generate.LOOP_PASSES).
    exit_gate: bool = False
    exit_threshold: float = 1.0
    # The dtype the residual stream is carried in between sublayers (and
    # from pass to pass); None = ``dtype``. "float32": a sublayer's input is
    # normed in float32 and rounded to ``dtype`` where it enters a matmul,
    # its output joins the stream in float32, and the head reads the final
    # norm rounded to ``dtype``. Rounding the stream itself at each of 384
    # sums is what a bfloat16 stack of 48 layers run four times cannot
    # afford (PERF.md, PR 37).
    residual_dtype: Optional[str] = None
    # Cross-entropy head chunking: >1 splits the LM-head matmul + softmax
    # into this many sequence chunks under jax.checkpoint, so the (B, T, V)
    # fp32 logits tensor — the dominant activation at GPT-2 vocab sizes —
    # never materialises whole. 0/1 = dense (reference semantics; identical
    # loss either way). Ignored when T is not divisible by it.
    loss_chunks: int = 8
    # lax.scan unroll factor for the layer loop (>= 1; lax.scan handles a
    # non-dividing remainder): >1 lets XLA fuse across layer boundaries at
    # the cost of compile time.
    scan_unroll: int = 1
    # Replace the layer lax.scan with a statically unrolled python loop.
    # The scan stacks every saved-for-backward activation into (n_layer,
    # ...) buffers via dynamic-update-slice — ~23% of step time on the
    # round-4 TPU trace (bitcast_dynamic-update-slice fusions). Unrolled,
    # XLA plans each layer's residuals as individual statically-addressed
    # buffers: no stacking copies, better fusion across the layer
    # boundary, at the cost of an n_layer-times-larger HLO (slower
    # compile). Ignored under pp (the pipeline has its own schedule).
    unroll_layers: bool = False
    # A stack whose softmax attention layers differ in kind (Laguna): one of
    # FULL_ATTN, WINDOW_ATTN a layer, under the published ``layer_types``.
    # A full layer has ``n_head`` query heads, rotates by ``rope_theta``,
    # ``rope_fraction`` and ``rope_yarn`` and caches a row a position; a
    # window layer has ``window_n_head`` query heads, rotates by
    # ``window_rope_theta`` and ``window_rope_fraction``, attends the last
    # ``attention_window`` positions (its own included) and caches that many
    # rows a slot in a ring (models/generate.py). Both kinds share
    # ``n_kv_head`` and ``head_dim``. None: every layer is the stack's one
    # attention.
    layer_types: Optional[Tuple[str, ...]] = None
    # The size of a head where a model states one (published ``head_dim``)
    # that is not ``n_embd // n_head``; None = that quotient.
    head_size: Optional[int] = None
    # Query heads of the window layers (published
    # ``num_attention_heads_per_layer``); None = ``n_head``.
    window_n_head: Optional[int] = None
    # The share of a head the rotary embedding turns, from its first
    # dimension on (published ``partial_rotary_factor``); the rest passes
    # through. 0: the kind rotates nothing and carries no position (a
    # published ``rope_layout`` entry of 0). ``layer_types`` only.
    rope_fraction: float = 1.0
    window_rope_theta: Optional[float] = None   # None = rope_theta
    window_rope_fraction: float = 1.0
    # YaRN on the full layers' rotation: (factor,
    # original_max_position_embeddings, beta_fast, beta_slow,
    # attention_factor), the published ``rope_parameters`` of that kind
    # (ops/attention.yarn_rope_tables). None: the plain frequencies.
    rope_yarn: Optional[Tuple[float, ...]] = None
    # One sigmoid gate a head and token on the attention's output before the
    # output projection, ``sigmoid(h W_g)`` with ``W_g`` (n_embd, heads)
    # (published ``gating``; leaf ``w_hg``). ``layer_types`` only; a hybrid
    # stack's ``output_gate`` is one a number.
    head_gate: bool = False
    # The dropless route (ops/moe.moe_dropless) under softmax scores: the k
    # largest router logits, gates the chosen experts' softmax
    # probabilities (over their sum under ``moe_norm_topk``) times
    # ``moe_route_scale``, shared experts beside them, nothing dropped.
    # False: "softmax" is the capacity route.
    moe_dropless: bool = False
    # What a routed expert's gate goes through before it multiplies the up
    # projection: "silu" (SwiGLU) or "relu" (ReGLU, ``relu(x W_g) * (x
    # W_u)``). The dropless route only; ``swiglu`` still says that the MLPs
    # are gated (the leaves ``w_eg``, ``w_e1``, ``w_e2``).
    expert_act: str = "silu"
    # The activations the router scores: "mlp" the MLP's normed input (the
    # post-attention norm's output, which the experts take too), or "attn"
    # the attention's normed input, so that a token's experts are known
    # before its attention has run; the experts still take the MLP's input.
    # The dropless route only.
    moe_router_input: str = "mlp"

    @classmethod
    def make(cls, **kwargs: Any) -> "GPTConfig":
        """Build + resolve + validate in one step (accepts n_embed alias)."""
        kwargs = dict(kwargs)
        if "n_embed" in kwargs:  # normalise the reference's stray spelling
            kwargs.setdefault("n_embd", kwargs.pop("n_embed"))
        for key in ("mixer_types", "layer_types", "rope_yarn"):
            if kwargs.get(key) is not None:  # YAML and JSON give lists
                kwargs[key] = tuple(kwargs[key])
        cfg = cls(**_reject_unknown(cls, kwargs))
        return cfg.resolved()

    def resolved(self) -> "GPTConfig":
        """Apply the preset table and validate (XOR semantics, fixing B1)."""
        type_given = self.model_type is not None
        dims_given = all(
            v is not None for v in (self.n_layer, self.n_head, self.n_embd)
        )
        any_dim_given = any(
            v is not None for v in (self.n_layer, self.n_head, self.n_embd)
        )
        if type_given and any_dim_given:
            raise ConfigError(
                "give either model_type (a preset) or explicit "
                "n_layer/n_head/n_embd, not both"
            )
        if not type_given and not dims_given:
            raise ConfigError(
                "model underspecified: give model_type or all of "
                "n_layer/n_head/n_embd"
            )
        out = self
        if type_given:
            if self.model_type not in MODEL_PRESETS:
                raise ConfigError(
                    f"unknown model_type {self.model_type!r}; "
                    f"presets: {sorted(MODEL_PRESETS)}"
                )
            out = dataclasses.replace(self, **MODEL_PRESETS[self.model_type])
        out.validate()
        return out

    def validate(self) -> None:
        if self.n_embd is None or self.n_head is None or self.n_layer is None:
            raise ConfigError("model dims unresolved; call .resolved() first")
        if self.head_size is None and self.n_embd % self.n_head != 0:
            raise ConfigError(
                f"n_embd={self.n_embd} not divisible by n_head={self.n_head}"
            )
        kv = self.n_kv_head if self.n_kv_head is not None else self.n_head
        if self.n_head % kv != 0:
            raise ConfigError(
                f"n_head={self.n_head} not divisible by n_kv_head={kv}"
            )
        if self.attention not in ("einsum", "flash", "ring", "ulysses"):
            raise ConfigError(f"unknown attention impl {self.attention!r}")
        # window/softcap compose with every attention impl, including the
        # sequence-parallel ones: the ring turns banded with static hop
        # skipping and ulysses holds the full sequence locally (r4 —
        # parallel/ring_attention.py, parallel/ulysses.py)
        if self.attention_window is not None and self.attention_window < 1:
            raise ConfigError(
                f"attention_window must be >= 1, got {self.attention_window}"
            )
        if self.attn_logit_softcap is not None and self.attn_logit_softcap <= 0:
            raise ConfigError(
                f"attn_logit_softcap must be > 0, got {self.attn_logit_softcap}"
            )
        if self.final_logit_softcap is not None and self.final_logit_softcap <= 0:
            raise ConfigError(
                f"final_logit_softcap must be > 0, got {self.final_logit_softcap}"
            )
        if self.scan_unroll < 1:
            raise ConfigError(f"scan_unroll must be >= 1, got {self.scan_unroll}")
        if self.pp_schedule not in ("gpipe", "1f1b"):
            raise ConfigError(
                f"unknown pp_schedule {self.pp_schedule!r} "
                "(choose 'gpipe' or '1f1b')"
            )
        if self.loss_chunks < 0:
            raise ConfigError(f"loss_chunks must be >= 0, got {self.loss_chunks}")
        if self.rope and self.head_dim % 2 != 0:
            raise ConfigError(
                f"rope needs an even head_dim, got {self.head_dim}"
            )
        if self.block_size <= 0 or self.vocab_size <= 0:
            raise ConfigError("block_size and vocab_size must be positive")
        if self.n_experts:
            if self.moe_top_k < 1 or self.moe_top_k > self.n_experts:
                raise ConfigError(
                    f"moe_top_k={self.moe_top_k} outside [1, {self.n_experts}]"
                )
        if self.moe_scoring not in ("softmax", "sigmoid"):
            raise ConfigError(f"unknown moe_scoring {self.moe_scoring!r}")
        if (self.moe_scoring == "sigmoid" or self.moe_dropless) \
                and not (self.n_experts and self.swiglu):
            raise ConfigError(
                "moe_scoring='sigmoid' and moe_dropless are the dropless "
                "route of SwiGLU experts: it needs n_experts > 0 and swiglu")
        if self.moe_scoring == "softmax" and not self.moe_dropless and (
                self.n_shared_experts or self.moe_route_scale != 1.0
                or not self.moe_norm_topk):
            raise ConfigError(
                "shared experts, a gate scale and un-renormalised gates are "
                "built for the dropless route only (moe_scoring='sigmoid', or "
                "moe_dropless under 'softmax'): the capacity route "
                "(ops/moe.py) renormalises softmax gates and adds nothing")
        if self.moe_scoring == "softmax" and self.moe_dropless \
                and not self.moe_norm_topk:
            raise ConfigError(
                "the dropless route under softmax scores renormalises the "
                "chosen experts' probabilities (moe_norm_topk): gates left "
                "as the softmax over all experts gives them are not written")
        if self.expert_act not in ("silu", "relu") \
                or self.moe_router_input not in ("mlp", "attn"):
            raise ConfigError(
                f"expert_act {self.expert_act!r} is 'silu' or 'relu' and "
                f"moe_router_input {self.moe_router_input!r} 'mlp' or 'attn'")
        if (self.expert_act != "silu" or self.moe_router_input != "mlp") \
                and not self.dropless:
            raise ConfigError(
                "expert_act and moe_router_input are the dropless route's "
                "(moe_scoring='sigmoid', or moe_dropless): the capacity "
                "route (ops/moe.moe_mlp) gates by SiLU and scores the MLP's "
                "input, and a dense MLP has no router")
        if self.expert_act != "silu" and self.n_shared_experts:
            raise ConfigError(
                "expert_act='relu' with shared experts is not written: the "
                "shared experts' one MLP is ops/layers.mlp_swiglu, and no "
                "published config says a shared expert of ReLU-gated ones")
        if not 0 <= self.n_dense_layers <= self.n_layer:
            raise ConfigError(
                f"n_dense_layers={self.n_dense_layers} outside "
                f"[0, {self.n_layer}]")
        if self.n_dense_layers and not self.n_experts:
            raise ConfigError(
                "n_dense_layers names the dense layers that lead an expert "
                "model: without n_experts every layer is dense already")
        if self.kv_lora_rank:
            if min(self.qk_nope_head_dim, self.qk_rope_head_dim,
                   self.v_head_dim) < 1 or self.qk_rope_head_dim % 2:
                raise ConfigError(
                    "latent attention needs qk_nope_head_dim, v_head_dim and "
                    "an even qk_rope_head_dim")
            if not (self.rope and self.rmsnorm):
                raise ConfigError(
                    "latent attention rotates its shared key and norms its "
                    "latent: it needs rope and rmsnorm")
            if self.attention != "einsum" or self.n_kv_head is not None \
                    or self.attention_window or self.attn_logit_softcap:
                raise ConfigError(
                    "latent attention is built for attention='einsum' with "
                    "no window, softcap or n_kv_head: the flash, ring and "
                    "ulysses paths take one head size for keys and values")
        elif self.qk_nope_head_dim or self.qk_rope_head_dim or self.v_head_dim:
            raise ConfigError(
                "qk_nope_head_dim, qk_rope_head_dim and v_head_dim belong "
                "to latent attention: set kv_lora_rank")
        if self.param_dtype not in ("float32", "bfloat16"):
            raise ConfigError(
                f"param_dtype {self.param_dtype!r}: float32 or bfloat16")
        if self.scale_depth < 0 or self.scale_emb <= 0 \
                or self.dim_model_base < 0:
            raise ConfigError(
                "scale_emb must be > 0, scale_depth and dim_model_base >= 0")
        self._validate_looped()
        self._validate_kinds()
        if self.mixer_types is not None:
            self._validate_hybrid()
        elif self.lightning_heads or self.lightning_head_dim \
                or self.qk_norm or self.output_gate:
            raise ConfigError(
                "lightning_heads, lightning_head_dim, qk_norm and "
                "output_gate belong to a hybrid stack: set mixer_types")

    def _validate_looped(self) -> None:
        """A looped stack's own rules, and what it is not built with, a
        sentence each."""
        if self.n_passes < 1:
            raise ConfigError(f"n_passes must be >= 1, got {self.n_passes}")
        if self.exit_threshold != 1.0:
            raise ConfigError(
                f"exit_threshold={self.exit_threshold}: only 1 is built, at "
                "which every token runs every pass. Under it a token leaves "
                "at the first pass whose cumulative exit mass reaches the "
                "threshold, and which keys and values that lane then owes "
                "the later passes of later tokens is in no published config: "
                "a guessed mechanism under a real model's name is worse "
                "than none")
        if (self.post_norms or self.exit_gate) and not self.rmsnorm:
            raise ConfigError(
                "post_norms and exit_gate are written for an RMS-normed "
                "stack: the norm after a sublayer has a scale and no bias, "
                "and the gate reads the final RMSNorm's output")
        if self.residual_dtype not in (None, "float32"):
            raise ConfigError(
                f"residual_dtype {self.residual_dtype!r}: None (the compute "
                "dtype) or 'float32'")
        if (self.post_norms or self.exit_gate or self.residual_dtype) \
                and self.mixer_types is not None:
            raise ConfigError(
                "post_norms, exit_gate and residual_dtype are not written "
                "for a hybrid stack (mixer_types), whose layers have their "
                "own block")
        if self.n_passes == 1:
            return
        if not self.rmsnorm:
            raise ConfigError(
                "a looped stack (n_passes > 1) carries its final RMSNorm's "
                "output from pass to pass: it needs rmsnorm")
        if self.mixer_types is not None:
            raise ConfigError(
                "a looped stack (n_passes > 1) is not written for a hybrid "
                "stack (mixer_types): a recurrent state a pass and layer, "
                "and which pass's state a later pass reads, is no published "
                "model's")
        if self.n_experts or self.kv_lora_rank:
            raise ConfigError(
                "a looped stack (n_passes > 1) is built over per-head rows "
                "and a dense MLP: the routed rows' counter has a row a layer "
                "of weights and the latent cache's absorbed attention is "
                "untested over passes x layers planes")
        if self.pp_microbatches:
            raise ConfigError(
                "a looped stack (n_passes > 1) is not pipelined "
                "(pp_microbatches): a stage would have to hand its output "
                "back to the first stage n_passes - 1 times, a schedule "
                "parallel/pipeline.py does not have")

    def _validate_kinds(self) -> None:
        """The rules of a stack whose attention layers differ in kind
        (``layer_types``), and what is not written for it, a sentence each."""
        if self.head_size is not None and (
                self.head_size < 1 or self.kv_lora_rank):
            raise ConfigError(
                "head_size states a per-head attention's head_dim: it is "
                "positive, and latent attention has sizes of its own")
        if self.layer_types is None:
            if self.window_n_head is not None or self.rope_fraction != 1.0 \
                    or self.window_rope_theta is not None \
                    or self.window_rope_fraction != 1.0 \
                    or self.rope_yarn is not None or self.head_gate:
                raise ConfigError(
                    "window_n_head, rope_fraction, window_rope_theta, "
                    "window_rope_fraction, rope_yarn and head_gate belong to "
                    "a stack of layer_types: set it")
            return
        kinds = set(self.layer_types)
        if len(self.layer_types) != self.n_layer or not kinds <= {
                FULL_ATTN, WINDOW_ATTN}:
            raise ConfigError(
                f"layer_types names one of {FULL_ATTN!r}, {WINDOW_ATTN!r} "
                f"for each of the {self.n_layer} layers, got "
                f"{self.layer_types}")
        if FULL_ATTN not in kinds:
            raise ConfigError(
                "a stack of layer_types needs a full attention layer: the "
                "serving pool, its audits and the benchmark's check read "
                "the rows of a position, and a ring keeps a window's")
        if WINDOW_ATTN in kinds and not self.attention_window:
            raise ConfigError(
                "a sliding_attention layer attends attention_window rows: "
                "set it")
        if not (self.rope and self.rmsnorm and self.swiglu):
            raise ConfigError(
                "a stack of layer_types rotates by kind, RMS-norms and has "
                "gated MLPs (swiglu; expert_act says what the experts' gate "
                "goes through): it needs rope, rmsnorm and swiglu")
        wnh = self.window_n_head or self.n_head
        if wnh % self.kv_heads:
            raise ConfigError(
                f"window_n_head={wnh} not divisible by the {self.kv_heads} "
                "KV heads")
        for kind in kinds:
            dim = self.rope_spec(kind)[0]
            if dim % 2 or not 0 <= dim <= self.head_dim:
                raise ConfigError(
                    f"a {kind} layer rotates {dim} of a head's "
                    f"{self.head_dim} dimensions: an even number of them, "
                    "or none (a fraction of 0: the kind carries no position)")
        if not any(self.rope_spec(kind)[0] for kind in kinds):
            raise ConfigError(
                "a stack of layer_types in which no kind rotates has no "
                "position anywhere (there is no position table under rope): "
                "give one kind a rope fraction above 0")
        if self.rope_yarn is not None and not self.rope_spec(FULL_ATTN)[0]:
            raise ConfigError(
                "rope_yarn blends the full layers' frequencies: with "
                "rope_fraction 0 they rotate nothing")
        if self.rope_yarn is not None and (
                len(self.rope_yarn) != 5 or self.rope_yarn[0] <= 1.0
                or min(self.rope_yarn[1:]) <= 0
                or self.rope_yarn[2] <= self.rope_yarn[3]):
            raise ConfigError(
                "rope_yarn is (factor > 1, original_max_position_embeddings, "
                "beta_fast > beta_slow > 0, attention_factor > 0), got "
                f"{self.rope_yarn}")
        if self.attention != "einsum":
            raise ConfigError(
                f"a stack of layer_types is built for attention='einsum': "
                f"the {self.attention!r} path takes one head count, one "
                "window and one rotation for every layer")
        if self.mixer_types is not None or self.kv_lora_rank \
                or self.n_passes > 1 or self.post_norms or self.exit_gate \
                or self.residual_dtype:
            raise ConfigError(
                "a stack of layer_types keeps per-head rows and runs its "
                "layers once: a hybrid stack (mixer_types), a latent "
                "(kv_lora_rank), passes, post_norms, an exit gate and "
                "residual_dtype are not written for it")
        if self.attn_logit_softcap or self.rope_interleave \
                or self.pp_microbatches:
            raise ConfigError(
                "a stack of layer_types takes no attn_logit_softcap, "
                "rotates the halves of what it rotates (no "
                "rope_interleave) and is not pipelined (pp_microbatches): "
                "the pipeline splits one stack of like layers")
        if self.n_experts and not self.dropless:
            raise ConfigError(
                "a stack of layer_types routes without dropping "
                "(moe_dropless, or moe_scoring='sigmoid'): the capacity "
                "route is not written for it")

    @property
    def closes_passes(self) -> bool:
        """Whether the final norm closes every pass inside the stack (a
        looped stack carries it to the next pass, an exit gate reads it), so
        that the head takes the last pass's output as it comes."""
        return self.n_passes > 1 or self.exit_gate

    @property
    def stream_dtype(self) -> str:
        """The dtype the residual stream is carried in."""
        return self.residual_dtype or self.dtype

    @property
    def cache_planes(self) -> int:
        """Planes of keys and values a token caches: one a pass and layer
        of a looped stack, ``n_layer`` where the layers run once."""
        return self.n_passes * self.n_layer

    def _validate_hybrid(self) -> None:
        """A hybrid stack's own rules, and what it does not compose with,
        a sentence each."""
        kinds = set(self.mixer_types)
        if len(self.mixer_types) != self.n_layer or not kinds <= {
                LIGHTNING, SPARSE}:
            raise ConfigError(
                f"mixer_types names one of {LIGHTNING!r}, {SPARSE!r} for "
                f"each of the {self.n_layer} layers, got {self.mixer_types}")
        if SPARSE not in kinds:
            raise ConfigError(
                "a hybrid stack needs a sparse layer: the serving pool, its "
                "audits and the benchmark's check read rows, and a stack of "
                "linear layers alone keeps none")
        if not (self.rope and self.rmsnorm and self.swiglu):
            raise ConfigError(
                "a hybrid stack has no position table, RMS-norms and a "
                "SwiGLU MLP: it needs rope, rmsnorm and swiglu")
        if LIGHTNING in kinds and (
                self.lightning_heads < 1 or self.lightning_head_dim < 2
                or self.lightning_head_dim % 2):
            raise ConfigError(
                "a lightning layer needs lightning_heads and an even "
                "lightning_head_dim (it rotates its queries and keys)")
        if self.attention != "einsum":
            raise ConfigError(
                f"a hybrid stack is built for attention='einsum': the "
                f"{self.attention!r} path takes one softmax attention for "
                "every layer and knows no state and no block selection")
        if self.attention_window or self.attn_logit_softcap:
            raise ConfigError(
                "a hybrid stack takes no attention_window and no "
                "attn_logit_softcap: the sparse mixer has its own window "
                "inside its selection and the linear one has no scores")
        if self.kv_lora_rank or self.n_experts:
            raise ConfigError(
                "a hybrid stack keeps per-head rows and a dense MLP: "
                "latent attention and experts are not written for it")
        if self.rope_interleave:
            raise ConfigError(
                "a hybrid stack's lightning layers rotate the halves of a "
                "head: rope_interleave is not written for it")
        if self.pp_microbatches:
            raise ConfigError(
                "a hybrid stack is not pipelined (pp_microbatches): the "
                "pipeline splits one stack of like layers")
        k, s, b = (self.sparse_kernel_size, self.sparse_kernel_stride,
                   self.sparse_block_size)
        if SPARSE in kinds and (
                min(k, s, b, self.sparse_topk, self.sparse_window) < 1
                or self.sparse_init_blocks < 0 or k % s or b % s
                or self.block_size % b or self.sparse_dense_len < 0):
            raise ConfigError(
                "the sparse mixer pools whole strides into kernels and "
                "whole strides into blocks: sparse_kernel_size and "
                "sparse_block_size must be multiples of "
                "sparse_kernel_stride, block_size a multiple of "
                "sparse_block_size, and every size positive")

    @property
    def head_dim(self) -> int:
        return self.head_size if self.head_size is not None \
            else self.n_embd // self.n_head

    @property
    def qk_head_dim(self) -> int:
        """A query's size against a key: nope + rope under latent
        attention, else ``head_dim``."""
        if self.kv_lora_rank:
            return self.qk_nope_head_dim + self.qk_rope_head_dim
        return self.head_dim

    @property
    def rope_dim(self) -> int:
        """The dimensions the rotary embedding turns: the shared rope key's
        under latent attention, else a whole head's."""
        return self.qk_rope_head_dim or self.head_dim

    @property
    def dense_width(self) -> int:
        """Inner width of a dense MLP."""
        return self.ffn_dim if self.ffn_dim is not None \
            else int(self.ffn_mult * self.n_embd)

    @property
    def expert_width(self) -> int:
        """Inner width of one routed expert."""
        return self.moe_ffn_dim if self.moe_ffn_dim is not None \
            else self.dense_width

    @property
    def shared_width(self) -> int:
        """Inner width of the shared experts' one MLP."""
        return self.n_shared_experts * self.expert_width

    @property
    def kv_heads(self) -> int:
        return self.n_kv_head if self.n_kv_head is not None else self.n_head

    @property
    def dropless(self) -> bool:
        """Whether an expert layer takes the dropless route
        (ops/moe.moe_dropless): under sigmoid scores always, under softmax
        scores where ``moe_dropless`` says so."""
        return bool(self.n_experts) and (
            self.moe_scoring == "sigmoid" or self.moe_dropless)

    def kind_layers(self, kind: str) -> Tuple[int, ...]:
        """The layers of a stack of ``layer_types`` of attention ``kind``,
        in order: a layer's place in this tuple is its place in its kind's
        stack of attention parameters and in its kind's leaves of the
        cache."""
        return tuple(i for i, k in enumerate(self.layer_types or ())
                     if k == kind)

    def kind_heads(self, kind: Optional[str] = None) -> Tuple[int, int, int]:
        """(query heads, KV heads, head size) of an attention layer of
        ``kind`` (None: the stack's one attention)."""
        nh = (self.window_n_head or self.n_head) if kind == WINDOW_ATTN \
            else self.n_head
        return nh, self.kv_heads, self.head_dim

    def kind_window(self, kind: Optional[str] = None) -> Optional[int]:
        """The positions a layer of ``kind`` attends, its own included
        (None: every one before it). In a stack of ``layer_types`` the
        window is the sliding layers'."""
        if self.layer_types is not None and kind != WINDOW_ATTN:
            return None
        return self.attention_window

    def rope_spec(self, kind: Optional[str] = None):
        """How a layer of ``kind`` rotates: (the dimensions of a head it
        turns, from the first on; theta; YaRN's five numbers or None)."""
        if kind == WINDOW_ATTN:
            theta = self.rope_theta if self.window_rope_theta is None \
                else self.window_rope_theta
            return (int(self.head_dim * self.window_rope_fraction), theta,
                    None)
        if self.layer_types is None:
            return self.rope_dim, self.rope_theta, None
        return (int(self.head_dim * self.rope_fraction), self.rope_theta,
                self.rope_yarn)

    @property
    def ring_rows(self) -> int:
        """Rows a window layer keeps a slot: its window, or every position
        where the window is no shorter than the context."""
        return min(self.attention_window or self.block_size, self.block_size)

    @property
    def layer_type_names(self) -> Optional[list]:
        """``layer_types`` as a configuration file spells it (a list)."""
        return None if self.layer_types is None else list(self.layer_types)

    @property
    def heads_per_layer(self) -> list:
        """Query heads a layer (published ``num_attention_heads_per_layer``)."""
        return [self.kind_heads(k)[0]
                for k in self.layer_types or (None,) * self.n_layer]

    @property
    def mlp_layer_types(self) -> list:
        """"dense" or "sparse" a layer (published ``mlp_layer_types``): the
        leading ``n_dense_layers`` dense, the others routed."""
        n_dense = self.n_dense_layers if self.n_experts else self.n_layer
        return ["dense"] * n_dense + ["sparse"] * (self.n_layer - n_dense)

    @property
    def rope_parameters(self) -> Optional[dict]:
        """The rotations of a stack of ``layer_types`` as the published
        ``rope_parameters`` spells them, a kind of layer each."""
        if self.layer_types is None:
            return None
        _, theta, yarn = self.rope_spec(FULL_ATTN)
        full = {"rope_theta": theta, "rope_type": "default",
                "partial_rotary_factor": self.rope_fraction}
        out = {}
        if yarn is not None:
            factor, original, fast, slow, attention_factor = yarn
            full = {"rope_theta": theta, "rope_type": "yarn",
                    "factor": factor,
                    "original_max_position_embeddings": original,
                    "beta_slow": slow, "beta_fast": fast,
                    "attention_factor": attention_factor,
                    "partial_rotary_factor": self.rope_fraction}
            out["original_max_position_embeddings"] = original
        _, w_theta, _ = self.rope_spec(WINDOW_ATTN)
        return {FULL_ATTN: full,
                WINDOW_ATTN: {"rope_type": "default", "rope_theta": w_theta,
                              "partial_rotary_factor":
                                  self.window_rope_fraction},
                **out}

    @property
    def rope_layout(self) -> list:
        """1 where a layer rotates its queries and keys, 0 where it carries
        no position (published ``rope_layout``): a layer's kind says."""
        return [int(bool(self.rope and self.rope_spec(k)[0]))
                for k in self.layer_types or (None,) * self.n_layer]

    @property
    def window_layout(self) -> list:
        """1 where a layer attends a sliding window, 0 where it attends
        every position before it (published ``sliding_window_layout``)."""
        return [int(self.kind_window(k) is not None)
                for k in self.layer_types or (None,) * self.n_layer]

    @property
    def router_softmax(self) -> bool:
        """Whether the router's scores are a softmax over the experts
        (published ``moe_primary_router_apply_softmax``)."""
        return bool(self.n_experts) and self.moe_scoring == "softmax"

    @property
    def mixer_names(self) -> Optional[list]:
        """``mixer_types`` as a configuration file spells it (a list)."""
        return None if self.mixer_types is None else list(self.mixer_types)

    def mixer_layers(self, kind: str) -> Tuple[int, ...]:
        """The layers of a hybrid stack that take mixer ``kind``, in order:
        a layer's place in this tuple is its place in its kind's stack of
        parameters and in its kind's leaves of the cache."""
        return tuple(i for i, m in enumerate(self.mixer_types or ())
                     if m == kind)

    def mixer_heads(self, kind: str) -> Tuple[int, int, int]:
        """(query heads, key and value heads, head size) of mixer ``kind``:
        a linear layer has a key head a query head."""
        if kind == LIGHTNING:
            return (self.lightning_heads, self.lightning_heads,
                    self.lightning_head_dim)
        return self.n_head, self.kv_heads, self.head_dim

    @property
    def residual_scale(self) -> float:
        """What every residual branch is multiplied by (``scale_depth``)."""
        if not self.scale_depth:
            return 1.0
        return self.scale_depth / (
            self.scale_depth_layers or self.n_layer) ** 0.5

    @property
    def head_divisor(self) -> float:
        """What the head's input is divided by (``dim_model_base``)."""
        return self.n_embd / self.dim_model_base if self.dim_model_base \
            else 1.0

    @property
    def sparse_config(self) -> dict:
        """The selection's sizes under the family's published names."""
        return {"kernel_size": self.sparse_kernel_size,
                "kernel_stride": self.sparse_kernel_stride,
                "block_size": self.sparse_block_size,
                "topk": self.sparse_topk,
                "window_size": self.sparse_window,
                "init_blocks": self.sparse_init_blocks,
                "dense_len": self.sparse_dense_len}

    @property
    def sparse_pooled_len(self) -> int:
        """Pooled keys a sparse layer keeps for ``block_size`` positions:
        one a stride (the last ``kernel/stride - 1`` never complete)."""
        return self.block_size // self.sparse_kernel_stride


@dataclass
class OptimizerConfig:
    """Reference OptimizerConfig (model.py:54-59): GPT-3 AdamW values."""

    learning_rate: float = 3e-4
    weight_decay: float = 0.1
    betas: Tuple[float, float] = (0.9, 0.95)
    eps: float = 1e-8
    # --- extensions: the LR schedule lore the reference README records
    # (warmup + cosine, README.md:93,125) but the reference never implements.
    schedule: str = "constant"  # "constant" | "cosine"
    warmup_steps: int = 0
    total_steps: Optional[int] = None  # required for cosine
    min_lr_ratio: float = 0.1

    def __post_init__(self) -> None:
        if isinstance(self.betas, list):
            self.betas = tuple(self.betas)  # YAML gives lists

    @classmethod
    def make(cls, **kwargs: Any) -> "OptimizerConfig":
        return cls(**_reject_unknown(cls, kwargs))


@dataclass
class DataConfig:
    """Reference DataConfig (char_dataset.py:12-17) + tokenizer selection."""

    path: str = ""
    block_size: int = 128
    train_split: float = 0.9
    truncate: float = 1.0
    # --- extensions ------------------------------------------------------
    # "char" = reference behavior; "bpe" = byte-level BPE (data/bpe.py):
    # trained on the corpus to bpe_vocab_size, or loaded from bpe_path
    # (a tokenizer saved with BPETokenizer.save, or trained earlier).
    tokenizer: str = "char"
    bpe_vocab_size: int = 512
    bpe_path: Optional[str] = None

    @classmethod
    def make(cls, **kwargs: Any) -> "DataConfig":
        cfg = cls(**_reject_unknown(cls, kwargs))
        if not (0.0 < cfg.train_split <= 1.0):
            raise ConfigError(f"train_split={cfg.train_split} outside (0, 1]")
        if not (0.0 < cfg.truncate <= 1.0):
            raise ConfigError(f"truncate={cfg.truncate} outside (0, 1]")
        if cfg.tokenizer not in ("char", "bpe"):
            raise ConfigError(f"unknown tokenizer {cfg.tokenizer!r}")
        return cfg


@dataclass
class MeshConfig:
    """Device-mesh shape for pjit/shard_map parallelism.

    Replaces the reference's implicit "one process per GPU, DDP over all"
    topology (trainer.py:71, slurm_run.sh:17-23) with an explicit named mesh:
    ``pp`` (pipeline stages), ``dp`` (data), ``fsdp`` (param shards), ``ep``
    (experts — also shards the batch, GShard-style), ``tp`` (tensor), ``sp``
    (sequence, for ring attention). -1 means "absorb all remaining devices".
    """

    pp: int = 1
    dp: int = -1
    fsdp: int = 1
    ep: int = 1
    tp: int = 1
    sp: int = 1

    @classmethod
    def make(cls, **kwargs: Any) -> "MeshConfig":
        return cls(**_reject_unknown(cls, kwargs))


@dataclass
class TrainerConfig:
    """Reference GPTTrainerConfig (trainer.py:21-29) + TPU extensions."""

    max_epochs: int = 10
    batch_size: int = 64  # global batch, split across the dp axis
    grad_norm_clip: float = 1.0
    snapshot_path: Optional[str] = None
    save_every: int = 1  # epochs between snapshots
    # kept for schema parity with the reference (unused there too —
    # the optimizer owns the LR); warn-level ignored.
    learning_rate: Optional[float] = None
    dl_num_workers: int = 0
    # --- extensions ------------------------------------------------------
    seed: int = 0
    log_every: int = 100          # steps between metric lines (reference: 100)
    eval_every: int = 1           # epochs between eval passes
    eval_batches: Optional[int] = None  # cap eval batches; None = full pass
    metrics_jsonl: Optional[str] = None  # JSONL metrics sink (§5.5 upgrade)
    tensorboard_dir: Optional[str] = None  # TensorBoard sink (§5.5 upgrade)
    # Write msgpack snapshots from a background thread (the host copy is
    # taken synchronously; serialization + object-store IO overlap training).
    async_save: bool = False
    # Multi-host msgpack saves gather the FULL state to EVERY host
    # (process_allgather) before process 0 writes the single blob — fine at
    # gpt2-124M, hopeless for billion-parameter state on a pod. Saves above
    # this many MB refuse with a pointer to the Orbax backend (sharded
    # collective writes, no gather; use a snapshot_path without the
    # .msgpack suffix). Raise deliberately if your hosts really have the
    # RAM and you want the single-blob format anyway.
    msgpack_gather_limit_mb: int = 8192
    # --- durability (training/durability.py) -----------------------------
    # Checkpoints retained in the commit manifest (keep-last-K rotation);
    # older step objects are deleted after the manifest stops referencing
    # them. >= 2 gives corruption-aware restore something to fall back to.
    keep_snapshots: int = 3
    # Retry budget for transient fsspec I/O around snapshot save/load
    # (exponential backoff + jitter; missing/permanent errors never retry).
    io_retries: int = 4
    io_retry_delay_s: float = 0.5   # base backoff delay (0 = no sleep, tests)
    # Install SIGTERM/SIGINT handlers in train(): request a stop at the
    # next step boundary, snapshot, and exit requeue-friendly (the
    # preemption contract of TPU spot/preemptible VMs). Only takes effect
    # in the main thread; False restores the previous die-mid-step behavior.
    handle_signals: bool = True
    # Accumulate gradients over this many micro-batches per optimizer step
    # (one lax.scan inside the same jitted step): activation memory scales
    # with batch_size/grad_accum_steps, semantics stay the full batch.
    grad_accum_steps: int = 1
    # ZeRO-style cross-replica weight-update sharding over the dp axis
    # (ISSUE 9, arXiv 2004.13336): reduce-scatter grads, run the optimizer
    # on the local 1/dp shard, allgather params; Adam moments are
    # physically 1/dp per device. Loss/param parity with the replicated
    # update (train.py --selftest-zero). No-op at dp=1; requires the
    # msgpack checkpoint backend (canonical-layout snapshots reshard to
    # any dp extent on restore).
    zero_dp: bool = False
    prefetch: int = 2  # background batch-prefetch depth; 0 disables
    # debug aids (SURVEY §5.2 — the reference shipped a real checkpoint race
    # and had no sanitizers): jax_debug_nans traps the first NaN/Inf inside
    # the compiled step instead of letting training silently diverge.
    debug_nans: bool = False
    mesh: MeshConfig = field(default_factory=MeshConfig)
    profile_dir: Optional[str] = None   # jax.profiler trace output
    profile_steps: Tuple[int, int] = (10, 20)
    max_steps: Optional[int] = None     # step cap (for benches/smoke runs)
    # --- telemetry (ISSUE 5) ---------------------------------------------
    # Serve /metrics (Prometheus text) + /healthz from process 0 on this
    # port; 0 disables. Negative values are rejected at bind time. Use a
    # fixed port for scrapers; the serving path's --metrics-port 0 idiom
    # (ephemeral) is for tests, where TrainerConfig keeps 0 = off because
    # a training job has no caller to read the bound port back.
    metrics_port: int = 0
    # Stream trainer spans (step/eval/snapshot timings) to this JSONL file
    # from process 0; feeds tools/trace_summary.py. None = ring buffer only.
    spans_jsonl: Optional[str] = None

    @classmethod
    def make(cls, **kwargs: Any) -> "TrainerConfig":
        kwargs = dict(kwargs)
        mesh = kwargs.pop("mesh", None)
        cfg = cls(**_reject_unknown(cls, {**kwargs}))
        if mesh is not None:
            cfg.mesh = mesh if isinstance(mesh, MeshConfig) else MeshConfig.make(**mesh)
        if isinstance(cfg.profile_steps, list):
            cfg.profile_steps = tuple(cfg.profile_steps)
        if cfg.learning_rate is not None:
            warnings.warn(
                "TrainerConfig.learning_rate is accepted for schema parity "
                "with the reference (trainer.py:21-29) but IGNORED — the "
                "optimizer owns the learning rate; set "
                "optimizer_config.learning_rate instead.",
                UserWarning,
                stacklevel=2,
            )
        return cfg


@dataclass
class ExperimentConfig:
    """The four-section bundle the reference unpacks at train.py:36-39."""

    gpt_config: GPTConfig
    optimizer_config: OptimizerConfig
    data_config: DataConfig
    trainer_config: TrainerConfig

    SECTIONS = {
        "gpt_config": GPTConfig,
        "optimizer_config": OptimizerConfig,
        "data_config": DataConfig,
        "trainer_config": TrainerConfig,
    }

    @classmethod
    def from_dict(cls, raw: Mapping[str, Any]) -> "ExperimentConfig":
        unknown = set(raw) - set(cls.SECTIONS)
        if unknown:
            raise ConfigError(
                f"unknown config section(s) {sorted(unknown)}; "
                f"valid: {sorted(cls.SECTIONS)}"
            )
        return cls(
            gpt_config=GPTConfig.make(**dict(raw.get("gpt_config", {}))),
            optimizer_config=OptimizerConfig.make(
                **dict(raw.get("optimizer_config", {}))
            ),
            data_config=DataConfig.make(**dict(raw.get("data_config", {}))),
            trainer_config=TrainerConfig.make(
                **dict(raw.get("trainer_config", {}))
            ),
        )

    def to_dict(self) -> dict[str, Any]:
        return dataclasses.asdict(self)


# ---------------------------------------------------------------------------
# YAML + CLI overrides
# ---------------------------------------------------------------------------


def _parse_override_value(text: str) -> Any:
    """Parse an override value with YAML scalar rules (1 -> int, true -> bool).

    YAML 1.1 quirk: ``1e-3`` (no dot) parses as a string; accept it as a float
    the way every CLI user expects.
    """
    value = yaml.safe_load(io.StringIO(text))
    if isinstance(value, str):
        try:
            return int(value)
        except ValueError:
            pass
        try:
            return float(value)
        except ValueError:
            pass
    return value


def apply_overrides(raw: dict[str, Any], overrides: Sequence[str]) -> dict[str, Any]:
    """Apply ``section.key=value`` dotted overrides (the Hydra CLI surface).

    ``section.key=value`` sets; ``~section.key`` deletes. Nested keys use
    further dots (e.g. ``trainer_config.mesh.dp=4``).
    """
    out = {k: (dict(v) if isinstance(v, Mapping) else v) for k, v in raw.items()}
    for ov in overrides:
        ov = ov.strip()
        if not ov:
            continue
        if ov.startswith("~"):
            path, value, delete = ov[1:], None, True
        elif "=" in ov:
            path, text = ov.split("=", 1)
            value, delete = _parse_override_value(text), False
        else:
            raise ConfigError(f"malformed override {ov!r}; want key=value or ~key")
        keys = path.split(".")
        node = out
        for k in keys[:-1]:
            nxt = node.get(k)
            if not isinstance(nxt, dict):
                nxt = dict(nxt) if isinstance(nxt, Mapping) else {}
                node[k] = nxt
            node = nxt
        if delete:
            node.pop(keys[-1], None)
        else:
            node[keys[-1]] = value
    return out


def load_config(
    path: Optional[str] = None, overrides: Sequence[str] = ()
) -> ExperimentConfig:
    """Load a YAML config file and apply CLI overrides.

    Replaces the reference's @hydra.main + manual dataclass unpacking
    (train.py:30-39) with the same observable behavior: a four-section YAML,
    each section validated into its dataclass, any key overridable from the
    command line as ``section.key=value``.
    """
    raw: dict[str, Any] = {}
    if path is not None:
        with open(path) as f:
            loaded = yaml.safe_load(f) or {}
        if not isinstance(loaded, Mapping):
            raise ConfigError(f"config file {path} is not a mapping")
        raw = {k: v for k, v in loaded.items() if k != "hydra"}
    raw = apply_overrides(raw, overrides)
    return ExperimentConfig.from_dict(raw)
