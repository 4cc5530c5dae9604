"""Optimizer factory: AdamW with the GPT-2 decay/no-decay parameter partition.

Replaces the reference's ``create_optimizer`` (/root/reference/mingpt/model.py:
62-122), which walks torch named_modules to split parameters into a decayed
group (Linear/attention projection weights) and an un-decayed group (all
biases, LayerNorm weights, token/positional embeddings), asserts the split is
a partition of all parameters (model.py:97-104), and builds a two-group AdamW
(model.py:107-121) with the GPT-3 hyperparameters (lr 3e-4, wd 0.1, betas
(0.9, 0.95) — OptimizerConfig, model.py:54-59).

TPU-native mechanism: there are no modules — the partition is a *pytree mask*
derived from parameter names, fed to ``optax.add_decayed_weights``. The
partition-completeness assert survives as ``decay_mask``'s refusal to classify
an unknown parameter name. Gradient clipping (the reference does it in the
trainer, trainer.py:129, with the deprecated-API bug B11) is folded into the
same optax chain as ``clip_by_global_norm``, so one fused update kernel does
clip -> Adam -> decay -> lr.

The LR schedule implements the warmup+cosine lore the reference README records
(README.md:93,125) but the reference never built (its LR is constant).
"""

from __future__ import annotations

from typing import Any, Callable, Optional

import jax
import optax

from mingpt_distributed_tpu.config import OptimizerConfig
from mingpt_distributed_tpu.utils.pytree import leaf_name

# Parameter-name -> weight-decay classification, mirroring the reference's
# module-walk rules (model.py:78-93):
#   decay:    every matmul weight (Linear / attention projections / LM head)
#   no-decay: every bias, every norm scale/bias, token + positional embeddings
_DECAY_NAMES = frozenset(
    {"wq", "wk", "wv", "wo", "w_fc", "w_proj", "w_gate", "w_up", "w_down",
     "head", "w_router", "w_e1", "w_e2", "w_eg",  # MoE router/experts are matmuls
     "w_sg", "w_su", "w_sd",  # the shared expert
     "w_kv_a", "w_kv_b",  # latent attention's down- and up-projections
     "w_og"}  # a hybrid stack's output gate
)
_NO_DECAY_NAMES = frozenset(
    {
        "wte", "wpe",  # embeddings (reference: Embedding + pos_embedding no-decay)
        "bq", "bk", "bv", "bo", "b_fc", "b_proj",  # biases
        "ln1_scale", "ln1_bias", "ln2_scale", "ln2_bias",
        "lnf_scale", "lnf_bias", "kv_norm_scale",
        "q_norm_scale", "k_norm_scale", "o_norm_scale",  # a hybrid stack's
        "e_bias",  # the router's choice bias: a bias
    }
)


def decay_mask(params: Any) -> Any:
    """Boolean pytree: True where weight decay applies.

    Raises on any parameter name that matches neither rule set — the pytree
    analogue of the reference's partition-completeness asserts
    (model.py:97-104): no parameter may be silently un-classified.
    """

    def classify(path, leaf):
        name = leaf_name(path)
        if name in _DECAY_NAMES:
            return True
        if name in _NO_DECAY_NAMES:
            return False
        raise ValueError(
            f"parameter {jax.tree_util.keystr(path)!r} not covered by the "
            f"decay/no-decay partition rules"
        )

    return jax.tree_util.tree_map_with_path(classify, params)


def lr_schedule(cfg: OptimizerConfig) -> Callable[[Any], Any]:
    """constant (reference behavior) or linear-warmup + cosine decay."""
    if cfg.schedule == "constant":
        if cfg.warmup_steps:
            return optax.linear_schedule(0.0, cfg.learning_rate, cfg.warmup_steps)
        return optax.constant_schedule(cfg.learning_rate)
    if cfg.schedule == "cosine":
        if cfg.total_steps is None:
            raise ValueError("cosine schedule needs optimizer_config.total_steps")
        return optax.warmup_cosine_decay_schedule(
            init_value=0.0,
            peak_value=cfg.learning_rate,
            warmup_steps=cfg.warmup_steps,
            decay_steps=cfg.total_steps,
            end_value=cfg.learning_rate * cfg.min_lr_ratio,
        )
    raise ValueError(f"unknown schedule {cfg.schedule!r}")


def make_optimizer(
    cfg: OptimizerConfig,
    grad_norm_clip: Optional[float] = None,
    schedule: Optional[Callable[[Any], Any]] = None,
) -> optax.GradientTransformation:
    """clip -> scale_by_adam -> masked weight decay -> lr, as one chain.

    ``schedule`` lets the caller share ONE schedule object between the
    optimizer and metrics reporting, so the logged lr is the applied lr by
    construction (defaults to ``lr_schedule(cfg)``).
    """
    parts = []
    if grad_norm_clip is not None and grad_norm_clip > 0:
        parts.append(optax.clip_by_global_norm(grad_norm_clip))
    parts += [
        optax.scale_by_adam(b1=cfg.betas[0], b2=cfg.betas[1], eps=cfg.eps),
        optax.add_decayed_weights(cfg.weight_decay, mask=decay_mask),
        optax.scale_by_learning_rate(schedule or lr_schedule(cfg)),
    ]
    return optax.chain(*parts)
