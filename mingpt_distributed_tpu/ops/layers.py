"""Elementwise / normalisation / MLP building blocks.

TPU-native replacements for the torch.nn modules the reference composes
(/root/reference/mingpt/model.py:171-231): pure functions over arrays, mixed
precision by construction — normalisations and softmax in float32, matmuls in
the configured compute dtype (bfloat16 on the MXU) — and everything traceable
under jit so XLA fuses the elementwise chains into the surrounding matmuls.

The MLP here is the *intended* reference MLP — Linear -> GELU -> Linear ->
Dropout (upstream minGPT, reference README.md:99). The reference as shipped
ordered it Linear -> Linear -> GELU (bug B5, model.py:179-184), collapsing to
a single linear map; that bug is deliberately not reproduced.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp


def layer_norm(x: jax.Array, scale: jax.Array, bias: jax.Array, eps: float = 1e-5) -> jax.Array:
    """LayerNorm in float32 regardless of input dtype (TPU numerics rule)."""
    dtype = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.var(x, axis=-1, keepdims=True)
    y = (x - mu) * jax.lax.rsqrt(var + eps)
    return (y * scale + bias).astype(dtype)


def rms_norm(x: jax.Array, scale: jax.Array, eps: float = 1e-5) -> jax.Array:
    """RMSNorm (Llama-retrofit toggle, BASELINE config #5)."""
    dtype = x.dtype
    x = x.astype(jnp.float32)
    y = x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps)
    return (y * scale).astype(dtype)


def gelu(x: jax.Array) -> jax.Array:
    """tanh-approximate GELU — the GPT-2 variant (HF ``gelu_new``), so
    from_pretrained logits match the OpenAI weights bit-for-bit-ish."""
    return jax.nn.gelu(x, approximate=True)


def dropout(
    x: jax.Array, rate: float, key: Optional[jax.Array], deterministic: bool
) -> jax.Array:
    """Inverted dropout; identity when deterministic or rate == 0."""
    if deterministic or rate == 0.0:
        return x
    assert key is not None, "dropout in train mode needs a PRNG key"
    keep = 1.0 - rate
    mask = jax.random.bernoulli(key, keep, x.shape)
    return jnp.where(mask, x / keep, 0.0).astype(x.dtype)


def dense(x: jax.Array, w: jax.Array, b: Optional[jax.Array] = None) -> jax.Array:
    """x @ w (+ b) with the matmul in x's compute dtype (bf16 on the MXU)."""
    y = x @ w.astype(x.dtype)
    if b is not None:
        y = y + b.astype(y.dtype)
    return y


def mlp_gelu(
    x: jax.Array,
    w_fc: jax.Array,
    b_fc: Optional[jax.Array],
    w_proj: jax.Array,
    b_proj: Optional[jax.Array],
) -> jax.Array:
    """The transformer MLP: fc -> GELU -> proj (correct B5 ordering)."""
    return dense(gelu(dense(x, w_fc, b_fc)), w_proj, b_proj)


def mlp_swiglu(
    x: jax.Array, w_gate: jax.Array, w_up: jax.Array, w_down: jax.Array
) -> jax.Array:
    """SwiGLU MLP (Llama retrofit): down(silu(gate(x)) * up(x)). The gate
    and up products stay in the matmuls' float32 accumulators through the
    activation and are rounded to x's dtype once, as the product the down
    matmul takes (three roundings fewer a layer than rounding each: a fifth
    of a bf16 block's error against a float32 reference, PERF.md PR 30)."""
    gate = jnp.dot(x, w_gate.astype(x.dtype),
                   preferred_element_type=jnp.float32)
    up = jnp.dot(x, w_up.astype(x.dtype), preferred_element_type=jnp.float32)
    return dense((jax.nn.silu(gate) * up).astype(x.dtype), w_down)
