"""Ouro's looped decoder (``model_type`` ``ouro``) in plain ``jax.numpy`` and
float32: the reference of ``configs/ouro-2.6b.json``. Every matmul runs
under ``jax.default_matmul_precision("highest")``. It shares no code with the
program and keeps no cache: a ``lax.scan`` over the passes around a
``lax.scan`` over the stacked layers, so that a layer's float32 weights
exist one layer at a time and every plane's keys and values are written
once, where they are returned (nothing of their size is joined up after).

The equations, ``rms(x) = x / sqrt(mean(x^2) + eps)``, R = ``total_ut_steps``,
L = ``num_hidden_layers``:

  h = embed[tokens]                                 no position table
  for pass t = 0 .. R-1, for layer l = 0 .. L-1, the same weights every pass:
    u = rms(h) * input_layernorm_l
    q, k, v = u q_proj, u k_proj, u v_proj          no bias; H and KV heads of head_dim
    q, k = rotate(q), rotate(k)                     at the token's position, every pass alike
    a = h + rms(softmax(causal(q . k / sqrt(head_dim))) v o_proj) * input_layernorm_2_l
    u = rms(a) * post_attention_layernorm_l
    h = a + rms((silu(u gate_proj) * (u up_proj)) down_proj) * post_attention_layernorm_2_l
  after layer L-1 of a pass: h_t = rms(h) * norm, and **h = h_t**: the normed
    output is what pass t+1 starts from, what the gate reads and what the head reads
  gate logit z_t = gate_w . h_t + gate_b            one Linear(hidden, 1), float32
  logits = h_{R-1} head                             untied, no softcap

``rotate`` turns the halves ``(x[i], x[i + head_dim/2])`` by the angle ``t *
theta^(-2i / head_dim)`` (rotate-half, the family's ``apply_rotary_pos_emb``).

The exit: ``g_t = sigmoid(z_t)``, ``p_t = g_t * prod_{j<t}(1 - g_j)``, the
last pass taking what is left; a token leaves at the first pass whose
cumulative ``p`` reaches ``early_exit_threshold``. At the published
threshold of 1 no token leaves before the last pass, and that is the only
value written here: ``hidden`` refuses another. The gate's logits are
returned a pass so that a gate left out of the program shows.

What is returned as cached (``harness/check.py``): ``ks`` and ``vs`` (R * L,
B, T, KV, head_dim), the rotated keys and the values of **every pass of every
layer**, plane ``t * L + l``: pass t of layer l attends its own keys and
values and no other pass's.

Weights arrive in the program's dtype (bfloat16) and are cast up a layer at
a time inside the scan; the attention runs over blocks of ``QUERY_BLOCK``
queries. Both are the same sums in another order.

``sizes`` holds the published keys ``num_attention_heads``,
``num_key_value_heads``, ``head_dim``, ``rope_theta``, ``rms_norm_eps``,
``total_ut_steps`` and ``early_exit_threshold``.

The twin (``harness/check.py``). ``hidden(..., act_dtype=jnp.bfloat16)`` is
the same code with every value rounded to that type where the program holds
that type: the embedding's output, every matmul's output, the rotated queries
and keys, the SwiGLU's inner product, the output of every norm of a
sublayer's input or output. The residual stream (each sum ``h + ...`` and the
final norm's output that is carried to the next pass and read by the gate)
stays float32, and so do the norms, the softmax and the gate inside, as the
configuration's ``departures`` say the program keeps them (the head's input,
``x`` as returned, is rounded by ``check``'s caller's ``logits`` no further:
the program rounds it to bfloat16 where it enters the head's matmul, a
rounding of one value a logit that the twin's rows do not read). At ``None``
nothing is rounded.

Departures from the published architecture: ``attention_bias`` is taken as
false (the catalog's row carries none; ``assumed``). ``loss`` is the last
pass's cross-entropy, which is what the contract of ``harness/check.py`` asks
of a reference and **not** the family's training objective (an expected loss
over the exits with an entropy term, which the config does not state); no
cell trains this model and the program refuses to.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from benchmarks.references.rounding import rounder

#: queries whose scores against all keys exist at one time
QUERY_BLOCK = 1024


def weights_from_program(params) -> dict:
    """The program's parameter pytree (``models/gpt.py``) under the source's
    names. Renames only: the arrays are shared, nothing is copied or cast.
    ``layers`` stacks the L layers along the first axis."""
    blk = params["blocks"]
    return {
        "embed": params["wte"], "head": params["head"],
        "norm": params["lnf_scale"],
        "gate_w": params["exit_gate_w"], "gate_b": params["exit_gate_b"],
        "layers": {
            "input_layernorm": blk["ln1_scale"],
            "input_layernorm_2": blk["ln1_post_scale"],
            "post_attention_layernorm": blk["ln2_scale"],
            "post_attention_layernorm_2": blk["ln2_post_scale"],
            "q_proj": blk["wq"], "k_proj": blk["wk"], "v_proj": blk["wv"],
            "o_proj": blk["wo"], "gate_proj": blk["w_gate"],
            "up_proj": blk["w_up"], "down_proj": blk["w_down"],
        },
    }


def _f32(a):
    return a.astype(jnp.float32)


def _rms(x, g, eps):
    return x / jnp.sqrt((x ** 2).mean(-1, keepdims=True) + eps) * _f32(g)


def _rotate(x, theta):
    """(B, T, H, hd) turned by position, the halves (i, i + hd/2) by
    t * theta^(-2i / hd)."""
    t, hd = x.shape[1], x.shape[-1]
    angle = jnp.arange(t, dtype=jnp.float32)[:, None] * theta ** (
        -jnp.arange(hd // 2, dtype=jnp.float32) / (hd // 2))
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    a, b = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def _attend(q, k, v):
    """Causal softmax attention, q (B, T, H, hd) over k, v (B, T, KV, hd),
    each KV head serving H / KV query heads: a block of queries against the
    keys up to the block's end."""
    t, groups = q.shape[1], q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, groups, axis=2), jnp.repeat(v, groups, axis=2)
    scale = 1.0 / math.sqrt(q.shape[-1])
    out = []
    for a in range(0, t, QUERY_BLOCK):
        b = min(a + QUERY_BLOCK, t)
        scores = jnp.einsum("bqhd,bkhd->bhqk", q[:, a:b], k[:, :b]) * scale
        causal = jnp.arange(a, b)[:, None] >= jnp.arange(b)
        scores = jnp.where(causal, scores, -jnp.inf)
        out.append(jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1),
                              v[:, :b]))
    return jnp.concatenate(out, axis=1)


def hidden(weights, tokens, sizes, act_dtype=None):
    """tokens (B, T) int32 -> (the last pass's final-RMSNorm hidden (B, T,
    d); the rotated keys and the values of every pass of every layer (R * L,
    B, T, KV, head_dim), plane ``t * L + l``; the exit gate's logits a pass
    (R, B, T) float32). ``act_dtype``: the twin (module docstring); None:
    float32 throughout."""
    if sizes["early_exit_threshold"] != 1:
        raise ValueError(
            "only early_exit_threshold 1 is written here: every token runs "
            "every pass")
    n_head, n_kv = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    hd, eps = sizes["head_dim"], sizes["rms_norm_eps"]
    theta, passes = float(sizes["rope_theta"]), sizes["total_ut_steps"]
    r = rounder(act_dtype)
    b, t = tokens.shape

    def layer(h, w):
        u = r(_rms(h, w["input_layernorm"], eps))
        q = r(u @ _f32(w["q_proj"])).reshape(b, t, n_head, hd)
        k = r(u @ _f32(w["k_proj"])).reshape(b, t, n_kv, hd)
        v = r(u @ _f32(w["v_proj"])).reshape(b, t, n_kv, hd)
        q, k = r(_rotate(q, theta)), r(_rotate(k, theta))
        att = r(r(_attend(q, k, v)).reshape(b, t, n_head * hd)
                @ _f32(w["o_proj"]))
        a = h + r(_rms(att, w["input_layernorm_2"], eps))       # float32 sum
        u = r(_rms(a, w["post_attention_layernorm"], eps))
        inner = r(jax.nn.silu(r(u @ _f32(w["gate_proj"])))
                  * r(u @ _f32(w["up_proj"])))
        mlp = r(inner @ _f32(w["down_proj"]))
        h = a + r(_rms(mlp, w["post_attention_layernorm_2"], eps))
        return h, (k, v)

    def one_pass(h, _):
        h, (k, v) = jax.lax.scan(layer, h, weights["layers"])   # same weights
        h = _rms(h, weights["norm"], eps)   # carried to the next pass, float32
        gate = h @ _f32(weights["gate_w"]) + _f32(weights["gate_b"])
        return h, (k, v, gate)

    with jax.default_matmul_precision("highest"):
        h = r(_f32(weights["embed"][tokens]))
        h, (ks, vs, gates) = jax.lax.scan(one_pass, h, None, length=passes)
    # (R, L, ...) -> (R * L, ...): plane t * L + l
    return (h, ks.reshape(-1, *ks.shape[2:]), vs.reshape(-1, *vs.shape[2:]),
            gates)


def exit_mass(gate_logits):
    """(R, ...) gate logits -> (R, ...) ``p_t = g_t * prod_{j<t}(1 - g_j)``,
    the last pass taking what is left."""
    g = jax.nn.sigmoid(gate_logits)
    p, left = [], jnp.ones_like(g[0])
    for step in range(g.shape[0] - 1):
        p.append(g[step] * left)
        left = left * (1.0 - g[step])
    return jnp.stack(p + [left])


def logits(weights, x):
    """Hidden states (..., d) -> float32 logits (..., V)."""
    with jax.default_matmul_precision("highest"):
        return x @ _f32(weights["head"])


def loss(weights, tokens, targets, sizes):
    """Mean cross-entropy of the last pass's logits over the positions whose
    target is not -1 (module docstring: not the family's objective)."""
    x = hidden(weights, tokens, sizes)[0]
    logp = jax.nn.log_softmax(logits(weights, x), -1)
    valid = targets != -1
    picked = jnp.take_along_axis(
        logp, jnp.where(valid, targets, 0)[..., None], -1)[..., 0]
    return -(picked * valid).sum() / valid.sum()
