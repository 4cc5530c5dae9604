"""DeepSeek-V3's decoder (``model_type`` ``deepseek_v3``) in plain
``jax.numpy`` and float32: the reference of ``configs/kanana-2-30b-a3b.json``
and of any configuration of that family without a query latent. Every matmul
runs under ``jax.default_matmul_precision("highest")``. It shares no code
with the program, keeps no cache and attends **non-absorbed**, as the
architecture is published: the latent is taken up to every head's own keys
and values. The program's cached forward never builds those (it attends the
latents themselves), so the two sides agree only if the absorption is right.

The equations, ``rms(x) = x / sqrt(mean(x^2) + eps)``:

  x = embed[tokens]                                 no position table
  per layer:
    h = rms(x) * input_norm
    q = h q_proj -> (H, nope + rope) = [q_nope | q_pe]       no query latent
    [c | k_pe] = h kv_a_proj                        (kv_lora_rank | rope)
    c = rms(c) * kv_a_norm
    q_pe, k_pe = rotate(q_pe), rotate(k_pe)         one k_pe for all heads
    [k_nope | v] = c kv_b_proj -> (H, nope | v_head_dim)
    score = (q_nope . k_nope + q_pe . k_pe) / sqrt(nope + rope)
    x = x + (softmax(causal(score)) v) o_proj
    h = rms(x) * post_norm
    layers before ``first_k_dense_replace``:
      x = x + (silu(h gate_proj) * (h up_proj)) down_proj
    the others:
      s = sigmoid(h router)                         E scores, float32
      the chosen k: those of the table the caller hands over, or, with no
      table or where a token's row of it has an entry under 0 (a layer the
      check has not followed yet), the k largest of s + bias
      (``e_score_correction_bias``; the
      group limit of ``noaux_tc`` is not written: ``n_group`` and
      ``topk_group`` must be 1, which is no limit)
      g = s[chosen] (the scores, never the bias), over their sum + 1e-20
      under ``norm_topk_prob``, times ``routed_scaling_factor``
      x = x + sum over the chosen e of g_e * expert_e(h) + shared(h)
      expert_e(h) = (silu(h eg[e]) * (h eu[e])) ed[e]; shared the same MLP
      of ``n_shared_experts`` times the width
  x = rms(x) * norm;  logits = x head               untied, no softcap

``rotate`` turns the neighbours ``(x[2i], x[2i+1])`` by the angle
``t * theta^(-2i / rope)`` and leaves them where they were
(``rope_interleave`` true; DeepSeek-V3's own ``apply_rotary_emb``, which
views the pairs as complex numbers). False would be the halves ``(x[i],
x[i + rope/2])``.

Every chosen expert computes its token: there is no capacity and nothing
drops.

What is returned as cached (``harness/check.py``): ``ks`` the rotated
``k_pe`` (L, B, T, 1, rope) and ``vs`` the normed latent ``c`` (L, B, T, 1,
kv_lora_rank): the two things the architecture caches a token. Under
absorption the latents are the values the attention averages and the first
part of every key.

The routed contract. ``hidden`` takes an optional table of experts (L, B, T,
k) and returns a fourth array (L, B, T, E): for an expert layer **what the
architecture takes its k best of, s + bias**, not a logit before the
sigmoid: the check ranks it to find the reference's own choice and measures
its spread to fence which other choices it may try, and both have to be of
the quantity the program ranks. For a dense layer the table is ignored and
the row returned has its first k entries +1 and the rest -1: a gap of 2, so
the check finds no near-tie where there is no choice. The tolerances and
the margin are the yardstick's; this file states none.

Weights arrive in the program's dtype (bfloat16 for kanana) and are cast up
where they are used. The experts run as a scan over blocks of
``EXPERT_BLOCK`` experts, read out of the stacked leaves a block at a time,
every token through every expert of the block under a gate that is zero
where the expert was not chosen (a mask, not a gather), so no copy of a
layer's 604M expert weights is ever whole, in float32 or as stored;
the attention runs over blocks of ``QUERY_BLOCK`` queries, so the (H, T, T)
scores of a 4k prompt never are either. Both are the same sums in another
order.

``sizes`` holds the published keys ``num_attention_heads``,
``kv_lora_rank``, ``qk_nope_head_dim``, ``qk_rope_head_dim``,
``v_head_dim``, ``rope_theta``, ``rope_interleave``, ``rms_norm_eps``,
``first_k_dense_replace``, ``num_experts_per_tok``, ``norm_topk_prob``,
``routed_scaling_factor``, ``scoring_func`` and, where present,
``q_lora_rank``, ``n_group``, ``topk_group``.

The twin (``harness/check.py``). ``hidden(..., act_dtype=jnp.bfloat16)`` is
the same code with every value rounded to that type where the published
model holds that type: the embedding's output, every matmul's output, the
rotated keys and queries, the SwiGLU's inner product, every residual sum,
every RMSNorm's output. The norms, the softmax, the router's scores, the
choice and the gates stay float32 inside, as the configuration's
``departures`` say the program keeps them, and every sum of a matmul is
still float32 at ``highest``. At ``None`` nothing is rounded: the function
of before, bit for bit. The twin takes the same table of experts as the
unrounded run, so the two differ by rounding alone and never by a route.

Departures from the published architecture: none intended.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from benchmarks.references.rounding import rounder
from benchmarks.references.routes import chosen_experts

#: experts whose float32 weights exist at one time
EXPERT_BLOCK = 8
#: queries whose scores against all keys exist at one time
QUERY_BLOCK = 1024


def weights_from_program(params) -> dict:
    """The program's parameter pytree (``models/gpt.py``) under the names
    above. Renames only: the arrays are shared, nothing is copied or cast.
    ``dense`` stacks the layers before ``first_k_dense_replace``, ``moe``
    the others."""
    def attention(blk):
        return {"input_norm": blk["ln1_scale"], "post_norm": blk["ln2_scale"],
                "q_proj": blk["wq"], "kv_a_proj": blk["w_kv_a"],
                "kv_a_norm": blk["kv_norm_scale"], "kv_b_proj": blk["w_kv_b"],
                "o_proj": blk["wo"]}

    dense, moe = params.get("dense_blocks"), params["blocks"]
    out = {
        "embed": params["wte"], "head": params["head"],
        "norm": params["lnf_scale"],
        "moe": {**attention(moe), "router": moe["w_router"],
                "bias": moe["e_bias"], "eg": moe["w_eg"], "eu": moe["w_e1"],
                "ed": moe["w_e2"], "shared_gate": moe["w_sg"],
                "shared_up": moe["w_su"], "shared_down": moe["w_sd"]},
    }
    if dense is not None:
        out["dense"] = {**attention(dense), "gate_proj": dense["w_gate"],
                        "up_proj": dense["w_up"], "down_proj": dense["w_down"]}
    return out


def _f32(a):
    return a.astype(jnp.float32)


def _rms(x, g, eps):
    return x / jnp.sqrt((x ** 2).mean(-1, keepdims=True) + eps) * _f32(g)


def _rotate(x, theta, interleave):
    """(B, T, H, e) turned by position, pair i by t * theta^(-2i / e)."""
    t, e = x.shape[1], x.shape[-1]
    angle = jnp.arange(t, dtype=jnp.float32)[:, None] * theta ** (
        -jnp.arange(e // 2, dtype=jnp.float32) / (e // 2))
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    if interleave:
        a, b = x[..., 0::2], x[..., 1::2]
        return jnp.stack([a * cos - b * sin, b * cos + a * sin],
                         -1).reshape(x.shape)
    a, b = x[..., :e // 2], x[..., e // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def _swiglu(h, gate, up, down, r):
    return r(r(jax.nn.silu(r(h @ _f32(gate))) * r(h @ _f32(up)))
             @ _f32(down))


def _attend(q, k, v):
    """Causal softmax attention, (B, T, H, *) each: a block of queries
    against the keys up to the block's end."""
    t = q.shape[1]
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


def _routed(h, w, stack, at, sizes, chosen, r):
    """(B, T, d) -> (the routed experts' sum (B, T, d), s + bias (B, T, E)).
    ``w`` is the layer's router and bias; the experts are read a block at a
    time out of ``stack``, all expert layers' (L, E, ...), at layer ``at``."""
    if sizes["scoring_func"] != "sigmoid":
        raise ValueError("this reference scores with a sigmoid")
    if sizes.get("n_group", 1) != 1 or sizes.get("topk_group", 1) != 1:
        raise ValueError("a group-limited choice is not written here")
    top_k = sizes["num_experts_per_tok"]
    s = jax.nn.sigmoid(h @ _f32(w["router"]))
    select = s + _f32(w["bias"])
    chosen = chosen_experts(select, top_k, chosen)
    g = jnp.take_along_axis(s, chosen, -1)
    if sizes["norm_topk_prob"]:
        g = g / (g.sum(-1, keepdims=True) + 1e-20)
    g = g * sizes["routed_scaling_factor"]
    e = s.shape[-1]
    gates = (jax.nn.one_hot(chosen, e) * g[..., None]).sum(-2)   # (B, T, E)

    n = math.gcd(e, EXPERT_BLOCK)
    # every layer's experts in blocks of n: (L * E / n, n, ...), free views
    blocks = {k: stack[k].reshape(-1, n, *stack[k].shape[2:])
              for k in ("eg", "eu", "ed")}
    gate_blocks = jnp.moveaxis(gates.reshape(*gates.shape[:-1], e // n, n),
                               -2, 0)

    def some_experts(total, item):
        i, gate = item
        eg, eu, ed = (_f32(blocks[k][at * (e // n) + i])
                      for k in ("eg", "eu", "ed"))
        inner = r(jax.nn.silu(r(jnp.einsum("btd,edf->btef", h, eg)))
                  * r(jnp.einsum("btd,edf->btef", h, eu)))
        return total + r(jnp.einsum("btef,efd,bte->btd", inner, ed, gate)), \
            None

    total, _ = jax.lax.scan(some_experts, jnp.zeros_like(h),
                            (jnp.arange(e // n), gate_blocks))
    return r(total), select


def hidden(weights, tokens, sizes, experts=None, act_dtype=None):
    """tokens (B, T) int32 -> (final-RMSNorm hidden (B, T, d); the rotated
    shared rope keys (L, B, T, 1, rope) and the normed latents (L, B, T, 1,
    kv_lora_rank) of every layer; what every layer takes its k best of (L,
    B, T, E)). ``experts`` (L, B, T, k) int32: the experts every token takes
    in every layer (a dense layer's row is ignored; a row with an entry
    under 0: that token's k best of s + bias there); None: the k best of
    s + bias. ``act_dtype``: the twin (module docstring); None: float32
    throughout."""
    if sizes.get("q_lora_rank") is not None:
        raise ValueError("a query latent (q_lora_rank) is not written here")
    n_head, rank = sizes["num_attention_heads"], sizes["kv_lora_rank"]
    nope, rope = sizes["qk_nope_head_dim"], sizes["qk_rope_head_dim"]
    v_dim, eps = sizes["v_head_dim"], sizes["rms_norm_eps"]
    theta, interleave = float(sizes["rope_theta"]), sizes["rope_interleave"]
    n_dense, top_k = sizes["first_k_dense_replace"], sizes["num_experts_per_tok"]
    r = rounder(act_dtype)
    b, t = tokens.shape
    n_experts = weights["moe"]["router"].shape[-1]
    n_layer = n_dense + weights["moe"]["router"].shape[0]
    no_choice = jnp.broadcast_to(
        jnp.where(jnp.arange(n_experts) < top_k, 1.0, -1.0),
        (b, t, n_experts))

    ks, vs, router = [], [], []
    with jax.default_matmul_precision("highest"):
        x = r(_f32(weights["embed"][tokens]))
        for layer in range(n_layer):
            stack, at = (weights["dense"], layer) if layer < n_dense \
                else (weights["moe"], layer - n_dense)
            # this layer's leaves, but for the experts': those stay stacked
            # and are read a block at a time (_routed)
            w = {k: a[at] for k, a in stack.items()
                 if k not in ("eg", "eu", "ed")}
            h = r(_rms(x, w["input_norm"], eps))
            q = r(h @ _f32(w["q_proj"])).reshape(b, t, n_head, nope + rope)
            kv_a = r(h @ _f32(w["kv_a_proj"]))
            c = r(_rms(kv_a[..., :rank], w["kv_a_norm"], eps))
            k_pe = r(_rotate(kv_a[..., None, rank:], theta, interleave))
            q_pe = r(_rotate(q[..., nope:], theta, interleave))
            kv_b = r(c @ _f32(w["kv_b_proj"])).reshape(
                b, t, n_head, nope + v_dim)
            k = jnp.concatenate([
                kv_b[..., :nope], jnp.broadcast_to(k_pe, (b, t, n_head, rope))
            ], -1)
            att = r(_attend(jnp.concatenate([q[..., :nope], q_pe], -1), k,
                            kv_b[..., nope:]))
            x = r(x + r(att.reshape(b, t, n_head * v_dim) @ _f32(w["o_proj"])))
            h = r(_rms(x, w["post_norm"], eps))
            if layer < n_dense:
                x = r(x + _swiglu(h, w["gate_proj"], w["up_proj"],
                                  w["down_proj"], r))
                router.append(no_choice)
            else:
                out, select = _routed(
                    h, w, stack, at, sizes,
                    None if experts is None else experts[layer], r)
                x = r(x + out + _swiglu(h, w["shared_gate"], w["shared_up"],
                                        w["shared_down"], r))
                router.append(select)
            ks.append(k_pe)
            vs.append(c[..., None, :])
        x = r(_rms(x, weights["norm"], eps))
    return x, jnp.stack(ks), jnp.stack(vs), jnp.stack(router)


def logits(weights, x):
    """Hidden states (..., d) -> float32 logits (..., V)."""
    with jax.default_matmul_precision("highest"):
        return x @ _f32(weights["head"])


def loss(weights, tokens, targets, sizes):
    """Mean cross-entropy over the positions whose target is not -1."""
    x = hidden(weights, tokens, sizes)[0]
    logp = jax.nn.log_softmax(logits(weights, x), -1)
    valid = targets != -1
    picked = jnp.take_along_axis(
        logp, jnp.where(valid, targets, 0)[..., None], -1)[..., 0]
    return -(picked * valid).sum() / valid.sum()
