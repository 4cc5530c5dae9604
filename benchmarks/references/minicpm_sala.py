"""MiniCPM-SALA's decoder (openbmb, ``mixer_types`` of ``lightning-attn`` and
``minicpm4``) in plain ``jax.numpy`` and float32: the reference of
``configs/minicpm-sala.json``. Every matmul runs under
``jax.default_matmul_precision("highest")``. It shares no code with the
program and keeps no cache: the linear layers are the recurrence as it is
written, one position at a time; the sparse layers spell the selection out.

The equations, ``rms(x, g) = x / sqrt(mean(x^2) + eps) * g``, ``s =
scale_depth / sqrt(scale_depth_num_hidden_layers)`` (the published depth, not
a cut's):

  x = scale_emb * embed[tokens]                     no position table
  per layer, by ``mixer_types[layer]``:
    u = rms(x, input_norm)
    ``lightning-attn``:
      q, k, v = u q_proj, u k_proj, u v_proj        ``lightning_nh`` heads of
                                                    ``lightning_head_dim``
      q, k = rms(q, q_norm), rms(k, k_norm)         per head
      q, k = rotate(q), rotate(k)                   halves, ``rope_theta``
      S_t = lambda_h S_{t-1} + k_t^T v_t            per head, S before the
                                                    first token 0
      o_t = q_t S_t / sqrt(lightning_head_dim)
      lambda_h = exp(-2^(-8 (h + 1) / lightning_nh))    h = 0 .. nh - 1
      m = (sigmoid(u gate_proj) * rms(o, o_norm)) o_proj    o: all heads
    ``minicpm4``:
      q = u q_proj  (``num_attention_heads`` heads)
      k, v = u k_proj, u v_proj  (``num_key_value_heads`` heads)
      q, k = rms(q, q_norm), rms(k, k_norm)         no rotation
      Kc_j = mean(k[stride j : stride j + kernel])  whole windows only
      for the query at position t:
        t < dense_len: attend every row <= t
        else, per KV head:
          p_j = softmax over the j with stride j + kernel - 1 <= t of
                q_t . Kc_j / sqrt(head_dim), per query head; summed over the
                query heads of the KV head
          block b (``block_size`` rows) scores max of p_j over the j whose
                window touches it: j in [b B/stride - (kernel/stride - 1),
                (b + 1) B/stride - 1]
          chosen: ``topk`` blocks in all, of those that start at or before
                t: the first ``init_blocks``, the blocks b >= (t - window_size
                + 1) // B, then the best-scoring others, ties to the earlier
          attend (softmax, 1 / sqrt(head_dim)) the rows <= t of the chosen
      m = (sigmoid(u gate_proj) * o) o_proj
    h = x + s m
    x = h + s (silu(v gate) * (v up)) down,  v = rms(h, post_norm)
  x = rms(x, norm) / (hidden_size / dim_model_base);  logits = x head

``rotate`` turns the halves ``(x[i], x[i + d/2])`` by ``t * theta^(-2i/d)``.

What is returned as cached (``harness/check.py``): ``ks``, ``vs`` of the
``minicpm4`` layers alone, in the stack's order, (L_sparse, B, T, 1, KV *
hd): the keys normed and not rotated, as that mixer attends them, and a
row's KV heads side by side, as the program's pool keeps them (the TPU tiles
a (2, 128) pair so that no matmul reads it; every sum of the check is over
both axes, so the errors are the per-head layout's). A
``lightning-attn`` layer caches no rows; its state is held by every sparse
layer's rows above it and by the logits. ``states`` (not part of the
contract; the repo's tests read it) returns every linear layer's S after the
last position.

Computed in blocks of ``BLOCK`` positions, the sequence padded up to whole
blocks (causal: padding after the end changes nothing before it), so that
32,768 positions fit beside a server's weights and pool: a layer's
projections, gate and MLP exist for one block at a time, a linear layer
carries S from block to block, and a sparse layer first makes all keys and
values (two KV heads: small), then attends ``QUERY_BLOCK`` queries at a
time against all rows.

The twin (``harness/check.py``). ``hidden(..., act_dtype=jnp.bfloat16)`` is
the same code with every value rounded to that type where the published
model holds that type: the embedding's output, every matmul's output, the
normed and rotated queries and keys, a linear layer's read-out ``o_t``, the
gated product, the SwiGLU's inner product, every residual sum, every
RMSNorm's output. The norms, the softmax, the selection's scores and choice,
the gates' sigmoid, the state ``S`` and its decay stay float32 inside, as the
configuration's ``departures`` say the program keeps them. At ``None``
nothing is rounded: the function of before, bit for bit.

``sizes`` holds the published keys ``mixer_types``, ``num_attention_heads``,
``num_key_value_heads``, ``lightning_nh``, ``lightning_head_dim``,
``rope_theta``, ``rms_norm_eps``, ``scale_emb``, ``scale_depth``,
``dim_model_base``, ``hidden_size``, and the file's own
``scale_depth_num_hidden_layers`` and ``sparse_config`` (``kernel_size``,
``kernel_stride``, ``block_size``, ``topk``, ``window_size``,
``init_blocks``, ``dense_len``).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from benchmarks.references.rounding import rounder

#: positions whose projections and MLP exist at one time
BLOCK = 512
#: queries of a sparse layer whose scores against all rows exist at one time
QUERY_BLOCK = 64

LIGHTNING, SPARSE = "lightning-attn", "minicpm4"


def weights_from_program(params) -> dict:
    """The program's parameter pytree (``models/gpt.py``) under the names
    above. Renames only: the arrays are shared, nothing is copied or cast.
    Each mixer's layers are stacked in the order ``mixer_types`` has them."""
    def stack(blk, lightning):
        out = {"input_norm": blk["ln1_scale"], "post_norm": blk["ln2_scale"],
               "q_proj": blk["wq"], "k_proj": blk["wk"], "v_proj": blk["wv"],
               "q_norm": blk["q_norm_scale"], "k_norm": blk["k_norm_scale"],
               "gate_proj": blk["w_og"], "o_proj": blk["wo"],
               "gate": blk["w_gate"], "up": blk["w_up"],
               "down": blk["w_down"]}
        if lightning:
            out["o_norm"] = blk["o_norm_scale"]
        return out

    out = {"embed": params["wte"], "head": params["head"],
           "norm": params["lnf_scale"]}
    if "lightning_blocks" in params:
        out[LIGHTNING] = stack(params["lightning_blocks"], True)
    if "sparse_blocks" in params:
        out[SPARSE] = stack(params["sparse_blocks"], False)
    return out


def _f32(a):
    return a.astype(jnp.float32)


class _Layer:
    """One layer's weights, read out of its mixer's stack at each use."""

    def __init__(self, stack, at):
        self.stack, self.at = stack, at

    def __getitem__(self, name):
        return self.stack[name][self.at]


def _rms(x, g, eps):
    return x / jnp.sqrt((x ** 2).mean(-1, keepdims=True) + eps) * _f32(g)


def _rotate(x, positions, theta):
    """(B, T, H, d) turned by ``positions`` (T,), the halves paired."""
    d = x.shape[-1]
    angle = _f32(positions)[:, None] * theta ** (
        -jnp.arange(d // 2, dtype=jnp.float32) / (d // 2))
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    a, b = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def _in_blocks(x, size):
    """(B, T, ...) -> (T / size, B, size, ...)."""
    b, t = x.shape[:2]
    return jnp.moveaxis(x.reshape(b, t // size, size, *x.shape[2:]), 1, 0)


def _whole(blocks):
    """The inverse of ``_in_blocks``."""
    n, b, size = blocks.shape[:3]
    return jnp.moveaxis(blocks, 0, 1).reshape(b, n * size, *blocks.shape[3:])


def _mlp_onto(h, w, s, eps, r):
    v = r(_rms(h, w["post_norm"], eps))
    inner = r(r(jax.nn.silu(r(v @ _f32(w["gate"])))) * r(v @ _f32(w["up"])))
    return r(h + s * r(inner @ _f32(w["down"])))


def _lightning_layer(x, w, sizes, s, r):
    """(B, T, d) -> ((B, T, d), S after the last position (B, H, hd, hd))."""
    nh, hd = sizes["lightning_nh"], sizes["lightning_head_dim"]
    eps, theta = sizes["rms_norm_eps"], float(sizes["rope_theta"])
    b = x.shape[0]
    decay = jnp.exp(-2.0 ** (
        -8.0 * jnp.arange(1, nh + 1, dtype=jnp.float32) / nh))

    def a_block(state, item):
        x_b, start = item
        size = x_b.shape[1]
        u = r(_rms(x_b, w["input_norm"], eps))
        positions = start + jnp.arange(size)
        q, k, v = (r(u @ _f32(w[p])).reshape(b, size, nh, hd)
                   for p in ("q_proj", "k_proj", "v_proj"))
        q = r(_rotate(r(_rms(q, w["q_norm"], eps)), positions, theta))
        k = r(_rotate(r(_rms(k, w["k_norm"], eps)), positions, theta))

        def a_position(st, qkv):
            q_t, k_t, v_t = qkv                         # (B, H, hd) each
            st = decay[:, None, None] * st \
                + k_t[..., :, None] * v_t[..., None, :]
            return st, (q_t[..., :, None] * st).sum(-2) / math.sqrt(hd)

        state, o = jax.lax.scan(
            a_position, state,
            tuple(jnp.moveaxis(a, 1, 0) for a in (q, k, v)), unroll=4)
        o = r(jnp.moveaxis(o, 0, 1).reshape(b, size, nh * hd))
        m = r(r(jax.nn.sigmoid(r(u @ _f32(w["gate_proj"])))
                * r(_rms(o, w["o_norm"], eps))) @ _f32(w["o_proj"]))
        return state, _mlp_onto(r(x_b + s * m), w, s, eps, r)

    size = min(BLOCK, x.shape[1])
    starts = jnp.arange(0, x.shape[1], size)
    state, out = jax.lax.scan(
        a_block, jnp.zeros((b, nh, hd, hd), jnp.float32),
        (_in_blocks(x, size), starts))
    return _whole(out), state


def _chosen_blocks(q, pooled, positions, sc):
    """(B, Q, H, hd) queries at ``positions`` (Q,) against pooled keys (B,
    J, KV, hd) -> (B, Q, KV, blocks) bool, the selection spelled out."""
    b, n_q, h, hd = q.shape
    n_pooled, kv = pooled.shape[1], pooled.shape[2]
    kernel, stride = sc["kernel_size"], sc["kernel_stride"]
    size, per = sc["block_size"], sc["block_size"] // sc["kernel_stride"]
    n_blocks = n_pooled // per
    t = positions[:, None]                                      # (Q, 1)

    scores = jnp.einsum("bqkgd,bjkd->bqkgj",
                        q.reshape(b, n_q, kv, h // kv, hd), pooled) \
        / math.sqrt(hd)
    seen = (jnp.arange(n_pooled) * stride + kernel - 1 <= t)    # (Q, J)
    seen = seen[None, :, None, None, :]
    scores = jnp.where(seen, scores, -jnp.inf)
    top = jnp.where(seen.any(-1, keepdims=True),
                    scores.max(-1, keepdims=True), 0.0)
    e = jnp.where(seen, jnp.exp(scores - top), 0.0)
    total = e.sum(-1, keepdims=True)
    p = jnp.where(total > 0, e / jnp.where(total > 0, total, 1.0), 0.0)
    p = p.sum(3)                                                # (B, Q, KV, J)

    # block b: the pooled keys from per * b - (kernel / stride - 1) to
    # per * (b + 1) - 1, those that exist
    reach = per + kernel // stride - 1
    idx = (jnp.arange(n_blocks) * per - (kernel // stride - 1))[:, None] \
        + jnp.arange(reach)                                     # (Nb, reach)
    exists = (idx >= 0) & (idx < n_pooled)
    touched = jnp.where(exists, p[..., jnp.clip(idx, 0, n_pooled - 1)],
                        -jnp.inf)                   # (B, Q, KV, Nb, reach)
    score = touched.max(-1)

    blocks = jnp.arange(n_blocks)
    starts_by_t = blocks * size <= t                            # (Q, Nb)
    forced = (blocks < sc["init_blocks"]) | (
        blocks >= (t - sc["window_size"] + 1) // size)
    rank = jnp.where(forced[None, :, None], jnp.inf, score)
    rank = jnp.where(starts_by_t[None, :, None], rank, -jnp.inf)
    order = jnp.argsort(-rank, axis=-1, stable=True)    # best first, ties
    place = jnp.argsort(order, axis=-1, stable=True)    # to the earlier
    chosen = (place < sc["topk"]) | (t < sc["dense_len"])[None, :, None]
    return chosen & starts_by_t[None, :, None]


def _sparse_layer(x, w, sizes, s, r):
    """(B, T, d) -> ((B, T, d), keys (B, T, KV, hd), values)."""
    nh, kv = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    eps, sc = sizes["rms_norm_eps"], sizes["sparse_config"]
    hd = w["q_proj"].shape[-1] // nh
    b, t, _ = x.shape
    kernel, stride, size = (sc["kernel_size"], sc["kernel_stride"],
                            sc["block_size"])
    if t % size:
        raise ValueError(f"{t} positions are no whole blocks of {size}")

    def keys_values(x_b):
        u = r(_rms(x_b, w["input_norm"], eps))
        k = r(_rms(r(u @ _f32(w["k_proj"])).reshape(b, -1, kv, hd),
                   w["k_norm"], eps))
        return k, r(u @ _f32(w["v_proj"])).reshape(b, -1, kv, hd)

    big = min(BLOCK, t)
    k, v = (_whole(a) for a in jax.lax.map(keys_values, _in_blocks(x, big)))
    # whole windows only; the table is filled up to one entry a stride with
    # entries no query sees
    n_whole = (t - kernel) // stride + 1
    windows = jnp.arange(n_whole)[:, None] * stride + jnp.arange(kernel)
    pooled = k[:, windows].mean(2)                      # (B, J, KV, hd)
    pooled = jnp.pad(pooled, ((0, 0), (0, t // stride - n_whole),
                              (0, 0), (0, 0)))

    def some_queries(item):
        x_q, start = item
        n_q = x_q.shape[1]
        u = r(_rms(x_q, w["input_norm"], eps))
        positions = start + jnp.arange(n_q)
        q = r(_rms(r(u @ _f32(w["q_proj"])).reshape(b, n_q, nh, hd),
                   w["q_norm"], eps))
        chosen = _chosen_blocks(q, pooled, positions, sc)   # (B, Q, KV, Nb)
        rows = jnp.arange(t)
        allowed = chosen[..., rows // size] \
            & (rows <= positions[:, None])[None, :, None]   # (B, Q, KV, T)
        scores = jnp.einsum("bqkgd,bskd->bqkgs",
                            q.reshape(b, n_q, kv, nh // kv, hd), k) \
            / math.sqrt(hd)
        scores = jnp.where(allowed[:, :, :, None], scores, -jnp.inf)
        o = r(jnp.einsum("bqkgs,bskd->bqkgd", jax.nn.softmax(scores, -1), v))
        m = r(r(jax.nn.sigmoid(r(u @ _f32(w["gate_proj"])))
                * o.reshape(b, n_q, nh * hd)) @ _f32(w["o_proj"]))
        return _mlp_onto(r(x_q + s * m), w, s, eps, r)

    small = math.gcd(QUERY_BLOCK, t)
    out = jax.lax.map(some_queries,
                      (_in_blocks(x, small), jnp.arange(0, t, small)))
    return _whole(out), k, v


def _layers(weights, tokens, sizes, act_dtype=None):
    """-> (x as the head takes it, ks, vs, states)."""
    sc = sizes["sparse_config"]
    r = rounder(act_dtype)
    s = sizes["scale_depth"] / math.sqrt(
        sizes.get("scale_depth_num_hidden_layers")
        or len(sizes["mixer_types"]))
    t = tokens.shape[1]
    # whole blocks of positions, and of the selection's rows
    unit = min(BLOCK, -(-t // sc["block_size"]) * sc["block_size"])
    unit = math.lcm(unit, sc["block_size"])
    tokens = jnp.pad(tokens, ((0, 0), (0, -t % unit)))
    ks, vs, states, seen = [], [], [], {LIGHTNING: 0, SPARSE: 0}
    with jax.default_matmul_precision("highest"):
        x = r(sizes["scale_emb"] * _f32(weights["embed"][tokens]))
        for kind in sizes["mixer_types"]:
            # a layer's leaves are read out of the stack where they are
            # used, inside the loops over blocks: sliced out here, each
            # would be copied whole into every loop
            w = _Layer(weights[kind], seen[kind])
            seen[kind] += 1
            if kind == LIGHTNING:
                x, state = _lightning_layer(x, w, sizes, s, r)
                states.append(state)
            elif kind == SPARSE:
                x, k, v = _sparse_layer(x, w, sizes, s, r)
                # as cached: a row's KV heads side by side
                ks.append(k[:, :t].reshape(k.shape[0], t, 1, -1))
                vs.append(v[:, :t].reshape(v.shape[0], t, 1, -1))
            else:
                raise ValueError(f"no mixer {kind!r} is written here")
        x = r(_rms(x[:, :t], weights["norm"], sizes["rms_norm_eps"])) \
            / (sizes["hidden_size"] / sizes["dim_model_base"])
    return x, ks, vs, states


def hidden(weights, tokens, sizes, act_dtype=None):
    """tokens (B, T) int32 -> (the hidden states as the head takes them (B,
    T, d): after the final RMSNorm and the division by ``hidden_size /
    dim_model_base``; the normed, unrotated keys and the values of the
    sparse layers, (L_sparse, B, T, 1, KV * hd) each). ``act_dtype``: the
    twin (module docstring); None: float32 throughout."""
    x, ks, vs, _ = _layers(weights, tokens, sizes, act_dtype)
    return x, jnp.stack(ks), jnp.stack(vs)


def states(weights, tokens, sizes):
    """Every linear layer's state after the last token, (L_linear, B, H, hd,
    hd). Only for a ``tokens`` of whole blocks (no padding is added to the
    recurrence's end)."""
    return jnp.stack(_layers(weights, tokens, sizes)[3])


def logits(weights, x):
    """``hidden``'s states (..., d) -> float32 logits (..., V)."""
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
