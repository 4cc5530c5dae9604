"""Lightning linear attention (Qin et al.; MiniMax-01): a decaying state a
head in place of a cache of rows.

Per head, with ``lambda_h = exp(-slope_h)``:

    S_t = lambda_h S_{t-1} + k_t^T v_t        (head_dim, head_dim), float32
    o_t = q_t S_t * scale

The state is all a layer keeps of its past, whatever the context's length:
it has no position axis, and a stale one is read where a stale row behind a
mask is not. Two forms of one recurrence:

* :func:`lightning_scan` (prefill, training): the sequence in chunks of
  ``CHUNK`` positions. Inside a chunk the positions attend each other
  through a decay matrix (two matmuls); every chunk's own sum of
  ``k^T v`` is one batched matmul; only the chunks' states are a
  sequential scan, of elementwise steps; and every position reads the
  state its chunk started from in one more batched matmul.
* :func:`lightning_step` (decode): one position a lane, the recurrence as
  written.

``valid`` masks positions that are no tokens (a bucket's padding, a parked
lane): they neither decay the state nor add to it, so the state after a
padded chunk is the state after its real tokens. The decay is counted in
valid tokens, by a running count, so the mask need not be a prefix.

Matmuls take their operands in the compute dtype and accumulate in float32;
the state and its decay stay float32 throughout.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

#: positions that attend each other through one decay matrix
CHUNK = 256


def slopes(n_heads: int) -> jax.Array:
    """(H,) float32 decay slopes, ``2^(-8 (h + 1) / H)``: head 0 forgets
    fastest. ``lambda_h = exp(-slope_h)``."""
    h = jnp.arange(1, n_heads + 1, dtype=jnp.float32)
    return 2.0 ** (-8.0 * h / n_heads)


@jax.named_scope("lightning_scan")
def lightning_scan(
    q: jax.Array,           # (B, T, H, D)
    k: jax.Array,           # (B, T, H, D)
    v: jax.Array,           # (B, T, H, D)
    state: jax.Array,       # (B, H, D, D) float32: S before the first token
    slope: jax.Array,       # (H,) float32
    scale: float,
    valid: Optional[jax.Array] = None,   # (B, T) bool
) -> Tuple[jax.Array, jax.Array]:
    """Returns (o (B, T, H, D) float32, the state after the last valid
    token (B, H, D, D) float32)."""
    b, t, h, d = q.shape
    c = min(CHUNK, t)
    pad = -t % c
    if valid is None:
        valid = jnp.ones((b, t), bool)
    if pad:
        q, k, v = (jnp.pad(a, ((0, 0), (0, pad), (0, 0), (0, 0)))
                   for a in (q, k, v))
        valid = jnp.pad(valid, ((0, 0), (0, pad)))
    n = (t + pad) // c
    q, k, v = (a.reshape(b, n, c, h, d) for a in (q, k, v))
    live = valid.reshape(b, n, c)
    # valid tokens of the chunk up to and including each position
    count = jnp.cumsum(live, axis=-1, dtype=jnp.float32)       # (B, N, C)
    total = count[..., -1]                                     # (B, N)
    rate = slope[:, None]                                      # (H, 1)

    # inside a chunk: position i reads j <= i, decayed by the valid tokens
    # between them
    lag = count[..., :, None] - count[..., None, :]            # (B, N, C, C)
    seen = (jnp.arange(c)[:, None] >= jnp.arange(c)) & live[..., None, :]
    decay = jnp.where(seen[:, :, None],
                      jnp.exp(-rate[:, :, None] * lag[:, :, None]), 0.0)
    scores = jnp.einsum("bnihd,bnjhd->bnhij", q, k,
                        preferred_element_type=jnp.float32)
    intra = jnp.einsum("bnhij,bnjhd->bnihd", (scores * decay).astype(v.dtype),
                       v, preferred_element_type=jnp.float32)

    # what each chunk adds to the state, decayed to the chunk's end
    to_end = jnp.where(live[:, :, None], jnp.exp(
        -rate * (total[..., None] - count)[:, :, None]), 0.0)  # (B, N, H, C)
    k_end = (k.astype(jnp.float32)
             * jnp.moveaxis(to_end, 2, 3)[..., None]).astype(k.dtype)
    added = jnp.einsum("bnjhd,bnjhe->bnhde", k_end, v,
                       preferred_element_type=jnp.float32)
    shrink = jnp.exp(-rate[:, 0] * total[..., None])           # (B, N, H)

    def carry_state(s, chunk):
        add, keep = chunk
        return keep[..., None, None] * s + add, s

    state, before = jax.lax.scan(
        carry_state, state.astype(jnp.float32),
        (jnp.moveaxis(added, 1, 0), jnp.moveaxis(shrink, 1, 0)))
    before = jnp.moveaxis(before, 0, 1)                        # (B, N, H, D, D)

    # every position reads the state its chunk started from
    from_start = jnp.exp(-rate * count[:, :, None])            # (B, N, H, C)
    q_start = (q.astype(jnp.float32)
               * jnp.moveaxis(from_start, 2, 3)[..., None]).astype(q.dtype)
    inter = jnp.einsum("bnihd,bnhde->bnihe", q_start,
                       before.astype(q.dtype),
                       preferred_element_type=jnp.float32)
    out = ((intra + inter) * scale).reshape(b, n * c, h, d)
    return out[:, :t], state


@jax.named_scope("lightning_step")
def lightning_step(
    q: jax.Array,           # (B, 1, H, D)
    k: jax.Array,
    v: jax.Array,
    state: jax.Array,       # (B, H, D, D) float32
    slope: jax.Array,       # (H,) float32
    scale: float,
    valid: Optional[jax.Array] = None,   # (B, 1) bool
) -> Tuple[jax.Array, jax.Array]:
    """One position a lane: (o (B, 1, H, D) float32, the new state). A lane
    that is not ``valid`` keeps its state as it was."""
    kf, vf = k[:, 0].astype(jnp.float32), v[:, 0].astype(jnp.float32)
    new = jnp.exp(-slope)[:, None, None] * state \
        + kf[..., :, None] * vf[..., None, :]
    if valid is not None:
        new = jnp.where(valid[:, 0, None, None, None], new, state)
    out = jnp.einsum("bhd,bhde->bhe", q[:, 0].astype(jnp.float32), new,
                     precision=jax.lax.Precision.HIGHEST) * scale
    return out[:, None], new
