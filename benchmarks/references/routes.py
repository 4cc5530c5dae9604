"""The routed contract's table, in one place for every routed reference
(``harness/check.py``): which experts a token takes where the caller hands
over a table, no table, or a table with rows the check has not filled."""

from __future__ import annotations

import jax
import jax.numpy as jnp


def chosen_experts(scores, top_k: int, table):
    """(..., E) scores -> (..., k) int32: the table's experts, or the
    ``top_k`` best of ``scores`` where there is no table or a token's row of
    it has an entry under 0 (a layer the check has not followed yet)."""
    own = jax.lax.top_k(scores, top_k)[1]
    if table is None:
        return own
    return jnp.where((table < 0).any(-1, keepdims=True), own, table)
