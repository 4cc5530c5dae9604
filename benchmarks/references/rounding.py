"""The twin's rounding, in one place for every reference: a reference's
``hidden(..., act_dtype=)`` rounds with this and with nothing else, so what
"rounded to the stated type" means is the yardstick's and not each file's."""

from __future__ import annotations

import jax
import jax.numpy as jnp


def rounder(act_dtype):
    """float32 -> float32, rounded to ``act_dtype``'s exponent and mantissa;
    None: nothing is touched. ``reduce_precision`` and not a cast there and
    back: the TPU compiler takes a pair of converts for excess precision it
    may keep, and a twin of casts read a quarter less error on the chip than
    the same twin on the CPU (PERF.md, PR 36)."""
    if act_dtype is None:
        return lambda a: a
    info = jnp.finfo(act_dtype)
    return lambda a: jax.lax.reduce_precision(a, info.nexp, info.nmant)
