"""Jitted public wrapper for the fused cache-lookup kernel.

``use_pallas=True`` runs the compiled kernel; the CPU test suite passes
``interpret=True`` to run the same kernel in the Pallas interpreter.
Cache tiers are padded with zero rows before dispatch (to one row for the
jnp oracle, to a positive multiple of the kernel's row block for the
kernel) — padding rows have no ids mapped to them, so they are never
selected.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.cache_lookup.cache_lookup import ROWS, fused_lookup
from repro.kernels.cache_lookup.ref import fused_lookup_ref


def _pad_rows(tier, multiple: int):
    n = tier.shape[0]
    pad = max(multiple, -(-n // multiple) * multiple) - n
    return jnp.pad(tier, ((0, pad), (0, 0))) if pad else tier


@partial(jax.jit, static_argnames=("use_pallas", "interpret"))
def fused_cache_lookup(ids, loc, slot, device_tier, host_tier,
                       use_pallas: bool = False, interpret: bool = False):
    """Fused lookup + dedup gather + miss-list emit; see cache_lookup.py
    for the 7-tuple output contract."""
    ids = jnp.asarray(ids, jnp.int32)
    loc = jnp.asarray(loc, jnp.int32)
    slot = jnp.asarray(slot, jnp.int32)
    dev = jnp.asarray(device_tier)
    host = jnp.asarray(host_tier)
    if use_pallas:
        return fused_lookup(ids, loc, slot, _pad_rows(dev, ROWS),
                            _pad_rows(host, ROWS), interpret=interpret)
    return fused_lookup_ref(ids, loc, slot, _pad_rows(dev, 1),
                            _pad_rows(host, 1))
