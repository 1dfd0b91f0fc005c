"""Jitted public wrapper for the cache-gather kernel with CPU fallback."""
from __future__ import annotations

from functools import partial

import jax

from repro.kernels.gather.gather import gather_rows
from repro.kernels.gather.ref import gather_rows_ref


@partial(jax.jit, static_argnames=("use_pallas", "interpret"))
def cache_gather(table, idx, use_pallas: bool = False, interpret: bool = False):
    """Device-tier cache lookup.  ``use_pallas=True`` runs the compiled
    kernel; the CPU test suite adds ``interpret=True``.  No caller in the
    system passes ``use_pallas=True`` yet."""
    if use_pallas:
        return gather_rows(table, idx, interpret=interpret)
    return gather_rows_ref(table, idx)
