"""Jitted wrapper for the WKV6 chunk kernel."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.rwkv_scan.ref import wkv_ref
from repro.kernels.rwkv_scan.rwkv_scan import wkv_pallas


@partial(jax.jit, static_argnames=("use_pallas", "interpret", "chunk"))
def wkv(r, k, v, logw, u, use_pallas: bool = False, interpret: bool = False,
        chunk: int = 16):
    """r,k,v,logw: (BH, T, N) fp32; u: (BH, N)."""
    if use_pallas:
        T = r.shape[1]
        pad = (-T) % chunk
        if pad:
            def z(a):
                return jnp.pad(a, ((0, 0), (0, pad), (0, 0)))
            out = wkv_pallas(z(r), z(k), z(v),
                             jnp.pad(logw, ((0, 0), (0, pad), (0, 0)),
                                     constant_values=-1e-4),
                             u, chunk=chunk, interpret=interpret)
            return out[:, :T]
        return wkv_pallas(r, k, v, logw, u, chunk=chunk, interpret=interpret)
    return wkv_ref(r, k, v, logw, u)
