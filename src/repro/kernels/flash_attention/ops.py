"""Jitted GQA-aware wrapper for the flash-attention kernel."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention.flash_attention import flash_attention
from repro.kernels.flash_attention.ref import attention_ref


@partial(jax.jit, static_argnames=("causal", "use_pallas", "interpret"))
def mha(q, k, v, causal: bool = True, use_pallas: bool = False,
        interpret: bool = False):
    """q: (B, S, H, hd); k, v: (B, T, K, hd) with H % K == 0."""
    B, S, H, hd = q.shape
    T, K = k.shape[1], k.shape[2]
    G = H // K
    kr = jnp.repeat(k, G, axis=2)
    vr = jnp.repeat(v, G, axis=2)
    qf = q.transpose(0, 2, 1, 3).reshape(B * H, S, hd)
    kf = kr.transpose(0, 2, 1, 3).reshape(B * H, T, hd)
    vf = vr.transpose(0, 2, 1, 3).reshape(B * H, T, hd)
    if use_pallas:
        o = flash_attention(qf, kf, vf, causal=causal, interpret=interpret,
                            block_q=min(128, S), block_k=min(128, T))
    else:
        o = attention_ref(qf, kf, vf, causal)
    return o.reshape(B, H, S, hd).transpose(0, 2, 1, 3)
