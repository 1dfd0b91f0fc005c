"""Jitted wrapper: segment mean/sum used by the GNN aggregators."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.segment_agg.ref import segment_sum_ref
from repro.kernels.segment_agg.segment_agg import segment_sum_pallas


@partial(jax.jit, static_argnames=("n_segments", "use_pallas", "interpret"))
def segment_sum(msgs, seg_ids, n_segments: int, use_pallas: bool = False,
                interpret: bool = False):
    if use_pallas:
        return segment_sum_pallas(msgs, seg_ids, n_segments,
                                  interpret=interpret)
    return segment_sum_ref(msgs, seg_ids, n_segments)


@partial(jax.jit, static_argnames=("n_segments", "use_pallas", "interpret"))
def segment_mean(msgs, seg_ids, n_segments: int, use_pallas: bool = False,
                 interpret: bool = False):
    s = segment_sum(msgs, seg_ids, n_segments, use_pallas, interpret)
    ones = jnp.ones((msgs.shape[0], 1), msgs.dtype)
    cnt = segment_sum(ones, seg_ids, n_segments, use_pallas, interpret)
    return s / jnp.maximum(cnt, 1.0)
