"""Grouped-query attention with memory-efficient chunked scoring.

Design notes (TPU):
  * Training/prefill never materialises the full (S, T) score matrix; a
    ``lax.scan`` over query chunks bounds the transient to (Cq, T) per head
    group.  This XLA chunked formulation runs on every backend and is
    what the multi-pod dry-run lowers; the Pallas flash-attention kernel
    (``repro.kernels.flash_attention``) is tested against it but has no
    caller.
  * Local (windowed) attention slices the KV stream per query chunk, so the
    transient is (Cq, W + Cq) — this is what makes recurrentgemma's 1:2
    local-attention blocks cheap at 32k.
  * Decode uses a sequence-sharded KV cache: the cache's time axis is laid
    out over the ``model`` mesh axis (context parallelism); the softmax
    reductions become small all-reduces instead of a full KV all-gather.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.distributed.sharding import annotate
from repro.models.layers import apply_rope, dense_init, rmsnorm

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# Parameters
# ---------------------------------------------------------------------------

def init_attention(key, d_model, n_heads, n_kv, head_dim, dtype,
                   qkv_bias=False, qk_norm=False, bias=False, stack: tuple = ()):
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], stack + (d_model, n_heads * head_dim), dtype, d_model),
        "wk": dense_init(ks[1], stack + (d_model, n_kv * head_dim), dtype, d_model),
        "wv": dense_init(ks[2], stack + (d_model, n_kv * head_dim), dtype, d_model),
        "wo": dense_init(ks[3], stack + (n_heads * head_dim, d_model), dtype,
                         n_heads * head_dim),
    }
    if qkv_bias:
        p["bq"] = jnp.zeros(stack + (n_heads * head_dim,), dtype)
        p["bk"] = jnp.zeros(stack + (n_kv * head_dim,), dtype)
        p["bv"] = jnp.zeros(stack + (n_kv * head_dim,), dtype)
    if bias:
        p["bo"] = jnp.zeros(stack + (d_model,), dtype)
    if qk_norm:
        p["q_norm"] = jnp.zeros(stack + (head_dim,), jnp.float32)
        p["k_norm"] = jnp.zeros(stack + (head_dim,), jnp.float32)
    return p


def project_qkv(x, p, *, n_heads, n_kv, head_dim, positions=None,
                rope_theta=0.0, qk_norm=False):
    """x: (B, S, D) -> q (B,S,H,hd), k/v (B,S,K,hd); RoPE applied if theta>0."""
    B, S, _ = x.shape
    q = x @ p["wq"]
    k = x @ p["wk"]
    v = x @ p["wv"]
    if "bq" in p:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = annotate(q.reshape(B, S, n_heads, head_dim), "batch", None, "heads", None)
    k = annotate(k.reshape(B, S, n_kv, head_dim), "batch", None, "kv_heads", None)
    v = annotate(v.reshape(B, S, n_kv, head_dim), "batch", None, "kv_heads", None)
    if qk_norm:
        q = rmsnorm(q, p["q_norm"])
        k = rmsnorm(k, p["k_norm"])
    if rope_theta:
        if positions is None:
            positions = jnp.arange(S)[None, :]
        q = apply_rope(q, positions, rope_theta)
        k = apply_rope(k, positions, rope_theta)
    return q, k, v


def output_proj(o, p):
    y = o @ p["wo"]
    if "bo" in p:
        y = y + p["bo"]
    return y


# ---------------------------------------------------------------------------
# Core attention math
# ---------------------------------------------------------------------------

def _scores_softmax_out(q, k, v, mask, scale, probs_dtype=jnp.float32):
    """q: (B,Cq,K,G,hd); k,v: (B,T,K,hd); mask: (B|1, 1|K, 1|G, Cq, T) bool."""
    with jax.named_scope("attn_core"):
        # explicit .astype(f32) casts (NOT preferred_element_type) so the
        # backward cotangents revert to bf16 at the cast boundary — with
        # preferred_element_type the whole backward chain (and its TP
        # all-reduces) runs in fp32 (2x link + HBM bytes; §Perf iteration 1)
        s = jnp.einsum("bqkgd,btkd->bkgqt", q.astype(jnp.float32),
                       k.astype(jnp.float32))
        s = s * scale
        s = jnp.where(mask, s, NEG_INF)
        # max/sum in fp32 for stability; the materialised normalised probs
        # can be bf16 (perf knob: halves the score-chain HBM bytes)
        m = jax.lax.stop_gradient(jnp.max(s, axis=-1, keepdims=True))
        if jnp.dtype(probs_dtype) == jnp.bfloat16:
            s = (s - m).astype(jnp.bfloat16)       # one bf16 materialisation
            p = jnp.exp(s.astype(jnp.float32))
        else:
            p = jnp.exp(s - m)
        denom = jnp.sum(p, axis=-1, keepdims=True)
        p = (p / denom).astype(probs_dtype)
        o = jnp.einsum("bkgqt,btkd->bqkgd", p.astype(v.dtype), v)
    return o


def attend(q, k, v, *, causal=True, window=0, q_chunk=512, q_offset=0,
           probs_dtype=jnp.float32):
    """Chunked attention.

    q: (B, S, H, hd);  k, v: (B, T, K, hd).  ``q_offset`` is the absolute
    position of q[0] within the KV stream (prefill: 0; enc-dec cross: n/a
    with causal=False).  Returns (B, S, H*hd).
    """
    B, S, H, hd = q.shape
    T, K = k.shape[1], k.shape[2]
    G = H // K
    scale = 1.0 / math.sqrt(hd)
    q = q.reshape(B, S, K, G, hd)

    q_chunk = min(q_chunk, S)
    if S % q_chunk:                      # pad S to a chunk multiple
        pad = q_chunk - S % q_chunk
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0), (0, 0)))
    nC = q.shape[1] // q_chunk
    qc = q.reshape(B, nC, q_chunk, K, G, hd).transpose(1, 0, 2, 3, 4, 5)

    kv_pos = jnp.arange(T)

    def chunk_fn(c, q_c):
        # q_c: (B, Cq, K, G, hd)
        q_pos = q_offset + c * q_chunk + jnp.arange(q_chunk)
        if window and causal:
            # slice KV to [start, start + W + Cq) around the chunk
            span = window + q_chunk
            start = jnp.clip(c * q_chunk + q_chunk - span + q_offset, 0,
                             max(T - span, 0))
            if span >= T:
                k_s, v_s, kv_p = k, v, kv_pos
            else:
                k_s = jax.lax.dynamic_slice_in_dim(k, start, span, axis=1)
                v_s = jax.lax.dynamic_slice_in_dim(v, start, span, axis=1)
                kv_p = start + jnp.arange(span)
        else:
            k_s, v_s, kv_p = k, v, kv_pos
        m = jnp.ones((q_chunk, k_s.shape[1]), bool)
        if causal:
            m &= q_pos[:, None] >= kv_p[None, :]
        if window:
            m &= q_pos[:, None] - kv_p[None, :] < window
        o = _scores_softmax_out(q_c, k_s, v_s, m[None, None, None], scale,
                                probs_dtype)
        return c + 1, o

    _, oc = jax.lax.scan(chunk_fn, 0, qc)
    o = oc.transpose(1, 0, 2, 3, 4, 5).reshape(B, nC * q_chunk, H * hd)
    return o[:, :S]


def decode_attend(q, k_cache, v_cache, pos):
    """Single-token decode. q: (B, 1, H, hd); caches: (B, T, K, hd) with the
    time axis sequence-sharded over the ``model`` mesh axis.  ``pos`` is the
    index of the current token (attends to [0, pos])."""
    B, _, H, hd = q.shape
    T, K = k_cache.shape[1], k_cache.shape[2]
    G = H // K
    scale = 1.0 / math.sqrt(hd)
    qg = q.reshape(B, 1, K, G, hd)
    with jax.named_scope("attn_core"):
        k_cache = annotate(k_cache, "batch", "kv_seq", None, None)
        v_cache = annotate(v_cache, "batch", "kv_seq", None, None)
        s = jnp.einsum("bqkgd,btkd->bkgqt", qg, k_cache,
                       preferred_element_type=jnp.float32) * scale
        s = annotate(s, "batch", None, None, None, "kv_seq")
        mask = (jnp.arange(T) <= pos)[None, None, None, None, :]
        s = jnp.where(mask, s, NEG_INF)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bkgqt,btkd->bqkgd", p.astype(v_cache.dtype), v_cache)
    return o.reshape(B, 1, H * hd)


def cache_update(k_cache, v_cache, k_new, v_new, pos):
    """Write k/v at time index ``pos`` (decode) or [0, S) (prefill)."""
    k_cache = jax.lax.dynamic_update_slice_in_dim(
        k_cache, k_new.astype(k_cache.dtype), pos, axis=1)
    v_cache = jax.lax.dynamic_update_slice_in_dim(
        v_cache, v_new.astype(v_cache.dtype), pos, axis=1)
    return k_cache, v_cache


# ---------------------------------------------------------------------------
# Full blocks
# ---------------------------------------------------------------------------

def attention_block(x, p, cfg, *, positions=None, causal=True, window=0,
                    q_chunk=512):
    """Train/prefill self-attention over (B, S, D)."""
    q, k, v = project_qkv(
        x, p, n_heads=cfg.n_heads, n_kv=cfg.n_kv_heads, head_dim=cfg.head_dim,
        positions=positions, rope_theta=cfg.rope_theta, qk_norm=cfg.qk_norm)
    o = attend(q, k, v, causal=causal, window=window, q_chunk=q_chunk,
               probs_dtype=jnp.dtype(getattr(cfg, "attn_probs_dtype", "float32")))
    return output_proj(o, p), (k, v)


def attention_decode_block(x, p, cfg, kv_cache, pos, *, window=0):
    """Decode self-attention for one token.  kv_cache: dict(k, v)."""
    q, k, v = project_qkv(
        x, p, n_heads=cfg.n_heads, n_kv=cfg.n_kv_heads, head_dim=cfg.head_dim,
        positions=jnp.full((x.shape[0], 1), pos),
        rope_theta=cfg.rope_theta, qk_norm=cfg.qk_norm)
    T = kv_cache["k"].shape[1]
    if window and window <= T:
        # ring buffer: during warmup (pos < T) entries [0, pos] are valid;
        # once full, every slot holds one of the last T (>= window) tokens.
        write_pos = jnp.mod(pos, T)
        valid_upto = jnp.minimum(pos, T - 1)
    else:
        write_pos = pos
        valid_upto = pos
    kc, vc = cache_update(kv_cache["k"], kv_cache["v"], k, v, write_pos)
    o = decode_attend(q, kc, vc, valid_upto)
    return output_proj(o, p), {"k": kc, "v": vc}


