"""Plain reference of the GAT forward pass for the tests (Velickovic et al.,
2018, arXiv:1710.10903): a loop over destination rows and an explicit list
of each row's in-edges, in float32 ``jax.numpy`` at ``highest`` matmul
precision.  It shares nothing with ``repro.gnn.models`` but the parameter
layout.

A batch is the sampler's: feature rows and per hop ``(src_pos, dst_pos,
edge_mask)``, outer hop first; layer 1 runs on the inner block.  Each
layer: ``z = h W`` viewed as (N, K, F'); row ``i`` attends over the sources
of its masked-in edges and itself, with the score ``leaky_relu(<z_i,
a_dst> + <z_j, a_src>, 0.2)`` per head; ``h'_i = elu(sum_j alpha_ij z_j +
b)``, the heads concatenated.  Departures from the paper, as the system
samples: two layers and a linear head on the seeds, no dropout, and a seed
that was not drawn as a neighbour of some seed has no layer-1 in-edges, so
its layer-1 output is its self term alone.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def in_edges(src_pos, dst_pos, edge_mask) -> dict:
    """Destination row -> the source rows of its masked-in edges, in
    order, duplicates kept (a draw with replacement is an edge each)."""
    m = np.asarray(edge_mask, bool)
    out: dict = {}
    for s, d in zip(np.asarray(src_pos)[m], np.asarray(dst_pos)[m]):
        out.setdefault(int(d), []).append(int(s))
    return out


def layer(lp, h, edges: dict, rows) -> dict:
    """One GAT layer's output for each row in ``rows``: row -> (K F',)."""
    k, f = lp["a_src"].shape
    z = (h @ lp["w"]).reshape(h.shape[0], k, f)
    out = {}
    for i in rows:
        srcs = edges.get(i, []) + [i]                 # its in-edges, then self
        zs = z[np.asarray(srcs)]                      # (m, K, F')
        e = jax.nn.leaky_relu(jnp.sum(z[i] * lp["a_dst"], axis=-1)
                              + jnp.sum(zs * lp["a_src"], axis=-1), 0.2)
        alpha = jax.nn.softmax(e, axis=0)             # over the m edges
        agg = jnp.sum(alpha[:, :, None] * zs, axis=0).reshape(k * f)
        out[i] = jax.nn.elu(agg + lp["b"])
    return out


def logits(params, feats, blocks, batch_size: int):
    """The seeds' logits, (batch_size, C)."""
    with jax.default_matmul_precision("highest"):
        hops = [in_edges(*b) for b in reversed(blocks)]   # layer 1 first
        # the rows each layer must output: the seeds at the last layer,
        # and at each layer below, the rows the layer above reads
        need = [list(range(batch_size))]
        for edges in reversed(hops[1:]):
            rows = set(need[0])
            for i in need[0]:
                rows.update(edges.get(i, []))
            need.insert(0, sorted(rows))
        h = jnp.asarray(feats, jnp.float32)
        for lp, edges, rows in zip(params["layers"], hops, need):
            out = layer(lp, h, edges, rows)
            h = jnp.zeros((h.shape[0], lp["w"].shape[1]), jnp.float32)
            h = h.at[np.asarray(rows)].set(jnp.stack([out[i] for i in rows]))
        return h[:batch_size] @ params["head"]["w"] + params["head"]["b"]


def loss(params, feats, blocks, labels, batch_size: int):
    """Mean cross-entropy of the seeds' logits."""
    lg = logits(params, feats, blocks, batch_size)
    lse = jax.nn.logsumexp(lg, axis=-1)
    gold = jnp.take_along_axis(lg, jnp.asarray(labels)[:, None], axis=-1)
    return jnp.mean(lse - gold[:, 0])
