"""GraphSAGE [Hamilton+17] and GCN [Kipf&Welling16] on padded sampled blocks.

Message passing uses ``jax.ops.segment_sum`` aggregation over
static-shaped edge lists on every backend, TPU included (the Pallas
``segment_agg`` kernel is tested against it but has no caller).  Hidden
dim 256, 2 hops per the paper's setup.
"""
from __future__ import annotations


import jax
import jax.numpy as jnp

from repro.models.layers import dense_init


def init_gnn_params(key, model: str, in_dim: int, hidden: int, n_classes: int,
                    n_layers: int = 2, dtype=jnp.float32):
    ks = jax.random.split(key, n_layers + 1)
    layers = []
    for i in range(n_layers):
        d_in = in_dim if i == 0 else hidden
        d_out = hidden
        if model == "sage":
            layers.append({
                "w_self": dense_init(ks[i], (d_in, d_out), dtype, d_in),
                "w_neigh": dense_init(jax.random.fold_in(ks[i], 1),
                                      (d_in, d_out), dtype, d_in),
                "b": jnp.zeros((d_out,), dtype),
            })
        else:  # gcn
            layers.append({
                "w": dense_init(ks[i], (d_in, d_out), dtype, d_in),
                "b": jnp.zeros((d_out,), dtype),
            })
    head = {"w": dense_init(ks[-1], (hidden, n_classes), dtype, hidden),
            "b": jnp.zeros((n_classes,), dtype)}
    return {"layers": layers, "head": head}


def _agg_mean(h, src_pos, dst_pos, edge_mask, n_nodes):
    """Mean aggregation: for each dst, mean of h[src] over valid edges."""
    w = edge_mask.astype(h.dtype)
    msg = h[src_pos] * w[:, None]
    summed = jax.ops.segment_sum(msg, dst_pos, num_segments=n_nodes)
    cnt = jax.ops.segment_sum(w, dst_pos, num_segments=n_nodes)
    return summed / jnp.maximum(cnt, 1.0)[:, None]


def _agg_gcn(h, src_pos, dst_pos, edge_mask, n_nodes):
    """Symmetric-normalised sum (degrees from the sampled block)."""
    w = edge_mask.astype(h.dtype)
    deg_dst = jax.ops.segment_sum(w, dst_pos, num_segments=n_nodes)
    deg_src = jax.ops.segment_sum(w, src_pos, num_segments=n_nodes)
    norm = jax.lax.rsqrt(jnp.maximum(deg_src[src_pos], 1.0)) * \
        jax.lax.rsqrt(jnp.maximum(deg_dst[dst_pos], 1.0))
    msg = h[src_pos] * (w * norm)[:, None]
    return jax.ops.segment_sum(msg, dst_pos, num_segments=n_nodes)


def gnn_forward(params, feats, blocks, model: str):
    """feats: (N_pad, F); blocks: list of (src_pos, dst_pos, edge_mask)
    outer-hop-first.  Applied inner-hop-first (reversed)."""
    h = feats
    n_nodes = feats.shape[0]
    layer_blocks = list(reversed(blocks))
    for lp, blk in zip(params["layers"], layer_blocks):
        src_pos, dst_pos, edge_mask = blk
        if model == "sage":
            nb = _agg_mean(h, src_pos, dst_pos, edge_mask, n_nodes)
            h = h @ lp["w_self"] + nb @ lp["w_neigh"] + lp["b"]
        else:
            nb = _agg_gcn(h, src_pos, dst_pos, edge_mask, n_nodes)
            h = nb @ lp["w"] + lp["b"]
        h = jax.nn.relu(h)
    return h


def gnn_loss(params, feats, blocks, labels, batch_size: int, model: str):
    h = gnn_forward(params, feats, blocks, model)
    logits = h[:batch_size] @ params["head"]["w"] + params["head"]["b"]
    logits = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    loss = jnp.mean(lse - gold)
    acc = jnp.mean((jnp.argmax(logits, -1) == labels).astype(jnp.float32))
    return loss, acc


def make_gnn_infer_step(model: str, batch_size: int):
    """Forward-only jit'd step for serving: params + padded blocks -> logits
    for the first ``batch_size`` nodes (the seeds).  No optimizer state, no
    gradients — the server shares one compiled step across all requests
    because the batcher pads every request to the sampler's static shapes."""
    @jax.jit
    def step(params, feats, src, dst, emask):
        blocks = [(s, d, m) for s, d, m in zip(src, dst, emask)]
        h = gnn_forward(params, feats, blocks, model)
        logits = h[:batch_size] @ params["head"]["w"] + params["head"]["b"]
        return logits.astype(jnp.float32)
    return step


def make_gnn_train_step(model: str, optimizer, batch_size: int,
                        embedding_grads: bool = False):
    """Jit'd training step.  With ``embedding_grads=True`` the step also
    differentiates w.r.t. the INPUT features and returns the feature
    gradient as a third output — the trainer's write path applies it to the
    trainable embedding rows and pushes them back through the cache."""
    @jax.jit
    def step(state, feats, src, dst, emask, labels):
        blocks = [(s, d, m) for s, d, m in zip(src, dst, emask)]
        if embedding_grads:
            (loss, acc), (pgrads, fgrad) = jax.value_and_grad(
                lambda p, f: gnn_loss(p, f, blocks, labels, batch_size,
                                      model),
                argnums=(0, 1), has_aux=True)(state["params"], feats)
            new_p, new_opt = optimizer.update(pgrads, state["opt"],
                                              state["params"])
            return ({"params": new_p, "opt": new_opt},
                    {"loss": loss, "acc": acc}, fgrad)
        (loss, acc), grads = jax.value_and_grad(
            lambda p: gnn_loss(p, feats, blocks, labels, batch_size, model),
            has_aux=True)(state["params"])
        new_p, new_opt = optimizer.update(grads, state["opt"], state["params"])
        return {"params": new_p, "opt": new_opt}, {"loss": loss, "acc": acc}
    return step
