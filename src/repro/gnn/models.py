"""GraphSAGE [Hamilton+17], GCN [Kipf&Welling16] and GAT [Velickovic+18]
on padded sampled blocks.

Message passing uses ``jax.ops.segment_sum`` aggregation over
static-shaped edge lists on every backend, TPU included (the Pallas
``segment_agg`` kernel is tested against it but has no caller).  GAT adds
a segment softmax over each row's in-edges and a self edge per row, made
here from ``arange(N)`` rather than sampled.  Hidden dim 256, 2 hops per
the paper's setup.
"""
from __future__ import annotations


import jax
import jax.numpy as jnp

from repro.models.layers import dense_init

MODELS = ("sage", "gcn", "gat")
GAT_HEADS = 4          # attention heads a new GAT layer gets; the forward
                       # reads the count from the parameters' shapes


def _check_model(model: str):
    if model not in MODELS:
        raise ValueError(f"unknown GNN model {model!r}: expected one of "
                         f"{MODELS}")


def init_gnn_params(key, model: str, in_dim: int, hidden: int, n_classes: int,
                    n_layers: int = 2, dtype=jnp.float32):
    """``hidden`` is each layer's output width; for GAT, ``GAT_HEADS``
    attention heads of ``hidden // GAT_HEADS`` features, concatenated."""
    _check_model(model)
    if model == "gat" and hidden % GAT_HEADS:
        raise ValueError(f"hidden={hidden} is not a multiple of "
                         f"heads={GAT_HEADS}")
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
        elif model == "gcn":
            layers.append({
                "w": dense_init(ks[i], (d_in, d_out), dtype, d_in),
                "b": jnp.zeros((d_out,), dtype),
            })
        else:  # gat: w's columns are the heads' features, head-major
            heads, f = GAT_HEADS, d_out // GAT_HEADS
            layers.append({
                "w": dense_init(ks[i], (d_in, d_out), dtype, d_in),
                "a_src": dense_init(jax.random.fold_in(ks[i], 1),
                                    (heads, f), dtype, f),
                "a_dst": dense_init(jax.random.fold_in(ks[i], 2),
                                    (heads, f), dtype, f),
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


def _agg_gat(z, a_src, a_dst, src_pos, dst_pos, edge_mask):
    """Multi-head attention over each row's in-edges and one self edge:
    ``z`` (N, K, F') the projected rows, ``a_src``/``a_dst`` (K, F').
    Score ``leaky_relu(<z_dst, a_dst> + <z_src, a_src>, 0.2)`` per head,
    softmax over the row's edges (masked edges weigh exactly zero), then
    ``sum alpha z_src``; returns (N, K, F')."""
    n = z.shape[0]
    rows = jnp.arange(n, dtype=src_pos.dtype)
    src = jnp.concatenate([src_pos, rows])
    dst = jnp.concatenate([dst_pos, rows])
    mask = jnp.concatenate([edge_mask, jnp.ones((n,), bool)])
    s_src = jnp.sum(z * a_src, axis=-1)                            # (N, K)
    s_dst = jnp.sum(z * a_dst, axis=-1)
    e = jax.nn.leaky_relu(s_dst[dst] + s_src[src], 0.2)            # (E+N, K)
    e = jnp.where(mask[:, None], e, -jnp.inf)
    # the self edge keeps every row's max finite; softmax is invariant to
    # the shift, so no gradient flows through it
    e_max = jax.lax.stop_gradient(
        jax.ops.segment_max(e, dst, num_segments=n))
    p = jnp.exp(e - e_max[dst])
    alpha = p / jax.ops.segment_sum(p, dst, num_segments=n)[dst]
    return jax.ops.segment_sum(alpha[..., None] * z[src], dst,
                               num_segments=n)


def gnn_forward(params, feats, blocks, model: str):
    """feats: (N_pad, F); blocks: list of (src_pos, dst_pos, edge_mask)
    outer-hop-first.  Applied inner-hop-first (reversed)."""
    _check_model(model)
    h = feats
    n_nodes = feats.shape[0]
    layer_blocks = list(reversed(blocks))
    for lp, blk in zip(params["layers"], layer_blocks):
        src_pos, dst_pos, edge_mask = blk
        if model == "sage":
            nb = _agg_mean(h, src_pos, dst_pos, edge_mask, n_nodes)
            h = jax.nn.relu(h @ lp["w_self"] + nb @ lp["w_neigh"] + lp["b"])
        elif model == "gcn":
            nb = _agg_gcn(h, src_pos, dst_pos, edge_mask, n_nodes)
            h = jax.nn.relu(nb @ lp["w"] + lp["b"])
        else:
            k, f = lp["a_src"].shape
            z = (h @ lp["w"]).reshape(n_nodes, k, f)
            with jax.named_scope("gat.attention"):
                agg = _agg_gat(z, lp["a_src"], lp["a_dst"], src_pos,
                               dst_pos, edge_mask)
            h = jax.nn.elu(agg.reshape(n_nodes, k * f) + lp["b"])
    return h


def edge_counts(model: str, blocks, n_rows: int, n_real: int) -> dict:
    """The edge rows a step's aggregation runs over (``edge_slots``: the
    blocks' static slots) and the masked-in ones (``real_edges``); GAT
    adds a self edge per row and layer, of which ``n_real`` are real."""
    _check_model(model)
    selfs = len(blocks) if model == "gat" else 0
    return {"edge_slots": sum(len(b.edge_mask) for b in blocks)
            + selfs * n_rows,
            "real_edges": sum(int(b.edge_mask.sum()) for b in blocks)
            + selfs * n_real}


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
