"""Plain reference of GCN (Kipf and Welling, 2017, arXiv:1609.02907) on a
sampled two-hop tree, float32.

Layer ``l``: ``h_v = relu(sum_{u in N(v)} h_u / sqrt(c(u) f) W + b)`` over
the ``f`` neighbours drawn for ``v``, where ``c(u)`` counts how often
``u`` was drawn in that hop; then a linear head on the seeds.  As the
system samples it there is no self-loop: a seed's own features do not
enter its output.
"""
from __future__ import annotations

import numpy as np

ARCH = "gcn"


def init_params(key, cfg: dict) -> dict:
    """Normal weights scaled by 1/sqrt(fan-in), zero biases."""
    import jax
    import jax.numpy as jnp

    d, h, c = cfg["feature_dim"], cfg["hidden"], cfg["n_classes"]
    ks = jax.random.split(key, cfg["n_layers"] + 1)
    layers = []
    for i in range(cfg["n_layers"]):
        din = d if i == 0 else h
        layers.append({"w": jax.random.normal(ks[i], (din, h)) / din ** 0.5,
                       "b": jnp.zeros((h,), jnp.float32)})
    return {"layers": layers,
            "head": {"w": jax.random.normal(ks[-1], (h, c)) / h ** 0.5,
                     "b": jnp.zeros((c,), jnp.float32)}}


def _draw_counts(src: np.ndarray) -> np.ndarray:
    """How often each entry's vertex was drawn in this hop."""
    u, inv, cnt = np.unique(src, return_inverse=True, return_counts=True)
    return cnt[inv].reshape(src.shape).astype(np.float32)


def aux(tree, pad: dict) -> dict:
    f = len(tree.front)
    c1 = np.ones(pad["src1"].shape, np.float32)
    c1[:f] = _draw_counts(tree.src1)
    return {"k": pad["k"], "c0": _draw_counts(tree.src0), "c1": c1}


def logits(p, x, aux, dot):
    import jax
    import jax.numpy as jnp

    l1, l2 = p["layers"]
    f1 = x["src1"].shape[1]
    f0 = aux["k"].shape[1]
    w1 = jax.lax.rsqrt(aux["c1"] * f1)[:, :, None]
    agg1 = jnp.sum(x["src1"] * w1, axis=1)                       # (F, D)
    h_front = jax.nn.relu(dot(agg1, l1["w"]) + l1["b"])
    w0 = jax.lax.rsqrt(aux["c0"] * f0)[:, :, None]
    agg2 = jnp.sum(h_front[aux["k"]] * w0, axis=1)               # (B, H)
    h2 = jax.nn.relu(dot(agg2, l2["w"]) + l2["b"])
    return dot(h2, p["head"]["w"]) + p["head"]["b"]


def matmuls(cfg: dict) -> list:
    """``(rows per seed, k, n, input gradient needed)`` of each matrix
    product one training seed requires: layer 1 for the seed and its
    ``fanouts[0]`` neighbours, layer 2 and the head for the seed."""
    d, h, c = cfg["feature_dim"], cfg["hidden"], cfg["n_classes"]
    return [(1 + cfg["fanouts"][0], d, h, False), (1, h, h, True),
            (1, h, c, True)]
