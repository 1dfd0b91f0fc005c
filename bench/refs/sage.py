"""Plain reference of GraphSAGE with the mean aggregator (Hamilton et al.,
2017, arXiv:1706.02216) on a sampled two-hop tree, float32.

Layer ``l``: ``h_v = relu(h_v W_self + mean_{u in N(v)} h_u W_neigh + b)``
over the neighbours drawn for ``v``; then a linear head on the seeds.  As
the system samples it, a seed's own layer-1 neighbour term exists only
where the seed was also drawn as a neighbour of some seed (its hop-2 draws
are then in the tree); otherwise it is zero.
"""
from __future__ import annotations

ARCH = "sage"


def init_params(key, cfg: dict) -> dict:
    """Normal weights scaled by 1/sqrt(fan-in), zero biases."""
    import jax
    import jax.numpy as jnp

    d, h, c = cfg["feature_dim"], cfg["hidden"], cfg["n_classes"]
    ks = jax.random.split(key, 2 * cfg["n_layers"] + 1)
    layers = []
    for i in range(cfg["n_layers"]):
        din = d if i == 0 else h
        layers.append({
            "w_self": jax.random.normal(ks[2 * i], (din, h)) / din ** 0.5,
            "w_neigh": jax.random.normal(ks[2 * i + 1], (din, h)) / din ** 0.5,
            "b": jnp.zeros((h,), jnp.float32)})
    return {"layers": layers,
            "head": {"w": jax.random.normal(ks[-1], (h, c)) / h ** 0.5,
                     "b": jnp.zeros((c,), jnp.float32)}}


def aux(tree, pad: dict) -> dict:
    return {k: pad[k] for k in ("k", "seed_j", "seed_in")}


def logits(p, x, aux, dot):
    import jax
    import jax.numpy as jnp

    l1, l2 = p["layers"]
    nb_front = jnp.mean(x["src1"], axis=1)                        # (F, D)
    nb_seed = nb_front[aux["seed_j"]] * aux["seed_in"][:, None]    # (B, D)
    h_front = jax.nn.relu(dot(x["front"], l1["w_self"])
                          + dot(nb_front, l1["w_neigh"]) + l1["b"])
    h_seed = jax.nn.relu(dot(x["seed"], l1["w_self"])
                         + dot(nb_seed, l1["w_neigh"]) + l1["b"])
    nb2 = jnp.mean(h_front[aux["k"]], axis=1)                      # (B, H)
    h2 = jax.nn.relu(dot(h_seed, l2["w_self"]) + dot(nb2, l2["w_neigh"])
                     + l2["b"])
    return dot(h2, p["head"]["w"]) + p["head"]["b"]


def matmuls(cfg: dict) -> list:
    """``(rows per seed, k, n, input gradient needed)`` of each matrix
    product one training seed requires: layer 1 for the seed and its
    ``fanouts[0]`` neighbours (self and neighbour weights), layer 2 and
    the head for the seed."""
    d, h, c = cfg["feature_dim"], cfg["hidden"], cfg["n_classes"]
    r1 = 1 + cfg["fanouts"][0]
    return [(r1, d, h, False), (r1, d, h, False),
            (1, h, h, True), (1, h, h, True), (1, h, c, True)]
