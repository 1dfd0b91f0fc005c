"""Plain reference of GAT (Velickovic et al., 2018, arXiv:1710.10903) on a
sampled two-hop tree, float32.

Layer ``l``: ``z_u = h_u W`` viewed as K heads of F' features; row ``v``
attends over itself and the neighbours drawn for it, with the score
``e_vu = leaky_relu(z_v . a_dst + z_u . a_src, 0.2)`` per head and
``alpha_v = softmax_u(e_v)``; ``h'_v = elu(sum_u alpha_vu z_u + b)``, the
heads concatenated.  A linear head on the seeds follows.  Duplicate draws
are separate edges, as in the system's blocks.

Departures from the paper, as the system samples: two attention layers
and a linear head (the paper's inductive model has three layers, a skip
connection and an averaged six-head output), no dropout, and a seed's
layer-1 neighbourhood exists only where the seed was also drawn as a
neighbour of some seed (its hop-2 draws are then in the tree); otherwise
its layer-1 output is its self term alone.
"""
from __future__ import annotations

ARCH = "gat"


def init_params(key, cfg: dict) -> dict:
    """Normal weights scaled by 1/sqrt(fan-in), attention vectors by
    1/sqrt(F'), zero biases: the program's layout."""
    import jax
    import jax.numpy as jnp

    d, h, c, k = (cfg["feature_dim"], cfg["hidden"], cfg["n_classes"],
                  cfg["heads"])
    f = h // k
    ks = jax.random.split(key, 3 * cfg["n_layers"] + 1)
    layers = []
    for i in range(cfg["n_layers"]):
        din = d if i == 0 else h
        layers.append({
            "w": jax.random.normal(ks[3 * i], (din, h)) / din ** 0.5,
            "a_src": jax.random.normal(ks[3 * i + 1], (k, f)) / f ** 0.5,
            "a_dst": jax.random.normal(ks[3 * i + 2], (k, f)) / f ** 0.5,
            "b": jnp.zeros((h,), jnp.float32)})
    return {"layers": layers,
            "head": {"w": jax.random.normal(ks[-1], (h, c)) / h ** 0.5,
                     "b": jnp.zeros((c,), jnp.float32)}}


def aux(tree, pad: dict) -> dict:
    return {k: pad[k] for k in ("k", "seed_j", "seed_in")}


def _per_head(a):
    """(K, F') -> the (K F', K) block-diagonal matrix whose product with a
    row of z gives each head's score term."""
    import jax.numpy as jnp

    k, f = a.shape
    return (a[:, :, None] * jnp.eye(k, dtype=a.dtype)[:, None, :]).reshape(
        k * f, k)


def _attend(lp, z_self, z_nb, dot):
    """Rows with projected features ``z_self`` (R, K F') attend over
    themselves and their drawn neighbours ``z_nb`` (R, f, K F')."""
    import jax
    import jax.numpy as jnp

    r, f, kf = z_nb.shape
    a_src, a_dst = _per_head(lp["a_src"]), _per_head(lp["a_dst"])
    k = a_src.shape[1]
    zz = jnp.concatenate([z_self[:, None], z_nb], axis=1)     # (R, 1+f, KF)
    e = jax.nn.leaky_relu(
        dot(z_self, a_dst)[:, None]
        + dot(zz.reshape(r * (1 + f), kf), a_src).reshape(r, 1 + f, k), 0.2)
    alpha = jax.nn.softmax(e, axis=1)                          # (R, 1+f, K)
    agg = jnp.sum(alpha[..., None] * zz.reshape(r, 1 + f, k, kf // k),
                  axis=1)
    return jax.nn.elu(agg.reshape(r, kf) + lp["b"])


def logits(p, x, aux, dot):
    import jax
    import jax.numpy as jnp

    l1, l2 = p["layers"]
    fr, f1, d = x["src1"].shape
    z_front = dot(x["front"], l1["w"])
    z_src1 = dot(x["src1"].reshape(fr * f1, d), l1["w"]).reshape(fr, f1, -1)
    h_front = _attend(l1, z_front, z_src1, dot)                # (F, KF)
    alone = jax.nn.elu(dot(x["seed"], l1["w"]) + l1["b"])     # self term only
    h_seed = jnp.where(aux["seed_in"][:, None] > 0,
                       h_front[aux["seed_j"]], alone)          # (B, KF)
    h2 = _attend(l2, dot(h_seed, l2["w"]), dot(h_front, l2["w"])[aux["k"]],
                 dot)
    return dot(h2, p["head"]["w"]) + p["head"]["b"]


def matmuls(cfg: dict) -> list:
    """``(rows per seed, k, n, input gradient needed)`` of each matrix
    product one training seed requires.  Per head ``sum_u alpha_vu W h_u =
    W sum_u alpha_vu h_u``, so each layer's projection counts once per
    destination row: the seed and its ``fanouts[0]`` neighbours at layer 1,
    the seed at layer 2, then the head.  The scores count as ``h .
    (W_k a_k)``, once per source row (each destination's drawn neighbours
    and itself) and once per destination row.  The per-edge softmax and
    weighted sums are memory-bound and counted nowhere, so ``mfu.train``
    does not see the edge work."""
    d, h, c, k = (cfg["feature_dim"], cfg["hidden"], cfg["n_classes"],
                  cfg["heads"])
    f0, f1 = cfg["fanouts"]
    r1 = 1 + f0
    return [(r1, d, h, False), (r1 * (1 + f1), d, k, False), (r1, d, k, False),
            (1, h, h, True), (1 + f0, h, k, True), (1, h, k, True),
            (1, h, c, True)]
