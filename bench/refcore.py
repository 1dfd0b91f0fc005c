"""The plain reference's shared parts: the sampled tree, its check against
the graph, matrix products at a stated precision, AdamW, and the gaps
that decide ``correct``.

Imports nothing of the system under test.  The architectures
(``refs/<arch>.py``) build on it.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass

import numpy as np


# ---------------------------------------------------------------- precision
def dot_highest(a, b):
    """float32 product at ``highest`` precision."""
    import jax
    import jax.numpy as jnp

    return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)


def _bf16_split(x):
    import jax.numpy as jnp

    hi = x.astype(jnp.bfloat16).astype(jnp.float32)
    lo = (x - hi).astype(jnp.bfloat16).astype(jnp.float32)
    return hi, lo


def _dot3(a, b):
    ah, al = _bf16_split(a)
    bh, bl = _bf16_split(b)
    return dot_highest(ah, bh) + dot_highest(ah, bl) + dot_highest(al, bh)


@functools.cache
def _dot3_vjp():
    import jax

    @jax.custom_vjp
    def dot3(a, b):
        return _dot3(a, b)

    def fwd(a, b):
        return _dot3(a, b), (a, b)

    def bwd(res, g):
        a, b = res
        return _dot3(g, b.T), _dot3(a.T, g)

    dot3.defvjp(fwd, bwd)
    return dot3


def dot_high(a, b):
    """float32 product at ``high`` precision, the control's: on a TPU
    JAX's own ``Precision.HIGH`` (three bfloat16 passes); elsewhere,
    where the backend computes every float32 product in full, the same
    three passes (hi*hi + hi*lo + lo*hi) written out, forward and in both
    gradients."""
    import jax
    import jax.numpy as jnp

    if jax.default_backend() == "tpu":
        return jnp.matmul(a, b, precision=jax.lax.Precision.HIGH)
    return _dot3_vjp()(a, b)


# ---------------------------------------------------------------- the tree
@dataclass
class Tree:
    """One batch's sampled two-hop tree in global vertex ids.

    ``src0[i]`` are the ``fanouts[0]`` neighbours drawn for seed ``i``;
    ``front`` is the sorted set of those neighbours, and ``src1[j]`` the
    ``fanouts[1]`` neighbours drawn for ``front[j]``."""
    seeds: np.ndarray          # (B,)
    src0: np.ndarray           # (B, f0)
    front: np.ndarray          # (F,)
    src1: np.ndarray           # (F, f1)
    labels: np.ndarray         # (B,)


def tree_from_blocks(nodes, node_mask, blocks, labels, fanouts):
    """Read a batch as the sampler laid it out: ``nodes`` (padded global
    ids), and per hop ``(src_pos, dst_pos, edge_mask)`` outer hop first.
    Returns ``(tree, faults)``: ``faults`` counts structural departures
    (wrong destinations, wrong fanout counts, a frontier that is not the
    set of the previous hop's neighbours)."""
    nodes = np.asarray(nodes)
    faults = 0
    real = nodes[np.asarray(node_mask, bool)]
    hops = []
    for src_pos, dst_pos, mask in blocks:
        m = np.asarray(mask, bool)
        hops.append((nodes[np.asarray(src_pos)[m]],
                     nodes[np.asarray(dst_pos)[m]]))
    (s0, d0), (s1, d1) = hops
    f0, f1 = fanouts
    b = len(labels)
    seeds = real[:b]
    if len(s0) != b * f0 or not np.array_equal(d0, np.repeat(seeds, f0)):
        faults += 1
        s0 = np.resize(s0, b * f0)
    front = np.unique(s0)
    if len(s1) != len(front) * f1 or not np.array_equal(
            d1, np.repeat(front, f1)):
        faults += 1
        s1 = np.resize(s1, len(front) * f1)
    if len(np.unique(seeds)) != b:
        faults += 1
    return Tree(seeds, s0.reshape(b, f0), front, s1.reshape(-1, f1),
                np.asarray(labels)), faults


def bad_edges(rowptr, col, dst, src) -> int:
    """How many sampled edges ``dst -> src`` are not edges of the graph
    (a vertex with no neighbours samples itself)."""
    dst = np.asarray(dst).ravel()
    src = np.asarray(src).ravel()
    order = np.argsort(dst, kind="stable")
    dst, src = dst[order], src[order]
    bounds = np.flatnonzero(np.diff(dst)) + 1
    bad = 0
    for lo, hi in zip(np.r_[0, bounds], np.r_[bounds, len(dst)]):
        d = dst[lo]
        seg = col[rowptr[d]:rowptr[d + 1]]
        ss = src[lo:hi]
        if len(seg) == 0:
            bad += int(np.count_nonzero(ss != d))
        elif len(seg) > 4096:
            bad += sum(1 for s in ss if not (seg == s).any())
        else:
            bad += int(np.count_nonzero(~np.isin(ss, seg)))
    return bad


def tree_bad_edges(rowptr, col, t: Tree) -> int:
    f0 = t.src0.shape[1]
    f1 = t.src1.shape[1]
    return (bad_edges(rowptr, col, np.repeat(t.seeds, f0), t.src0)
            + bad_edges(rowptr, col, np.repeat(t.front, f1), t.src1))


# ---------------------------------------------------------------- AdamW
def adamw_step(params, grads, m, v, step, opt):
    """One AdamW step with global-norm clipping, as the configuration
    states it (``opt``: lr, b1, b2, eps, weight_decay, max_grad_norm).
    Returns ``(params, m, v, clipped_grads)``."""
    import jax
    import jax.numpy as jnp

    leaves = jax.tree.leaves(grads)
    gn = jnp.sqrt(sum(jnp.sum(g * g) for g in leaves))
    scale = jnp.minimum(1.0, opt["max_grad_norm"] / jnp.maximum(gn, 1e-9))
    grads = jax.tree.map(lambda g: g * scale, grads)
    b1, b2 = opt["b1"], opt["b2"]
    bc1 = 1.0 - b1 ** step
    bc2 = 1.0 - b2 ** step
    m = jax.tree.map(lambda a, g: b1 * a + (1 - b1) * g, m, grads)
    v = jax.tree.map(lambda a, g: b2 * a + (1 - b2) * g * g, v, grads)

    def upd(p, mm, vv):
        u = (mm / bc1) / (jnp.sqrt(vv / bc2) + opt["eps"])
        return p - opt["lr"] * (u + opt["weight_decay"] * p)
    return jax.tree.map(upd, params, m, v), m, v, grads


# ---------------------------------------------------------------- gaps
def leaf_norms(tree) -> list:
    import jax

    return [float(np.linalg.norm(np.asarray(x, np.float64)))
            for x in jax.tree.leaves(tree)]


def norm_gap(prog: list, ref: list, keep=None) -> float:
    """Worst leaf's ``|‖prog‖ - ‖ref‖|`` over the larger of that leaf's
    reference norm and the median leaf's."""
    med = float(np.median(ref))
    idx = range(len(ref)) if keep is None else keep
    return max((abs(prog[i] - ref[i]) / max(ref[i], med) for i in idx),
               default=0.0)


def worst_leaf(prog: list, ref: list) -> list:
    """Index, size of gap and reference norm of the leaf ``norm_gap``
    finds worst (a diagnostic of the study)."""
    med = float(np.median(ref))
    gaps = [abs(p - r) / max(r, med) for p, r in zip(prog, ref)]
    i = int(np.argmax(gaps))
    return [i, gaps[i], ref[i], med]


def diff_gap(prog, ref) -> float:
    """Worst leaf's ``‖prog - ref‖`` over the larger of that leaf's
    reference norm and the median leaf's."""
    import jax

    p = [np.asarray(x, np.float64) for x in jax.tree.leaves(prog)]
    r = [np.asarray(x, np.float64) for x in jax.tree.leaves(ref)]
    norms = [float(np.linalg.norm(x)) for x in r]
    med = float(np.median(norms))
    return max(float(np.linalg.norm(a - b)) / max(n, med)
               for a, b, n in zip(p, r, norms))


def moved_leaves(first_grad_norms: list, floor: float = 1e-3) -> list:
    """Leaves whose first reference gradient is above ``floor`` times the
    median leaf's: the others move under Adam by round-off alone."""
    med = float(np.median(first_grad_norms))
    return [i for i, g in enumerate(first_grad_norms) if g > floor * med]


def logit_gap(served: np.ndarray, ref: np.ndarray) -> float:
    """Widest ``|served - ref|`` over the reference logits' RMS."""
    served = np.asarray(served, np.float64)
    ref = np.asarray(ref, np.float64)
    rms = float(np.sqrt(np.mean(ref * ref)))
    return float(np.max(np.abs(served - ref))) / max(rms, 1e-30)


# ---------------------------------------------------------------- inputs
def padded(t: Tree, fmax: int) -> dict:
    """Index arrays of one tree at fixed shapes (the frontier padded to
    ``fmax`` rows), so one compiled reference serves every batch.
    ``k[i, j]`` is the frontier row of ``src0[i, j]``; ``seed_j`` the
    frontier row of each seed and ``seed_in`` whether it has one."""
    f = len(t.front)
    if f > fmax:
        raise ValueError(f"frontier of {f} rows over the padded {fmax}")
    front = np.zeros(fmax, np.int64)
    front[:f] = t.front
    src1 = np.zeros((fmax, t.src1.shape[1]), np.int64)
    src1[:f] = t.src1
    j = np.minimum(np.searchsorted(t.front, t.seeds), f - 1)
    return {"front": front, "src1": src1,
            "k": np.searchsorted(t.front, t.src0).astype(np.int32),
            "seed_j": j.astype(np.int32),
            "seed_in": (t.front[j] == t.seeds).astype(np.float32)}


def features(t: Tree, pad: dict, rows) -> dict:
    """Feature rows of the tree's vertices, made by ``rows(ids)``."""
    fmax, f1 = pad["src1"].shape
    return {"seed": rows(t.seeds),
            "front": rows(pad["front"]),
            "src1": rows(pad["src1"].ravel()).reshape(fmax, f1, -1)}


def cross_entropy(logits, labels):
    import jax
    import jax.numpy as jnp

    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    return jnp.mean(lse - gold)


def train_reference(arch, params, trees, rows, opt, dot, fmax,
                    half_batch: bool = False):
    """Follow the program's first steps: AdamW from ``params`` on the
    batches ``trees`` in order.  Returns ``(losses, first clipped
    gradient, params after the last step)``.  ``half_batch`` plants a
    fault: the loss is the mean over the first half of the seeds."""
    import jax
    import jax.numpy as jnp

    def loss_fn(p, x, aux, labels):
        lg = arch.logits(p, x, aux, dot)
        if half_batch:
            n = labels.shape[0] // 2
            lg, labels = lg[:n], labels[:n]
        return cross_entropy(lg, labels)

    vg = jax.jit(jax.value_and_grad(loss_fn))
    m = jax.tree.map(jnp.zeros_like, params)
    v = jax.tree.map(jnp.zeros_like, params)
    losses, first = [], None
    for i, t in enumerate(trees, 1):
        pad = padded(t, fmax)
        x, aux = features(t, pad, rows), arch.aux(t, pad)
        loss, g = vg(params, x, aux, jnp.asarray(t.labels, jnp.int32))
        params, m, v, gc = adamw_step(params, g, m, v, i, opt)
        losses.append(float(loss))
        if first is None:
            first = gc
    return losses, first, params


def serve_reference(arch, params, trees, rows, dot, fmax) -> list:
    """Logits of each request's tree."""
    import jax

    fwd = jax.jit(lambda p, x, aux: arch.logits(p, x, aux, dot))
    out = []
    for t in trees:
        pad = padded(t, fmax)
        out.append(np.asarray(fwd(params, features(t, pad, rows),
                                  arch.aux(t, pad))))
    return out
