"""Mixture-of-Experts block (GShard-style capacity dispatch, EP-sharded).

Expert parallelism: expert-stacked weights are sharded over the ``model``
mesh axis ("experts" logical axis); the dispatch/combine einsums carry the
token->expert traffic, which GSPMD lowers to all-to-alls between the
``data``-sharded token dim and the ``model``-sharded expert dim.

Token-dropping capacity dispatch (capacity_factor, GShard §3) is the
paper-faithful baseline; a sort-based dropless path is the §Perf hillclimb
(see EXPERIMENTS.md).
"""
from __future__ import annotations

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp

from repro.distributed.sharding import annotate
from repro.models.layers import dense_init, init_mlp, mlp


@dataclass(frozen=True)
class MoEConfig:
    n_experts: int
    top_k: int
    d_expert: int
    n_shared: int = 0
    capacity_factor: float = 1.25
    group_size: int = 1024          # tokens per dispatch group
    n_experts_padded: int = 0       # pad experts to a TP-divisible count
    aux_loss_weight: float = 0.01
    z_loss_weight: float = 1e-3
    impl: str = "gshard"            # "gshard" (one-hot dispatch) | "dropless"
                                    # (sort + ragged_dot EP, §Perf kimi fix)

    @property
    def e_pad(self) -> int:
        return self.n_experts_padded or self.n_experts


def init_moe(key, d_model, mcfg: MoEConfig, dtype, act: str, stack: tuple = ()):
    ks = jax.random.split(key, 5)
    E, F = mcfg.e_pad, mcfg.d_expert
    p = {
        "router": dense_init(ks[0], stack + (d_model, E), jnp.float32, d_model),
        "experts": {
            "w_gate": dense_init(ks[1], stack + (E, d_model, F), dtype, d_model),
            "w_up": dense_init(ks[2], stack + (E, d_model, F), dtype, d_model),
            "w_down": dense_init(ks[3], stack + (E, F, d_model), dtype, F),
        },
    }
    if mcfg.n_shared:
        p["shared"] = init_mlp(ks[4], d_model, mcfg.n_shared * F, act,
                               dtype, stack=stack)
    return p


def _capacity(tokens_per_group: int, mcfg: MoEConfig) -> int:
    c = int(math.ceil(tokens_per_group * mcfg.top_k * mcfg.capacity_factor
                      / mcfg.e_pad))
    return max(4, -(-c // 4) * 4)   # round up to a multiple of 4


def router_weights(logits, mcfg: MoEConfig, valid_experts: int):
    """logits: (..., E) fp32 -> (topw, topi, aux_loss, z_loss)."""
    logits = logits.astype(jnp.float32)
    if valid_experts < logits.shape[-1]:          # mask padding experts
        pad_mask = jnp.arange(logits.shape[-1]) < valid_experts
        logits = jnp.where(pad_mask, logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    topw, topi = jax.lax.top_k(probs, mcfg.top_k)
    topw = topw / jnp.maximum(topw.sum(-1, keepdims=True), 1e-9)
    # Switch-style load-balance loss + router z-loss
    E = logits.shape[-1]
    me = jnp.mean(probs.reshape(-1, E), axis=0)
    one_hot_top1 = jax.nn.one_hot(topi[..., 0].reshape(-1), E, dtype=jnp.float32)
    ce = jnp.mean(one_hot_top1, axis=0)
    aux = valid_experts * jnp.sum(me * ce)
    z = jnp.mean(jnp.square(jax.nn.logsumexp(logits, axis=-1)))
    return topw, topi, aux, z


def moe_block(x, p, mcfg: MoEConfig, act: str = "swiglu"):
    """x: (B, S, D) -> (y, aux_losses dict). Pure function of params."""
    with jax.named_scope("moe_core"):
        if mcfg.impl == "dropless":
            return _moe_block_dropless(x, p, mcfg, act)
        return _moe_block(x, p, mcfg, act)


def _moe_block_dropless(x, p, mcfg: MoEConfig, act: str = "swiglu"):
    """Sort-based EP MoE (MaxText sparse-matmul style, §Perf kimi iteration).

    The GShard one-hot dispatch materialises (G,Sg,E,C) tensors (~40 GB/chip
    transients on the 1T arch); this path instead, per `model` shard:
    every shard sees the (model-replicated) activations, selects the
    (token, k) assignments routed to ITS local experts, sorts them, runs
    grouped GEMMs via ``jax.lax.ragged_dot``, scatter-adds weighted outputs,
    and psums over `model` (the same output reduction the dense path pays).
    No token-capacity drops up to the 2x-average overflow buffer.
    """
    from jax.sharding import PartitionSpec as P
    from repro.distributed.sharding import current_ctx

    B, S, D = x.shape
    E = mcfg.e_pad
    T = B * S
    K = mcfg.top_k

    ctx = current_ctx()
    model_n = ctx.mesh.shape.get("model", 1) if ctx is not None else 1
    e_loc = E // model_n

    def local(x_loc, router_w, wg, wu, wd, sh_params):
        # x_loc: (B_loc, S, D) replicated over `model`; w*: (e_loc, D, F)
        if model_n > 1:
            e_off = jax.lax.axis_index("model") * e_loc
        else:
            e_off = 0
        Tl = x_loc.shape[0] * x_loc.shape[1]
        # 2x the average per-shard assignment load; at model_n == 1 this
        # keeps every assignment (exactly dropless)
        cap = min(max(8, 2 * Tl * K // max(model_n, 1)), Tl * K)
        xf = x_loc.reshape(Tl, D)
        logits = xf.astype(jnp.float32) @ router_w
        topw, topi, aux, z = router_weights(logits[None], mcfg, mcfg.n_experts)
        topw, topi = topw[0], topi[0]                       # (Tl, K)
        tok_idx = jnp.repeat(jnp.arange(Tl), K)
        expert = topi.reshape(-1)
        w = topw.reshape(-1)
        key = jnp.where((expert >= e_off) & (expert < e_off + e_loc),
                        expert - e_off, e_loc)              # e_loc = foreign
        order = jnp.argsort(key, stable=True)[:cap]
        keys = key[order]
        valid = keys < e_loc
        tok = tok_idx[order]
        xg = xf[tok] * valid[:, None].astype(xf.dtype)
        gs = jnp.bincount(jnp.where(valid, keys, e_loc), length=e_loc + 1)[:e_loc]
        gs = gs.astype(jnp.int32)
        if act in ("swiglu", "geglu"):
            act_fn = jax.nn.silu if act == "swiglu" else jax.nn.gelu
            h = act_fn(jax.lax.ragged_dot(xg, wg, gs)) * \
                jax.lax.ragged_dot(xg, wu, gs)
        else:
            h = jax.nn.gelu(jax.lax.ragged_dot(xg, wu, gs))
        y = jax.lax.ragged_dot(h, wd, gs)
        y = y * (w[order] * valid)[:, None].astype(y.dtype)
        out = jnp.zeros((Tl, D), y.dtype).at[tok].add(y)
        if model_n > 1:
            out = jax.lax.psum(out, "model")
            aux = jax.lax.pmean(aux, "model")
            z = jax.lax.pmean(z, "model")
        out = out.reshape(x_loc.shape)
        if sh_params is not None:
            out = out + mlp(x_loc, sh_params, act)
        return out, aux, z

    we = p["experts"]
    sh = p.get("shared")
    if ctx is not None and model_n > 1:
        batch_ax = tuple(a for a in ("pod", "data") if a in ctx.mesh.shape)
        xspec = P(batch_ax if B % ctx.axis_size(batch_ax) == 0 else None,
                  None, None)
        wspec = P("model", None, None)
        shspec = (jax.tree.map(lambda _: P(), sh) if sh is not None else None)
        fn = jax.shard_map(
            local, mesh=ctx.mesh,
            in_specs=(xspec, P(None, None), wspec, wspec, wspec, shspec),
            out_specs=(xspec, P(), P()),
            check_vma=False)
        y, aux, z = fn(x, p["router"], we["w_gate"], we["w_up"], we["w_down"], sh)
    else:
        y, aux, z = local(x, p["router"], we["w_gate"], we["w_up"],
                          we["w_down"], sh)
    losses = {"moe_aux": mcfg.aux_loss_weight * aux,
              "moe_z": mcfg.z_loss_weight * z}
    return y, losses


def _moe_block(x, p, mcfg: MoEConfig, act: str = "swiglu"):
    B, S, D = x.shape
    E = mcfg.e_pad
    # group tokens batch-major (split within each sequence) so the group dim
    # inherits the batch sharding; decode (S=1) gets one group per token
    Sg = min(mcfg.group_size, S)
    if S % Sg:
        Sg = S
    G = B * (S // Sg)
    xg = x.reshape(G, Sg, D)
    xg = annotate(xg, "batch", None, None)

    logits = xg.astype(jnp.float32) @ p["router"]          # (G, Sg, E)
    topw, topi, aux, z = router_weights(logits, mcfg, mcfg.n_experts)

    C = _capacity(Sg, mcfg)
    # position of each (token, k) assignment within its expert's capacity
    mask = jax.nn.one_hot(topi, E, dtype=jnp.float32)       # (G, Sg, K, E)
    mask_flat = mask.reshape(G, Sg * mcfg.top_k, E)         # token-major, k-minor
    pos_flat = jnp.cumsum(mask_flat, axis=1) - mask_flat
    pos = jnp.einsum("gte,gte->gt", pos_flat, mask_flat).reshape(G, Sg, mcfg.top_k)
    keep = (pos < C).astype(jnp.float32)
    w = topw * keep                                          # dropped -> 0

    pos_oh = jax.nn.one_hot(pos, C, dtype=jnp.float32) * keep[..., None]  # (G,Sg,K,C)
    dispatch = jnp.einsum("gske,gskc->gsec", mask, pos_oh)   # (G, Sg, E, C)
    combine = jnp.einsum("gske,gskc,gsk->gsec", mask, pos_oh, w)
    dispatch = annotate(dispatch.astype(x.dtype), "batch", None, "experts", None)
    combine = annotate(combine, "batch", None, "experts", None)

    # dispatch -> (E, G, C, D): all-to-all between data-sharded G and
    # model-sharded E under GSPMD
    expert_in = jnp.einsum("gsec,gsd->egcd", dispatch, xg)
    expert_in = annotate(expert_in, "experts", "batch", None, None)

    we = p["experts"]
    if act in ("swiglu", "geglu"):
        act_fn = jax.nn.silu if act == "swiglu" else jax.nn.gelu
        h = act_fn(jnp.einsum("egcd,edf->egcf", expert_in, we["w_gate"])) * \
            jnp.einsum("egcd,edf->egcf", expert_in, we["w_up"])
    else:
        h = jax.nn.gelu(jnp.einsum("egcd,edf->egcf", expert_in, we["w_up"]))
    expert_out = jnp.einsum("egcf,efd->egcd", h, we["w_down"])
    expert_out = annotate(expert_out, "experts", "batch", None, None)

    y = jnp.einsum("egcd,gsec->gsd", expert_out, combine.astype(x.dtype))
    y = annotate(y, "batch", None, None).reshape(B, S, D)

    if "shared" in p:
        y = y + mlp(x, p["shared"], act)
    losses = {"moe_aux": mcfg.aux_loss_weight * aux,
              "moe_z": mcfg.z_loss_weight * z}
    return y, losses
