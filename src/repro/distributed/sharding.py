"""Logical-axis sharding rules for the production mesh.

The model code annotates activations with *logical* axis names
(``annotate(x, "batch", None, "heads", None)``).  A context installed by the
launcher maps logical names onto mesh axes; outside any context the
annotations are no-ops, so the same model code runs on 1 CPU device (smoke
tests) and on a 512-chip multi-pod mesh (dry-run) unchanged.

Divisibility guard: JAX requires *input* shardings to divide array dims
evenly, and uneven internal shardings are legal but wasteful; ``annotate``
therefore silently drops a mesh axis whose size does not divide the
corresponding dim (e.g. llama3.2's 24 heads over a 16-way ``model`` axis —
the projection stays sharded on the flattened ``heads*head_dim`` dim
instead, which is divisible for every assigned architecture).
"""
from __future__ import annotations

from contextlib import contextmanager
from dataclasses import dataclass, field
from typing import Any

import jax
import numpy as np
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

# Logical axis name -> mesh axis (or tuple of mesh axes).
DEFAULT_RULES: dict[str, Any] = {
    "batch": ("pod", "data"),
    "fsdp": ("data",),          # FSDP within a pod; pure DP across pods
    "heads": "model",
    "kv_heads": "model",
    "ff": "model",
    "vocab": "model",
    "experts": "model",
    "kv_seq": "model",          # sequence/context parallel KV caches
    "seq_sp": "model",          # sequence parallelism for B=1 long-context
    "d_model": None,
    "rnn": "model",             # recurrent state channels / rwkv heads
}


@dataclass
class ShardingCtx:
    mesh: Mesh
    rules: dict[str, Any] = field(default_factory=lambda: dict(DEFAULT_RULES))

    def axis_size(self, mesh_axes) -> int:
        if mesh_axes is None:
            return 1
        if isinstance(mesh_axes, str):
            mesh_axes = (mesh_axes,)
        return int(np.prod([self.mesh.shape[a] for a in mesh_axes
                            if a in self.mesh.shape]))

    def resolve(self, name, dim_size):
        """Logical name -> mesh axes for one dim, dropping non-dividing axes."""
        if name is None:
            return None
        axes = self.rules.get(name)
        if axes is None:
            return None
        if isinstance(axes, str):
            axes = (axes,)
        axes = tuple(a for a in axes if a in self.mesh.shape)
        # greedily keep a prefix of axes whose product divides the dim
        kept = []
        prod = 1
        for a in axes:
            if dim_size % (prod * self.mesh.shape[a]) == 0:
                kept.append(a)
                prod *= self.mesh.shape[a]
        if not kept:
            return None
        return kept[0] if len(kept) == 1 else tuple(kept)

    def spec(self, names, shape) -> P:
        assert len(names) == len(shape), (names, shape)
        return P(*(self.resolve(n, d) for n, d in zip(names, shape)))

    def sharding(self, names, shape, memory_kind=None) -> NamedSharding:
        s = NamedSharding(self.mesh, self.spec(names, shape))
        if memory_kind:
            s = s.with_memory_kind(memory_kind)
        return s


_ACTIVE: list[ShardingCtx] = []


def auto_axes(mesh: Mesh) -> Mesh:
    """``mesh`` with every axis ``Auto``.  The model code places arrays by
    sharding constraints that the partitioner propagates; ``jax.make_mesh``
    makes ``Explicit`` axes, under which every gather or reshape of a
    sharded operand must name its output sharding."""
    if all(t == AxisType.Auto for t in mesh.axis_types):
        return mesh
    return Mesh(mesh.devices, mesh.axis_names,
                axis_types=(AxisType.Auto,) * len(mesh.axis_names))


@contextmanager
def use_mesh(mesh: Mesh, rules: dict | None = None):
    ctx = ShardingCtx(auto_axes(mesh), {**DEFAULT_RULES, **(rules or {})})
    _ACTIVE.append(ctx)
    try:
        yield ctx
    finally:
        _ACTIVE.pop()


def current_ctx() -> ShardingCtx | None:
    return _ACTIVE[-1] if _ACTIVE else None


def annotate(x, *names):
    """Constrain ``x``'s sharding by logical axis names (no-op without mesh)."""
    ctx = current_ctx()
    if ctx is None:
        return x
    try:
        return jax.lax.with_sharding_constraint(x, ctx.sharding(names, x.shape))
    except Exception:
        return x


# ---------------------------------------------------------------------------
# Parameter partition specs (name-based rules)
# ---------------------------------------------------------------------------

def _path_names(path) -> list[str]:
    out = []
    for p in path:
        if hasattr(p, "key"):
            out.append(str(p.key))
        elif hasattr(p, "name"):
            out.append(str(p.name))
        else:
            out.append(str(p))
    return out


def param_logical_axes(path, shape, *, fsdp: bool = False) -> tuple:
    """Return logical axis names for a parameter leaf, keyed on its name.

    Leading stack dims (layers / experts) are inferred from rank: rules below
    describe the trailing matrix dims.
    """
    names = _path_names(path)
    leaf = names[-1]
    moe_expert = any(n in ("experts", "moe") for n in names) and leaf in (
        "w_gate", "w_up", "w_down", "wi", "wo_e")
    rank = len(shape)

    def pad(trailing):
        lead: list = [None] * (rank - len(trailing))
        # expert-stacked params: shard the expert dim (dim -4 or -3)
        if moe_expert and rank >= 3:
            lead[-1] = "experts"
        return tuple(lead) + tuple(trailing)

    if moe_expert:
        # EP: shard the expert dim only; inner matrix dims get FSDP at most
        # (sharding them on `model` too would duplicate the mesh axis)
        return pad(("fsdp" if fsdp else None, None))
    if leaf in ("wq", "wk", "wv", "w_gate", "w_up", "wi", "w_in", "w_gate_in",
                "w_r", "w_k", "w_v", "w_g", "w_rec_x", "w_rec_gate"):
        return pad(("fsdp" if fsdp else None, "heads" if leaf in ("wq",) else
                    ("kv_heads" if leaf in ("wk", "wv") else "ff")))
    if leaf in ("wo", "w_down", "wo_e", "w_out", "w_o"):
        return pad(("heads" if leaf in ("wo", "w_o") else "ff",
                    "fsdp" if fsdp else None))
    if leaf == "embed":
        return pad(("vocab", "fsdp" if fsdp else None))
    if leaf == "unembed":
        return pad(("fsdp" if fsdp else None, "vocab"))
    if leaf == "router":
        return pad(("fsdp" if fsdp else None, None))
    # norms / biases / small vectors: replicated
    return tuple([None] * rank)


def param_specs(params_tree, ctx: ShardingCtx, *, fsdp: bool = False,
                memory_kind: str | None = None):
    """Tree of NamedShardings matching ``params_tree`` (arrays or SDS)."""
    def one(path, leaf):
        names = param_logical_axes(path, leaf.shape, fsdp=fsdp)
        return ctx.sharding(names, leaf.shape, memory_kind=memory_kind)
    return jax.tree_util.tree_map_with_path(one, params_tree)


def with_specs(tree, specs):
    """Attach shardings to a ShapeDtypeStruct tree (for AOT lowering)."""
    return jax.tree.map(
        lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
        tree, specs)


def batch_axes(ctx: ShardingCtx) -> tuple:
    return tuple(a for a in ("pod", "data") if a in ctx.mesh.shape)
