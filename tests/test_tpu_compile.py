"""Ahead-of-time compiles for a described TPU v5e chip at the shapes
``chip_smoke.py`` runs: the GNN train and infer steps and the fused
cache-lookup kernel.  Nothing runs; the TPU compiler refuses here what the
chip would refuse (block tiling, scalar memory, device memory)."""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.gnn.models import (init_gnn_params, make_gnn_infer_step,
                              make_gnn_train_step)
from repro.kernels.cache_lookup.cache_lookup import SMEM_BYTES, smem_bytes
from repro.kernels.cache_lookup.ops import fused_cache_lookup
from repro.train.optim import adamw

HBM_BYTES = 16 * 1024 ** 3          # one TPU v5e chip
D, HIDDEN, CLASSES = 1024, 256, 47  # CL row width, paper hidden size
FANOUTS = (25, 10)
# (table rows N, batch B) on the kernel's scalar-memory frontier: each
# compiles, and one more id in the batch does not
FRONTIER = [(1_024, 43_008), (65_536, 21_504), (126_976, 1_024)]


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    log_dir = os.environ.get("TPU_LOG_DIR")
    os.environ["TPU_LOG_DIR"] = "disabled"
    # a compile for a described chip cannot be read back from the
    # persistent cache, so keep it out of the cache
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield SingleDeviceSharding(topo.devices[0])
    finally:
        jax.config.update("jax_enable_compilation_cache", was_on)
        cc.reset_cache()
        if log_dir is None:
            os.environ.pop("TPU_LOG_DIR", None)
        else:
            os.environ["TPU_LOG_DIR"] = log_dir


def _sds(tree, sharding):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree)


def _batch(batch, sharding):
    """Padded sampler shapes: feats, (src, dst, mask) per hop, labels."""
    edges, e = [], batch
    for f in FANOUTS:
        e *= f
        edges.append(e)
    rows = batch + sum(edges)

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
    return (s((rows, D), jnp.float32),
            tuple(s((n,), jnp.int32) for n in edges),
            tuple(s((n,), jnp.int32) for n in edges),
            tuple(s((n,), jnp.bool_) for n in edges),
            s((batch,), jnp.int32))


def _device_bytes(compiled):
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes
            + m.generated_code_size_in_bytes)


def _params():
    return jax.eval_shape(lambda: init_gnn_params(
        jax.random.key(0), "sage", D, HIDDEN, CLASSES))


def test_train_step_compiles_and_fits(one_chip):
    opt = adamw(1e-3)
    state = jax.eval_shape(lambda p: {"params": p, "opt": opt.init(p)},
                           _params())
    feats, src, dst, em, labels = _batch(1024, one_chip)
    assert feats.shape == (282_624, D)
    compiled = make_gnn_train_step("sage", opt, 1024).lower(
        _sds(state, one_chip), feats, src, dst, em, labels).compile()
    assert feats.size * 4 < _device_bytes(compiled) < HBM_BYTES


def test_infer_step_compiles_and_fits(one_chip):
    feats, src, dst, em, _ = _batch(64, one_chip)
    compiled = make_gnn_infer_step("sage", 64).lower(
        _sds(_params(), one_chip), feats, src, dst, em).compile()
    assert feats.size * 4 < _device_bytes(compiled) < HBM_BYTES


def _lookup_args(n, b, sharding):
    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
    # 5 % device and 10 % host tiers, unpadded as HeteroCache holds them
    return (s((b,), jnp.int32), s((n,), jnp.int8), s((n,), jnp.int32),
            s((n // 20, D), jnp.float32), s((n // 10, D), jnp.float32))


@pytest.mark.parametrize("n,b", FRONTIER)
def test_cache_lookup_kernel_compiles_on_smem_frontier(one_chip, n, b):
    assert smem_bytes(n, b) <= SMEM_BYTES < smem_bytes(n, b + 1)
    lowered = fused_cache_lookup.lower(*_lookup_args(n, b, one_chip),
                                       use_pallas=True)
    assert "tpu_custom_call" in lowered.as_text()
    lowered.compile()


@pytest.mark.parametrize("n,b", FRONTIER)
def test_cache_lookup_kernel_refused_past_smem(one_chip, n, b):
    lowered = fused_cache_lookup.lower(*_lookup_args(n, b + 1, one_chip),
                                       use_pallas=True)
    with pytest.raises(Exception, match="smem"):
        lowered.compile()
