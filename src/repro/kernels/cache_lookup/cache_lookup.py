"""Pallas TPU kernel: fused cache lookup + dedup gather + miss-list emit.

Helios's core mechanism (paper §3.2-3.3) is a *GPU-managed* cache: the
accelerator does the cache lookup at memory bandwidth and misses feed a
GPU-initiated IO stack directly, so the host never walks the id batch.
This kernel is the TPU analogue.  One launch over a raw (duplicated) id
batch performs, per grid step:

  1. **slot lookup** — ``loc``/``slot`` tables are scalar-prefetched into
     SMEM; ``loc[id]`` picks the tier (0 device / 1 host / 2 storage /
     3 remote) and ``slot[id]`` drives the BlockSpec index_map, so the DMA
     engine fetches the ``ROWS``-row block holding the cached row
     HBM->VMEM with no gather unit;
  2. **duplicate collapse** — the id batch is also resident in VMEM as a
     (1, B) vector; a VPU compare against the current id plus a masked
     min-reduce yields the first occurrence index (``first_idx``), no sort;
  3. **tiered gather + scatter** — the selected tier row (or zeros for a
     miss) is written to ``out[i]`` in the padded output buffer;
  4. **miss-list emission** — first occurrences of storage/remote ids are
     compacted into ``miss_ids/miss_dest`` and ``rem_ids/rem_dest`` via an
     SMEM running counter (TPU grid steps are sequential, so the counter
     is a plain scalar); the compacted lists feed
     ``AsyncIOEngine.submit()`` / ``RemoteIOEngine.submit()`` verbatim.

Output contract (fixed shapes so the op jits; ``counts`` carries the
valid prefix lengths, the tail is padded with -1):

  out        (B, D)  gathered rows; zeros at storage/remote positions
  first_idx  (B,)    index of the first occurrence of ids[i] in the batch
  miss_ids   (B,)    storage-tier ids, first occurrences, batch order
  miss_dest  (B,)    output row for each entry of miss_ids
  rem_ids    (B,)    remote-tier ids, first occurrences, batch order
  rem_dest   (B,)    output row for each entry of rem_ids
  counts     (2,)    [n_storage_unique, n_remote_unique]

Both cache tiers must hold a positive multiple of ``ROWS`` rows;
``ops.fused_cache_lookup`` pads them with zero rows (never selected: no
id maps to a padding slot).

Scalar memory bounds the shapes: the ``(N,)`` tables, the ``(B,)`` id
batch and the five ``(B,)`` SMEM outputs must fit in it together
(``smem_bytes``).  On a TPU v5e that caps ``B`` at 21,504 for
``N = 65,536``, and ``N`` at 126,976 for ``B <= 1,024``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

ROWS = 8    # rows per gathered tier block (the f32 sublane tile)
SMEM_BYTES = 1 << 20    # scalar memory of one TPU v5e core
_SMEM_PAGE = 4096       # Mosaic allocates SMEM operands in 4 KiB pages


def smem_bytes(n_rows: int, batch: int) -> int:
    """Scalar memory the kernel allocates for ``n_rows`` table entries and
    a ``batch``-id launch: the two int32 tables, the id batch, the five
    (B,) outputs, the counts and the counter scratch, each page-rounded."""
    def page(n):
        return -(-4 * n // _SMEM_PAGE) * _SMEM_PAGE
    return 2 * page(n_rows) + 6 * page(batch) + 2 * page(2)


def _fused_kernel(ids_s, loc_s, slot_s,          # scalar prefetch (SMEM)
                  idvec_ref, dev_ref, host_ref,  # VMEM inputs
                  out_ref, first_ref,            # outputs
                  mid_ref, mdst_ref, rid_ref, rdst_ref, cnt_ref,
                  cnt_scr):                      # SMEM scratch
    i = pl.program_id(0)
    n = pl.num_programs(0)
    idv = ids_s[i]
    tier = loc_s[idv]
    sl = slot_s[idv]

    @pl.when(i == 0)
    def _init():
        cnt_scr[0] = 0
        cnt_scr[1] = 0

    # Clear this step's slot in the compacted lists.  The running counters
    # never exceed the step index (<=1 append per step), so slot i cannot
    # have been written by an earlier step.
    mid_ref[i] = -1
    mdst_ref[i] = -1
    rid_ref[i] = -1
    rdst_ref[i] = -1

    # Duplicate collapse: first occurrence of idv across the whole batch.
    pos = jax.lax.broadcasted_iota(jnp.int32, (1, idvec_ref.shape[1]), 1)
    eq = idvec_ref[...] == idv
    first = jnp.min(jnp.where(eq, pos, n))
    first_ref[i] = first
    is_first = first == i

    # Tiered gather: the index_maps staged the ROWS-row blocks holding the
    # candidate device and host rows (block 0 when the tier does not
    # apply); pick the row inside each block, then select by tier.
    r = sl % ROWS
    dev_row = dev_ref[pl.ds(r, 1), :]
    host_row = host_ref[pl.ds(r, 1), :].astype(dev_row.dtype)
    row = jnp.where(tier == 0, dev_row,
                    jnp.where(tier == 1, host_row, jnp.zeros_like(dev_row)))
    out_ref[pl.ds(i % ROWS, 1), :] = row.astype(out_ref.dtype)

    # Miss-list emission: compact first-occurrence storage/remote ids with
    # SMEM running counters (grid steps are sequential on TPU).
    @pl.when((tier == 2) & is_first)
    def _emit_storage():
        c = cnt_scr[0]
        mid_ref[c] = idv
        mdst_ref[c] = i
        cnt_scr[0] = c + 1

    @pl.when((tier == 3) & is_first)
    def _emit_remote():
        c = cnt_scr[1]
        rid_ref[c] = idv
        rdst_ref[c] = i
        cnt_scr[1] = c + 1

    cnt_ref[0] = cnt_scr[0]
    cnt_ref[1] = cnt_scr[1]


def fused_lookup(ids: jax.Array, loc: jax.Array, slot: jax.Array,
                 device_tier: jax.Array, host_tier: jax.Array, *,
                 interpret: bool = False):
    """ids: (B,) int32 raw (possibly duplicated) node ids; loc/slot: (N,)
    int32 tier tables; device_tier: (n_dev, D); host_tier: (n_host, D).
    Both tiers must hold a positive multiple of ``ROWS`` rows (pad
    upstream).  Returns the 7-tuple documented in the module docstring."""
    B = ids.shape[0]
    D = device_tier.shape[1]
    b_pad = -(-B // ROWS) * ROWS

    def dev_map(i, ids_ref, loc_ref, slot_ref):
        v = ids_ref[i]
        return (jnp.where(loc_ref[v] == 0, slot_ref[v] // ROWS, 0), 0)

    def host_map(i, ids_ref, loc_ref, slot_ref):
        v = ids_ref[i]
        return (jnp.where(loc_ref[v] == 1, slot_ref[v] // ROWS, 0), 0)

    smem_i32 = pl.BlockSpec(memory_space=pltpu.SMEM)
    out_shape = (
        jax.ShapeDtypeStruct((b_pad, D), device_tier.dtype),  # out
        jax.ShapeDtypeStruct((B,), jnp.int32),             # first_idx
        jax.ShapeDtypeStruct((B,), jnp.int32),             # miss_ids
        jax.ShapeDtypeStruct((B,), jnp.int32),             # miss_dest
        jax.ShapeDtypeStruct((B,), jnp.int32),             # rem_ids
        jax.ShapeDtypeStruct((B,), jnp.int32),             # rem_dest
        jax.ShapeDtypeStruct((2,), jnp.int32),             # counts
    )
    # Row blocks are ROWS tall (Mosaic tiles the second-minor dim by 8):
    # step i writes row i % ROWS of output block i // ROWS, which stays
    # resident in VMEM across its ROWS consecutive steps.
    out_specs = (
        pl.BlockSpec((ROWS, D), lambda i, *_: (i // ROWS, 0)),
        smem_i32, smem_i32, smem_i32, smem_i32, smem_i32, smem_i32,
    )
    in_specs = [
        pl.BlockSpec((1, B), lambda i, *_: (0, 0)),  # id batch, VMEM resident
        pl.BlockSpec((ROWS, D), dev_map),
        pl.BlockSpec((ROWS, D), host_map),
    ]

    res = pl.pallas_call(
        _fused_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(B,),
            in_specs=in_specs,
            out_specs=out_specs,
            scratch_shapes=[pltpu.SMEM((2,), jnp.int32)],
        ),
        out_shape=out_shape,
        # steps share SMEM counters and revisit output blocks: sequential
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(ids.astype(jnp.int32), loc.astype(jnp.int32), slot.astype(jnp.int32),
      ids.astype(jnp.int32).reshape(1, B), device_tier, host_tier)
    return (res[0][:B],) + tuple(res[1:])
