"""Pallas TPU kernels: the writes into the stacked paged scale pools.

The paged decode kernel reads the stacked (L, P, ps, HKV) f32 scale pools
in the layout Mosaic gives every operand: row-major, the (ps, HKV) page in
whole tiles.  XLA keeps an f32 pool that it scatters into inside the
decode loop in a compact layout of its own choosing (the page axis minor)
and relayouts the whole stack before each kernel call.  So the decode
loop's two writers of the scale pools are these kernels, which keep the
pools in the kernel's layout and write them in place:

* ``append_scales_paged_pallas``: each new token's scales, one DMA a row
  and pool from VMEM to its (layer, page, offset) row;
* ``copy_scale_pages_pallas``: the beam step's copy-on-write, whole pages
  through VMEM, every layer.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.decode_attention import scale_page_extent


def _append_kernel(page_ref, off_ref, layer_ref, ks_ref, vs_ref, ks_in,
                   vs_in, ks_out, vs_out, sems, *, n_pages: int):
    """Refs: each row's page and offset and the layer (SMEM), the rows' new
    K and V scales (VMEM), the two stacked scale pools (HBM, in and out
    alias), and a DMA semaphore per pool.  Rows whose page is past the
    pool write nowhere."""
    del ks_in, vs_in                      # aliased by ks_out, vs_out
    layer = layer_ref[0]
    lanes = ks_ref.shape[-1]

    def each_copy(action):
        def row(b, carry):
            page = page_ref[b]

            @pl.when(page < n_pages)
            def _copy():
                dst = (layer, page, pl.ds(off_ref[b], 1), pl.ds(0, lanes))
                for n, (src, pool) in enumerate(((ks_ref, ks_out),
                                                 (vs_ref, vs_out))):
                    action(pltpu.make_async_copy(src.at[b], pool.at[dst],
                                                 sems.at[n]))
            return carry

        jax.lax.fori_loop(0, page_ref.shape[0], row, 0)

    each_copy(lambda cp: cp.start())
    each_copy(lambda cp: cp.wait())


@functools.partial(jax.jit, static_argnames=("interpret",))
def append_scales_paged_pallas(
    ks_pages: jax.Array,     # (L, P, ps, HKV) f32 stacked scale pool
    vs_pages: jax.Array,     # (L, P, ps, HKV) f32
    ks: jax.Array,           # (N, HKV) f32 new K scales, one row each
    vs: jax.Array,           # (N, HKV) f32
    page: jax.Array,         # (N,) int32 page ids; ≥ P writes nowhere
    off: jax.Array,          # (N,) int32 offsets within the page
    layer,                   # int or int32 scalar
    *,
    interpret: bool = False,
):
    """Scatter ``ks[n]``/``vs[n]`` to ``[layer, page[n], off[n]]`` of the
    pools, in place, and return the pools.  Each row is one DMA a pool of
    the row's HKV scales, padded to the pool's lane tile on the chip (the
    lanes past HKV are the tile padding the pool holds).  No two rows may
    name the same (page, offset)."""
    _, P, ps, HKV = ks_pages.shape
    N = ks.shape[0]
    lanes = scale_page_extent(ps, HKV, interpret)[1]
    rows = lambda x: jnp.pad(x.astype(jnp.float32),
                             ((0, 0), (0, lanes - HKV))).reshape(N, 1, lanes)
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    pools = jax.ShapeDtypeStruct(ks_pages.shape, ks_pages.dtype)
    return pl.pallas_call(
        functools.partial(_append_kernel, n_pages=P),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(),
            in_specs=[vmem, vmem, hbm, hbm], out_specs=[hbm, hbm],
            scratch_shapes=[pltpu.SemaphoreType.DMA((2,))]),
        out_shape=[pools, pools],
        # operands count the three scalar-prefetch ones first
        input_output_aliases={5: 0, 6: 1},
        interpret=interpret,
    )(page.astype(jnp.int32), off.astype(jnp.int32),
      jnp.asarray(layer, jnp.int32).reshape(1), rows(ks), rows(vs),
      ks_pages, vs_pages)


def _copy_kernel(src_ref, dst_ref, ks_in, vs_in, ks_out, vs_out, buf, sems,
                 *, n_layers: int, n_pages: int):
    """Refs: each row's source and destination page (SMEM), the two
    stacked scale pools (HBM, in and out alias), a VMEM page buffer per
    row, and read and write DMA semaphores.  For each layer and pool every
    moved page is read before any is written, so a page that is one row's
    source and another's destination moves as a gather then a scatter
    would.  Rows whose destination is past the pool, or is their source,
    copy nothing."""
    del ks_in, vs_in                      # aliased by ks_out, vs_out
    extent = tuple(pl.ds(0, e) for e in buf.shape[1:])

    def each_copy(layer, pool, read, action):
        def row(b, carry):
            src, dst = src_ref[b], dst_ref[b]

            @pl.when((dst < n_pages) & (src != dst))
            def _copy():
                if read:
                    cp = pltpu.make_async_copy(
                        pool.at[(layer, src) + extent], buf.at[b], sems.at[0])
                else:
                    cp = pltpu.make_async_copy(
                        buf.at[b], pool.at[(layer, dst) + extent], sems.at[1])
                action(cp)
            return carry

        jax.lax.fori_loop(0, src_ref.shape[0], row, 0)

    def layer(li, carry):
        for pool in (ks_out, vs_out):
            for read in (True, False):
                each_copy(li, pool, read, lambda cp: cp.start())
                each_copy(li, pool, read, lambda cp: cp.wait())
        return carry

    jax.lax.fori_loop(0, n_layers, layer, 0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def copy_scale_pages_pallas(
    ks_pages: jax.Array,     # (L, P, ps, HKV) f32 stacked scale pool
    vs_pages: jax.Array,     # (L, P, ps, HKV) f32
    src: jax.Array,          # (B,) int32 page each row copies, < P
    dst: jax.Array,          # (B,) int32 page it copies to; ≥ P: none
    *,
    interpret: bool = False,
):
    """Copy page ``src[b]`` onto page ``dst[b]`` in every layer of both
    scale pools, in place, and return the pools: the copy-on-write of
    :func:`repro.models.kv_cache.cow_write_slot`.  Each moved page goes
    through VMEM, one DMA in and one out a layer and pool, at the extent
    the paged decode kernel copies a page at (``scale_page_extent``).
    No two rows may name the same destination."""
    L, P, ps, HKV = ks_pages.shape
    B = src.shape[0]
    page = scale_page_extent(ps, HKV, interpret)
    buf = pltpu.VMEM((B,) + page, jnp.float32)
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    pools = jax.ShapeDtypeStruct(ks_pages.shape, ks_pages.dtype)
    vmem_bytes = B * page[0] * page[1] * 4
    return pl.pallas_call(
        functools.partial(_copy_kernel, n_layers=L, n_pages=P),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(),
            in_specs=[hbm, hbm], out_specs=[hbm, hbm],
            scratch_shapes=[buf, pltpu.SemaphoreType.DMA((2,))]),
        out_shape=[pools, pools],
        input_output_aliases={2: 0, 3: 1},
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=max(16 << 20, vmem_bytes + (4 << 20))),
        interpret=interpret,
    )(src.astype(jnp.int32), dst.astype(jnp.int32), ks_pages, vs_pages)
