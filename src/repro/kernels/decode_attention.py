"""Pallas TPU kernels: flash-decode attention over an INT8 KV cache.

TPU-native form of the paper's §5.3 (quantized GatherNd): during
auto-regressive decode the per-step cost is dominated by *reading the KV
cache* — exactly the big-tensor copies the paper quantized.  Keeping the
cache int8 and dequantizing in VMEM registers cuts decode HBM traffic ~4×
vs f32 (2× vs bf16) and shrinks beam-search cache reorders by the same
factor.  One query token per sequence attends to its cache; GQA query
groups (G = H / H_kv) share a KV head.

Two cache layouts, two kernels:

* contiguous (``decode_attention_pallas``): grid (batch, seq_blocks) with an
  online (flash) softmax, f32 running max / sum / accumulator per KV head
  in VMEM scratch.  Each K/V block spans every KV head — the TPU's block
  rules want the last two block dims whole (or (8, 128)-aligned), so a
  one-head slice of the ``HKV`` axis is not a legal block — and the body
  loops over heads.  Sequence lengths arrive as a scalar-prefetch operand.
* paged (``decode_attention_paged_pallas``): grid over blocks of rows (and,
  for rows too long for VMEM, chunks of page slots).  The page pools stay
  in HBM; each grid step copies only the live pages of its rows
  (``ceil(len / page_size)`` slots of each row's block table) into VMEM by
  manual DMA, whole tiles at a time, double-buffered so the next step's
  pages are in flight while this one computes, and runs every head of a
  page in one pass on the vector unit.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
DEFAULT_BLOCK_S = 256
# VMEM the paged kernel's scratch and blocks may fill; the rows and page
# slots of a grid step follow from it (see paged_block_plan)
PAGED_VMEM_BUDGET = 8 * 1024 * 1024


def _flash_kernel(len_ref, q_ref, k_ref, ks_ref, v_ref, vs_ref, out_ref,
                  m_ref, l_ref, acc_ref, *, n_steps: int, block_len: int,
                  sm_scale: float):
    """One online-softmax step over one contiguous K/V block for every KV
    head."""
    b, s = pl.program_id(0), pl.program_id(1)

    @pl.when(s == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    ks_all, vs_all = ks_ref[0], vs_ref[0]                    # (n, HKV)
    length = len_ref[b]
    for h in range(k_ref.shape[2]):
        q = q_ref[0, h].astype(jnp.float32)                  # (G, dh)
        k = k_ref[0, :, h, :].astype(jnp.float32)            # (n, dh)
        k = k * ks_all[:, h:h + 1]                           # dequant in VREGs
        scores = jax.lax.dot_general(                        # (G, n)
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale

        pos = s * block_len + jax.lax.broadcasted_iota(
            jnp.int32, scores.shape, 1)
        valid = pos < length
        scores = jnp.where(valid, scores, NEG_INF)

        m_prev = m_ref[h]                                    # (G, 1)
        m_new = jnp.maximum(m_prev, jnp.max(scores, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(scores - m_new)
        p = jnp.where(valid, p, 0.0)

        v = v_ref[0, :, h, :].astype(jnp.float32)
        v = v * vs_all[:, h:h + 1]

        l_ref[h] = l_ref[h] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[h] = acc_ref[h] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_ref[h] = m_new

    @pl.when(s == n_steps - 1)
    def _finalize():
        out = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
        out_ref[0] = out.astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("sm_scale", "block_s", "interpret"))
def decode_attention_pallas(
    q: jax.Array,          # (B, H, dh)
    k_q: jax.Array,        # (B, S, HKV, dh) int8
    k_scale: jax.Array,    # (B, S, HKV) f32
    v_q: jax.Array,        # (B, S, HKV, dh) int8
    v_scale: jax.Array,    # (B, S, HKV) f32
    lengths: jax.Array,    # (B,) int32
    *,
    sm_scale: float,
    block_s: int = DEFAULT_BLOCK_S,
    interpret: bool = False,
) -> jax.Array:
    B, S, HKV, dh = k_q.shape
    H = q.shape[1]
    assert H % HKV == 0, (H, HKV)
    G = H // HKV
    bs = min(block_s, S)
    pad = (-S) % bs
    if pad:
        k_q = jnp.pad(k_q, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v_q = jnp.pad(v_q, ((0, 0), (0, pad), (0, 0), (0, 0)))
        k_scale = jnp.pad(k_scale, ((0, 0), (0, pad), (0, 0)))
        v_scale = jnp.pad(v_scale, ((0, 0), (0, pad), (0, 0)))
    n_steps = (S + pad) // bs

    kv_spec = pl.BlockSpec((1, bs, HKV, dh), lambda b, s, L: (b, s, 0, 0))
    sc_spec = pl.BlockSpec((1, bs, HKV), lambda b, s, L: (b, s, 0))
    head_spec = pl.BlockSpec((1, HKV, G, dh), lambda b, s, L: (b, 0, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, n_steps),
        in_specs=[head_spec, kv_spec, sc_spec, kv_spec, sc_spec],
        out_specs=head_spec,
        scratch_shapes=[
            pltpu.VMEM((HKV, G, 1), jnp.float32),    # running max
            pltpu.VMEM((HKV, G, 1), jnp.float32),    # running denom
            pltpu.VMEM((HKV, G, dh), jnp.float32),   # output accumulator
        ],
    )
    out = pl.pallas_call(
        functools.partial(_flash_kernel, n_steps=n_steps, block_len=bs,
                          sm_scale=sm_scale),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, HKV, G, dh), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(lengths.astype(jnp.int32), q.reshape(B, HKV, G, dh), k_q, k_scale, v_q,
      v_scale)
    return out.reshape(B, H, dh)


# ---------------------------------------------------------------------------
# paged cache
# ---------------------------------------------------------------------------

def _hbm_tile(rows: int, lanes: int, dtype) -> tuple:
    """Extent Mosaic gives the two minor dims of an HBM operand: lanes
    rounded up to 128, rows to the sublane tile (the next power of two, at
    least the dtype's packing, 4 for int8, and at most 8).  A DMA must move
    whole tiles, so the paged kernel copies a page at this extent."""
    tile = max(min(8, pl.next_power_of_2(rows)), 4 // jnp.dtype(dtype).itemsize)
    return -(-rows // tile) * tile, -(-lanes // 128) * 128


def _page_extents(ps: int, HKV: int, dh: int, interpret: bool) -> tuple:
    """(K/V page, scale page) shapes the kernel copies: the tile-padded
    extents on the chip, the logical ones in interpret mode (whose arrays
    carry no padding)."""
    if interpret:
        return (ps, HKV, dh), (ps, HKV)
    return ((ps,) + _hbm_tile(HKV, dh, jnp.int8),
            _hbm_tile(ps, HKV, jnp.float32))


def _tiled_bytes(buf) -> int:
    """VMEM bytes of a scratch buffer: its two minor dims in whole tiles."""
    *lead, r, lanes = buf.shape
    dtype = jnp.dtype(buf.dtype)
    tiled = math.prod(_hbm_tile(r, lanes, dtype))
    return math.prod(lead) * tiled * dtype.itemsize


def _paged_scratch(rows: int, chunk: int, ps: int, H: int, HKV: int, dh: int,
                   interpret: bool) -> list:
    """VMEM scratch of a block of ``rows`` rows walking ``chunk`` page slots
    a grid step: f32 queries and outputs, the online softmax's running max,
    sum and accumulator per query group, and two slots (this step, the
    next) of the chunk's pages — K, K scale, V, V scale."""
    G = H // HKV
    kv, sc = _page_extents(ps, HKV, dh, interpret)
    lead = (2, rows, chunk)
    return ([pltpu.VMEM((rows, H, dh), jnp.float32)] * 2
            + [pltpu.VMEM((rows, G, HKV, 1), jnp.float32)] * 2
            + [pltpu.VMEM((rows, G, HKV, dh), jnp.float32),
               pltpu.VMEM(lead + kv, jnp.int8),
               pltpu.VMEM(lead + sc, jnp.float32),
               pltpu.VMEM(lead + kv, jnp.int8),
               pltpu.VMEM(lead + sc, jnp.float32)])


def paged_vmem_bytes(rows: int, chunk: int, ps: int, H: int, HKV: int,
                     dh: int) -> int:
    """VMEM the paged kernel plans on the chip: its scratch, and the
    pipeline's two buffers each of the query and output blocks (counted
    as f32)."""
    scratch = _paged_scratch(rows, chunk, ps, H, HKV, dh, interpret=False)
    blocks = 4 * _tiled_bytes(pltpu.VMEM((rows, H, dh), jnp.float32))
    return sum(_tiled_bytes(b) for b in scratch) + blocks


def paged_block_plan(B: int, maxP: int, ps: int, H: int, HKV: int,
                     dh: int) -> tuple:
    """(rows, chunk) of the paged kernel's grid steps, from shapes alone:
    a row's page slots are walked in ``ceil(maxP / chunk)`` equal chunks,
    one chunk when every slot of a row fits ``PAGED_VMEM_BUDGET`` twice,
    and each step takes as many rows as fit the budget, at most ``B``.
    Raises ``ValueError`` when not even one page slot of one row fits."""
    fixed = paged_vmem_bytes(1, 0, ps, H, HKV, dh)
    per_slot = paged_vmem_bytes(1, 1, ps, H, HKV, dh) - fixed
    most = (PAGED_VMEM_BUDGET - fixed) // per_slot
    if most < 1:
        raise ValueError(
            f"a page of {ps} tokens x {HKV} KV heads x {dh} needs "
            f"{fixed + per_slot} bytes of VMEM with one row's state; the "
            f"paged kernel's budget is {PAGED_VMEM_BUDGET}")
    chunk = pl.cdiv(maxP, pl.cdiv(maxP, most))
    rows = PAGED_VMEM_BUDGET // paged_vmem_bytes(1, chunk, ps, H, HKV, dh)
    return min(B, rows), chunk


def _paged_kernel(tab_ref, len_ref, q_ref, k_hbm, ks_hbm, v_hbm, vs_hbm,
                  out_ref, q32_ref, o32_ref, m_ref, l_ref, acc_ref, k_buf,
                  ks_buf, v_buf, vs_buf, sems, *, B: int, rows: int,
                  chunk: int, n_chunks: int, max_pages: int, page_size: int,
                  groups: int, sm_scale: float):
    """One chunk of page slots of a block of ``rows`` rows.  Refs: the flat
    block table and the lengths (SMEM), the block's queries, the four page
    pools (HBM), the block's output, f32 queries and outputs, the running
    max, sum and accumulator, the two-slot page buffers and their DMA
    semaphores (one per pool and slot)."""
    i, c = pl.program_id(0), pl.program_id(1)
    # grid steps run in order: step t's buffer slot is t % 2
    step = i * n_chunks + c
    n_steps = pl.num_programs(0) * n_chunks
    slot = step % 2
    ps, G = page_size, groups
    HKV, dh = q_ref.shape[1] // G, q_ref.shape[2]

    def live_pages(b, ch):
        """Pages row ``b`` holds in chunk ``ch``: its slots below
        ceil(len / ps) there, none past the batch."""
        length = len_ref[jnp.minimum(b, B - 1)]
        n = jnp.minimum((length + ps - 1) // ps, max_pages) - ch * chunk
        return jnp.where(b < B, jnp.clip(n, 0, chunk), 0)

    def each_copy(t, buf_slot, action):
        """``action`` on the four page copies of every live page of grid
        step ``t``'s rows and chunk, into ``buf_slot`` of the buffers."""
        block, ch = t // n_chunks, t % n_chunks

        def row(r, carry):
            b = block * rows + r

            def page(j, carry):
                pid = tab_ref[b * max_pages + ch * chunk + j]
                for n, (src, dst) in enumerate(((k_hbm, k_buf),
                                                (ks_hbm, ks_buf),
                                                (v_hbm, v_buf),
                                                (vs_hbm, vs_buf))):
                    extent = dst.shape[3:]
                    # the page's whole tiles: rows and lanes past the
                    # pool's logical shape are the tile padding it holds
                    src = src.at[(pid,) + tuple(pl.ds(0, e) for e in extent)]
                    action(pltpu.make_async_copy(
                        src, dst.at[buf_slot, r, j], sems.at[n, buf_slot]))
                return carry

            return jax.lax.fori_loop(0, live_pages(b, ch), page, carry)

        jax.lax.fori_loop(0, rows, row, 0)

    @pl.when(step == 0)
    def _first_step():
        each_copy(0, 0, lambda cp: cp.start())

    @pl.when(step + 1 < n_steps)
    def _prefetch_next_step():
        each_copy(step + 1, 1 - slot, lambda cp: cp.start())

    @pl.when(c == 0)
    def _new_block():
        # f32 copies: a group's heads are every G-th row, and Mosaic
        # strides loads and stores of 32-bit data only
        q32_ref[...] = q_ref[...].astype(jnp.float32)
        if n_chunks > 1:
            m_ref[...] = jnp.full_like(m_ref, NEG_INF)
            l_ref[...] = jnp.zeros_like(l_ref)
            acc_ref[...] = jnp.zeros_like(acc_ref)

    each_copy(step, slot, lambda cp: cp.wait())

    def finish(r, state):
        """Row ``r``'s output from its softmax state."""
        for g, (_, l, acc) in enumerate(state):
            o32_ref[r, pl.ds(g, HKV, stride=G), :] = (
                acc / jnp.maximum(l, 1e-30))

    def stored(r):
        return tuple((m_ref[r, g], l_ref[r, g], acc_ref[r, g])
                     for g in range(G))

    def row(r, carry):
        b = i * rows + r
        length = len_ref[jnp.minimum(b, B - 1)]
        # query head h·G + g attends KV head h (the oracle's reshape)
        qs = [q32_ref[r, pl.ds(g, HKV, stride=G), :] for g in range(G)]

        def page(j, state):
            # heads on sublanes: a token's (HKV, dh) slab is one tile, and
            # every per-head value below is an (HKV, 1) column of it
            k = k_buf[slot, r, j][:, :HKV, :dh].astype(jnp.float32)
            v = v_buf[slot, r, j][:, :HKV, :dh].astype(jnp.float32)
            ks = ks_buf[slot, r, j][:ps, :HKV][:, :, None] * sm_scale
            vs = vs_buf[slot, r, j][:ps, :HKV][:, :, None]
            pos = (c * chunk + j) * ps + jax.lax.broadcasted_iota(
                jnp.int32, (ps, HKV, 1), 0)
            valid = pos < length
            new = []
            for q, (m, l, acc) in zip(qs, state):
                s = jnp.sum(k * q[None], axis=-1, keepdims=True) * ks
                s = jnp.where(valid, s, NEG_INF)             # (ps, HKV, 1)
                m_new = jnp.maximum(m, jnp.max(s, axis=0))   # (HKV, 1)
                alpha = jnp.exp(m - m_new)
                p = jnp.where(valid, jnp.exp(s - m_new[None]), 0.0)
                l = l * alpha + jnp.sum(p, axis=0)
                acc = acc * alpha + jnp.sum((p * vs) * v, axis=0)  # dequant V
                new.append((m_new, l, acc))
            return tuple(new)

        if n_chunks == 1:
            # the whole row in this step: its state stays in registers
            init = tuple((jnp.full((HKV, 1), NEG_INF, jnp.float32),
                          jnp.zeros((HKV, 1), jnp.float32),
                          jnp.zeros((HKV, dh), jnp.float32))
                         for _ in range(G))
            finish(r, jax.lax.fori_loop(0, live_pages(b, c), page, init))
        else:
            state = jax.lax.fori_loop(0, live_pages(b, c), page, stored(r))
            for g, (m, l, acc) in enumerate(state):
                m_ref[r, g], l_ref[r, g], acc_ref[r, g] = m, l, acc
        return carry

    jax.lax.fori_loop(0, rows, row, 0)

    @pl.when(c == n_chunks - 1)
    def _write_block():
        if n_chunks > 1:
            def row(r, carry):
                finish(r, stored(r))
                return carry

            jax.lax.fori_loop(0, rows, row, 0)
        out_ref[...] = o32_ref[...].astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("sm_scale", "interpret"))
def decode_attention_paged_pallas(
    q: jax.Array,            # (B, H, dh)
    k_pages: jax.Array,      # (P, ps, HKV, dh) int8 page pool
    k_scale: jax.Array,      # (P, ps, HKV) f32
    v_pages: jax.Array,      # (P, ps, HKV, dh) int8
    v_scale: jax.Array,      # (P, ps, HKV) f32
    block_tables: jax.Array, # (B, maxP) int32; sentinel P = unreserved
    lengths: jax.Array,      # (B,) int32
    *,
    sm_scale: float,
    interpret: bool = False,
) -> jax.Array:
    """Flash-decode over a paged INT8 KV cache (paper §5.3, paged).

    Grid (ceil(B / rows), ceil(maxP / chunk)) over blocks of ``rows`` rows
    and chunks of ``chunk`` page slots (:func:`paged_block_plan`: one chunk
    unless a row's slots outgrow the VMEM budget).  The block table and
    lengths ride in as scalar-prefetch operands; the pools stay in HBM, and
    each grid step DMAs exactly the pages its rows hold in its chunk —
    slots below ``ceil(len / page_size)``, wherever they sit in the pool —
    into VMEM, while the next step's copies are in flight.  A row of length
    0 copies nothing and writes 0; slots past a row's length, sentinels
    included, are never read.  Each page runs all of a row's heads in one
    online-softmax pass, in f32 from the dequantized INT8 K/V, on the
    vector unit: with G = 1 each dot is a matrix-vector product, too thin
    for the MXU.  The running max, sum and accumulator carry a row across
    chunks.
    """
    B, H, dh = q.shape
    P, ps, HKV, _ = k_pages.shape
    maxP = block_tables.shape[1]
    assert H % HKV == 0, (H, HKV)
    rows, chunk = paged_block_plan(B, maxP, ps, H, HKV, dh)
    n_chunks = pl.cdiv(maxP, chunk)
    # slots past a row's live pages are never copied; the clip keeps a
    # sentinel inside a live slot (a caller's bug) from reading past the pool
    tab = jnp.clip(block_tables.astype(jnp.int32), 0, P - 1).reshape(-1)
    row_spec = pl.BlockSpec((rows, H, dh), lambda i, c, t, L: (i, 0, 0))
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(pl.cdiv(B, rows), n_chunks),
        in_specs=[row_spec, hbm, hbm, hbm, hbm],
        out_specs=row_spec,
        scratch_shapes=_paged_scratch(rows, chunk, ps, H, HKV, dh, interpret)
        + [pltpu.SemaphoreType.DMA((4, 2))],
    )
    return pl.pallas_call(
        functools.partial(_paged_kernel, B=B, rows=rows, chunk=chunk,
                          n_chunks=n_chunks, max_pages=maxP, page_size=ps,
                          groups=H // HKV, sm_scale=sm_scale),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, dh), q.dtype),
        compiler_params=pltpu.CompilerParams(
            # the next step's copies start in this one: the steps run in
            # order
            dimension_semantics=("arbitrary", "arbitrary"),
        ),
        interpret=interpret,
    )(tab, lengths.astype(jnp.int32), q, k_pages, k_scale, v_pages, v_scale)
