"""Pallas TPU kernel: flash-decode attention over an INT8 KV cache.

TPU-native form of the paper's §5.3 (quantized GatherNd): during
auto-regressive decode the per-step cost is dominated by *reading the KV
cache* — exactly the big-tensor copies the paper quantized.  Keeping the
cache int8 and dequantizing in VMEM registers cuts decode HBM traffic ~4×
vs f32 (2× vs bf16) and shrinks beam-search cache reorders by the same
factor.

One query token per sequence attends to the full cache with an online
(flash) softmax: grid (batch, seq_blocks), f32 running max / sum /
accumulator per KV head in VMEM scratch.  Each K/V block spans every KV
head — the TPU's block rules want the last two block dims whole (or
(8, 128)-aligned), so a one-head slice of the ``HKV`` axis is not a legal
block — and the body loops over heads.  GQA query groups (G = H / H_kv)
ride along the sublane dim.  Sequence lengths (and the paged variant's
block tables) arrive as scalar-prefetch operands in SMEM.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
DEFAULT_BLOCK_S = 256
# f32/int8-dequant compute tiles want ≥ 8 rows in the sublane dim: a paged
# grid step covering a single page_size < 8 page would run its dots on
# mostly-empty tiles, so small-page pools fetch SUBLANE // page_size pages
# per step instead (see decode_attention_paged_pallas)
SUBLANE = 8


def _flash_kernel(*refs, n_prefetch: int, block_pages: int, n_steps: int,
                  block_len: int, sm_scale: float):
    """One online-softmax step over ``block_pages`` K/V blocks for every KV
    head.  Refs: ``n_prefetch`` SMEM operands (lengths last), q, then
    ``block_pages`` blocks each of k, k_scale, v, v_scale, the output and
    three scratch buffers.  Consecutive blocks hold consecutive token
    positions, so stacking them along the sublane dim keeps the position
    iota contiguous; with ``block_pages > 1`` a ``page_size < 8`` pool
    still feeds the dots full sublane tiles."""
    F = block_pages
    len_ref = refs[n_prefetch - 1]
    q_ref = refs[n_prefetch]
    kv = refs[n_prefetch + 1:n_prefetch + 1 + 4 * F]
    k_refs, ks_refs, v_refs, vs_refs = (kv[i * F:(i + 1) * F]
                                        for i in range(4))
    out_ref, m_ref, l_ref, acc_ref = refs[n_prefetch + 1 + 4 * F:]
    b, s = pl.program_id(0), pl.program_id(1)

    @pl.when(s == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def rows(blocks, h):
        parts = [r[0, :, h, :] for r in blocks]
        return parts[0] if F == 1 else jnp.concatenate(parts, axis=0)

    def scales(blocks):
        parts = [r[0] for r in blocks]                       # (n, HKV)
        return parts[0] if F == 1 else jnp.concatenate(parts, axis=0)

    ks_all, vs_all = scales(ks_refs), scales(vs_refs)
    length = len_ref[b]
    for h in range(k_refs[0].shape[2]):
        q = q_ref[0, h].astype(jnp.float32)                  # (G, dh)
        k = rows(k_refs, h).astype(jnp.float32)              # (F·n, dh)
        k = k * ks_all[:, h:h + 1]                           # dequant in VREGs
        scores = jax.lax.dot_general(                        # (G, F·n)
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale

        # logical position of this block's tokens; the length mask also
        # hides sentinel (unreserved) page slots, clamped into the pool
        pos = s * block_len + jax.lax.broadcasted_iota(
            jnp.int32, scores.shape, 1)
        valid = pos < length
        scores = jnp.where(valid, scores, NEG_INF)

        m_prev = m_ref[h]                                    # (G, 1)
        m_new = jnp.maximum(m_prev, jnp.max(scores, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(scores - m_new)
        p = jnp.where(valid, p, 0.0)

        v = rows(v_refs, h).astype(jnp.float32)
        v = v * vs_all[:, h:h + 1]

        l_ref[h] = l_ref[h] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[h] = acc_ref[h] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_ref[h] = m_new

    @pl.when(s == n_steps - 1)
    def _finalize():
        out = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
        out_ref[0] = out.astype(out_ref.dtype)


def _flash_decode(q, prefetch, kv_specs, kv_operands, *, n_steps: int,
                  block_len: int, block_pages: int, sm_scale: float,
                  interpret: bool) -> jax.Array:
    """The pallas_call both cache layouts share: grid (batch, n_steps)."""
    B, H, dh = q.shape
    HKV = kv_operands[0].shape[-2]
    assert H % HKV == 0, (H, HKV)
    G = H // HKV
    n_prefetch = len(prefetch)

    def head_map(b, s, *_):
        return (b, 0, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=n_prefetch,
        grid=(B, n_steps),
        in_specs=[pl.BlockSpec((1, HKV, G, dh), head_map), *kv_specs],
        out_specs=pl.BlockSpec((1, HKV, G, dh), head_map),
        scratch_shapes=[
            pltpu.VMEM((HKV, G, 1), jnp.float32),    # running max
            pltpu.VMEM((HKV, G, 1), jnp.float32),    # running denom
            pltpu.VMEM((HKV, G, dh), jnp.float32),   # output accumulator
        ],
    )
    out = pl.pallas_call(
        functools.partial(_flash_kernel, n_prefetch=n_prefetch,
                          block_pages=block_pages, n_steps=n_steps,
                          block_len=block_len, sm_scale=sm_scale),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, HKV, G, dh), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(*prefetch, q.reshape(B, HKV, G, dh), *kv_operands)
    return out.reshape(B, H, dh)


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
    _, S, HKV, dh = k_q.shape
    bs = min(block_s, S)
    pad = (-S) % bs
    if pad:
        k_q = jnp.pad(k_q, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v_q = jnp.pad(v_q, ((0, 0), (0, pad), (0, 0), (0, 0)))
        k_scale = jnp.pad(k_scale, ((0, 0), (0, pad), (0, 0)))
        v_scale = jnp.pad(v_scale, ((0, 0), (0, pad), (0, 0)))

    kv_spec = pl.BlockSpec((1, bs, HKV, dh), lambda b, s, L: (b, s, 0, 0))
    sc_spec = pl.BlockSpec((1, bs, HKV), lambda b, s, L: (b, s, 0))
    return _flash_decode(
        q, (lengths.astype(jnp.int32),),
        [kv_spec, sc_spec, kv_spec, sc_spec], [k_q, k_scale, v_q, v_scale],
        n_steps=(S + pad) // bs, block_len=bs, block_pages=1,
        sm_scale=sm_scale, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("sm_scale", "interpret",
                                             "pages_per_block"))
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
    pages_per_block: int = 0,  # 0 = auto: SUBLANE // ps for small pages
) -> jax.Array:
    """Flash-decode over a paged INT8 KV cache (paper §5.3, paged).

    Grid (batch, page_slot_block); the block table rides in as a
    scalar-prefetch operand so each slot's physical page id is known
    before the body runs and the K/V DMAs fetch pages directly — the
    paper's "big tensor stops moving" taken to its endpoint: decode reads
    exactly the pages a row owns, wherever they sit in the pool.

    When ``page_size < SUBLANE`` each grid step covers
    ``pages_per_block = SUBLANE // page_size`` consecutive slots (auto
    unless overridden) so the per-step dot still fills the 8-row sublane
    tile; block tables fill slots densely from the front, so a block's
    pages hold contiguous positions and the tail mask is unchanged.
    """
    P, ps, HKV, dh = k_pages.shape
    maxP = block_tables.shape[1]

    if pages_per_block < 0:
        raise ValueError(f"pages_per_block must be >= 0, got {pages_per_block}")
    F = pages_per_block or max(1, SUBLANE // ps)

    tab = block_tables.astype(jnp.int32)
    if maxP % F:
        # pad logical slots to a block multiple with sentinels: their
        # positions land past every cursor, so the `pos < len` mask drops
        # them exactly like any other unreserved slot
        tab = jnp.pad(tab, ((0, 0), (0, (-maxP) % F)), constant_values=P)
        maxP = tab.shape[1]
    tab = jnp.clip(tab, 0, P - 1)

    def kv_spec(j):
        return pl.BlockSpec((1, ps, HKV, dh),
                            lambda b, s, t, L: (t[b, s * F + j], 0, 0, 0))

    def sc_spec(j):
        return pl.BlockSpec((1, ps, HKV),
                            lambda b, s, t, L: (t[b, s * F + j], 0, 0))

    specs = ([kv_spec(j) for j in range(F)] + [sc_spec(j) for j in range(F)]
             + [kv_spec(j) for j in range(F)] + [sc_spec(j) for j in range(F)])
    operands = ([k_pages] * F + [k_scale] * F + [v_pages] * F
                + [v_scale] * F)
    return _flash_decode(
        q, (tab, lengths.astype(jnp.int32)), specs, operands,
        n_steps=maxP // F, block_len=F * ps, block_pages=F,
        sm_scale=sm_scale, interpret=interpret)
