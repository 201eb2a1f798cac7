"""Pure-jnp oracles for every Pallas kernel in this package.

Each ``ref_*`` function computes the kernel's result with plain jax.numpy at
full (int32/float32) precision.  The kernel tests sweep shapes/dtypes and
``assert_allclose`` against these.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

INT8_MAX = 127.0


# ---------------------------------------------------------------------------
# int8 matmul
# ---------------------------------------------------------------------------

def ref_int8_matmul(
    a_q: jax.Array,            # (M, K) int8
    a_scale: jax.Array,        # (M, 1) or scalar f32 (dequant scale)
    b_q: jax.Array,            # (K, N) int8
    b_scale: jax.Array,        # (1, N) or scalar f32
    a_zero_point: Optional[jax.Array] = None,   # scalar f32 (q-space offset)
    bias: Optional[jax.Array] = None,           # (N,) f32
    out_dtype=jnp.float32,
) -> jax.Array:
    """Exact integer accumulation then affine epilogue.

    real(a) = (a_q - zp_a) * a_scale ;  real(b) = b_q * b_scale (symmetric)
    =>  a @ b = a_scale*b_scale * (a_q@b_q - zp_a * colsum(b_q))
    """
    acc = jax.lax.dot_general(
        a_q, b_q, (((1,), (0,)), ((), ())), preferred_element_type=jnp.int32
    ).astype(jnp.float32)
    if a_zero_point is not None:
        colsum = jnp.sum(b_q.astype(jnp.int32), axis=0, keepdims=True)
        acc = acc - jnp.asarray(a_zero_point, jnp.float32) * colsum.astype(jnp.float32)
    out = acc * jnp.asarray(a_scale, jnp.float32) * jnp.asarray(b_scale, jnp.float32)
    if bias is not None:
        out = out + bias.astype(jnp.float32)
    return out.astype(out_dtype)


def ref_int8_matmul_batched(
    a_q: jax.Array,            # (E, M, K) int8
    a_scale: jax.Array,        # (E, M, 1) f32
    b_q: jax.Array,            # (E, K, N) int8
    b_scale: jax.Array,        # (E, 1, N) f32
    out_dtype=jnp.float32,
) -> jax.Array:
    """Grouped (per-expert) int8 matmul oracle."""
    acc = jax.lax.dot_general(
        a_q, b_q, (((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.int32,
    ).astype(jnp.float32)
    out = acc * jnp.asarray(a_scale, jnp.float32) * jnp.asarray(b_scale,
                                                                jnp.float32)
    return out.astype(out_dtype)


# ---------------------------------------------------------------------------
# int4 (block-quantized weight) matmul
# ---------------------------------------------------------------------------

def ref_int4_matmul(
    a_q: jax.Array,            # (M, K) int8 activations
    a_scale: jax.Array,        # (M, 1) or scalar f32 (dequant scale)
    b_packed: jax.Array,       # (K_store//2, N) int8 packed nibbles
    b_scale: jax.Array,        # (n_groups, N) f32/f16 block scales
    b_min: jax.Array,          # (n_groups, N) f32/f16 block minimums
    a_zero_point: Optional[jax.Array] = None,   # scalar f32 (q-space offset)
    bias: Optional[jax.Array] = None,           # (N,) f32
    *,
    group_size: int,
    out_dtype=jnp.float32,
) -> jax.Array:
    """Group-wise oracle for the dequant-in-kernel INT4 matmul.

    real(b)[k, n] = nib[k, n] * scale[k // G, n] + vmin[k // G, n]
    =>  a @ b = a_scale * [ Σ_g (scale_g · (a_q @ nib)_g + vmin_g · rowsum_g)
                            - zp · colsum(real(b)) ] + bias

    The per-group integer dots are exact (int32); the f32 combination runs
    in ascending-group order with the same op sequence as the Pallas kernel,
    so interpret-mode results are bit-identical (both paths execute each
    primitive separately — no cross-op FMA contraction).
    """
    from repro.core.qtensor import unpack_nibbles

    M, K = a_q.shape
    n_g = b_scale.shape[0]
    G = group_size
    k_store = n_g * G
    N = b_packed.shape[1]
    nib = unpack_nibbles(b_packed).astype(jnp.int8)          # (k_store, N)
    a_p = (jnp.pad(a_q, ((0, 0), (0, k_store - K)))
           if k_store > K else a_q)
    acc = jnp.zeros((M, N), jnp.float32)
    for g in range(n_g):
        a_g = a_p[:, g * G:(g + 1) * G]
        d = jnp.dot(a_g, nib[g * G:(g + 1) * G, :],
                    preferred_element_type=jnp.int32)
        rsum = jnp.sum(a_g.astype(jnp.int32), axis=1, keepdims=True)
        acc = acc + (d.astype(jnp.float32)
                     * b_scale[g].astype(jnp.float32)[None, :]
                     + rsum.astype(jnp.float32)
                     * b_min[g].astype(jnp.float32)[None, :])
    if a_zero_point is not None:
        s = jnp.repeat(b_scale.astype(jnp.float32), G, axis=0)
        m = jnp.repeat(b_min.astype(jnp.float32), G, axis=0)
        deq = nib.astype(jnp.float32) * s + m
        colsum = jnp.sum(deq[:K, :], axis=0, keepdims=True)
        acc = acc - jnp.asarray(a_zero_point, jnp.float32) * colsum
    out = acc * jnp.asarray(a_scale, jnp.float32)
    if bias is not None:
        out = out + bias.astype(jnp.float32)
    return out.astype(out_dtype)


# ---------------------------------------------------------------------------
# quantize
# ---------------------------------------------------------------------------

def ref_quantize_rowwise(x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Dynamic symmetric row-wise quantization: returns (int8, (M,1) scales)."""
    amax = jnp.maximum(jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1,
                               keepdims=True), 1e-12)
    scale = amax / INT8_MAX
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale), -INT8_MAX, INT8_MAX)
    return q.astype(jnp.int8), scale


def ref_quantize_static(x: jax.Array, amax: jax.Array) -> jax.Array:
    """Static-scale symmetric quantization (calibrated threshold)."""
    scale = jnp.maximum(jnp.asarray(amax, jnp.float32), 1e-12) / INT8_MAX
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale), -INT8_MAX, INT8_MAX)
    return q.astype(jnp.int8)


# ---------------------------------------------------------------------------
# decode attention over int8 KV cache
# ---------------------------------------------------------------------------

def ref_decode_attention(
    q: jax.Array,          # (B, H, dh) f32/bf16
    k_q: jax.Array,        # (B, S, HKV, dh) int8
    k_scale: jax.Array,    # (B, S, HKV) f32
    v_q: jax.Array,        # (B, S, HKV, dh) int8
    v_scale: jax.Array,    # (B, S, HKV) f32
    lengths: jax.Array,    # (B,) int32 — valid cache length per sequence
    sm_scale: float,
) -> jax.Array:
    """Masked attention of one query token against a dequantized KV cache."""
    B, S, HKV, dh = k_q.shape
    H = q.shape[1]
    G = H // HKV
    k = k_q.astype(jnp.float32) * k_scale[..., None]
    v = v_q.astype(jnp.float32) * v_scale[..., None]
    qf = q.astype(jnp.float32).reshape(B, HKV, G, dh)
    # scores: (B, HKV, G, S)
    scores = jnp.einsum("bhgd,bshd->bhgs", qf, k) * sm_scale
    mask = jnp.arange(S)[None, :] < lengths[:, None]          # (B, S)
    scores = jnp.where(mask[:, None, None, :], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhgs,bshd->bhgd", probs, v)
    return out.reshape(B, H, dh).astype(q.dtype)


def ref_decode_attention_paged(
    q: jax.Array,           # (B, H, dh) f32/bf16
    k_pages: jax.Array,     # (L, P, ps, HKV, dh) int8 stacked page pools
    k_scale: jax.Array,     # (L, P, ps, HKV) f32
    v_pages: jax.Array,     # (L, P, ps, HKV, dh) int8
    v_scale: jax.Array,     # (L, P, ps, HKV) f32
    block_tables: jax.Array,  # (B, maxP) int32 (sentinel = P, clamped)
    lengths: jax.Array,     # (B,) int32
    layer,                  # int or int32 scalar
    sm_scale: float,
) -> jax.Array:
    """Paged oracle: take layer ``layer`` of the pools, linearize each
    row's pages through its block table, then run the contiguous oracle.
    Sentinel (unreserved) entries clamp into the pool and are masked out
    by ``lengths``; with the logical capacity equal to the contiguous
    cache's ``S`` the result is bit-identical to
    :func:`ref_decode_attention` on the linearized rows.
    """
    P = k_pages.shape[1]
    B, maxP = block_tables.shape
    tab = jnp.clip(block_tables, 0, P - 1)

    def lin(pool):
        got = pool[layer][tab]                    # (B, maxP, ps, …)
        return got.reshape((B, maxP * pool.shape[2]) + pool.shape[3:])

    return ref_decode_attention(q, lin(k_pages), lin(k_scale),
                                lin(v_pages), lin(v_scale), lengths, sm_scale)
