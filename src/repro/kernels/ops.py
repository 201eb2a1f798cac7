"""Public jit'd wrappers around the Pallas kernels.

Every op dispatches on ``impl``:

* ``"pallas"``     — the TPU kernel (the deployment path),
* ``"interpret"``  — the same kernel body interpreted on CPU (tests),
* ``"xla"``        — pure-jnp fallback (identical math; this is what the
                     CPU dry-run compiles, and the oracle for tests).
* ``"auto"``       — pallas on TPU backends, xla elsewhere (the default
                     of ``QuantContext``, so a chip runs the kernels and a
                     kernel the chip's compiler refuses raises).

The wrappers are QTensor-aware and handle leading-batch flattening so model
code can stay shape-agnostic.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.qtensor import BlockQTensor, QTensor
from repro.kernels import ref
from repro.kernels.decode_attention import (
    decode_attention_paged_pallas,
    decode_attention_pallas,
)
from repro.kernels.int4_matmul import int4_matmul_pallas
from repro.kernels.int8_matmul import (
    int8_matmul_batched_pallas,
    int8_matmul_pallas,
)
from repro.kernels.quantize import quantize_rowwise_pallas, quantize_static_pallas


def resolve_impl(impl: str) -> str:
    """The kernel path ``impl`` names on this process's default backend."""
    if impl == "auto":
        return "pallas" if jax.default_backend() == "tpu" else "xla"
    return impl


# ---------------------------------------------------------------------------
# int8 matmul
# ---------------------------------------------------------------------------

def _row_scale(scale, M: int) -> jax.Array:
    """Normalize an activation scale to (1, 1) or (M, 1) f32."""
    return (jnp.reshape(jnp.asarray(scale, jnp.float32), (1, 1))
            if jnp.size(scale) == 1
            else jnp.reshape(jnp.asarray(scale, jnp.float32), (M, 1)))


def _fold_zero_point(zero_point) -> Optional[jax.Array]:
    """Symmetric activations have zp == 0 everywhere; fold to the no-zp fast
    path when that is decidable at trace time (calibrated constants)."""
    if jnp.size(zero_point) != 1:
        return None
    if isinstance(zero_point, (float, int)):
        return None if float(zero_point) == 0.0 else jnp.float32(zero_point)
    azp = jnp.asarray(zero_point)
    try:  # concrete (calibrated constant) → fold the decision now
        return None if float(azp) == 0.0 else azp.astype(jnp.float32)
    except Exception:  # traced → keep correction term
        return azp.astype(jnp.float32)


def int8_matmul(
    a: QTensor,
    b: QTensor,
    bias: Optional[jax.Array] = None,
    *,
    out_dtype=jnp.float32,
    impl: str = "auto",
) -> jax.Array:
    """``dequant(a) @ dequant(b) + bias`` computed in int8 on the MXU.

    ``a``: activations, shape (..., K); scale per-row (…,1) or scalar;
    ``b``: weights, shape (K, N); symmetric per-column scale (1, N)/scalar.
    """
    impl = resolve_impl(impl)
    batch_shape = a.data.shape[:-1]
    K = a.data.shape[-1]
    N = b.data.shape[-1]
    a2 = a.data.reshape(-1, K)
    M = a2.shape[0]
    a_scale = _row_scale(a.scale, M)
    b_scale = jnp.asarray(b.scale, jnp.float32)
    b_scale = (jnp.broadcast_to(b_scale.reshape(1, 1), (1, N))
               if b_scale.size == 1 else b_scale.reshape(1, N))
    zp = _fold_zero_point(a.zero_point)
    if impl in ("pallas", "interpret"):
        out = int8_matmul_pallas(
            a2, a_scale, b.data, b_scale, zp, bias,
            out_dtype=out_dtype, interpret=(impl == "interpret"),
        )
    else:
        out = ref.ref_int8_matmul(a2, a_scale, b.data, b_scale, zp, bias,
                                  out_dtype=out_dtype)
    return out.reshape(*batch_shape, N)


def int4_matmul(
    a: QTensor,
    b: BlockQTensor,
    bias: Optional[jax.Array] = None,
    *,
    out_dtype=jnp.float32,
    impl: str = "auto",
) -> jax.Array:
    """``dequant(a) @ block_dequant(b) + bias`` with dequant fused in-kernel.

    ``a``: int8 activations, shape (..., K); scale per-row (…, 1) or scalar;
    ``b``: block-quantized INT4 weights (packed nibbles + group scale/min).
    """
    impl = resolve_impl(impl)
    batch_shape = a.data.shape[:-1]
    K = a.data.shape[-1]
    if b.data.ndim != 2:
        raise ValueError(f"int4_matmul wants 2-D weights, got {b.shape}")
    if K != b.k_dim:
        raise ValueError(f"K mismatch: activations {K}, weights {b.k_dim}")
    N = b.data.shape[-1]
    a2 = a.data.reshape(-1, K)
    M = a2.shape[0]
    a_scale = _row_scale(a.scale, M)
    zp = _fold_zero_point(a.zero_point)
    if impl in ("pallas", "interpret"):
        out = int4_matmul_pallas(
            a2, a_scale, b.data, b.scale, b.vmin, zp, bias,
            group_size=b.group_size, out_dtype=out_dtype,
            interpret=(impl == "interpret"),
        )
    else:
        out = ref.ref_int4_matmul(a2, a_scale, b.data, b.scale, b.vmin,
                                  zp, bias, group_size=b.group_size,
                                  out_dtype=out_dtype)
    return out.reshape(*batch_shape, N)


def int8_matmul_batched(
    a: QTensor,                    # data (E, M, K); scale (E, M, 1) or scalar
    b: QTensor,                    # data (E, K, N); scale (E, 1, N)
    *,
    out_dtype=jnp.float32,
    impl: str = "auto",
) -> jax.Array:
    """Per-expert grouped int8 matmul (MoE expert FFN hot path)."""
    impl = resolve_impl(impl)
    E, M, K = a.data.shape
    _, _, N = b.data.shape
    a_scale = (jnp.broadcast_to(jnp.asarray(a.scale, jnp.float32),
                                (E, M, 1))
               if jnp.size(a.scale) != 1
               else jnp.broadcast_to(jnp.asarray(a.scale, jnp.float32
                                                 ).reshape(1, 1, 1), (E, 1, 1)))
    b_scale = jnp.asarray(b.scale, jnp.float32).reshape(E, 1, N)
    if impl in ("pallas", "interpret"):
        return int8_matmul_batched_pallas(
            a.data, a_scale, b.data, b_scale, out_dtype=out_dtype,
            interpret=(impl == "interpret"))
    return ref.ref_int8_matmul_batched(a.data, a_scale, b.data, b_scale,
                                       out_dtype=out_dtype)


# ---------------------------------------------------------------------------
# quantize
# ---------------------------------------------------------------------------

def quantize_rowwise(x: jax.Array, *, impl: str = "auto") -> QTensor:
    """Dynamic symmetric per-row quantization of (..., K) activations."""
    impl = resolve_impl(impl)
    batch_shape = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1])
    if impl in ("pallas", "interpret"):
        q, scale = quantize_rowwise_pallas(x2, interpret=(impl == "interpret"))
    else:
        q, scale = ref.ref_quantize_rowwise(x2)
    return QTensor(
        data=q.reshape(*batch_shape, x.shape[-1]),
        scale=scale.reshape(*batch_shape, 1),
        zero_point=jnp.zeros((), jnp.float32),
        axis=None,
    )


def quantize_static(x: jax.Array, amax, *, impl: str = "auto") -> QTensor:
    """Calibrated symmetric quantization with a constant threshold."""
    impl = resolve_impl(impl)
    batch_shape = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1])
    if impl in ("pallas", "interpret"):
        q = quantize_static_pallas(x2, jnp.float32(amax),
                                   interpret=(impl == "interpret"))
    else:
        q = ref.ref_quantize_static(x2, jnp.float32(amax))
    return QTensor(
        data=q.reshape(x.shape),
        scale=jnp.float32(amax) / 127.0,
        zero_point=jnp.zeros((), jnp.float32),
        axis=None,
    )


# ---------------------------------------------------------------------------
# decode attention over int8 KV cache
# ---------------------------------------------------------------------------

def decode_attention(
    q: jax.Array,
    k_q: jax.Array,
    k_scale: jax.Array,
    v_q: jax.Array,
    v_scale: jax.Array,
    lengths: jax.Array,
    *,
    sm_scale: float,
    impl: str = "auto",
) -> jax.Array:
    impl = resolve_impl(impl)
    if impl in ("pallas", "interpret"):
        return decode_attention_pallas(
            q, k_q, k_scale, v_q, v_scale, lengths,
            sm_scale=sm_scale, interpret=(impl == "interpret"),
        )
    return ref.ref_decode_attention(q, k_q, k_scale, v_q, v_scale, lengths,
                                    sm_scale)


def decode_attention_paged(
    q: jax.Array,            # (B, H, dh)
    k_pages: jax.Array,      # (P, ps, HKV, dh) int8
    k_scale: jax.Array,      # (P, ps, HKV) f32
    v_pages: jax.Array,      # (P, ps, HKV, dh) int8
    v_scale: jax.Array,      # (P, ps, HKV) f32
    block_tables: jax.Array, # (B, maxP) int32
    lengths: jax.Array,      # (B,) int32
    *,
    sm_scale: float,
    impl: str = "auto",
) -> jax.Array:
    """Paged-cache decode attention: the Pallas kernel copies each row's
    live pages through the scalar-prefetched block table, many rows a grid
    step; the XLA fallback linearizes the table then reuses the contiguous
    oracle."""
    impl = resolve_impl(impl)
    if impl in ("pallas", "interpret"):
        return decode_attention_paged_pallas(
            q, k_pages, k_scale, v_pages, v_scale, block_tables, lengths,
            sm_scale=sm_scale, interpret=(impl == "interpret"),
        )
    return ref.ref_decode_attention_paged(q, k_pages, k_scale, v_pages,
                                          v_scale, block_tables, lengths,
                                          sm_scale)
