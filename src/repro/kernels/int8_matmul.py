"""Pallas TPU kernel: s8·s8→s32 matmul with fused dequantize epilogue.

This is the TPU-native analogue of the paper's MKL/VNNI ``QuantizedMatMul``
(§5.2): the MXU consumes int8 operand tiles at 2× the bf16 FLOP rate and
accumulates in int32.  The epilogue applies

    out = (acc - zp_a · colsum(b_q)) · a_scale · b_scale + bias

inside the kernel, so no separate Requantize/Dequantize pass ever touches
HBM — the paper's §5.5 "eliminate graph ops" expressed as epilogue fusion.

Tiling: grid (M/bm, N/bn, K/bk) with K innermost; the int32 accumulator
lives in VMEM scratch.  Default blocks (256, 256, 512) keep the working set
at ~0.6 MB (a) + 0.5 MB (b) + 0.25 MB (acc) per step — far under the 16 MB
v5e VMEM — while every matmul dim stays a multiple of the (32, 128) int8
native tile.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BM = 256
DEFAULT_BN = 256
DEFAULT_BK = 512


def _kernel(a_ref, b_ref, a_scale_ref, b_scale_ref, zp_ref, colsum_ref,
            bias_ref, out_ref, acc_ref, *, k_steps: int, has_zp: bool,
            has_bias: bool):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # MXU step: int8 × int8 → int32
    acc_ref[...] += jnp.dot(
        a_ref[...], b_ref[...], preferred_element_type=jnp.int32
    )

    @pl.when(pl.program_id(2) == k_steps - 1)
    def _epilogue():
        acc = acc_ref[...].astype(jnp.float32)
        if has_zp:
            # zero-point correction for asymmetric activations
            # (independent-mode calibration): zp is scalar in q-space.
            acc = acc - zp_ref[0, 0] * colsum_ref[...].astype(jnp.float32)
        out = acc * a_scale_ref[...] * b_scale_ref[...]
        if has_bias:
            out = out + bias_ref[...].astype(jnp.float32)
        out_ref[...] = out.astype(out_ref.dtype)


def _batched_kernel(a_ref, b_ref, a_scale_ref, b_scale_ref, out_ref, acc_ref,
                    *, k_steps: int):
    """Expert-batched variant: grid (E, M/bm, N/bn, K/bk)."""
    @pl.when(pl.program_id(3) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(
        a_ref[0], b_ref[0], preferred_element_type=jnp.int32
    )

    @pl.when(pl.program_id(3) == k_steps - 1)
    def _epilogue():
        acc = acc_ref[...].astype(jnp.float32)
        out_ref[0] = (acc * a_scale_ref[0] * b_scale_ref[0]
                      ).astype(out_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("out_dtype", "bm", "bn", "bk", "interpret")
)
def int8_matmul_batched_pallas(
    a_q: jax.Array,                   # (E, M, K) int8
    a_scale: jax.Array,               # (E, M, 1) f32
    b_q: jax.Array,                   # (E, K, N) int8
    b_scale: jax.Array,               # (E, 1, N) f32
    *,
    out_dtype=jnp.float32,
    bm: int = DEFAULT_BM,
    bn: int = DEFAULT_BN,
    bk: int = DEFAULT_BK,
    interpret: bool = False,
) -> jax.Array:
    """Grouped (per-expert) s8 matmul — the MoE expert-FFN hot path."""
    E, M, K = a_q.shape
    _, _, N = b_q.shape
    bm = min(bm, max(8, M))
    bn = min(bn, max(128, N))
    bk = min(bk, max(128, K))
    a_p = _pad_to(a_q, (1, bm, bk))
    b_p = _pad_to(b_q, (1, bk, bn))
    a_scale_p = _pad_to(jnp.broadcast_to(a_scale, (E, M, 1)
                                         ).astype(jnp.float32), (1, bm, 1))
    b_scale_p = _pad_to(jnp.broadcast_to(b_scale, (E, 1, N)
                                         ).astype(jnp.float32), (1, 1, bn))
    _, Mp, Kp = a_p.shape
    _, _, Np = b_p.shape
    m_steps, n_steps, k_steps = Mp // bm, Np // bn, Kp // bk

    out = pl.pallas_call(
        functools.partial(_batched_kernel, k_steps=k_steps),
        grid=(E, m_steps, n_steps, k_steps),
        in_specs=[
            pl.BlockSpec((1, bm, bk), lambda e, i, j, k: (e, i, k)),
            pl.BlockSpec((1, bk, bn), lambda e, i, j, k: (e, k, j)),
            pl.BlockSpec((1, bm, 1), lambda e, i, j, k: (e, i, 0)),
            pl.BlockSpec((1, 1, bn), lambda e, i, j, k: (e, 0, j)),
        ],
        out_specs=pl.BlockSpec((1, bm, bn), lambda e, i, j, k: (e, i, j)),
        out_shape=jax.ShapeDtypeStruct((E, Mp, Np), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
        ),
        interpret=interpret,
    )(a_p, b_p, a_scale_p, b_scale_p)
    return out[:, :M, :N]


def _pad_to(x: jax.Array, multiples) -> jax.Array:
    pads = []
    needs = False
    for dim, mult in zip(x.shape, multiples):
        pad = (-dim) % mult
        pads.append((0, pad))
        needs = needs or pad > 0
    return jnp.pad(x, pads) if needs else x


@functools.partial(
    jax.jit,
    static_argnames=("out_dtype", "bm", "bn", "bk", "interpret"),
)
def int8_matmul_pallas(
    a_q: jax.Array,                       # (M, K) int8
    a_scale: jax.Array,                   # (M, 1) or (1, 1) f32
    b_q: jax.Array,                       # (K, N) int8
    b_scale: jax.Array,                   # (1, N) or (1, 1) f32
    a_zero_point: Optional[jax.Array] = None,   # scalar f32 (q-space)
    bias: Optional[jax.Array] = None,           # (N,) f32
    *,
    out_dtype=jnp.float32,
    bm: int = DEFAULT_BM,
    bn: int = DEFAULT_BN,
    bk: int = DEFAULT_BK,
    interpret: bool = False,
) -> jax.Array:
    M, K = a_q.shape
    K2, N = b_q.shape
    assert K == K2, (a_q.shape, b_q.shape)
    bm = min(bm, max(8, M))
    bn = min(bn, max(128, N))
    bk = min(bk, max(128, K))

    a_p = _pad_to(a_q, (bm, bk))
    b_p = _pad_to(b_q, (bk, bn))
    Mp, Kp = a_p.shape
    _, Np = b_p.shape

    a_scale_p = _pad_to(jnp.broadcast_to(a_scale, (M, 1)).astype(jnp.float32),
                        (bm, 1))
    b_scale_p = _pad_to(jnp.broadcast_to(b_scale, (1, N)).astype(jnp.float32),
                        (1, bn))

    has_zp = a_zero_point is not None
    has_bias = bias is not None
    if has_zp:
        zp = jnp.asarray(a_zero_point, jnp.float32).reshape(1, 1)
        colsum = jnp.sum(b_p.astype(jnp.int32), axis=0, keepdims=True)
        colsum = colsum.astype(jnp.float32)
    else:
        zp = jnp.zeros((1, 1), jnp.float32)
        colsum = jnp.zeros((1, Np), jnp.float32)
    bias_p = (_pad_to(bias.reshape(1, N).astype(jnp.float32), (1, bn))
              if has_bias else jnp.zeros((1, Np), jnp.float32))

    m_steps, n_steps, k_steps = Mp // bm, Np // bn, Kp // bk

    out = pl.pallas_call(
        functools.partial(_kernel, k_steps=k_steps, has_zp=has_zp,
                          has_bias=has_bias),
        grid=(m_steps, n_steps, k_steps),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),      # a
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),      # b
            pl.BlockSpec((bm, 1), lambda i, j, k: (i, 0)),       # a_scale
            pl.BlockSpec((1, bn), lambda i, j, k: (0, j)),       # b_scale
            pl.BlockSpec((1, 1), lambda i, j, k: (0, 0)),        # zp
            pl.BlockSpec((1, bn), lambda i, j, k: (0, j)),       # colsum
            pl.BlockSpec((1, bn), lambda i, j, k: (0, j)),       # bias
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((Mp, Np), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(a_p, b_p, a_scale_p, b_scale_p, zp, colsum, bias_p)
    return out[:M, :N]
