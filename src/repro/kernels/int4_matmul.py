"""Pallas TPU kernel: dequant-in-kernel INT4(weight) × INT8(activation) matmul.

The weight operand arrives as a :class:`~repro.core.qtensor.BlockQTensor`
payload: 4-bit codes packed two-nibbles-per-int8 along K plus per-block
(group-wise) scale/min pairs.  The kernel unpacks the nibbles and applies the
block affine map *inside* the K loop, so the unpacked FP weights never touch
HBM — decode streams 4 bits + ~0.25 bits of metadata per weight instead of 8.

Math.  With activations ``real(a) = (a_q - zp) * a_scale`` and weights
``real(b)[k, n] = nib[k, n] * scale[g, n] + vmin[g, n]`` (g = k // G):

    a @ b = a_scale * [ Σ_g ( scale_g · (a_q[:, g] @ nib[g])          (MXU, s8·s8→s32)
                            + vmin_g · rowsum(a_q[:, g]) )            (VPU)
                        - zp · colsum(real(b)) ]  + bias

Each group's two integer reductions are exact in int32; only the per-group
combination runs in f32, in ascending-group order — the same order the
reference oracle uses, which is what makes bit-identity tests meaningful.

Tiling: grid (M/bm, N/bn, K/bk) with K innermost, like ``int8_matmul``.  The
wrapper forces ``bk`` to a multiple of ``group_size`` so a block's scale/min
never straddles two k-tiles; packed rows tile at ``bk // 2``, and the
scale/min operands are laid out as (K/bk, bk // group_size, N) so each step's
block spans its whole second-minor dim, as the TPU's block rules require.

Nibble order.  Packed row r holds K rows 2r (low nibble) and 2r+1 (high).
Interleaving those rows back in VMEM is a sublane shuffle the TPU compiler
does not lower, so the wrapper instead permutes each group's activation
columns to [even k | odd k]; the kernel then stacks a group's low nibbles
over its high nibbles, an aligned concatenation.  Permuting the contraction
axis of both operands leaves every int32 dot unchanged.

Padding contract (the colsum/zp analogue of the INT8 one): ``a`` is padded
with zeros along K, so padded rows contribute exactly zero to both the MXU
term (0 · nib) and the min term (rowsum counts only real activations);
grid-tail groups beyond the stored K get scale = vmin = 0 as a second guard.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.int8_matmul import _pad_to

DEFAULT_BM = 256
DEFAULT_BN = 256
DEFAULT_BK = 512


def _kernel(a_ref, b_ref, scale_ref, min_ref, a_scale_ref, zp_ref,
            colsum_ref, bias_ref, out_ref, acc_ref, *, k_steps: int,
            groups_per_block: int, group_size: int, has_zp: bool,
            has_bias: bool):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    packed = b_ref[...].astype(jnp.int32)             # (bk//2, bn), sign-ext.
    lo = packed & 0xF                                 # codes of even k rows
    hi = (packed >> 4) & 0xF                          # codes of odd k rows
    a_tile = a_ref[...]                               # (bm, bk) int8
    scales = scale_ref[0]                             # (bk//G, bn) f32
    mins = min_ref[0]                                 # (bk//G, bn) f32
    half = group_size // 2
    for g in range(groups_per_block):
        rows = slice(g * half, (g + 1) * half)
        nib = jnp.concatenate([lo[rows], hi[rows]], axis=0).astype(jnp.int8)
        a_g = a_tile[:, g * group_size:(g + 1) * group_size]  # [even | odd]
        # MXU step: s8 × s8 → s32, exact
        d = jnp.dot(a_g, nib, preferred_element_type=jnp.int32)
        rsum = jnp.sum(a_g.astype(jnp.int32), axis=1, keepdims=True)
        acc_ref[...] += (d.astype(jnp.float32) * scales[g][None, :]
                         + rsum.astype(jnp.float32) * mins[g][None, :])

    @pl.when(pl.program_id(2) == k_steps - 1)
    def _epilogue():
        acc = acc_ref[...]
        if has_zp:
            # zero-point correction for asymmetric activations: colsum here
            # is over the *dequantized* weights (precomputed in the wrapper).
            acc = acc - zp_ref[0, 0] * colsum_ref[...]
        out = acc * a_scale_ref[...]
        if has_bias:
            out = out + bias_ref[...].astype(jnp.float32)
        out_ref[...] = out.astype(out_ref.dtype)


def _pick_bk(k_store: int, group_size: int, bk: int) -> int:
    """Largest multiple of ``group_size`` ≤ ``bk`` (at least one group),
    clamped to the stored K so tiny layers stay single-step."""
    cand = group_size * max(1, bk // group_size)
    return min(cand, -(-k_store // group_size) * group_size) \
        if k_store < cand else cand


@functools.partial(
    jax.jit,
    static_argnames=("group_size", "out_dtype", "bm", "bn", "bk", "interpret"),
)
def int4_matmul_pallas(
    a_q: jax.Array,                       # (M, K) int8 activations
    a_scale: jax.Array,                   # (M, 1) or (1, 1) f32
    b_packed: jax.Array,                  # (K_store//2, N) int8 packed nibbles
    b_scale: jax.Array,                   # (n_groups, N) f32/f16
    b_min: jax.Array,                     # (n_groups, N) f32/f16
    a_zero_point: Optional[jax.Array] = None,   # scalar f32 (q-space)
    bias: Optional[jax.Array] = None,           # (N,) f32
    *,
    group_size: int,
    out_dtype=jnp.float32,
    bm: int = DEFAULT_BM,
    bn: int = DEFAULT_BN,
    bk: int = DEFAULT_BK,
    interpret: bool = False,
) -> jax.Array:
    M, K = a_q.shape
    K2, N = b_packed.shape
    n_g = b_scale.shape[0]
    k_store = n_g * group_size
    if group_size % 2:
        raise ValueError(f"group_size must be even, got {group_size}")
    if 2 * K2 != k_store:
        raise ValueError(f"packed rows {K2} inconsistent with "
                         f"{n_g} groups of {group_size}")
    if K > k_store:
        raise ValueError(f"activation K={K} exceeds stored K={k_store}")

    bm = min(bm, max(8, M))
    bn = min(bn, max(128, N))
    bk = _pick_bk(k_store, group_size, bk)

    # pad along K to the grid: a with zeros (the padding contract), the
    # weight payload with zero bytes and the tail groups with scale=min=0.
    Kp = -(-k_store // bk) * bk
    a_p = _pad_to(jnp.pad(a_q, ((0, 0), (0, Kp - K))), (bm, bk))
    Mp = a_p.shape[0]
    a_p = (a_p.reshape(Mp, Kp // group_size, group_size // 2, 2)
           .swapaxes(-1, -2).reshape(Mp, Kp))      # per group: [even | odd]
    b_p = _pad_to(b_packed, (Kp // 2, bn))
    Np = b_p.shape[1]
    gpb = bk // group_size
    k_steps = Kp // bk
    # the chip's kernels cannot load f16, so scale/min widen to f32 here
    # (exact) — a (K/G, N) pass, 1/G of the payload's rows
    scale_p = _pad_to(b_scale.astype(jnp.float32), (Kp // group_size, bn)
                      ).reshape(k_steps, gpb, Np)
    min_p = _pad_to(b_min.astype(jnp.float32), (Kp // group_size, bn)
                    ).reshape(k_steps, gpb, Np)

    a_scale_p = _pad_to(jnp.broadcast_to(a_scale, (M, 1)).astype(jnp.float32),
                        (bm, 1))

    has_zp = a_zero_point is not None
    has_bias = bias is not None
    if has_zp:
        zp = jnp.asarray(a_zero_point, jnp.float32).reshape(1, 1)
        # Σ_{k<K} real(b)[k, n] — over the *logical* rows only: padded a rows
        # carry no zero-point because they are not real activations.
        from repro.core.qtensor import unpack_nibbles
        nib = unpack_nibbles(b_packed).astype(jnp.float32)       # (k_store, N)
        s = jnp.repeat(b_scale.astype(jnp.float32), group_size, axis=0)
        m = jnp.repeat(b_min.astype(jnp.float32), group_size, axis=0)
        deq = nib * s + m
        colsum = jnp.sum(deq[:K, :], axis=0, keepdims=True)
        colsum = _pad_to(colsum, (1, bn))
    else:
        zp = jnp.zeros((1, 1), jnp.float32)
        colsum = jnp.zeros((1, Np), jnp.float32)
    bias_p = (_pad_to(bias.reshape(1, N).astype(jnp.float32), (1, bn))
              if has_bias else jnp.zeros((1, Np), jnp.float32))

    m_steps, n_steps = Mp // bm, Np // bn

    out = pl.pallas_call(
        functools.partial(_kernel, k_steps=k_steps, groups_per_block=gpb,
                          group_size=group_size, has_zp=has_zp,
                          has_bias=has_bias),
        grid=(m_steps, n_steps, k_steps),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),           # a
            pl.BlockSpec((bk // 2, bn), lambda i, j, k: (k, j)),      # packed b
            pl.BlockSpec((1, gpb, bn), lambda i, j, k: (k, 0, j)),    # scales
            pl.BlockSpec((1, gpb, bn), lambda i, j, k: (k, 0, j)),    # mins
            pl.BlockSpec((bm, 1), lambda i, j, k: (i, 0)),            # a_scale
            pl.BlockSpec((1, 1), lambda i, j, k: (0, 0)),             # zp
            pl.BlockSpec((1, bn), lambda i, j, k: (0, j)),            # colsum
            pl.BlockSpec((1, bn), lambda i, j, k: (0, j)),            # bias
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((Mp, Np), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(a_p, b_p, scale_p, min_p, a_scale_p, zp, colsum, bias_p)
    return out[:M, :N]
