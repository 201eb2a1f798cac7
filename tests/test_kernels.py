"""Per-kernel validation: Pallas (interpret mode) vs pure-jnp oracles,
with shape/dtype sweeps + hypothesis-generated shapes."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.core.qtensor import QTensor
from repro.kernels import ops, ref


def _mk_qt(rng, shape, scale_shape):
    data = jnp.asarray(rng.integers(-127, 128, shape), jnp.int8)
    scale = jnp.asarray(rng.uniform(1e-3, 0.1, scale_shape), jnp.float32)
    return QTensor(data, scale, jnp.zeros((), jnp.float32), None)


# ---------------------------------------------------------------------------
# int8 matmul
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("M,K,N", [
    (8, 128, 128), (37, 100, 65), (128, 512, 384), (1, 256, 32),
    (130, 96, 200),
])
def test_int8_matmul_shapes(rng, M, K, N):
    a = _mk_qt(rng, (M, K), (M, 1))
    b = _mk_qt(rng, (K, N), (1, N))
    bias = jnp.asarray(rng.normal(size=N), jnp.float32)
    got = ops.int8_matmul(a, b, bias, impl="interpret")
    want = ops.int8_matmul(a, b, bias, impl="xla")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-4)


@pytest.mark.parametrize("out_dtype", [jnp.float32, jnp.bfloat16])
def test_int8_matmul_out_dtypes(rng, out_dtype):
    a = _mk_qt(rng, (16, 64), (16, 1))
    b = _mk_qt(rng, (64, 32), (1, 32))
    got = ops.int8_matmul(a, b, out_dtype=out_dtype, impl="interpret")
    assert got.dtype == out_dtype


def test_int8_matmul_zero_point(rng):
    a = QTensor(jnp.asarray(rng.integers(-127, 128, (24, 48)), jnp.int8),
                jnp.float32(0.03), jnp.float32(5.0), None)
    b = _mk_qt(rng, (48, 40), (1, 40))
    got = ops.int8_matmul(a, b, impl="interpret")
    want = ops.int8_matmul(a, b, impl="xla")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-4)


def test_int8_matmul_exact_vs_float_reference(rng):
    """Int8 kernel must equal float math on exactly-representable values."""
    a_f = rng.integers(-50, 50, (16, 32)).astype(np.float32)
    b_f = rng.integers(-50, 50, (32, 24)).astype(np.float32)
    a = QTensor(jnp.asarray(a_f.astype(np.int8)), jnp.float32(1.0),
                jnp.zeros(()), None)
    b = QTensor(jnp.asarray(b_f.astype(np.int8)), jnp.float32(1.0),
                jnp.zeros(()), None)
    got = np.asarray(ops.int8_matmul(a, b, impl="interpret"))
    np.testing.assert_allclose(got, a_f @ b_f, rtol=0, atol=0)


def test_int8_matmul_batched(rng):
    a = _mk_qt(rng, (4, 24, 64), (4, 24, 1))
    b = _mk_qt(rng, (4, 64, 48), (4, 1, 48))
    got = ops.int8_matmul_batched(a, b, impl="interpret")
    want = ops.int8_matmul_batched(a, b, impl="xla")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-4)


@given(st.integers(1, 64), st.integers(1, 96), st.integers(1, 64))
@settings(max_examples=12, deadline=None)
def test_prop_int8_matmul_any_shape(M, K, N):
    rng = np.random.default_rng(M * 1000 + K * 10 + N)
    a = _mk_qt(rng, (M, K), (M, 1))
    b = _mk_qt(rng, (K, N), (1, N))
    got = ops.int8_matmul(a, b, impl="interpret")
    want = ops.int8_matmul(a, b, impl="xla")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-4)


# ---------------------------------------------------------------------------
# quantize kernels
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("M,K", [(8, 64), (50, 300), (1, 128), (129, 32)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_quantize_rowwise(rng, M, K, dtype):
    x = jnp.asarray(rng.normal(size=(M, K)) * 10, dtype)
    got = ops.quantize_rowwise(x, impl="interpret")
    want = ops.quantize_rowwise(x, impl="xla")
    # bf16 inputs can land exactly on rounding boundaries: allow ±1 quantum
    diff = np.abs(np.asarray(got.data, np.int32)
                  - np.asarray(want.data, np.int32))
    assert diff.max() <= 1
    np.testing.assert_allclose(np.asarray(got.scale), np.asarray(want.scale),
                               rtol=1e-6)


def test_quantize_static(rng):
    x = jnp.asarray(rng.normal(size=(40, 100)) * 5, jnp.float32)
    got = ops.quantize_static(x, 3.0, impl="interpret")
    want = ops.quantize_static(x, 3.0, impl="xla")
    np.testing.assert_array_equal(np.asarray(got.data), np.asarray(want.data))
    # clipping: all values map within [-127, 127]
    assert int(jnp.max(jnp.abs(got.data))) <= 127


# Awkward row counts: 8 < M < block_rows with M % 8 != 0 used to pick a
# sublane-misaligned Pallas block (bm = M) — interpret mode accepted it
# but real TPU lowering rejects non-multiple-of-8 block rows.  The sweep
# pins the rounded-up block shape to reference-quantizer parity.
AWKWARD_M = [9, 12, 17, 100, 127, 129, 250, 255, 257]


@pytest.mark.parametrize("M", AWKWARD_M)
def test_quantize_rowwise_awkward_rows(rng, M):
    from repro.kernels.quantize import quantize_rowwise_pallas
    x = jnp.asarray(rng.normal(size=(M, 64)) * 7, jnp.float32)
    q, scale = quantize_rowwise_pallas(x, interpret=True)
    want = ops.quantize_rowwise(x, impl="xla")
    assert q.shape == (M, 64) and scale.shape == (M, 1)
    np.testing.assert_array_equal(np.asarray(q), np.asarray(want.data))
    np.testing.assert_allclose(np.asarray(scale), np.asarray(want.scale),
                               rtol=1e-6)


@pytest.mark.parametrize("M", AWKWARD_M)
def test_quantize_static_awkward_rows(rng, M):
    from repro.kernels.quantize import quantize_static_pallas
    x = jnp.asarray(rng.normal(size=(M, 48)) * 5, jnp.float32)
    q = quantize_static_pallas(x, jnp.float32(3.0), interpret=True)
    want = ops.quantize_static(x, 3.0, impl="xla")
    assert q.shape == (M, 48)
    np.testing.assert_array_equal(np.asarray(q), np.asarray(want.data))


# ---------------------------------------------------------------------------
# decode attention (int8 KV cache)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B,H,HKV,dh,S", [
    (2, 8, 4, 64, 300), (1, 4, 1, 128, 64), (3, 8, 8, 32, 513),
    (2, 16, 2, 64, 128),
])
def test_decode_attention(rng, B, H, HKV, dh, S):
    """The contiguous kernel over a cache stacked on two layers of
    different contents, each layer read by its index, vs the oracle."""
    L = 2
    q = jnp.asarray(rng.normal(size=(B, H, dh)), jnp.float32)
    kq = jnp.asarray(rng.integers(-127, 128, (L, B, S, HKV, dh)), jnp.int8)
    vq = jnp.asarray(rng.integers(-127, 128, (L, B, S, HKV, dh)), jnp.int8)
    ks = jnp.asarray(rng.uniform(1e-3, 2e-2, (L, B, S, HKV)), jnp.float32)
    vs = jnp.asarray(rng.uniform(1e-3, 2e-2, (L, B, S, HKV)), jnp.float32)
    lengths = jnp.asarray(rng.integers(1, S + 1, (B,)), jnp.int32)
    sm = 1.0 / np.sqrt(dh)
    for layer in (0, jnp.int32(1)):
        got = ops.decode_attention(q, kq, ks, vq, vs, lengths, layer,
                                   sm_scale=sm, impl="interpret")
        want = ops.decode_attention(q, kq, ks, vq, vs, lengths, layer,
                                    sm_scale=sm, impl="xla")
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=3e-4, atol=3e-4)


def test_decode_attention_respects_lengths(rng):
    """Tokens beyond `lengths` must not influence the output."""
    B, H, dh, S = 1, 4, 32, 64
    q = jnp.asarray(rng.normal(size=(B, H, dh)), jnp.float32)
    kq = jnp.asarray(rng.integers(-127, 128, (1, B, S, H, dh)), jnp.int8)
    vq = jnp.asarray(rng.integers(-127, 128, (1, B, S, H, dh)), jnp.int8)
    ks = jnp.ones((1, B, S, H), jnp.float32) * 0.01
    vs = jnp.ones((1, B, S, H), jnp.float32) * 0.01
    lengths = jnp.asarray([20], jnp.int32)
    out1 = ops.decode_attention(q, kq, ks, vq, vs, lengths, 0,
                                sm_scale=0.1, impl="interpret")
    # poison the out-of-range region
    kq2 = kq.at[:, :, 20:].set(127)
    vq2 = vq.at[:, :, 20:].set(-127)
    out2 = ops.decode_attention(q, kq2, ks, vq2, vs, lengths, 0,
                                sm_scale=0.1, impl="interpret")
    np.testing.assert_allclose(np.asarray(out1), np.asarray(out2),
                               rtol=1e-6, atol=1e-6)


# ---------------------------------------------------------------------------
# int4 block-quantized matmul (dequant fused in-kernel)
# ---------------------------------------------------------------------------

import functools

from repro.core.qtensor import (
    BlockQTensor, pack_nibbles, quantize_block, unpack_nibbles,
)
from repro.kernels.int4_matmul import _pick_bk, int4_matmul_pallas


def _mk_bqt(rng, K, N, G, scale_dtype=jnp.float16):
    w = jnp.asarray(rng.normal(size=(K, N)), jnp.float32)
    return quantize_block(w, group_size=G, scale_dtype=scale_dtype)


def _mk_act(rng, M, K, zp=None):
    data = jnp.asarray(rng.integers(-127, 128, (M, K)), jnp.int8)
    scale = jnp.asarray(rng.uniform(1e-3, 0.05, (M, 1)), jnp.float32)
    zp_arr = jnp.float32(zp) if zp is not None else jnp.zeros((), jnp.float32)
    return QTensor(data, scale, zp_arr, None)


def _jit_oracle(G):
    """Bit-identity needs *both* paths XLA-compiled: the interpret-mode
    kernel body is traced/compiled (FMA contraction applies), so the oracle
    must be jitted too — an eager ref call differs in the last ulp."""
    return jax.jit(functools.partial(ref.ref_int4_matmul, group_size=G))


# The sweep deliberately includes: group_size not dividing the default bk
# (G=48 → bk=480), K not a multiple of the group (tail-group edge padding),
# multi-k-step grids with a padded grid tail (K=700/2048), sublane-awkward M,
# and lane-awkward N.
INT4_CASES = [
    #  M,    K,   N,   G, scale_dtype
    (8,    64, 128,  32, jnp.float32),
    (3,   100, 130,  32, jnp.float16),
    (12,  700, 257,  48, jnp.float16),
    (1,    16, 128,  16, jnp.float32),
    (5,  1000,  64, 128, jnp.float16),
    (17, 2048, 512, 128, jnp.float16),
    (9,   130,  96,  64, jnp.float16),
]


@pytest.mark.parametrize("M,K,N,G,scale_dtype", INT4_CASES)
@pytest.mark.parametrize("zp", [None, 3.0])
def test_int4_matmul_bit_identical_to_reference(rng, M, K, N, G, scale_dtype,
                                                zp):
    """Interpret-mode kernel must be bit-identical to the jitted group-wise
    oracle — same int32 MXU dots, same ascending-group f32 combination."""
    b = _mk_bqt(rng, K, N, G, scale_dtype)
    a = _mk_act(rng, M, K, zp)
    zp_arr = jnp.float32(zp) if zp is not None else None
    got = int4_matmul_pallas(a.data, a.scale, b.data, b.scale, b.vmin,
                             zp_arr, None, group_size=G, interpret=True)
    want = _jit_oracle(G)(a.data, a.scale, b.data, b.scale, b.vmin,
                          zp_arr, None)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("M,K,N,G,scale_dtype", INT4_CASES[:4])
def test_int4_matmul_matches_float_dequant(rng, M, K, N, G, scale_dtype):
    """Kernel ≈ dense float matmul against the reference dequantized weights
    (validates the whole integer decomposition, not just oracle agreement)."""
    b = _mk_bqt(rng, K, N, G, scale_dtype)
    a = _mk_act(rng, M, K)
    got = ops.int4_matmul(a, b, impl="interpret")
    want = a.dequantize() @ b.dequantize()
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def test_int4_matmul_exact_vs_float_reference(rng):
    """Power-of-two scale/min and integer activations are exactly
    representable: kernel must equal float math with zero tolerance."""
    M, K, N, G = 16, 64, 32, 32
    codes = jnp.asarray(rng.integers(0, 16, (K, N)), jnp.int32)
    b = BlockQTensor(data=pack_nibbles(codes),
                     scale=jnp.full((K // G, N), 0.5, jnp.float32),
                     vmin=jnp.full((K // G, N), -4.0, jnp.float32),
                     group_size=G, k_dim=K)
    a_f = rng.integers(-50, 50, (M, K)).astype(np.float32)
    a = QTensor(jnp.asarray(a_f.astype(np.int8)), jnp.float32(1.0),
                jnp.zeros((), jnp.float32), None)
    got = ops.int4_matmul(a, b, impl="interpret")
    want = a_f @ np.asarray(b.dequantize())
    np.testing.assert_array_equal(np.asarray(got), want)


def test_int4_matmul_padding_contributes_zero(rng):
    """Stored rows beyond k_dim must not leak into the result: poisoning the
    padded tail nibbles (0x0 → 0xF) leaves the output bit-identical."""
    K, G, N, M = 70, 32, 64, 5          # k_store = 96, 26 padded rows
    n_g, k_store = 3, 96
    codes = np.asarray(rng.integers(0, 16, (k_store, N)), np.int32)
    scale = jnp.asarray(rng.uniform(0.01, 0.1, (n_g, N)), jnp.float16)
    vmin = jnp.asarray(rng.uniform(-1.0, 0.0, (n_g, N)), jnp.float16)
    a = _mk_act(rng, M, K, zp=2.0)

    outs = []
    for fill in (0, 15):
        poisoned = codes.copy()
        poisoned[K:, :] = fill
        b = BlockQTensor(data=pack_nibbles(jnp.asarray(poisoned)),
                         scale=scale, vmin=vmin, group_size=G, k_dim=K)
        outs.append(np.asarray(ops.int4_matmul(a, b, impl="interpret")))
    np.testing.assert_array_equal(outs[0], outs[1])


@pytest.mark.parametrize("out_dtype", [jnp.float32, jnp.bfloat16])
def test_int4_matmul_out_dtypes(rng, out_dtype):
    b = _mk_bqt(rng, 64, 128, 32)
    a = _mk_act(rng, 8, 64)
    got = ops.int4_matmul(a, b, out_dtype=out_dtype, impl="interpret")
    assert got.dtype == out_dtype
    want = ops.int4_matmul(a, b, out_dtype=out_dtype, impl="xla")
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=1e-2, atol=1e-2)


def test_int4_matmul_leading_batch_dims(rng):
    """ops.int4_matmul flattens (..., K) activations like int8_matmul."""
    B, T, K, N, G = 2, 3, 64, 128, 32
    b = _mk_bqt(rng, K, N, G)
    data = jnp.asarray(rng.integers(-127, 128, (B, T, K)), jnp.int8)
    scale = jnp.asarray(rng.uniform(1e-3, 0.05, (B, T, 1)), jnp.float32)
    a = QTensor(data, scale, jnp.zeros((), jnp.float32), None)
    got = ops.int4_matmul(a, b, impl="interpret")
    assert got.shape == (B, T, N)
    flat = QTensor(data.reshape(-1, K), scale.reshape(-1, 1),
                   jnp.zeros((), jnp.float32), None)
    want = ops.int4_matmul(flat, b, impl="interpret").reshape(B, T, N)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_int4_matmul_bias(rng):
    b = _mk_bqt(rng, 96, 64, 48)
    a = _mk_act(rng, 7, 96)
    bias = jnp.asarray(rng.normal(size=64), jnp.float32)
    got = ops.int4_matmul(a, b, bias, impl="interpret")
    want = jnp.asarray(
        _jit_oracle(48)(a.data, _row_scale_for_test(a.scale, 7),
                        b.data, b.scale, b.vmin, None, bias))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def _row_scale_for_test(scale, M):
    return jnp.reshape(jnp.asarray(scale, jnp.float32), (M, 1))


def test_pick_bk_invariants():
    """bk must be a multiple of group_size (a block's scale/min never
    straddles two k-tiles) and never exceed the padded store."""
    for k_store, G in [(96, 32), (512, 48), (4096, 128), (64, 64), (32, 128)]:
        bk = _pick_bk(k_store, G, 512)
        assert bk % G == 0 and bk >= G
        assert bk <= max(k_store, G)


@given(st.integers(1, 33), st.integers(1, 200), st.integers(1, 150),
       st.sampled_from([16, 32, 48, 64]))
@settings(max_examples=12, deadline=None)
def test_int4_matmul_property(M, K, N, G):
    r = np.random.default_rng(M * 7919 + K * 131 + N * 17 + G)
    b = _mk_bqt(r, K, N, G)
    a = _mk_act(r, M, K)
    got = int4_matmul_pallas(a.data, a.scale, b.data, b.scale, b.vmin,
                             None, None, group_size=G, interpret=True)
    want = _jit_oracle(G)(a.data, a.scale, b.data, b.scale, b.vmin,
                          None, None)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-6)
