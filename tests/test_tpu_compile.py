"""Compile-only checks: the main path's Pallas kernels at transformer-base
shapes, compiled for a described (not attached) TPU v5e chip.

Interpret mode runs a kernel body on the CPU but never meets the TPU
compiler's rules (block tiling, supported ops and dtypes), so every kernel
here is compiled for the chip and must come out as a ``tpu_custom_call``.
Nothing runs: this says nothing about results or times.

The chip is described inside a module-scoped fixture, never at import: only
one process at a time may load the TPU library, and under pytest-xdist every
worker imports this file.  Keep these tests in this one file.
"""

import dataclasses
import os
import re
from collections import Counter

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec, \
    SingleDeviceSharding

from repro.core import QuantMode, QuantPolicy, quantize_model
from repro.core.ptq import QuantContext
from repro.data import make_corpus
from repro.distributed.sharding import abstract_with_sharding
from repro.kernels import ops
from repro.kernels.decode_attention import (decode_attention_paged_pallas,
                                            decode_attention_pallas)
from repro.kernels.int4_matmul import int4_matmul_pallas
from repro.kernels.int8_matmul import int8_matmul_pallas
from repro.kernels.kv_pool import (append_scales_paged_pallas,
                                   copy_scale_pages_pallas)
from repro.kernels.quantize import (quantize_rowwise_pallas,
                                    quantize_static_pallas)
from repro.launch.hlo_analysis import pallas_kernel_calls
from repro.launch.serve import quantize_for_serving, serving_config
from repro.models import build_model
from repro.models import kv_cache as kvc
from repro.serving.engine import log_probs, on_whole_rows
from repro.serving.sharding import param_shardings

# transformer-base (configs/transformer_base.py) and the serving defaults
D_MODEL, D_FF, HEADS, HEAD_DIM = 512, 2048, 8, 64
PAGE_SIZE, MAX_LEN, GROUP = 16, 96, 128
VOCAB, BEAM_ROWS = 37000, 16
DECODE_ROWS, PREFILL_ROWS = 8, 256


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one; keep the cache out of it
    cache_was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield desc
    jax.config.update("jax_enable_compilation_cache", cache_was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compiles_to_kernel(kernel, fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert pallas_kernel_calls(text) == {kernel: 1}


@pytest.mark.parametrize("M", [DECODE_ROWS, PREFILL_ROWS])
@pytest.mark.parametrize("K,N", [(D_MODEL, D_FF), (D_FF, D_MODEL)])
def test_int8_matmul_compiles(one_chip, M, K, N):
    _compiles_to_kernel(
        "int8_matmul_pallas",
        lambda a, s, b, bs: int8_matmul_pallas(a, s, b, bs,
                                               out_dtype=jnp.bfloat16),
        one_chip, ((M, K), jnp.int8), ((M, 1), jnp.float32),
        ((K, N), jnp.int8), ((1, N), jnp.float32))


@pytest.mark.parametrize("M", [DECODE_ROWS, PREFILL_ROWS])
@pytest.mark.parametrize("kind", ["rowwise", "static"])
def test_quantize_compiles(one_chip, kind, M):
    if kind == "rowwise":
        fn = quantize_rowwise_pallas
    else:
        def fn(x):
            return quantize_static_pallas(x, jnp.float32(3.0))
    _compiles_to_kernel(f"quantize_{kind}_pallas", fn, one_chip,
                        ((M, D_MODEL), jnp.bfloat16))


def test_decode_attention_compiles(one_chip):
    B, L = DECODE_ROWS, 2
    _compiles_to_kernel(
        "decode_attention_pallas",
        lambda q, k, ks, v, vs, n, layer: decode_attention_pallas(
            q, k, ks, v, vs, n, layer, sm_scale=HEAD_DIM ** -0.5),
        one_chip, ((B, HEADS, HEAD_DIM), jnp.bfloat16),
        ((L, B, MAX_LEN, HEADS, HEAD_DIM), jnp.int8),
        ((L, B, MAX_LEN, HEADS), jnp.float32),
        ((L, B, MAX_LEN, HEADS, HEAD_DIM), jnp.int8),
        ((L, B, MAX_LEN, HEADS), jnp.float32), ((B,), jnp.int32),
        ((), jnp.int32))


def test_decode_attention_paged_compiles(one_chip):
    B, max_pages, L = DECODE_ROWS, MAX_LEN // PAGE_SIZE, 2
    P = B * max_pages
    _compiles_to_kernel(
        "decode_attention_paged_pallas",
        lambda q, k, ks, v, vs, t, n, layer: decode_attention_paged_pallas(
            q, k, ks, v, vs, t, n, layer, sm_scale=HEAD_DIM ** -0.5),
        one_chip, ((B, HEADS, HEAD_DIM), jnp.bfloat16),
        ((L, P, PAGE_SIZE, HEADS, HEAD_DIM), jnp.int8),
        ((L, P, PAGE_SIZE, HEADS), jnp.float32),
        ((L, P, PAGE_SIZE, HEADS, HEAD_DIM), jnp.int8),
        ((L, P, PAGE_SIZE, HEADS), jnp.float32),
        ((B, max_pages), jnp.int32), ((B,), jnp.int32), ((), jnp.int32))


@pytest.mark.parametrize("B,H,HKV,max_pages", [
    (512, HEADS, HEADS, 9),      # the offline beam-4 cell: 512 rows, 144
    (512, 16, 16, 9),            # transformer-big's 16 heads
    (19, HEADS, 2, 4),           # GQA (G=4), rows no multiple of the block
    (2, HEADS, HEADS, 256),      # 4096-token rows: four chunks of slots
])
def test_decode_attention_paged_compiles_row_blocks(one_chip, B, H, HKV,
                                                     max_pages):
    P, L = B * max_pages, 6
    _compiles_to_kernel(
        "decode_attention_paged_pallas",
        lambda q, k, ks, v, vs, t, n, layer: decode_attention_paged_pallas(
            q, k, ks, v, vs, t, n, layer, sm_scale=HEAD_DIM ** -0.5),
        one_chip, ((B, H, HEAD_DIM), jnp.bfloat16),
        ((L, P, PAGE_SIZE, HKV, HEAD_DIM), jnp.int8),
        ((L, P, PAGE_SIZE, HKV), jnp.float32),
        ((L, P, PAGE_SIZE, HKV, HEAD_DIM), jnp.int8),
        ((L, P, PAGE_SIZE, HKV), jnp.float32),
        ((B, max_pages), jnp.int32), ((B,), jnp.int32), ((), jnp.int32))


@pytest.mark.parametrize("B,HKV", [(512, HEADS), (512, 16), (19, 2)])
def test_scale_pool_writes_compile(one_chip, B, HKV):
    """The Pallas writes of the stacked scale pools, at the offline cell's
    pool (512 rows of 9 pages, 6 layers), transformer-big's 16 KV heads
    and a GQA pool: one row's scales a DMA, and whole pages through VMEM
    for the copy-on-write."""
    P, L = B * 9, 6
    pool = ((L, P, PAGE_SIZE, HKV), jnp.float32)
    _compiles_to_kernel(
        "append_scales_paged_pallas",
        lambda ks_p, vs_p, ks, vs, page, off, layer:
            append_scales_paged_pallas(ks_p, vs_p, ks, vs, page, off, layer),
        one_chip, pool, pool, ((B, HKV), jnp.float32),
        ((B, HKV), jnp.float32), ((B,), jnp.int32), ((B,), jnp.int32),
        ((), jnp.int32))
    _compiles_to_kernel(
        "copy_scale_pages_pallas",
        lambda ks_p, vs_p, src, dst: copy_scale_pages_pallas(
            ks_p, vs_p, src, dst),
        one_chip, pool, pool, ((B,), jnp.int32), ((B,), jnp.int32))


@pytest.mark.parametrize("K,N", [(D_MODEL, D_FF), (D_FF, D_MODEL)])
def test_int4_matmul_compiles(one_chip, K, N):
    M = DECODE_ROWS
    _compiles_to_kernel(
        "int4_matmul_pallas",
        lambda a, s, b, sc, mn: int4_matmul_pallas(
            a, s, b, sc, mn, group_size=GROUP, out_dtype=jnp.bfloat16),
        one_chip, ((M, K), jnp.int8), ((M, 1), jnp.float32),
        ((K // 2, N), jnp.int8), ((K // GROUP, N), jnp.float16),
        ((K // GROUP, N), jnp.float16))


def _float_sum_all_reduces(hlo: str) -> int:
    """All-reduces whose combiner adds floats: their result depends on the
    order the devices' partial sums arrive in."""
    adders, region = set(), None
    for line in hlo.splitlines():
        head = re.match(r"%?([\w.\-]+) .*\{$", line)
        if head:
            region = head.group(1)
        elif "ROOT" in line and " add(" in line:
            adders.add(region)
    return sum(1 for line in hlo.splitlines()
               if re.search(r"= \(?(f16|bf16|f32)\[.* all-reduce\(", line)
               and re.search(r"to_apply=%?([\w.\-]+)", line).group(1)
               in adders)


@pytest.mark.parametrize("out_sharded", [False, True])
@pytest.mark.parametrize("mesh_given", [False, True])
def test_tp4_beam_log_probs_sum_on_one_device(topo, mesh_given, out_sharded):
    """Vocab-sharded logits (tp=4, as the unembedding leaves them): given
    the mesh, ``log_probs`` normalises whole rows on each device, so no
    float sum crosses devices and a tp=4 beam ranks like tp=1 — whatever
    sharding the consumer asks of the result."""
    mesh = Mesh(np.asarray(topo.devices).reshape(1, 4), ("data", "model"))
    vocab_sharded = NamedSharding(mesh, PartitionSpec(None, "model"))
    logits = jax.ShapeDtypeStruct((BEAM_ROWS, VOCAB), jnp.bfloat16,
                                  sharding=vocab_sharded)
    fn = jax.jit(lambda x: log_probs(x, mesh if mesh_given else None),
                 out_shardings=vocab_sharded if out_sharded else None)
    n = _float_sum_all_reduces(fn.lower(logits).compile().as_text())
    assert n == (0 if mesh_given else 1)


def _instructions(hlo: str) -> list:
    """A compiled program's instructions without names, metadata and
    shardings: what two per-device programs have in common."""
    out = []
    for line in hlo.splitlines():
        line = line.strip()
        if not line.startswith(("%", "ROOT")):
            continue
        line = re.sub(r"(parameter\(\d+\)).*", r"\1", line)
        line = re.sub(r", metadata=\{[^}]*\}|, sharding=.*", "", line)
        line = re.sub(r"%[\w.\-]+|[\w.\-]+: ", "%", line)
        line = re.sub(r"(calls|to_apply|called_computations|condition|body)"
                      r"=[^,}\s]+", r"\1=", line)
        out.append(line)
    return out


def test_tp4_encoder_is_the_one_chip_program(topo, one_chip):
    """A tp=4 engine runs the encoder whole on every chip, from replicated
    encoder weights, and only then splits the cross-K/V on heads for the
    decode state.  Each chip's program holds the one-chip encoder
    instruction for instruction, so the cross-K/V it writes match tp=1 bit
    for bit.  (Split on heads, the encoder's attention rounds differently
    at 2 of 8 heads per chip, and beam search ranks near ties by the
    difference.)  Published widths, one encoder and one decoder layer."""
    cfg = dataclasses.replace(serving_config("transformer-base",
                                             published=True),
                              n_layers=1, n_enc_layers=1)
    tiny = dataclasses.replace(cfg, d_model=64, d_ff=128, vocab=512,
                               head_dim=8, dtype="float32")
    tiny_model = build_model(tiny)
    _, _, recs = quantize_for_serving(
        tiny_model, tiny_model.init(jax.random.PRNGKey(0)),
        make_corpus(2, tiny.vocab, seed=0))          # same site names
    policy = QuantPolicy(mode=QuantMode("symmetric"), act_quant="static")
    model = build_model(cfg)
    p_abs = jax.eval_shape(
        lambda k: quantize_model(model.init(k), recs, policy)[0],
        jax.random.PRNGKey(0))
    qctx = dataclasses.replace(quantize_model({}, recs, policy)[1],
                               impl="xla")
    mesh = Mesh(np.asarray(topo.devices).reshape(1, 4), ("data", "model"))

    def encoder_program(mesh, params_sharding, batch_sharding, out=None):
        fn = on_whole_rows(
            lambda p, b: model.encode_cross_kv(p, b, quant=qctx), mesh)
        batch = {"src_tokens": jax.ShapeDtypeStruct(
                     (BEAM_ROWS, 64), jnp.int32, sharding=batch_sharding),
                 "src_lengths": jax.ShapeDtypeStruct(
                     (BEAM_ROWS,), jnp.int32, sharding=batch_sharding)}
        params = abstract_with_sharding(p_abs, params_sharding)
        return jax.jit(fn, out_shardings=out).lower(
            params, batch).compile().as_text()

    whole = NamedSharding(mesh, PartitionSpec())
    heads = NamedSharding(mesh, PartitionSpec(None, None, None, "model"))
    tp4 = encoder_program(mesh, param_shardings(p_abs, mesh, kv_heads=HEADS),
                          whole, out=(heads, heads, whole))
    tp1 = encoder_program(None, jax.tree.map(lambda _: one_chip, p_abs),
                          one_chip)
    assert " all-gather" not in tp4 and " all-reduce" not in tp4
    missing = Counter(_instructions(tp1)) - Counter(_instructions(tp4))
    # only the output's last reshape and tuple differ: tp=4 slices them
    assert missing and all(" reshape(" in line or " tuple(" in line
                           for line in missing), sorted(missing)


def test_scoped_decode_step_keeps_kernel_names(one_chip):
    """Named scopes change op metadata only.  In a paged INT8 decode step
    at published widths (one decoder layer), every Pallas call keeps the
    instruction name of its jitted wrapper, which the benchmark's trace
    reduction matches kernels by, and sits under its layer's scope."""
    cfg = dataclasses.replace(serving_config("transformer-base",
                                             published=True),
                              n_layers=1, n_enc_layers=1)
    tiny = dataclasses.replace(cfg, d_model=64, d_ff=128, vocab=512,
                               head_dim=8, dtype="float32")
    tiny_model = build_model(tiny)
    _, _, recs = quantize_for_serving(
        tiny_model, tiny_model.init(jax.random.PRNGKey(0)),
        make_corpus(2, tiny.vocab, seed=0))          # same site names
    policy = QuantPolicy(mode=QuantMode("symmetric"), act_quant="static")
    model = build_model(cfg)
    p_abs = jax.eval_shape(
        lambda k: quantize_model(model.init(k), recs, policy)[0],
        jax.random.PRNGKey(0))
    qctx = dataclasses.replace(quantize_model({}, recs, policy)[1],
                               impl="pallas")
    assert qctx.quantize_kv
    state = jax.eval_shape(lambda: model.init_decode_state(
        DECODE_ROWS, MAX_LEN, quantized=True, enc_len=64, paged=True,
        page_size=PAGE_SIZE, n_pages=DECODE_ROWS * MAX_LEN // PAGE_SIZE))
    on_chip = lambda tree: jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        tree)
    tokens = jax.ShapeDtypeStruct((DECODE_ROWS,), jnp.int32,
                                  sharding=one_chip)
    text = jax.jit(lambda p, t, s: model.decode_step(p, t, s, quant=qctx)
                   ).lower(on_chip(p_abs), tokens, on_chip(state)
                           ).compile().as_text()
    calls = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    kernels = pallas_kernel_calls(text)
    assert {"int8_matmul_pallas", "decode_attention_paged_pallas"} <= \
        set(kernels) and "unnamed" not in kernels
    for line in calls:
        name = line.split(" = ", 1)[0].strip().lstrip("%").rsplit(".", 1)[0]
        assert re.search(rf"/jit\({name}\)/pallas_call", line), line
        if name == "decode_attention_paged_pallas":
            assert "/self_attention/" in line, line


def _computations(hlo: str) -> dict:
    """A compiled module's computations: name → its instructions, each
    (name, output type, opcode, operand names, called computations)."""
    comps, cur = {}, None
    for line in hlo.splitlines():
        head = re.match(r"(?:ENTRY )?%?([\w.\-]+) .*\{$", line)
        if head:
            cur = comps.setdefault(head.group(1), [])
            continue
        ins = re.match(r"\s*(?:ROOT )?%([\w.\-]+) = (\(.*?\)|\S+) "
                       r"([\w\-]+)\((.*?)\)(.*)", line)
        if cur is not None and ins:
            name, out, op, args, rest = ins.groups()
            cur.append((name, out, op, re.findall(r"%([\w.\-]+)", args),
                        re.findall(r"(?:calls|body)=%([\w.\-]+)", rest),
                        line))
    return comps


def test_decode_loop_writes_the_stacked_pool_in_place(one_chip):
    """The decode loop never materialises a layer of the self-KV pool, nor
    copies the stacked pool.  A paged INT8 decode step and the beam
    reorder at published widths and depth (six decoder layers), the
    offline cell's pool (512 rows of 9 pages), in a ``while_loop`` that
    carries the donated decode state as a burst program does: the loop
    body calls the paged kernel once a layer, on the stacked K/V pools and
    scale pools themselves, and no copy, slice, concatenation or fusion in
    it outputs a pool-shaped array other than an in-place scatter or
    update.  (At this depth XLA would keep scale pools it scatters into
    itself in a compact layout and relayout the whole stack before every
    kernel call; at two layers it would not, so two do not guard.)"""
    L, rows = 6, 512
    cfg = dataclasses.replace(serving_config("transformer-base",
                                             published=True),
                              n_layers=L, n_enc_layers=1)
    tiny = dataclasses.replace(cfg, d_model=64, d_ff=128, vocab=512,
                               head_dim=8, dtype="float32")
    tiny_model = build_model(tiny)
    _, _, recs = quantize_for_serving(
        tiny_model, tiny_model.init(jax.random.PRNGKey(0)),
        make_corpus(2, tiny.vocab, seed=0))          # same site names
    policy = QuantPolicy(mode=QuantMode("symmetric"), act_quant="static")
    model = build_model(cfg)
    p_abs = jax.eval_shape(
        lambda k: quantize_model(model.init(k), recs, policy)[0],
        jax.random.PRNGKey(0))
    qctx = dataclasses.replace(quantize_model({}, recs, policy)[1],
                               impl="pallas")
    max_len = 144
    P = rows * max_len // PAGE_SIZE
    state = jax.eval_shape(lambda: model.init_decode_state(
        rows, max_len, quantized=True, enc_len=128, paged=True,
        page_size=PAGE_SIZE, n_pages=P))
    on_chip = lambda tree: jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        tree)

    def loop(params, tokens, state):
        def body(carry):
            i, tokens, state = carry
            logits, state = model.decode_step(params, tokens, state,
                                              quant=qctx)
            tokens = jnp.argmax(logits, -1).astype(jnp.int32)
            beams = (jnp.arange(rows) // 4) * 4 + tokens % 4
            state = dict(state, cache=kvc.gather_beams_paged(
                state["cache"], beams, impl="pallas"))
            return i + 1, tokens, state

        return jax.lax.while_loop(lambda c: c[0] < 8, body,
                                  (jnp.int32(0), tokens, state))

    tokens = jax.ShapeDtypeStruct((rows,), jnp.int32, sharding=one_chip)
    text = jax.jit(loop, donate_argnums=(2,)).lower(
        on_chip(p_abs), tokens, on_chip(state)).compile().as_text()
    comps = _computations(text)
    bodies = [c for ins_list in comps.values() for ins in ins_list
              if ins[2] == "while" for c in ins[4]]
    assert len(bodies) == 1
    body = comps[bodies[0]]
    shape = {name: out.split("{")[0] for name, out, *_ in body}
    kv = f"s8[{L},{P},{PAGE_SIZE},{HEADS},{HEAD_DIM}]"
    sc = f"f32[{L},{P},{PAGE_SIZE},{HEADS}]"
    pools = re.compile(r"^\(?(s8|f32)\[(\d+,)?(1,)?%d,%d,%d(,%d)?\]"
                       % (P, PAGE_SIZE, HEADS, HEAD_DIM))
    calls = [ins for ins in body
             if "decode_attention_paged_pallas" in ins[5]
             and 'custom_call_target="tpu_custom_call"' in ins[5]]
    assert len(calls) == L
    for *_, operands, _, _ in calls:
        assert sorted(shape[o] for o in operands if o in shape
                      and pools.match(shape[o])) == [sc, sc, kv, kv]
    in_place = ("scatter", "dynamic-update-slice")
    for name, out, op, operands, called, line in body:
        if not pools.match(out):
            continue
        if op == "fusion":
            root = [i for i in comps[called[0]] if "ROOT" in i[5]]
            assert root[0][2] in in_place, line
        else:
            assert op not in ("copy", "copy-start", "copy-done", "slice",
                              "slice-start", "slice-done", "dynamic-slice",
                              "concatenate", "transpose"), line
            assert "ConcatBitcast" not in line, line


def test_quant_context_resolves_to_xla_on_cpu():
    """The ``"auto"`` kernel default picks Pallas only on a TPU; the CPU
    path (every other test) keeps running the jnp references."""
    assert jax.default_backend() == "cpu"
    assert QuantContext(policy=QuantPolicy()).impl == "auto"
    assert ops.resolve_impl(QuantContext(policy=QuantPolicy()).impl) == "xla"
    assert ops.resolve_impl("interpret") == "interpret"
