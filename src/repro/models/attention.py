"""Multi-head / grouped-query attention with prefill + decode paths.

Prefill/train uses a chunked (flash-style) attention written in pure jnp —
`lax.scan` over query chunks with f32 accumulation — so 32k-context graphs
never materialize the full (S×S) score tensor.  Decode reads the (optionally
INT8) KV cache through ``kernels.ops.decode_attention``.
"""

from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.calibration import Taps
from repro.core.ptq import FP_CONTEXT, QuantContext
from repro.kernels import ops
from repro.models import kv_cache as kvc
from repro.models.layers import apply_rope, dense, dense_init

NEG_INF = -1e30


def attention_init(key, cfg, *, stack: tuple = (), dtype=jnp.float32,
                   cross: bool = False):
    d, h, hkv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd
    keys = jax.random.split(key, 4)
    return {
        "q_proj": dense_init(keys[0], d, h * hd, bias=cfg.attn_bias,
                             dtype=dtype, stack=stack),
        "k_proj": dense_init(keys[1], d, hkv * hd, bias=cfg.attn_bias,
                             dtype=dtype, stack=stack),
        "v_proj": dense_init(keys[2], d, hkv * hd, bias=cfg.attn_bias,
                             dtype=dtype, stack=stack),
        "o_proj": dense_init(keys[3], h * hd, d, bias=cfg.attn_bias,
                             dtype=dtype, stack=stack),
    }


# ---------------------------------------------------------------------------
# chunked full attention (train / prefill)
# ---------------------------------------------------------------------------

def chunked_attention(
    q: jax.Array,                 # (B, Sq, H, dh)
    k: jax.Array,                 # (B, Sk, HKV, dh)
    v: jax.Array,                 # (B, Sk, HKV, dh)
    *,
    causal: bool,
    q_positions: Optional[jax.Array] = None,   # (B, Sq) global positions
    kv_lengths: Optional[jax.Array] = None,    # (B,) valid kv length
    q_chunk: int = 1024,
    unroll: bool = False,
) -> jax.Array:
    """``unroll=True`` replaces the chunk scan with a trace-time loop —
    used by the roofline cost extraction, where while-loop bodies would be
    counted once by ``cost_analysis`` (see EXPERIMENTS.md §Roofline)."""
    B, Sq, H, dh = q.shape
    _, Sk, HKV, _ = k.shape
    G = H // HKV
    sm_scale = 1.0 / math.sqrt(dh)

    if q_positions is None:
        q_positions = jnp.broadcast_to(jnp.arange(Sq, dtype=jnp.int32),
                                       (B, Sq))
    k_positions = jnp.arange(Sk, dtype=jnp.int32)

    C = min(q_chunk, Sq)
    pad = (-Sq) % C
    if pad:
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
        q_positions = jnp.pad(q_positions, ((0, 0), (0, pad)))
    n_chunks = (Sq + pad) // C

    # GQA: broadcast KV to the full head count rather than splitting q heads
    # into (HKV, G) — the flat H dim shards over "model" (HKV and G alone
    # often don't divide the axis; H does).  KV bytes grow G× but score
    # memory — the prefill bottleneck — shards 16-way.
    if G > 1:
        k = jnp.repeat(k, G, axis=2)
        v = jnp.repeat(v, G, axis=2)

    qg = q.reshape(B, n_chunks, C, H, dh)
    pg = q_positions.reshape(B, n_chunks, C)

    def one_chunk(carry, xs, k=k, v=v, k_positions=k_positions):
        q_c, pos_c = xs                          # (B, C, H, dh), (B, C)
        Sk_c = k.shape[1]
        # bf16 operands, f32 accumulation (MXU-native): keeping K/V in the
        # activation dtype halves their HBM/ICI traffic vs upcasting before
        # the scan (§Perf iteration B4)
        scores = jnp.einsum("bchd,bshd->bhcs", q_c, k,
                            preferred_element_type=jnp.float32) * sm_scale
        mask = jnp.ones((B, C, Sk_c), bool)
        if causal:
            mask &= pos_c[:, :, None] >= k_positions[None, None, :]
        if kv_lengths is not None:
            mask &= k_positions[None, None, :] < kv_lengths[:, None, None]
        scores = jnp.where(mask[:, None], scores, NEG_INF)   # (B,1,C,Sk)
        probs = jax.nn.softmax(scores, axis=-1)
        out = jnp.einsum("bhcs,bshd->bchd", probs.astype(q.dtype), v,
                         preferred_element_type=jnp.float32)
        return carry, out.astype(q.dtype)

    xs = (jnp.moveaxis(qg, 1, 0), jnp.moveaxis(pg, 1, 0))
    if unroll:
        # static chunk index → causal BLOCK SKIPPING: chunk i only attends
        # keys [0, (i+1)·C) — halves attention FLOPs at long context
        # (§Perf iteration C2; the Pallas flash kernel does the same on TPU)
        outs = []
        for i in range(n_chunks):
            hi = min((i + 1) * C, Sk) if causal else Sk
            _, o = one_chunk(None, (xs[0][i], xs[1][i]),
                             k=k[:, :hi], v=v[:, :hi],
                             k_positions=k_positions[:hi])
            outs.append(o)
        out = jnp.stack(outs, axis=0)
    else:
        # remat each chunk: recompute the f32 scores/probs in backward
        # instead of saving (chunks × B × H × C × S f32 would dominate the
        # training working set — flash-attention's usual trade).
        _, out = jax.lax.scan(jax.checkpoint(one_chunk), None, xs)
    out = jnp.moveaxis(out, 0, 1).reshape(B, n_chunks * C, H, dh)
    return out[:, :Sq]


# ---------------------------------------------------------------------------
# attention block
# ---------------------------------------------------------------------------

def attention(
    params,
    x: jax.Array,                     # (B, S, D)
    *,
    cfg,
    site: str,
    quant: QuantContext = FP_CONTEXT,
    taps: Optional[Taps] = None,
    positions: Optional[jax.Array] = None,      # (B, S)
    kv_lengths: Optional[jax.Array] = None,
    causal: bool = True,
    rope: bool = True,
    cache: Optional[kvc.LayerCacheView] = None,
    memory: Optional[Tuple[jax.Array, jax.Array]] = None,   # cross-attn (k, v)
    memory_lengths: Optional[jax.Array] = None,
    unroll: bool = False,
    per_query: bool = False,
) -> Tuple[jax.Array, Optional[Tuple]]:
    """Returns (output, new_cache_entries).

    Modes:
    * ``cache is None and memory is None`` — train/prefill self-attention.
    * ``cache is not None`` — decode against the cache: S == 1 is the
      classic single-token step; S > 1 is the speculative *verify* step
      (S consecutive positions appended at the cursor, each query causally
      masked to its own prefix).
    * ``memory is not None`` — cross-attention onto precomputed (k, v).
    """
    B, S, D = x.shape
    H, HKV, dh = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    dt = x.dtype

    q = dense(params["q_proj"], x, site=f"{site}/q_proj", quant=quant,
              taps=taps).reshape(B, S, H, dh)

    if memory is not None:
        k, v = memory
        if per_query and S > 1:
            # decode-side cross-attention over S drafted positions: run the
            # S == 1 shape per query so every position reduces in exactly
            # the order the sequential decode path uses (XLA re-tiles the
            # softmax·V contraction for wider Sq, which costs bit-identity)
            out = jnp.concatenate(
                [chunked_attention(q[:, j:j + 1], k, v, causal=False,
                                   kv_lengths=memory_lengths, unroll=unroll)
                 for j in range(S)], axis=1)
        else:
            out = chunked_attention(q, k, v, causal=False,
                                    kv_lengths=memory_lengths, unroll=unroll)
        out = out.reshape(B, S, H * dh)
        y = dense(params["o_proj"], out, site=f"{site}/o_proj", quant=quant,
                  taps=taps)
        return y, None

    k = dense(params["k_proj"], x, site=f"{site}/k_proj", quant=quant,
              taps=taps).reshape(B, S, HKV, dh)
    v = dense(params["v_proj"], x, site=f"{site}/v_proj", quant=quant,
              taps=taps).reshape(B, S, HKV, dh)

    if positions is None:
        if cache is not None:
            positions = (cache.lengths[:, None]         # (B, S) from cursor
                         + jnp.arange(S, dtype=jnp.int32)[None, :])
        else:
            positions = jnp.broadcast_to(
                jnp.arange(S, dtype=jnp.int32), (B, S))
    if rope:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)

    new_entries = (k, v)

    if cache is not None:
        # ---- decode: append at each sequence's cursor, then attend ----
        # The cache stays stacked: the new K/V are written in place at
        # ``cache.layer`` and the kernels read the stack by that index.
        paged = cache.block_tables is not None
        layer = cache.layer
        with jax.named_scope("kv_pool"):
            if paged:
                append = (kvc.append_token_paged if S == 1
                          else kvc.append_tokens_paged)
                k_c, v_c, ks_c, vs_c = append(
                    cache.k, cache.v, cache.k_scale, cache.v_scale,
                    cache.block_tables, k, v, cache.lengths, layer,
                    impl=quant.impl)
            else:
                # S > 1 is the speculative verify: all S drafted positions
                append = kvc.append_token if S == 1 else kvc.append_tokens
                k_c, v_c, ks_c, vs_c = append(
                    cache.k, cache.v, cache.k_scale, cache.v_scale, k, v,
                    cache.lengths, layer)
        sm_scale = 1.0 / math.sqrt(dh)
        if ks_c is None:
            # FP cache: one layer's rows, for the jnp attention below.  The
            # paged FALLBACK linearizes the pool through the table — it
            # materializes a gathered copy per step, so it trades the
            # beam-reorder slab gather for an attention-side one (a wash at
            # worst; the cross-K/V gather still disappears).  The
            # deployment path is the INT8 cache, whose Pallas kernel walks
            # the table in place with no copy.
            k_fp, v_fp = k_c[layer], v_c[layer]
            if paged:
                k_fp = kvc.linearize_pages(k_fp, cache.block_tables)
                v_fp = kvc.linearize_pages(v_fp, cache.block_tables)
        # Each query position j attends its own causal prefix by running
        # the SAME single-query kernel with cursor lengths + j + 1 — for
        # S == 1 this is literally the pre-speculation decode step, and for
        # S > 1 it makes the verify pass bit-identical to sequential decode
        # by construction (identical kernel, shapes, and masked lengths).
        outs = []
        for j in range(S):
            q1 = q[:, j].reshape(B, H, dh)
            lengths = cache.lengths + (j + 1)
            if ks_c is None:
                o = _fp_decode_attention(q1, k_fp, v_fp, lengths, sm_scale)
            elif paged:
                o = ops.decode_attention_paged(
                    q1, k_c, ks_c, v_c, vs_c, cache.block_tables, lengths,
                    layer, sm_scale=sm_scale, impl=quant.impl)
            else:
                o = ops.decode_attention(q1, k_c, ks_c, v_c, vs_c, lengths,
                                         layer, sm_scale=sm_scale,
                                         impl=quant.impl)
            outs.append(o)
        out = jnp.stack(outs, axis=1).reshape(B, S, H * dh)
        y = dense(params["o_proj"], out, site=f"{site}/o_proj", quant=quant,
                  taps=taps)
        return y, (k_c, v_c, ks_c, vs_c)

    # ---- train / prefill ----
    out = chunked_attention(q, k, v, causal=causal, q_positions=positions,
                            kv_lengths=kv_lengths, unroll=unroll)
    out = out.reshape(B, S, H * dh)
    y = dense(params["o_proj"], out, site=f"{site}/o_proj", quant=quant,
              taps=taps)
    return y, new_entries


def _fp_decode_attention(q, k, v, lengths, sm_scale):
    """bf16 cache decode path (baseline without the paper's technique)."""
    B, H, dh = q.shape
    _, Sk, HKV, _ = k.shape
    G = H // HKV
    qf = q.astype(jnp.float32).reshape(B, HKV, G, dh)
    scores = jnp.einsum("bhgd,bshd->bhgs", qf, k.astype(jnp.float32))
    scores = scores * sm_scale
    mask = jnp.arange(Sk)[None, :] < lengths[:, None]
    scores = jnp.where(mask[:, None, None, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhgs,bshd->bhgd", probs, v.astype(jnp.float32))
    return out.reshape(B, H, dh).astype(q.dtype)
