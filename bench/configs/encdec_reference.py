"""Plain float32 reference of the encoder-decoder Transformer the
transformer-*-int8 configurations run.

Straight ``jax.numpy`` at ``highest`` matmul precision, no kernels, cache,
quantization or batching tricks; it imports nothing of the program.  It
follows Vaswani et al. 2017 (arXiv:1706.03762) with the departures each
configuration file lists: pre-norm blocks with a final LayerNorm (eps
1e-5), GELU (tanh form) in the feed-forward block, biases on every
projection, sinusoidal positions added to embeddings scaled by
sqrt(d_model), and the output projection tied to the embedding table.

Weights are a dict in the checkpoint layout the serving program loads:
``embed/table``, ``{enc,dec}_final_norm/{scale,bias}``,
``enc_blocks.<i>/{attn_norm,attn,ffn_norm,ffn}`` and
``dec_blocks.<i>/{self_norm,self_attn,cross_norm,cross_attn,ffn_norm,ffn}``
with ``{q,k,v,o}_proj/{w,b}`` and ``ffn/{in,out}/{w,b}``; ``w`` is
``(d_in, d_out)``.
"""

from __future__ import annotations

import math
from typing import Any, Dict

import jax
import jax.numpy as jnp

NEG = -1e30


def layernorm(p, x, eps: float = 1e-5):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]


def gelu(x):
    return 0.5 * x * (1.0 + jnp.tanh(math.sqrt(2.0 / math.pi)
                                     * (x + 0.044715 * x ** 3)))


def linear(p, x):
    return x @ p["w"] + p["b"]


def positions(n: int, d: int):
    pos = jnp.arange(n, dtype=jnp.float32)[:, None]
    angle = pos / jnp.power(10000.0, jnp.arange(0, d, 2,
                                                 dtype=jnp.float32) / d)
    pe = jnp.zeros((n, d), jnp.float32)
    return pe.at[:, 0::2].set(jnp.sin(angle)).at[:, 1::2].set(jnp.cos(angle))


def attention(p, xq, xkv, mask, n_heads: int, head_dim: int):
    """``mask``: (B, Sq, Sk) True where a query may attend a key."""
    B, Sq, _ = xq.shape
    Sk = xkv.shape[1]
    q = linear(p["q_proj"], xq).reshape(B, Sq, n_heads, head_dim)
    k = linear(p["k_proj"], xkv).reshape(B, Sk, n_heads, head_dim)
    v = linear(p["v_proj"], xkv).reshape(B, Sk, n_heads, head_dim)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(head_dim)
    s = jnp.where(mask[:, None], s, NEG)
    a = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", a, v).reshape(B, Sq, -1)
    return linear(p["o_proj"], o)


def ffn(p, x):
    return linear(p["out"], gelu(linear(p["in"], x)))


def embed(params, ids, d: int):
    x = params["embed"]["table"][ids] * math.sqrt(d)
    return x + positions(ids.shape[1], d)[None]


def encode(params: Dict[str, Any], cfg: Dict[str, Any], src, src_lengths):
    d, H, dh = cfg["d_model"], cfg["n_heads"], cfg["head_dim"]
    x = embed(params, src, d)
    S = src.shape[1]
    keys = jnp.arange(S)[None, :] < src_lengths[:, None]        # (B, S)
    mask = jnp.broadcast_to(keys[:, None, :], (src.shape[0], S, S))
    for i in range(cfg["n_enc_layers"]):
        p = params[f"enc_blocks.{i}"]
        x = x + attention(p["attn"], layernorm(p["attn_norm"], x),
                          layernorm(p["attn_norm"], x), mask, H, dh)
        x = x + ffn(p["ffn"], layernorm(p["ffn_norm"], x))
    return layernorm(params["enc_final_norm"], x)


def logits(params: Dict[str, Any], cfg: Dict[str, Any], src, src_lengths,
           dec_tokens):
    """Teacher-forced decoder logits (B, T, vocab) for ``dec_tokens``
    (B, T): position t sees ``dec_tokens[:, :t+1]`` and the source."""
    d, H, dh = cfg["d_model"], cfg["n_heads"], cfg["head_dim"]
    with jax.default_matmul_precision("highest"):
        memory = encode(params, cfg, src, src_lengths)
        B, T = dec_tokens.shape
        S = src.shape[1]
        causal = jnp.broadcast_to(jnp.tril(jnp.ones((T, T), bool)),
                                  (B, T, T))
        cross = jnp.broadcast_to(
            (jnp.arange(S)[None, :] < src_lengths[:, None])[:, None, :],
            (B, T, S))
        y = embed(params, dec_tokens, d)
        for i in range(cfg["n_layers"]):
            p = params[f"dec_blocks.{i}"]
            h = layernorm(p["self_norm"], y)
            y = y + attention(p["self_attn"], h, h, causal, H, dh)
            y = y + attention(p["cross_attn"], layernorm(p["cross_norm"], y),
                              memory, cross, H, dh)
            y = y + ffn(p["ffn"], layernorm(p["ffn_norm"], y))
        y = layernorm(params["dec_final_norm"], y)
        return y @ params["embed"]["table"].T
