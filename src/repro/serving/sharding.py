"""Decode-state shardings for tensor-parallel serving.

The engine's burst programs run unchanged under GSPMD: we place the
*inputs* — params via ``distributed.sharding.named_shardings`` and the
decode state via :func:`decode_state_shardings` below — and jit compiles
one SPMD program per mesh, with the per-layer all-reduces inside the
``lax.while_loop``.  Nothing host-side changes: block tables, the token
ring, cursors and allocator state stay replicated, so the scheduler,
prefix cache and preemption spill paths never see the mesh.

What shards where (``tensor`` axis, default ``"model"``):

* K/V pools — paged ``(L, n_pages, ps, HKV, dh)``, contiguous
  ``(L, B, S, HKV, dh)``, cross ``(L, B, enc, HKV, dh)`` and prefix
  pools — split on the heads axis: ``P(None, None, None, tensor, None)``.
* their per-token quant scales ``(..., HKV)``: ``P(None, None, None,
  tensor)``.
* everything else (block tables, lengths, cursors, token ring):
  replicated.

Params (:func:`param_shardings`) follow the training tensor rules, except
what the encoder path reads (the embedding table, the encoder blocks and
the cross-attention K/V projections), which every device holds whole: the
engine runs the encoder whole on each device (``engine.on_whole_rows``).

GQA guard: when ``HKV`` does not divide the tensor axis the pools fall
back to replicated — mirroring ``_base_spec``'s k/v_proj rule — instead
of crashing in ``NamedSharding`` construction.  Q heads still shard, so
the attention math stays correct (each device holds every KV head but
only its Q-head slice).
"""

from __future__ import annotations

from typing import Any

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.distributed.sharding import named_shardings

__all__ = ["tp_degree", "kv_pools_shardable", "decode_state_specs",
           "decode_state_shardings", "mesh_axis_sizes", "param_shardings"]


def tp_degree(mesh, tensor: str = "model") -> int:
    """Size of the tensor axis (1 when the mesh doesn't have it)."""
    if mesh is None or tensor not in mesh.axis_names:
        return 1
    return int(mesh.shape[tensor])


def mesh_axis_sizes(mesh) -> tuple:
    """Mesh shape as a plain tuple in axis order — for ServeResult."""
    return tuple(int(mesh.shape[a]) for a in mesh.axis_names)


def param_shardings(params: Any, mesh, *, kv_heads: int,
                    tensor: str = "model") -> Any:
    """Shardings for an enc-dec model's params on a serving mesh: the
    training tensor rules with fsdp off, except that everything the
    encoder path reads is replicated — the embedding table, the encoder
    blocks, and each decoder layer's cross-attention K/V projections."""
    rules = named_shardings(params, mesh, tensor=tensor, fsdp=None,
                            kv_heads=kv_heads)
    whole = NamedSharding(mesh, P())

    def replicate(tree):
        return jax.tree_util.tree_map(lambda _: whole, tree)

    out = {}
    for k, v in rules.items():
        if k == "embed" or k.startswith("enc_"):
            v = replicate(v)
        elif k.startswith("dec_blocks.") and "cross_attn" in v:
            v = dict(v, cross_attn=dict(
                v["cross_attn"], k_proj=replicate(v["cross_attn"]["k_proj"]),
                v_proj=replicate(v["cross_attn"]["v_proj"])))
        out[k] = v
    return out


def kv_pools_shardable(mesh, kv_heads: int, tensor: str = "model") -> bool:
    """True iff the K/V pools can split their heads over ``tensor``."""
    tp = tp_degree(mesh, tensor)
    return tp > 1 and kv_heads > 0 and kv_heads % tp == 0


def decode_state_specs(state: Any, *, kv_heads: int, head_dim: int,
                       shard_kv: bool, tensor: str = "model"):
    """PartitionSpec tree matching ``state`` (pools on heads, rest replicated).

    Leaves are recognised structurally — every head-carrying array in a
    decode state is rank-5 ``(..., HKV, dh)`` and every quant scale is a
    rank-4 float ``(..., HKV)``; nothing else in the state has those
    trailing dims.
    """
    def spec(x):
        if not shard_kv:
            return P()
        shape = getattr(x, "shape", ())
        if len(shape) == 5 and shape[-2] == kv_heads and shape[-1] == head_dim:
            return P(None, None, None, tensor, None)
        if (len(shape) == 4 and shape[-1] == kv_heads
                and np.issubdtype(np.dtype(x.dtype), np.floating)):
            return P(None, None, None, tensor)
        return P()

    return jax.tree_util.tree_map(spec, state)


def decode_state_shardings(state: Any, mesh, *, kv_heads: int, head_dim: int,
                           tensor: str = "model"):
    """NamedSharding tree for ``jax.device_put(state, ...)`` on ``mesh``."""
    specs = decode_state_specs(
        state, kv_heads=kv_heads, head_dim=head_dim,
        shard_kv=kv_pools_shardable(mesh, kv_heads, tensor), tensor=tensor)
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), specs,
        is_leaf=lambda s: isinstance(s, P))
