"""Decoder-only transformer LM (dense / MoE / VLM-backbone families).

Layer stacking:
* ``cfg.scan_layers=True`` — parameters stacked (L, …), applied with
  ``lax.scan`` (+ per-layer remat) so the HLO is depth-independent; sites
  use the layer-agnostic ``blocks.*`` names.
* ``cfg.scan_layers=False`` — per-layer dicts ``blocks.{i}`` and a python
  loop; used by calibration (per-site taps) and the smoke tests.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.calibration import Taps
from repro.core.ptq import FP_CONTEXT, QuantContext
from repro.distributed.context import constrain, tag_block_grads
from repro.models import kv_cache as kvc
from repro.models.attention import attention, attention_init
from repro.models.ffn import ffn, ffn_init
from repro.models.layers import embed, embedding_init, norm, norm_init, unembed
from repro.models.moe import moe_ffn, moe_init


class DecoderLM:
    def __init__(self, cfg):
        self.cfg = cfg

    # ------------------------------------------------------------------ init
    def _block_init(self, key, *, stack: tuple = ()):
        cfg = self.cfg
        k1, k2 = jax.random.split(key)
        block = {
            "attn_norm": norm_init(cfg.d_model, cfg.norm, stack=stack),
            "attn": attention_init(k1, cfg, stack=stack),
            "ffn_norm": norm_init(cfg.d_model, cfg.norm, stack=stack),
        }
        if cfg.moe is not None:
            block["moe"] = moe_init(k2, cfg, stack=stack)
        else:
            block["ffn"] = ffn_init(k2, cfg, stack=stack)
        return block

    def init(self, key) -> Dict[str, Any]:
        cfg = self.cfg
        keys = jax.random.split(key, cfg.n_layers + 2)
        params: Dict[str, Any] = {
            "embed": embedding_init(keys[0], cfg.vocab, cfg.d_model),
            "final_norm": norm_init(cfg.d_model, cfg.norm),
        }
        if cfg.scan_layers:
            params["blocks"] = self._block_init(keys[1],
                                                stack=(cfg.n_layers,))
        else:
            for i in range(cfg.n_layers):
                params[f"blocks.{i}"] = self._block_init(keys[i + 1])
        return params

    # --------------------------------------------------------------- forward
    def _block_apply(self, bparams, x, *, site, quant, taps, positions,
                     kv_lengths, unroll, cache_view=None):
        cfg = self.cfg
        h = norm(bparams["attn_norm"], x, cfg.norm)
        a, entries = attention(
            bparams["attn"], h, cfg=cfg, site=f"{site}/attn", quant=quant,
            taps=taps, positions=positions, kv_lengths=kv_lengths,
            cache=cache_view, unroll=unroll)
        x = x + a
        h = norm(bparams["ffn_norm"], x, cfg.norm)
        if cfg.moe is not None:
            f, aux = moe_ffn(bparams["moe"], h, cfg=cfg, site=f"{site}/moe",
                             quant=quant, taps=taps)
        else:
            f = ffn(bparams["ffn"], h, cfg=cfg, site=f"{site}/ffn",
                    quant=quant, taps=taps)
            aux = {}
        return x + f, entries, aux

    def _inputs(self, params, batch):
        cfg = self.cfg
        dt = cfg.activation_dtype
        if "embeds" in batch:
            return batch["embeds"].astype(dt)
        return embed(params["embed"], batch["tokens"], dt)

    def forward(self, params, batch, *, quant: QuantContext = FP_CONTEXT,
                taps: Optional[Taps] = None, unroll: bool = False,
                ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
        """Full-sequence forward (train / prefill-style). Returns (logits, aux)."""
        cfg = self.cfg
        x = self._inputs(params, batch)
        B, S, _ = x.shape
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
        kv_lengths = batch.get("lengths")
        aux_total = {"load_balance_loss": jnp.float32(0.0)}

        if cfg.scan_layers:
            def layer(x, bparams):
                bparams = tag_block_grads(bparams)
                f = lambda xx: self._block_apply(
                    bparams, xx, site="blocks.*", quant=quant, taps=taps,
                    positions=positions, kv_lengths=kv_lengths, unroll=unroll)
                if cfg.remat:
                    f = jax.checkpoint(f)
                # barrier: keeps XLA from batching the per-layer f32
                # upcast of every saved carry into one (L,B,S,D) f32 blob
                x, _, aux = f(jax.lax.optimization_barrier(constrain(x)))
                return x, aux.get("load_balance_loss", jnp.float32(0.0))

            x, lb = jax.lax.scan(layer, x, params["blocks"])
            aux_total["load_balance_loss"] = jnp.sum(lb)
        else:
            for i in range(cfg.n_layers):
                x, _, aux = self._block_apply(
                    params[f"blocks.{i}"], x, site=f"blocks.{i}", quant=quant,
                    taps=taps, positions=positions, kv_lengths=kv_lengths,
                    unroll=unroll)
                if "load_balance_loss" in aux:
                    aux_total["load_balance_loss"] += aux["load_balance_loss"]

        x = norm(params["final_norm"], x, cfg.norm)
        logits = unembed(params["embed"], x)
        return logits, aux_total

    # ---------------------------------------------------------------- decode
    def init_decode_state(self, batch: int, max_len: int, *,
                          quantized: bool) -> Dict[str, Any]:
        cfg = self.cfg
        return {
            "cache": kvc.init_cache(cfg.n_layers, batch, max_len,
                                    cfg.n_kv_heads, cfg.hd,
                                    quantized=quantized,
                                    dtype=cfg.activation_dtype),
        }

    def prefill(self, params, batch, state, *,
                quant: QuantContext = FP_CONTEXT) -> Tuple[jax.Array, Dict]:
        """Run the prompt, fill the cache, return last-position logits."""
        cfg = self.cfg
        x = self._inputs(params, batch)
        B, S, _ = x.shape
        lengths = batch.get("lengths")
        if lengths is None:
            lengths = jnp.full((B,), S, jnp.int32)
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
        cache = state["cache"]
        quantized = cache.quantized

        def entries_out(entries):
            """Quantize K/V inside the layer loop so the stacked per-layer
            outputs are int8 (4× smaller transients than bf16)."""
            k, v = entries
            if quantized:
                kq, ks = kvc.quantize_kv(k)
                vq, vs = kvc.quantize_kv(v)
                return kq, vq, ks, vs
            return (k.astype(cache.k.dtype), v.astype(cache.v.dtype),
                    jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32))

        if cfg.scan_layers:
            def layer(x, bparams):
                x, entries, _ = self._block_apply(
                    bparams, x, site="blocks.*", quant=quant, taps=None,
                    positions=positions, kv_lengths=lengths, unroll=False)
                return x, entries_out(entries)

            x, (ks, vs, kss, vss) = jax.lax.scan(layer, x, params["blocks"])
        else:
            outs = []
            for i in range(cfg.n_layers):
                x, entries, _ = self._block_apply(
                    params[f"blocks.{i}"], x, site=f"blocks.{i}", quant=quant,
                    taps=None, positions=positions, kv_lengths=lengths,
                    unroll=False)
                outs.append(entries_out(entries))
            ks = jnp.stack([o[0] for o in outs])
            vs = jnp.stack([o[1] for o in outs])
            kss = jnp.stack([o[2] for o in outs])
            vss = jnp.stack([o[3] for o in outs])

        # write into the (donated) cache buffers at positions [0, S)
        dus = lambda buf, new: jax.lax.dynamic_update_slice_in_dim(
            buf, new, 0, 2)
        k_c, v_c = dus(cache.k, ks), dus(cache.v, vs)
        if quantized:
            ks_c, vs_c = dus(cache.k_scale, kss), dus(cache.v_scale, vss)
        else:
            ks_c = vs_c = None
        state = dict(state)
        state["cache"] = kvc.KVCache(k=k_c, v=v_c, k_scale=ks_c,
                                     v_scale=vs_c, lengths=lengths)

        x = norm(params["final_norm"], x, cfg.norm)
        # logits at each sequence's last valid position
        idx = jnp.maximum(lengths - 1, 0)
        x_last = x[jnp.arange(B), idx]
        logits = unembed(params["embed"], x_last[:, None, :])[:, 0]
        return logits, state

    def decode_step(self, params, tokens_or_embeds, state, *,
                    quant: QuantContext = FP_CONTEXT
                    ) -> Tuple[jax.Array, Dict]:
        """One decode step. tokens: (B,) int32 (or (B,1,D) embeds)."""
        cfg = self.cfg
        cache = state["cache"]
        if tokens_or_embeds.ndim == 1:
            x = embed(params["embed"], tokens_or_embeds[:, None],
                      cfg.activation_dtype)
        else:
            x = tokens_or_embeds.astype(cfg.activation_dtype)
        B = x.shape[0]

        def block_with_cache(x, bparams, pools, layer, site):
            """One layer; ``pools`` are the stacked (k, v, k_scale,
            v_scale), written in place at ``layer`` and handed back."""
            view = kvc.LayerCacheView(*pools, lengths=cache.lengths,
                                      layer=layer)
            x, pools, _ = self._block_apply(
                bparams, x, site=site, quant=quant, taps=None,
                positions=None, kv_lengths=None, unroll=False,
                cache_view=view)
            return x, pools

        pools = (cache.k, cache.v, cache.k_scale, cache.v_scale)
        if cfg.scan_layers:
            # The stacked cache rides in the scan CARRY, so exactly one
            # copy lives — xs/ys would keep input and output caches alive
            # simultaneously (2× HBM for the dominant decode buffer).
            def layer(carry, xs):
                x, pools = carry
                bparams, li = xs
                return block_with_cache(x, bparams, pools, li,
                                        "blocks.*"), None

            idx = jnp.arange(cfg.n_layers, dtype=jnp.int32)
            (x, pools), _ = jax.lax.scan(layer, (x, pools),
                                         (params["blocks"], idx))
        else:
            for i in range(cfg.n_layers):
                x, pools = block_with_cache(x, params[f"blocks.{i}"], pools,
                                            i, f"blocks.{i}")
        k_c, v_c, ks_c, vs_c = pools

        state = dict(state)
        state["cache"] = kvc.KVCache(k=k_c, v=v_c, k_scale=ks_c,
                                     v_scale=vs_c, lengths=cache.lengths + 1)
        x = norm(params["final_norm"], x, cfg.norm)
        logits = unembed(params["embed"], x)[:, 0]
        return logits, state
