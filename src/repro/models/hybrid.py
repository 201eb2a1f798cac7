"""zamba2-style hybrid: Mamba2 backbone + a SHARED attention block applied
every ``attn_every`` layers.

The shared block has ONE set of parameters reused at each application
(zamba2's signature trick), but each application needs its own KV cache at
decode time — caches are stacked (n_apps, B, S, H, dh).

long_500k runs through this model: the Mamba2 state is O(1) in context, and
only the 9 shared-attention caches scale with sequence (sharded over the
"data" mesh axis there).
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.calibration import Taps
from repro.core.ptq import FP_CONTEXT, QuantContext
from repro.distributed.context import constrain
from repro.models import kv_cache as kvc
from repro.models.attention import attention, attention_init
from repro.models.ffn import ffn, ffn_init
from repro.models.layers import embed, embedding_init, norm, norm_init, unembed
from repro.models.ssm import SSMState, ssm_block, ssm_decode_step, ssm_init


class HybridLM:
    def __init__(self, cfg):
        self.cfg = cfg
        self.n_apps = cfg.n_layers // cfg.hybrid.attn_every

    # ------------------------------------------------------------------ init
    def init(self, key) -> Dict[str, Any]:
        cfg = self.cfg
        k_e, k_m, k_a, k_f = jax.random.split(key, 4)
        params: Dict[str, Any] = {
            "embed": embedding_init(k_e, cfg.vocab, cfg.d_model),
            "final_norm": norm_init(cfg.d_model, cfg.norm),
            "shared": {
                "attn_norm": norm_init(cfg.d_model, cfg.norm),
                "attn": attention_init(k_a, cfg),
                "ffn_norm": norm_init(cfg.d_model, cfg.norm),
                "ffn": ffn_init(k_f, cfg),
            },
        }
        if cfg.scan_layers:
            params["mamba"] = ssm_init(k_m, cfg, stack=(cfg.n_layers,))
        else:
            keys = jax.random.split(k_m, cfg.n_layers)
            for i in range(cfg.n_layers):
                params[f"mamba.{i}"] = ssm_init(keys[i], cfg)
        return params

    def _shared_block(self, params, x, *, quant, taps, positions, kv_lengths,
                      unroll, cache_view=None):
        cfg = self.cfg
        sp = params["shared"]
        h = norm(sp["attn_norm"], x, cfg.norm)
        a, entries = attention(sp["attn"], h, cfg=cfg, site="shared/attn",
                               quant=quant, taps=taps, positions=positions,
                               kv_lengths=kv_lengths, cache=cache_view,
                               unroll=unroll)
        x = x + a
        h = norm(sp["ffn_norm"], x, cfg.norm)
        x = x + ffn(sp["ffn"], h, cfg=cfg, site="shared/ffn", quant=quant,
                    taps=taps)
        return x, entries

    # --------------------------------------------------------------- forward
    def forward(self, params, batch, *, quant: QuantContext = FP_CONTEXT,
                taps: Optional[Taps] = None, unroll: bool = False
                ) -> Tuple[jax.Array, Dict]:
        cfg = self.cfg
        every = cfg.hybrid.attn_every
        x = embed(params["embed"], batch["tokens"], cfg.activation_dtype)
        B, S, _ = x.shape
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
        lengths = batch.get("lengths")

        if cfg.scan_layers:
            grouped = jax.tree_util.tree_map(
                lambda a: a.reshape(self.n_apps, every, *a.shape[1:]),
                params["mamba"])

            def group_fn(x, gparams):
                def inner(x, bp):
                    f = lambda xx: xx + ssm_block(bp, xx, cfg=cfg,
                                                  site="blocks.*/mamba",
                                                  quant=quant, taps=taps,
                                                  unroll=unroll)[0]
                    if cfg.remat:
                        f = jax.checkpoint(f)
                    return f(constrain(x)), None
                x, _ = jax.lax.scan(inner, x, gparams)
                g = lambda xx: self._shared_block(
                    params, xx, quant=quant, taps=taps, positions=positions,
                    kv_lengths=lengths, unroll=unroll)[0]
                if cfg.remat:
                    g = jax.checkpoint(g)
                return g(x), None

            x, _ = jax.lax.scan(group_fn, x, grouped)
        else:
            for i in range(cfg.n_layers):
                y, _ = ssm_block(params[f"mamba.{i}"], x, cfg=cfg,
                                 site=f"blocks.{i}/mamba", quant=quant,
                                 taps=taps, unroll=unroll)
                x = x + y
                if (i + 1) % every == 0:
                    x, _ = self._shared_block(params, x, quant=quant,
                                              taps=taps, positions=positions,
                                              kv_lengths=lengths,
                                              unroll=unroll)

        x = norm(params["final_norm"], x, cfg.norm)
        return unembed(params["embed"], x), {}

    # ---------------------------------------------------------------- decode
    def init_decode_state(self, batch: int, max_len: int, *,
                          quantized: bool) -> Dict[str, Any]:
        cfg = self.cfg
        s = cfg.ssm
        d_inner = s.expand * cfg.d_model
        H = d_inner // s.head_dim
        ssm_states = SSMState(
            h=jnp.zeros((cfg.n_layers, batch, H, s.state, s.head_dim),
                        jnp.float32),
            conv=jnp.zeros((cfg.n_layers, batch, s.conv_width - 1, d_inner),
                           cfg.activation_dtype),
        )
        cache = kvc.init_cache(self.n_apps, batch, max_len, cfg.n_kv_heads,
                               cfg.hd, quantized=quantized,
                               dtype=cfg.activation_dtype)
        return {"ssm": ssm_states, "cache": cache}

    def prefill(self, params, batch, state, *,
                quant: QuantContext = FP_CONTEXT) -> Tuple[jax.Array, Dict]:
        cfg = self.cfg
        every = cfg.hybrid.attn_every
        x = embed(params["embed"], batch["tokens"], cfg.activation_dtype)
        B, S, _ = x.shape
        lengths = batch.get("lengths",
                            jnp.full((B,), S, jnp.int32))
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
        cache = state["cache"]
        quantized = cache.quantized

        def entries_out(entries):
            k, v = entries
            if quantized:
                kq, kss_ = kvc.quantize_kv(k)
                vq, vss_ = kvc.quantize_kv(v)
                return kq, vq, kss_, vss_
            return (k.astype(cache.k.dtype), v.astype(cache.v.dtype),
                    jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32))

        if cfg.scan_layers:
            grouped = jax.tree_util.tree_map(
                lambda a: a.reshape(self.n_apps, every, *a.shape[1:]),
                params["mamba"])

            def group(x, gparams):
                def inner(x, bp):
                    y, st = ssm_block(bp, x, cfg=cfg, site="blocks.*/mamba",
                                      quant=quant, taps=None,
                                      return_state=True)
                    return x + y, st
                x, states = jax.lax.scan(inner, x, gparams)
                x, entries = self._shared_block(
                    params, x, quant=quant, taps=None, positions=positions,
                    kv_lengths=lengths, unroll=False)
                return x, (states, *entries_out(entries))

            x, (states, ks, vs, kss, vss) = jax.lax.scan(group, x, grouped)
            new_ssm = jax.tree_util.tree_map(
                lambda a: a.reshape(cfg.n_layers, *a.shape[2:]), states)
        else:
            h_list, conv_list = [], []
            k_list, v_list, ks_list, vs_list = [], [], [], []
            for i in range(cfg.n_layers):
                y, st = ssm_block(params[f"mamba.{i}"], x, cfg=cfg,
                                  site=f"blocks.{i}/mamba", quant=quant,
                                  taps=None, return_state=True)
                x = x + y
                h_list.append(st.h)
                conv_list.append(st.conv)
                if (i + 1) % every == 0:
                    x, entries = self._shared_block(
                        params, x, quant=quant, taps=None,
                        positions=positions, kv_lengths=lengths,
                        unroll=False)
                    o = entries_out(entries)
                    k_list.append(o[0]); v_list.append(o[1])
                    ks_list.append(o[2]); vs_list.append(o[3])
            ks, vs = jnp.stack(k_list), jnp.stack(v_list)
            kss, vss = jnp.stack(ks_list), jnp.stack(vs_list)
            new_ssm = SSMState(h=jnp.stack(h_list),
                               conv=jnp.stack(conv_list))
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
        state["ssm"] = new_ssm

        x = norm(params["final_norm"], x, cfg.norm)
        idx = jnp.maximum(lengths - 1, 0)
        x_last = x[jnp.arange(B), idx]
        return unembed(params["embed"], x_last[:, None, :])[:, 0], state

    def decode_step(self, params, tokens, state, *,
                    quant: QuantContext = FP_CONTEXT) -> Tuple[jax.Array, Dict]:
        cfg = self.cfg
        every = cfg.hybrid.attn_every
        cache = state["cache"]
        ssm = state["ssm"]
        x = embed(params["embed"], tokens[:, None], cfg.activation_dtype)

        def shared_with_cache(x, pools, app):
            """The shared attention block at application ``app``; ``pools``
            are the stacked (k, v, k_scale, v_scale), written in place at
            ``app`` and handed back."""
            view = kvc.LayerCacheView(*pools, lengths=cache.lengths,
                                      layer=app)
            return self._shared_block(
                params, x, quant=quant, taps=None, positions=None,
                kv_lengths=None, unroll=False, cache_view=view)

        pools = (cache.k, cache.v, cache.k_scale, cache.v_scale)
        if cfg.scan_layers:
            grouped = jax.tree_util.tree_map(
                lambda a: a.reshape(self.n_apps, every, *a.shape[1:]),
                params["mamba"])
            gidx = jnp.arange(self.n_apps, dtype=jnp.int32)

            def group(carry, xs):
                x, h_all, conv_all, pools = carry
                gparams, gi = xs

                def inner(icarry, ys):
                    x, h_all, conv_all = icarry
                    bp, j = ys
                    li = gi * every + j
                    st = SSMState(
                        h=jax.lax.dynamic_index_in_dim(h_all, li, 0, False),
                        conv=jax.lax.dynamic_index_in_dim(conv_all, li, 0,
                                                          False))
                    y, st2 = ssm_decode_step(bp, x, st, cfg=cfg,
                                             site="blocks.*/mamba",
                                             quant=quant)
                    h_all = jax.lax.dynamic_update_index_in_dim(
                        h_all, st2.h, li, 0)
                    conv_all = jax.lax.dynamic_update_index_in_dim(
                        conv_all, st2.conv, li, 0)
                    return (x + y, h_all, conv_all), None

                (x, h_all, conv_all), _ = jax.lax.scan(
                    inner, (x, h_all, conv_all),
                    (gparams, jnp.arange(every, dtype=jnp.int32)))

                x, pools = shared_with_cache(x, pools, gi)
                return (x, h_all, conv_all, pools), None

            (x, h_all, conv_all, pools), _ = jax.lax.scan(
                group, (x, ssm.h, ssm.conv, pools), (grouped, gidx))
            new_ssm = SSMState(h=h_all, conv=conv_all)
        else:
            h_list, conv_list = [], []
            app = 0
            for i in range(cfg.n_layers):
                st = SSMState(h=ssm.h[i], conv=ssm.conv[i])
                y, st2 = ssm_decode_step(params[f"mamba.{i}"], x, st, cfg=cfg,
                                         site=f"blocks.{i}/mamba", quant=quant)
                x = x + y
                h_list.append(st2.h); conv_list.append(st2.conv)
                if (i + 1) % every == 0:
                    x, pools = shared_with_cache(x, pools, app)
                    app += 1
            new_ssm = SSMState(h=jnp.stack(h_list), conv=jnp.stack(conv_list))
        k_c, v_c, ks_c, vs_c = pools

        state = dict(state)
        state["ssm"] = new_ssm
        state["cache"] = kvc.KVCache(k=k_c, v=v_c, k_scale=ks_c,
                                     v_scale=vs_c, lengths=cache.lengths + 1)
        x = norm(params["final_norm"], x, cfg.norm)
        return unembed(params["embed"], x)[:, 0], state
