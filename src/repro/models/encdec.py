"""Encoder-decoder transformer — whisper-base backbone and the paper's own
Transformer NMT model (Vaswani base).

Structure mirrors the paper's workload: encoder (bidirectional self-attn),
auto-regressive decoder (causal self-attn + cross-attn), the decoder
while-loop being where the paper's GatherNd/batching optimizations live.

Cross-attention K/V are computed once from the encoder memory and cached —
with INT8 cache quantization they are quantized *once* per request
(the cheapest possible activation quantization site).

Inputs: ``src_tokens`` (B, S_enc) or ``src_embeds`` (B, S_enc, D) for the
audio stub; ``tgt_tokens`` (B, S_dec) for teacher forcing.
"""

from __future__ import annotations

import math
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


def sinusoidal_positions(S: int, D: int, dtype) -> jax.Array:
    pos = jnp.arange(S, dtype=jnp.float32)[:, None]
    dim = jnp.arange(0, D, 2, dtype=jnp.float32)[None, :]
    angle = pos / jnp.power(10000.0, dim / D)
    pe = jnp.zeros((S, D), jnp.float32)
    pe = pe.at[:, 0::2].set(jnp.sin(angle))
    pe = pe.at[:, 1::2].set(jnp.cos(angle))
    return pe.astype(dtype)


class EncDecLM:
    def __init__(self, cfg):
        self.cfg = cfg

    # ------------------------------------------------------------------ init
    def _enc_block_init(self, key, stack=()):
        cfg = self.cfg
        k1, k2 = jax.random.split(key)
        return {
            "attn_norm": norm_init(cfg.d_model, cfg.norm, stack=stack),
            "attn": attention_init(k1, cfg, stack=stack),
            "ffn_norm": norm_init(cfg.d_model, cfg.norm, stack=stack),
            "ffn": ffn_init(k2, cfg, stack=stack),
        }

    def _dec_block_init(self, key, stack=()):
        cfg = self.cfg
        k1, k2, k3 = jax.random.split(key, 3)
        return {
            "self_norm": norm_init(cfg.d_model, cfg.norm, stack=stack),
            "self_attn": attention_init(k1, cfg, stack=stack),
            "cross_norm": norm_init(cfg.d_model, cfg.norm, stack=stack),
            "cross_attn": attention_init(k2, cfg, stack=stack),
            "ffn_norm": norm_init(cfg.d_model, cfg.norm, stack=stack),
            "ffn": ffn_init(k3, cfg, stack=stack),
        }

    def init(self, key) -> Dict[str, Any]:
        cfg = self.cfg
        n_enc, n_dec = cfg.n_enc_layers, cfg.n_layers
        keys = jax.random.split(key, n_enc + n_dec + 3)
        params: Dict[str, Any] = {
            "embed": embedding_init(keys[0], cfg.vocab, cfg.d_model),
            "enc_final_norm": norm_init(cfg.d_model, cfg.norm),
            "dec_final_norm": norm_init(cfg.d_model, cfg.norm),
        }
        if cfg.scan_layers:
            params["enc_blocks"] = self._enc_block_init(keys[1],
                                                        stack=(n_enc,))
            params["dec_blocks"] = self._dec_block_init(keys[2],
                                                        stack=(n_dec,))
        else:
            for i in range(n_enc):
                params[f"enc_blocks.{i}"] = self._enc_block_init(keys[1 + i])
            for i in range(n_dec):
                params[f"dec_blocks.{i}"] = self._dec_block_init(
                    keys[1 + n_enc + i])
        return params

    # ---------------------------------------------------------------- encode
    def encode(self, params, batch, *, quant: QuantContext = FP_CONTEXT,
               taps: Optional[Taps] = None, unroll: bool = False) -> jax.Array:
        with jax.named_scope("encoder"):
            cfg = self.cfg
            dt = cfg.activation_dtype
            if "src_embeds" in batch:
                x = batch["src_embeds"].astype(dt)
            else:
                x = embed(params["embed"], batch["src_tokens"], dt)
                x = x * math.sqrt(cfg.d_model)
            B, S, D = x.shape
            x = x + sinusoidal_positions(S, D, dt)[None]
            lengths = batch.get("src_lengths")

            def block(x, bparams, site):
                with jax.named_scope("self_attention"):
                    h = norm(bparams["attn_norm"], x, cfg.norm)
                    a, _ = attention(bparams["attn"], h, cfg=cfg,
                                     site=f"{site}/attn", quant=quant,
                                     taps=taps, causal=False, rope=False,
                                     kv_lengths=lengths, unroll=unroll)
                    x = x + a
                with jax.named_scope("ffn"):
                    h = norm(bparams["ffn_norm"], x, cfg.norm)
                    return x + ffn(bparams["ffn"], h, cfg=cfg,
                                   site=f"{site}/ffn", quant=quant, taps=taps)

            if cfg.scan_layers:
                def layer(x, bp):
                    f = lambda xx: block(xx, bp, "enc_blocks.*")
                    if cfg.remat:
                        f = jax.checkpoint(f)
                    return f(constrain(x)), None
                x, _ = jax.lax.scan(layer, x, params["enc_blocks"])
            else:
                for i in range(cfg.n_enc_layers):
                    x = block(x, params[f"enc_blocks.{i}"], f"enc_blocks.{i}")
            return norm(params["enc_final_norm"], x, cfg.norm)

    # ---------------------------------------------------------------- decode
    def _dec_block(self, bparams, x, memory, *, site, quant, taps, positions,
                   kv_lengths, memory_lengths, unroll, cache_view=None):
        cfg = self.cfg
        with jax.named_scope("self_attention"):
            h = norm(bparams["self_norm"], x, cfg.norm)
            a, entries = attention(
                bparams["self_attn"], h, cfg=cfg, site=f"{site}/self_attn",
                quant=quant, taps=taps, positions=positions,
                kv_lengths=kv_lengths, cache=cache_view, rope=False,
                unroll=unroll)
            x = x + a
        with jax.named_scope("cross_attention"):
            h = norm(bparams["cross_norm"], x, cfg.norm)
            c, _ = attention(
                bparams["cross_attn"], h, cfg=cfg, site=f"{site}/cross_attn",
                quant=quant, taps=taps, memory=memory,
                memory_lengths=memory_lengths, unroll=unroll,
                per_query=cache_view is not None)
            x = x + c
        with jax.named_scope("ffn"):
            h = norm(bparams["ffn_norm"], x, cfg.norm)
            f = ffn(bparams["ffn"], h, cfg=cfg, site=f"{site}/ffn",
                    quant=quant, taps=taps)
            return x + f, entries

    def _cross_kv(self, bparams, memory, *, site, quant, taps):
        """Project encoder memory to this layer's cross K/V (done once)."""
        cfg = self.cfg
        B, S, _ = memory.shape
        from repro.models.layers import dense  # local import to avoid cycle
        k = dense(bparams["cross_attn"]["k_proj"], memory,
                  site=f"{site}/cross_attn/k_proj", quant=quant,
                  taps=taps).reshape(B, S, cfg.n_kv_heads, cfg.hd)
        v = dense(bparams["cross_attn"]["v_proj"], memory,
                  site=f"{site}/cross_attn/v_proj", quant=quant,
                  taps=taps).reshape(B, S, cfg.n_kv_heads, cfg.hd)
        return k, v

    def forward(self, params, batch, *, quant: QuantContext = FP_CONTEXT,
                taps: Optional[Taps] = None, unroll: bool = False
                ) -> Tuple[jax.Array, Dict]:
        """Teacher-forced training forward: returns decoder logits."""
        cfg = self.cfg
        dt = cfg.activation_dtype
        memory = self.encode(params, batch, quant=quant, taps=taps,
                             unroll=unroll)
        mem_lengths = batch.get("src_lengths")

        x = embed(params["embed"], batch["tgt_tokens"], dt)
        x = x * math.sqrt(cfg.d_model)
        B, S, D = x.shape
        x = x + sinusoidal_positions(S, D, dt)[None]
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
        tgt_lengths = batch.get("tgt_lengths")

        def block(x, bparams, site):
            kv = self._cross_kv(bparams, memory, site=site, quant=quant,
                                taps=taps)
            y, _ = self._dec_block(bparams, x, kv, site=site, quant=quant,
                                   taps=taps, positions=positions,
                                   kv_lengths=tgt_lengths,
                                   memory_lengths=mem_lengths, unroll=unroll)
            return y

        if cfg.scan_layers:
            def layer(x, bp):
                f = lambda xx: block(xx, bp, "dec_blocks.*")
                if cfg.remat:
                    f = jax.checkpoint(f)
                return f(constrain(x)), None
            x, _ = jax.lax.scan(layer, x, params["dec_blocks"])
        else:
            for i in range(cfg.n_layers):
                x = block(x, params[f"dec_blocks.{i}"], f"dec_blocks.{i}")

        x = norm(params["dec_final_norm"], x, cfg.norm)
        logits = unembed(params["embed"], x)
        return logits, {}

    # ------------------------------------------------------- serving states
    def init_decode_state(self, batch: int, max_len: int, *,
                          quantized: bool,
                          enc_len: Optional[int] = None,
                          paged: bool = False,
                          page_size: int = 16,
                          n_pages: Optional[int] = None) -> Dict[str, Any]:
        """``enc_len``: pre-allocate cross K/V buffers of that length (used
        by the dry-run to lower serve_step without running prefill).

        ``paged=True`` backs the self-attention cache with a page pool +
        block tables (``kv_cache.PagedKVCache``) instead of contiguous
        rows; rows own no pages until :meth:`splice_prefill` assigns a
        reservation.  ``n_pages`` bounds the pool (default: contiguous-
        equivalent capacity).
        """
        cfg = self.cfg
        if paged:
            cache = kvc.init_paged_cache(
                cfg.n_layers, batch, max_len, cfg.n_kv_heads, cfg.hd,
                page_size=page_size, n_pages=n_pages, quantized=quantized,
                dtype=cfg.activation_dtype)
        else:
            cache = kvc.init_cache(cfg.n_layers, batch, max_len,
                                   cfg.n_kv_heads, cfg.hd,
                                   quantized=quantized,
                                   dtype=cfg.activation_dtype)
        state: Dict[str, Any] = {
            "cache": cache,
            "cross_k": None, "cross_v": None, "src_lengths": None,
        }
        if enc_len is not None:
            shape = (cfg.n_layers, batch, enc_len, cfg.n_kv_heads, cfg.hd)
            state["cross_k"] = jnp.zeros(shape, cfg.activation_dtype)
            state["cross_v"] = jnp.zeros(shape, cfg.activation_dtype)
            state["src_lengths"] = jnp.full((batch,), enc_len, jnp.int32)
        return state

    def encode_cross_kv(self, params, batch, *,
                        quant: QuantContext = FP_CONTEXT
                        ) -> Tuple[jax.Array, jax.Array, jax.Array]:
        """Encode-once front half of :meth:`prefill`.

        Runs the encoder and projects every decoder layer's cross K/V from
        the memory — the part of prefill whose cost scales with the source
        length.  Returns ``(cross_k, cross_v, src_lengths)`` with
        ``cross_k``/``cross_v`` layer-major ``(L, B, S_enc, HKV, dh)``.

        Split out so the continuous-serving engine can (a) call it *inside*
        the fused decode-burst program (admissions ride the burst dispatch)
        and (b) encode each admitted source exactly once, broadcasting the
        result across a beam group's rows via :meth:`splice_prefill`
        instead of paying ``beam×`` encoder FLOPs on tiled inputs.
        """
        memory = self.encode(params, batch, quant=quant)
        B = memory.shape[0]
        src_lengths = batch.get(
            "src_lengths", jnp.full((B,), memory.shape[1], jnp.int32))
        ck, cv = self._cross_kv_stack(params, memory, quant=quant)
        return ck, cv, src_lengths

    def _cross_kv_stack(self, params, memory, *, quant: QuantContext
                        ) -> Tuple[jax.Array, jax.Array]:
        """Every decoder layer's cross K/V from the encoder memory,
        layer-major ``(L, B, S_enc, HKV, dh)``: encoder work, done once
        per source."""
        cfg = self.cfg
        with jax.named_scope("encoder"):
            if cfg.scan_layers:
                def layer(_, bp):
                    k, v = self._cross_kv(bp, memory, site="dec_blocks.*",
                                          quant=quant, taps=None)
                    return None, (k, v)
                _, (ck, cv) = jax.lax.scan(layer, None, params["dec_blocks"])
            else:
                ks, vs = [], []
                for i in range(cfg.n_layers):
                    k, v = self._cross_kv(params[f"dec_blocks.{i}"], memory,
                                          site=f"dec_blocks.{i}", quant=quant,
                                          taps=None)
                    ks.append(k); vs.append(v)
                ck, cv = jnp.stack(ks), jnp.stack(vs)
            return ck, cv

    # ----------------------------------------------- staged (chunked) encode
    # The encoder is bidirectional (every layer attends over the full
    # source), so a long source cannot be prefilled token-chunk by
    # token-chunk the way a causal decoder stack can.  What *can* be
    # split across serving rounds is depth: embed once, then run one
    # encoder layer per round, then project cross K/V and splice.  Each
    # stage is a small dispatch riding alongside the decode burst, so a
    # long source adds at most one layer of encoder work per round
    # instead of monopolizing a whole fused-admission round.  The three
    # functions below are exact restatements of :meth:`encode` +
    # :meth:`encode_cross_kv` (same op sequence, same quant sites), so a
    # staged prefill is bit-identical to the monolithic one.

    def encode_staged_begin(self, params, batch) -> jax.Array:
        """Embedding + position half of :meth:`encode`; returns ``x``."""
        cfg = self.cfg
        dt = cfg.activation_dtype
        if "src_embeds" in batch:
            x = batch["src_embeds"].astype(dt)
        else:
            x = embed(params["embed"], batch["src_tokens"], dt)
            x = x * math.sqrt(cfg.d_model)
        B, S, D = x.shape
        return x + sinusoidal_positions(S, D, dt)[None]

    def encode_staged_layer(self, params, x: jax.Array, layer_idx: int, *,
                            src_lengths: Optional[jax.Array] = None,
                            quant: QuantContext = FP_CONTEXT) -> jax.Array:
        """One encoder layer of :meth:`encode` (``layer_idx`` static)."""
        cfg = self.cfg
        if cfg.scan_layers:
            bparams = jax.tree_util.tree_map(lambda p: p[layer_idx],
                                             params["enc_blocks"])
            site = "enc_blocks.*"
        else:
            bparams = params[f"enc_blocks.{layer_idx}"]
            site = f"enc_blocks.{layer_idx}"
        h = norm(bparams["attn_norm"], x, cfg.norm)
        a, _ = attention(bparams["attn"], h, cfg=cfg, site=f"{site}/attn",
                         quant=quant, taps=None, causal=False, rope=False,
                         kv_lengths=src_lengths, unroll=False)
        x = x + a
        h = norm(bparams["ffn_norm"], x, cfg.norm)
        return x + ffn(bparams["ffn"], h, cfg=cfg, site=f"{site}/ffn",
                       quant=quant, taps=None)

    def encode_staged_finish(self, params, x: jax.Array, *,
                             src_lengths: Optional[jax.Array] = None,
                             quant: QuantContext = FP_CONTEXT
                             ) -> Tuple[jax.Array, jax.Array, jax.Array]:
        """Final norm + cross-K/V projections (back half of
        :meth:`encode_cross_kv`); returns ``(ck, cv, src_lengths)``."""
        cfg = self.cfg
        memory = norm(params["enc_final_norm"], x, cfg.norm)
        B = memory.shape[0]
        if src_lengths is None:
            src_lengths = jnp.full((B,), memory.shape[1], jnp.int32)
        ck, cv = self._cross_kv_stack(params, memory, quant=quant)
        return ck, cv, jnp.asarray(src_lengths, jnp.int32)

    def splice_prefill(self, state: Dict[str, Any], cross_k: jax.Array,
                       cross_v: jax.Array, src_lengths: jax.Array,
                       base_rows: jax.Array, *, group: int = 1,
                       pages: Optional[jax.Array] = None) -> Dict[str, Any]:
        """Broadcast-splice an :meth:`encode_cross_kv` result into decode
        state rows — jit-callable, so the serving engine can run it inside
        the fused burst program.

        ``base_rows``: (B_sub,) destination rows, one per encoded source;
        with ``group > 1`` each source is broadcast to ``group`` contiguous
        rows ``[base, base + group)`` (a beam group shares one encoded
        memory).  Out-of-range bases are padding and dropped whole-group by
        jax scatter semantics.  The self-attention KV rows are *not*
        copied: their cursors are reset to 0, which masks every stale
        position exactly (attention masks with a hard ``where``), so the
        next decode step on a spliced row is bit-identical to a step on a
        freshly initialised side batch.

        Paged cache: ``pages`` (len(rows), maxP) carries each spliced
        row's page reservation (sentinel-padded); the rows' block tables
        and ``own_pages`` are installed alongside the cursor reset
        (``kv_cache.assign_pages``) — still no payload copy.
        """
        rows = kvc.group_rows(jnp.asarray(base_rows, jnp.int32), group)
        if group > 1:
            cross_k = jnp.repeat(cross_k, group, axis=1)
            cross_v = jnp.repeat(cross_v, group, axis=1)
            src_lengths = jnp.repeat(src_lengths, group, axis=0)
        out = dict(state)
        out["cross_k"] = state["cross_k"].at[:, rows].set(
            cross_k.astype(state["cross_k"].dtype), mode="drop")
        out["cross_v"] = state["cross_v"].at[:, rows].set(
            cross_v.astype(state["cross_v"].dtype), mode="drop")
        out["src_lengths"] = state["src_lengths"].at[rows].set(
            src_lengths.astype(jnp.int32), mode="drop")
        cache = state["cache"]
        if isinstance(cache, kvc.PagedKVCache):
            if pages is None:
                raise ValueError("paged splice_prefill needs the spliced "
                                 "rows' page reservations")
            out["cache"] = kvc.assign_pages(cache, rows, pages)
        else:
            out["cache"] = kvc.KVCache(
                k=cache.k, v=cache.v, k_scale=cache.k_scale,
                v_scale=cache.v_scale,
                lengths=cache.lengths.at[rows].set(0, mode="drop"))
        return out

    def prefill(self, params, batch, state, *,
                quant: QuantContext = FP_CONTEXT) -> Tuple[jax.Array, Dict]:
        """Encode source; compute+cache per-layer cross K/V; emit BOS logits.

        Composition of :meth:`encode_cross_kv` and the BOS decode step —
        the fused-admission serving path calls the two halves itself (with
        :meth:`splice_prefill` in between) inside its burst program.
        """
        ck, cv, src_lengths = self.encode_cross_kv(params, batch,
                                                   quant=quant)
        state = dict(state)
        state["cross_k"], state["cross_v"] = ck, cv
        state["src_lengths"] = src_lengths
        bos = jnp.zeros((ck.shape[1],), jnp.int32)
        return self.decode_step(params, bos, state, quant=quant)

    def decode_step(self, params, tokens, state, *,
                    quant: QuantContext = FP_CONTEXT) -> Tuple[jax.Array, Dict]:
        """Single-token decode: ``tokens`` (B,) → (logits (B, V), state)."""
        logits, state = self.decode_step_multi(params, tokens[:, None], state,
                                               quant=quant)
        return logits[:, 0], state

    def decode_step_multi(self, params, tokens, state, *,
                          quant: QuantContext = FP_CONTEXT
                          ) -> Tuple[jax.Array, Dict]:
        """Decode ``T`` consecutive positions per row in one pass.

        ``tokens``: (B, T) — position t of row b is embedded at cursor
        ``lengths[b] + t`` and causally masked to its own prefix, so the
        returned logits (B, T, V) match T sequential :meth:`decode_step`
        calls bit-for-bit (same kernels per query — see ``attention``).
        The cache advances by T.  This is the speculative-decoding verify
        primitive; ``decode_step`` is the T == 1 wrapper.
        """
        cfg = self.cfg
        dt = cfg.activation_dtype
        cache = state["cache"]
        B, T = tokens.shape
        x = embed(params["embed"], tokens, dt) * math.sqrt(cfg.d_model)
        pe = sinusoidal_positions(cache.capacity, cfg.d_model, dt)
        # clamp explicitly: inside a decode burst (lax.while_loop in the
        # serving engine) finished rows keep stepping past their cursor;
        # their reads must stay in bounds (outputs are EOS-masked anyway)
        pos = jnp.minimum(cache.lengths[:, None]
                          + jnp.arange(T, dtype=jnp.int32)[None, :],
                          cache.capacity - 1)
        x = x + pe[pos]

        paged = isinstance(cache, kvc.PagedKVCache)
        tables = cache.block_tables if paged else None

        def block_with_cache(x, bparams, kl, vl, ksl, vsl, ck, cv, site):
            view = kvc.LayerCacheView(k=kl, v=vl, k_scale=ksl, v_scale=vsl,
                                      lengths=cache.lengths,
                                      block_tables=tables)
            y, entries = self._dec_block(
                bparams, x, (ck, cv), site=site, quant=quant, taps=None,
                positions=None, kv_lengths=None,
                memory_lengths=state["src_lengths"], unroll=False,
                cache_view=view)
            return y, entries

        if cfg.scan_layers:
            # full self-cache in the scan carry (single live copy — see
            # transformer.py); cross K/V are read-only xs.
            idx = jnp.arange(cfg.n_layers, dtype=jnp.int32)
            quantized = cache.quantized

            def layer(carry, xs):
                x, kc, vc, ksc, vsc = carry
                bp, ck, cv, li = xs
                with jax.named_scope("kv_pool"):
                    kl = jax.lax.dynamic_index_in_dim(kc, li, 0,
                                                      keepdims=False)
                    vl = jax.lax.dynamic_index_in_dim(vc, li, 0,
                                                      keepdims=False)
                    ksl = (jax.lax.dynamic_index_in_dim(ksc, li, 0,
                                                        keepdims=False)
                           if quantized else None)
                    vsl = (jax.lax.dynamic_index_in_dim(vsc, li, 0,
                                                        keepdims=False)
                           if quantized else None)
                x, e = block_with_cache(x, bp, kl, vl, ksl, vsl, ck, cv,
                                        "dec_blocks.*")
                with jax.named_scope("kv_pool"):
                    kc = jax.lax.dynamic_update_index_in_dim(kc, e[0], li, 0)
                    vc = jax.lax.dynamic_update_index_in_dim(vc, e[1], li, 0)
                    if quantized:
                        ksc = jax.lax.dynamic_update_index_in_dim(
                            ksc, e[2], li, 0)
                        vsc = jax.lax.dynamic_update_index_in_dim(
                            vsc, e[3], li, 0)
                return (x, kc, vc, ksc, vsc), None

            init = (x, cache.k, cache.v,
                    cache.k_scale if quantized else jnp.zeros((), x.dtype),
                    cache.v_scale if quantized else jnp.zeros((), x.dtype))
            (x, k_c, v_c, ks_c, vs_c), _ = jax.lax.scan(
                layer, init,
                (params["dec_blocks"], state["cross_k"], state["cross_v"],
                 idx))
            if not quantized:
                ks_c = vs_c = None
        else:
            kL, vL, ksL, vsL = [], [], [], []
            for i in range(cfg.n_layers):
                with jax.named_scope("kv_pool"):
                    kl, vl = cache.k[i], cache.v[i]
                    ksl = cache.k_scale[i] if cache.quantized else None
                    vsl = cache.v_scale[i] if cache.quantized else None
                with jax.named_scope("cross_attention"):
                    ck, cv = state["cross_k"][i], state["cross_v"][i]
                x, e = block_with_cache(x, params[f"dec_blocks.{i}"], kl, vl,
                                        ksl, vsl, ck, cv, f"dec_blocks.{i}")
                kL.append(e[0]); vL.append(e[1])
                ksL.append(e[2]); vsL.append(e[3])
            with jax.named_scope("kv_pool"):
                k_c, v_c = jnp.stack(kL), jnp.stack(vL)
                ks_c = jnp.stack(ksL) if cache.quantized else None
                vs_c = jnp.stack(vsL) if cache.quantized else None

        state = dict(state)
        if paged:
            state["cache"] = kvc.PagedKVCache(
                k=k_c, v=v_c, k_scale=ks_c, v_scale=vs_c,
                block_tables=cache.block_tables, own_pages=cache.own_pages,
                lengths=cache.lengths + T)
        else:
            state["cache"] = kvc.KVCache(k=k_c, v=v_c, k_scale=ks_c,
                                         v_scale=vs_c,
                                         lengths=cache.lengths + T)
        with jax.named_scope("logits_head"):
            x = norm(params["dec_final_norm"], x, cfg.norm)
            return unembed(params["embed"], x), state
