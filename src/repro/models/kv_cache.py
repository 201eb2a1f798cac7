"""KV cache with optional INT8 payload (paper §5.3, TPU-adapted).

The paper found the decoder while-loop's GatherNd (beam-search cache
reordering) dominated by memory copies and quantized it for a 3.8× copy-size
reduction.  On TPU the same traffic appears twice per decode step:

* every attention read streams the whole cache from HBM, and
* beam reordering gathers it along the batch axis.

Keeping the cache int8 (per-token per-head symmetric scales, computed when
the token is appended — one cheap amax over head_dim) cuts both 4× vs f32.

Ragged batches: sequences in a decode batch may have different lengths.
Appends scatter each sequence's new token at its own ``lengths[b]`` cursor,
so token-sorted (but not exactly equal-length) batches — the paper's §5.4
input pipeline — decode correctly.
"""

from __future__ import annotations

import dataclasses
from typing import List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp

from repro.kernels import ops

INT8_MAX = 127.0
_EPS = 1e-12


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class KVCache:
    """Fixed-capacity cache for one attention stack (layers stacked).

    ``k``/``v``: (L, B, S_max, HKV, dh) int8 or activation dtype.
    ``k_scale``/``v_scale``: (L, B, S_max, HKV) f32, or None (fp cache).
    ``lengths``: (B,) int32 valid lengths / per-sequence write cursors.
    """

    k: jax.Array
    v: jax.Array
    k_scale: Optional[jax.Array]
    v_scale: Optional[jax.Array]
    lengths: jax.Array

    def tree_flatten(self):
        return ((self.k, self.v, self.k_scale, self.v_scale, self.lengths),
                None)

    @classmethod
    def tree_unflatten(cls, aux, leaves):
        return cls(*leaves)

    @property
    def quantized(self) -> bool:
        return self.k_scale is not None

    @property
    def capacity(self) -> int:
        return self.k.shape[2]

    def nbytes(self) -> int:
        n = self.k.size * self.k.dtype.itemsize * 2
        if self.quantized:
            n += self.k_scale.size * 4 * 2
        return int(n)


def init_cache(n_layers: int, batch: int, max_len: int, n_kv: int, dh: int,
               *, quantized: bool, dtype=jnp.bfloat16) -> KVCache:
    shape = (n_layers, batch, max_len, n_kv, dh)
    if quantized:
        k = jnp.zeros(shape, jnp.int8)
        v = jnp.zeros(shape, jnp.int8)
        ks = jnp.zeros(shape[:-1], jnp.float32)
        vs = jnp.zeros(shape[:-1], jnp.float32)
    else:
        k = jnp.zeros(shape, dtype)
        v = jnp.zeros(shape, dtype)
        ks = vs = None
    return KVCache(k=k, v=v, k_scale=ks, v_scale=vs,
                   lengths=jnp.zeros((batch,), jnp.int32))


def quantize_kv(x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Per-token per-head symmetric quantization: (…, dh) → int8 + scale."""
    amax = jnp.maximum(jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1), _EPS)
    scale = amax / INT8_MAX
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale[..., None]),
                 -INT8_MAX, INT8_MAX).astype(jnp.int8)
    return q, scale


@dataclasses.dataclass(frozen=True)
class LayerCacheView:
    """One layer of a stacked cache, as consumed by attention.

    The arrays stay stacked, so a decode step never materialises a layer:
    appends scatter at ``layer`` and the decode kernels read the stack by
    it.  Contiguous cache: ``k``/``v`` are (L, B, S, HKV, dh) rows.  Paged
    cache: ``k``/``v`` are the (L, P, ps, HKV, dh) page pools and
    ``block_tables`` (B, maxP) maps rows to pages (None ⇔ contiguous).
    ``layer`` is a Python int in an unrolled layer loop and an int32
    scalar in a scanned one.
    """

    k: jax.Array            # (L, B, S, HKV, dh) or (L, P, ps, HKV, dh) paged
    v: jax.Array
    k_scale: Optional[jax.Array]
    v_scale: Optional[jax.Array]
    lengths: jax.Array      # (B,)
    layer: Union[int, jax.Array]
    block_tables: Optional[jax.Array] = None      # (B, maxP) when paged


def fill_prefix(
    k_cache: jax.Array,                  # (B, S_max, HKV, dh)
    v_cache: jax.Array,
    ks_cache: Optional[jax.Array],
    vs_cache: Optional[jax.Array],
    k_new: jax.Array,                    # (B, T, HKV, dh) fp — prefill block
    v_new: jax.Array,
):
    """Write the prefill's K/V at positions [0, T) (right-padded batches)."""
    if ks_cache is not None:
        kq, ks = quantize_kv(k_new)
        vq, vs = quantize_kv(v_new)
        k_cache = jax.lax.dynamic_update_slice_in_dim(k_cache, kq, 0, 1)
        v_cache = jax.lax.dynamic_update_slice_in_dim(v_cache, vq, 0, 1)
        ks_cache = jax.lax.dynamic_update_slice_in_dim(ks_cache, ks, 0, 1)
        vs_cache = jax.lax.dynamic_update_slice_in_dim(vs_cache, vs, 0, 1)
    else:
        k_cache = jax.lax.dynamic_update_slice_in_dim(
            k_cache, k_new.astype(k_cache.dtype), 0, 1)
        v_cache = jax.lax.dynamic_update_slice_in_dim(
            v_cache, v_new.astype(v_cache.dtype), 0, 1)
    return k_cache, v_cache, ks_cache, vs_cache


def append_token(
    k_cache: jax.Array,                  # (L, B, S_max, HKV, dh)
    v_cache: jax.Array,
    ks_cache: Optional[jax.Array],       # (L, B, S_max, HKV)
    vs_cache: Optional[jax.Array],
    k_new: jax.Array,                    # (B, 1, HKV, dh) fp
    v_new: jax.Array,
    lengths: jax.Array,                  # (B,) per-sequence cursors
    layer,                               # int or int32 scalar
):
    """Scatter one new token per sequence at its own cursor (ragged decode),
    into layer ``layer`` of the stacked cache, in place.

    ``mode="drop"`` is load-bearing for decode bursts: rows that finished
    mid-burst keep stepping with cursors at/past capacity until the burst
    edge, and their writes must vanish rather than clamp onto the last
    valid position (which could corrupt a still-live neighbour of a
    shared-capacity cache on backends where clamping is the default).
    """
    b_idx = jnp.arange(k_cache.shape[1])
    return _scatter(k_cache, v_cache, ks_cache, vs_cache, k_new[:, 0],
                    v_new[:, 0], (layer, b_idx, lengths))


def append_tokens(
    k_cache: jax.Array,                  # (L, B, S_max, HKV, dh)
    v_cache: jax.Array,
    ks_cache: Optional[jax.Array],       # (L, B, S_max, HKV)
    vs_cache: Optional[jax.Array],
    k_new: jax.Array,                    # (B, T, HKV, dh) fp
    v_new: jax.Array,
    lengths: jax.Array,                  # (B,) per-sequence cursors
    layer,                               # int or int32 scalar
):
    """Scatter ``T`` consecutive tokens per row starting at its cursor.

    The speculative-decode verify pass appends a whole drafted window at
    once: row b's token t lands at position ``lengths[b] + t``.  Positions
    are distinct within a row so there are no scatter collisions, and the
    same ``mode="drop"`` contract as :func:`append_token` applies — any
    position at/past capacity writes nowhere.
    """
    B, T = k_new.shape[0], k_new.shape[1]
    b_idx = jnp.arange(B)[:, None]
    pos = lengths[:, None] + jnp.arange(T, dtype=lengths.dtype)[None, :]
    return _scatter(k_cache, v_cache, ks_cache, vs_cache, k_new, v_new,
                    (layer, b_idx, pos))


def _scatter(k_cache, v_cache, ks_cache, vs_cache, k_new, v_new, at):
    """Write new K/V (quantized when the cache has scales) at index ``at``
    of the stacked caches; out-of-range indices write nowhere."""
    put = lambda c, x: c.at[at].set(x.astype(c.dtype), mode="drop")
    if ks_cache is None:
        return put(k_cache, k_new), put(v_cache, v_new), None, None
    kq, ks = quantize_kv(k_new)
    vq, vs = quantize_kv(v_new)
    return (put(k_cache, kq), put(v_cache, vq), put(ks_cache, ks),
            put(vs_cache, vs))


def insert_at_slots(cache: KVCache, sub: KVCache,
                    slots: jax.Array) -> KVCache:
    """Scatter ``sub``'s batch rows into ``slots`` of the running cache.

    The continuous-batching engine (``serving/engine.py``) prefills newly
    admitted requests as a small side batch and splices the resulting rows
    into the long-lived decode cache mid-flight, so a finished sequence's
    slot is refilled instead of idling until the batch drains.

    ``slots``: (B_sub,) int32 destination rows, unique.  Works for both FP
    and INT8 caches (both sides must agree); out-of-range slot indices are
    dropped (jax scatter semantics), which the engine uses to pad admission
    groups to a fixed compile-stable width.
    """
    if cache.quantized != sub.quantized:
        raise ValueError("cannot mix quantized and fp caches "
                         f"(main quantized={cache.quantized}, "
                         f"sub quantized={sub.quantized})")
    if cache.capacity != sub.capacity:
        raise ValueError(f"capacity mismatch: {cache.capacity} vs "
                         f"{sub.capacity}")
    slots = jnp.asarray(slots, jnp.int32)
    put = lambda main, part: (None if main is None
                              else main.at[:, slots].set(
                                  part.astype(main.dtype)))
    return KVCache(
        k=put(cache.k, sub.k), v=put(cache.v, sub.v),
        k_scale=put(cache.k_scale, sub.k_scale),
        v_scale=put(cache.v_scale, sub.v_scale),
        lengths=cache.lengths.at[slots].set(sub.lengths),
    )


def free_slots(cache: KVCache, slots: jax.Array) -> KVCache:
    """Mark ``slots`` empty by resetting their write cursors to zero.

    The payload is left in place — every read (attention, gathers) is
    masked by ``lengths``, and the next ``insert_at_slots`` overwrites the
    rows wholesale — so eviction is a (B,)-sized scatter, not a cache copy.
    """
    slots = jnp.asarray(slots, jnp.int32)
    return KVCache(
        k=cache.k, v=cache.v, k_scale=cache.k_scale, v_scale=cache.v_scale,
        lengths=cache.lengths.at[slots].set(0),
    )


def free_inactive(cache: KVCache, live: jax.Array) -> KVCache:
    """Mask-driven ``free_slots`` for use *inside* a jitted burst program.

    ``live``: (B,) bool — rows whose cursor must be preserved.  Every other
    row (finished since the last admission, or never occupied) gets its
    write cursor reset to 0, exactly what the host-dispatched
    ``free_slots`` did between bursts before admissions were fused into
    the burst program.  Payload untouched — reads are length-masked and
    the next ``splice_prefill``/``insert_at_slots`` overwrites the rows.
    """
    return KVCache(
        k=cache.k, v=cache.v, k_scale=cache.k_scale, v_scale=cache.v_scale,
        lengths=jnp.where(live, cache.lengths, 0),
    )


def with_lengths(cache, lengths: jax.Array):
    """Replace the write cursors of a :class:`KVCache`/:class:`PagedKVCache`.

    Speculative decoding rolls rejected draft positions back by resetting
    cursors — the payload past the cursor is junk by contract (reads are
    length-masked, later writes overwrite), so rollback is cursor-only.
    """
    return dataclasses.replace(cache, lengths=lengths)


def group_rows(base_slots: jax.Array, group: int) -> jax.Array:
    """Expand group base rows to the strided row set they own.

    ``base_slots``: (G,) int32 group base rows (multiples of ``group`` for
    in-range entries) → (G * group,) row indices ``base + [0, group)``.
    An out-of-range sentinel base (≥ the cache batch) expands to ``group``
    out-of-range rows, so the padding convention of ``insert_at_slots``
    (OOB rows are dropped by jax scatter semantics) carries over to whole
    groups.
    """
    base = jnp.asarray(base_slots, jnp.int32)
    return (base[:, None] + jnp.arange(group, dtype=jnp.int32)[None, :]
            ).reshape(-1)


def insert_at_groups(cache: KVCache, sub: KVCache, base_slots: jax.Array,
                     group: int) -> KVCache:
    """Group-strided ``insert_at_slots``: splice whole beam groups.

    ``sub`` holds ``len(base_slots) * group`` batch rows — ``group``
    contiguous rows per admitted request — scattered into rows
    ``[base, base + group)`` of each base slot.  Works for FP and INT8
    caches exactly like ``insert_at_slots`` (it is one).
    """
    return insert_at_slots(cache, sub, group_rows(base_slots, group))


def free_groups(cache: KVCache, base_slots: jax.Array, group: int) -> KVCache:
    """Group-strided ``free_slots``: a finishing beam group frees all
    ``group`` of its rows atomically (cursor reset only — see
    ``free_slots`` for why no payload copy happens)."""
    return free_slots(cache, group_rows(base_slots, group))


def gather_beams(cache: KVCache, beam_idx: jax.Array) -> KVCache:
    """Beam-search cache reorder along batch — the paper's GatherNd.

    ``beam_idx``: (B,) int32 source rows.  On an int8 cache this moves 4×
    fewer bytes than f32 (2× vs bf16); ``benchmarks/bench_kv_gather.py``
    measures exactly this op.  The paged cache
    (:func:`gather_beams_paged`) takes the same optimization to its
    logical endpoint: the payload stops moving entirely.
    """
    take = lambda a: jnp.take(a, beam_idx, axis=1) if a is not None else None
    return KVCache(
        k=take(cache.k), v=take(cache.v),
        k_scale=take(cache.k_scale), v_scale=take(cache.v_scale),
        lengths=jnp.take(cache.lengths, beam_idx, axis=0),
    )


# ---------------------------------------------------------------------------
# paged cache: fixed-size pages + per-row block tables
# ---------------------------------------------------------------------------
#
# The contiguous cache above reserves a full (S_max,) row per decode slot and
# beam-reorders by moving the whole slab.  The paged cache stores tokens in
# fixed-size pages shared by all rows; each row sees its sequence through a
# block table of page ids.  Consequences:
#
# * beam reorder = permuting (B, maxP) int32 block-table rows plus one
#   partial-page copy per row (the page currently being written) — the
#   payload slab never moves, which is the logical endpoint of the paper's
#   §5.3 copy-size optimization (INT8 shrank the gather 4×; paging makes it
#   ~S_max/page_size smaller again, independent of dtype);
# * HBM is reserved per *request* (ceil(budget / page_size) pages per live
#   row) instead of per grid row, so short-budget requests stop paying for
#   S_max capacity and a fixed pool admits more concurrent rows;
# * freeing is returning page ids to a free list — fragmentation cannot
#   exist, which is what unlocks mixed beam widths per request.
#
# Sentinel convention: the page id ``n_pages`` (one past the pool) marks an
# unreserved block-table slot.  Every payload write goes through
# ``mode="drop"`` scatters, so a row stepping past its reservation (finished
# rows keep stepping until the burst edge) writes nowhere; reads clamp into
# the pool and are masked by ``lengths``.

@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class PagedKVCache:
    """Paged cache for one attention stack (layers stacked).

    ``k``/``v``: (L, n_pages, page_size, HKV, dh) int8 or activation dtype.
    ``k_scale``/``v_scale``: (L, n_pages, page_size, HKV) f32 or None.
    ``block_tables``: (B, max_pages) int32 — row r's logical view: token
    position p lives in page ``block_tables[r, p // page_size]`` at offset
    ``p % page_size``.  After a beam reorder, early (read-only) entries may
    point into a sibling row's pages; the entry for the *next write slot*
    always points into ``own_pages`` (see :func:`gather_beams_paged`).
    ``own_pages``: (B, max_pages) int32 — the pages physically reserved for
    row r (never permuted by beam reorders; sentinel past the reservation).
    ``lengths``: (B,) int32 valid lengths / per-row write cursors.
    """

    k: jax.Array
    v: jax.Array
    k_scale: Optional[jax.Array]
    v_scale: Optional[jax.Array]
    block_tables: jax.Array
    own_pages: jax.Array
    lengths: jax.Array

    def tree_flatten(self):
        return ((self.k, self.v, self.k_scale, self.v_scale,
                 self.block_tables, self.own_pages, self.lengths), None)

    @classmethod
    def tree_unflatten(cls, aux, leaves):
        return cls(*leaves)

    @property
    def quantized(self) -> bool:
        return self.k_scale is not None

    @property
    def n_pages(self) -> int:
        return self.k.shape[1]

    @property
    def page_size(self) -> int:
        return self.k.shape[2]

    @property
    def max_pages(self) -> int:
        return self.block_tables.shape[1]

    @property
    def capacity(self) -> int:
        """Logical row capacity in tokens (same contract as ``KVCache``)."""
        return self.max_pages * self.page_size

    def nbytes(self) -> int:
        n = self.k.size * self.k.dtype.itemsize * 2
        if self.quantized:
            n += self.k_scale.size * 4 * 2
        n += (self.block_tables.size + self.own_pages.size) * 4
        return int(n)

    def reorder_bytes_per_step(self) -> int:
        """Bytes a beam reorder moves per decode step: the block-table /
        length permutation plus one partial-page payload copy per row —
        compare ``KVCache.nbytes()``, which :func:`gather_beams` moves."""
        L, _, ps, HKV, dh = self.k.shape
        B = self.block_tables.shape[0]
        page = L * B * ps * HKV * dh * self.k.dtype.itemsize * 2
        if self.quantized:
            page += L * B * ps * HKV * 4 * 2
        return int(page + self.block_tables.size * 4 + B * 4)


def pages_per_row(n_tokens: int, page_size: int) -> int:
    """Pages needed to hold ``n_tokens`` cache positions (≥ 1)."""
    return max((int(n_tokens) + page_size - 1) // page_size, 1)


def init_paged_cache(n_layers: int, batch: int, max_len: int, n_kv: int,
                     dh: int, *, page_size: int, n_pages: Optional[int] = None,
                     quantized: bool, dtype=jnp.bfloat16) -> PagedKVCache:
    """Pool of ``n_pages`` pages + empty (all-sentinel) block tables.

    ``max_len`` must be a page multiple (the engine validates) so the
    linearized paged view has exactly the contiguous cache's shape — that
    shape equality is what makes the paged path bit-identical to the
    unpaged one.  ``n_pages`` defaults to full contiguous-equivalent
    capacity (``batch × max_pages``); serving configs pass less and admit
    against the page budget instead.
    """
    if max_len % page_size:
        raise ValueError(f"max_len={max_len} must be a multiple of "
                         f"page_size={page_size}")
    max_pages = max_len // page_size
    if n_pages is None:
        n_pages = batch * max_pages
    shape = (n_layers, n_pages, page_size, n_kv, dh)
    if quantized:
        k = jnp.zeros(shape, jnp.int8)
        v = jnp.zeros(shape, jnp.int8)
        ks = jnp.zeros(shape[:-1], jnp.float32)
        vs = jnp.zeros(shape[:-1], jnp.float32)
    else:
        k = jnp.zeros(shape, dtype)
        v = jnp.zeros(shape, dtype)
        ks = vs = None
    # two distinct buffers (donation-safe: the serving engine donates the
    # whole decode state, and one buffer may not be donated twice)
    tables = jnp.full((batch, max_pages), n_pages, jnp.int32)
    own = jnp.full((batch, max_pages), n_pages, jnp.int32)
    return PagedKVCache(k=k, v=v, k_scale=ks, v_scale=vs,
                        block_tables=tables, own_pages=own,
                        lengths=jnp.zeros((batch,), jnp.int32))


def append_token_paged(
    k_pages: jax.Array,                  # (L, P, ps, HKV, dh) stacked pools
    v_pages: jax.Array,
    ks_pages: Optional[jax.Array],       # (L, P, ps, HKV)
    vs_pages: Optional[jax.Array],
    block_tables: jax.Array,             # (B, maxP) int32
    k_new: jax.Array,                    # (B, 1, HKV, dh) fp
    v_new: jax.Array,
    lengths: jax.Array,                  # (B,) per-row cursors
    layer,                               # int or int32 scalar
    *,
    impl: str = "xla",                   # kernel path of the scale writes
):
    """Paged ``append_token``: scatter one token per row at its cursor,
    into layer ``layer`` of the stacked pools, in place.

    The destination page comes from the block table; rows whose cursor is
    past capacity, or whose table entry is the unreserved sentinel, drop
    the write (same ``mode="drop"`` contract as the contiguous append —
    finished rows keep stepping inside a burst and must write nowhere).
    """
    P, ps = k_pages.shape[1], k_pages.shape[2]
    maxP = block_tables.shape[1]
    b_idx = jnp.arange(block_tables.shape[0])
    slot = lengths // ps
    off = lengths % ps
    entry = block_tables[b_idx, jnp.minimum(slot, maxP - 1)]
    page = jnp.where(slot < maxP, entry, P)          # past capacity → drop
    return _append_paged(k_pages, v_pages, ks_pages, vs_pages, k_new[:, 0],
                         v_new[:, 0], layer, page, off, impl)


def append_tokens_paged(
    k_pages: jax.Array,                  # (L, P, ps, HKV, dh) stacked pools
    v_pages: jax.Array,
    ks_pages: Optional[jax.Array],       # (L, P, ps, HKV)
    vs_pages: Optional[jax.Array],
    block_tables: jax.Array,             # (B, maxP) int32
    k_new: jax.Array,                    # (B, T, HKV, dh) fp
    v_new: jax.Array,
    lengths: jax.Array,                  # (B,) per-row cursors
    layer,                               # int or int32 scalar
    *,
    impl: str = "xla",                   # kernel path of the scale writes
):
    """Paged :func:`append_tokens`: T consecutive tokens per row.

    Row b's token t targets position ``lengths[b] + t``; its page comes
    from the block table, sentinel/past-capacity positions drop (same
    contract as :func:`append_token_paged`).  Positions are distinct
    within a row, so no two writes collide on a (page, offset) pair.
    """
    P, ps = k_pages.shape[1], k_pages.shape[2]
    maxP = block_tables.shape[1]
    B, T = k_new.shape[0], k_new.shape[1]
    b_idx = jnp.arange(B)[:, None]
    pos = lengths[:, None] + jnp.arange(T, dtype=lengths.dtype)[None, :]
    slot = pos // ps
    off = pos % ps
    entry = block_tables[b_idx, jnp.minimum(slot, maxP - 1)]
    page = jnp.where(slot < maxP, entry, P)          # past capacity → drop
    return _append_paged(k_pages, v_pages, ks_pages, vs_pages, k_new, v_new,
                         layer, page, off, impl)


def _append_paged(k_pages, v_pages, ks_pages, vs_pages, k_new, v_new, layer,
                  page, off, impl):
    """Write new tokens (rows of ``k_new``/``v_new``, one per ``page`` and
    ``off`` entry) at ``[layer, page, off]`` of the stacked pools, in
    place; a page past the pool writes nowhere.  The scales of an INT8
    pool go through ``ops.append_scales_paged`` (see ``kernels/kv_pool``)
    so that they stay in the layout the paged decode kernel reads."""
    at = (layer, page, off)
    if ks_pages is None:
        return _scatter(k_pages, v_pages, None, None, k_new, v_new, at)
    kq, ks = quantize_kv(k_new)
    vq, vs = quantize_kv(v_new)
    k_pages, v_pages, _, _ = _scatter(k_pages, v_pages, None, None, kq, vq,
                                      at)
    HKV = ks.shape[-1]
    ks_pages, vs_pages = ops.append_scales_paged(
        ks_pages, vs_pages, ks.reshape(-1, HKV), vs.reshape(-1, HKV),
        page.reshape(-1), off.reshape(-1), layer, impl=impl)
    return k_pages, v_pages, ks_pages, vs_pages


def linearize_pages(pages: jax.Array, block_tables: jax.Array) -> jax.Array:
    """Gather one layer's paged payload into the contiguous row view.

    ``pages``: (P, ps, …) → (B, maxP·ps, …).  Sentinel entries clamp into
    the pool and read garbage — every consumer masks by ``lengths``.  This
    is the XLA fallback read path; the Pallas kernel walks the table
    per-block instead and never materializes this view.
    """
    P = pages.shape[0]
    B, maxP = block_tables.shape
    got = pages[jnp.clip(block_tables, 0, P - 1)]    # (B, maxP, ps, …)
    return got.reshape((B, maxP * pages.shape[1]) + pages.shape[2:])


def assign_pages(cache: PagedKVCache, rows: jax.Array,
                 pages: jax.Array) -> PagedKVCache:
    """Install per-row page reservations (admission).

    ``rows``: (R,) destination rows (OOB sentinels dropped);
    ``pages``: (R, maxP) page ids, sentinel-padded past each row's
    reservation.  Both ``own_pages`` and ``block_tables`` are set — a
    freshly admitted row starts with its logical view equal to its
    physical reservation — and the cursor resets to 0.
    """
    rows = jnp.asarray(rows, jnp.int32)
    pages = jnp.asarray(pages, jnp.int32)
    return PagedKVCache(
        k=cache.k, v=cache.v, k_scale=cache.k_scale, v_scale=cache.v_scale,
        block_tables=cache.block_tables.at[rows].set(pages, mode="drop"),
        own_pages=cache.own_pages.at[rows].set(pages, mode="drop"),
        lengths=cache.lengths.at[rows].set(0, mode="drop"),
    )


def free_slots_paged(cache: PagedKVCache, slots: jax.Array) -> PagedKVCache:
    """Paged ``free_slots``: reset cursors AND sentinel the freed rows'
    tables.  The sentinel matters here (unlike the contiguous cache, where
    a dead row harmlessly scribbles inside its own slab): a freed row keeps
    stepping until refilled, and its pages may be handed to a *new* request
    — its writes must drop, not land in reallocated pages."""
    slots = jnp.asarray(slots, jnp.int32)
    sent = jnp.full((slots.shape[0], cache.max_pages), cache.n_pages,
                    jnp.int32)
    return PagedKVCache(
        k=cache.k, v=cache.v, k_scale=cache.k_scale, v_scale=cache.v_scale,
        block_tables=cache.block_tables.at[slots].set(sent, mode="drop"),
        own_pages=cache.own_pages.at[slots].set(sent, mode="drop"),
        lengths=cache.lengths.at[slots].set(0, mode="drop"),
    )


def free_inactive_paged(cache: PagedKVCache, live: jax.Array) -> PagedKVCache:
    """Mask-driven :func:`free_slots_paged` for the fused burst prologue:
    every row not in ``live`` gets cursor 0 and all-sentinel tables, so its
    pages can be reassigned by the splice that follows in the same
    program."""
    live_col = live[:, None]
    sent = jnp.int32(cache.n_pages)
    return PagedKVCache(
        k=cache.k, v=cache.v, k_scale=cache.k_scale, v_scale=cache.v_scale,
        block_tables=jnp.where(live_col, cache.block_tables, sent),
        own_pages=jnp.where(live_col, cache.own_pages, sent),
        lengths=jnp.where(live, cache.lengths, 0),
    )


def insert_rows_paged(cache: PagedKVCache, sub: KVCache, slots: jax.Array,
                      pages: jax.Array) -> PagedKVCache:
    """Splice a *contiguous* prefilled side batch into the paged cache
    (the unfused admission path: prefill runs on a plain side batch, then
    its rows are copied into the destination rows' reserved pages).

    ``sub``'s row capacity must equal the paged logical capacity
    (``maxP × ps`` — the engine guarantees ``max_len`` is a page
    multiple); each sub row is reshaped into page-sized chunks and
    scattered to ``pages`` (sentinel entries drop their chunk, so
    unreserved tails and OOB padding rows vanish).
    """
    if cache.quantized != sub.quantized:
        raise ValueError("cannot mix quantized and fp caches "
                         f"(main quantized={cache.quantized}, "
                         f"sub quantized={sub.quantized})")
    if sub.capacity != cache.capacity:
        raise ValueError(f"capacity mismatch: paged {cache.capacity} vs "
                         f"side batch {sub.capacity}")
    ps, maxP = cache.page_size, cache.max_pages
    W = sub.k.shape[1]
    ids = jnp.asarray(pages, jnp.int32).reshape(W * maxP)

    def put(pool, part):
        if pool is None:
            return None
        # (L, W, maxP·ps, …) → (L, W·maxP, ps, …) page-chunked payload
        chunks = part.reshape((part.shape[0], W * maxP, ps) + part.shape[3:])
        return pool.at[:, ids].set(chunks.astype(pool.dtype), mode="drop")

    return PagedKVCache(
        k=put(cache.k, sub.k), v=put(cache.v, sub.v),
        k_scale=put(cache.k_scale, sub.k_scale),
        v_scale=put(cache.v_scale, sub.v_scale),
        block_tables=cache.block_tables.at[slots].set(
            jnp.asarray(pages, jnp.int32), mode="drop"),
        own_pages=cache.own_pages.at[slots].set(
            jnp.asarray(pages, jnp.int32), mode="drop"),
        lengths=cache.lengths.at[slots].set(sub.lengths, mode="drop"),
    )


def cow_write_slot(cache: PagedKVCache, *, impl: str = "xla"
                   ) -> PagedKVCache:
    """Copy-on-write for each row's *current write slot* page.

    For every row, copy the page its block table currently maps for the
    next write position into the row's privately-owned page for that slot
    (``own_pages``) and repoint the table entry there.  After this, the
    next ``append_token_paged`` is guaranteed to land in a page the row
    owns exclusively — it never writes into a page another row (or a
    cached prefix chain with refcount > 1) also maps.

    Rows whose table entry already points at their own page copy a page
    onto itself (a no-op on content); rows whose own slot is the
    unreserved sentinel drop the copy (``mode="drop"``).  This is the
    primitive behind the zero-copy beam reorder (see
    :func:`gather_beams_paged`) and the copy-on-write contract of shared
    prefix pages: shared pages are only ever *read* through block tables,
    and any row about to write through a shared mapping first diverts the
    write slot into its own reservation here.  The scale pools of an INT8
    cache are copied by ``ops.copy_scale_pages`` on ``impl`` (see
    ``kernels/kv_pool``).
    """
    P, ps, maxP = cache.n_pages, cache.page_size, cache.max_pages
    B = cache.block_tables.shape[0]
    b_idx = jnp.arange(B)
    sp = jnp.minimum(cache.lengths // ps, maxP - 1)  # next write slot
    src_page = jnp.clip(cache.block_tables[b_idx, sp], 0, P - 1)
    dst_page = cache.own_pages[b_idx, sp]            # sentinel → copy drops

    def cow(pool):
        payload = jnp.take(pool, src_page, axis=1)   # (L, B, ps, …)
        return pool.at[:, dst_page].set(payload, mode="drop")

    ks, vs = cache.k_scale, cache.v_scale
    if cache.quantized:
        ks, vs = ops.copy_scale_pages(ks, vs, src_page, dst_page, impl=impl)
    return PagedKVCache(
        k=cow(cache.k), v=cow(cache.v), k_scale=ks, v_scale=vs,
        block_tables=cache.block_tables.at[b_idx, sp].set(dst_page),
        own_pages=cache.own_pages,                   # physical, never moves
        lengths=cache.lengths,
    )


def gather_beams_paged(cache: PagedKVCache, beam_idx: jax.Array, *,
                       impl: str = "xla") -> PagedKVCache:
    """Zero-copy beam reorder: permute block tables, not payload.

    The contiguous :func:`gather_beams` moves the whole (L, B, S, HKV, dh)
    slab every beam step; here the reorder is

    1. gather the (B, maxP) block tables and (B,) cursors by ``beam_idx``
       (int32 index traffic only);
    2. :func:`cow_write_slot`: copy the source lineage's *current partial
       page* into the destination row's own page for that slot and point
       the table entry there — so the next append (which lands in that
       slot) writes into a page the row owns privately, never into a page
       a sibling also writes.

    Invariant maintained: at append time, the table entry for the slot
    being written always comes from ``own_pages`` — fresh admissions set
    the whole table to ``own_pages`` and every reorder re-establishes it
    for the next write slot.  Full (read-only) pages stay shared between
    beams; sharing is always intra-group, and a group's rows are freed
    atomically, so no refcounting is needed on device.
    """
    return cow_write_slot(PagedKVCache(
        k=cache.k, v=cache.v, k_scale=cache.k_scale, v_scale=cache.v_scale,
        block_tables=jnp.take(cache.block_tables, beam_idx, axis=0),
        own_pages=cache.own_pages,
        lengths=jnp.take(cache.lengths, beam_idx, axis=0),
    ), impl=impl)


# ---------------------------------------------------------------------------
# prefix-chain pools: page-granular storage for cached cross-attention K/V
# ---------------------------------------------------------------------------
#
# The prefix cache (serving/prefix_cache.py) stores each cached source's
# encoded cross-attention K/V as a chain of fixed-size pages in a dedicated
# pool of shape (L, n_pages, page_size, HKV, dh), kept in the activation
# dtype (never re-quantized: a cached read must be bit-identical to a fresh
# encode).  These two helpers are the only device ops it needs: scatter a
# freshly encoded batch into reserved chains, and gather chains back into
# the (L, B, S, HKV, dh) layout that ``splice_prefill`` consumes.

def insert_chain_pages(pool: jax.Array, part: jax.Array,
                       pages: jax.Array) -> jax.Array:
    """Scatter per-row payload into reserved page chains.

    ``pool``: (L, P, ps, …); ``part``: (L, B, S, …); ``pages``: (B, nP)
    int32 with ``nP = ceil(S / ps)`` — sentinel entries (≥ P) drop their
    chunk, so padding rows write nowhere.
    """
    L, B, S = part.shape[0], part.shape[1], part.shape[2]
    ps = pool.shape[2]
    nP = pages.shape[1]
    pad = nP * ps - S
    if pad:
        part = jnp.pad(part, [(0, 0), (0, 0), (0, pad)]
                       + [(0, 0)] * (part.ndim - 3))
    chunks = part.reshape((L, B * nP, ps) + part.shape[3:])
    ids = jnp.asarray(pages, jnp.int32).reshape(B * nP)
    return pool.at[:, ids].set(chunks.astype(pool.dtype), mode="drop")


def gather_chain_pages(pool: jax.Array, pages: jax.Array,
                       seq_len: int) -> jax.Array:
    """Read page chains back as contiguous rows.

    ``pages``: (B, nP) int32 → (L, B, seq_len, …).  Sentinel entries clamp
    into the pool and read garbage past each chain's valid span — callers
    mask by source length exactly as they would a fresh encode's padding.
    """
    P = pool.shape[1]
    B, nP = pages.shape
    got = pool[:, jnp.clip(pages, 0, P - 1)]         # (L, B, nP, ps, …)
    got = got.reshape((pool.shape[0], B, nP * pool.shape[2]) + pool.shape[3:])
    return got[:, :, :seq_len]


class PageAllocator:
    """Host-side page pool: free list + refcounts + high-water mark.

    The scheduler reserves ``pages_per_row(budget) × live rows`` pages at
    admission and returns them at release, so admission is gated by real
    HBM instead of contiguous row capacity.  Refcounts support shared
    reservations (``retain``): the prefix cache hash-conses page chains
    across requests, so counts > 1 are real — the chain's tree entry holds
    one reference and every request currently reading it holds another.
    Decode reservations stay exclusive (sharing there happens on device,
    strictly inside beam groups that free atomically).

    Every mutating call validates its *entire* argument first and only
    then mutates, so a bad call (double free, retain of a free page,
    duplicate page ids whose combined drop exceeds the refcount) raises
    without changing any state — callers can treat errors as atomic.
    """

    def __init__(self, n_pages: int, page_size: int, *,
                 overcommit_limit: float = 1.0):
        if n_pages < 1 or page_size < 1:
            raise ValueError(f"bad pool: n_pages={n_pages}, "
                             f"page_size={page_size}")
        if overcommit_limit < 1.0:
            raise ValueError(
                f"overcommit_limit={overcommit_limit} must be >= 1.0")
        self.n_pages = int(n_pages)
        self.page_size = int(page_size)
        self.overcommit_limit = float(overcommit_limit)
        self._free = list(range(self.n_pages - 1, -1, -1))   # pop() = page 0
        self._refcount = [0] * self.n_pages
        self.hwm = 0
        self.free_lwm = self.n_pages      # low-water mark of the free list
        self.reserved = 0                 # virtual worst-case reservations
        self.spilled = 0                  # pages' worth of KV held on host

    @property
    def n_free(self) -> int:
        return len(self._free)

    @property
    def in_use(self) -> int:
        return self.n_pages - len(self._free)

    def pages_for_tokens(self, n_tokens: int) -> int:
        return pages_per_row(n_tokens, self.page_size)

    def _check(self, pages: Sequence[int]) -> None:
        for p in pages:
            if not 0 <= p < self.n_pages:
                raise ValueError(f"page id {p} outside pool "
                                 f"[0, {self.n_pages})")

    def alloc(self, n: int) -> Optional[List[int]]:
        """Take ``n`` pages (refcount 1 each) or None if the pool can't.

        The free list is peeked and validated *before* any page leaves it:
        a corrupted pool (a free-listed page with a live refcount) raises
        with the free list intact rather than handing out the page.  These
        are raised exceptions, not asserts — the invariants must hold
        under ``python -O`` too, now that refcounts > 1 are real.
        """
        if n < 0:
            raise ValueError(f"cannot alloc {n} pages")
        if n > len(self._free):
            return None
        candidates = self._free[len(self._free) - n:]
        for p in candidates:
            if self._refcount[p] != 0:
                raise RuntimeError(
                    f"page {p} double-assigned: on the free list with "
                    f"refcount {self._refcount[p]}")
        del self._free[len(self._free) - n:]
        pages = list(reversed(candidates))               # pop() order
        for p in pages:
            self._refcount[p] = 1
        self.hwm = max(self.hwm, self.in_use)
        self.free_lwm = min(self.free_lwm, len(self._free))
        return pages

    # -------------------------------------------- overcommit reservations
    # ``reserve``/``unreserve`` track *virtual* worst-case page claims: the
    # scheduler reserves each admitted request's full worst case but only
    # physically allocates what the next burst needs, so the sum of
    # reservations may exceed the physical pool — up to
    # ``overcommit_limit × n_pages``.  The gap is backed by preemption
    # (spill a victim's pages to host when a physical alloc comes up
    # short), which is what makes overcommit deadlock-free.

    @property
    def reserve_cap(self) -> int:
        return int(self.overcommit_limit * self.n_pages)

    def can_reserve(self, n: int) -> bool:
        if n < 0:
            raise ValueError(f"cannot reserve {n} pages")
        return self.reserved + n <= self.reserve_cap

    def reserve(self, n: int) -> bool:
        if not self.can_reserve(n):
            return False
        self.reserved += n
        return True

    def unreserve(self, n: int) -> None:
        if n < 0 or n > self.reserved:
            raise ValueError(f"unreserve({n}) with reserved={self.reserved}")
        self.reserved -= n

    # ------------------------------------------------- spill accounting
    def spill(self, pages: Sequence[int]) -> None:
        """Release ``pages`` whose content moved to a host spill store.

        Atomic: validates exactly like :meth:`release` (it *is* a release)
        before mutating, then counts the pages as spilled so leak checks
        can demand ``spilled == 0`` after every chaos schedule.
        """
        self.release(pages)          # validate-then-mutate, may raise
        self.spilled += len(pages)

    def unspill(self, n: int) -> None:
        """Account ``n`` spilled pages' worth of KV restored on device
        (the physical pages come from a fresh :meth:`alloc`)."""
        if n < 0 or n > self.spilled:
            raise ValueError(f"unspill({n}) with spilled={self.spilled}")
        self.spilled -= n

    @property
    def fragmentation(self) -> float:
        """Free-list scatter in [0, 1]: 0 when the free pages form one
        contiguous id run, →1 as every free page sits in its own run.
        Paged serving is immune to it (any page serves any slot); the stat
        exists to show that churn *does* scatter the pool and the engine
        keeps running at full occupancy anyway."""
        if len(self._free) <= 1:
            return 0.0
        ids = sorted(self._free)
        runs = 1 + sum(1 for a, b in zip(ids, ids[1:]) if b != a + 1)
        return (runs - 1) / (len(self._free) - 1)

    def retain(self, pages: Sequence[int]) -> None:
        self._check(pages)
        for p in pages:
            if self._refcount[p] <= 0:
                raise ValueError(f"retain of unallocated page {p}")
        for p in pages:
            self._refcount[p] += 1

    def release(self, pages: Sequence[int]) -> None:
        # validate the FULL list (with multiplicity: releasing [p, p]
        # against refcount 1 is a double free) before mutating anything —
        # a partial release would leave the pool inconsistent.
        self._check(pages)
        drops: dict = {}
        for p in pages:
            drops[p] = drops.get(p, 0) + 1
        for p, n in drops.items():
            if self._refcount[p] < n:
                raise ValueError(
                    f"release of page {p} ×{n} exceeds refcount "
                    f"{self._refcount[p]} (double free)")
        for p in pages:
            self._refcount[p] -= 1
            if self._refcount[p] == 0:
                self._free.append(p)

    def refcount(self, page: int) -> int:
        return self._refcount[page]
