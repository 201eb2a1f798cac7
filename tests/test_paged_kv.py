"""Paged INT8 KV cache (ISSUE 5): block tables end to end.

Three layers of coverage:

* **Allocator / scheduler properties** (hypothesis-compat): no page is
  ever double-assigned, refcounts return to zero after release, freed
  requests' pages are fully reclaimed, and mixed-beam admission churn
  never deadlocks against a page budget.
* **Cache-op units**: paged append/linearize round-trips against the
  contiguous cache, the zero-copy beam reorder (`gather_beams_paged`)
  agrees logically with the slab gather, freed rows' writes drop, and the
  paged Pallas flash-decode kernel (interpret mode) matches the pure-jnp
  oracle including sentinel table entries.
* **Engine identity matrix**: `serve(paged=True)` — greedy and beam,
  beam ∈ {1, 4} and per-request mixed widths, FP and INT8 cache, fused
  and unfused admission, several burst lengths incl. ``auto`` — is
  token-identical to the unpaged engine (and therefore to per-request
  ``generate``/``generate_beam``), with every page returned by the end,
  even when the page pool is smaller than contiguous-equivalent capacity.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _hypothesis_compat import given, settings, st
from repro.configs import get_config
from repro.core import QuantPolicy, quantize_model
from repro.data import make_corpus
from repro.data.synthetic import pad_batch
from repro.kernels import ops, ref
from repro.models import build_model
from repro.models import kv_cache as kvc
from repro.serving import ContinuousScheduler, Request, ServingEngine

MAX_LEN = 32
PAGE_SIZE = 8
BUDGETS = [3, 7, 0, 5, 6, 2]
MIXED_WIDTHS = [4, 2, 1, 3, 4, 2]


# ------------------------------------------------------------------ fixtures
_CACHED = {}


def _module_state():
    if "engines" not in _CACHED:
        cfg = get_config("transformer-base").reduced(
            vocab=32, d_model=48, n_layers=1, n_enc_layers=1, d_ff=96,
            n_heads=2, n_kv_heads=2, head_dim=24)
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        qparams, qctx = quantize_model(params, {},
                                       QuantPolicy(act_quant="dynamic"))
        engines = {
            "fp": ServingEngine(model, params, max_len=MAX_LEN),
            "int8": ServingEngine(model, qparams, quant=qctx,
                                  max_len=MAX_LEN),
            "fp_paged": ServingEngine(model, params, max_len=MAX_LEN,
                                      paged=True, page_size=PAGE_SIZE),
            "int8_paged": ServingEngine(model, qparams, quant=qctx,
                                        max_len=MAX_LEN, paged=True,
                                        page_size=PAGE_SIZE),
        }
        assert engines["int8_paged"].quant.quantize_kv
        _CACHED.update(
            cfg=cfg, model=model, params=params, engines=engines,
            requests=make_corpus(len(BUDGETS), cfg.vocab, seed=11,
                                 max_words=8),
            refs={})
    return _CACHED


def _reference(quant, beam):
    """Per-request reference streams, computed once per (engine, beam)."""
    state = _module_state()
    key = (quant, tuple(beam) if isinstance(beam, list) else beam)
    if key not in state["refs"]:
        eng = state["engines"][quant]
        outs = []
        widths = beam if isinstance(beam, list) else [beam] * len(BUDGETS)
        for s, cap, b in zip(state["requests"], BUDGETS, widths):
            src, lens = pad_batch([s.src])
            if beam is None:
                res = eng.generate({"src_tokens": src, "src_lengths": lens},
                                   max_new_tokens=int(cap), burst_len=1)
            else:
                res = eng.generate_beam(
                    {"src_tokens": src, "src_lengths": lens}, beam=int(b),
                    max_new_tokens=int(cap), burst_len=1)
            outs.append(np.asarray(res.tokens[0])[:int(cap)])
        state["refs"][key] = outs
    return state["refs"][key]


# ---------------------------------------------------------------- allocator
def test_allocator_basics():
    al = kvc.PageAllocator(8, 4)
    a = al.alloc(3)
    b = al.alloc(5)
    assert sorted(a + b) == list(range(8))
    assert al.alloc(1) is None and al.n_free == 0 and al.in_use == 8
    al.release(a)
    assert al.n_free == 3 and al.hwm == 8
    c = al.alloc(2)
    assert not set(c) & set(b)          # no double assignment
    al.release(b)
    al.release(c)
    assert al.in_use == 0
    assert all(al.refcount(p) == 0 for p in range(8))


def test_allocator_refcounts():
    al = kvc.PageAllocator(4, 4)
    pages = al.alloc(2)
    al.retain(pages)                     # rc = 2
    al.release(pages)                    # rc = 1: still held
    assert al.in_use == 2
    al.release(pages)                    # rc = 0: reclaimed
    assert al.in_use == 0
    with pytest.raises(ValueError):
        al.release(pages)
    with pytest.raises(ValueError):
        al.retain(pages)


@given(st.integers(min_value=1, max_value=64),
       st.integers(min_value=0, max_value=10 ** 6))
@settings(max_examples=20, deadline=None)
def test_property_allocator_churn(n_pages, seed):
    """Random alloc/retain/release interleavings: pages are exclusive
    while held, every refcount returns to zero, the free list is exactly
    the complement of live pages, and the pool is whole at the end."""
    rng = np.random.default_rng(seed)
    al = kvc.PageAllocator(n_pages, 4)
    live = []                            # list of (pages, extra_refs)
    for _ in range(40):
        op = rng.integers(0, 3)
        if op == 0:
            n = int(rng.integers(0, n_pages + 1))
            free_before = al.n_free
            got = al.alloc(n)
            if n > free_before:
                assert got is None       # over-ask must fail, not oversell
            if got is not None:
                flat = [p for ps, _ in live for p in ps]
                assert not set(got) & set(flat)      # exclusivity
                live.append((got, 0))
        elif op == 1 and live:
            i = int(rng.integers(0, len(live)))
            al.retain(live[i][0])
            live[i] = (live[i][0], live[i][1] + 1)
        elif op == 2 and live:
            i = int(rng.integers(0, len(live)))
            pages, extra = live.pop(i)
            for _ in range(extra + 1):
                al.release(pages)
        held = sum(len(ps) for ps, _ in live)
        assert al.in_use == held and al.n_free == n_pages - held
    for pages, extra in live:
        for _ in range(extra + 1):
            al.release(pages)
    assert al.in_use == 0
    assert all(al.refcount(p) == 0 for p in range(n_pages))


@given(st.integers(min_value=1, max_value=4),
       st.integers(min_value=0, max_value=10 ** 6))
@settings(max_examples=15, deadline=None)
def test_property_mixed_beam_admission_never_deadlocks(max_beam, seed):
    """Scheduler + allocator churn with random mixed beam widths and
    budgets against a page pool: admission must always make progress
    (never wedge with work waiting and nothing running), freed requests'
    pages must be fully reclaimed, and every request finishes once."""
    rng = np.random.default_rng(seed)
    page_size = 4
    n_groups = int(rng.integers(1, 4))
    rows = max_beam * n_groups
    # pool just big enough for the worst single request, so the gate binds
    worst = max_beam * kvc.pages_per_row(16, page_size)
    n_pages = int(rng.integers(worst, 2 * worst + 1))
    al = kvc.PageAllocator(n_pages, page_size)

    def cost(req):
        return req.beam * al.pages_for_tokens(req.max_new_tokens)

    sched = ContinuousScheduler(rows, group_size=max_beam, allocator=al,
                                pages_per_request=cost)
    reqs = [Request(req_id=i, src=np.arange(3, dtype=np.int32),
                    max_new_tokens=int(rng.integers(1, 17)),
                    beam=int(rng.integers(1, max_beam + 1)))
            for i in range(int(rng.integers(1, 13)))]
    sched.submit_many(reqs)
    finishes = {r.req_id: 0 for r in reqs}
    for _ in range(10 ** 4):
        if sched.all_done:
            break
        sched.admit(0.0)
        running = list(sched.slot_map.values())
        assert running, "admission wedged with requests waiting"
        held = [p for r in running for p in r.pages]
        assert len(held) == len(set(held))           # exclusive while held
        assert al.in_use == len(held)
        k = int(rng.integers(1, len(running) + 1))
        for i in rng.choice(len(running), size=k, replace=False):
            finishes[running[int(i)].req_id] += 1
            sched.release(running[int(i)])
    assert sched.all_done
    assert all(n == 1 for n in finishes.values())
    assert al.in_use == 0                            # fully reclaimed
    assert all(al.refcount(p) == 0 for p in range(n_pages))


def test_allocator_release_is_atomic():
    """A bad release (double free, out-of-pool id, duplicate ids whose
    combined drop exceeds the refcount) raises WITHOUT mutating: the
    regression was validate-while-mutating, which returned a prefix of
    the list before raising and left the pool inconsistent."""
    al = kvc.PageAllocator(8, 4)
    a = al.alloc(3)
    b = al.alloc(2)
    al.release(b)

    def snapshot():
        return ([al.refcount(p) for p in range(8)], al.n_free, al.in_use)

    before = snapshot()
    with pytest.raises(ValueError):
        al.release(a + b)            # b already free: would drop a first
    assert snapshot() == before      # ...but must not have
    with pytest.raises(ValueError):
        al.release([a[0], a[0]])     # duplicate ids vs refcount 1
    assert snapshot() == before
    with pytest.raises(ValueError):
        al.release([a[0], 99])       # out-of-pool id after a valid one
    assert snapshot() == before
    al.release(a)                    # the valid release still works
    assert al.in_use == 0


def test_allocator_alloc_raises_on_corrupt_pool():
    """Double-assignment detection is a raised exception (not a bare
    assert that vanishes under ``python -O``), and alloc validates before
    popping so the free list survives the error."""
    al = kvc.PageAllocator(4, 4)
    with pytest.raises(ValueError):
        al.alloc(-1)
    held = al.alloc(2)
    # white-box corruption: a free-listed page with a live refcount
    victim = next(p for p in range(4) if p not in held)
    al._refcount[victim] = 1
    free_before = al.n_free
    with pytest.raises(RuntimeError):
        al.alloc(4 - len(held))
    assert al.n_free == free_before  # peek-validate: nothing left the list
    al._refcount[victim] = 0
    got = al.alloc(2)
    assert sorted(held + got) == list(range(4))


@given(st.integers(min_value=2, max_value=32),
       st.integers(min_value=0, max_value=10 ** 6))
@settings(max_examples=20, deadline=None)
def test_property_shared_reservation_churn(n_pages, seed):
    """Chains with refcounts > 1 (one owner + independent readers, the
    prefix-cache shape): random retain/release interleavings keep
    ``in_use`` equal to the pages with any live reference, never free a
    page early, and fully reclaim once every reference drops."""
    rng = np.random.default_rng(seed)
    al = kvc.PageAllocator(n_pages, 4)
    chains = []                          # (pages, n_refs) — owner + readers
    for _ in range(60):
        op = rng.integers(0, 3)
        if op == 0:
            got = al.alloc(int(rng.integers(1, n_pages + 1)))
            if got is not None:
                chains.append([got, 1])
        elif op == 1 and chains:
            c = chains[int(rng.integers(0, len(chains)))]
            al.retain(c[0])              # a reader joins
            c[1] += 1
        elif op == 2 and chains:
            i = int(rng.integers(0, len(chains)))
            chains[i][1] -= 1            # one reference drops
            al.release(chains[i][0])
            if chains[i][1] == 0:
                pages = chains.pop(i)[0]
                assert all(al.refcount(p) == 0 for p in pages)
        live = {p for c in chains for p in c[0]}
        assert al.in_use == len(live)
        for pages, refs in chains:
            assert all(al.refcount(p) == refs for p in pages)
    for pages, refs in chains:
        for _ in range(refs):
            al.release(pages)
    assert al.in_use == 0
    assert all(al.refcount(p) == 0 for p in range(n_pages))


@pytest.mark.parametrize("quantized", [False, True])
def test_cow_never_writes_shared_page(rng, quantized):
    """Copy-on-write invariant: resolving a row's write slot never writes
    a page with refcount > 1 — the shared source page's payload is
    bit-unchanged and the copy lands in the row's own reservation."""
    paged, _ = _paged_with_rows(rng, quantized=quantized, n_rows=2,
                                lengths=(6, 6))
    sentinel = paged.n_pages
    al = kvc.PageAllocator(paged.n_pages, 4)
    shared = al.alloc(2)                 # both rows read these
    own0 = al.alloc(2)                   # each row's private reservation
    own1 = al.alloc(2)
    al.retain(shared)                    # rc 2: a second reader joined
    sp = 6 // 4                          # the partial write slot
    tables = np.full((2, 4), sentinel, np.int32)
    own = np.full((2, 4), sentinel, np.int32)
    tables[0, :2] = tables[1, :2] = shared
    own[0, :2], own[1, :2] = own0, own1
    cache = kvc.PagedKVCache(
        k=paged.k, v=paged.v, k_scale=paged.k_scale, v_scale=paged.v_scale,
        block_tables=jnp.asarray(tables), own_pages=jnp.asarray(own),
        lengths=paged.lengths)
    out = kvc.cow_write_slot(cache)
    tab_after = np.asarray(out.block_tables)
    for r in range(2):
        dst = int(tab_after[r, sp])
        assert al.refcount(dst) == 1, (
            f"CoW wrote page {dst} with refcount {al.refcount(dst)}")
        assert dst == int(own[r, sp])    # the row's own reservation
    # shared page payload bit-unchanged; the copy carries its history
    src = int(tables[1, sp])
    np.testing.assert_array_equal(np.asarray(out.k[:, src]),
                                  np.asarray(cache.k[:, src]))
    np.testing.assert_array_equal(
        np.asarray(out.k[:, int(tab_after[1, sp])]),
        np.asarray(cache.k[:, src]))
    # full (pre-slot) shared pages stay shared — no copy amplification
    np.testing.assert_array_equal(tab_after[:, :sp], tables[:, :sp])


@given(st.integers(min_value=1, max_value=12),
       st.integers(min_value=0, max_value=10 ** 6))
@settings(max_examples=15, deadline=None)
def test_property_prefix_admission_never_deadlocks(pool_pages, seed):
    """Prefix-cache admission against an arbitrarily small chain pool
    always makes progress: every admit() returns hit/insert/skip (skip =
    serve uncached), eviction only touches unreferenced chains, and after
    every reader finishes + clear() the pool is fully reclaimed."""
    from repro.serving.prefix_cache import PrefixCache
    rng = np.random.default_rng(seed)
    pc = PrefixCache(kvc.PageAllocator(pool_pages, 4))
    sources = [np.asarray(rng.integers(1, 9, size=rng.integers(1, 13)),
                          np.int32) for _ in range(6)]
    open_chains = []
    for _ in range(80):
        if open_chains and rng.random() < 0.4:
            pc.finish(open_chains.pop(int(rng.integers(0,
                                                       len(open_chains)))))
            continue
        src = sources[int(rng.integers(0, len(sources)))]
        role, chain = pc.admit(src)
        assert role in ("hit", "insert", "skip")
        if role == "skip":
            assert chain is None         # uncached but never wedged
        else:
            assert chain.src_len == len(src)
            open_chains.append(chain)
    for chain in open_chains:
        pc.finish(chain)
    pc.clear()
    assert pc.n_chains == 0
    assert pc.allocator.in_use == 0
    assert all(pc.allocator.refcount(p) == 0 for p in range(pool_pages))


# ------------------------------------------------------------- cache units
def _paged_with_rows(rng, *, quantized, n_rows=3, lengths=(5, 8, 0)):
    """A paged cache with per-row reservations + the contiguous cache
    holding the same logical contents, built by appending tokens."""
    L, HKV, DH = 2, 2, 4
    ps, max_len = 4, 16
    al = kvc.PageAllocator(n_rows * max_len // ps, ps)
    paged = kvc.init_paged_cache(L, n_rows, max_len, HKV, DH, page_size=ps,
                                 quantized=quantized, dtype=jnp.float32)
    flat = kvc.init_cache(L, n_rows, max_len, HKV, DH, quantized=quantized,
                          dtype=jnp.float32)
    pages = np.full((n_rows, max_len // ps), paged.n_pages, np.int32)
    for r in range(n_rows):
        got = al.alloc(max_len // ps)
        pages[r] = got
    paged = kvc.assign_pages(paged, jnp.arange(n_rows), jnp.asarray(pages))
    for t in range(max(lengths)):
        k_new = jnp.asarray(rng.normal(size=(n_rows, 1, HKV, DH)),
                            jnp.float32)
        v_new = jnp.asarray(rng.normal(size=(n_rows, 1, HKV, DH)),
                            jnp.float32)
        cur = jnp.asarray([min(t, n) for n in lengths], jnp.int32)
        live = np.asarray([t < n for n in lengths])
        # contiguous append (drop rows already at their target length by
        # pointing their cursor past capacity — mirrors finished rows)
        cur_flat = jnp.where(jnp.asarray(live), cur, flat.capacity)
        k_c, v_c, ks_c, vs_c = kvc.append_token(
            flat.k, flat.v, flat.k_scale, flat.v_scale, k_new, v_new,
            cur_flat, 0)
        flat = kvc.KVCache(k=k_c, v=v_c, k_scale=ks_c, v_scale=vs_c,
                           lengths=flat.lengths)
        cur_paged = jnp.where(jnp.asarray(live), cur, paged.capacity)
        kp, vp, ksp, vsp = kvc.append_token_paged(
            paged.k, paged.v, paged.k_scale, paged.v_scale,
            paged.block_tables, k_new, v_new, cur_paged, 0)
        paged = kvc.PagedKVCache(
            k=kp, v=vp, k_scale=ksp, v_scale=vsp,
            block_tables=paged.block_tables, own_pages=paged.own_pages,
            lengths=paged.lengths)
    lengths = jnp.asarray(lengths, jnp.int32)
    paged = kvc.PagedKVCache(k=paged.k, v=paged.v, k_scale=paged.k_scale,
                             v_scale=paged.v_scale,
                             block_tables=paged.block_tables,
                             own_pages=paged.own_pages, lengths=lengths)
    flat = kvc.KVCache(k=flat.k, v=flat.v, k_scale=flat.k_scale,
                       v_scale=flat.v_scale, lengths=lengths)
    return paged, flat


@pytest.mark.parametrize("quantized", [False, True])
def test_paged_append_linearizes_to_contiguous(rng, quantized):
    """Tokens appended through block tables read back (linearized) exactly
    as the contiguous cache's rows, for every valid position."""
    paged, flat = _paged_with_rows(rng, quantized=quantized)
    lin_k = np.asarray(kvc.linearize_pages(paged.k[0], paged.block_tables))
    lin_v = np.asarray(kvc.linearize_pages(paged.v[0], paged.block_tables))
    for r, n in enumerate(np.asarray(paged.lengths)):
        np.testing.assert_array_equal(lin_k[r, :n],
                                      np.asarray(flat.k[0, r, :n]))
        np.testing.assert_array_equal(lin_v[r, :n],
                                      np.asarray(flat.v[0, r, :n]))
    if quantized:
        lin_ks = np.asarray(kvc.linearize_pages(paged.k_scale[0],
                                                paged.block_tables))
        for r, n in enumerate(np.asarray(paged.lengths)):
            np.testing.assert_array_equal(
                lin_ks[r, :n], np.asarray(flat.k_scale[0, r, :n]))


@pytest.mark.parametrize("impl", ["xla", "interpret"])
@pytest.mark.parametrize("quantized", [False, True])
def test_gather_beams_paged_matches_slab_gather(rng, quantized, impl):
    """The block-table permutation + partial-page copy produces the same
    *logical* rows as the full slab gather, and the next append after the
    reorder lands in a privately-owned page (no cross-row corruption);
    the scale pools copied by XLA or by the Pallas scale copy."""
    paged, flat = _paged_with_rows(rng, quantized=quantized, n_rows=4,
                                   lengths=(6, 6, 6, 6))
    idx = jnp.asarray([2, 2, 0, 1], jnp.int32)
    g_flat = kvc.gather_beams(flat, idx)
    g_paged = kvc.gather_beams_paged(paged, idx, impl=impl)
    np.testing.assert_array_equal(np.asarray(g_paged.lengths),
                                  np.asarray(g_flat.lengths))
    lin = np.asarray(kvc.linearize_pages(g_paged.k[0],
                                         g_paged.block_tables))
    for r in range(4):
        np.testing.assert_array_equal(lin[r, :6],
                                      np.asarray(g_flat.k[0, r, :6]))
    if quantized:
        lin_s = np.asarray(kvc.linearize_pages(g_paged.v_scale[0],
                                               g_paged.block_tables))
        for r in range(4):
            np.testing.assert_array_equal(
                lin_s[r, :6], np.asarray(g_flat.v_scale[0, r, :6]))
    # rows 0 and 1 both gathered row 2: appending different tokens next
    # must not collide (each row's write slot points into its own pages)
    k_new = jnp.asarray(rng.normal(size=(4, 1, 2, 4)), jnp.float32)
    v_new = jnp.asarray(rng.normal(size=(4, 1, 2, 4)), jnp.float32)
    kp, vp, _, _ = kvc.append_token_paged(
        g_paged.k, g_paged.v, g_paged.k_scale, g_paged.v_scale,
        g_paged.block_tables, k_new, v_new, g_paged.lengths, 0, impl=impl)
    lin2 = np.asarray(kvc.linearize_pages(kp[0], g_paged.block_tables))
    for r in range(4):
        np.testing.assert_array_equal(lin2[r, :6], lin[r, :6])  # history kept
        if quantized:
            continue                     # int8 rounding covered via engine
        np.testing.assert_allclose(lin2[r, 6], np.asarray(k_new[r, 0]),
                                   rtol=1e-6)


@pytest.mark.parametrize("n_new", [1, 3])
@pytest.mark.parametrize("cache_kind", ["paged", "paged_interpret",
                                        "contiguous"])
def test_append_at_a_layer_leaves_the_others(rng, cache_kind, n_new):
    """An append into layer i of the stacked cache writes that layer's
    pages (or rows) and scales in place and leaves every other layer
    bit-identical: one token a row (decode) and three (speculative
    verify), paged (scales written by XLA, or by the Pallas scale writer
    in interpret mode) and contiguous, the layer a Python int or an int32
    scalar.  The last row's cursor is past capacity: it writes nowhere."""
    L, B, HKV, DH, ps, max_len = 3, 4, 2, 4, 4, 16
    paged = cache_kind != "contiguous"
    impl = "interpret" if cache_kind == "paged_interpret" else "xla"
    if paged:
        cache = kvc.init_paged_cache(L, B, max_len, HKV, DH, page_size=ps,
                                     quantized=True)
        pages = jnp.arange(B * max_len // ps, dtype=jnp.int32)
        cache = kvc.assign_pages(cache, jnp.arange(B),
                                 pages.reshape(B, max_len // ps))
    else:
        cache = kvc.init_cache(L, B, max_len, HKV, DH, quantized=True)
    pools = (cache.k, cache.v, cache.k_scale, cache.v_scale)
    # each layer starts with contents of its own
    pools = tuple(jnp.asarray(rng.integers(1, 100, p.shape), p.dtype)
                  for p in pools)
    lengths = jnp.asarray([0, 5, 9, max_len], jnp.int32)
    k_new = jnp.asarray(rng.normal(size=(B, n_new, HKV, DH)), jnp.float32)
    v_new = jnp.asarray(rng.normal(size=(B, n_new, HKV, DH)), jnp.float32)
    for layer in (0, jnp.int32(1), 2):
        if paged:
            append = (kvc.append_token_paged if n_new == 1
                      else kvc.append_tokens_paged)
            got = append(*pools, cache.block_tables, k_new, v_new, lengths,
                         layer, impl=impl)
        else:
            append = kvc.append_token if n_new == 1 else kvc.append_tokens
            got = append(*pools, k_new, v_new, lengths, layer)
        i = int(layer)
        for before, after in zip(pools, got):
            before, after = np.asarray(before), np.asarray(after)
            for other in set(range(L)) - {i}:
                np.testing.assert_array_equal(after[other], before[other])
            assert not np.array_equal(after[i], before[i])
        for pool, new in ((0, kvc.quantize_kv(k_new)[0]),
                          (2, kvc.quantize_kv(k_new)[1]),
                          (3, kvc.quantize_kv(v_new)[1])):
            rows = (kvc.linearize_pages(got[pool][i], cache.block_tables)
                    if paged else got[pool][i])
            for b, n in enumerate(np.asarray(lengths)[:-1]):
                np.testing.assert_array_equal(
                    np.asarray(rows[b, n:n + n_new]), np.asarray(new[b]))
        # the row past capacity left its own pages (or row) as they were
        last = (kvc.linearize_pages(got[0][i], cache.block_tables)
                if paged else got[0][i])[B - 1]
        before = (kvc.linearize_pages(pools[0][i], cache.block_tables)
                  if paged else pools[0][i])[B - 1]
        np.testing.assert_array_equal(np.asarray(last), np.asarray(before))


def test_scale_page_copy_kernel_matches_xla(rng):
    """The Pallas copy-on-write of the scale pools (interpret mode) moves
    exactly what the XLA gather-then-scatter moves, in every layer: two
    rows that swap pages, a row that copies its page onto itself, a row
    whose destination is the unreserved sentinel, and rows that copy one
    page to two places."""
    L, P, ps, HKV = 3, 9, 4, 2
    ks = jnp.asarray(rng.normal(size=(L, P, ps, HKV)), jnp.float32)
    vs = jnp.asarray(rng.normal(size=(L, P, ps, HKV)), jnp.float32)
    src = jnp.asarray([1, 2, 4, 3, 5, 5], jnp.int32)
    dst = jnp.asarray([2, 1, 4, P, 6, 7], jnp.int32)
    want = ops.copy_scale_pages(ks, vs, src, dst, impl="xla")
    got = ops.copy_scale_pages(ks, vs, src, dst, impl="interpret")
    for w, g in zip(want, got):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    assert np.array_equal(np.asarray(got[0][:, 2]), np.asarray(ks[:, 1]))
    assert np.array_equal(np.asarray(got[0][:, 1]), np.asarray(ks[:, 2]))


def test_free_slots_paged_drops_writes(rng):
    """A freed row's table goes to sentinel: its later appends vanish
    instead of landing in (possibly reallocated) pages."""
    paged, _ = _paged_with_rows(rng, quantized=False)
    freed = kvc.free_slots_paged(paged, jnp.asarray([0, 1, 2], jnp.int32))
    assert np.all(np.asarray(freed.lengths) == 0)
    assert np.all(np.asarray(freed.block_tables) == paged.n_pages)
    k_new = jnp.asarray(rng.normal(size=(3, 1, 2, 4)), jnp.float32)
    kp, _, _, _ = kvc.append_token_paged(
        freed.k, freed.v, None, None, freed.block_tables,
        k_new, k_new, jnp.zeros((3,), jnp.int32), 0)
    np.testing.assert_array_equal(np.asarray(kp), np.asarray(freed.k))
    # reserved rows' appends (same cursors) do land
    assert not np.array_equal(
        np.asarray(kvc.append_token_paged(
            paged.k, paged.v, None, None, paged.block_tables,
            k_new, k_new, jnp.zeros((3,), jnp.int32), 0)[0]),
        np.asarray(paged.k))


def _paged_case(rng, B, H, HKV, dh, ps, maxP, lengths, sentinel_tail,
                n_layers=2):
    """Random stacked INT8 pools, each layer's contents its own, and
    dense-prefix block tables: row b holds ceil(len_b / ps) pages from a
    permutation of the pool, the rest of its table is the sentinel ``P``
    (or, with ``sentinel_tail=False``, real pages the row does not
    own)."""
    P = B * maxP + 3
    L = n_layers
    q = jnp.asarray(rng.normal(size=(B, H, dh)), jnp.float32)
    kp = jnp.asarray(rng.integers(-127, 128, (L, P, ps, HKV, dh)), jnp.int8)
    vp = jnp.asarray(rng.integers(-127, 128, (L, P, ps, HKV, dh)), jnp.int8)
    ks = jnp.asarray(rng.uniform(0.001, 0.02, (L, P, ps, HKV)), jnp.float32)
    vs = jnp.asarray(rng.uniform(0.001, 0.02, (L, P, ps, HKV)), jnp.float32)
    perm = rng.permutation(P)
    tab = (np.full((B, maxP), P, np.int32) if sentinel_tail else
           rng.integers(0, P, (B, maxP)).astype(np.int32))
    c = 0
    for b, n in enumerate(-(-np.asarray(lengths) // ps)):
        tab[b, :n] = perm[c:c + n]
        c += n
    return (q, kp, ks, vp, vs, jnp.asarray(tab),
            jnp.asarray(lengths, jnp.int32))


# (B, H, HKV, dh, ps, maxP, lengths); a length of None draws one in
# [1, maxP·ps].  At 19 rows of transformer-base's page shape the kernel
# takes 9 rows a block: blocks of 9, 9 and 1.
PAGED_CASES = {
    "gqa2_ps4": (3, 4, 2, 8, 4, 4, [11, 16, 5]),
    "ps2_odd_slots": (3, 4, 2, 8, 2, 5, [None] * 3),
    "ps2": (3, 4, 2, 8, 2, 8, [None] * 3),
    "ps4_three_slots": (3, 4, 2, 8, 4, 3, [None] * 3),
    "ps16_full_and_empty": (4, 8, 8, 16, 16, 3, [48, 0, 1, 17]),
    "rows_not_a_block_multiple": (19, 8, 8, 64, 16, 9,
                                  [0, 144] + [None] * 17),
    "hkv16": (5, 16, 16, 8, 16, 2, [32, 9, 0, 16, 1]),
    # 1600-token rows outgrow VMEM: two chunks of 50 page slots, a row
    # ending in each and a row filling both
    "rows_in_two_chunks": (3, 8, 8, 64, 16, 100, [1600, 700, 1000]),
}


@pytest.mark.parametrize("sentinel_tail", [True, False])
@pytest.mark.parametrize("case", sorted(PAGED_CASES))
def test_paged_kernel_interpret_matches_oracle(rng, case, sentinel_tail):
    """Pallas paged flash-decode (row blocks, live pages only) vs the
    pure-jnp oracle: GQA, page sizes 2/4/16, full and one-token rows, odd
    slot counts, a batch that is no multiple of the row block, rows walked
    in two chunks of page slots, and tails of sentinel or foreign table
    entries.  The pools are stacked over two layers of different contents
    and every layer is checked, by a Python index and by an int32 scalar,
    so a kernel that reads the wrong layer fails.  An empty row attends to
    nothing: its output is exactly 0 (the oracle's softmax over no
    position is NaN)."""
    from repro.kernels.decode_attention import paged_block_plan
    B, H, HKV, dh, ps, maxP, lengths = PAGED_CASES[case]
    rows, chunk = paged_block_plan(B, maxP, ps, H, HKV, dh)
    if case == "rows_not_a_block_multiple":
        assert B % rows == 1
    assert (chunk == 50) == (case == "rows_in_two_chunks")
    lengths = [int(rng.integers(1, maxP * ps + 1)) if n is None else n
               for n in lengths]
    args = _paged_case(rng, B, H, HKV, dh, ps, maxP, lengths, sentinel_tail)
    live = np.asarray(lengths) > 0
    outs = []
    for layer in (0, jnp.int32(1)):
        want = np.asarray(ref.ref_decode_attention_paged(*args, layer, 0.35))
        got = np.asarray(ops.decode_attention_paged(*args, layer,
                                                    sm_scale=0.35,
                                                    impl="interpret"))
        np.testing.assert_allclose(got[live], want[live], rtol=2e-5,
                                   atol=2e-5)
        assert not np.isnan(got).any()
        np.testing.assert_array_equal(got[~live], 0.0)
        outs.append(got)
    if live.any():
        assert not np.allclose(outs[0][live], outs[1][live])


@pytest.mark.parametrize("B,H,HKV,maxP", [
    (512, 8, 8, 9), (8, 8, 8, 9), (512, 16, 16, 9), (3, 4, 2, 9),
    (512, 8, 1, 9), (4, 8, 8, 256), (1, 8, 8, 10_000)])
def test_paged_rows_per_block_fits_budget(B, H, HKV, maxP):
    """The row block and the chunk of page slots follow from shapes alone:
    the kernel's VMEM plan, page buffers at the chip's tile-padded extent,
    fits the budget; a block never outgrows the batch; a row's slots are
    cut into equal chunks only when they do not fit.  At the offline
    cell's shapes a page slot is 16 KiB each of K and V (an (8, 64) int8
    slab per token fills an (8, 128) tile) and 8 KiB each of their
    scales, and a block takes 9 rows of all 9 slots."""
    from repro.kernels.decode_attention import (
        PAGED_VMEM_BUDGET, _paged_scratch, _tiled_bytes, paged_block_plan,
        paged_vmem_bytes)
    ps, dh = 16, 64
    rows, chunk = paged_block_plan(B, maxP, ps, H, HKV, dh)
    assert 1 <= rows <= B and 1 <= chunk <= maxP
    n_chunks = -(-maxP // chunk)
    assert -(-maxP // n_chunks) == chunk
    planned = paged_vmem_bytes(rows, chunk, ps, H, HKV, dh)
    assert planned <= PAGED_VMEM_BUDGET
    if chunk < maxP:
        assert rows == 1
        assert paged_vmem_bytes(1, maxP, ps, H, HKV, dh) > PAGED_VMEM_BUDGET
    assert rows == B or planned + planned // rows > PAGED_VMEM_BUDGET
    if (B, H, HKV, maxP) == (512, 8, 8, 9):
        pages = _paged_scratch(rows, chunk, ps, H, HKV, dh,
                               interpret=False)[5:]
        assert sum(map(_tiled_bytes, pages)) == \
            rows * 2 * maxP * 2 * (16 + 8) * 1024
        assert (rows, chunk) == (9, 9)


def test_paged_block_plan_refuses_a_page_past_the_budget():
    """A page too big to hold twice for one row is refused by name, not
    left to the chip's compiler."""
    from repro.kernels.decode_attention import paged_block_plan
    with pytest.raises(ValueError, match="budget"):
        paged_block_plan(1, 2, 2048, 8, 8, 64)


# (B, H, HKV, dh, ps, maxP, lengths) at the chip's real shapes; a length of
# None draws one in [0, maxP·ps]
ON_CHIP_CASES = {
    "base_offline_beam4": (512, 8, 8, 64, 16, 9, [None] * 512),
    "base_greedy": (8, 8, 8, 64, 16, 9, [None] * 8),
    "big_offline_beam4": (512, 16, 16, 64, 16, 9, [None] * 512),
    "gqa4": (19, 8, 2, 64, 16, 9, [None] * 19),
    "rows_in_chunks": (3, 8, 8, 64, 16, 256, [4096, 1000, 2500]),
}


@pytest.mark.skipif(jax.default_backend() != "tpu",
                    reason="runs the compiled kernel: TPU only")
@pytest.mark.parametrize("case", sorted(ON_CHIP_CASES))
def test_paged_kernel_on_chip_matches_oracle(case):
    """The compiled paged kernel on the chip vs the oracle in f32 at the
    base, transformer-big, greedy, GQA and chunked shapes, over pools
    stacked on two layers, each layer read by its index.  On the chip
    each page copy moves the pool's whole HBM tiles past its logical shape
    (``_page_extents``); a tiling that differs from the one assumed reads
    other pages' bytes and fails here.  ``chip_smoke.py`` runs this."""
    rng = np.random.default_rng(1)
    B, H, HKV, dh, ps, maxP, lengths = ON_CHIP_CASES[case]
    lengths = [int(rng.integers(0, maxP * ps + 1)) if n is None else n
               for n in lengths]
    args = _paged_case(rng, B, H, HKV, dh, ps, maxP, lengths, True)
    live = np.asarray(lengths) > 0
    for layer in (0, 1):
        with jax.default_matmul_precision("highest"):
            want = np.asarray(ref.ref_decode_attention_paged(
                *args, layer, dh ** -0.5))
        got = np.asarray(ops.decode_attention_paged(
            *args, layer, sm_scale=dh ** -0.5, impl="pallas"))
        np.testing.assert_allclose(got[live], want[live], rtol=1e-4,
                                   atol=1e-4)
        np.testing.assert_array_equal(got[~live], 0.0)


@pytest.mark.skipif(jax.default_backend() != "tpu",
                    reason="runs the compiled kernels: TPU only")
@pytest.mark.parametrize("HKV", [8, 16, 2])
def test_scale_pool_writes_on_chip_match_xla(HKV):
    """The compiled Pallas writes of the stacked scale pools on the chip
    leave the pools bit-identical to XLA's scatter and gather-then-scatter,
    at the offline cell's pool (6 layers, 512 rows of 9 pages of 16), at
    transformer-big's 16 KV heads and at 2: an append at a layer, with
    sentinel rows, and the copy-on-write with swapped, self-copied and
    sentinel pages.  On the chip each row's scales are one DMA into the
    pool's tile-padded rows; a tiling that differs from the one assumed
    writes other rows' bytes and fails here.  ``chip_smoke.py`` runs
    this."""
    rng = np.random.default_rng(2)
    L, B, ps = 6, 512, 16
    P = B * 9
    pools = [jnp.asarray(rng.uniform(0.001, 0.02, (L, P, ps, HKV)),
                         jnp.float32) for _ in range(2)]
    new = [jnp.asarray(rng.uniform(0.001, 0.02, (B, HKV)), jnp.float32)
           for _ in range(2)]
    page = rng.permutation(P)[:B].astype(np.int32)
    page[::7] = P                                     # rows that drop
    off = rng.integers(0, ps, B).astype(np.int32)
    # copy-on-write: distinct destinations (each row's own page); pairs
    # of rows that swap pages, rows that copy their page onto itself,
    # rows whose destination is the sentinel, and rows that read the page
    # the next row writes
    dst = rng.permutation(P)[:B].astype(np.int32)
    src = np.roll(dst, -1)
    src[0:64:2], src[1:64:2] = dst[1:64:2], dst[0:64:2]
    src[64:128] = dst[64:128]
    dst[128:160] = P
    src, dst = jnp.asarray(src), jnp.asarray(dst)
    for layer in (0, 5):
        got, want = (ops.append_scales_paged(
            *pools, *new, jnp.asarray(page), jnp.asarray(off), layer,
            impl=impl) for impl in ("pallas", "xla"))
        for g, w in zip(got, want):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    got, want = (ops.copy_scale_pages(*pools, src, dst, impl=impl)
                 for impl in ("pallas", "xla"))
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def test_init_paged_cache_validates_page_multiple():
    with pytest.raises(ValueError):
        kvc.init_paged_cache(1, 2, 30, 2, 4, page_size=8, quantized=False)
    with pytest.raises(ValueError):
        ServingEngine(object(), {}, max_len=30, paged=True, page_size=8)


# ------------------------------------------------------- engine identity
@pytest.mark.parametrize("quant", ["fp", "int8"])
@pytest.mark.parametrize("burst_len", [1, 3])
@pytest.mark.parametrize("fused", [True, False])
def test_paged_greedy_identity(quant, burst_len, fused):
    """Paged greedy serve == unpaged serve == per-request generate, and
    every page comes back to the pool."""
    state = _module_state()
    requests = state["requests"]
    res = state["engines"][f"{quant}_paged"].serve(
        requests, n_slots=3, max_new_tokens=BUDGETS, burst_len=burst_len,
        fused_admission=fused)
    want = _reference(quant, None)
    for i in range(len(requests)):
        np.testing.assert_array_equal(res.tokens_for(i), want[i])
    assert res.paged and res.page_size == PAGE_SIZE
    assert res.pages_in_use == 0
    assert 0 < res.page_hwm <= 3 * (MAX_LEN // PAGE_SIZE)
    assert res.reorder_bytes == 0        # greedy: nothing to reorder


@pytest.mark.parametrize("quant", ["fp", "int8"])
@pytest.mark.parametrize("burst_len", [1, 3])
@pytest.mark.parametrize("beam", [1, 4])
@pytest.mark.parametrize("fused", [True, False])
def test_paged_beam_identity(quant, burst_len, beam, fused):
    """Paged beam serve (zero-copy block-table reorder) is token-identical
    to per-request generate_beam for beam ∈ {1, 4}, FP and INT8 cache,
    fused and unfused admission."""
    state = _module_state()
    requests = state["requests"]
    res = state["engines"][f"{quant}_paged"].serve(
        requests, n_slots=2 * beam, max_new_tokens=BUDGETS,
        burst_len=burst_len, beam=beam, fused_admission=fused)
    want = _reference(quant, beam)
    for i in range(len(requests)):
        np.testing.assert_array_equal(res.tokens_for(i), want[i])
    assert res.pages_in_use == 0 and res.paged
    assert res.reorder_bytes > 0


@pytest.mark.parametrize("paged", [False, True])
@pytest.mark.parametrize("fused", [True, False])
def test_mixed_beam_widths_identity(paged, fused):
    """Mixed per-request beam widths in ONE grid: every request matches
    its own generate_beam(beam=width) stream — parked rows never leak a
    hypothesis — on both the paged and unpaged engines."""
    state = _module_state()
    requests = state["requests"]
    eng = state["engines"]["fp_paged" if paged else "fp"]
    res = eng.serve(requests, n_slots=8, max_new_tokens=BUDGETS,
                    burst_len=3, beam=MIXED_WIDTHS, fused_admission=fused)
    want = _reference("fp", MIXED_WIDTHS)
    for i in range(len(requests)):
        np.testing.assert_array_equal(res.tokens_for(i), want[i])
    assert res.beam == max(MIXED_WIDTHS)
    assert all(r.status == "finished" for r in res.requests)


def test_paged_auto_burst_identity():
    """burst_len='auto' (adaptive cap) over the paged cache stays
    token-identical for greedy and beam serving."""
    state = _module_state()
    requests = state["requests"]
    eng = state["engines"]["fp_paged"]
    res = eng.serve(requests, n_slots=3, max_new_tokens=BUDGETS,
                    burst_len="auto")
    for i, w in enumerate(_reference("fp", None)):
        np.testing.assert_array_equal(res.tokens_for(i), w)
    res = eng.serve(requests, n_slots=4, max_new_tokens=BUDGETS,
                    burst_len="auto", beam=2)
    for i, w in enumerate(_reference("fp", 2)):
        np.testing.assert_array_equal(res.tokens_for(i), w)
    assert res.auto_burst and res.paged and res.pages_in_use == 0


def test_request_reuse_does_not_pin_beam():
    """Regression: serve() must not write its default width into the
    caller's Request objects — a reused Request once served with beam=2
    must follow a later serve's beam=4, not silently stay 2-wide."""
    state = _module_state()
    eng = state["engines"]["fp"]
    reqs = [Request(req_id=i, src=np.asarray(s.src, np.int32),
                    max_new_tokens=int(b))
            for i, (s, b) in enumerate(zip(state["requests"], BUDGETS))]
    eng.serve(reqs, n_slots=4, max_new_tokens=BUDGETS, beam=2)
    assert all(r.beam is None for r in reqs)         # caller-owned field
    res = eng.serve(reqs, n_slots=8, max_new_tokens=BUDGETS, beam=4)
    want = _reference("fp", 4)
    for i in range(len(reqs)):
        np.testing.assert_array_equal(res.tokens_for(i), want[i])


def test_paged_admission_against_page_budget():
    """A pool smaller than contiguous-equivalent capacity throttles
    admission instead of deadlocking or corrupting: identity holds, the
    high-water mark respects the budget, and narrow-beam requests reserve
    fewer pages than the grid width would."""
    state = _module_state()
    model, params = state["model"], state["params"]
    requests = state["requests"]
    # 2 pages: only 2 of the 3 grid rows can hold requests at once — the
    # page gate (not row capacity) paces admission
    eng = ServingEngine(model, params, max_len=MAX_LEN, paged=True,
                        page_size=PAGE_SIZE, n_pages=2)
    res = eng.serve(requests, n_slots=3, max_new_tokens=BUDGETS,
                    burst_len=2)
    want = _reference("fp", None)
    for i in range(len(requests)):
        np.testing.assert_array_equal(res.tokens_for(i), want[i])
    assert res.pages_in_use == 0 and res.page_hwm <= 2
    # a request whose reservation exceeds the pool is rejected up front
    with pytest.raises(ValueError):
        eng.serve(requests, n_slots=3, max_new_tokens=MAX_LEN)


def test_paged_result_metrics_exposed():
    state = _module_state()
    res = state["engines"]["fp_paged"].serve(
        state["requests"], n_slots=4, max_new_tokens=BUDGETS, beam=2)
    m = res.metrics()
    assert m["paged"] == 1.0 and m["pages_in_use"] == 0.0
    assert m["page_hwm"] > 0 and m["reorder_bytes"] > 0
    unpaged = state["engines"]["fp"].serve(
        state["requests"], n_slots=4, max_new_tokens=BUDGETS, beam=2)
    # the whole point: the paged reorder moves a fraction of the slab
    assert res.reorder_bytes * 2 < unpaged.reorder_bytes


@pytest.mark.parametrize("fused", [True, False])
@pytest.mark.parametrize("beam", [None, 2])
def test_paged_kv_page_counters(beam, fused):
    """``kv_pages_read`` and ``kv_page_slots`` from known lengths.  With an
    EOS the model never emits, every request decodes exactly its budget;
    its k-th decoder position is attended with length k, so the kernel
    copies ceil(k / page_size) pages of each of its rows in each layer,
    out of max_len / page_size slots.  Unfused admission decodes position
    1 in the prefill program, outside the decode steps counted."""
    state = _module_state()
    ps = 2
    eng = ServingEngine(state["model"], state["params"], max_len=MAX_LEN,
                        paged=True, page_size=ps, eos_id=-1)
    kw = {} if beam is None else {"beam": beam}
    res = eng.serve(state["requests"], n_slots=4, max_new_tokens=BUDGETS,
                    burst_len=3, fused_admission=fused, **kw)
    rows, layers = beam or 1, state["cfg"].n_layers
    positions = [range(1 if fused else 2, cap + 1) for cap in BUDGETS]
    assert [len(r.tokens) for r in res.requests] == BUDGETS
    assert res.kv_pages_read == layers * rows * sum(
        -(-k // ps) for pos in positions for k in pos)
    assert res.kv_page_slots == layers * (MAX_LEN // ps) * rows * sum(
        len(pos) for pos in positions)
    assert res.kv_page_slots == \
        layers * (MAX_LEN // ps) * res.busy_slot_steps
    m = res.metrics()
    assert (m["kv_pages_read"], m["kv_page_slots"]) == \
        (res.kv_pages_read, res.kv_page_slots)
