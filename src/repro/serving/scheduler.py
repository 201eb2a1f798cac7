"""Serving-side batch composition (paper §5.4 + §5.6 front half).

Two generations of scheduler live here:

* ``TokenSortedScheduler`` — the paper's static composer: orders incoming
  requests by **token count** (descending — long batches first keeps the
  stream pipeline busy at the tail), composes fixed-size batches padded to
  bucketed lengths, and exposes them through a thread-safe ``BatchQueue``
  that the parallel streams (``streams.py``) drain asynchronously — the
  paper's parent-session batch queue.

* ``ContinuousScheduler`` — the request-lifecycle manager behind
  ``ServingEngine.serve``: requests flow *waiting → running → finished*
  through a fixed pool of decode **slots**.  Admission is strict FIFO (no
  starvation by construction) with an optional per-round prefill token
  budget; a slot freed by a finished sequence is refilled mid-decode
  instead of idling until the whole batch drains.  Per-request arrival /
  first-token / finish timestamps feed the latency metrics the benchmarks
  report.

  With ``group_size > 1`` (continuous **beam** serving) a request occupies
  a *group* of ``group_size`` contiguous decode rows instead of one: the
  free list holds group base rows, admission hands out whole groups, and
  release frees all ``group_size`` rows atomically — so the engine's
  beam-reorder gathers always stay inside one group's row span and freed
  row sets are always multiples of the beam width.
"""

from __future__ import annotations

import collections
import dataclasses
import queue
import threading
from typing import Callable, Deque, Dict, List, Optional, Sequence

import numpy as np

from repro.data.sorting import make_batches, next_pow2, padding_stats
from repro.data.synthetic import Sentence, pad_batch


@dataclasses.dataclass
class WorkItem:
    batch_id: int
    indices: List[int]                 # request ids in this batch
    batch: Dict[str, np.ndarray]
    n_real_tokens: int
    n_padded_tokens: int


class TokenSortedScheduler:
    """Requests → ordered, padded batches (+ padding accounting)."""

    def __init__(self, batch_size: int, *, sort_mode: str = "tokens",
                 pad_to_multiple: int = 8):
        self.batch_size = batch_size
        self.sort_mode = sort_mode
        self.pad_to_multiple = pad_to_multiple

    def _round(self, n: int) -> int:
        m = self.pad_to_multiple
        return ((n + m - 1) // m) * m

    def plan(self, requests: Sequence[Sentence]) -> List[WorkItem]:
        batches = make_batches(requests, self.batch_size, self.sort_mode)
        items = []
        for bid, idx in enumerate(batches):
            sents = [requests[i] for i in idx]
            L = self._round(max(s.n_tokens for s in sents))
            src, lens = pad_batch([s.src for s in sents], length=L)
            items.append(WorkItem(
                batch_id=bid,
                indices=list(idx),
                batch={"src_tokens": src, "src_lengths": lens},
                n_real_tokens=int(lens.sum()),
                n_padded_tokens=int(L * len(sents)),
            ))
        return items

    def stats(self, requests: Sequence[Sentence]) -> dict:
        batches = make_batches(requests, self.batch_size, self.sort_mode)
        return padding_stats(requests, batches)


@dataclasses.dataclass
class Request:
    """One serving request and its measured lifecycle."""

    req_id: int
    src: np.ndarray                     # (S,) int32 source tokens
    max_new_tokens: int = 64
    arrival_s: float = 0.0
    # SLO knobs (caller-owned config, like ``beam``): absolute deadline on
    # the serve clock (None = best-effort) and a priority boost — both
    # feed the EDF-with-aging wait-queue order and victim selection.
    deadline_s: Optional[float] = None
    priority: float = 0.0

    # lifecycle (scheduler/engine-maintained)
    status: str = "waiting"             # waiting | running | finished | rejected
    slot: Optional[int] = None          # base row of the request's group
    admitted_s: Optional[float] = None
    first_token_s: Optional[float] = None
    finish_s: Optional[float] = None
    tokens: List[int] = dataclasses.field(default_factory=list)
    # decode-step attribution: with burst decode, wall-clock latencies are
    # observed at burst *edges*, so the step counters carry the exact
    # position — admission and release in global decode-step time.
    admitted_step: Optional[int] = None
    finish_step: Optional[int] = None
    # beam serving: winning hypothesis' length-penalized log-prob (None for
    # greedy decode, where there is exactly one hypothesis per request)
    score: Optional[float] = None
    # mixed-beam serving: this request's own beam width (None = the serve
    # call's default).  A request with beam < the grid's group width only
    # runs (and reserves KV pages for) `beam` of its group's rows; the
    # rest are parked.  Caller-owned config — the engine resolves widths
    # into its own map and never writes this field.
    beam: Optional[int] = None
    # paged KV cache: flat page ids reserved for this request (scheduler-
    # managed: allocated at admission, returned at release)
    pages: Optional[List[int]] = None
    # prefix cache (scheduler-managed): how this admission was routed
    # ("hit" | "insert" | "skip" | None when the cache is off) and the
    # chain whose reference this request holds until release
    prefix_role: Optional[str] = None
    prefix_chain: Optional[object] = None
    # overload machinery (scheduler/engine-maintained): why a shed request
    # was rejected; how many times it was preempted; the host-side spill
    # payload (serving/preemption.py:SpilledRequest) while preempted; how
    # many admission rounds it has waited (starvation aging); and the
    # virtual worst-case page reservation it holds under overcommit
    reject_reason: Optional[str] = None
    preemptions: int = 0
    spill: Optional[object] = None
    wait_rounds: int = 0
    reserved_pages: int = 0

    @property
    def n_src_tokens(self) -> int:
        return int(len(self.src))

    @property
    def first_token_latency_s(self) -> Optional[float]:
        if self.first_token_s is None:
            return None
        return self.first_token_s - self.arrival_s

    @property
    def total_latency_s(self) -> Optional[float]:
        if self.finish_s is None:
            return None
        return self.finish_s - self.arrival_s


def pad_rows_pow2(src: np.ndarray, lens: np.ndarray
                  ) -> "tuple[np.ndarray, np.ndarray, int]":
    """Pad an admission batch to the next power-of-two row count.

    Padding rows replay row 0 — their results are discarded downstream
    (out-of-range destination sentinels; jax scatter drop semantics) — so
    prefill programs compile one variant per pow2 width, never per
    admission-group size.  The ONE padding contract shared by the fused
    (``ContinuousScheduler.plan_admission``) and unfused
    (``ServingEngine._prefill_padded``) admission paths: both must
    specialize on identical device shapes or the compile-cache bound and
    the fused/unfused identity guarantee silently break.
    Returns ``(src, lens, width)``.
    """
    n = src.shape[0]
    width = next_pow2(n)
    if width > n:
        src = np.concatenate(
            [src, np.broadcast_to(src[0], (width - n,) + src.shape[1:])],
            axis=0)
        lens = np.concatenate(
            [lens, np.broadcast_to(lens[0], (width - n,))])
    return src, lens, width


@dataclasses.dataclass
class AdmissionPlan:
    """One admission round, shaped for the fused decode-burst program.

    The fused-admission engine feeds admissions to the device as *burst
    program inputs* instead of a separate prefill dispatch, so the
    padding contract is device-shaped and compile-stable: sources are
    right-padded to ``enc_len`` columns and the batch is padded to a
    power-of-two ``width`` (padding rows replay row 0; their ``base_rows``
    entry is the out-of-range sentinel ``oob_row``, so every scatter
    inside the burst program drops them).  Zero-budget requests never
    reach the device — they are finished at admission and reported in
    ``released``.
    """

    requests: List[Request]            # admitted, budget > 0, slot order
    released: List[Request]            # zero-budget: finished at admission
    src_tokens: np.ndarray             # (width, enc_len) int32
    src_lengths: np.ndarray            # (width,) int32
    base_rows: np.ndarray              # (width,) int32; padding → oob_row
    width: int                         # pow2 batch width (0 = no device work)
    # ---- prefix cache extension (all empty/zero when the cache is off).
    # ``requests`` above then holds only the *encode* rows (prefix misses);
    # hits skip the encoder entirely and arrive pre-shaped here.
    hits: List[Request] = dataclasses.field(default_factory=list)
    hit_rows: np.ndarray = dataclasses.field(       # (hit_width,) base rows
        default_factory=lambda: np.zeros((0,), np.int32))
    hit_lengths: np.ndarray = dataclasses.field(    # (hit_width,) src lengths
        default_factory=lambda: np.zeros((0,), np.int32))
    hit_pages: np.ndarray = dataclasses.field(      # (hit_width, maxPP) chains
        default_factory=lambda: np.zeros((0, 0), np.int32))
    hit_width: int = 0                         # pow2 (0 = no hits)
    # per-encode-row chain reservations: rows routed "insert" carry their
    # chain's page ids (sentinel-padded); "skip"/padding rows all-sentinel
    ins_pages: np.ndarray = dataclasses.field(      # (width, maxPP)
        default_factory=lambda: np.zeros((0, 0), np.int32))
    # overload extensions: ``resumed`` requests carry a host spill payload
    # (preempted earlier; the engine restores their KV instead of encoding)
    # and ``staged`` requests have sources past the chunked-prefill budget
    # (the engine spreads their encode across rounds, one layer per round;
    # neither kind occupies an encode row in this plan)
    resumed: List[Request] = dataclasses.field(default_factory=list)
    staged: List[Request] = dataclasses.field(default_factory=list)

    @property
    def n_admitted(self) -> int:
        return (len(self.requests) + len(self.hits) + len(self.released)
                + len(self.resumed) + len(self.staged))

    @property
    def prefix_hit_pages(self) -> int:
        """Chain pages whose encode+store this round's hits skipped."""
        return sum(r.prefix_chain.n_pages for r in self.hits)


class ContinuousScheduler:
    """Admission control + slot lifecycle for continuous batching.

    ``n_slots`` decode rows exist for the whole serve; a request occupies
    exactly one slot *group* of ``group_size`` contiguous rows from
    admission to finish (``group_size=1`` — greedy — makes a group one
    row, the original behaviour).  ``admit`` hands out free groups to
    waiting requests in strict FIFO order — bounded per round by
    ``prefill_token_budget`` (sum of source tokens prefillable in one go)
    so a burst of long requests cannot monopolize a prefill round.  The
    first waiting request is always admitted when a group is free, so no
    request can starve regardless of the length mix.

    ``Request.slot`` and ``slot_map`` keys are group *base rows* (always
    multiples of ``group_size``); a group's rows are
    ``[base, base + group_size)``.  Rows past ``n_groups * group_size``
    (when ``group_size`` does not divide ``n_slots``) are never assigned —
    that is the beam-starvation tax the README quantifies.
    ``prefill_token_budget`` is denominated in prefilled **row**-tokens:
    a group prefill replicates the source across its rows, so a request
    charges ``group_size × n_src_tokens`` against the round's budget.
    (Fused encode-once admission actually *encodes* the source only once
    per group, but the budget deliberately keeps the row-token
    denomination so admission pacing — and therefore the token stream —
    is identical between the fused and unfused engines.)
    """

    def __init__(self, n_slots: int, *, group_size: int = 1,
                 prefill_token_budget: Optional[int] = None,
                 allocator=None,
                 pages_per_request: Optional[Callable[[Request], int]] = None,
                 prefix_cache=None,
                 initial_pages: Optional[Callable[[Request], int]] = None,
                 prefill_chunk: Optional[int] = None,
                 starvation_aging: float = 0.5):
        if n_slots < 1:
            raise ValueError(f"need at least one slot, got {n_slots}")
        if group_size < 1:
            raise ValueError(f"group_size must be ≥ 1, got {group_size}")
        if n_slots < group_size:
            raise ValueError(f"{n_slots} rows cannot hold a group of "
                             f"{group_size}")
        if (allocator is None) != (pages_per_request is None):
            raise ValueError("allocator and pages_per_request go together")
        self.n_slots = n_slots
        self.group_size = group_size
        self.n_groups = n_slots // group_size
        self.prefill_token_budget = prefill_token_budget
        # paged KV admission: a request needs a free slot group AND pages
        # from the allocator.  ``pages_per_request`` is the worst case
        # (the request's full budget); by default it is also what gets
        # physically allocated, so admission can never over-commit and
        # decode never needs to preempt — the head of the queue blocks
        # the round when the pool is short (pages return at release, so
        # it always eventually admits).  With ``initial_pages`` set the
        # worst case becomes a *virtual* reservation (allocator.reserve,
        # capped at overcommit_limit × n_pages) and only next-burst pages
        # are allocated up front — the engine grows rows mid-flight and
        # preempts-by-page-spill when growth or admission comes up short.
        self.allocator = allocator
        self.pages_per_request = pages_per_request
        # cross-request prefix cache: routes each admission "hit" /
        # "insert" / "skip".  Chain pages come from the cache's OWN
        # allocator (separate pool), so chain reservations can never eat
        # into the decode page budget above — a full prefix pool degrades
        # to uncached admission, it cannot wedge the FIFO.
        self.prefix_cache = prefix_cache
        # overcommit: ``pages_per_request`` stays the worst case (virtual,
        # tracked by allocator.reserve); ``initial_pages`` — when given —
        # is what admission *physically* allocates (enough for the next
        # burst), with growth/preemption covering the gap.  None keeps the
        # legacy reserve-everything behaviour exactly.
        self.initial_pages = initial_pages
        # chunked prefill: sources longer than this (in tokens) are routed
        # to AdmissionPlan.staged instead of the round's encode rows
        self.prefill_chunk = prefill_chunk
        # EDF aging: each admission round a request waits shrinks its
        # urgency key by this many (virtual) seconds, so a best-effort
        # request eventually outranks any stream of tight deadlines
        if starvation_aging < 0:
            raise ValueError(f"starvation_aging must be >= 0, "
                             f"got {starvation_aging}")
        self.starvation_aging = float(starvation_aging)
        self._waiting: Deque[Request] = collections.deque()
        self._free: List[int] = [g * group_size for g in range(self.n_groups)]
        self.slot_map: Dict[int, Request] = {}
        self.finished: List[Request] = []
        self.rejected: List[Request] = []

    # ------------------------------------------------------------- lifecycle
    def submit(self, req: Request) -> None:
        # reset the whole lifecycle so a Request object can be re-served
        req.status = "waiting"
        req.slot = None
        req.admitted_s = None
        req.first_token_s = None
        req.finish_s = None
        req.tokens = []
        req.admitted_step = None
        req.finish_step = None
        req.score = None
        req.pages = None
        req.prefix_role = None
        req.prefix_chain = None
        req.reject_reason = None
        req.preemptions = 0
        req.spill = None
        req.wait_rounds = 0
        req.reserved_pages = 0
        self._waiting.append(req)

    def submit_many(self, reqs: Sequence[Request]) -> None:
        for r in reqs:
            self.submit(r)

    # ------------------------------------------------ deadline-aware order
    _NO_DEADLINE = 1e6                 # best-effort = very late deadline

    def urgency_key(self, req: Request) -> float:
        """Scalar wait-queue/victim key — smaller = more urgent.

        Earliest-deadline-first, nudged by ``priority`` and by starvation
        aging (every round spent waiting makes a request
        ``starvation_aging`` virtual seconds more urgent, so best-effort
        traffic cannot starve behind a stream of tight deadlines).
        """
        d = req.deadline_s if req.deadline_s is not None else self._NO_DEADLINE
        return d - req.priority - self.starvation_aging * req.wait_rounds

    def victim_key(self, req: Request) -> float:
        """Preemption-comparison key — deadline and priority ONLY.

        Starvation aging is deliberately excluded: aging exists to move a
        waiting request up the *queue*, not to let it evict an
        equally-urgent running one (with aging in the key, any deadline-
        free request would eventually out-rank every running peer and the
        pool would thrash on evictions that buy nothing).
        """
        d = req.deadline_s if req.deadline_s is not None else self._NO_DEADLINE
        return d - req.priority

    def _sort_waiting(self) -> None:
        """EDF-with-aging order; preempted (spilled) requests win ties.

        Skipped entirely when nothing in the queue carries a deadline, a
        priority, aging credit, or a spill — the default stays strict
        submission-order FIFO, byte-for-byte.
        """
        if len(self._waiting) < 2:
            return
        if not any(r.deadline_s is not None or r.priority or r.spill
                   is not None or r.wait_rounds for r in self._waiting):
            return
        self._waiting = collections.deque(sorted(
            self._waiting,
            key=lambda r: (self.urgency_key(r),
                           0 if r.spill is not None else 1)))

    def _shed(self, now: float) -> List[Request]:
        """Reject waiting requests whose deadline is provably unmeetable
        (already in the past — no admission order can produce a first
        token before a deadline that has elapsed).  Preempted requests are
        exempt: they already consumed encode + decode work, and their
        spilled KV is freed only through the engine's resume/abandon path.
        """
        shed: List[Request] = []
        keep: Deque[Request] = collections.deque()
        for req in self._waiting:
            if (req.deadline_s is not None and now > req.deadline_s
                    and req.spill is None):
                req.status = "rejected"
                req.reject_reason = (
                    f"deadline {req.deadline_s:.3f}s already passed at "
                    f"admission (now={now:.3f}s)")
                req.finish_s = now
                self.rejected.append(req)
                shed.append(req)
            else:
                keep.append(req)
        self._waiting = keep
        return shed

    def admit(self, now: float = 0.0, *,
              step: Optional[int] = None) -> List[Request]:
        """Move waiting requests into free slot groups (one prefill round).

        With burst decode, admission happens only at burst edges; ``step``
        records the global decode-step count at that edge so queueing can
        be attributed exactly even though ``now`` is burst-granular.

        Order: shed provably-late requests, sort by urgency (no-op for
        deadline-free traffic — strict FIFO is preserved exactly), then
        admit while slots, the prefill budget, and the page pool allow.
        Under overcommit (``initial_pages`` set) a request is gated by a
        *virtual* worst-case reservation (``allocator.reserve``) but only
        its next-burst pages are physically allocated.
        """
        self._shed(now)
        self._sort_waiting()
        admitted: List[Request] = []
        budget = self.prefill_token_budget
        used = 0
        while self._waiting and self._free:
            req = self._waiting[0]
            # budget is in prefilled *row*-tokens: a beam group encodes its
            # source once per row, so a request costs group_size × its
            # source length (group_size=1 reduces to plain source tokens)
            cost = req.n_src_tokens * self.group_size
            if admitted and budget is not None and used + cost > budget:
                break                    # next round; queue order preserved
            pages = None
            worst = 0
            if self.allocator is not None:
                worst = self.pages_per_request(req)
                if not self.allocator.can_reserve(worst):
                    break    # virtual budget exhausted: head waits
                n_pages = worst
                if self.initial_pages is not None:
                    n_pages = min(self.initial_pages(req), worst)
                pages = self.allocator.alloc(n_pages)
                if pages is None:
                    break    # pool short: the head waits (or the engine
                             # preempts a victim and retries next round)
                self.allocator.reserve(worst)
            self._waiting.popleft()
            slot = self._free.pop(0)
            req.status = "running"
            req.slot = slot
            req.pages = pages
            req.reserved_pages = worst
            req.admitted_s = now
            req.admitted_step = step
            self.slot_map[slot] = req
            used += cost
            admitted.append(req)
        for req in self._waiting:
            req.wait_rounds += 1         # starvation aging
        return admitted

    def admission_shortfall(self) -> Optional[Dict[str, int]]:
        """Why the most urgent waiting request cannot be admitted *now*,
        in pages — or None when nothing page-related blocks it.

        ``pages_short``: physical pages missing for its initial
        allocation; ``reserve_short``: virtual reservation room missing
        under the overcommit cap.  Both are fixable by preempting running
        victims (preemption spills physical pages AND returns the
        victim's worst-case reservation), which is exactly what the
        engine does with this signal.
        """
        if not self._waiting or not self._free or self.allocator is None:
            return None
        self._sort_waiting()
        req = self._waiting[0]
        worst = self.pages_per_request(req)
        n_pages = worst
        if self.initial_pages is not None:
            n_pages = min(self.initial_pages(req), worst)
        reserve_short = max(
            0, self.allocator.reserved + worst - self.allocator.reserve_cap)
        pages_short = max(0, n_pages - self.allocator.n_free)
        if not reserve_short and not pages_short:
            return None
        return {"reserve_short": reserve_short, "pages_short": pages_short,
                "head_key": self.victim_key(req)}

    def preempt(self, req: Request, now: float = 0.0) -> int:
        """Evict a running request back to the wait queue; returns its
        freed group base row.

        The caller (engine) has already copied the victim's KV pages to
        host — ``req.spill`` holds the payload — so its pages go back to
        the pool through the allocator's spill accounting (a staged victim
        whose encode never finished has nothing to spill: plain release).
        The victim keeps its emitted tokens and re-enters at the *front*
        of its urgency class (spilled requests win ties), so resume beats
        fresh admissions and a preempted request cannot starve.
        """
        if req.status != "running" or req.slot is None:
            raise ValueError(f"request {req.req_id} is not running "
                             f"(status={req.status})")
        slot = req.slot
        req.status = "waiting"
        req.slot = None
        req.preemptions += 1
        if req.pages is not None:
            if req.spill is not None:
                self.allocator.spill(req.pages)
            else:
                self.allocator.release(req.pages)
            req.pages = None
        if req.reserved_pages:
            self.allocator.unreserve(req.reserved_pages)
            req.reserved_pages = 0
        if req.prefix_chain is not None:
            # drop the chain reference: resume re-splices cross K/V from
            # the spill payload, not from the prefix pool
            self.prefix_cache.finish(req.prefix_chain)
            req.prefix_chain = None
            req.prefix_role = None
        del self.slot_map[slot]
        self._free.append(slot)
        self._free.sort()
        self._waiting.appendleft(req)
        return slot

    def assign_prefix(self, reqs: Sequence[Request]
                      ) -> "tuple[List[Request], List[Request]]":
        """Route live admissions through the prefix cache.

        Returns ``(misses, hits)``: misses (roles "insert"/"skip") must be
        encoded; hits skip the encoder and splice their cached chain.
        Routing is sequential on purpose — a source admitted twice in ONE
        round makes the first occurrence the "insert" and the second a
        "hit" on the chain reserved moments earlier (the engine orders the
        pool scatter before the hit gather inside one program, so the
        same-round hit reads the freshly written pages).
        """
        if self.prefix_cache is None:
            return list(reqs), []
        misses: List[Request] = []
        hits: List[Request] = []
        for req in reqs:
            role, chain = self.prefix_cache.admit(req.src)
            req.prefix_role = role
            req.prefix_chain = chain
            (hits if role == "hit" else misses).append(req)
        return misses, hits

    def chain_pages_matrix(self, reqs: Sequence[Request], width: int,
                           enc_len: int, stride: int = 1) -> np.ndarray:
        """(width, maxPP) chain page ids, sentinel-padded.

        ``maxPP`` is the chain length of a full ``enc_len`` source against
        the *prefix* allocator's page size; rows without a chain (role
        "skip", padding) are all-sentinel so their page-chunk scatters and
        gathers drop/clamp.  ``stride``: request ``i``'s chain lands on
        row ``i × stride`` (the unfused beam side batch tiles each source
        ``beam×``, and only the group's first row feeds the pool insert).
        """
        al = self.prefix_cache.allocator
        maxPP = (enc_len + al.page_size - 1) // al.page_size
        out = np.full((width, max(maxPP, 1)), al.n_pages, np.int32)
        for i, req in enumerate(reqs):
            if req.prefix_chain is not None:
                out[i * stride, :req.prefix_chain.n_pages] = \
                    req.prefix_chain.pages
        return out

    def shape_hits(self, hits: Sequence[Request], *, enc_len: int,
                   oob_row: int
                   ) -> "tuple[np.ndarray, np.ndarray, np.ndarray, int]":
        """Shape prefix hits for a device splice: pow2-padded
        ``(hit_rows, hit_lengths, hit_pages, hit_width)`` under the same
        row-0-replay / oob-destination contract as :func:`pad_rows_pow2`.
        """
        hlens = np.asarray([r.n_src_tokens for r in hits], np.int32)
        hrows = np.asarray([r.slot for r in hits], np.int32)
        hw = next_pow2(len(hits))
        pad = hw - len(hits)
        hit_lengths = np.concatenate(
            [hlens, np.broadcast_to(hlens[:1], (pad,))])
        hit_rows = np.concatenate(
            [hrows, np.full((pad,), oob_row, np.int32)])
        hit_pages = self.chain_pages_matrix(hits, hw, enc_len)
        hit_pages[len(hits):] = hit_pages[0]         # padding replays row 0
        return hit_rows, hit_lengths, hit_pages, hw

    def plan_admission(self, now: float = 0.0, *, step: Optional[int] = None,
                       enc_len: int, oob_row: int) -> AdmissionPlan:
        """Admit one round and shape it for the fused burst program.

        Runs :meth:`admit`, finishes zero-budget requests on the spot
        (their output is empty by definition; they need no device work),
        and packs the remainder into the :class:`AdmissionPlan` padding
        contract: sources right-padded to ``enc_len``, batch padded to a
        power-of-two width with row-0 replays, destinations padded with
        the ``oob_row`` sentinel so in-program scatters drop them.

        With a prefix cache attached the round splits: cache hits skip the
        encoder (``hit_*`` fields carry their chain pages, base rows and
        source lengths, pow2-padded under the same row-0-replay contract)
        and only the misses occupy encode rows; misses routed "insert"
        additionally carry their chain reservation in ``ins_pages`` so the
        fused program can store the fresh encode for the next requester.
        Zero-budget requests are excluded *before* cache routing — they
        never encode, so an "insert" for one would cache garbage.
        """
        live: List[Request] = []
        released: List[Request] = []
        resumed: List[Request] = []
        staged: List[Request] = []
        for req in self.admit(now, step=step):
            if req.max_new_tokens <= 0:
                req.first_token_s = now          # observed: empty output
                self.release(req, now, step=step)
                released.append(req)
            elif req.spill is not None:
                # preempted earlier: KV restores from the host spill
                # payload — no encode row, no prefix routing (the cross
                # K/V in the spill already reflects any chain it read)
                resumed.append(req)
            elif (self.prefill_chunk is not None
                    and req.n_src_tokens > self.prefill_chunk):
                # chunked prefill: encode spreads across rounds (engine-
                # driven, one encoder layer per round), so the source
                # never occupies this round's encode rows.  Staged
                # sources bypass the prefix cache both ways: an exact-hit
                # would have no reason to stage (hits skip the encoder),
                # and inserting a chain would force the monolithic
                # encode layout this path exists to avoid.
                staged.append(req)
            else:
                live.append(req)
        misses, hits = self.assign_prefix(live)
        if misses:
            src, lens = pad_batch([r.src for r in misses], length=enc_len)
            src, lens, width = pad_rows_pow2(src, lens)
            base = np.full((width,), oob_row, np.int32)
            base[:len(misses)] = [r.slot for r in misses]
        else:
            width = 0
            src = np.zeros((0, enc_len), np.int32)
            lens = base = np.zeros((0,), np.int32)
        plan = AdmissionPlan(requests=misses, released=released,
                             src_tokens=np.ascontiguousarray(src),
                             src_lengths=np.ascontiguousarray(lens),
                             base_rows=base, width=width,
                             resumed=resumed, staged=staged)
        if self.prefix_cache is not None:
            plan.ins_pages = self.chain_pages_matrix(misses, width, enc_len)
            if hits:
                (plan.hit_rows, plan.hit_lengths, plan.hit_pages,
                 plan.hit_width) = self.shape_hits(hits, enc_len=enc_len,
                                                   oob_row=oob_row)
                plan.hits = hits
        return plan

    def release(self, req: Request, now: float = 0.0, *,
                step: Optional[int] = None) -> int:
        """Finish a running request and return its freed group base row
        (all ``group_size`` rows of the group are freed atomically).

        ``step``: the exact global decode step the request finished at —
        inside a burst this is finer-grained than ``now``, which is only
        observed at the burst edge.
        """
        if req.status != "running" or req.slot is None:
            raise ValueError(f"request {req.req_id} is not running "
                             f"(status={req.status})")
        slot = req.slot
        req.status = "finished"
        req.finish_s = now
        req.finish_step = step
        req.slot = None
        if req.pages is not None:
            self.allocator.release(req.pages)
            req.pages = None
        if req.reserved_pages:
            self.allocator.unreserve(req.reserved_pages)
            req.reserved_pages = 0
        if req.prefix_chain is not None:
            self.prefix_cache.finish(req.prefix_chain)
            req.prefix_chain = None
        del self.slot_map[slot]
        self._free.append(slot)
        self._free.sort()
        self.finished.append(req)
        return slot

    # ------------------------------------------------------------ inspection
    @property
    def n_free(self) -> int:
        """Free slot *groups* (== free rows when ``group_size == 1``)."""
        return len(self._free)

    @property
    def n_running(self) -> int:
        return len(self.slot_map)

    @property
    def n_waiting(self) -> int:
        return len(self._waiting)

    @property
    def all_done(self) -> bool:
        return not self._waiting and not self.slot_map


class BatchQueue:
    """Thread-safe queue feeding the worker streams (paper Fig. 6)."""

    def __init__(self, items: Optional[Sequence[WorkItem]] = None):
        self._q: queue.Queue = queue.Queue()
        self._lock = threading.Lock()
        self.enqueued = 0
        if items:
            for item in items:
                self.put(item)

    def put(self, item: WorkItem) -> None:
        with self._lock:
            self.enqueued += 1
        self._q.put(item)

    def close(self, n_consumers: int) -> None:
        for _ in range(n_consumers):
            self._q.put(None)

    def get(self) -> Optional[WorkItem]:
        return self._q.get()
