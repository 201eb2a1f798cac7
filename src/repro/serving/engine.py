"""Serving engine: prefill + auto-regressive decode (greedy, beam, continuous).

This is the paper's workload: batched NMT inference with a decoder
while-loop.  Beam search reorders the KV cache every step through
``kv_cache.gather_beams`` — the GatherNd the paper quantized (§5.3); with an
INT8 cache the reorder moves 4× fewer bytes.

Beyond the paper's static batches, :meth:`ServingEngine.serve` implements
**continuous batching**: a fixed pool of ``n_slots`` decode rows runs one
shared decode step; when a sequence finishes, its KV-cache slot is refilled
by prefilling the next waiting request (``kv_cache.insert_at_slots``) while
the other slots keep decoding.  Admission order and pacing come from
``scheduler.ContinuousScheduler``; prefill side-batches are padded to
power-of-two widths so the whole serve compiles O(log slots) programs.
Greedy decode through ``serve`` is token-identical to per-request
:meth:`generate` — every per-row computation is batch-independent.

``serve(beam=B)`` extends continuous batching to **beam search**: a request
occupies a *group* of ``B`` contiguous rows, the scheduler admits/releases
whole groups, and the decode burst runs the beam-search body (top-k +
device-side cache reorder — the paper's §5.3 GatherNd) with per-group
budget/finished masks so groups at different lifecycle stages share one
grid.  Finished groups are drained and refilled at burst edges; output is
token-identical to per-request :meth:`generate_beam` for every
``burst_len``, with FP or INT8 KV cache.

**Decode bursts.**  The per-token serving loop used to dispatch one jitted
step per token and synchronize with the host every step (``np.asarray`` of
the argmax) — framework dispatch, not math, dominated small per-step work
(the paper §5.5; Quinn & Ballesteros arXiv:1804.05038 for CPU NMT).  All
three decode paths now run **bursts of up to ``burst_len`` steps entirely
on device** inside one jitted ``lax.while_loop``: argmax, EOS masking,
per-row budget countdown, and a ``(rows, K)`` token ring buffer live in the
loop carry, and the host is touched only at burst boundaries, where the
scheduler drains tokens, releases finished slots and refills them.  A burst
exits early once every row is finished, so ``burst_len=1`` exactly
reproduces the per-step loop (token-identical for every ``burst_len``);
rows that finish mid-burst keep computing but are masked to EOS — the
utilization cost ``benchmarks/bench_decode_burst.py`` quantifies against
the saved host round trips.  Burst lengths are bucketed to powers of two
(``data.sorting.next_pow2``): the compiled ring-buffer width is the bucket,
the *actual* step cap is a device scalar, so sweeping ``burst_len`` costs
O(log K) compiles.

**Fused admission.**  Decode bursts left one host dispatch per admission
round: refilling freed slots ran a separate jitted prefill and drained its
first token before the next burst could start.  With
``fused_admission=True`` (the default) an admission round is folded *into*
the burst program: the padded admitted sources ride along as device
inputs, and the program encodes them, splices their cross-K/V into the
grid rows (``encdec.splice_prefill``), resets the spliced rows' KV
cursors, seeds BOS tokens, and then runs the decode ``while_loop`` — the
spliced rows' first step *is* the BOS prefill step, so a serve round is
exactly one dispatch and one device→host sync whether or not it admitted.
Beam groups additionally encode each admitted source **once** and
broadcast the memory/cross-KV across the group's ``beam`` rows (the old
side-batch prefill tiled the source ``beam`` times — ``beam×`` encoder
FLOPs for identical rows); the group's first-step top-k falls out of the
shared beam step by seeding row 0 with score 0 and rows ``1..B-1`` with
``-1e30``, which reproduces ``generate_beam``'s beam-0 top-k exactly.
Output is token-identical to the unfused path (and therefore to
per-request ``generate``/``generate_beam``) for every ``burst_len``, FP
and INT8 cache; ``ServeResult.prefill_dispatches`` stays 0 and
``encoder_tokens`` drops ``beam×`` for beam serving.

``burst_len="auto"`` puts the step cap under the
``burst_control.AdaptiveBurst`` controller: the compiled ring width stays
pinned at the max power-of-two bucket while the device-scalar cap
shrinks/grows between bursts as measured mid-burst EOS waste crosses the
measured per-sync cost — adapting never triggers a new compile.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec

from repro.core.ptq import FP_CONTEXT, QuantContext
from repro.data.sorting import next_pow2
from repro.data.synthetic import EOS, pad_batch
from repro.distributed.fault import StepWatchdog
from repro.kernels.ops import resolve_impl
from repro.models import kv_cache as kvc
from repro.serving.sharding import decode_state_shardings, mesh_axis_sizes, \
    param_shardings, tp_degree
from repro.serving.burst_control import AdaptiveBurst
from repro.serving.chaos import ChaosSchedule
from repro.serving.preemption import SpilledRequest, SpillStore, pick_victims
from repro.serving.prefix_cache import PrefixCache
from repro.serving.scheduler import ContinuousScheduler, Request, \
    pad_rows_pow2

# new-group beam-score seed: row 0 scores 0, rows 1..B-1 score so low that
# the shared beam step's group top-k can only draw candidates from row 0 —
# which reproduces generate_beam's first-step "top-k over beam-0 logits"
# without a special-cased first step (see _make_fused_beam_serve_burst)
BEAM_SEED_NEG = np.float32(-1e30)

# compiled ring-buffer bucket for burst_len="auto": the AdaptiveBurst cap
# moves as a device scalar inside [1, AUTO_MAX_BURST] — one compile total
AUTO_MAX_BURST = 64


def _partitionable(quant: Optional[QuantContext]) -> Optional[QuantContext]:
    """``quant`` with its kernel choice moved off the Pallas kernels, for
    a program GSPMD has to partition across several devices."""
    if quant is None or resolve_impl(quant.impl) != "pallas":
        return quant
    return dataclasses.replace(quant, impl="xla")


def on_whole_rows(fn: Callable, mesh=None) -> Callable:
    """``fn`` run whole on every device of ``mesh``, over replicated
    operands; ``fn`` itself without a mesh of several devices.

    The TPU compiler rounds a float reduction differently at another
    per-device shape (attention over 2 of 8 heads, a softmax over a
    quarter of the vocab) or when partial sums cross devices, so a tp>1
    program that splits one can stop matching tp=1 bit for bit.  Run
    whole, each device computes the one-device program; ``shard_map``
    keeps GSPMD from partitioning it again."""
    if mesh is None or mesh.size == 1:
        return fn
    return jax.shard_map(fn, mesh=mesh, in_specs=PartitionSpec(),
                         out_specs=PartitionSpec(), check_vma=False)


def log_probs(logits: jax.Array, mesh=None) -> jax.Array:
    """f32 log-softmax over the vocab, as beam search ranks with it: on a
    mesh, each device normalises whole rows (:func:`on_whole_rows`), since
    a last-bit difference in the normaliser flips near-tied hypotheses."""
    return on_whole_rows(
        lambda x: jax.nn.log_softmax(x.astype(jnp.float32), axis=-1),
        mesh)(logits)


def _spec_accept(d: jax.Array, v: jax.Array, remaining: jax.Array,
                 eos: int) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Speculative-decoding acceptance: longest agreeing prefix + bonus.

    ``d``: (B, s) drafted tokens; ``v``: (B, s+1) verifier greedy tokens
    over the same positions (``v[:, j]`` is what sequential decode would
    emit after accepting ``j`` drafts); ``remaining``: (B,) per-row token
    budgets (0 ⇔ inactive row).

    Returns ``(stop, hit_eos, accepted)``: ``stop`` (B,) is how many of
    ``v[:, :stop]`` this macro-step emits — the longest prefix where the
    draft agrees with the verifier, plus the verifier's first correction
    token, clamped by the first verifier EOS (emitted, then the row stops,
    exactly like the sequential loop) and by the budget; ``hit_eos``
    marks rows whose emitted window ends in EOS; ``accepted`` counts the
    emitted tokens that came from the draft (the acceptance-rate
    numerator).  Rows with ``remaining == 0`` emit nothing.
    """
    s = d.shape[1]
    active = remaining > 0
    agree = jnp.cumprod((d == v[:, :s]).astype(jnp.int32), axis=1)
    a = jnp.sum(agree, axis=1)                  # longest agreeing prefix
    cand = a + 1                                # + verifier's correction
    idx = jnp.arange(s + 1, dtype=jnp.int32)[None, :]
    eos_first = jnp.min(jnp.where(v == eos, idx, s + 1), axis=1)
    stop = jnp.minimum(jnp.minimum(cand, eos_first + 1), remaining)
    stop = jnp.where(active, stop, 0)
    hit_eos = active & (eos_first + 1 <= jnp.minimum(cand, remaining))
    accepted = jnp.minimum(a, stop)
    return stop, hit_eos, accepted


@dataclasses.dataclass
class GenerationResult:
    tokens: List[np.ndarray]          # per-sequence generated ids (no EOS)
    steps: int
    prefill_s: float
    decode_s: float
    host_syncs: int = 0               # device→host round trips (prefill + bursts)
    speculative_k: int = 0            # draft window (0 = plain decode)
    draft_tokens: int = 0             # tokens proposed by the draft model
    accepted_tokens: int = 0          # drafted tokens the verifier kept

    @property
    def acceptance_rate(self) -> float:
        return self.accepted_tokens / max(self.draft_tokens, 1)

    @property
    def total_s(self) -> float:
        return self.prefill_s + self.decode_s

    @property
    def n_tokens(self) -> int:
        return int(sum(len(t) for t in self.tokens))

    @property
    def tokens_per_s(self) -> float:
        return self.n_tokens / max(self.total_s, 1e-9)

    @property
    def decode_steps_per_s(self) -> float:
        # steps counts grid columns; the first column is emitted by prefill,
        # outside the decode_s window, so it is discounted here
        return max(self.steps - 1, 0) / max(self.decode_s, 1e-9)


@dataclasses.dataclass
class ServeResult:
    """Outcome of one continuous-batching serve.

    With ``beam > 1`` every request occupied a group of ``beam`` decode
    rows: ``n_slots`` still counts *rows*, ``busy_slot_steps`` counts all
    rows of a busy group (so ``utilization`` stays an occupied-row
    fraction of the computed grid), and each ``Request.tokens`` holds the
    group's *winning* hypothesis (``Request.score`` its length-penalized
    log-prob).
    """

    requests: List[Request]           # submission order, lifecycle filled in
    n_slots: int
    decode_steps: int
    busy_slot_steps: int              # Σ over steps of occupied rows
    prefill_rounds: int               # admission rounds (fused or not)
    wall_s: float
    host_syncs: int = 0               # device→host round trips (prefill + bursts)
    burst_len: int = 1                # final step cap (adapts when auto_burst)
    beam: int = 1                     # rows per request group (1 = greedy)
    prefill_dispatches: int = 0       # host-dispatched prefill programs
    #                                   (0 ⇔ admissions rode the burst program)
    encoder_tokens: int = 0           # encoder row-tokens computed for
    #                                   admissions (beam× lower when fused)
    fused_admission: bool = True
    auto_burst: bool = False          # burst_len ran under AdaptiveBurst
    paged: bool = False               # KV cache was paged (block tables)
    page_size: int = 0
    pages_in_use: int = 0             # allocator pages still held at the end
    page_hwm: int = 0                 # peak concurrent pages over the serve
    reorder_bytes: int = 0            # total bytes beam reorders moved
    #                                   (slab gathers unpaged; block-table
    #                                   permutation + partial-page copy paged)
    # paged decode attention: over the decode steps, layers and occupied
    # rows that busy_slot_steps counts (one-token steps; speculative
    # macro-steps are left out), the pages the kernel copies,
    # ceil(len / page_size), and the block-table slots, max_len / page_size
    kv_pages_read: int = 0
    kv_page_slots: int = 0
    # cross-request prefix cache (per-serve deltas; the cache itself —
    # tree, chains, pool — persists on the engine across serves)
    prefix_cache: bool = False
    prefix_hits: int = 0              # admissions that skipped the encoder
    prefix_misses: int = 0
    prefix_inserts: int = 0           # misses that cached their encode
    prefix_evictions: int = 0
    prefix_hit_pages: int = 0         # chain pages hits read instead of wrote
    prefix_pages_allocated: int = 0   # chain pages reserved by this serve
    prefix_chains: int = 0            # chains resident at serve end
    # overload machinery (preempt-by-page-spill / deadline admission /
    # chunked prefill — all zero on a serve that never hit pressure)
    overcommit: float = 1.0           # reserve cap ÷ physical pool size
    preemptions: int = 0              # evictions (chaos-forced + pressure)
    spill_events: int = 0             # KV page sets copied to host
    restore_events: int = 0           # spills re-spliced on re-admission
    spilled_bytes: int = 0            # cumulative host bytes spilled
    straggler_rounds: int = 0         # watchdog-flagged burst rounds
    chunked_admissions: int = 0       # requests whose prefill was staged
    chunk_rounds: int = 0             # staged encoder dispatches
    peak_running: int = 0             # max concurrent running requests
    rejected: int = 0                 # requests shed (deadline unmeetable)
    deadline_misses: int = 0          # shed + finished past their deadline
    free_lwm: int = 0                 # page free-list low-water mark
    fragmentation: float = 0.0        # final free-list scatter in [0, 1]
    # self-speculative decoding (draft with draft_quant, verify with the
    # engine quant context — greedy output stays bit-identical to the
    # non-speculative path by construction)
    speculative_k: int = 0            # draft window (0 = speculation off)
    draft_tokens: int = 0             # tokens proposed by the draft passes
    accepted_tokens: int = 0          # drafted tokens the verifier kept
    # multi-chip serving: tensor-parallel burst (mesh on the engine) and/or
    # data-parallel replicas (ReplicaRouter sets ``replicas`` post-merge)
    mesh_shape: Tuple[int, ...] = ()  # mesh axis sizes, () = unsharded
    tp_degree: int = 1                # "model"-axis width the burst ran at
    replicas: int = 1                 # engine replicas behind the router
    collective_bytes_per_step: int = 0  # predicted per-device wire bytes
    #                                     per decode step (ring all-reduce)

    @property
    def acceptance_rate(self) -> float:
        """Fraction of drafted tokens the verifier accepted (0 when
        speculation was off — no drafts were proposed)."""
        return self.accepted_tokens / max(self.draft_tokens, 1)

    @property
    def n_groups(self) -> int:
        """Request groups the decode grid holds (== n_slots for greedy)."""
        return self.n_slots // self.beam

    @property
    def n_tokens(self) -> int:
        return int(sum(len(r.tokens) for r in self.requests))

    @property
    def utilization(self) -> float:
        """Occupied-row fraction of the decode grid actually computed.

        Beam-group aware: a busy group accounts for all ``beam`` of its
        rows.  ``n_slots`` is the *computed* grid — rows a non-dividing
        ``beam`` would strand are trimmed before the serve (``n_slots``
        here is already the trimmed row count), so the starvation cost of
        a coarse beam shows up as fewer group servers (and in
        ``simulate_continuous(..., beam=B)``'s ``idle_rows``), not as a
        deflated utilization.
        """
        return self.busy_slot_steps / max(self.n_slots * self.decode_steps, 1)

    @property
    def tokens_per_s(self) -> float:
        return self.n_tokens / max(self.wall_s, 1e-9)

    @property
    def decode_steps_per_s(self) -> float:
        return self.decode_steps / max(self.wall_s, 1e-9)

    def tokens_for(self, req_id: int) -> np.ndarray:
        """Generated ids for one request — the winning hypothesis when the
        serve ran with ``beam > 1`` (one row per request otherwise)."""
        for r in self.requests:
            if r.req_id == req_id:
                return np.asarray(r.tokens, np.int32)
        raise KeyError(req_id)

    def metrics(self) -> Dict[str, float]:
        first = [r.first_token_latency_s for r in self.requests
                 if r.first_token_latency_s is not None]
        total = [r.total_latency_s for r in self.requests
                 if r.total_latency_s is not None]
        return {
            "n_requests": float(len(self.requests)),
            "n_tokens": float(self.n_tokens),
            "beam": float(self.beam),
            "n_groups": float(self.n_groups),
            "wall_s": self.wall_s,
            "tokens_per_s": self.tokens_per_s,
            "utilization": self.utilization,
            "decode_steps": float(self.decode_steps),
            "decode_steps_per_s": self.decode_steps_per_s,
            "host_syncs": float(self.host_syncs),
            "burst_len": float(self.burst_len),
            "prefill_rounds": float(self.prefill_rounds),
            "prefill_dispatches": float(self.prefill_dispatches),
            "encoder_tokens": float(self.encoder_tokens),
            "paged": float(self.paged),
            "pages_in_use": float(self.pages_in_use),
            "page_hwm": float(self.page_hwm),
            "reorder_bytes": float(self.reorder_bytes),
            "kv_pages_read": float(self.kv_pages_read),
            "kv_page_slots": float(self.kv_page_slots),
            "prefix_cache": float(self.prefix_cache),
            "prefix_hits": float(self.prefix_hits),
            "prefix_misses": float(self.prefix_misses),
            "prefix_inserts": float(self.prefix_inserts),
            "prefix_evictions": float(self.prefix_evictions),
            "prefix_hit_pages": float(self.prefix_hit_pages),
            "prefix_pages_allocated": float(self.prefix_pages_allocated),
            "prefix_chains": float(self.prefix_chains),
            "prefix_hit_rate": (self.prefix_hits /
                                max(self.prefix_hits + self.prefix_misses, 1)),
            "overcommit": float(self.overcommit),
            "preemptions": float(self.preemptions),
            "spill_events": float(self.spill_events),
            "restore_events": float(self.restore_events),
            "spilled_bytes": float(self.spilled_bytes),
            "straggler_rounds": float(self.straggler_rounds),
            "chunked_admissions": float(self.chunked_admissions),
            "chunk_rounds": float(self.chunk_rounds),
            "peak_running": float(self.peak_running),
            "rejected": float(self.rejected),
            "deadline_misses": float(self.deadline_misses),
            "free_lwm": float(self.free_lwm),
            "fragmentation": float(self.fragmentation),
            "speculative_k": float(self.speculative_k),
            "draft_tokens": float(self.draft_tokens),
            "accepted_tokens": float(self.accepted_tokens),
            "acceptance_rate": self.acceptance_rate,
            "tp_degree": float(self.tp_degree),
            "replicas": float(self.replicas),
            "collective_bytes_per_step":
                float(self.collective_bytes_per_step),
            "first_token_latency_mean_s": float(np.mean(first)) if first else 0.0,
            "first_token_latency_p95_s":
                float(np.percentile(first, 95)) if first else 0.0,
            "total_latency_mean_s": float(np.mean(total)) if total else 0.0,
            "total_latency_p95_s":
                float(np.percentile(total, 95)) if total else 0.0,
        }


class ServingEngine:
    def __init__(self, model, params, *, quant: QuantContext = FP_CONTEXT,
                 max_len: int = 256, eos_id: int = EOS,
                 donate_state: bool = True,
                 burst_len: Union[int, str] = 8,
                 paged: bool = False, page_size: int = 16,
                 n_pages: Optional[int] = None,
                 admission_enc_bucket: str = "max",
                 prefix_cache: bool = False,
                 prefix_pages: int = 256,
                 prefix_page_size: Optional[int] = None,
                 draft_quant: Optional[QuantContext] = None,
                 mesh=None, device=None):
        if mesh is not None and device is not None:
            raise ValueError("pass a mesh or a device, not both")
        self.model = model
        # tensor-parallel serving: with a ("data","model") mesh the burst
        # programs compile as ONE SPMD program — GSPMD places the per-layer
        # all-reduces inside the lax.while_loop, so a serve round stays one
        # dispatch + one host sync.  We only *place* the inputs, both by
        # serving.sharding: weights by the training tensor rules (fsdp off)
        # with the encoder's replicated, the decode state with K/V pools
        # split on heads and host-facing buffers replicated.
        # Without a mesh, ``device`` pins the params and every decode state
        # to one device (a router replica), else they stay where they are.
        self.mesh = mesh
        self.device = device
        self.tp = tp_degree(mesh)
        if mesh is not None:
            params = jax.device_put(params, param_shardings(
                params, mesh, kv_heads=model.cfg.n_kv_heads))
        elif device is not None:
            params = jax.device_put(params, device)
        self.params = params
        # GSPMD cannot partition a Pallas (Mosaic) kernel — lowering one
        # under a multi-device sharding raises — so a sharded engine runs
        # the kernels' XLA forms, which it partitions like any other op
        if mesh is not None and mesh.size > 1:
            quant = _partitionable(quant)
            draft_quant = _partitionable(draft_quant)
        self.quant = quant
        # speculative decoding draft context: the k cheap draft steps run
        # with these weights/activations (e.g. INT8 while ``quant`` is FP —
        # the paper's <0.5% quality gap is exactly the regime where such
        # drafts are accepted almost always).  None → draft with ``quant``
        # itself (degenerate self-speculation, acceptance 1.0).  The KV
        # cache layout always follows ``quant`` — the verifier owns every
        # cache entry past the accepted cursor, which is what makes greedy
        # output bit-identical to the non-speculative ``quant`` path.
        self.draft_quant = quant if draft_quant is None else draft_quant
        self.max_len = max_len
        self.eos_id = eos_id
        if burst_len != "auto":
            burst_len = int(burst_len)
            if burst_len < 1:
                raise ValueError(f"burst_len must be ≥ 1, got {burst_len}")
        self.burst_len = burst_len
        self._donate_state = donate_state
        # paged KV cache (serve() paths): fixed-size pages + block tables;
        # max_len must be a page multiple so the paged logical view has
        # exactly the contiguous shape (bit-identical numerics).
        self.paged = bool(paged)
        self.page_size = int(page_size)
        self.n_pages = n_pages
        if self.paged and max_len % self.page_size:
            raise ValueError(f"paged cache needs max_len % page_size == 0, "
                             f"got {max_len} % {self.page_size}")
        if admission_enc_bucket not in ("max", "exact"):
            raise ValueError("admission_enc_bucket must be 'max' or "
                             f"'exact', got {admission_enc_bucket!r}")
        self.admission_enc_bucket = admission_enc_bucket
        self._enc_bucket_hwm = 0
        # cross-request prefix cache: persists ACROSS serve() calls on this
        # engine (the pool's page granularity makes its device shape
        # independent of any one serve's enc_len or grid size).  Built
        # lazily so engines that never enable it pay nothing.
        self.prefix_cache_default = bool(prefix_cache)
        self.prefix_pages = int(prefix_pages)
        self.prefix_page_size = int(prefix_page_size or page_size)
        self._prefix_cache_obj: Optional[PrefixCache] = None
        self._prefix_pool: Optional[Tuple[jax.Array, jax.Array]] = None
        self._pool_insert_jit: Optional[Callable] = None
        self._hit_splice_jits: Dict[int, Callable] = {}

        # the encoder (once per request) runs whole on every device of a
        # mesh, so its cross-K/V match tp=1 bit for bit (on_whole_rows)
        encode = on_whole_rows(
            lambda p, b: model.encode_cross_kv(p, b, quant=quant), mesh)
        self._encode = encode

        def prefill(p, b, s):
            # model.prefill, with the encoder run as above
            ck, cv, slens = encode(p, b)
            s = dict(s, cross_k=ck, cross_v=cv, src_lengths=slens)
            return model.decode_step(p, jnp.zeros((ck.shape[1],), jnp.int32),
                                     s, quant=quant)

        self._prefill = jax.jit(prefill)
        # continuous-batching row splice: scatter a prefilled side-batch into
        # the long-lived decode state.  Donates the old state/token buffers —
        # the caller always rebinds to the returned ones.
        self._insert = jax.jit(self._insert_rows, donate_argnums=(0, 2))
        # paged variant (unfused admission): the side batch prefills into a
        # plain contiguous cache, then its rows are page-chunked into the
        # destination rows' reservations and the block tables installed
        self._insert_paged = jax.jit(self._insert_rows_paged,
                                     donate_argnums=(0, 2))
        # burst programs, keyed by compiled ring-buffer width (greedy) or
        # (width, beam) — power-of-two bucketed, so O(log K) entries.  The
        # fused-admission variants additionally respecialize (inside
        # jax.jit's own shape cache) per pow2 admission width × enc_len.
        self._burst_jits: Dict[int, Callable] = {}
        self._beam_burst_jits: Dict[Tuple[int, int], Callable] = {}
        self._beam_serve_jits: Dict[Tuple[int, int], Callable] = {}
        self._fused_burst_jits: Dict[int, Callable] = {}
        self._fused_beam_serve_jits: Dict[Tuple[int, int], Callable] = {}
        # speculative burst programs, keyed (ring width, speculative_k)
        self._spec_burst_jits: Dict[Tuple[int, int], Callable] = {}
        self._spec_fused_burst_jits: Dict[Tuple[int, int], Callable] = {}
        # overload machinery: preempt-by-page-spill gathers/scatters,
        # overcommit page growth, and chunked-prefill staged encodes —
        # keyed by row count (1 greedy, group width beam) / encoder layer
        self._spill_jits: Dict[int, Callable] = {}
        self._resume_jits: Dict[int, Callable] = {}
        self._grow_jits: Dict[int, Callable] = {}
        self._chunk_splice_jits: Dict[int, Callable] = {}
        self._stage_begin_jit: Optional[Callable] = None
        self._stage_finish_jit: Optional[Callable] = None
        self._stage_layer_jits: Dict[int, Callable] = {}

    # ------------------------------------------------------------------ util
    def _init_state(self, batch_size: int):
        return self._shard_state(self.model.init_decode_state(
            batch_size, self.max_len, quantized=self.quant.quantize_kv))

    def _shard_state(self, state):
        """Place a fresh decode state on the engine mesh: K/V pools (self,
        cross, prefix) split on the heads axis, block tables / cursors /
        token buffers replicated.  Without a mesh, on the engine's device
        if it has one."""
        if self.mesh is None:
            return (state if self.device is None
                    else jax.device_put(state, self.device))
        cfg = self.model.cfg
        return jax.device_put(state, decode_state_shardings(
            state, self.mesh, kv_heads=cfg.n_kv_heads, head_dim=cfg.hd))

    def compile_burst(self, n_slots: int,
                      enc_len: int) -> jax.stages.Compiled:
        """Ahead-of-time compile of the greedy decode burst that
        :meth:`serve` dispatches between admissions, for an ``n_slots``-row
        grid over sources padded to ``enc_len`` — the serve's own burst
        width, cache layout and placement.  ``as_text()`` of the result
        shows which kernels the program calls."""
        K = self._resolve_burst(None)
        width = next_pow2(AUTO_MAX_BURST if K == "auto" else K)
        state = self._shard_state(self.model.init_decode_state(
            n_slots, self.max_len, quantized=self.quant.quantize_kv,
            enc_len=enc_len, paged=self.paged, page_size=self.page_size,
            n_pages=(self._make_allocator(n_slots).n_pages if self.paged
                     else None)))
        rows = jnp.zeros((n_slots,), jnp.int32)
        return self._greedy_burst_fn(width).lower(
            self.params, rows, rows, jnp.int32(width), state).compile()

    def _mesh_result_fields(self, rows: int) -> Dict[str, Any]:
        """ServeResult kwargs describing the mesh the serve ran on."""
        if self.mesh is None:
            return {}
        from repro.launch.roofline import decode_collective_bytes
        cfg = self.model.cfg
        return dict(
            mesh_shape=mesh_axis_sizes(self.mesh),
            tp_degree=self.tp,
            collective_bytes_per_step=decode_collective_bytes(
                n_layers=cfg.n_layers, d_model=cfg.d_model, rows=rows,
                tp=self.tp, act_bytes=cfg.activation_dtype.itemsize,
                vocab=cfg.vocab))

    def _resolve_burst(self, burst_len: Optional[Union[int, str]]
                       ) -> Union[int, str]:
        """Resolve a call-site burst length: an int cap, or the sentinel
        ``"auto"`` (serve puts the cap under :class:`AdaptiveBurst`)."""
        k = self.burst_len if burst_len is None else burst_len
        if isinstance(k, str):
            if k == "auto":
                return "auto"
            raise ValueError(
                f"burst_len must be an int ≥ 1 or 'auto', got {k!r}")
        k = int(k)
        if k < 1:
            raise ValueError(f"burst_len must be ≥ 1, got {k}")
        return k

    def _check_overload_args(self, overcommit: float,
                             prefill_chunk: Optional[int],
                             chaos: Optional[ChaosSchedule],
                             fused_admission: bool) -> None:
        if overcommit < 1.0:
            raise ValueError(f"overcommit must be >= 1.0, got {overcommit}")
        if overcommit > 1.0 and not self.paged:
            raise ValueError("overcommit needs the paged KV cache "
                             "(preempt-by-page-spill backs it)")
        if chaos is not None and not self.paged:
            raise ValueError("chaos preemption needs the paged KV cache "
                             "(spill/restore move pages)")
        if prefill_chunk is not None:
            if prefill_chunk < 1:
                raise ValueError(f"prefill_chunk must be >= 1, "
                                 f"got {prefill_chunk}")
            if not fused_admission:
                raise ValueError("prefill_chunk requires fused_admission "
                                 "(staged encodes ride the fused rounds)")

    def _burst_controller(self, K: Union[int, str]
                          ) -> Optional[AdaptiveBurst]:
        """An :class:`AdaptiveBurst` when ``K == "auto"``, else None."""
        if K != "auto":
            return None
        start = self.burst_len if isinstance(self.burst_len, int) else 8
        return AdaptiveBurst(start=start, max_burst=AUTO_MAX_BURST)

    def compiled_variants(self) -> Optional[int]:
        """Compiled burst-program variants held by this engine — the outer
        pow2-bucketed builders times jax.jit's inner shape cache (fused
        admission respecializes per admission width × enc_len).  The
        ``admission_enc_bucket`` regression in ``bench_continuous.py``
        asserts this stops growing with the source-length mix.

        Returns None when the jax version exposes no per-function cache
        introspection (``_cache_size``), so callers skip the comparison
        instead of asserting on degenerate equal counts.
        """
        n = 0
        for d in (self._burst_jits, self._beam_burst_jits,
                  self._beam_serve_jits, self._fused_burst_jits,
                  self._fused_beam_serve_jits):
            for fn in d.values():
                size = getattr(fn, "_cache_size", None)
                if not callable(size):
                    return None
                n += size()
        return n

    def _enc_bucket(self, reqs: Sequence[Request], m: int) -> int:
        """Admission ``enc_len`` bucket for one serve.

        ``admission_enc_bucket="exact"`` keeps the historical behaviour —
        the serve's max source length rounded to ``pad_to_multiple`` — so
        every distinct length mix compiles its own burst-program variant
        (the cross-K/V state buffers and fused admission inputs are all
        ``enc_len``-shaped).  ``"max"`` (default) pads to a power-of-two
        bucket held monotone across serves on this engine: a sweep over
        many source-length mixes converges onto ONE variant per (ring
        bucket × admission width) once the largest bucket has been seen.
        Padding is masked (hard ``where`` on ``src_lengths``), so tokens
        are identical either way.
        """
        enc_len = max(r.n_src_tokens for r in reqs)
        enc_len = ((enc_len + m - 1) // m) * m
        if self.admission_enc_bucket == "exact":
            return enc_len
        self._enc_bucket_hwm = max(self._enc_bucket_hwm, next_pow2(enc_len))
        return self._enc_bucket_hwm

    # ------------------------------------------------------------- paged util
    @property
    def _max_pages(self) -> int:
        return self.max_len // self.page_size

    def _make_allocator(self, n_rows: int,
                        overcommit: float = 1.0) -> kvc.PageAllocator:
        """Fresh page pool for one serve: ``n_pages`` from the constructor,
        or contiguous-equivalent capacity (every grid row could hold
        ``max_len`` tokens) when unset.  ``overcommit`` scales the
        *virtual* reservation cap past the physical pool (preemption by
        page spill covers the gap)."""
        n_pages = self.n_pages or n_rows * self._max_pages
        return kvc.PageAllocator(n_pages, self.page_size,
                                 overcommit_limit=overcommit)

    def _initial_pages(self, req: Request, rows: int, hint: int) -> int:
        """Pages physically allocated at (re-)admission under overcommit:
        enough to hold what the request already decoded (its spill cursor
        when resuming) plus one max-length burst — growth covers the rest,
        round by round."""
        have = 0
        if req.spill is not None:
            have = int(np.max(req.spill.lengths))
        cap_tok = min(req.max_new_tokens, self.max_len)
        return rows * kvc.pages_per_row(min(have + hint, cap_tok),
                                        self.page_size)

    def _pages_per_request(self, req: Request, rows: int) -> int:
        """Worst-case reservation: the request's full decode budget, per
        *live* row (parked rows of a narrow beam reserve nothing)."""
        return rows * kvc.pages_per_row(
            min(req.max_new_tokens, self.max_len), self.page_size)

    def _kv_page_counts(self, done: int, steps: int,
                        rows: int) -> Tuple[int, int]:
        """Pages the paged decode kernel copies, and the block-table slots,
        for ``rows`` rows over ``steps`` one-token decode steps that follow
        ``done`` earlier ones, summed over the layers.  A step copies
        ceil(len / page_size) pages of each row, ``len`` counting the
        position the step appends."""
        ps = self.page_size

        def upto(n):       # Σ_{k=1..n} ceil(k / ps), in closed form
            q, r = divmod(n, ps)
            return ps * q * (q + 1) // 2 + r * (q + 1)

        per_layer = rows * self.model.cfg.n_layers
        return (per_layer * (upto(done + steps) - upto(done)),
                per_layer * steps * (self.max_len // ps))

    def _page_rows(self, reqs: Sequence[Request], rows_per_req: int,
                   n_req_rows: int, sentinel: int,
                   widths: Optional[Sequence[int]] = None) -> np.ndarray:
        """Shape admitted requests' page reservations as device input:
        (n_req_rows × rows_per_req, maxP) int32, sentinel-padded — padding
        requests, parked rows, and each row's tail past its reservation
        all read as sentinel (writes there drop)."""
        maxP = self._max_pages
        out = np.full((n_req_rows * rows_per_req, maxP), sentinel, np.int32)
        for i, r in enumerate(reqs):
            live = widths[i] if widths is not None else rows_per_req
            flat = np.asarray(r.pages, np.int32)
            if flat.size == 0:
                continue
            ppr = flat.size // live
            per_row = flat.reshape(live, ppr)
            out[i * rows_per_req:i * rows_per_req + live, :ppr] = per_row
        return out

    # ------------------------------------------------- preempt-by-page-spill
    def _spill_fn(self, n_rows: int) -> Callable:
        """Jitted spill gather: linearize ``n_rows`` paged cache rows into
        logical ``(L, W, cap, …)`` views (INT8 payload + scales verbatim —
        no requantization round trip) plus cursors, current tokens, and
        cross-K/V.  One dispatch + one host sync per preemption; junk past
        each cursor rides along and is masked on restore exactly like any
        partially filled row.  NOT donating: the live state survives."""
        fn = self._spill_jits.get(n_rows)
        if fn is None:
            def spill(state, tokens, rows):
                cache = state["cache"]
                P = cache.n_pages
                cap = cache.max_pages * cache.page_size
                tb = jnp.clip(cache.block_tables[rows], 0, P - 1)

                def lin(pool):
                    if pool is None:
                        return None
                    got = pool[:, tb]          # (L, W, maxP, ps, …)
                    return got.reshape((pool.shape[0], n_rows, cap)
                                       + pool.shape[3:])

                return (lin(cache.k), lin(cache.v), lin(cache.k_scale),
                        lin(cache.v_scale), cache.lengths[rows],
                        tokens[rows], state["cross_k"][:, rows],
                        state["cross_v"][:, rows],
                        state["src_lengths"][rows])

            fn = jax.jit(spill)
            self._spill_jits[n_rows] = fn
        return fn

    def _resume_fn(self, n_rows: int) -> Callable:
        """Jitted resume scatter: the spilled logical rows become a host-
        built contiguous side batch and re-enter through the SAME paged
        splice admission uses (``kv_cache.insert_rows_paged``), plus the
        cross-K/V / source-length / current-token scatters — so a resumed
        request is indistinguishable from one that was never preempted."""
        fn = self._resume_jits.get(n_rows)
        if fn is None:
            def resume(state, tokens, slots, pages, k, v, ks, vs, lengths,
                       row_tokens, ck, cv, slens):
                sub = kvc.KVCache(k=k, v=v, k_scale=ks, v_scale=vs,
                                  lengths=lengths)
                out = dict(state)
                out["cache"] = kvc.insert_rows_paged(state["cache"], sub,
                                                     slots, pages)
                out["cross_k"] = state["cross_k"].at[:, slots].set(
                    ck.astype(state["cross_k"].dtype), mode="drop")
                out["cross_v"] = state["cross_v"].at[:, slots].set(
                    cv.astype(state["cross_v"].dtype), mode="drop")
                out["src_lengths"] = state["src_lengths"].at[slots].set(
                    slens, mode="drop")
                tokens = tokens.at[slots].set(row_tokens, mode="drop")
                return out, tokens

            donate = (0, 1) if self._donate_state else ()
            fn = jax.jit(resume, donate_argnums=donate)
            self._resume_jits[n_rows] = fn
        return fn

    def _grow_fn(self, n_rows: int) -> Callable:
        """Jitted page growth: install freshly allocated page ids into
        ``n_rows`` rows' block tables.  ``upd`` is (n_rows, maxP) int32
        with -1 = keep; new slots are written to BOTH ``block_tables`` and
        ``own_pages`` — a grown slot is owned by construction, which is
        the copy-on-write invariant every beam reorder relies on."""
        fn = self._grow_jits.get(n_rows)
        if fn is None:
            def grow(state, rows, upd):
                cache = state["cache"]
                new_t = jnp.where(upd >= 0, upd, cache.block_tables[rows])
                new_o = jnp.where(upd >= 0, upd, cache.own_pages[rows])
                out = dict(state)
                out["cache"] = dataclasses.replace(
                    cache,
                    block_tables=cache.block_tables.at[rows].set(
                        new_t, mode="drop"),
                    own_pages=cache.own_pages.at[rows].set(
                        new_o, mode="drop"))
                return out

            donate = (0,) if self._donate_state else ()
            fn = jax.jit(grow, donate_argnums=donate)
            self._grow_jits[n_rows] = fn
        return fn

    # ---------------------------------------------------- chunked prefill
    def _stage_fns(self) -> Tuple[Callable, Callable]:
        """Jitted begin/finish of a depth-staged encode (chunked prefill).
        The bidirectional encoder cannot chunk over source *tokens*, so a
        long source's encode is spread over *layers*: one width-1 encoder
        layer per serving round rides between decode bursts instead of one
        monolithic width-W encode stalling a whole round."""
        if self._stage_begin_jit is None:
            model, quant, mesh = self.model, self.quant, self.mesh
            self._stage_begin_jit = jax.jit(on_whole_rows(
                lambda p, src, lens: model.encode_staged_begin(
                    p, {"src_tokens": src, "src_lengths": lens}), mesh))
            self._stage_finish_jit = jax.jit(on_whole_rows(
                lambda p, x, lens: model.encode_staged_finish(
                    p, x, src_lengths=lens, quant=quant), mesh))
        return self._stage_begin_jit, self._stage_finish_jit

    def _stage_layer_fn(self, layer_idx: int) -> Callable:
        fn = self._stage_layer_jits.get(layer_idx)
        if fn is None:
            model, quant = self.model, self.quant
            fn = jax.jit(on_whole_rows(
                lambda p, x, lens: model.encode_staged_layer(
                    p, x, layer_idx, src_lengths=lens, quant=quant),
                self.mesh))
            self._stage_layer_jits[layer_idx] = fn
        return fn

    def _chunk_splice_fn(self, group: int) -> Callable:
        """Jitted completion of a staged encode: splice the finished
        cross-K/V into the request's grid rows and seed BOS — exactly the
        fused-admission splice, one round later than a monolithic encode
        would have landed it."""
        fn = self._chunk_splice_jits.get(group)
        if fn is None:
            model = self.model

            def csplice(state, tokens, ck, cv, slens, base_rows, extra):
                state = model.splice_prefill(state, ck, cv, slens,
                                             base_rows, group=group,
                                             pages=extra.get("pages"))
                rows = kvc.group_rows(jnp.asarray(base_rows, jnp.int32),
                                      group)
                tokens = tokens.at[rows].set(0, mode="drop")       # BOS
                return state, tokens

            donate = (0, 1) if self._donate_state else ()
            fn = jax.jit(csplice, donate_argnums=donate)
            self._chunk_splice_jits[group] = fn
        return fn

    # ------------------------------------------------------------ prefix cache
    def _ensure_prefix_cache(self) -> PrefixCache:
        """The engine-lifetime prefix cache + its device-side chain pool.

        The pool is a pair of ``(L, prefix_pages, ps, HKV, dh)`` arrays in
        the *activation* dtype — NOT the decode cache's (possibly int8)
        dtype: a chain must read back bit-identical to a fresh
        ``encode_cross_kv``, and the quantize→dequantize round trip of the
        INT8 decode pool would break the token-identity gate.  During a
        serve the arrays ride inside the decode state (so fused bursts
        scatter/gather them in-program and donation recycles their
        buffers); between serves the engine re-binds them here.
        """
        if self._prefix_cache_obj is None:
            self._prefix_cache_obj = PrefixCache(
                kvc.PageAllocator(self.prefix_pages, self.prefix_page_size))
            cfg = self.model.cfg
            shape = (cfg.n_layers, self.prefix_pages, self.prefix_page_size,
                     cfg.n_kv_heads, cfg.hd)
            self._prefix_pool = (jnp.zeros(shape, cfg.activation_dtype),
                                 jnp.zeros(shape, cfg.activation_dtype))
        return self._prefix_cache_obj

    def _resolve_prefix_cache(self, prefix_cache: Optional[bool]
                              ) -> Optional[PrefixCache]:
        use = (self.prefix_cache_default if prefix_cache is None
               else bool(prefix_cache))
        return self._ensure_prefix_cache() if use else None

    def _prefix_result_fields(self, pc: Optional[PrefixCache],
                              stats0) -> Dict[str, Any]:
        """ServeResult kwargs: per-serve deltas of the persistent stats."""
        if pc is None:
            return {}
        s = pc.stats
        return dict(prefix_cache=True,
                    prefix_hits=s.hits - stats0.hits,
                    prefix_misses=s.misses - stats0.misses,
                    prefix_inserts=s.inserts - stats0.inserts,
                    prefix_evictions=s.evictions - stats0.evictions,
                    prefix_hit_pages=s.hit_pages - stats0.hit_pages,
                    prefix_pages_allocated=(s.pages_allocated
                                            - stats0.pages_allocated),
                    prefix_chains=pc.n_chains)

    @staticmethod
    def _overload_result_fields(overcommit, preempt_count, store, watchdog,
                                sched, reqs, allocator, peak_running,
                                chunked_admissions, chunk_rounds
                                ) -> Dict[str, Any]:
        """ServeResult kwargs for the overload machinery counters."""
        misses = len(sched.rejected) + sum(
            1 for r in reqs
            if (r.status == "finished" and r.deadline_s is not None
                and r.finish_s is not None and r.finish_s > r.deadline_s))
        return dict(
            overcommit=overcommit,
            preemptions=preempt_count,
            spill_events=store.spill_events,
            restore_events=store.restore_events,
            spilled_bytes=store.spilled_bytes,
            straggler_rounds=len(watchdog.straggler_steps),
            chunked_admissions=chunked_admissions,
            chunk_rounds=chunk_rounds,
            peak_running=peak_running,
            rejected=len(sched.rejected),
            deadline_misses=misses,
            free_lwm=allocator.free_lwm if allocator else 0,
            fragmentation=allocator.fragmentation if allocator else 0.0)

    def _pool_insert_fn(self) -> Callable:
        """Jitted unfused-path pool insert: scatter a prefilled side
        batch's cross-K/V into reserved chain pages (fused admission does
        the same scatter inside the burst program)."""
        if self._pool_insert_jit is None:
            def fn(state, ck, cv, pages):
                out = dict(state)
                out["prefix_k"] = kvc.insert_chain_pages(
                    state["prefix_k"], ck, pages)
                out["prefix_v"] = kvc.insert_chain_pages(
                    state["prefix_v"], cv, pages)
                return out
            donate = (0,) if self._donate_state else ()
            self._pool_insert_jit = jax.jit(fn, donate_argnums=donate)
        return self._pool_insert_jit

    def _hit_splice_fn(self, group: int) -> Callable:
        """Jitted unfused-path hit splice: gather cached chains from the
        prefix pool and splice them into the admitted rows — no encoder.
        The rows' first token is deferred to the next burst (BOS seed),
        exactly the fused-admission seeding, so token *streams* stay
        identical (per-request content is pacing-independent)."""
        fn = self._hit_splice_jits.get(group)
        if fn is None:
            model = self.model

            def splice(state, tokens, hit_pages, hit_lens, hit_rows, extra):
                enc_len = state["cross_k"].shape[2]
                hk = kvc.gather_chain_pages(state["prefix_k"], hit_pages,
                                            enc_len)
                hv = kvc.gather_chain_pages(state["prefix_v"], hit_pages,
                                            enc_len)
                state = model.splice_prefill(
                    state, hk, hv, hit_lens, hit_rows, group=group,
                    pages=extra.get("dec_pages"))
                rows = kvc.group_rows(jnp.asarray(hit_rows, jnp.int32),
                                      group)
                tokens = tokens.at[rows].set(0, mode="drop")       # BOS
                return state, tokens

            donate = (0, 1) if self._donate_state else ()
            fn = jax.jit(splice, donate_argnums=donate)
            self._hit_splice_jits[group] = fn
        return fn

    def _beam_gather_state(self, state: Dict[str, Any], idx: jax.Array):
        """Reorder every batch-major leaf of the decode state (paper §5.3).

        Paged cache: the reorder degenerates to a block-table permutation
        plus one partial-page copy (``kv_cache.gather_beams_paged``) — and
        the cross-K/V / source-length leaves are *skipped entirely*: beam
        reorders only ever permute rows within a group, and a group's rows
        share one broadcast encoder memory, so that gather is an identity
        by construction.  The cache payload slab stops moving.
        """
        cache = state.get("cache")
        if isinstance(cache, kvc.PagedKVCache):
            out = dict(state)
            out["cache"] = kvc.gather_beams_paged(cache, idx,
                                                  impl=self.quant.impl)
            return out

        def gather(leaf):
            return jnp.take(leaf, idx, axis=0)

        out = {}
        for k, v in state.items():
            if k == "cache" and isinstance(v, kvc.KVCache):
                out[k] = kvc.gather_beams(v, idx)
            elif v is None:
                out[k] = None
            elif k in ("cross_k", "cross_v"):
                # layer-major (L, B, S, H, dh): the batch axis is 1
                out[k] = jnp.take(v, idx, axis=1)
            elif k in ("prefix_k", "prefix_v"):
                # chain page pools have no batch axis — beam reorders
                # permute rows, and chains are read-only row-agnostic data
                out[k] = v
            else:
                out[k] = jax.tree_util.tree_map(gather, v)
        return out

    @staticmethod
    def _winner(grid: np.ndarray, scores: np.ndarray, alpha: float,
                eos_id: int) -> Tuple[np.ndarray, float]:
        """Pick one beam group's length-penalized best hypothesis.

        ``grid``: (beam, T) host-side token history in final beam order;
        ``scores``: (beam,) final log-probs.  Returns ``(tokens, score)``
        with ``tokens`` truncated before EOS.  Shared by
        :meth:`generate_beam` and the continuous beam serve's group drain
        — one implementation, so the two paths cannot drift apart.
        """
        hit = grid == eos_id
        lengths = np.where(hit.any(axis=1), np.argmax(hit, axis=1),
                           grid.shape[1])
        pen = ((5.0 + lengths) / 6.0) ** alpha
        final = scores / pen
        best = int(final.argmax())
        return grid[best, :lengths[best]], float(final[best])

    @staticmethod
    def _insert_rows(state: Dict[str, Any], sub: Dict[str, Any],
                     tokens: jax.Array, sub_tokens: jax.Array,
                     slots: jax.Array):
        """Splice a prefilled side-batch into the running decode state.

        ``slots``: (B_sub,) destination rows; entries ≥ n_slots are padding
        and dropped by jax scatter semantics (admission groups are padded to
        a power-of-two width for compile stability).
        """
        out = dict(state)
        out["cache"] = kvc.insert_at_slots(state["cache"], sub["cache"],
                                           slots)
        out["cross_k"] = state["cross_k"].at[:, slots].set(sub["cross_k"])
        out["cross_v"] = state["cross_v"].at[:, slots].set(sub["cross_v"])
        out["src_lengths"] = state["src_lengths"].at[slots].set(
            sub["src_lengths"])
        tokens = tokens.at[slots].set(sub_tokens)
        return out, tokens

    @staticmethod
    def _insert_rows_paged(state: Dict[str, Any], sub: Dict[str, Any],
                           tokens: jax.Array, sub_tokens: jax.Array,
                           slots: jax.Array, pages: jax.Array):
        """Paged ``_insert_rows``: same splice contract, but the main cache
        is a page pool — the contiguous side-batch rows are chunked into
        the destination rows' page reservations (``pages``, sentinel-
        padded) and the block tables installed alongside."""
        out = dict(state)
        out["cache"] = kvc.insert_rows_paged(state["cache"], sub["cache"],
                                             slots, pages)
        out["cross_k"] = state["cross_k"].at[:, slots].set(sub["cross_k"])
        out["cross_v"] = state["cross_v"].at[:, slots].set(sub["cross_v"])
        out["src_lengths"] = state["src_lengths"].at[slots].set(
            sub["src_lengths"])
        tokens = tokens.at[slots].set(sub_tokens)
        return out, tokens

    # ------------------------------------------------------- prefill splice
    def _prefill_padded(self, src_rows: np.ndarray, len_rows: np.ndarray):
        """Prefill a side batch padded to a power-of-two width.

        Padding rows replay row 0 — their results are discarded because
        ``_splice_rows`` gives them out-of-range destinations — so prefill
        compiles one program per pow2 width, not per admission-group size
        (``scheduler.pad_rows_pow2``, the contract shared with the fused
        path's ``plan_admission``).  Returns ``(logits, sub_state, width)``.
        """
        src_rows, len_rows, width = pad_rows_pow2(src_rows, len_rows)
        sub = self.model.init_decode_state(
            width, self.max_len, quantized=self.quant.quantize_kv)
        logits, sub = self._prefill(
            self.params,
            {"src_tokens": jnp.asarray(src_rows),
             "src_lengths": jnp.asarray(len_rows)},
            sub)
        return logits, sub, width

    def _splice_rows(self, state, tokens, sub, sub_tokens, rows: np.ndarray,
                     width: int, pages: Optional[np.ndarray] = None):
        """Splice the first ``len(rows)`` rows of a prefilled side batch
        into the running decode state at ``rows``; the side batch's
        padding rows get an out-of-range sentinel destination (the total
        row count) and are dropped by jax scatter semantics.
        ``sub_tokens`` is already ``width``-long (padding-row entries are
        discarded with their rows), keeping every device shape a function
        of the pow2 bucket, never of the admission-group size.
        ``pages`` (paged cache): (width, maxP) per-row page reservations,
        sentinel rows for the padding."""
        slots = np.full((width,), tokens.shape[0], np.int32)  # OOB sentinel
        slots[:len(rows)] = rows
        if pages is not None:
            return self._insert_paged(state, sub, tokens, sub_tokens,
                                      jnp.asarray(slots), jnp.asarray(pages))
        return self._insert(state, sub, tokens, sub_tokens,
                            jnp.asarray(slots))

    def _admission_prologue(self, params, state, tokens, live, adm_src,
                            adm_lens, adm_rows, extra, group: int = 1):
        """Fused-admission prologue shared by the greedy and beam burst
        programs, so the token-identity-critical free→encode→splice
        sequence exists exactly once:

        1. reset dead rows (cursor only unpaged; cursor + sentinel tables
           paged — their pages may be reassigned by this very splice);
        2. if the round has encode rows (``adm_src`` non-empty — a static
           shape, so empty rounds compile the branch away): encode them,
           optionally scatter the fresh cross-K/V into reserved prefix
           chains (``extra["ins_pages"]``), splice into the grid (paged
           reservations from ``extra["pages"]``), and seed BOS;
        3. if the round has prefix *hits* (``extra["hit_rows"]``): gather
           their chains from the prefix pool and splice those rows with no
           encoder work at all — the refcount bump already happened on the
           host.  The insert scatter in (2) is ordered before this gather,
           so a source admitted twice in one round reads the pages its
           sibling wrote moments earlier in the same program.

        ``extra`` is a dict pytree: key *presence* is static (each
        combination traces its own specialization, a small bounded set),
        which is how zero-width encode/hit rounds cost nothing.
        """
        model = self.model
        state = dict(state)
        enc_len = adm_src.shape[1]
        with jax.named_scope("admission"):
            if self.paged:
                state["cache"] = kvc.free_inactive_paged(state["cache"],
                                                         live)
            else:
                state["cache"] = kvc.free_inactive(state["cache"], live)
        if adm_src.shape[0]:
            ck, cv, slens = self._encode(
                params, {"src_tokens": adm_src, "src_lengths": adm_lens})
            with jax.named_scope("admission"):
                if "ins_pages" in extra:
                    state["prefix_k"] = kvc.insert_chain_pages(
                        state["prefix_k"], ck, extra["ins_pages"])
                    state["prefix_v"] = kvc.insert_chain_pages(
                        state["prefix_v"], cv, extra["ins_pages"])
                state = model.splice_prefill(state, ck, cv, slens, adm_rows,
                                             group=group,
                                             pages=extra.get("pages"))
                rows = kvc.group_rows(jnp.asarray(adm_rows, jnp.int32),
                                      group)
                tokens = tokens.at[rows].set(0, mode="drop")       # BOS
        if "hit_rows" in extra:
            with jax.named_scope("admission"):
                hk = kvc.gather_chain_pages(state["prefix_k"],
                                            extra["hit_pages"], enc_len)
                hv = kvc.gather_chain_pages(state["prefix_v"],
                                            extra["hit_pages"], enc_len)
                state = model.splice_prefill(
                    state, hk, hv, extra["hit_lens"], extra["hit_rows"],
                    group=group, pages=extra.get("hit_dec_pages"))
                rows = kvc.group_rows(
                    jnp.asarray(extra["hit_rows"], jnp.int32), group)
                tokens = tokens.at[rows].set(0, mode="drop")       # BOS
        return state, tokens

    # ---------------------------------------------------------------- bursts
    def _greedy_burst_fn(self, width: int) -> Callable:
        fn = self._burst_jits.get(width)
        if fn is None:
            fn = self._make_greedy_burst(width)
            self._burst_jits[width] = fn
        return fn

    def _greedy_while(self, width: int) -> Callable:
        """The greedy burst ``while_loop`` body, shared (un-jitted) by the
        plain and fused-admission burst programs so the token-identity-
        critical math exists exactly once.

        Carry: step counter, current tokens, per-row ``remaining`` budgets,
        decode state (KV cache updated in place each step), and a
        ``(rows, width)`` token ring buffer.  A row is *active* while
        ``remaining > 0``; emitting EOS or exhausting the budget zeroes it.
        Inactive rows keep stepping (the grid is one fused program) but
        their outputs are masked to EOS and their cache writes land past
        their cursor (dropped by ``kv_cache.append_token`` scatter
        semantics).  The loop exits early once no row is active, so
        ``steps_cap=1`` reproduces the per-step path exactly.
        """
        model, quant, eos = self.model, self.quant, self.eos_id

        def burst(params, tokens, remaining, steps_cap, state):
            buf0 = jnp.full((tokens.shape[0], width), eos, jnp.int32)

            def cond(carry):
                step, _, remaining, _, _ = carry
                return (step < steps_cap) & jnp.any(remaining > 0)

            def body(carry):
                step, tokens, remaining, state, buf = carry
                logits, state = model.decode_step(params, tokens, state,
                                                  quant=quant)
                active = remaining > 0
                with jax.named_scope("logits_head"):
                    nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                nxt = jnp.where(active, nxt, eos)
                buf = buf.at[:, step].set(nxt)
                remaining = jnp.where(active & (nxt != eos), remaining - 1,
                                      jnp.zeros_like(remaining))
                return (step + 1, nxt, remaining, state, buf)

            carry = (jnp.int32(0), tokens,
                     jnp.asarray(remaining, jnp.int32), state, buf0)
            step, tokens, remaining, state, buf = jax.lax.while_loop(
                cond, body, carry)
            return tokens, remaining, state, buf, step

        return burst

    def _make_greedy_burst(self, width: int) -> Callable:
        """Jitted ``while_loop`` running up to ``steps_cap ≤ width`` greedy
        decode steps on device (see :meth:`_greedy_while`)."""
        donate = (1, 4) if self._donate_state else ()
        return jax.jit(self._greedy_while(width), donate_argnums=donate)

    def _fused_greedy_burst_fn(self, width: int) -> Callable:
        fn = self._fused_burst_jits.get(width)
        if fn is None:
            fn = self._make_fused_greedy_burst(width)
            self._fused_burst_jits[width] = fn
        return fn

    def _make_fused_greedy_burst(self, width: int) -> Callable:
        """Greedy burst with the admission round folded into the program.

        Prologue, before the shared :meth:`_greedy_while` loop:

        1. encode the padded admitted sources **inside the program**
           (``encdec.encode_cross_kv``) — no separate prefill dispatch;
        2. reset the cursors of dead rows (``remaining == 0``: finished or
           never occupied), replacing the host-dispatched ``free_slots``
           call the unfused path paid between bursts;
        3. splice the encoded cross-K/V into the admitted rows and zero
           their cursors (``encdec.splice_prefill``) — the self-attention
           cache rows need no copy, length masking hides every stale
           position exactly;
        4. seed the admitted rows' current token with BOS.

        The loop's first iteration then runs the BOS decode step for the
        admitted rows — the exact computation the unfused path ran as a
        separate prefill — while mid-flight rows take their next ordinary
        step in the same fused grid.  ``adm_rows`` entries ≥ n_slots are
        padding (dropped by scatter semantics), so the program specializes
        only on the pow2 admission width, never the admitted count.
        """
        prologue = self._admission_prologue
        loop = self._greedy_while(width)

        def burst(params, tokens, remaining, steps_cap, state,
                  adm_src, adm_lens, adm_rows, extra):
            state, tokens = prologue(params, state, tokens, remaining > 0,
                                     adm_src, adm_lens, adm_rows, extra)
            return loop(params, tokens, remaining, steps_cap, state)

        donate = (1, 4) if self._donate_state else ()
        return jax.jit(burst, donate_argnums=donate)

    # ------------------------------------------------- speculative decoding
    def _spec_greedy_burst_fn(self, width: int, spec_k: int) -> Callable:
        fn = self._spec_burst_jits.get((width, spec_k))
        if fn is None:
            donate = (1, 4) if self._donate_state else ()
            fn = jax.jit(self._spec_greedy_while(width, spec_k),
                         donate_argnums=donate)
            self._spec_burst_jits[(width, spec_k)] = fn
        return fn

    def _spec_fused_greedy_burst_fn(self, width: int, spec_k: int) -> Callable:
        fn = self._spec_fused_burst_jits.get((width, spec_k))
        if fn is None:
            prologue = self._admission_prologue
            loop = self._spec_greedy_while(width, spec_k)

            def burst(params, tokens, remaining, steps_cap, state,
                      adm_src, adm_lens, adm_rows, extra):
                state, tokens = prologue(params, state, tokens,
                                         remaining > 0, adm_src, adm_lens,
                                         adm_rows, extra)
                return loop(params, tokens, remaining, steps_cap, state)

            donate = (1, 4) if self._donate_state else ()
            fn = jax.jit(burst, donate_argnums=donate)
            self._spec_fused_burst_jits[(width, spec_k)] = fn
        return fn

    def _spec_greedy_while(self, width: int, spec_k: int) -> Callable:
        """Self-speculative greedy burst: every ``while_loop`` iteration
        (one *macro-step*) runs ``spec_k`` sequential draft steps with the
        ``draft_quant`` context, then ONE batched multi-position verify
        pass with the engine ``quant`` context, and emits the longest
        draft prefix the verifier agrees with plus the verifier's own
        correction token (:func:`_spec_accept`) — all on device, so host
        syncs per serve round stay exactly one, same as the plain burst.

        The drafts' KV writes are scratch: the verify pass re-appends
        positions ``[n0, n0 + spec_k]`` from the *pre-draft* cache state
        with verifier-quality values, and the accepted cursor
        ``n0 + stop`` is installed with :func:`kv_cache.with_lengths` —
        rejected positions become junk past the cursor, which the cache
        contract already tolerates (reads are length-masked, later writes
        overwrite).  Accepted positions therefore hold the verifier's KV
        of exactly the tokens sequential decode would have fed, which is
        why greedy output is bit-identical to the non-speculative path.

        Ring-buffer layout: ``width`` macro-steps × up to ``spec_k + 1``
        tokens each, written at per-row ``emitted`` cursors (rows emit
        different counts per macro-step, so the host drain reads
        ``emitted[row]`` entries, not a column count).  The per-row
        ``emitted``/``drafted``/``accepted`` counters and ``act_steps``
        (macro-steps the row was live — the busy/wasted accounting unit
        under speculation) ride back as 4 extra ring columns.
        """
        model, eos = self.model, self.eos_id
        quant, draft_quant = self.quant, self.draft_quant
        s = spec_k
        width_cols = width * (s + 1)

        def burst(params, tokens, remaining, steps_cap, state):
            B = tokens.shape[0]
            buf0 = jnp.full((B, width_cols), eos, jnp.int32)
            zeros = jnp.zeros((B,), jnp.int32)
            b_idx = jnp.arange(B)

            def cond(carry):
                step, _, remaining = carry[0], carry[1], carry[2]
                return (step < steps_cap) & jnp.any(remaining > 0)

            def body(carry):
                (step, tokens, remaining, state, buf,
                 emitted, drafted, accepted, act_steps) = carry
                n0 = state["cache"].lengths
                active = remaining > 0
                # ---- draft: s sequential cheap steps (static unroll)
                dst, cur, drafts = state, tokens, []
                for _ in range(s):
                    lg, dst = model.decode_step(params, cur, dst,
                                                quant=draft_quant)
                    cur = jnp.argmax(lg, axis=-1).astype(jnp.int32)
                    drafts.append(cur)
                d = jnp.stack(drafts, axis=1)              # (B, s)
                # ---- verify: one batched pass over (t0, d_1 … d_s)
                # against the PRE-draft cache (cursors n0) — its appends
                # overwrite every draft-scratch position
                seq = jnp.concatenate([tokens[:, None], d], axis=1)
                vlogits, vstate = model.decode_step_multi(params, seq,
                                                          state, quant=quant)
                v = jnp.argmax(vlogits, axis=-1).astype(jnp.int32)  # (B,s+1)
                stop, hit_eos, acc = _spec_accept(d, v, remaining, eos)
                # ---- roll back rejected positions: cursor-only
                vstate = dict(vstate)
                vstate["cache"] = kvc.with_lengths(vstate["cache"],
                                                   n0 + stop)
                # ---- emit v[:, :stop] at per-row ring cursors
                for j in range(s + 1):
                    col = jnp.where(active & (j < stop), emitted + j,
                                    width_cols)          # OOB → drop
                    buf = buf.at[b_idx, col].set(v[:, j], mode="drop")
                remaining = jnp.where(hit_eos, 0, remaining - stop)
                nxt = jnp.where(active,
                                v[b_idx, jnp.maximum(stop - 1, 0)], eos)
                return (step + 1, nxt, remaining, vstate, buf,
                        emitted + stop, drafted + jnp.where(active, s, 0),
                        accepted + acc,
                        act_steps + active.astype(jnp.int32))

            carry = (jnp.int32(0), tokens, jnp.asarray(remaining, jnp.int32),
                     state, buf0, zeros, zeros, zeros, zeros)
            (step, tokens, remaining, state, buf,
             emitted, drafted, accepted, act_steps) = jax.lax.while_loop(
                cond, body, carry)
            # pack the per-row counters as 4 extra ring columns so the
            # burst returns the same 5-tuple as the plain greedy burst and
            # the host drain still costs exactly ONE device→host transfer
            packed = jnp.concatenate(
                [buf, emitted[:, None], drafted[:, None],
                 accepted[:, None], act_steps[:, None]], axis=1)
            return tokens, remaining, state, packed, step

        return burst

    def _beam_burst_fn(self, width: int, beam: int) -> Callable:
        fn = self._beam_burst_jits.get((width, beam))
        if fn is None:
            fn = self._make_beam_burst(width, beam)
            self._beam_burst_jits[(width, beam)] = fn
        return fn

    def _make_beam_step(self, beam: int) -> Callable:
        """One beam-search decode step — log-softmax, finished-beam EOS
        masking, per-group top-k, score update, and the **cache reorder**
        (the paper's §5.3 GatherNd) — shared by both beam burst builders
        so the token-identity-critical math exists exactly once.

        ``act_r`` is a per-row activity mask: rows of inactive groups
        gather themselves (identity permutation) and keep their tokens /
        scores / finished / permutation-composition / ring-buffer entries
        frozen while their decode state advances with garbage (nothing
        reads it).  An all-True mask reproduces the unmasked
        ``generate_beam`` step exactly.

        ``parked`` is a per-row mask for **mixed beam widths**: a request
        with ``beam_req < beam`` occupies only the first ``beam_req`` rows
        of its group; the tail rows are *parked* — pinned to EOS /
        ``BEAM_SEED_NEG`` / finished, self-gathering — so their candidates
        score ``-1e30 + 0`` and can never enter the group's top-k ahead of
        a real hypothesis, while the top-k's first ``beam_req`` slots (it
        returns descending) are exactly ``top_k(real candidates,
        beam_req)``: the step *is* a ``beam_req``-wide beam step.  An
        all-False mask reproduces the uniform-width step exactly.
        """
        model, quant, eos = self.model, self.quant, self.eos_id
        gather_state, mesh = self._beam_gather_state, self.mesh

        def step_fn(params, tokens, scores, finished, comp, state, buf,
                    step, act_r, parked):
            R = tokens.shape[0]
            G = R // beam
            logits, state = model.decode_step(params, tokens, state,
                                              quant=quant)
            with jax.named_scope("logits_head"):
                lp = log_probs(logits, mesh)
            with jax.named_scope("beam_step"):
                V = lp.shape[-1]
                # finished beams only extend with EOS at no cost
                eos_only = jnp.full_like(lp, -1e30).at[:, eos].set(0.0)
                lp = jnp.where(finished[:, None], eos_only, lp)
                cand = (scores[:, None] + lp).reshape(G, beam * V)
                scores_new, flat_idx = jax.lax.top_k(cand, beam)
                src_beam = flat_idx // V
                tok_new = (flat_idx % V).reshape(R).astype(jnp.int32)
                tok_new = jnp.where(parked, eos, tok_new)
                gidx = (src_beam + jnp.arange(G)[:, None] * beam).reshape(R)
                gidx = jnp.where(act_r & ~parked, gidx,
                                 jnp.arange(R, dtype=jnp.int32))
                state = gather_state(state, gidx)
                tokens = jnp.where(act_r, tok_new, tokens)
                scores = jnp.where(act_r, scores_new.reshape(R), scores)
                scores = jnp.where(parked, BEAM_SEED_NEG, scores)
                finished = jnp.take(finished, gidx, axis=0) | \
                    (act_r & (tokens == eos)) | parked
                comp = jnp.take(comp, gidx, axis=0)
                buf = jnp.take(buf, gidx, axis=0)
                buf = buf.at[:, step].set(jnp.where(act_r, tokens, eos))
                return tokens, scores, finished, comp, state, buf

        return step_fn

    def _make_beam_burst(self, width: int, beam: int) -> Callable:
        """Beam-search burst: top-k, score update, **cache reorder** (the
        paper's §5.3 GatherNd) all inside the scanned body.

        Besides the token ring buffer it carries ``comp`` — the composition
        of this burst's beam-reorder permutations — so the host can apply
        one gather to the token history per *burst* instead of one per
        step.  Ring-buffer rows are reordered alongside the state, so at
        burst exit the buffer is already in final beam order.
        """
        eos = self.eos_id
        step_fn = self._make_beam_step(beam)

        def burst(params, tokens, scores, finished, steps_cap, state):
            BB = tokens.shape[0]
            buf0 = jnp.full((BB, width), eos, jnp.int32)
            comp0 = jnp.arange(BB, dtype=jnp.int32)
            all_rows = jnp.ones((BB,), bool)
            none_parked = jnp.zeros((BB,), bool)

            def cond(carry):
                step, _, _, finished, _, _, _ = carry
                return (step < steps_cap) & ~jnp.all(finished)

            def body(carry):
                step, tokens, scores, finished, comp, state, buf = carry
                tokens, scores, finished, comp, state, buf = step_fn(
                    params, tokens, scores, finished, comp, state, buf,
                    step, all_rows, none_parked)
                return (step + 1, tokens, scores, finished, comp, state, buf)

            carry = (jnp.int32(0), tokens, scores, finished, comp0, state,
                     buf0)
            (step, tokens, scores, finished, comp, state, buf) = \
                jax.lax.while_loop(cond, body, carry)
            return tokens, scores, finished, comp, state, buf, step

        donate = (1, 5) if self._donate_state else ()
        return jax.jit(burst, donate_argnums=donate)

    def _beam_serve_burst_fn(self, width: int, beam: int) -> Callable:
        fn = self._beam_serve_jits.get((width, beam))
        if fn is None:
            fn = self._make_beam_serve_burst(width, beam)
            self._beam_serve_jits[(width, beam)] = fn
        return fn

    def _beam_serve_while(self, width: int, beam: int) -> Callable:
        """Continuous-batching beam burst loop (un-jitted, shared by the
        plain and fused-admission burst programs):
        ``_make_beam_burst``'s body with
        **per-group** lifecycle masks, so requests at different stages of
        their budgets share one decode grid.

        The grid is ``G = rows // beam`` independent beam groups.  Each
        group carries its own ``remaining`` step budget; a group is
        *active* while ``remaining > 0`` and not all of its rows have
        finished.  Inactive groups (budget exhausted, fully finished, or
        unoccupied rows) keep stepping — the grid is one fused program —
        but their tokens / scores / finished / permutation-composition /
        ring-buffer rows are frozen by the per-row mask (see
        ``_make_beam_step``), so at the burst edge the host drains each
        group exactly as ``generate_beam`` would have left it at its own
        early exit.  Groups only *deactivate* mid-burst (admission happens
        at burst edges), so every group active at step ``s`` has taken
        exactly ``s`` steps and the global ring column doubles as the
        per-group one; per-group steps taken are recovered on the host as
        ``remaining_in - remaining_out``.
        """
        eos = self.eos_id
        step_fn = self._make_beam_step(beam)

        def burst(params, tokens, scores, finished, remaining, steps_cap,
                  state, parked):
            R = tokens.shape[0]
            G = R // beam
            buf0 = jnp.full((R, width), eos, jnp.int32)
            ident = jnp.arange(R, dtype=jnp.int32)

            def active_groups(finished, remaining):
                alive = ~jnp.all(finished.reshape(G, beam), axis=1)
                return (remaining > 0) & alive                    # (G,)

            def cond(carry):
                step, _, _, finished, remaining, _, _, _ = carry
                return (step < steps_cap) & \
                    jnp.any(active_groups(finished, remaining))

            def body(carry):
                (step, tokens, scores, finished, remaining, comp, state,
                 buf) = carry
                act_g = active_groups(finished, remaining)        # (G,)
                act_r = jnp.repeat(act_g, beam)                   # (R,)
                tokens, scores, finished, comp, state, buf = step_fn(
                    params, tokens, scores, finished, comp, state, buf,
                    step, act_r, parked)
                remaining = remaining - act_g.astype(remaining.dtype)
                return (step + 1, tokens, scores, finished, remaining, comp,
                        state, buf)

            carry = (jnp.int32(0), tokens, scores.astype(jnp.float32),
                     finished, jnp.asarray(remaining, jnp.int32), ident,
                     state, buf0)
            (step, tokens, scores, finished, remaining, comp, state, buf) = \
                jax.lax.while_loop(cond, body, carry)
            return tokens, scores, finished, remaining, comp, state, buf, step

        return burst

    def _make_beam_serve_burst(self, width: int, beam: int) -> Callable:
        donate = (1, 6) if self._donate_state else ()
        return jax.jit(self._beam_serve_while(width, beam),
                       donate_argnums=donate)

    def _fused_beam_serve_burst_fn(self, width: int, beam: int) -> Callable:
        fn = self._fused_beam_serve_jits.get((width, beam))
        if fn is None:
            fn = self._make_fused_beam_serve_burst(width, beam)
            self._fused_beam_serve_jits[(width, beam)] = fn
        return fn

    def _make_fused_beam_serve_burst(self, width: int, beam: int) -> Callable:
        """Beam-group burst with the admission round folded in —
        **encode-once** prefill.

        The prologue encodes each admitted source exactly once
        (``adm_src`` holds one row per admitted *request*, not per beam
        row) and ``encdec.splice_prefill(group=beam)`` broadcasts the
        memory/cross-KV across the group's ``beam`` rows — the unfused
        side-batch tiled the source ``beam`` times through the encoder for
        bit-identical rows, a ``beam×`` FLOP tax.  Dead rows' cursors are
        reset in-program (replacing the host-dispatched ``free_groups``),
        admitted rows get BOS tokens, and the shared group-masked loop
        runs.  The host seeds the admitted groups' scores as
        ``[0, -1e30, …]`` and ``finished = False`` (uploaded with the
        per-burst score/finished round-trip it already pays), which makes
        the shared beam step's first iteration reproduce
        ``generate_beam``'s first step exactly: every candidate outside
        row 0 carries score ``-1e30 + logprob`` and can never enter the
        top-k, and flat top-k tie-breaking prefers row 0's candidates —
        so the group's first tokens are the top-``beam`` tokens of the
        beam-0 logits, at the beam-0 log-probs.
        """
        prologue = self._admission_prologue
        loop = self._beam_serve_while(width, beam)

        def burst(params, tokens, scores, finished, remaining, steps_cap,
                  state, parked, adm_src, adm_lens, adm_bases, extra):
            live = jnp.repeat(remaining > 0, beam)                 # (R,)
            state, tokens = prologue(params, state, tokens, live, adm_src,
                                     adm_lens, adm_bases, extra, group=beam)
            return loop(params, tokens, scores, finished, remaining,
                        steps_cap, state, parked)

        donate = (1, 6) if self._donate_state else ()
        return jax.jit(burst, donate_argnums=donate)

    # ---------------------------------------------------------------- greedy
    def generate(self, batch: Dict[str, np.ndarray], *,
                 max_new_tokens: int = 64,
                 burst_len: Optional[int] = None,
                 speculative_k: Optional[int] = None) -> GenerationResult:
        K = self._resolve_burst(burst_len)
        if K == "auto":
            K = 8      # adaptation targets serve(); static batches use a mid cap
        spec = int(speculative_k or 0)
        if spec < 0:
            raise ValueError(f"speculative_k must be >= 0, got {spec}")
        if spec and not hasattr(self.model, "decode_step_multi"):
            raise ValueError(
                "speculative decoding needs a model with decode_step_multi "
                f"(multi-position verify); {type(self.model).__name__} "
                "does not provide one")
        width = next_pow2(K)
        burst = (self._spec_greedy_burst_fn(width, spec) if spec
                 else self._greedy_burst_fn(width))
        batch = {k: jnp.asarray(v) for k, v in batch.items()}
        B = next(iter(batch.values())).shape[0]

        t0 = time.perf_counter()
        state = self._init_state(B)
        logits, state = self._prefill(self.params, batch, state)
        jax.block_until_ready(logits)
        t1 = time.perf_counter()

        tokens = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        first = np.asarray(tokens)
        host_syncs = 1
        cols = [first]
        # speculative bursts emit ragged per-row counts, so the output is
        # accumulated as per-row segments instead of grid columns
        rows = [[int(first[b])] for b in range(B)]
        emit_col = width * (spec + 1)
        draft_total = 0
        accept_total = 0
        remaining_np = np.where(first == self.eos_id, 0,
                                max(max_new_tokens - 1, 0)).astype(np.int32)
        remaining = jnp.asarray(remaining_np)
        steps = 1
        cap = jnp.asarray(K, jnp.int32)
        while remaining_np.any():
            tokens, remaining, state, buf, s = burst(
                self.params, tokens, remaining, cap, state)
            buf_host = np.asarray(buf)             # one host sync per burst
            s = int(s)
            remaining_np = np.asarray(remaining)
            host_syncs += 1
            if spec:
                for b in range(B):
                    n = int(buf_host[b, emit_col])
                    rows[b].extend(int(x) for x in buf_host[b, :n])
                    draft_total += int(buf_host[b, emit_col + 1])
                    accept_total += int(buf_host[b, emit_col + 2])
            else:
                cols.extend(buf_host[:, i] for i in range(s))
            steps += s
        t2 = time.perf_counter()

        if spec:
            grid_rows = [np.asarray(r, np.int32) for r in rows]
        else:
            grid = np.stack(cols, axis=1)                       # (B, T)
            grid_rows = [grid[b] for b in range(B)]
        seqs = []
        for row in grid_rows:
            stop = np.argmax(row == self.eos_id) if (row == self.eos_id).any() \
                else len(row)
            seqs.append(row[:stop])
        return GenerationResult(tokens=seqs, steps=steps,
                                prefill_s=t1 - t0, decode_s=t2 - t1,
                                host_syncs=host_syncs,
                                speculative_k=spec,
                                draft_tokens=draft_total,
                                accepted_tokens=accept_total)

    # ------------------------------------------------------------ continuous
    def _as_requests(
        self, requests: Sequence[Any],
        max_new_tokens: Union[int, Sequence[int]],
    ) -> List[Request]:
        per_req = (list(max_new_tokens)
                   if isinstance(max_new_tokens, (list, tuple, np.ndarray))
                   else [int(max_new_tokens)] * len(requests))
        if len(per_req) != len(requests):
            raise ValueError("max_new_tokens sequence length "
                             f"{len(per_req)} != {len(requests)} requests")
        out = []
        for i, (r, m) in enumerate(zip(requests, per_req)):
            if isinstance(r, Request):
                out.append(r)
                continue
            src = r.src if hasattr(r, "src") else np.asarray(r, np.int32)
            out.append(Request(req_id=i, src=np.asarray(src, np.int32),
                               max_new_tokens=int(m)))
        ids = [r.req_id for r in out]
        if len(set(ids)) != len(ids):
            raise ValueError("duplicate req_ids in serve() input (raw "
                             "requests are numbered by position; supplied "
                             "Request ids must not collide)")
        return out

    def serve(self, requests: Sequence[Any], *, n_slots: int = 8,
              max_new_tokens: Union[int, Sequence[int]] = 64,
              prefill_token_budget: Optional[int] = None,
              admit_min_free: int = 1,
              pad_to_multiple: int = 8,
              burst_len: Optional[Union[int, str]] = None,
              beam: Optional[Union[int, Sequence[int]]] = None,
              alpha: float = 0.6,
              fused_admission: bool = True,
              prefix_cache: Optional[bool] = None,
              overcommit: float = 1.0,
              prefill_chunk: Optional[int] = None,
              chaos: Optional[ChaosSchedule] = None,
              speculative_k: Optional[int] = None) -> ServeResult:
        """Continuous-batching decode over a request stream.

        ``requests`` may be ``Sentence``s, raw token arrays, or ``Request``
        objects (the latter carry their own ``max_new_tokens``); submission
        order is arrival order.  All ``n_slots`` rows share one jitted
        decode burst of up to ``burst_len`` steps (engine default if None);
        the host is touched only at burst boundaries, where finished rows
        are released (``kv_cache.free_slots``) and refilled from the
        waiting queue (``kv_cache.insert_at_slots``), so the decode grid
        stays saturated even when generation lengths are wildly skewed.
        Greedy decode is token-identical to per-request :meth:`generate`
        for every ``burst_len``; ``burst_len=1`` reproduces the per-step
        loop (slot refill and latency observation every token), larger
        bursts amortize host round trips at the cost of finished rows
        idling (masked to EOS) until the next burst edge.

        ``beam`` switches the grid to continuous **beam search**: each
        request occupies a group of ``beam`` contiguous rows (so the grid
        holds ``n_slots // beam`` groups), the burst runs the beam-search
        body — top-k, score update, on-device cache reorder (the paper's
        §5.3 GatherNd) — with per-group budget/finished masks, finished
        groups are drained and their ``beam`` rows refilled at burst
        edges, and each request's ``tokens`` is the winning hypothesis
        under the ``alpha`` length penalty.  Token-identical to
        per-request :meth:`generate_beam` for every ``burst_len``, FP and
        INT8 KV cache alike.  ``beam=None`` (default) is the greedy path;
        ``beam=1`` runs the beam machinery with single-row groups (same
        tokens as greedy, but with scores and the beam drain path).
        ``beam`` may also be a per-request sequence (mixed widths in one
        grid: narrower requests park their groups' tail rows and — on the
        paged cache — reserve pages only for the rows they actually run).

        ``admit_min_free`` is admission hysteresis: wait until that many
        slot groups are free before paying for a prefill round (larger
        values amortize prefill dispatches at a small utilization/latency
        cost; 1 = refill immediately).  The last stragglers are always
        admitted.

        ``fused_admission=True`` (default) folds each admission round into
        the burst program — a serve round is ONE jitted dispatch and one
        device→host sync, admitted or not, and ``prefill_dispatches``
        stays 0; ``False`` keeps the PR 3 behaviour (separate prefill
        dispatch + first-token drain per admission round) as the measured
        baseline.  Token streams are identical either way; with fusion the
        first token of an admitted request is *observed* one burst edge
        later (it is emitted by the burst's first step, not by a prefill
        drain), which is the latency grain the queueing model
        ``streams.simulate_continuous(fused_admission=...)`` mirrors.

        ``burst_len="auto"`` lets :class:`burst_control.AdaptiveBurst`
        move the step cap between bursts (pow2 values under one compiled
        ring-width bucket, so adapting never recompiles).

        ``prefix_cache`` (None = the engine constructor's setting) turns
        on cross-request prefix sharing: an admission whose source exactly
        matches a cached one skips the encoder and splices the cached
        cross-K/V chain (a host-side refcount bump instead of encode +
        store); misses cache their encode for the next requester.  The
        cache persists across serve() calls on this engine.  Token
        streams are identical to a cold-cache serve — hits change *where*
        the cross-K/V comes from, never its values.

        **Overload behaviour** (all default-off; tokens stay identical to
        an unloaded serve in every mode):

        * ``overcommit > 1.0`` (paged cache only) admits past worst-case
          page reservation — a request's full-budget reservation becomes
          *virtual* (capped at ``overcommit × n_pages``), only next-burst
          pages are allocated up front, rows grow page by page between
          bursts, and when growth or a more urgent admission comes up
          short a victim is **preempted by page spill**: its KV pages,
          cursors and tokens are copied to host
          (``serving/preemption.py``), its pages freed, and it resumes
          later through the normal paged splice, bit-identically.
        * ``Request.deadline_s`` / ``Request.priority`` order the wait
          queue EDF-first (with starvation aging) and pick preemption
          victims; a request whose deadline has already passed at an
          admission edge is **shed** (status "rejected" with a reason)
          instead of wasting encode work.
        * ``prefill_chunk`` (fused admission only) stages sources longer
          than the chunk over serving rounds — one width-1 encoder layer
          per round between decode bursts — so one long prefill cannot
          stall every running request's next token.
        * ``chaos`` injects deterministic seeded faults at round edges
          (``serving/chaos.py``): forced preemptions and synthetic slow
          rounds for the ``StepWatchdog``.  The test harness uses it to
          prove the preempt/resume identity.

        ``speculative_k`` (greedy only) turns on **self-speculative
        decoding**: every burst loop iteration drafts ``speculative_k``
        tokens through the cheap ``draft_quant`` path, verifies them with
        ONE batched multi-position pass through the engine's own ``quant``
        path, and emits the longest agreeing prefix plus the verifier's
        correction.  Output is bit-identical to ``speculative_k=None``
        (lossless verification — emitted tokens always come from the
        verifier); the win is wall-clock when the draft path is cheaper
        and acceptance is high.  ``ServeResult`` reports
        ``draft_tokens``/``accepted_tokens``/``acceptance_rate``.
        """
        if beam is not None:
            if speculative_k:
                raise ValueError("speculative decoding is greedy-only; "
                                 "beam and speculative_k cannot combine")
            return self._serve_beam(
                requests, n_slots=n_slots, beam=beam, alpha=alpha,
                max_new_tokens=max_new_tokens,
                prefill_token_budget=prefill_token_budget,
                admit_min_free=admit_min_free,
                pad_to_multiple=pad_to_multiple, burst_len=burst_len,
                fused_admission=fused_admission, prefix_cache=prefix_cache,
                overcommit=overcommit, prefill_chunk=prefill_chunk,
                chaos=chaos)
        with jax.profiler.TraceAnnotation("engine.setup", round=0):
            self._check_overload_args(overcommit, prefill_chunk, chaos,
                                      fused_admission)
            spec = int(speculative_k or 0)
            if spec < 0:
                raise ValueError(f"speculative_k must be >= 0, got {spec}")
            if spec and not hasattr(self.model, "decode_step_multi"):
                raise ValueError(
                    "speculative decoding needs a model with "
                    "decode_step_multi (multi-position verify); "
                    f"{type(self.model).__name__} does not provide one")
            spec_mult = spec + 1
            K = self._resolve_burst(burst_len)
            ctrl = self._burst_controller(K)
            reqs = self._as_requests(requests, max_new_tokens)
            if not reqs:
                return ServeResult(requests=[], n_slots=n_slots,
                                   decode_steps=0,
                                   busy_slot_steps=0, prefill_rounds=0,
                                   wall_s=0.0, host_syncs=0,
                                   burst_len=ctrl.k if ctrl else K,
                                   fused_admission=fused_admission,
                                   auto_burst=ctrl is not None,
                                   paged=self.paged, page_size=self.page_size,
                                   speculative_k=spec,
                                   **self._mesh_result_fields(n_slots))
            if max(r.max_new_tokens for r in reqs) > self.max_len:
                raise ValueError("a request's max_new_tokens exceeds the "
                                 f"engine KV capacity {self.max_len}")
            width = next_pow2(ctrl.max_burst if ctrl else K)
            if spec:
                burst = self._spec_greedy_burst_fn(width, spec)
                fused_burst = (self._spec_fused_greedy_burst_fn(width, spec)
                               if fused_admission else None)
            else:
                burst = self._greedy_burst_fn(width)
                fused_burst = (self._fused_greedy_burst_fn(width)
                               if fused_admission else None)
            enc_len = self._enc_bucket(reqs, pad_to_multiple)
            pc = self._resolve_prefix_cache(prefix_cache)
            stats0 = pc.stats.snapshot() if pc else None

            allocator = None
            if self.paged:
                allocator = self._make_allocator(n_slots, overcommit)
                for r in reqs:
                    need = self._pages_per_request(r, 1)
                    if need > allocator.n_pages:
                        raise ValueError(
                            f"request {r.req_id} needs {need} pages but the "
                            f"pool holds {allocator.n_pages}")
            # overcommit: admission allocates only next-burst pages; the loop
            # grows rows and preempts-by-spill under pressure.  The hint is
            # the largest step cap a burst can take — under speculation every
            # macro-step may append up to spec+1 KV positions, so the page
            # reach scales by spec_mult or accepted writes would be dropped.
            burst_hint = (ctrl.max_burst if ctrl else K) * spec_mult
            initial_fn = None
            if allocator is not None and overcommit > 1.0:
                initial_fn = lambda r: self._initial_pages(r, 1, burst_hint)
            sched = ContinuousScheduler(
                n_slots, prefill_token_budget=prefill_token_budget,
                allocator=allocator,
                pages_per_request=(
                    (lambda r: self._pages_per_request(r, 1))
                    if allocator else None),
                prefix_cache=pc, initial_pages=initial_fn,
                prefill_chunk=prefill_chunk)
            sched.submit_many(reqs)

            quantized = self.quant.quantize_kv
            state = self.model.init_decode_state(
                n_slots, self.max_len, quantized=quantized, enc_len=enc_len,
                paged=self.paged, page_size=self.page_size,
                n_pages=allocator.n_pages if allocator else None)
            if pc is not None:
                state["prefix_k"], state["prefix_v"] = self._prefix_pool
            state = self._shard_state(state)
            tokens = jnp.zeros((n_slots,), jnp.int32)

        t0 = time.perf_counter()
        now = lambda: time.perf_counter() - t0
        decode_steps = 0
        busy_slot_steps = 0
        kv_pages_read = kv_page_slots = 0
        prefill_rounds = 0
        host_syncs = 0
        prefill_dispatches = 0
        encoder_tokens = 0
        draft_tokens = 0
        accepted_tokens = 0
        # fixed caps upload the device scalar once; auto rebuilds per round
        cap_fixed = None if ctrl else jnp.asarray(K, jnp.int32)
        # ---- overload machinery (all inert on an unloaded serve)
        store = SpillStore()
        watchdog = StepWatchdog()
        staging: Dict[int, Dict[str, Any]] = {}   # slot → staged-encode state
        # with growth (overcommit) or preemption in play, freed pages can
        # be handed to OTHER rows between fused prologues — dead rows must
        # be sentineled eagerly, not lazily at the next admission burst
        eager_free = (overcommit > 1.0) or (chaos is not None)
        preempt_count = 0
        peak_running = 0
        chunked_admissions = 0
        chunk_rounds = 0
        maxP = self._max_pages

        def preempt_req(req: Request) -> None:
            """Spill one running request's device state to host and evict
            it (a mid-stage chunked prefill holds no device state worth
            saving: drop the stage and restage from scratch on
            re-admission — deterministic, so tokens are unaffected)."""
            nonlocal state, host_syncs, preempt_count
            slot = req.slot
            if slot in staging:
                staging.pop(slot)
                sched.preempt(req, now())
            else:
                outs = self._spill_fn(1)(
                    state, tokens, jnp.asarray(np.asarray([slot], np.int32)))
                k, v, ks, vs, lens, toks, ck, cv, slens = [
                    None if o is None else np.asarray(o) for o in outs]
                host_syncs += 1
                req.spill = SpilledRequest(
                    req_id=req.req_id, n_rows=1, k=k, v=v, k_scale=ks,
                    v_scale=vs, lengths=lens, tokens_row=toks, cross_k=ck,
                    cross_v=cv, src_lengths=slens,
                    n_pages=len(req.pages or []))
                store.put(req.spill)
                sched.preempt(req, now())
            preempt_count += 1
            # sentinel the victim row NOW: its stale block table would
            # otherwise route the next burst's (masked but real) writes
            # into pages growth/resume may already have handed to others
            state = dict(state)
            state["cache"] = kvc.free_slots_paged(
                state["cache"], np.asarray([slot], np.int32))

        def grow_rows(k_cap: int) -> None:
            """Pre-burst page growth for overcommitted rows: every running
            row gets pages to cover its cursor + the next burst, evicting
            least-urgent victims when the pool is dry (mandatory — a row
            that cannot grow cannot take its next step)."""
            nonlocal state
            if initial_fn is None:
                return
            for slot, req in list(sched.slot_map.items()):
                if sched.slot_map.get(slot) is not req or slot in staging:
                    continue       # victim of an earlier growth this round
                cursor = len(req.tokens)
                cap_tok = min(req.max_new_tokens, self.max_len)
                need = kvc.pages_per_row(min(cursor + k_cap, cap_tok),
                                         self.page_size)
                extra = need - len(req.pages)
                if extra <= 0:
                    continue
                newp = allocator.alloc(extra)
                while newp is None:
                    victims, covered = pick_victims(
                        [r for r in sched.slot_map.values() if r is not req],
                        pages_needed=extra - allocator.n_free,
                        key_fn=sched.victim_key,
                        pages_held_fn=lambda r: len(r.pages or []))
                    if not victims or not covered:
                        # fail BEFORE spilling: preempting victims that
                        # cannot cover the need pays spill + re-encode for
                        # nothing and wedges anyway
                        raise RuntimeError(
                            "page growth wedged: no preemptable victim "
                            f"set covers request {req.req_id}'s need "
                            f"({extra} pages)")
                    for v in victims:
                        preempt_req(v)
                    newp = allocator.alloc(extra)
                have = len(req.pages)
                upd = np.full((1, maxP), -1, np.int32)
                upd[0, have:have + extra] = newp
                req.pages.extend(newp)
                state = self._grow_fn(1)(
                    state, jnp.asarray(np.asarray([slot], np.int32)),
                    jnp.asarray(upd))

        def preempt_for_admission() -> None:
            """Admission-driven preemption: free pages for the most urgent
            waiting request by evicting strictly-less-urgent running ones
            (``min_key`` — equal urgency never evicts, so requests cannot
            ping-pong)."""
            if initial_fn is None:
                return
            for _ in range(n_slots + len(reqs)):
                short = sched.admission_shortfall()
                if short is None:
                    return
                need = max(short["pages_short"], 1)
                victims, covered = pick_victims(
                    list(sched.slot_map.values()), pages_needed=need,
                    key_fn=sched.victim_key,
                    pages_held_fn=lambda r: len(r.pages or []),
                    min_key=short["head_key"])
                if not victims or not covered:
                    # insufficient coverage: spilling these victims would
                    # not let the head request in — keep them running
                    return
                for v in victims:
                    preempt_req(v)

        def restore_resumed(resumed: List[Request]) -> None:
            """Re-splice spilled payloads into freshly admitted rows —
            the resume half of preempt-by-page-spill."""
            nonlocal state, tokens
            for req in resumed:
                sp = req.spill
                pages = np.full((1, maxP), allocator.n_pages, np.int32)
                pages[0, :len(req.pages)] = req.pages
                state, tokens = self._resume_fn(1)(
                    state, tokens,
                    jnp.asarray(np.asarray([req.slot], np.int32)),
                    jnp.asarray(pages),
                    jnp.asarray(sp.k), jnp.asarray(sp.v),
                    None if sp.k_scale is None else jnp.asarray(sp.k_scale),
                    None if sp.v_scale is None else jnp.asarray(sp.v_scale),
                    jnp.asarray(sp.lengths), jnp.asarray(sp.tokens_row),
                    jnp.asarray(sp.cross_k), jnp.asarray(sp.cross_v),
                    jnp.asarray(sp.src_lengths))
                store.pop(req.req_id)
                allocator.unspill(sp.n_pages)
                req.spill = None

        def advance_staging() -> None:
            """Run ONE encoder layer for every staged (chunked) prefill;
            finished stages splice their cross-K/V and seed BOS, so the
            request starts decoding next round."""
            nonlocal state, tokens, chunk_rounds
            n_enc = self.model.cfg.n_enc_layers
            for slot, st in list(staging.items()):
                req = st["req"]
                if st["x"] is None:
                    src = np.zeros((1, enc_len), np.int32)
                    src[0, :req.n_src_tokens] = req.src
                    st["lens"] = jnp.asarray(
                        np.asarray([req.n_src_tokens], np.int32))
                    begin, _ = self._stage_fns()
                    st["x"] = begin(self.params, jnp.asarray(src),
                                    st["lens"])
                st["x"] = self._stage_layer_fn(st["li"])(
                    self.params, st["x"], st["lens"])
                st["li"] += 1
                chunk_rounds += 1
                if st["li"] >= n_enc:
                    _, finish = self._stage_fns()
                    ck, cv, slens = finish(self.params, st["x"], st["lens"])
                    extra = {}
                    if allocator:
                        extra["pages"] = jnp.asarray(self._page_rows(
                            [req], 1, 1, allocator.n_pages))
                    state, tokens = self._chunk_splice_fn(1)(
                        state, tokens, ck, cv, slens,
                        jnp.asarray(np.asarray([req.slot], np.int32)),
                        extra)
                    staging.pop(slot)

        def prefill_into_slots(admitted, state, tokens):
            """Prefill newly admitted requests and splice them in."""
            g = len(admitted)
            src_pad, lens = pad_batch([r.src for r in admitted],
                                      length=enc_len)
            logits, sub, width = self._prefill_padded(src_pad, lens)
            # argmax at the padded width: device shapes depend only on the
            # pow2 bucket; the admission-group size g appears host-side
            first = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            if pc is not None and any(r.prefix_role == "insert"
                                      for r in admitted):
                ins = sched.chain_pages_matrix(admitted, width, enc_len)
                state = self._pool_insert_fn()(
                    state, sub["cross_k"], sub["cross_v"], jnp.asarray(ins))
            pages = (self._page_rows(admitted, 1, width, allocator.n_pages)
                     if allocator else None)
            state, tokens = self._splice_rows(
                state, tokens, sub, first,
                np.asarray([r.slot for r in admitted], np.int32), width,
                pages=pages)
            first_host = np.asarray(first)[:g]
            t = now()
            for r, tok in zip(admitted, first_host):
                r.first_token_s = t
                tok = int(tok)
                if r.max_new_tokens <= 0 or tok == self.eos_id:
                    sched.release(r, t, step=decode_steps)
                    # zero budget / empty translation
                else:
                    r.tokens.append(tok)
                    if r.max_new_tokens <= 1:
                        sched.release(r, t, step=decode_steps)
            return state, tokens

        round_idx = 0
        while not sched.all_done:
            rnd = round_idx
            round_idx += 1
            with jax.profiler.TraceAnnotation("engine.round", round=rnd):
                with jax.profiler.TraceAnnotation("engine.admit", round=rnd):
                    # (a) chaos: forced preemptions at this round edge
                    if chaos is not None and sched.slot_map:
                        by_id = {r.req_id: r for r in sched.slot_map.values()}
                        for rid in chaos.victims_for(rnd, list(by_id)):
                            preempt_req(by_id[rid])
                    # (b) overcommit growth for mid-flight rows (may itself
                    # evict); speculative macro-steps write up to spec+1
                    # positions each
                    grow_rows((ctrl.k if ctrl else K) * spec_mult)
                    # (c) admission pressure: evict strictly-less-urgent
                    # victims
                    preempt_for_admission()
                    plan = None
                    admitted = []
                    want_admit = (sched.n_waiting and sched.n_free >=
                                  min(max(admit_min_free, 1), sched.n_waiting,
                                      n_slots))
                    if want_admit and fused_admission:
                        # admission rides the NEXT burst dispatch: the plan's
                        # padded sources/destinations become burst-program
                        # inputs
                        plan = sched.plan_admission(now(), step=decode_steps,
                                                    enc_len=enc_len,
                                                    oob_row=n_slots)
                        if plan.n_admitted:
                            prefill_rounds += 1
                        encoder_tokens += len(plan.requests) * enc_len
                        if plan.resumed:
                            restore_resumed(plan.resumed)
                        for r in plan.staged:
                            staging[r.slot] = {"req": r, "x": None, "li": 0,
                                               "lens": None}
                        chunked_admissions += len(plan.staged)
                        encoder_tokens += len(plan.staged) * enc_len
                    elif want_admit:
                        admitted = sched.admit(now(), step=decode_steps)
                        if admitted:
                            prefill_rounds += 1
                            resumed = [r for r in admitted
                                       if r.spill is not None]
                            fresh = [r for r in admitted if r.spill is None]
                            if resumed:
                                restore_resumed(resumed)
                            hits: List[Request] = []
                            if pc is not None:
                                # zero-budget requests skip prefix routing:
                                # they release inside prefill_into_slots before
                                # any finish() could pair with their admit()
                                misses, hits = sched.assign_prefix(
                                    [r for r in fresh if r.max_new_tokens > 0])
                                enc_list = misses + [r for r in fresh
                                                     if r.max_new_tokens <= 0]
                            else:
                                enc_list = fresh
                            if enc_list:
                                prefill_dispatches += 1
                                host_syncs += 1   # the first-token drain syncs
                                encoder_tokens += len(enc_list) * enc_len
                                state, tokens = prefill_into_slots(
                                    enc_list, state, tokens)
                            if hits:
                                # no encoder: gather the cached chains and
                                # defer the first token to the next burst (BOS
                                # seed)
                                hrows, hlens, hpages, hw = sched.shape_hits(
                                    hits, enc_len=enc_len, oob_row=n_slots)
                                extra = ({"dec_pages": jnp.asarray(
                                    self._page_rows(hits, 1, hw,
                                                    allocator.n_pages))}
                                         if allocator else {})
                                state, tokens = self._hit_splice_fn(1)(
                                    state, tokens, jnp.asarray(hpages),
                                    jnp.asarray(hlens), jnp.asarray(hrows),
                                    extra)
                    peak_running = max(peak_running, sched.n_running)
                    # per-row budgets: every occupied slot has ≥1 token left
                    # to emit.  Staging slots stay at 0 — they hold no KV yet,
                    # so the fused prologue treats them as dead (re-sentinels
                    # their tables) until their chunked encode completes.
                    remaining = np.zeros((n_slots,), np.int32)
                    for slot, req in sched.slot_map.items():
                        if slot in staging:
                            continue
                        remaining[slot] = req.max_new_tokens - len(req.tokens)
                    has_adm = plan is not None and (plan.width
                                                    or plan.hit_width)
                if not sched.slot_map:
                    continue    # every admitted request finished on token 1
                if not remaining.any() and not has_adm:
                    # pure-staging round: nothing to decode — push the staged
                    # encodes one layer and come back
                    with jax.profiler.TraceAnnotation("engine.free",
                                                      round=rnd):
                        advance_staging()
                    continue
                with jax.profiler.TraceAnnotation("engine.dispatch",
                                                  round=rnd):
                    cap = jnp.asarray(ctrl.k, jnp.int32) if ctrl else cap_fixed
                    t_dispatch = time.perf_counter()
                    if plan is not None and (plan.width or plan.hit_width):
                        extra = {}
                        if allocator and plan.width:
                            extra["pages"] = jnp.asarray(self._page_rows(
                                plan.requests, 1, plan.width,
                                allocator.n_pages))
                        if pc is not None and plan.width:
                            extra["ins_pages"] = jnp.asarray(plan.ins_pages)
                        if plan.hit_width:
                            extra["hit_rows"] = jnp.asarray(plan.hit_rows)
                            extra["hit_lens"] = jnp.asarray(plan.hit_lengths)
                            extra["hit_pages"] = jnp.asarray(plan.hit_pages)
                            if allocator:
                                extra["hit_dec_pages"] = jnp.asarray(
                                    self._page_rows(plan.hits, 1,
                                                    plan.hit_width,
                                                    allocator.n_pages))
                        tokens, _, state, buf, steps_dev = fused_burst(
                            self.params, tokens, jnp.asarray(remaining), cap,
                            state, jnp.asarray(plan.src_tokens),
                            jnp.asarray(plan.src_lengths),
                            jnp.asarray(plan.base_rows), extra)
                    else:
                        tokens, _, state, buf, steps_dev = burst(
                            self.params, tokens, jnp.asarray(remaining), cap,
                            state)
                with jax.profiler.TraceAnnotation("engine.wait", round=rnd):
                    buf_host = np.asarray(buf)    # ONE host sync per burst
                    steps = int(steps_dev)
                burst_wall = time.perf_counter() - t_dispatch
                host_syncs += 1
                step_base = decode_steps
                decode_steps += steps

                with jax.profiler.TraceAnnotation("engine.drain", round=rnd):
                    # drain the ring buffer: release at EOS / budget
                    # exhaustion; latencies are observed at the burst edge
                    # (burst granularity)
                    t = now()
                    freed = []
                    wasted_row_steps = 0
                    emit_col = width * spec_mult   # first packed-counter col
                    for slot, req in list(sched.slot_map.items()):
                        if slot in staging:
                            # mid-stage rows are inert grid: their ring columns
                            # are masked EOS, not output (draining one would
                            # falsely release the request)
                            wasted_row_steps += steps
                            continue
                        if req.first_token_s is None:
                            req.first_token_s = t   # fused: from this burst
                        if spec:
                            # speculative ring: rows emit different counts per
                            # macro-step, so the drain is driven by the per-row
                            # emitted counter, and busy/wasted are counted in
                            # macro-steps the row was live (act column).
                            # Release steps are attributed at burst
                            # granularity.
                            n_emit = int(buf_host[slot, emit_col])
                            act = int(buf_host[slot, emit_col + 3])
                            for i in range(n_emit):
                                tok = int(buf_host[slot, i])
                                if tok == self.eos_id:
                                    freed.append(sched.release(
                                        req, t, step=step_base + steps))
                                    break
                                req.tokens.append(tok)
                                if len(req.tokens) >= req.max_new_tokens:
                                    freed.append(sched.release(
                                        req, t, step=step_base + steps))
                                    break
                            busy_slot_steps += act
                            wasted_row_steps += steps - act
                            draft_tokens += int(buf_host[slot, emit_col + 1])
                            accepted_tokens += int(
                                buf_host[slot, emit_col + 2])
                            continue
                        used = steps
                        done = len(req.tokens)
                        for s in range(steps):
                            tok = int(buf_host[slot, s])
                            if tok == self.eos_id:
                                used = s + 1
                                freed.append(sched.release(
                                    req, t, step=step_base + s + 1))
                                break
                            req.tokens.append(tok)
                            if len(req.tokens) >= req.max_new_tokens:
                                used = s + 1
                                freed.append(sched.release(
                                    req, t, step=step_base + s + 1))
                                break
                        busy_slot_steps += used
                        wasted_row_steps += steps - used
                        if self.paged:
                            read, slots = self._kv_page_counts(done, used, 1)
                            kv_pages_read += read
                            kv_page_slots += slots
                    if ctrl:
                        ctrl.observe(burst_wall, steps, wasted_row_steps,
                                     n_slots)
                    watchdog.observe(burst_wall +
                                     (chaos.slow_for(rnd) if chaos else 0.0))
                with jax.profiler.TraceAnnotation("engine.free", round=rnd):
                    if freed and (not fused_admission or eager_free):
                        # fused mode normally resets dead cursors inside the
                        # next admission burst's prologue — but under
                        # growth/preemption freed pages can be handed out
                        # before any prologue runs, so dead rows are sentineled
                        # eagerly
                        state = dict(state)
                        free = (kvc.free_slots_paged if self.paged
                                else kvc.free_slots)
                        state["cache"] = free(state["cache"],
                                              np.asarray(freed, np.int32))
                    # (h) advance chunked prefills one encoder layer, after the
                    # drain so a stage admitted this round runs its first layer
                    # in this round but never rides this round's burst
                    advance_staging()

        if pc is not None:
            # hand the (possibly donated-through) pool arrays back to the
            # engine so the next serve and the tree agree on contents
            self._prefix_pool = (state["prefix_k"], state["prefix_v"])
        return ServeResult(requests=reqs, n_slots=n_slots,
                           decode_steps=decode_steps,
                           busy_slot_steps=busy_slot_steps,
                           kv_pages_read=kv_pages_read,
                           kv_page_slots=kv_page_slots,
                           prefill_rounds=prefill_rounds, wall_s=now(),
                           host_syncs=host_syncs,
                           burst_len=ctrl.k if ctrl else K,
                           prefill_dispatches=prefill_dispatches,
                           encoder_tokens=encoder_tokens,
                           fused_admission=fused_admission,
                           auto_burst=ctrl is not None,
                           paged=self.paged, page_size=self.page_size,
                           pages_in_use=allocator.in_use if allocator else 0,
                           page_hwm=allocator.hwm if allocator else 0,
                           speculative_k=spec,
                           draft_tokens=draft_tokens,
                           accepted_tokens=accepted_tokens,
                           **self._mesh_result_fields(n_slots),
                           **self._overload_result_fields(
                               overcommit, preempt_count, store, watchdog,
                               sched, reqs, allocator, peak_running,
                               chunked_admissions, chunk_rounds),
                           **self._prefix_result_fields(pc, stats0))

    # ------------------------------------------------- continuous beam search
    def _serve_beam(self, requests: Sequence[Any], *, n_slots: int,
                    beam: Union[int, Sequence[int]], alpha: float,
                    max_new_tokens: Union[int, Sequence[int]],
                    prefill_token_budget: Optional[int],
                    admit_min_free: int, pad_to_multiple: int,
                    burst_len: Optional[Union[int, str]],
                    fused_admission: bool = True,
                    prefix_cache: Optional[bool] = None,
                    overcommit: float = 1.0,
                    prefill_chunk: Optional[int] = None,
                    chaos: Optional[ChaosSchedule] = None) -> ServeResult:
        """Continuous beam search: beam-group slot lifecycle.

        Structure mirrors the greedy ``serve`` loop, at group granularity:

        * a request is admitted into ``beam`` contiguous rows; its source
          is prefilled replicated across the group (exactly as
          ``generate_beam`` tiles its batch) and its first ``beam`` tokens
          come from one top-k over the group's beam-0 logits;
        * each burst runs ``_make_beam_serve_burst``'s group-masked body;
          at the edge the host replays the group's composed beam
          permutation over its token history, appends the new ring-buffer
          columns, and — when the group's budget is spent or every row has
          finished — picks the length-penalized winner, releases the
          request, and frees all ``beam`` rows atomically
          (``kv_cache.free_groups``) so the next waiting request can take
          the group mid-decode.

        Host-visible per-group state (scores, finished mask) round-trips
        through float32/bool numpy between bursts — bit-exact, which is
        what keeps the output token-identical to per-request
        :meth:`generate_beam` at every ``burst_len``.

        With ``fused_admission=True`` the admission round rides the burst
        program (one dispatch per round): each source is encoded **once**
        and broadcast across its group's rows, group scores are seeded
        host-side as ``[0, -1e30, …]`` so the burst's first step takes the
        top-k over beam-0 logits exactly as ``generate_beam`` does, and
        the group's token history starts empty (the first tokens arrive
        with the burst drain, in final beam order).

        **Mixed beam widths**: ``beam`` may be a per-request sequence (or
        ``Request.beam`` may be set).  The grid compiles one program at
        the *maximum* width; a narrower request runs only the first
        ``beam_req`` rows of its group and the tail rows are *parked*
        (see ``_make_beam_step``) — each step is then exactly a
        ``beam_req``-wide beam step, so every request stays
        token-identical to ``generate_beam(beam=beam_req)``.  With the
        paged cache, parked rows reserve **no pages**, so mixed widths
        cost HBM proportional to the widths actually requested — no
        fragmentation-aware free list, because pages cannot fragment.

        Overload machinery (overcommit growth, preempt-by-page-spill,
        deadline shedding, chunked prefill, chaos) works at *group*
        granularity: a preemption spills the whole group — all ``beam``
        rows' pages plus the host-side search state (scores, finished
        mask, token history, budget) — and resume re-seeds both sides
        bit-identically.
        """
        with jax.profiler.TraceAnnotation("engine.setup", round=0):
            self._check_overload_args(overcommit, prefill_chunk, chaos,
                                      fused_admission)
            reqs = self._as_requests(requests, max_new_tokens)
            # resolve each request's effective width WITHOUT mutating the
            # caller's Request objects (a serve()-written default would stick
            # to a reused Request and silently shadow a later serve's beam):
            # an explicit `beam` sequence wins, then a user-set Request.beam,
            # then the scalar default
            if isinstance(beam, (list, tuple, np.ndarray)):
                seq = [int(b) for b in beam]
                if len(seq) != len(reqs):
                    raise ValueError(f"beam sequence length {len(seq)} != "
                                     f"{len(reqs)} requests")
                width_of = {r.req_id: b for r, b in zip(reqs, seq)}
                default_beam = max(seq) if seq else 1
            else:
                default_beam = int(beam)
                if default_beam < 1:
                    raise ValueError(f"beam must be ≥ 1, got {default_beam}")
                width_of = {r.req_id: (int(r.beam) if r.beam is not None
                                       else default_beam) for r in reqs}
            for r in reqs:
                if width_of[r.req_id] < 1:
                    raise ValueError(f"beam must be ≥ 1, got "
                                     f"{width_of[r.req_id]} "
                                     f"(request {r.req_id})")
            beam = max(list(width_of.values()) + [default_beam])  # grid width
            K = self._resolve_burst(burst_len)
            ctrl = self._burst_controller(K)
            n_groups = n_slots // beam
            if n_groups < 1:
                raise ValueError(f"n_slots={n_slots} rows cannot hold a "
                                 f"beam-{beam} group")
            R = n_groups * beam                 # rows actually in the grid
            if not reqs:
                return ServeResult(requests=[], n_slots=R, decode_steps=0,
                                   busy_slot_steps=0, prefill_rounds=0,
                                   wall_s=0.0, host_syncs=0,
                                   burst_len=ctrl.k if ctrl else K,
                                   beam=beam, fused_admission=fused_admission,
                                   auto_burst=ctrl is not None,
                                   paged=self.paged, page_size=self.page_size,
                                   **self._mesh_result_fields(R))
            if max(r.max_new_tokens for r in reqs) > self.max_len:
                raise ValueError("a request's max_new_tokens exceeds the "
                                 f"engine KV capacity {self.max_len}")
            width = next_pow2(ctrl.max_burst if ctrl else K)
            burst = self._beam_serve_burst_fn(width, beam)
            fused_burst = (self._fused_beam_serve_burst_fn(width, beam)
                           if fused_admission else None)
            enc_len = self._enc_bucket(reqs, pad_to_multiple)
            pc = self._resolve_prefix_cache(prefix_cache)
            stats0 = pc.stats.snapshot() if pc else None

            allocator = None
            if self.paged:
                allocator = self._make_allocator(R, overcommit)
                for r in reqs:
                    need = self._pages_per_request(r, width_of[r.req_id])
                    if need > allocator.n_pages:
                        raise ValueError(
                            f"request {r.req_id} needs {need} pages but the "
                            f"pool holds {allocator.n_pages}")
            burst_hint = ctrl.max_burst if ctrl else K
            initial_fn = None
            if allocator is not None and overcommit > 1.0:
                initial_fn = lambda r: self._initial_pages(
                    r, width_of[r.req_id], burst_hint)
            sched = ContinuousScheduler(
                R, group_size=beam, prefill_token_budget=prefill_token_budget,
                allocator=allocator,
                pages_per_request=(
                    (lambda r: self._pages_per_request(r, width_of[r.req_id]))
                    if allocator else None),
                prefix_cache=pc, initial_pages=initial_fn,
                prefill_chunk=prefill_chunk)
            sched.submit_many(reqs)

            quantized = self.quant.quantize_kv
            state = self.model.init_decode_state(
                R, self.max_len, quantized=quantized, enc_len=enc_len,
                paged=self.paged, page_size=self.page_size,
                n_pages=allocator.n_pages if allocator else None)
            if pc is not None:
                state["prefix_k"], state["prefix_v"] = self._prefix_pool
            state = self._shard_state(state)
            tokens = jnp.zeros((R,), jnp.int32)
            # bytes one beam step's cache reorder moves: paged = the table
            # permutation + one partial-page copy per row; unpaged = the whole
            # KV slab plus the per-row cross-K/V gather
            cache0 = state["cache"]
            if self.paged:
                reorder_step_bytes = cache0.reorder_bytes_per_step()
            else:
                cross_bytes = 0
                if state["cross_k"] is not None:
                    cross_bytes = 2 * (state["cross_k"].size
                                       * state["cross_k"].dtype.itemsize)
                reorder_step_bytes = cache0.nbytes() + cross_bytes
            # host-side per-row beam state (re-uploaded each burst, bit-exact)
            scores_np = np.zeros((R,), np.float32)
            finished_np = np.ones((R,), bool)   # unoccupied rows are inert
            # base → its (beam,) columns; base → decode steps left
            histories: Dict[int, List[np.ndarray]] = {}
            budget_left: Dict[int, int] = {}

        t0 = time.perf_counter()
        now = lambda: time.perf_counter() - t0
        decode_steps = 0
        busy_slot_steps = 0
        kv_pages_read = kv_page_slots = 0
        prefill_rounds = 0
        host_syncs = 0
        prefill_dispatches = 0
        encoder_tokens = 0
        # fixed caps upload the device scalar once; auto rebuilds per round
        cap_fixed = None if ctrl else jnp.asarray(K, jnp.int32)
        # ---- overload machinery (all inert on an unloaded serve)
        store = SpillStore()
        watchdog = StepWatchdog()
        staging: Dict[int, Dict[str, Any]] = {}   # base → staged-encode state
        eager_free = (overcommit > 1.0) or (chaos is not None)
        preempt_count = 0
        peak_running = 0
        chunked_admissions = 0
        chunk_rounds = 0
        maxP = self._max_pages

        def preempt_req(req: Request) -> None:
            """Spill one running group — all ``beam`` rows' device state
            plus the host-side search state — and evict it (a mid-stage
            chunked prefill just drops its stage and restages later)."""
            nonlocal state, host_syncs, preempt_count
            base = req.slot
            if base in staging:
                staging.pop(base)
                sched.preempt(req, now())
            else:
                rows = np.arange(base, base + beam, dtype=np.int32)
                outs = self._spill_fn(beam)(state, tokens,
                                            jnp.asarray(rows))
                k, v, ks, vs, lens, toks, ck, cv, slens = [
                    None if o is None else np.asarray(o) for o in outs]
                host_syncs += 1
                req.spill = SpilledRequest(
                    req_id=req.req_id, n_rows=beam, k=k, v=v, k_scale=ks,
                    v_scale=vs, lengths=lens, tokens_row=toks, cross_k=ck,
                    cross_v=cv, src_lengths=slens,
                    n_pages=len(req.pages or []),
                    beam={"scores": scores_np[base:base + beam].copy(),
                          "finished": finished_np[base:base + beam].copy(),
                          "history": histories.pop(base, []),
                          "budget_left": budget_left.pop(base, 0)})
                store.put(req.spill)
                sched.preempt(req, now())
                finished_np[base:base + beam] = True   # rows now inert
            preempt_count += 1
            state = dict(state)
            state["cache"] = kvc.free_slots_paged(
                state["cache"],
                np.arange(base, base + beam, dtype=np.int32))

        def grow_rows(k_cap: int) -> None:
            """Pre-burst page growth at group granularity: each live row
            of a running group gets pages for its cursor + next burst."""
            nonlocal state
            if initial_fn is None:
                return
            for base, req in list(sched.slot_map.items()):
                if sched.slot_map.get(base) is not req or base in staging:
                    continue
                b = width_of[req.req_id]
                cursor = req.max_new_tokens - budget_left[base]
                cap_tok = min(req.max_new_tokens, self.max_len)
                need = kvc.pages_per_row(min(cursor + k_cap, cap_tok),
                                         self.page_size)
                have_pr = len(req.pages) // b
                extra_pr = need - have_pr
                if extra_pr <= 0:
                    continue
                extra = extra_pr * b
                newp = allocator.alloc(extra)
                while newp is None:
                    victims, covered = pick_victims(
                        [r for r in sched.slot_map.values() if r is not req],
                        pages_needed=extra - allocator.n_free,
                        key_fn=sched.victim_key,
                        pages_held_fn=lambda r: len(r.pages or []))
                    if not victims or not covered:
                        # fail BEFORE spilling (see greedy grow_rows)
                        raise RuntimeError(
                            "page growth wedged: no preemptable victim "
                            f"set covers request {req.req_id}'s need "
                            f"({extra} pages)")
                    for v in victims:
                        preempt_req(v)
                    newp = allocator.alloc(extra)
                upd = np.full((beam, maxP), -1, np.int32)
                for i in range(b):
                    upd[i, have_pr:have_pr + extra_pr] = \
                        newp[i * extra_pr:(i + 1) * extra_pr]
                # flat page list becomes interleaved after growth — only
                # len() (growth) and release (order-agnostic) read it from
                # here on; a resume always reallocates fresh
                req.pages.extend(newp)
                state = self._grow_fn(beam)(
                    state,
                    jnp.asarray(np.arange(base, base + beam,
                                          dtype=np.int32)),
                    jnp.asarray(upd))

        def preempt_for_admission() -> None:
            if initial_fn is None:
                return
            for _ in range(n_groups + len(reqs)):
                short = sched.admission_shortfall()
                if short is None:
                    return
                need = max(short["pages_short"], 1)
                victims, covered = pick_victims(
                    list(sched.slot_map.values()), pages_needed=need,
                    key_fn=sched.victim_key,
                    pages_held_fn=lambda r: len(r.pages or []),
                    min_key=short["head_key"])
                if not victims or not covered:
                    # insufficient coverage: spilling these victims would
                    # not let the head request in — keep them running
                    return
                for v in victims:
                    preempt_req(v)

        def restore_resumed(resumed: List[Request]) -> None:
            """Re-splice spilled groups: device KV through the paged
            splice, host search state verbatim."""
            nonlocal state, tokens
            for req in resumed:
                sp = req.spill
                base, b = req.slot, width_of[req.req_id]
                pages = self._page_rows([req], beam, 1, allocator.n_pages,
                                        widths=[b])
                rows = np.arange(base, base + beam, dtype=np.int32)
                state, tokens = self._resume_fn(beam)(
                    state, tokens, jnp.asarray(rows), jnp.asarray(pages),
                    jnp.asarray(sp.k), jnp.asarray(sp.v),
                    None if sp.k_scale is None else jnp.asarray(sp.k_scale),
                    None if sp.v_scale is None else jnp.asarray(sp.v_scale),
                    jnp.asarray(sp.lengths), jnp.asarray(sp.tokens_row),
                    jnp.asarray(sp.cross_k), jnp.asarray(sp.cross_v),
                    jnp.asarray(sp.src_lengths))
                scores_np[base:base + beam] = sp.beam["scores"]
                finished_np[base:base + beam] = sp.beam["finished"]
                histories[base] = list(sp.beam["history"])
                budget_left[base] = sp.beam["budget_left"]
                store.pop(req.req_id)
                allocator.unspill(sp.n_pages)
                req.spill = None

        def advance_staging() -> None:
            """One encoder layer per round for staged (chunked) prefills;
            completion splices the group and seeds its beam state exactly
            like fused admission."""
            nonlocal state, tokens, chunk_rounds
            n_enc = self.model.cfg.n_enc_layers
            for base, st in list(staging.items()):
                req = st["req"]
                if st["x"] is None:
                    src = np.zeros((1, enc_len), np.int32)
                    src[0, :req.n_src_tokens] = req.src
                    st["lens"] = jnp.asarray(
                        np.asarray([req.n_src_tokens], np.int32))
                    begin, _ = self._stage_fns()
                    st["x"] = begin(self.params, jnp.asarray(src),
                                    st["lens"])
                st["x"] = self._stage_layer_fn(st["li"])(
                    self.params, st["x"], st["lens"])
                st["li"] += 1
                chunk_rounds += 1
                if st["li"] >= n_enc:
                    _, finish = self._stage_fns()
                    ck, cv, slens = finish(self.params, st["x"], st["lens"])
                    b = width_of[req.req_id]
                    extra = {}
                    if allocator:
                        extra["pages"] = jnp.asarray(self._page_rows(
                            [req], beam, 1, allocator.n_pages, widths=[b]))
                    state, tokens = self._chunk_splice_fn(beam)(
                        state, tokens, ck, cv, slens,
                        jnp.asarray(np.asarray([base], np.int32)), extra)
                    scores_np[base] = 0.0
                    scores_np[base + 1:base + beam] = BEAM_SEED_NEG
                    finished_np[base:base + b] = False
                    finished_np[base + b:base + beam] = True
                    histories[base] = []
                    budget_left[base] = req.max_new_tokens
                    staging.pop(base)

        def finalize(req: Request, base: int, t: float, step: int) -> int:
            """Pick the group's winner (same helper ``generate_beam``
            uses), then release the request (returns the freed base row).
            Only the request's own ``beam`` rows compete — parked tail
            rows of a narrow group carry no hypotheses."""
            b = width_of[req.req_id]
            grid = np.stack(histories.pop(base), axis=1)[:b]   # (b, T)
            toks, score = self._winner(grid, scores_np[base:base + b],
                                       alpha, self.eos_id)
            req.tokens = [int(x) for x in toks]
            req.score = score
            budget_left.pop(base, None)
            finished_np[base:base + beam] = True
            return sched.release(req, t, step=step)

        def prefill_groups(admitted, state, tokens):
            """Prefill admitted requests replicated to their beam rows and
            splice the groups in; drain first tokens (one top-k per group,
            identical to ``generate_beam``'s first step)."""
            g = len(admitted)
            rows = g * beam
            src_pad, lens = pad_batch([r.src for r in admitted],
                                      length=enc_len)
            logits, sub, width = self._prefill_padded(
                np.repeat(src_pad, beam, axis=0),
                np.repeat(lens, beam, axis=0))
            if pc is not None and any(r.prefix_role == "insert"
                                      for r in admitted):
                # the tiled side batch holds request i's (batch-independent)
                # encode at row i*beam — scatter that row into its chain
                ins = sched.chain_pages_matrix(admitted, width, enc_len,
                                               stride=beam)
                state = self._pool_insert_fn()(
                    state, sub["cross_k"], sub["cross_v"], jnp.asarray(ins))
            # log-softmax at the padded width (device shapes stay a
            # function of the pow2 bucket); the (g, beam)-shaped first-step
            # top-k moves to the host, where a stable argsort of the
            # negated row reproduces jax.lax.top_k exactly (descending
            # values, ties broken by ascending index) on the same float32
            # log-probs generate_beam's device top-k selects from
            lp = np.asarray(log_probs(logits, self.mesh))
            first = lp[:rows].reshape(g, beam, -1)[:, 0]     # (g, V)
            tok_host = np.argsort(-first, axis=-1,
                                  kind="stable")[:, :beam].astype(np.int32)
            sc_host = np.take_along_axis(first, tok_host, axis=-1)
            # narrow requests: only the first beam_req candidates become
            # hypotheses; the parked tail rows seed as finished EOS rows
            # at the score floor (exactly the fused path's park seed)
            for i, r in enumerate(admitted):
                b = width_of[r.req_id]
                tok_host[i, b:] = self.eos_id
                sc_host[i, b:] = BEAM_SEED_NEG
            sub_np = np.full((width,), self.eos_id, np.int32)
            sub_np[:rows] = tok_host.reshape(rows)
            pages = None
            if allocator:
                pages = np.full((width, self._max_pages), allocator.n_pages,
                                np.int32)
                pages[:rows] = self._page_rows(
                    admitted, beam, g, allocator.n_pages,
                    widths=[width_of[r.req_id] for r in admitted])
            state, tokens = self._splice_rows(
                state, tokens, sub, jnp.asarray(sub_np),
                np.asarray(kvc.group_rows(
                    np.asarray([r.slot for r in admitted], np.int32),
                    beam)),
                width, pages=pages)
            t = now()
            for i, r in enumerate(admitted):
                base, b = r.slot, width_of[r.req_id]
                r.first_token_s = t
                if r.max_new_tokens <= 0:
                    finished_np[base:base + beam] = True
                    sched.release(r, t, step=decode_steps)
                    continue                     # zero budget: empty output
                scores_np[base:base + beam] = sc_host[i]
                fin = tok_host[i] == self.eos_id
                fin[b:] = True                   # parked rows stay finished
                finished_np[base:base + beam] = fin
                histories[base] = [tok_host[i].astype(np.int32)]
                budget_left[base] = r.max_new_tokens - 1
                if fin.all() or budget_left[base] <= 0:
                    finalize(r, base, t, step=decode_steps)
            return state, tokens

        round_idx = 0
        while not sched.all_done:
            rnd = round_idx
            round_idx += 1
            with jax.profiler.TraceAnnotation("engine.round", round=rnd):
                with jax.profiler.TraceAnnotation("engine.admit", round=rnd):
                    # (a) chaos: forced preemptions at this round edge
                    if chaos is not None and sched.slot_map:
                        by_id = {r.req_id: r for r in sched.slot_map.values()}
                        for rid in chaos.victims_for(rnd, list(by_id)):
                            preempt_req(by_id[rid])
                    # (b) overcommit growth for mid-flight groups (may evict)
                    grow_rows(ctrl.k if ctrl else K)
                    # (c) admission pressure: evict strictly-less-urgent
                    # victims
                    preempt_for_admission()
                    plan = None
                    admitted = []
                    want_admit = (sched.n_waiting and sched.n_free >=
                                  min(max(admit_min_free, 1), sched.n_waiting,
                                      n_groups))
                    if want_admit and fused_admission:
                        # encode-once fused admission: the plan carries ONE
                        # source row per request; the burst program broadcasts
                        # it across the group's rows.  Host seeds the group's
                        # beam state so the shared step's first iteration IS
                        # generate_beam's first step (see
                        # _make_fused_beam_serve_burst).
                        plan = sched.plan_admission(now(), step=decode_steps,
                                                    enc_len=enc_len, oob_row=R)
                        if plan.n_admitted:
                            prefill_rounds += 1
                        encoder_tokens += len(plan.requests) * enc_len
                        if plan.resumed:
                            restore_resumed(plan.resumed)
                        for r in plan.staged:
                            staging[r.slot] = {"req": r, "x": None, "li": 0,
                                               "lens": None}
                        chunked_admissions += len(plan.staged)
                        encoder_tokens += len(plan.staged) * enc_len
                        for r in plan.requests + plan.hits:
                            base, b = r.slot, width_of[r.req_id]
                            scores_np[base] = 0.0
                            scores_np[base + 1:base + beam] = BEAM_SEED_NEG
                            finished_np[base:base + b] = False
                            finished_np[base + b:base + beam] = True  # parked
                            histories[base] = []
                            budget_left[base] = r.max_new_tokens
                    elif want_admit:
                        admitted = sched.admit(now(), step=decode_steps)
                        if admitted:
                            prefill_rounds += 1
                            resumed = [r for r in admitted
                                       if r.spill is not None]
                            fresh = [r for r in admitted if r.spill is None]
                            if resumed:
                                restore_resumed(resumed)
                            hits: List[Request] = []
                            if pc is not None:
                                # zero-budget requests skip prefix routing:
                                # they release inside prefill_groups before any
                                # finish() could pair with their admit()
                                misses, hits = sched.assign_prefix(
                                    [r for r in fresh if r.max_new_tokens > 0])
                                enc_list = misses + [r for r in fresh
                                                     if r.max_new_tokens <= 0]
                            else:
                                enc_list = fresh
                            if enc_list:
                                prefill_dispatches += 1
                                host_syncs += 1   # the first-token drain syncs
                                # the unfused side batch tiles each source
                                # beam× through the encoder — the FLOP tax
                                # encode-once fusion removes
                                encoder_tokens += (len(enc_list) * beam
                                                   * enc_len)
                                state, tokens = prefill_groups(enc_list, state,
                                                               tokens)
                            if hits:
                                # no encoder: gather cached chains, splice them
                                # across each group's rows, and seed the group
                                # exactly like fused admission (first tokens
                                # arrive with the next burst, in final beam
                                # order)
                                hrows, hlens, hpages, hw = sched.shape_hits(
                                    hits, enc_len=enc_len, oob_row=R)
                                extra = ({"dec_pages": jnp.asarray(
                                    self._page_rows(
                                        hits, beam, hw, allocator.n_pages,
                                        widths=[width_of[r.req_id]
                                                for r in hits]))}
                                         if allocator else {})
                                state, tokens = self._hit_splice_fn(beam)(
                                    state, tokens, jnp.asarray(hpages),
                                    jnp.asarray(hlens), jnp.asarray(hrows),
                                    extra)
                                for r in hits:
                                    base, b = r.slot, width_of[r.req_id]
                                    scores_np[base] = 0.0
                                    scores_np[base + 1:base + beam] = \
                                        BEAM_SEED_NEG
                                    finished_np[base:base + b] = False
                                    finished_np[base + b:base + beam] = True
                                    histories[base] = []
                                    budget_left[base] = r.max_new_tokens
                    peak_running = max(peak_running, sched.n_running)
                    # staging groups stay at budget 0 / finished rows — they
                    # hold no KV yet; the fused prologue re-sentinels their
                    # tables and the burst's act mask keeps their rows frozen
                    remaining_in = np.zeros((n_groups,), np.int32)
                    parked_np = np.zeros((R,), bool)
                    for base, req in sched.slot_map.items():
                        if base in staging:
                            continue
                        remaining_in[base // beam] = budget_left[base]
                        parked_np[base + width_of[req.req_id]:
                                  base + beam] = True
                    has_adm = plan is not None and (plan.width
                                                    or plan.hit_width)
                if not sched.slot_map:
                    continue    # every admitted group finished on token 1
                if not remaining_in.any() and not has_adm:
                    # pure-staging round: nothing to decode — push the staged
                    # encodes one layer and come back
                    with jax.profiler.TraceAnnotation("engine.free",
                                                      round=rnd):
                        advance_staging()
                    continue
                with jax.profiler.TraceAnnotation("engine.dispatch",
                                                  round=rnd):
                    parked = jnp.asarray(parked_np)
                    cap = jnp.asarray(ctrl.k, jnp.int32) if ctrl else cap_fixed
                    t_dispatch = time.perf_counter()
                    if plan is not None and (plan.width or plan.hit_width):
                        extra = {}
                        if allocator and plan.width:
                            extra["pages"] = jnp.asarray(self._page_rows(
                                plan.requests, beam, plan.width,
                                allocator.n_pages, widths=[width_of[r.req_id]
                                        for r in plan.requests]))
                        if pc is not None and plan.width:
                            extra["ins_pages"] = jnp.asarray(plan.ins_pages)
                        if plan.hit_width:
                            extra["hit_rows"] = jnp.asarray(plan.hit_rows)
                            extra["hit_lens"] = jnp.asarray(plan.hit_lengths)
                            extra["hit_pages"] = jnp.asarray(plan.hit_pages)
                            if allocator:
                                extra["hit_dec_pages"] = jnp.asarray(
                                    self._page_rows(
                                        plan.hits, beam, plan.hit_width,
                                        allocator.n_pages,
                                        widths=[width_of[r.req_id]
                                                for r in plan.hits]))
                        (tokens, scores_dev, finished_dev, remaining_dev, comp,
                         state, buf, steps_dev) = fused_burst(
                            self.params, tokens, jnp.asarray(scores_np),
                            jnp.asarray(finished_np),
                            jnp.asarray(remaining_in), cap, state, parked,
                            jnp.asarray(plan.src_tokens),
                            jnp.asarray(plan.src_lengths),
                            jnp.asarray(plan.base_rows), extra)
                    else:
                        (tokens, scores_dev, finished_dev, remaining_dev, comp,
                         state, buf, steps_dev) = burst(
                            self.params, tokens, jnp.asarray(scores_np),
                            jnp.asarray(finished_np),
                            jnp.asarray(remaining_in), cap, state, parked)
                with jax.profiler.TraceAnnotation("engine.wait", round=rnd):
                    buf_host = np.asarray(buf)    # ONE host sync per burst
                    comp_host = np.asarray(comp)
                    scores_np = np.array(scores_dev, np.float32)
                    finished_np = np.array(finished_dev, bool)
                    remaining_out = np.asarray(remaining_dev)
                    steps = int(steps_dev)
                burst_wall = time.perf_counter() - t_dispatch
                host_syncs += 1
                step_base = decode_steps
                decode_steps += steps

                with jax.profiler.TraceAnnotation("engine.drain", round=rnd):
                    # drain at the burst edge: replay each group's composed
                    # beam permutation over its host-side history, append its
                    # new ring columns, finalize groups that finished or spent
                    # their budget
                    t = now()
                    freed = []
                    wasted_row_steps = 0
                    for base, req in list(sched.slot_map.items()):
                        if base in staging:
                            # staged encode in flight: the group's rows rode
                            # the burst frozen (finished, budget 0) — pure
                            # overhead
                            wasted_row_steps += steps * beam
                            continue
                        gi = base // beam
                        s_g = int(remaining_in[gi] - remaining_out[gi])
                        if req.first_token_s is None:
                            req.first_token_s = t   # fused: from this burst
                        b_req = width_of[req.req_id]
                        if self.paged:
                            read, slots = self._kv_page_counts(
                                len(histories[base]), s_g, b_req)
                            kv_pages_read += read
                            kv_page_slots += slots
                        if s_g:
                            local = comp_host[base:base + beam] - base
                            hist = [c[local] for c in histories[base]]
                            hist.extend(buf_host[base:base + beam, j]
                                        for j in range(s_g))
                            histories[base] = hist
                            budget_left[base] -= s_g
                        # parked rows of narrow requests are computed-but-idle
                        # grid
                        busy_slot_steps += s_g * b_req
                        wasted_row_steps += (steps - s_g) * beam + \
                            s_g * (beam - b_req)
                        if finished_np[base:base + beam].all() or \
                                budget_left[base] <= 0:
                            freed.append(finalize(req, base, t,
                                                  step=step_base + s_g))
                    if ctrl:
                        ctrl.observe(burst_wall, steps, wasted_row_steps, R)
                    watchdog.observe(burst_wall +
                                     (chaos.slow_for(rnd) if chaos else 0.0))
                with jax.profiler.TraceAnnotation("engine.free", round=rnd):
                    if freed and (not fused_admission or eager_free):
                        # fused mode resets dead cursors inside the next
                        # admission burst's prologue (kv_cache.free_inactive) —
                        # no dispatch.  Under overcommit/chaos, free eagerly
                        # even then: growth or resume may hand the freed pages
                        # to another group before any admission prologue runs,
                        # and the dead group's stale block table would route
                        # masked-but-real writes into them.
                        state = dict(state)
                        if self.paged:
                            state["cache"] = kvc.free_slots_paged(
                                state["cache"],
                                kvc.group_rows(np.asarray(freed, np.int32),
                                               beam))
                        else:
                            state["cache"] = kvc.free_groups(
                                state["cache"], np.asarray(freed, np.int32),
                                beam)
                    # staged encodes advance one layer per serving round
                    advance_staging()

        if pc is not None:
            # hand the (possibly donated-through) pool arrays back to the
            # engine so the next serve and the tree agree on contents
            self._prefix_pool = (state["prefix_k"], state["prefix_v"])
        return ServeResult(requests=reqs, n_slots=R,
                           decode_steps=decode_steps,
                           busy_slot_steps=busy_slot_steps,
                           kv_pages_read=kv_pages_read,
                           kv_page_slots=kv_page_slots,
                           prefill_rounds=prefill_rounds, wall_s=now(),
                           host_syncs=host_syncs,
                           burst_len=ctrl.k if ctrl else K, beam=beam,
                           prefill_dispatches=prefill_dispatches,
                           encoder_tokens=encoder_tokens,
                           fused_admission=fused_admission,
                           auto_burst=ctrl is not None,
                           paged=self.paged, page_size=self.page_size,
                           pages_in_use=allocator.in_use if allocator else 0,
                           page_hwm=allocator.hwm if allocator else 0,
                           reorder_bytes=reorder_step_bytes * decode_steps,
                           **self._mesh_result_fields(R),
                           **self._overload_result_fields(
                               overcommit, preempt_count, store, watchdog,
                               sched, reqs, allocator, peak_running,
                               chunked_admissions, chunk_rounds),
                           **self._prefix_result_fields(pc, stats0))

    # ------------------------------------------------------------------ beam
    def generate_beam(self, batch: Dict[str, np.ndarray], *, beam: int = 4,
                      max_new_tokens: int = 64, alpha: float = 0.6,
                      burst_len: Optional[int] = None) -> GenerationResult:
        """Beam search with per-step cache reordering (paper's GatherNd).

        The whole per-step body — log-softmax, top-k, score update, cache
        gather — runs inside the jitted burst; the host reorders the token
        history once per burst via the composed beam permutation.
        """
        K = self._resolve_burst(burst_len)
        if K == "auto":
            K = 8      # adaptation targets serve(); static batches use a mid cap
        bfn = self._beam_burst_fn(next_pow2(K), beam)
        batch = {k: jnp.asarray(v) for k, v in batch.items()}
        B = next(iter(batch.values())).shape[0]

        # expand each request to `beam` rows
        def tile(a):
            return jnp.repeat(a, beam, axis=0)
        beam_batch = {k: tile(v) for k, v in batch.items()}
        BB = B * beam

        t0 = time.perf_counter()
        state = self._init_state(BB)
        logits, state = self._prefill(self.params, beam_batch, state)
        jax.block_until_ready(logits)
        t1 = time.perf_counter()

        logprobs = log_probs(logits, self.mesh)
        V = logprobs.shape[-1]
        # first step: take top-`beam` distinct tokens of beam 0 per request
        first = logprobs.reshape(B, beam, V)[:, 0]              # (B, V)
        scores, tok0 = jax.lax.top_k(first, beam)               # (B, beam)
        scores = scores.reshape(BB)
        tokens = tok0.reshape(BB).astype(jnp.int32)
        seq = [np.asarray(tokens)]
        host_syncs = 1
        finished = tokens == self.eos_id
        all_done = bool(jnp.all(finished))

        steps_left = max_new_tokens - 1
        while steps_left > 0 and not all_done:
            cap = jnp.asarray(min(K, steps_left), jnp.int32)
            tokens, scores, finished, comp, state, buf, s = bfn(
                self.params, tokens, scores, finished, cap, state)
            s = int(s)
            comp_host = np.asarray(comp)
            buf_host = np.asarray(buf)
            all_done = bool(np.asarray(finished).all())
            host_syncs += 1
            # ---- the paper's §5.3 hot op happened on device; replay the
            # composed reorder over the host-side history once per burst
            seq = [c[comp_host] for c in seq]
            seq.extend(buf_host[:, i] for i in range(s))
            steps_left -= s
        jax.block_until_ready(scores)
        t2 = time.perf_counter()

        # best beam per request by length-penalized score
        grid = np.stack(seq, axis=1)                             # (BB, T)
        scores_host = np.asarray(scores, np.float32)
        seqs = [self._winner(grid[b * beam:(b + 1) * beam],
                             scores_host[b * beam:(b + 1) * beam],
                             alpha, self.eos_id)[0]
                for b in range(B)]
        return GenerationResult(tokens=seqs, steps=len(seq),
                                prefill_s=t1 - t0, decode_s=t2 - t1,
                                host_syncs=host_syncs)
