"""Serving entry point: the paper's full inference stack.

``python -m repro.launch.serve --arch transformer-base --requests 64
  --quant symmetric --streams 2 --beam 1``

The model is the arch's CPU-runnable reduction (``cfg.reduced()``) by
default; ``--published`` builds it at its published widths instead (see
:func:`serving_config`).  Weights are seeded random (``PRNGKey(0)``).

Pipeline (``--mode static``, the paper's): synthetic requests →
token-sorted scheduler → (optional calibrated INT8 PTQ) → parallel stream
workers → throughput report.

``--mode continuous`` swaps the back half for the continuous batching
engine: requests are bin-packed to a token budget (FFD) for admission
order, then stream through ``ServingEngine.serve``'s slot-refill decode
loop, reporting per-request first-token/total latency and decode-grid
utilization.  ``--beam B`` (B > 1) with ``--mode continuous`` serves beam
search through the same engine: each request takes a group of B contiguous
decode rows (`--slots // B` groups), finished groups free all B rows
atomically and are refilled mid-decode.  Admissions ride the burst program
by default (one jitted dispatch per serve round; ``--unfused-admission``
restores the separate-prefill baseline), and ``--burst-len auto`` puts the
burst cap under the adaptive controller.
"""

from __future__ import annotations

import argparse
import dataclasses
import time
from typing import Dict, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ModelConfig, get_config
from repro.core import (
    Calibrator,
    QuantMode,
    QuantPolicy,
    Taps,
    count_quantized,
    quantize_model,
)
from repro.core.calibration import SiteCalibration
from repro.core.ptq import FP_CONTEXT, QuantContext
from repro.data import Sentence, make_corpus, pack_batches_token_budget
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.models import build_model
from repro.serving import ParallelStreams, ReplicaRouter, Request, \
    ServingEngine, TokenSortedScheduler, make_chaos

# decode positions every serving engine built here holds per row
MAX_LEN = 96


def serving_config(arch: str, *, published: bool = False) -> ModelConfig:
    """The model configuration this entry point builds.

    ``published=False`` gives the CPU-runnable reduction (d_model 64, two
    layers, float32).  ``published=True`` keeps every width, head count,
    the vocab and the bf16 activations as published, and runs the layers
    unrolled: calibration taps name one site per layer, which a
    ``lax.scan`` body traced once cannot, and ``remat`` only matters for
    training.
    """
    cfg = get_config(arch)
    if not cfg.enc_dec:
        raise SystemExit("serving expects an enc-dec (NMT) arch")
    if published:
        return dataclasses.replace(cfg, scan_layers=False, remat=False)
    return cfg.reduced()


def calibrate(model, params, sentences: Sequence[Sentence],
              mode: str) -> Dict[str, SiteCalibration]:
    """KL calibration over teacher-forced forwards of ``sentences``."""
    def tapped(p, src, tgt):
        taps = Taps()
        model.forward(p, {"src_tokens": src, "tgt_tokens": tgt}, taps=taps)
        return taps.values

    forward = jax.jit(tapped)           # one compile per sentence shape
    cal = Calibrator()
    for s in sentences:
        values = forward(params, jnp.asarray(s.src[None, :]),
                         jnp.asarray(np.concatenate([[1], s.tgt, [2]])[None]))
        for name, value in values.items():
            cal.observe_site(name, value)
    return cal.compute(mode)


def quantize_for_serving(model, params, calib: Sequence[Sentence], *,
                         mode: str = "symmetric", weight_bits: int = 8,
                         weight_group_size: int = 128
                         ) -> Tuple[dict, QuantContext, Dict[str, SiteCalibration]]:
    """Calibrate, then PTQ with static activation thresholds.

    Returns the quantized params, their runtime context (kernel choice
    ``"auto"``: the Pallas kernels on a TPU) and the calibration records.
    """
    recs = calibrate(model, params, calib, mode)
    qparams, qctx = quantize_model(
        params, recs, QuantPolicy(mode=QuantMode(mode), act_quant="static"),
        weight_bits=weight_bits, weight_group_size=weight_group_size)
    return qparams, qctx, recs


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="transformer-base")
    ap.add_argument("--published", action="store_true",
                    help="build the arch at its published widths (bf16, "
                         "unrolled layers) instead of the CPU-sized "
                         "reduction")
    ap.add_argument("--requests", type=int, default=64)
    ap.add_argument("--batch-size", type=int, default=16)
    ap.add_argument("--quant", default="symmetric",
                    choices=["none", "naive", "symmetric", "independent",
                             "conjugate"])
    ap.add_argument("--streams", type=int, default=2)
    ap.add_argument("--beam", type=int, default=1,
                    help="beam width (1 = greedy); with --mode continuous, "
                         "each request occupies a group of `beam` decode "
                         "rows (--slots // beam groups)")
    ap.add_argument("--max-new-tokens", type=int, default=24)
    ap.add_argument("--sort", default="tokens",
                    choices=["none", "words", "tokens"])
    ap.add_argument("--mode", default="static",
                    choices=["static", "continuous"])
    ap.add_argument("--slots", type=int, default=8,
                    help="decode slots for --mode continuous")
    ap.add_argument("--token-budget", type=int, default=256,
                    help="FFD bin budget (padded tokens) for admission "
                         "order in --mode continuous")
    ap.add_argument("--burst-len", default="8",
                    help="decode steps fused on device per host round trip "
                         "(1 = per-step loop; larger bursts cut dispatch "
                         "overhead but delay slot refill to burst edges); "
                         "'auto' adapts the cap between bursts from "
                         "measured sync cost vs mid-burst EOS waste")
    ap.add_argument("--unfused-admission", action="store_true",
                    help="serve admissions as separate prefill dispatches "
                         "(the pre-fusion baseline) instead of folding "
                         "them into the burst program")
    ap.add_argument("--paged", action="store_true",
                    help="back the decode KV cache with fixed-size pages + "
                         "block tables: beam reorder becomes a table "
                         "permutation (no slab copy) and admission is "
                         "paced by a page budget instead of contiguous "
                         "row capacity (--mode continuous only)")
    ap.add_argument("--page-size", type=int, default=16,
                    help="tokens per KV page (--paged; must divide the "
                         "engine max_len)")
    ap.add_argument("--n-pages", type=int, default=None,
                    help="page-pool size (--paged; default: contiguous-"
                         "equivalent capacity)")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="share encoded cross-K/V across requests with "
                         "identical sources: a radix-tree hit bumps a page "
                         "refcount instead of re-running the encoder "
                         "(--mode continuous only; token-identical output)")
    ap.add_argument("--prefix-pages", type=int, default=256,
                    help="prefix-cache chain-pool size in pages "
                         "(--prefix-cache; LRU-evicted under pressure)")
    ap.add_argument("--deadline-ms", type=float, default=None,
                    help="per-request deadline on the serve clock (--mode "
                         "continuous): the wait queue runs EDF-with-aging "
                         "and provably-unmeetable requests are shed with "
                         "status 'rejected' instead of admitted (note: "
                         "jit compile lands inside the first serve, so "
                         "tight SLOs shed on cold starts)")
    ap.add_argument("--overcommit", type=float, default=1.0,
                    help="KV page reservation cap as a multiple of the "
                         "physical pool (--paged; >1 admits past worst-"
                         "case reservation, preempt-by-page-spill covers "
                         "the shortfall when budgets actually collide)")
    ap.add_argument("--prefill-chunk", type=int, default=None,
                    help="sources longer than this many tokens stage one "
                         "encoder layer per serving round instead of "
                         "blocking an admission round on the full encode "
                         "(--mode continuous with fused admission)")
    ap.add_argument("--chaos-seed", type=int, default=None,
                    help="serving chaos harness: inject a seeded forced-"
                         "preemption schedule at burst edges (--paged); "
                         "output tokens are identical to an uninterrupted "
                         "serve — use to drill spill/restore in situ")
    ap.add_argument("--mesh", default=None, metavar="DATA,MODEL",
                    help="run the engine tensor-parallel on a (data,model) "
                         "mesh, e.g. '1,4': weights and K/V-pool heads "
                         "split on the model axis, token-identical output "
                         "(--mode continuous; needs that many devices — "
                         "XLA_FLAGS=--xla_force_host_platform_device_count"
                         "=N exposes host devices)")
    ap.add_argument("--replicas", type=int, default=1,
                    help="data-parallel engine replicas behind the "
                         "free-page/queue-depth router (--mode continuous; "
                         "each replica serves its share concurrently)")
    ap.add_argument("--weight-bits", type=int, default=8, choices=(8, 4),
                    help="weight payload precision: 8 = the paper's "
                         "per-channel INT8 everywhere; 4 = decoder FFN and "
                         "attention output projections drop to block-wise "
                         "INT4 (packed nibbles + group scale/min, dequant "
                         "fused into the matmul kernel) while activations, "
                         "attention score paths and the KV cache stay INT8")
    ap.add_argument("--weight-group-size", type=int, default=128,
                    help="rows per INT4 scale/min block along d_in "
                         "(--weight-bits 4; smaller = more accurate, "
                         "larger = fewer metadata bytes)")
    args = ap.parse_args()
    burst_len = args.burst_len if args.burst_len == "auto" \
        else int(args.burst_len)

    enable_compile_cache()
    cfg = serving_config(args.arch, published=args.published)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    corpus = make_corpus(args.requests + 64, cfg.vocab, seed=11)
    requests = corpus[:args.requests]

    qctx = FP_CONTEXT
    if args.quant != "none":
        params, qctx, recs = quantize_for_serving(
            model, params, corpus[args.requests:args.requests + 32],
            mode=args.quant, weight_bits=args.weight_bits,
            weight_group_size=args.weight_group_size)
        print(f"quantized with mode={args.quant}: "
              f"{sum(r.quantize for r in recs.values())}/{len(recs)} "
              "calibrated sites quantizable")
        if args.weight_bits == 4:
            stats = count_quantized(params)
            print(f"INT4 weights: {stats['int4_linears']} decoder linears, "
                  f"{stats['int4_bytes']} bytes "
                  f"(group_size={args.weight_group_size}); "
                  f"INT8 elsewhere: {stats['int8_bytes']} bytes")

    if args.mesh and args.mode != "continuous":
        raise SystemExit("--mesh needs --mode continuous")
    if args.replicas > 1 and args.mode != "continuous":
        raise SystemExit("--replicas needs --mode continuous")

    if args.mode == "continuous":
        mesh = None
        if args.mesh:
            try:
                data_ax, model_ax = (int(x) for x in args.mesh.split(","))
            except ValueError:
                raise SystemExit(f"--mesh wants 'DATA,MODEL', "
                                 f"got {args.mesh!r}")
            mesh = make_host_mesh(data=data_ax, model=model_ax)

        def mk_engine(device=None):
            return ServingEngine(model, params, quant=qctx, max_len=MAX_LEN,
                                 burst_len=burst_len, paged=args.paged,
                                 page_size=args.page_size,
                                 n_pages=args.n_pages,
                                 prefix_cache=args.prefix_cache,
                                 prefix_pages=args.prefix_pages,
                                 mesh=mesh, device=device)

        bins = pack_batches_token_budget(requests, args.token_budget)
        order = [i for b in bins for i in b]     # FFD admission order
        beam = args.beam if args.beam > 1 else None
        reqs = [requests[i] for i in order]
        if args.deadline_ms is not None:
            reqs = [Request(req_id=k, src=np.asarray(s.src, np.int32),
                            max_new_tokens=args.max_new_tokens,
                            deadline_s=args.deadline_ms / 1e3)
                    for k, s in enumerate(reqs)]
        chaos = (make_chaos(args.chaos_seed, n_rounds=256, preempt_every=2)
                 if args.chaos_seed is not None else None)
        serve_kw = dict(n_slots=args.slots,
                        max_new_tokens=args.max_new_tokens,
                        beam=beam,
                        fused_admission=not args.unfused_admission,
                        overcommit=args.overcommit,
                        prefill_chunk=args.prefill_chunk,
                        chaos=chaos)
        if args.replicas > 1:
            router = (ReplicaRouter.on_devices(mk_engine, args.replicas)
                      if mesh is None else     # replicas share the tp mesh
                      ReplicaRouter([mk_engine()
                                     for _ in range(args.replicas)]))
            rres = router.serve(reqs, **serve_kw)
            print(f"router x{args.replicas}: {len(rres.requests)} requests "
                  f"in {rres.wall_s:.2f}s ({rres.tokens_per_s:.1f} tok/s), "
                  f"per-replica peak_running "
                  f"{rres.peak_running_per_replica}, "
                  f"assignment counts "
                  f"{[rres.assignment.count(i) for i in range(args.replicas)]}")
            for i, r in enumerate(rres.results):
                print(f"  replica {i}: {sum(len(q.tokens) for q in r.requests)}"
                      f" tokens, {r.host_syncs} syncs, "
                      f"utilization {r.utilization:.2f}"
                      + (f", tp={r.tp_degree} mesh={r.mesh_shape}"
                         if r.tp_degree > 1 else ""))
            return
        engine = mk_engine()
        t0 = time.perf_counter()
        res = engine.serve(reqs, **serve_kw)
        dt = time.perf_counter() - t0
        met = res.metrics()
        print(f"served {args.requests} requests in {dt:.2f}s "
              f"({res.tokens_per_s:.1f} tok/s, "
              f"slot utilization {res.utilization:.2f}, "
              f"{res.prefill_rounds} admission rounds)")
        if res.tp_degree > 1:
            print(f"tensor-parallel: mesh {res.mesh_shape} "
                  f"(tp={res.tp_degree}), predicted "
                  f"{res.collective_bytes_per_step} collective "
                  f"bytes/step/device inside the burst")
        if beam:
            print(f"beam={res.beam}: {res.n_groups} groups of {res.beam} "
                  f"rows in a {res.n_slots}-row grid"
                  + (f" ({args.slots - res.n_slots} rows stranded — "
                     f"beam does not divide --slots)"
                     if res.n_slots != args.slots else ""))
        print(f"burst_len={res.burst_len}"
              + (" (auto)" if res.auto_burst else "")
              + f": {res.host_syncs} host syncs for "
              f"{res.decode_steps} decode steps "
              f"({res.decode_steps_per_s:.0f} steps/s)")
        print(("fused admission" if res.fused_admission
               else "UNFUSED admission")
              + f": {res.prefill_dispatches} prefill dispatches, "
              f"{res.encoder_tokens} encoder row-tokens")
        if res.paged:
            print(f"paged KV: page_size={res.page_size}, "
                  f"peak {res.page_hwm} pages "
                  f"({res.page_hwm * res.page_size} tokens), "
                  f"{res.pages_in_use} leaked, "
                  f"beam-reorder bytes {res.reorder_bytes}")
        if res.prefix_cache:
            print(f"prefix cache: {res.prefix_hits} hits / "
                  f"{res.prefix_hits + res.prefix_misses} admissions "
                  f"(hit rate {met['prefix_hit_rate']:.2f}), "
                  f"{res.prefix_hit_pages} chain pages reused, "
                  f"{res.prefix_pages_allocated} allocated, "
                  f"{res.prefix_evictions} evicted, "
                  f"{res.prefix_chains} chains resident")
        if (res.preemptions or res.chunked_admissions or res.rejected
                or res.overcommit != 1.0 or chaos is not None
                or args.deadline_ms is not None):
            print(f"overload: overcommit={res.overcommit} "
                  f"peak_running={res.peak_running}, "
                  f"{res.preemptions} preemptions "
                  f"({res.spill_events} spills / {res.restore_events} "
                  f"restores, {res.spilled_bytes / 1024:.1f} KiB to host), "
                  f"free_lwm={res.free_lwm}")
            print(f"         {res.chunked_admissions} chunked admissions "
                  f"({res.chunk_rounds} staged encoder rounds), "
                  f"{res.rejected} shed, "
                  f"{res.deadline_misses} deadline misses, "
                  f"{res.straggler_rounds} straggler rounds")
        print(f"latency: first-token mean "
              f"{met['first_token_latency_mean_s']:.3f}s "
              f"p95 {met['first_token_latency_p95_s']:.3f}s; total mean "
              f"{met['total_latency_mean_s']:.3f}s "
              f"p95 {met['total_latency_p95_s']:.3f}s")
        return

    engines = [ServingEngine(model, params, quant=qctx, max_len=MAX_LEN)
               for _ in range(args.streams)]
    sched = TokenSortedScheduler(batch_size=args.batch_size,
                                 sort_mode=args.sort)
    items = sched.plan(requests)
    print(f"{len(items)} batches; padding stats: {sched.stats(requests)}")

    def run_batch(sid: int, item) -> int:
        eng = engines[sid]
        if args.beam > 1:
            res = eng.generate_beam(item.batch, beam=args.beam,
                                    max_new_tokens=args.max_new_tokens)
        else:
            res = eng.generate(item.batch,
                               max_new_tokens=args.max_new_tokens)
        return res.n_tokens

    streams = ParallelStreams(run_batch, n_streams=args.streams)
    t0 = time.perf_counter()
    out = streams.run(items)
    dt = time.perf_counter() - t0
    print(f"served {args.requests} requests in {dt:.2f}s "
          f"({args.requests / dt:.2f} sentences/s, "
          f"{out['throughput_tok_s']:.1f} tok/s, "
          f"stream utilization {out['utilization']:.2f})")


if __name__ == "__main__":
    main()
