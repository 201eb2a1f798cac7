#!/usr/bin/env python3
"""Bring-up check of the paper's INT8 serving path on a TPU.

    python chip_smoke.py                # one chip
    python chip_smoke.py --four-chips   # multi-chip paths, one four-chip host

One chip: transformer-base at its published widths (d_model 512, 6+6
layers, 8 heads, d_ff 2048, vocab 37000, bf16 activations; seeded random
weights) is calibrated and INT8-quantized by ``launch/serve.py``'s own
helpers (symmetric, static activation thresholds, INT8 paged KV cache),
then serves synthetic requests through ``ServingEngine.serve`` —
continuous, paged, fused admission — greedy and with beam 4.  Checks:

* every request finishes with tokens;
* greedy ``serve`` tokens equal ``generate`` tokens for the same requests;
* the compiled serve burst calls the Pallas INT8 GEMM, paged
  decode-attention and scale-writing kernels;
* one decode step's logits through the Pallas kernels agree with the same
  step through the kernels' XLA forms within ``LOGIT_RTOL``;
* the compiled paged decode-attention kernel matches its f32 oracle at the
  base, transformer-big, greedy, GQA and chunked shapes, reading each
  layer of a stacked pool by its index
  (``tests/test_paged_kv.py::test_paged_kernel_on_chip_matches_oracle``),
  and the compiled writes of the stacked scale pools leave them as XLA's
  scatters would
  (``tests/test_paged_kv.py::test_scale_pool_writes_on_chip_match_xla``).

``--four-chips`` runs only the multi-chip checks: a tp=4 serve (mesh 1x4)
against a tp=1 serve of the same requests, greedy and beam 4; and a
four-replica router, each replica on its own chip, against one engine
serving each replica's share.

Times and memory printed are one cold run's smoke numbers, not benchmark
results.  Any failed check exits non-zero.  On success the last line of
stdout is ``{"ok": true, "device": {...}}``.  Without a TPU, or without
the repo's ``src/`` beside this file, it exits non-zero before building
anything.
"""

from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import sys
import time
from pathlib import Path

import numpy as np

ARCH = "transformer-base"
N_REQUESTS = 16
N_CALIB = 8            # calibration sentences (one compile per source length)
MAX_NEW_TOKENS = 16
N_SLOTS = 8            # greedy decode rows
BEAM = 4               # the paper's beam width
BEAM_GROUPS = 4        # beam serve grid: BEAM * BEAM_GROUPS rows
PAGE_SIZE = 16
LOGIT_RTOL = 2e-2      # max |pallas - xla| / max |xla| over f32 logits
N_CHIPS = 4            # --four-chips: tp degree and replica count
SEED = 0               # random weights, requests and calibration sentences


def require_tpu():
    """The chip's devices, after putting the repo's ``src`` on the path;
    exits non-zero when either is missing."""
    src = Path(__file__).resolve().parent / "src"
    if not (src / "repro").is_dir():
        sys.exit(f"chip_smoke: {src / 'repro'} not found; run this from a "
                 "checkout of the repository")
    sys.path.insert(0, str(src))
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU, JAX found "
                 f"{devices[0].platform!r}")
    return devices


class Checks:
    """Runs every check to the end, printing each; remembers failures."""

    def __init__(self):
        self.failed = []

    def __call__(self, name: str, ok: bool, detail: str = "") -> None:
        print(f"check {name}: {'ok' if ok else 'FAILED'}"
              + (f" ({detail})" if detail else ""), flush=True)
        if not ok:
            self.failed.append(name)


def smoke(msg: str) -> None:
    print(f"smoke (not a benchmark): {msg}", flush=True)


def timed(fn):
    t0 = time.perf_counter()
    out = fn()
    return out, time.perf_counter() - t0


def build():
    """Published-width model with seeded weights, requests and calibration
    sentences, through ``launch/serve.py``'s own config."""
    import jax
    from repro.data import make_corpus
    from repro.launch.serve import serving_config
    from repro.models import build_model

    cfg = serving_config(ARCH, published=True)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(SEED))
    corpus = make_corpus(N_REQUESTS + N_CALIB, cfg.vocab, seed=SEED)
    print(f"model: {cfg.name} d_model={cfg.d_model} "
          f"layers={cfg.n_enc_layers}+{cfg.n_layers} heads={cfg.n_heads} "
          f"d_ff={cfg.d_ff} vocab={cfg.vocab} dtype={cfg.dtype}", flush=True)
    return model, params, corpus[:N_REQUESTS], corpus[N_REQUESTS:]


def quantize(model, params, calib):
    import jax
    from repro.launch.serve import quantize_for_serving

    (qparams, qctx, recs), dt = timed(lambda: jax.block_until_ready(
        quantize_for_serving(model, params, calib, mode="symmetric")))
    smoke(f"calibrate + INT8 PTQ {dt:.1f}s (compile included), "
          f"{sum(r.quantize for r in recs.values())}/{len(recs)} sites "
          "quantizable")
    return qparams, qctx


def engine_for(model, params, quant, **kw):
    from repro.launch.serve import MAX_LEN
    from repro.serving import ServingEngine

    return ServingEngine(model, params, quant=quant, max_len=MAX_LEN,
                         paged=True, page_size=PAGE_SIZE, **kw)


def serve(engine, requests, beam=None):
    n_slots = N_SLOTS if beam is None else BEAM * BEAM_GROUPS
    return engine.serve(requests, n_slots=n_slots, beam=beam,
                        max_new_tokens=MAX_NEW_TOKENS)


def tokens(res, ids):
    return [np.asarray(res.tokens_for(i)) for i in ids]


def differing(want, got) -> list:
    return [k for k, (a, b) in enumerate(zip(want, got))
            if not np.array_equal(a, b)]


def all_finished(res) -> bool:
    return all(r.status == "finished" and len(r.tokens) > 0
               for r in res.requests)


def decode_logits(model, params, qctx, requests, warm_steps: int = 4):
    """One decode step's f32 logits through each kernel path, from the same
    paged INT8 state: the sources encoded and spliced in, then
    ``warm_steps`` greedy steps through the Pallas kernels."""
    import jax
    import jax.numpy as jnp
    from repro.data import pad_batch
    from repro.launch.serve import MAX_LEN

    src, lens = pad_batch([s.src for s in requests])
    B, max_pages = len(requests), MAX_LEN // PAGE_SIZE
    ck, cv, slens = jax.jit(functools.partial(
        model.encode_cross_kv, quant=qctx))(
            params, {"src_tokens": src, "src_lengths": lens})
    state = model.init_decode_state(B, MAX_LEN, quantized=True,
                                    enc_len=src.shape[1], paged=True,
                                    page_size=PAGE_SIZE)
    state = model.splice_prefill(
        state, ck, cv, slens, jnp.arange(B),
        pages=jnp.arange(B * max_pages).reshape(B, max_pages))
    step = {impl: jax.jit(functools.partial(
                model.decode_step, quant=dataclasses.replace(qctx, impl=impl)))
            for impl in ("pallas", "xla")}
    toks = jnp.zeros((B,), jnp.int32)                       # BOS
    for _ in range(warm_steps):
        logits, state = step["pallas"](params, toks, state)
        toks = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return {impl: np.asarray(f(params, toks, state)[0], np.float32)
            for impl, f in step.items()}


def paged_kernel_parity(check: Checks) -> None:
    """The repo's on-chip tests of the paged kernel and of the scale-pool
    writes, run in this process (the chip belongs to one process at a
    time)."""
    import pytest
    test = (Path(__file__).resolve().parent / "tests" / "test_paged_kv.py")
    for name, case in (
            ("paged_kernel_matches_oracle",
             "test_paged_kernel_on_chip_matches_oracle"),
            ("scale_pool_writes_match_xla",
             "test_scale_pool_writes_on_chip_match_xla")):
        rc = pytest.main(["-q", "-p", "no:cacheprovider", f"{test}::{case}"])
        check(name, rc == 0, f"pytest exit code {rc}")


def one_chip(check: Checks) -> None:
    import jax
    from repro.data import pad_batch
    from repro.data.sorting import next_pow2
    from repro.kernels.ops import resolve_impl
    from repro.launch.hlo_analysis import pallas_kernel_calls

    paged_kernel_parity(check)
    model, params, requests, calib = build()
    qparams, qctx = quantize(model, params, calib)
    check("kernels_resolve_to_pallas", resolve_impl(qctx.impl) == "pallas",
          f"impl={qctx.impl!r}")
    check("int8_kv_cache", qctx.quantize_kv)
    engine = engine_for(model, qparams, qctx)
    ids = range(N_REQUESTS)

    res, cold = timed(lambda: serve(engine, requests))
    warm_res, warm = timed(lambda: serve(engine, requests))
    served = tokens(res, ids)
    smoke(f"greedy serve of {N_REQUESTS} requests ({res.n_tokens} tokens): "
          f"{cold:.1f}s cold (compile included), {warm:.2f}s warm")
    check("greedy_all_finished", all_finished(res))
    check("greedy_serve_repeats", not differing(served, tokens(warm_res, ids)))

    src, lens = pad_batch([s.src for s in requests])
    gen = engine.generate({"src_tokens": src, "src_lengths": lens},
                          max_new_tokens=MAX_NEW_TOKENS)
    bad = differing([t[:MAX_NEW_TOKENS] for t in gen.tokens], served)
    check("greedy_serve_equals_generate", not bad,
          f"{len(bad)}/{N_REQUESTS} requests differ: {bad}" if bad else "")

    bres, bcold = timed(lambda: serve(engine, requests, beam=BEAM))
    smoke(f"beam-{BEAM} serve of {N_REQUESTS} requests ({bres.n_tokens} "
          f"tokens): {bcold:.1f}s cold (compile included)")
    check("beam_all_finished", all_finished(bres)
          and all(np.isfinite(r.score) for r in bres.requests))

    enc_len = next_pow2(max(len(s.src) for s in requests))
    calls = pallas_kernel_calls(
        engine.compile_burst(N_SLOTS, enc_len).as_text())
    print(f"serve burst kernel call sites: {calls}", flush=True)
    check("burst_calls_int8_matmul", calls.get("int8_matmul_pallas", 0) > 0)
    check("burst_calls_paged_attention",
          calls.get("decode_attention_paged_pallas", 0) > 0)
    check("burst_calls_scale_writes",
          calls.get("append_scales_paged_pallas", 0) > 0)

    logits = decode_logits(model, qparams, qctx, requests)
    ref = logits["xla"]
    rel = float(np.max(np.abs(logits["pallas"] - ref)) / np.max(np.abs(ref)))
    agree = int(np.sum(logits["pallas"].argmax(-1) == ref.argmax(-1)))
    check("pallas_logits_match_xla",
          bool(np.isfinite(logits["pallas"]).all()) and rel <= LOGIT_RTOL,
          f"max |diff| / max |xla| = {rel:.3e} (limit {LOGIT_RTOL}), "
          f"argmax agrees on {agree}/{len(ref)} rows")

    stats = jax.devices()[0].memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    smoke("peak device memory "
          + (f"{peak / 2**30:.2f} GiB" if peak else "not reported"))


def four_chips(check: Checks) -> None:
    import jax
    from repro.kernels.ops import resolve_impl
    from repro.launch.mesh import make_host_mesh
    from repro.serving import ReplicaRouter, Request

    if len(jax.devices()) != N_CHIPS:
        sys.exit(f"chip_smoke: --four-chips needs {N_CHIPS} devices, "
                 f"JAX found {len(jax.devices())}")
    model, params, requests, calib = build()
    qparams, qctx = quantize(model, params, calib)
    ids = range(N_REQUESTS)

    # (a) tensor parallel: GSPMD cannot partition a Pallas kernel, so the
    # sharded engine runs the kernels' XLA forms; tp=1 runs them too, so
    # the comparison isolates the sharding
    tp1 = engine_for(model, qparams, dataclasses.replace(qctx, impl="xla"))
    tp4 = engine_for(model, qparams, qctx,
                     mesh=make_host_mesh(data=1, model=N_CHIPS))
    check("tp4_runs_xla_forms", resolve_impl(tp4.quant.impl) == "xla",
          tp4.quant.impl)
    for beam in (None, BEAM):
        kind = "greedy" if beam is None else f"beam{beam}"
        r1, t1 = timed(lambda: serve(tp1, requests, beam))
        r4, t4 = timed(lambda: serve(tp4, requests, beam))
        smoke(f"{kind} serve, tp=1 {t1:.1f}s, tp={r4.tp_degree} {t4:.1f}s "
              "(compile included)")
        bad = differing(tokens(r1, ids), tokens(r4, ids))
        check(f"tp{N_CHIPS}_equals_tp1_{kind}",
              all_finished(r1) and all_finished(r4) and not bad
              and r4.tp_degree == N_CHIPS,
              f"{len(bad)}/{N_REQUESTS} requests differ: {bad}" if bad else "")

    # (b) replicas, each pinned to its own chip
    router = ReplicaRouter.on_devices(
        lambda device: engine_for(model, qparams, qctx, device=device),
        N_CHIPS)
    placed = [jax.tree.leaves(e.params)[0].devices() for e in router.engines]
    check("replicas_on_distinct_devices",
          all(len(d) == 1 for d in placed)
          and len(set().union(*placed)) == N_CHIPS,
          str([str(next(iter(d))) for d in placed]))
    rres, tr = timed(lambda: router.serve(requests, n_slots=N_SLOTS,
                                          max_new_tokens=MAX_NEW_TOKENS))
    smoke(f"router x{N_CHIPS} serve {tr:.1f}s (compile included), "
          f"assignment counts "
          f"{[rres.assignment.count(i) for i in range(N_CHIPS)]}")
    one = engine_for(model, qparams, qctx, device=jax.devices()[0])
    bad = []
    for i in range(N_CHIPS):
        share = [Request(req_id=k, src=np.asarray(requests[k].src, np.int32),
                         max_new_tokens=MAX_NEW_TOKENS)
                 for k, a in enumerate(rres.assignment) if a == i]
        ref = one.serve(share, n_slots=N_SLOTS, max_new_tokens=MAX_NEW_TOKENS)
        got = [r.req_id for r in share]
        bad += [got[k] for k in differing(tokens(ref, got),
                                          tokens(rres, got))]
    check("router_replicas_equal_one_replica",
          set(rres.assignment) == set(range(N_CHIPS))
          and all(r.status == "finished" for r in rres.requests) and not bad,
          f"{len(bad)}/{N_REQUESTS} requests differ: {bad}" if bad else "")


def main() -> None:
    ap = argparse.ArgumentParser(
        description="Run the INT8 serving path once on a TPU and check it.")
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the tensor-parallel (tp=4) and "
                         "four-replica router checks, on a four-chip host")
    args = ap.parse_args()
    devices = require_tpu()
    from repro.launch.compile_cache import enable_compile_cache

    print(f"compile cache: {enable_compile_cache()}")
    print(f"device: {devices[0].device_kind} x{len(devices)}", flush=True)
    check = Checks()
    (four_chips if args.four_chips else one_chip)(check)
    if check.failed:
        sys.exit(f"chip_smoke: failed checks: {', '.join(check.failed)}")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()
