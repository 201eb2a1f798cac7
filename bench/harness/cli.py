"""One run of one cell: set up, measure a window, check, print one line.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace 0|1

With ``--trace 0`` the result's metrics are the cell's end-to-end metrics,
with ``--trace 1`` its per-layer metrics, read from a profiler trace of
the window.  The last line of standard output is one JSON object.
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import shutil
import sys
import tempfile
import time
from collections import Counter
from typing import Any, Callable, Dict, List, Optional

import numpy as np

from harness import spec

KERNELS = ("int8_matmul_pallas", "decode_attention_paged_pallas",
           "quantize_static_pallas")


class NoChip(Exception):
    """JAX finds no TPU, or fewer chips than the cell asks for."""


def require_chips(n: int):
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"needs a TPU; JAX found {devices[0].platform!r}")
    if len(devices) < n:
        raise NoChip(f"the cell needs {n} chips; JAX found {len(devices)}")
    return devices


def enable_compile_cache() -> None:
    """JAX's persistent cache at a fixed place inside the checkout; every
    program is written to it, however fast it compiled."""
    import jax

    jax.config.update("jax_compilation_cache_dir",
                      str(spec.ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


class CompileCount:
    """Executables built (compiled or loaded from the cache) and cache
    misses, from JAX's monitoring events; one listener per process."""

    _instance: Optional["CompileCount"] = None

    def __init__(self):
        import jax

        self.counts: Counter = Counter()
        self.names: List[str] = []
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    @classmethod
    def get(cls) -> "CompileCount":
        if cls._instance is None:
            cls._instance = cls()
        return cls._instance

    def _dur(self, name: str, secs: float, **kw) -> None:
        if name == "/jax/core/compile/backend_compile_duration":
            self.counts["executables"] += 1
            self.names.append(str(kw.get("fun_name", "?")))
            self.seconds += secs

    def _event(self, name: str, **kw) -> None:
        if name == "/jax/compilation_cache/cache_misses":
            self.counts["cache_misses"] += 1

    def snapshot(self) -> Dict[str, int]:
        return dict(self.counts)


@dataclasses.dataclass
class Context:
    """What a metric reader reads."""

    cell: Dict[str, Any]
    cfg: Dict[str, Any]
    mix: Dict[str, Any]
    window: Any                       # window.Window
    setup_s: float
    n_chips: int
    peaks: Dict[str, float]
    work: Any = None                  # work.Work
    trace: Any = None                 # trace.TraceSummary

    @property
    def results(self) -> List[Any]:
        return [r for c in self.window.calls for r in c.engine_results]

    @property
    def replica_tokens(self) -> List[int]:
        per: Dict[int, int] = {}
        for c in self.window.calls:
            for i, r in enumerate(c.engine_results):
                per[i] = per.get(i, 0) + sum(len(q.tokens)
                                             for q in r.requests)
        return [per[i] for i in sorted(per)]


def peaks_for(kind: str) -> Dict[str, float]:
    table = json.loads((spec.BENCH_DIR / "peaks.json").read_text())
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in peaks.json")
    return table[kind]


def count_work(ctx: Context):
    from harness import work

    shape = work.Shape.of(ctx.cfg)
    served = []
    for c in ctx.window.calls:
        for s, r in zip(c.sents, c.result.requests):
            # a beam group runs its whole budget unless every hypothesis
            # ends; a greedy row stops at EOS
            steps = s.max_new_tokens if ctx.mix["beam"] else min(
                len(r.tokens) + 1, s.max_new_tokens)
            served.append(work.Served(len(s.src), steps, s.beam))
    res = ctx.results
    return work.count(shape, served,
                      decode_steps=work.summed(res, "decode_steps"),
                      encodes=work.summed(res, "prefill_rounds"),
                      row_steps_cap=work.summed(res, "busy_slot_steps"))


def judge(system, window, limits: Dict[str, Any], seed: int,
          control_bits: Optional[int] = None):
    """The comparison with the plain reference (see ``harness.compare``),
    run after the program's state is freed.  With ``control_bits``, the
    control (the reference at that many weight bits) is read on the same
    sample too, into ``control_<number>`` of the detail."""
    from harness import compare
    from harness.window import served_requests

    cfg = system.cfg_file
    mix = system.mix
    failed, answers = compare.check_answers(served_requests(window),
                                            system.vocab)
    reference = spec.load_module(
        spec.BENCH_DIR / "configs" / f"{cfg['reference']}.py",
        cfg["reference"]).logits
    params = system.make_weights()
    numbers = {"failed_requests": float(failed)}
    detail: Dict[str, float] = {}
    greedy = [a for a in answers if a.beam == 1]
    beams = [a for a in answers if a.beam > 1]
    for name, group in (("logit_gap", greedy), ("score_gap", beams)):
        if not group:
            continue
        picked = compare.sample(group, mix["sample_requests"], seed)
        readings = compare.reference_readings(
            reference, cfg, params, picked,
            src_pad=mix["source_length"]["max"],
            dec_pad=cfg["serving"]["max_len"])
        if name == "logit_gap":
            numbers[name] = max(float(g.max()) for g, _ in readings)
        else:
            numbers[name] = compare.widest_score_gap(picked, readings,
                                                     mix["alpha"])
        detail[f"{name}_requests"] = len(picked)
        detail[f"{name}_tokens"] = sum(len(g) for g, _ in readings)
        if control_bits:
            low = compare.fake_quant(params, control_bits)
            ctl = compare.control_readings(
                reference, cfg, params, low, picked,
                src_pad=mix["source_length"]["max"],
                dec_pad=cfg["serving"]["max_len"])
            del low
            detail[f"control_{name}"] = (
                max(float(g.max()) for g, _ in ctl) if name == "logit_gap"
                else compare.widest_control_score_gap(readings, ctl))
    del params
    compared = {"failed_requests": {"value": failed, "limit": 0}}
    for name, lim in limits.items():
        compared[name] = {"value": numbers.get(name, np.inf),
                          "limit": lim["limit"]}
    detail.update({k: v for k, v in numbers.items() if k not in compared})
    return compare.Verdict(attempted=window.n_requests, failed=failed,
                           compared=compared, detail=detail)


def read_metrics(names: List[Dict[str, Any]], kind: str,
                 ctx: Context) -> Dict[str, Dict[str, Any]]:
    out = {}
    for m in names:
        value = spec.reader(kind, m["name"])(ctx)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def breakdown(summary) -> Dict[str, list]:
    ops = sorted(summary.op_s.items(), key=lambda kv: -kv[1])[:10]
    return {"device_ops": [[k, v] for k, v in ops],
            "idle_gaps": [[k, v] for k, v in summary.idle_gaps[:10]]}


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             t_start: float, require_chip: bool = True,
             bench: Optional[Dict[str, Any]] = None,
             cfg_override: Optional[Dict[str, Any]] = None,
             mix_override: Optional[Dict[str, Any]] = None,
             limits_override: Optional[Dict[str, Any]] = None,
             weight_bits: Optional[int] = None,
             control_bits: Optional[int] = None,
             compile_cache: bool = True,
             log: Callable[[str], None] = lambda s: None) -> Dict[str, Any]:
    """Everything a run does after parsing its arguments; returns the
    result object.  Tests drive it at reduced sizes through the overrides,
    with ``require_chip=False``, and read the control beside the program
    with ``control_bits``."""
    bench = bench or spec.load_benchmark()
    cell = spec.workload(bench, workload)
    cfg = {**spec.config_file(bench, cell["config"]), **(cfg_override or {})}
    mix = {**spec.traffic_file(cell["traffic"]), **(mix_override or {})}
    limits = limits_override or spec.limits_file(workload)
    if require_chip:
        require_chips(cell["chips"])
    if compile_cache:
        enable_compile_cache()
    import jax

    from harness import system as system_mod, window as window_mod

    compiles = CompileCount.get()
    devices = jax.devices()
    peaks = peaks_for(devices[0].device_kind) if require_chip else {}

    stages = [("init", time.perf_counter())]
    system = system_mod.System(cfg, mix, weight_bits=weight_bits)
    system.quantize()
    stages.append(("calibrate+quantize", time.perf_counter()))
    system.start()
    variants = system.warm_up()
    stages.append(("warm-up", time.perf_counter()))
    setup_s = time.perf_counter() - t_start
    before = compiles.snapshot()
    n_names = len(compiles.names)
    log(f"setup {setup_s:.3f} s ("
        + ", ".join(f"{name} {t1 - t0:.1f} s" for (_, t0), (name, t1)
                    in zip([("start", t_start)] + stages[:-1], stages))
        + f"): {before.get('executables', 0)} executables "
        f"({before.get('cache_misses', 0)} compiled) in "
        f"{compiles.seconds:.1f} s, {variants} burst variants warm")

    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    annotate = (jax.profiler.TraceAnnotation if trace
                else window_mod.no_spans)
    if trace:
        jax.profiler.start_trace(trace_dir)
    window = window_mod.run(system, seed, seconds, annotate)
    if trace:
        jax.profiler.stop_trace()
    after = compiles.snapshot()
    in_window = after.get("executables", 0) - before.get("executables", 0)
    log(f"window {window.elapsed_s:.3f} s: {len(window.calls)} calls, "
        f"{window.n_requests} requests, {window.n_tokens} tokens, "
        f"{in_window} executables built inside the window"
        + (f": {compiles.names[n_names:]}" if in_window else ""))

    used = devices[:cell["chips"]]
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in used)
    ctx = Context(cell=cell, cfg=cfg, mix=mix, window=window,
                  setup_s=setup_s, n_chips=cell["chips"], peaks=peaks)
    system.stop()
    gc.collect()

    summary = None
    if trace:
        from harness import trace as trace_mod

        t0 = time.perf_counter()
        ops, spans = trace_mod.extract(trace_mod.find_xplane(trace_dir))
        shutil.rmtree(trace_dir, ignore_errors=True)
        bounds = trace_mod.window_of(spans)
        summary = trace_mod.reduce(ops, spans, window_ns=bounds,
                                   kernels=KERNELS)
        log(f"trace: {len(ops)} device ops on {summary.devices}, read in "
            f"{time.perf_counter() - t0:.1f} s; kernels "
            f"{summary.kernel_s} calls {summary.kernel_calls}; idle by "
            f"host activity {summary.idle_by_activity}")
        ctx.trace = summary
        ctx.work = count_work(ctx)

    verdict = judge(system, window, limits, seed, control_bits)
    if trace:
        metrics = read_metrics(spec.per_layer_for(bench, workload),
                               "metrics", ctx)
    else:
        metrics = read_metrics(spec.end_to_end_for(bench, workload),
                               "end_to_end", ctx)
    dev = {"platform": devices[0].platform, "kind": devices[0].device_kind,
           "count": len(devices), "memory_peak_bytes": int(peak)}
    if summary is not None:
        dev["busy_s"] = summary.mean_busy_s
        dev["window_s"] = summary.window_s
    out = {"correct": verdict.correct, "attempted": verdict.attempted,
           "failed": verdict.failed, "metrics": metrics, "device": dev}
    if summary is not None:
        out["breakdown"] = breakdown(summary)
    out["checks"] = {"executables_in_window": in_window,
                     **verdict.detail}
    out["compared"] = verdict.compared
    for line in verdict.lines():
        log(line)
    return out


def main(argv: Optional[List[str]] = None, *, t_start: float) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    def log(msg: str) -> None:
        print(f"bench: {msg}", file=sys.stderr, flush=True)

    try:
        out = run_cell(args.workload, args.seed, args.seconds,
                       bool(args.trace), t_start=t_start, log=log)
    except (NoChip, spec.SpecError) as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    print(json.dumps(out), flush=True)
    return 0
