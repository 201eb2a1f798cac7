#!/usr/bin/env python3
"""Readings a cell's limits are set from: the numbers the comparison reads
(``harness.compare``) for the program on many seeds and for the controls
on a few, in one process.

    python3 bench/readings.py --workload <name> --seeds 1,2,...
        [--control-seeds 7,8,9] [--seconds 2] [--sentences 1024]

The program runs as the configuration states.  Two controls, each in the
precision below the configuration's INT8: the program's own INT4 weight
path (``weight_bits=4``) served through the same timed path on
``--control-seeds``, and the plain reference at INT4 weights
(``compare.fake_quant``) put in the program's place on every program
seed's sample.  Each seed drives the cell's timed path for a short window
at the cell's own load and compares its answers with the plain reference
as a benchmark run does; ``--sentences`` shortens an offline job (same
grid, same length distribution).  Prints one JSON line per seed and a last
line with, for each number, the largest program reading and the smallest
reading of each control.  Not part of a benchmark run.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH.parent / "src"))
sys.path.insert(0, str(BENCH))

from harness import cli, spec  # noqa: E402


def readings(workload: str, seeds, seconds: float, weight_bits=None,
             sentences=None, control_bits=None):
    from harness import system as system_mod, window as window_mod

    bench = spec.load_benchmark()
    cell = spec.workload(bench, workload)
    cfg = spec.config_file(bench, cell["config"])
    mix = spec.traffic_file(cell["traffic"])
    if sentences:
        mix["job_sentences"] = sentences
    limits = {"score_gap" if mix["beam"] else "logit_gap":
              {"limit": float("inf")}}
    system = system_mod.System(cfg, mix, weight_bits=weight_bits)
    t0 = time.perf_counter()
    system.quantize()
    system.start()
    system.warm_up()
    setup = time.perf_counter() - t0
    out = []
    for seed in seeds:
        window = window_mod.run(system, seed, seconds)
        verdict = cli.judge(system, window, limits, seed,
                            control_bits=control_bits)
        row = {"workload": workload, "weight_bits": system.weight_bits,
               "seed": seed, "setup_s": setup,
               **{k: v["value"] for k, v in verdict.compared.items()},
               "failed": verdict.failed, "attempted": verdict.attempted,
               "window_s": window.elapsed_s, **verdict.detail}
        print(json.dumps(row), flush=True)
        out.append(row)
    system.stop()
    gc.collect()
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=2.0)
    ap.add_argument("--sentences", type=int, default=None)
    args = ap.parse_args()
    cell = spec.workload(spec.load_benchmark(), args.workload)
    cli.require_chips(cell["chips"])
    cli.enable_compile_cache()
    seeds = [int(s) for s in args.seeds.split(",") if s]
    ctl_seeds = [int(s) for s in args.control_seeds.split(",") if s]
    prog = readings(args.workload, seeds, args.seconds,
                    sentences=args.sentences, control_bits=4)
    ctl = readings(args.workload, ctl_seeds, args.seconds, weight_bits=4,
                   sentences=args.sentences) if ctl_seeds else []
    summary = {"workload": args.workload}
    for name in ("logit_gap", "score_gap"):
        if name in prog[0]:
            summary[name] = {
                "lower": max(r[name] for r in prog if r["failed"] == 0),
                "program": [r[name] for r in prog],
                "upper_int4_path": min((r[name] for r in ctl),
                                       default=None),
                "int4_path": [r[name] for r in ctl],
                "upper_int4_reference": min(
                    (r[f"control_{name}"] for r in prog
                     if f"control_{name}" in r), default=None),
                "int4_reference": [r.get(f"control_{name}") for r in prog]}
    print(json.dumps(summary))
    return 0


if __name__ == "__main__":
    sys.exit(main())
