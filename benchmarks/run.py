"""Benchmark driver — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows.  Mapping to the paper:

* bench_calibration_modes  → Table 1  (BLEU per quantization mode)
* bench_int8_matmul        → Figure 3 (INT8 vs FP32 GEMM speedups)
* bench_kv_gather          → §5.3     (quantized GatherNd / beam reorder)
* bench_batching           → §5.4 + Figures 6/8 (sorting, parallel streams)
* bench_op_distribution    → Figure 7 (op-class split FP32 vs INT8)
* bench_continuous         → beyond §5.6 (static vs continuous batching)
* bench_decode_burst       → beyond §5.5 (on-device decode bursts vs
                             per-token host dispatch)
* bench_beam_serve         → §5.3 serving-side (continuous beam groups vs
                             per-request beam search, FP and INT8 cache)
"""

import sys
import time
import traceback


def main() -> None:
    from benchmarks import (
        bench_batching,
        bench_beam_serve,
        bench_calibration_modes,
        bench_continuous,
        bench_decode_burst,
        bench_int8_matmul,
        bench_kv_gather,
        bench_op_distribution,
    )
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    modules = [
        ("table1", bench_calibration_modes),
        ("fig3", bench_int8_matmul),
        ("s5.3", bench_kv_gather),
        ("fig6/8", bench_batching),
        ("fig7", bench_op_distribution),
        ("continuous", bench_continuous),
        ("burst", bench_decode_burst),
        ("beam", bench_beam_serve),
    ]
    only = sys.argv[1] if len(sys.argv) > 1 else None
    print("name,us_per_call,derived")
    failures = 0
    for tag, mod in modules:
        if only and only not in tag and only not in mod.__name__:
            continue
        t0 = time.time()
        try:
            for row in mod.run():
                print(",".join(str(x) for x in row), flush=True)
        except Exception as e:  # pragma: no cover
            failures += 1
            print(f"{mod.__name__},ERROR,{e!r}", flush=True)
            traceback.print_exc(file=sys.stderr)
        print(f"# {mod.__name__} finished in {time.time() - t0:.1f}s",
              flush=True)
    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
