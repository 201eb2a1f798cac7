"""Reduced sizes at which the tests drive whole runs on the CPU
(``run_cell`` with ``require_chip=False``): every width cut to a toy, the
traffic cut to a few dozen short sentences.

A run of a mix is a run of the first cell of ``BENCHMARK.json`` that
serves it, with that cell's metrics; a mix held as data for a later cell
is driven under the first cell's entry, with its file in place of the
cell's traffic."""

import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from harness import cli, spec  # noqa: E402

BENCH = spec.load_benchmark()
MIXES = sorted(p.stem for p in (spec.BENCH_DIR / "traffic").glob("*.json"))

TINY_CFG = {
    "n_enc_layers": 2, "n_layers": 2, "d_model": 64, "n_heads": 4,
    "n_kv_heads": 4, "head_dim": 16, "d_ff": 128, "vocab": 128,
    "dtype": "float32",
    "serving": {"max_len": 32, "page_size": 16, "paged": True,
                "fused_admission": True, "burst_len": 8,
                "admission_enc_bucket": "max"},
    "calibration": {"sentences": 4, "length": 8},
}
LENGTHS = {"source_length": {"dist": "lognormal", "median": 6, "sigma": 0.6,
                             "min": 2, "max": 16},
           "budget": {"factor": 1.1, "cap": 16}, "sample_requests": 64}
# at these sizes, over 48 to 64 sampled requests (350 to 520 tokens), sound
# runs read a widest score gap of 0.0116 to 0.0165 (beam 4; seeds 11, 12,
# 13, 2**31 + 7, 3000000019) and a widest logit gap of 0 to 0.0103
# (greedy; seeds 11, 12, 2**31 + 7); the control, the reference at INT4
# weights, 0.046 to 0.048 and 0.097 to 0.102; a planted fault reads tenths
TINY_LIMITS = {"greedy": {"logit_gap": {"limit": 0.03}},
               "beam": {"score_gap": {"limit": 0.025}}}


def tiny_mix(name: str) -> dict:
    mix = {**spec.traffic_file(name), **LENGTHS}
    if mix["kind"] == "offline":
        mix.update(job_sentences=16 * mix["replicas"], n_slots=8)
    else:
        mix.update(n_slots=4, block_calls=8,
                   paragraph_sentences={"min": 1, "max": 4})
    return mix


def cell_for(mix: str) -> str:
    return next((w["name"] for w in BENCH["workloads"]
                 if w["traffic"] == mix), BENCH["workloads"][0]["name"])


def run(mix: str, seed: int = 2**31 + 7, *, seconds: float = 0.2,
        trace: bool = False, **kw) -> dict:
    m = tiny_mix(mix)
    limits = TINY_LIMITS["beam" if m["beam"] else "greedy"]
    return cli.run_cell(cell_for(mix), seed, seconds, trace,
                        t_start=time.perf_counter(), require_chip=False,
                        compile_cache=False, cfg_override=TINY_CFG,
                        mix_override=m, limits_override=limits, **kw)
