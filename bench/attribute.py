#!/usr/bin/env python3
"""One traced run of a cell, with its time put down to the program's layers.

    python3 bench/attribute.py --workload <name> --seed <n> --seconds <s>
        [--records <path.json.gz>]

Runs the cell as ``bench/run.py --trace 1`` does and prints that run's
result object with an ``attribution`` entry added: device seconds per
named scope (``harness.attribution``), the program's host spans, the
window's idle time by the host span it fell in, the per-layer numbers
these give, and the window's tokens per second with tracing on (the
result's own metrics are the traced run's per-layer metrics, read as
``bench/run.py`` reads them).  It keeps what the harness extracts and
serves by wrapping ``trace.extract`` and ``window.run``, which it leaves
otherwise unchanged.  ``--records`` also writes the extracted records,
from which the reduction can be run again without the chip.  Not part of
a benchmark run.

A stopgap: it doubles the harness's own traced path, and goes once
``trace.reduce`` takes the program's spans and scopes and the per-layer
metrics read them (PERF.md, Open questions).
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gzip  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH.parent / "src"))
sys.path.insert(0, str(BENCH))

from harness import attribution, cli, trace, window  # noqa: E402


def log(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr, flush=True)


def save_records(path: str, ops, spans, harness) -> None:
    names, scopes = {}, {}
    rows = [[op.device, names.setdefault(op.name, len(names)), op.start_ns,
             op.dur_ns, scopes.setdefault("/".join(op.scopes), len(scopes))]
            for op in ops]
    with gzip.open(path, "wt") as f:
        json.dump({"names": list(names), "scopes": list(scopes), "ops": rows,
                   "spans": [[s.name, s.start_ns, s.dur_ns, s.round]
                             for s in spans],
                   "harness": [[s.name, s.start_ns, s.dur_ns]
                               for s in harness]}, f)


def load_records(path: str):
    """The records ``save_records`` wrote: ``(ops, spans, harness)``."""
    with gzip.open(path, "rt") as f:
        d = json.load(f)
    ops = [attribution.ScopedOp(dev, d["names"][n], t0, dur,
                                tuple(filter(None, d["scopes"][s].split("/"))))
           for dev, n, t0, dur, s in d["ops"]]
    return (ops, [attribution.ProgramSpan(*s) for s in d["spans"]],
            [trace.HostSpan(*s) for s in d["harness"]])


def summarize(ops, spans, harness, *, decode_steps: int,
              sentences: int) -> dict:
    attr = attribution.reduce(ops, spans, harness,
                              window_ns=trace.window_of(harness))
    return {"scope_s": attr.scope_s, "within_s": attr.within_s,
            "unscoped_s": attr.unscoped_s,
            "unscoped_share": attr.unscoped_share(),
            "span_s": attr.span_s, "span_n": attr.span_n,
            "idle_gaps": attr.idle_gaps, "idle_by_span": attr.idle_by_span,
            "idle_in_spans_share": attr.idle_in_spans_share(),
            "per_layer": attribution.per_layer(
                attr, decode_steps=decode_steps, sentences=sentences)}


def attributed_run(workload: str, seed: int, seconds: float, *,
                   records=None, **kw) -> dict:
    """``cli.run_cell`` traced, with the ``attribution`` entry added;
    ``kw`` goes to ``run_cell``."""
    kept = {}
    extract, run = trace.extract, window.run

    def keep_extract(path):
        kept["records"] = attribution.extract(path)
        return extract(path)

    def keep_run(*a, **k):
        kept["window"] = run(*a, **k)
        return kept["window"]

    trace.extract, window.run = keep_extract, keep_run
    try:
        out = cli.run_cell(workload, seed, seconds, True, **kw)
    finally:
        trace.extract, window.run = extract, run
    ops, spans, harness = kept["records"]
    if records:
        save_records(records, ops, spans, harness)
    win = kept["window"]
    results = [r for c in win.calls for r in c.engine_results]
    out["attribution"] = {
        "traced_tokens_per_s": win.n_tokens / win.serve_s,
        **summarize(ops, spans, harness,
                    decode_steps=sum(r.decode_steps for r in results),
                    sentences=win.n_requests)}
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--records", default=None)
    args = ap.parse_args()
    try:
        out = attributed_run(args.workload, args.seed, args.seconds,
                             records=args.records, t_start=T_START, log=log)
    except (cli.NoChip, cli.spec.SpecError) as e:
        log(str(e))
        return 2
    a = out["attribution"]
    log(f"attribution: scopes {a['scope_s']}, unscoped {a['unscoped_s']:.3f}"
        f" s ({a['unscoped_share']}); spans {a['span_s']} n {a['span_n']};"
        f" idle by span {a['idle_by_span']}")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
