"""Post-compile HLO analysis: collective-traffic accounting, and which
Pallas kernels a compiled program calls.

``cost_analysis()`` gives FLOPs/bytes but no collective bytes, and it counts
while-loop bodies ONCE (verified empirically — see EXPERIMENTS.md
§Methodology).  This module parses the compiled module text:

* finds every ``all-gather`` / ``all-reduce`` / ``reduce-scatter`` /
  ``all-to-all`` / ``collective-permute`` op and sums operand bytes;
* attributes each op to its enclosing computation;
* recovers while-loop trip counts from the loop-condition computations
  (``compare(…, constant(N))``) and multiplies bodies accordingly, so a
  collective inside the layer scan counts n_layers times.

All sizes are **per-device** (the compiled module is the SPMD per-device
program); multiply by device count for fleet totals where needed.
"""

from __future__ import annotations

import dataclasses
import re
from collections import defaultdict
from typing import Dict, List, Tuple

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}

COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
               "collective-permute")

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
# header: `%name (args…) -> result {`  — args may contain nested parens
_COMP_START = re.compile(r"^(?:ENTRY\s+)?%?([\w\.\-]+)\s*\(.*->.*\{\s*$")
_WHILE_RE = re.compile(r"while\(.*?\).*?condition=%?([\w\.\-]+).*?body=%?([\w\.\-]+)")
_CONST_RE = re.compile(r"constant\((\d+)\)")
_TRIP_RE = re.compile(r'known_trip_count.*?"n"\s*:\s*"(\d+)"')


def shape_bytes(type_str: str) -> int:
    """Bytes of one HLO type string, e.g. 'f32[16,128]' (tuples: sum parts)."""
    total = 0
    for m in _SHAPE_RE.finditer(type_str):
        dt, dims = m.group(1), m.group(2)
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


@dataclasses.dataclass
class CollectiveOp:
    kind: str
    computation: str
    bytes_once: int
    multiplier: int = 1

    @property
    def bytes_total(self) -> int:
        return self.bytes_once * self.multiplier


def _split_computations(hlo: str) -> Dict[str, List[str]]:
    comps: Dict[str, List[str]] = {}
    current = None
    for line in hlo.splitlines():
        m = _COMP_START.match(line.strip()) if line and not line.startswith(" ") \
            else None
        if m and "{" in line:
            current = m.group(1)
            comps[current] = []
        elif current is not None:
            comps[current].append(line)
            if line.strip() == "}":
                current = None
    return comps


def _trip_count(cond_lines: List[str]) -> int:
    """Best-effort loop bound from the condition computation's constants."""
    consts = []
    for line in cond_lines:
        if "constant(" in line and "compare" not in line:
            consts += [int(c) for c in _CONST_RE.findall(line)]
    return max(consts) if consts else 1


def analyze_collectives(hlo: str) -> Dict:
    comps = _split_computations(hlo)

    # while-loop structure: body computation -> trip count.  XLA annotates
    # `backend_config={"known_trip_count":{"n":"48"}}` on the while op; fall
    # back to the condition computation's compare-constant when absent.
    multipliers: Dict[str, int] = defaultdict(lambda: 1)
    edges: List[Tuple[str, str, int]] = []   # (caller, body, trips)
    for name, lines in comps.items():
        for line in lines:
            m = _WHILE_RE.search(line)
            if m:
                cond, body = m.group(1), m.group(2)
                t = _TRIP_RE.search(line)
                trips = (int(t.group(1)) if t
                         else _trip_count(comps.get(cond, [])))
                edges.append((name, body, trips))

    # propagate multipliers from the entry computation down (nested loops
    # multiply); entry computations have multiplier 1
    changed = True
    rounds = 0
    while changed and rounds < 10:
        changed = False
        rounds += 1
        for caller, body, trips in edges:
            new = multipliers[caller] * trips
            if new > multipliers[body]:
                multipliers[body] = new
                changed = True

    ops: List[CollectiveOp] = []
    group_re = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
    for comp, lines in comps.items():
        for line in lines:
            if "=" not in line:
                continue
            rhs = line.split("=", 1)[1]
            for kind in COLLECTIVES:
                key = f" {kind}("
                start = f" {kind}-start("
                if key not in rhs and start not in rhs:
                    continue
                # result type string sits between '=' and the op keyword
                idx = rhs.find(kind)
                result_b = shape_bytes(rhs[:idx])
                gm = group_re.search(rhs)
                g = int(gm.group(2)) if gm else 2
                # per-device wire bytes for ring implementations
                if kind == "all-reduce":
                    b = int(2 * result_b * (g - 1) / g)
                elif kind == "reduce-scatter":
                    b = int(result_b * (g - 1))          # operand-sized
                elif kind == "collective-permute":
                    b = result_b
                else:                                     # AG / A2A
                    b = int(result_b * (g - 1) / g)
                ops.append(CollectiveOp(kind=kind, computation=comp,
                                        bytes_once=b,
                                        multiplier=multipliers[comp]))
                break

    by_kind: Dict[str, int] = defaultdict(int)
    for op in ops:
        by_kind[op.kind] += op.bytes_total
    return {
        "total_bytes": int(sum(op.bytes_total for op in ops)),
        "by_kind": dict(by_kind),
        "n_ops": len(ops),
        "loop_multipliers": {b: m for (_, b, _), m in
                             zip(edges, [multipliers[b] for _, b, _ in edges])},
    }


def count_hlo_ops(hlo: str, op_names: Tuple[str, ...]) -> Dict[str, int]:
    """Occurrence counts (with loop multipliers) for arbitrary op names."""
    comps = _split_computations(hlo)
    multipliers: Dict[str, int] = defaultdict(lambda: 1)
    for name, lines in comps.items():
        for line in lines:
            m = _WHILE_RE.search(line)
            if m:
                trips = _trip_count(comps.get(m.group(1), []))
                multipliers[m.group(2)] = max(multipliers[m.group(2)], trips)
    out: Dict[str, int] = defaultdict(int)
    for comp, lines in comps.items():
        for line in lines:
            for op in op_names:
                if f" {op}(" in line:
                    out[op] += multipliers[comp]
    return dict(out)


_PALLAS_RE = re.compile(r'op_name="[^"]*?jit\((\w+)\)/pallas_call')


def pallas_kernel_calls(hlo: str) -> Dict[str, int]:
    """Compiled Pallas kernel call sites (``tpu_custom_call``) per kernel,
    named by the jitted wrapper that launched them (e.g.
    ``int8_matmul_pallas``); call sites, not trip-weighted executions."""
    out: Dict[str, int] = defaultdict(int)
    for line in hlo.splitlines():
        if 'custom_call_target="tpu_custom_call"' in line:
            m = _PALLAS_RE.search(line)
            out[m.group(1) if m else "unnamed"] += 1
    return dict(out)
