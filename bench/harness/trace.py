"""From a profiler trace to busy time, kernel time and idle gaps.

``extract`` reads the ``.xplane.pb`` the JAX profiler writes into plain
records; ``reduce`` turns them into the numbers the per-layer metrics
read.  The two halves are apart so that the reduction can be checked on
records made by hand.
"""

from __future__ import annotations

import dataclasses
import glob
import os
from collections import defaultdict
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

# the harness's own host spans, innermost first when they nest
HOST_SPANS = ("job generation", "result reading", "serve")
DEVICE_OP_LINE = "XLA Ops"
# ops that contain others on the same line: busy, but not an op of their own
CONTAINERS = ("while", "conditional", "call")


@dataclasses.dataclass(frozen=True)
class DeviceOp:
    device: str
    name: str                     # HLO instruction name, e.g. "fusion.12"
    start_ns: float
    dur_ns: float

    @property
    def kind(self) -> str:
        """The name without its numeric suffix: a Pallas kernel keeps the
        name of the jitted wrapper that launched it
        (``int8_matmul_pallas.3`` is ``int8_matmul_pallas``)."""
        base, _, suffix = self.name.rpartition(".")
        return base if base and suffix.isdigit() else self.name


def instruction_name(event_name: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` → ``fusion.12``."""
    return event_name.split(" = ", 1)[0].strip().lstrip("%")


@dataclasses.dataclass(frozen=True)
class HostSpan:
    name: str
    start_ns: float
    dur_ns: float


@dataclasses.dataclass
class TraceSummary:
    window_s: float                       # traced window, host clock
    devices: List[str]
    busy_s: Dict[str, float]              # per device: union of op intervals
    op_s: Dict[str, float]                # op kind → summed device seconds
    kernel_s: Dict[str, float]            # kernel → summed device seconds
    kernel_calls: Dict[str, int]
    idle_gaps: List[Tuple[str, float]]    # longest gaps, host activity label
    idle_by_activity: Dict[str, float]

    @property
    def mean_busy_s(self) -> float:
        return sum(self.busy_s.values()) / max(len(self.busy_s), 1)

    def idle_share(self) -> Optional[float]:
        if not self.devices or self.window_s <= 0:
            return None
        return 1.0 - self.mean_busy_s / self.window_s


def union(intervals: Iterable[Tuple[float, float]]
          ) -> List[Tuple[float, float]]:
    """Merged (start, end) intervals, sorted."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo: float, hi: float):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def activity(t: float, spans: Sequence[HostSpan]) -> str:
    """What the host was doing at ``t``: the innermost harness span that
    covers it (``serve`` alone reads "serve host")."""
    covering = {sp.name for sp in spans
                if sp.start_ns <= t < sp.start_ns + sp.dur_ns}
    for name in HOST_SPANS:
        if name in covering:
            return "serve host" if name == "serve" else name
    return "outside harness spans"


def reduce(ops: Sequence[DeviceOp], spans: Sequence[HostSpan], *,
           window_ns: Tuple[float, float], kernels: Sequence[str],
           top: int = 10) -> TraceSummary:
    """Busy union per device, time per op kind and per kernel (container
    ops such as a ``while`` count as busy but not as an op), and the idle
    gaps of the first device labelled by the host's activity, inside
    ``window_ns``."""
    lo, hi = window_ns
    by_dev: Dict[str, List[Tuple[float, float]]] = defaultdict(list)
    op_s: Dict[str, float] = defaultdict(float)
    kernel_s = {k: 0.0 for k in kernels}
    kernel_calls = {k: 0 for k in kernels}
    for op in ops:
        iv = clip([(op.start_ns, op.start_ns + op.dur_ns)], lo, hi)
        if not iv:
            continue
        s, e = iv[0]
        by_dev[op.device].append((s, e))
        kind = op.kind
        if kind in CONTAINERS:
            continue
        op_s[kind] += (e - s) * 1e-9
        if kind in kernel_s:
            kernel_s[kind] += (e - s) * 1e-9
            kernel_calls[kind] += 1
    devices = sorted(by_dev)
    busy = {d: sum(e - s for s, e in union(by_dev[d])) * 1e-9
            for d in devices}
    gaps: List[Tuple[str, float]] = []
    idle_by: Dict[str, float] = defaultdict(float)
    if devices:
        merged = union(by_dev[devices[0]])
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        for s, e in zip(edges[0::2], edges[1::2]):
            if e > s:
                label = activity((s + e) / 2, spans)
                gaps.append((label, (e - s) * 1e-9))
                idle_by[label] += (e - s) * 1e-9
    gaps.sort(key=lambda g: -g[1])
    return TraceSummary(window_s=(hi - lo) * 1e-9, devices=devices,
                        busy_s=busy, op_s=dict(op_s), kernel_s=kernel_s,
                        kernel_calls=kernel_calls, idle_gaps=gaps[:top],
                        idle_by_activity=dict(idle_by))


def find_xplane(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if len(paths) != 1:
        raise RuntimeError(f"expected one xplane.pb under {trace_dir}, "
                           f"found {len(paths)}")
    return paths[0]


def extract(path: str) -> Tuple[List[DeviceOp], List[HostSpan]]:
    """Device op events of every TPU plane and the harness's host spans."""
    from jax.profiler import ProfileData

    ops: List[DeviceOp] = []
    spans: List[HostSpan] = []
    pd = ProfileData.from_file(path)
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            dev = plane.name[len("/device:"):]
            for line in plane.lines:
                if line.name != DEVICE_OP_LINE:
                    continue
                for ev in line.events:
                    ops.append(DeviceOp(dev, instruction_name(ev.name),
                                        ev.start_ns, ev.duration_ns))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in HOST_SPANS:
                        spans.append(HostSpan(ev.name, ev.start_ns,
                                              ev.duration_ns))
    return ops, spans


def window_of(spans: Sequence[HostSpan]) -> Optional[Tuple[float, float]]:
    """The traced window: from the first harness span to the last."""
    if not spans:
        return None
    return (min(s.start_ns for s in spans),
            max(s.start_ns + s.dur_ns for s in spans))
