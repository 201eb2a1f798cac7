"""From a profiler trace to time per layer: the program's named scopes and
host spans on the profiler's own clock.

The program names the parts of its model step with ``jax.named_scope``
(the scopes in ``SCOPES``; they reach its HLO instructions' metadata as
their ``op_name`` path) and the phases of its host loop with
``jax.profiler.TraceAnnotation`` (``engine.*``, each with its
``round``).  ``extract`` reads both from an ``.xplane.pb``: the spans
from the host planes, each device op's scopes from the optimized HLO
that the profiler keeps in the same file, found through the program
execution that covers the op.  ``reduce`` puts each op's time down to
its innermost scope, totals the program's spans, and labels the window's
idle time by the innermost program span the host was in, falling back to
the harness's own labels (``trace.activity``) outside them.  The halves
are apart so that the reduction can be checked on records made by hand.

This adds to ``harness.trace`` and changes none of its numbers: a
``ScopedOp`` is a ``trace.DeviceOp``, so ``trace.reduce`` reads the same
records and gives the same summary.
"""

from __future__ import annotations

import bisect
import dataclasses
import re
from collections import Counter, defaultdict
from typing import Dict, List, Optional, Sequence, Tuple

from harness import trace

# the program's named scopes, as its burst programs use them
SCOPES = ("encoder", "admission", "self_attention", "cross_attention", "ffn",
          "kv_pool", "logits_head", "beam_step")
SPAN_PREFIXES = ("engine.",)
MODULE_LINE = "XLA Modules"


@dataclasses.dataclass(frozen=True)
class ScopedOp(trace.DeviceOp):
    scopes: Tuple[str, ...] = ()   # SCOPES on its op_name, outermost first


@dataclasses.dataclass(frozen=True)
class ProgramSpan:
    name: str
    start_ns: float
    dur_ns: float
    round: Optional[int] = None


def scopes_of(op_name: str) -> Tuple[str, ...]:
    """``jit(burst)/while/body/kv_pool/slice`` → ``("kv_pool",)``."""
    return tuple(part for part in op_name.split("/") if part in SCOPES)


@dataclasses.dataclass
class Attribution:
    scope_s: Dict[str, float]        # innermost scope → device seconds
    within_s: Dict[str, float]       # scope → device seconds of ops under it
    unscoped_s: float                # device seconds of ops under no scope
    span_s: Dict[str, float]         # program span → summed host seconds
    span_n: Dict[str, int]
    idle_gaps: List[Tuple[str, float]]   # longest gaps, labelled
    idle_by_span: Dict[str, float]       # idle seconds under each label

    def unscoped_share(self) -> Optional[float]:
        """Share of the device's op time under no scope."""
        total = sum(self.scope_s.values()) + self.unscoped_s
        return self.unscoped_s / total if total > 0 else None

    def idle_in_spans_share(self) -> Optional[float]:
        """Share of the window's idle time under a program span."""
        idle = sum(self.idle_by_span.values())
        inside = sum(v for k, v in self.idle_by_span.items()
                     if k.startswith(SPAN_PREFIXES))
        return inside / idle if idle > 0 else None


def _innermost(spans: Sequence[ProgramSpan],
               harness: Sequence[trace.HostSpan]
               ) -> Tuple[List[float], List[Optional[str]]]:
    """Boundaries of all the spans, sorted, and for each interval between
    two neighbours the name of the shortest program span covering it
    (None where none does)."""
    bounds = sorted({t for sp in [*spans, *harness]
                     for t in (sp.start_ns, sp.start_ns + sp.dur_ns)})
    by_start = sorted(spans, key=lambda sp: sp.start_ns)
    labels: List[Optional[str]] = []
    active: List[ProgramSpan] = []
    i = 0
    for lo in bounds[:-1]:
        while i < len(by_start) and by_start[i].start_ns <= lo:
            active.append(by_start[i])
            i += 1
        active = [sp for sp in active if sp.start_ns + sp.dur_ns > lo]
        labels.append(min(active, key=lambda sp: sp.dur_ns).name
                      if active else None)
    return bounds, labels


def _label_parts(s: float, e: float, bounds, labels,
                 harness: Sequence[trace.HostSpan]
                 ) -> Dict[str, float]:
    """The gap [s, e) cut where the innermost span changes: each part's
    label and length in ns.  A part outside every program span takes the
    harness's label at its middle."""
    out: Dict[str, float] = defaultdict(float)
    cuts = [s] + bounds[bisect.bisect_right(bounds, s):
                        bisect.bisect_left(bounds, e)] + [e]
    for lo, hi in zip(cuts, cuts[1:]):
        if hi <= lo:
            continue
        j = bisect.bisect_right(bounds, lo) - 1
        label = labels[j] if 0 <= j < len(labels) else None
        out[label or trace.activity((lo + hi) / 2, harness)] += hi - lo
    return out


def reduce(ops: Sequence[trace.DeviceOp], spans: Sequence[ProgramSpan],
           harness: Sequence[trace.HostSpan], *,
           window_ns: Tuple[float, float], top: int = 10) -> Attribution:
    """Device seconds per scope inside ``window_ns`` (each op to its
    innermost scope, containers such as a ``while`` to none, as in
    ``trace.reduce``), the program spans' totals, and the idle time of the
    first device split by the host span it passed in.  A listed gap takes
    the label that covers most of it."""
    lo, hi = window_ns
    scope_s: Dict[str, float] = defaultdict(float)
    within_s: Dict[str, float] = defaultdict(float)
    unscoped = 0.0
    by_dev: Dict[str, List[Tuple[float, float]]] = defaultdict(list)
    for op in ops:
        iv = trace.clip([(op.start_ns, op.start_ns + op.dur_ns)], lo, hi)
        if not iv:
            continue
        s, e = iv[0]
        by_dev[op.device].append((s, e))
        if op.kind in trace.CONTAINERS:
            continue
        sec = (e - s) * 1e-9
        scopes = getattr(op, "scopes", ())
        if not scopes:
            unscoped += sec
            continue
        scope_s[scopes[-1]] += sec
        for name in set(scopes):
            within_s[name] += sec
    span_s: Dict[str, float] = defaultdict(float)
    span_n: Dict[str, int] = defaultdict(int)
    for sp in spans:
        span_s[sp.name] += sp.dur_ns * 1e-9
        span_n[sp.name] += 1
    bounds, labels = _innermost(spans, harness)
    gaps: List[Tuple[str, float]] = []
    idle_by: Dict[str, float] = defaultdict(float)
    if by_dev:
        merged = trace.union(by_dev[min(by_dev)])
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        for s, e in zip(edges[0::2], edges[1::2]):
            if e <= s:
                continue
            parts = _label_parts(s, e, bounds, labels, harness)
            for label, ns in parts.items():
                idle_by[label] += ns * 1e-9
            gaps.append((max(parts, key=parts.get), (e - s) * 1e-9))
    gaps.sort(key=lambda g: -g[1])
    return Attribution(scope_s=dict(scope_s), within_s=dict(within_s),
                       unscoped_s=unscoped, span_s=dict(span_s),
                       span_n=dict(span_n), idle_gaps=gaps[:top],
                       idle_by_span=dict(idle_by))


def per_layer(attr: Attribution, *, decode_steps: int,
              sentences: int) -> Dict[str, Optional[float]]:
    """The per-layer numbers the program's spans and scopes give, each
    None where its span or scope is missing from the trace (a program
    without them)."""
    def per(total: Optional[float], n: int, scale: float):
        return total / n * scale if total and n else None

    rounds = attr.span_n.get("engine.round", 0)
    edge = (attr.span_s["engine.round"] - attr.span_s["engine.wait"]
            if rounds and "engine.wait" in attr.span_s else None)
    return {
        "host_edge_ms": per(edge, rounds, 1e3),
        "kv_pool_ms_per_step": per(attr.scope_s.get("kv_pool"),
                                   decode_steps, 1e3),
        "logits_head_ms_per_step": per(attr.scope_s.get("logits_head"),
                                       decode_steps, 1e3),
        "beam_step_ms_per_step": per(attr.scope_s.get("beam_step"),
                                     decode_steps, 1e3),
        "encoder_us_per_sentence": per(attr.within_s.get("encoder"),
                                       sentences, 1e6),
    }


_COMPUTATION = re.compile(r"^(?:ENTRY )?%([\w.\-]+) ")
_INSTRUCTION = re.compile(r"^\s+(?:ROOT )?%([\w.\-]+) = ")
_CALLED = re.compile(r"(?:calls|to_apply|body|condition)=%([\w.\-]+)")
_NAME = re.compile(r"%([\w.\-]+)")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def hlo_scopes(text: str) -> Dict[str, Tuple[str, ...]]:
    """Scopes of each instruction of a compiled program (its HLO text with
    metadata): those of its ``op_name``.  XLA leaves the ops it makes
    itself without one, so a fusion with none takes its fused
    computation's most common scopes, and an op that still has none (an
    async copy or slice, a concatenation) those of the first op in the
    same computation that uses its result."""
    comps: Dict[str, list] = {}
    cur = None
    for line in text.splitlines():
        head = _COMPUTATION.match(line)
        if head and line.endswith("{"):
            cur = comps.setdefault(head.group(1), [])
            continue
        ins = _INSTRUCTION.match(line)
        if ins and cur is not None:
            rhs = line[ins.end():]
            op_name = _OP_NAME.search(rhs)
            operands = _NAME.findall(_CALLED.sub("", rhs))
            cur.append((ins.group(1),
                        scopes_of(op_name.group(1)) if op_name else (),
                        _CALLED.findall(rhs), operands))

    def inner(comp: str, seen: Tuple[str, ...] = ()) -> Counter:
        count: Counter = Counter()
        for _, scopes, called, _ in comps.get(comp, ()):
            if scopes:
                count[scopes] += 1
            for c in called:
                if c not in seen:
                    count.update(inner(c, seen + (comp,)))
        return count

    out: Dict[str, Tuple[str, ...]] = {}
    for instructions in comps.values():
        scopes = {}
        for name, own, called, _ in instructions:
            count: Counter = Counter()
            if not own:
                for c in called:
                    count.update(inner(c))
            scopes[name] = own or (count.most_common(1)[0][0] if count
                                   else ())
        users = defaultdict(list)
        for name, _, _, operands in instructions:
            for o in operands:
                if o in scopes and o != name:
                    users[o].append(name)
        for name, *_ in reversed(instructions):
            if not scopes[name]:
                scopes[name] = next((scopes[u] for u in users[name]
                                     if scopes[u]), ())
        out.update(scopes)
    return out


def _fields(buf, lo: int = 0, hi: Optional[int] = None):
    """``(field number, value)`` of each field of a protobuf message in
    ``buf[lo:hi]``: an int for a varint, ``(start, end)`` for a
    length-delimited field; fixed-width fields are skipped."""
    hi = len(buf) if hi is None else hi
    i = lo
    while i < hi:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            value, i = _varint(buf, i)
            yield key >> 3, value
        elif kind == 2:
            n, i = _varint(buf, i)
            yield key >> 3, (i, i + n)
            i += n
        else:
            i += 8 if kind == 1 else 4


def _varint(buf, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        shift += 7
        if b < 0x80:
            return out, i


def _program_id(module_event_name: str) -> Optional[int]:
    """``jit_burst(4226856294427187261)`` → its program id."""
    m = re.search(r"\((\d+)\)$", module_event_name)
    return int(m.group(1)) if m else None


def hlo_programs(path: str, wanted=None
                 ) -> Dict[int, Dict[str, Tuple[str, ...]]]:
    """``hlo_scopes`` of each program whose optimized HLO the profiler kept
    in the trace (the ``Hlo Proto`` stats of the ``/host:metadata``
    plane), keyed by program id; only the ``wanted`` ids if given.  The
    XSpace is walked as raw protobuf (plane 1 of the space; name 2 and
    event metadata 4 of a plane; name 2 and stats 5 of a metadata entry;
    bytes 6 of a stat; module 1 of an HloProto), since ``ProfileData``
    does not expose event metadata."""
    from jax._src.lib import xla_client

    with open(path, "rb") as f:
        buf = f.read()
    options = xla_client._xla.HloPrintOptions.short_parsable()
    options.print_metadata = options.print_percent = True
    out = {}
    for field, plane in _fields(buf):
        fields = list(_fields(buf, *plane)) if field == 1 else []
        name = dict(fields).get(2)
        if name is None or buf[slice(*name)] != b"/host:metadata":
            continue
        for f, entry in fields:
            if f != 4:
                continue
            meta = list(_fields(buf, *dict(_fields(buf, *entry))[2]))
            pid = _program_id(buf[slice(*dict(meta)[2])].decode())
            if pid is None or (wanted is not None and pid not in wanted):
                continue
            for f_meta, stat in meta:
                proto = dict(_fields(buf, *stat)).get(6) if f_meta == 5 \
                    else None
                if proto is not None:
                    module = buf[slice(*dict(_fields(buf, *proto))[1])]
                    out[pid] = hlo_scopes(
                        xla_client.XlaComputation(module).get_hlo_module()
                        .to_string(options))
    return out


def _device_ops(plane, dev: str, programs) -> List[ScopedOp]:
    """The plane's op events, each with the scopes its program's HLO
    gives its instruction; the program is the one whose execution on
    the ``XLA Modules`` line covers the op."""
    modules = []                        # (start, end, program id)
    for line in plane.lines:
        if line.name == MODULE_LINE:
            modules = sorted((ev.start_ns, ev.start_ns + ev.duration_ns,
                              _program_id(ev.name)) for ev in line.events)
    starts = [m[0] for m in modules]
    ops = []
    for line in plane.lines:
        if line.name != trace.DEVICE_OP_LINE:
            continue
        for ev in line.events:
            name = trace.instruction_name(ev.name)
            j = bisect.bisect_right(starts, ev.start_ns) - 1
            hlo = (programs.get(modules[j][2], {})
                   if j >= 0 and ev.start_ns < modules[j][1] else {})
            ops.append(ScopedOp(dev, name, ev.start_ns, ev.duration_ns,
                                hlo.get(name, ())))
    return ops


def extract(path: str) -> Tuple[List[ScopedOp], List[ProgramSpan],
                                List[trace.HostSpan]]:
    """Device ops of every TPU plane with their scopes, the program's host
    spans, and the harness's host spans."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    device = [p for p in pd.planes if p.name.startswith("/device:TPU:")]
    ran = {_program_id(ev.name) for p in device for line in p.lines
           if line.name == MODULE_LINE for ev in line.events}
    programs = hlo_programs(path, ran) if device else {}
    ops: List[ScopedOp] = []
    spans: List[ProgramSpan] = []
    harness: List[trace.HostSpan] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            ops += _device_ops(plane, plane.name[len("/device:"):], programs)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIXES):
                        rnd = dict(ev.stats).get("round")
                        spans.append(ProgramSpan(ev.name, ev.start_ns,
                                                 ev.duration_ns, rnd))
                    elif ev.name in trace.HOST_SPANS:
                        harness.append(trace.HostSpan(ev.name, ev.start_ns,
                                                      ev.duration_ns))
    return ops, spans, harness
