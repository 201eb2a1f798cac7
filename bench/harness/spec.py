"""``BENCHMARK.json`` and the files it names, resolved by name.

Every configuration, traffic mix, per-layer metric, end-to-end metric and
cell limit is a file of its own under ``bench/`` (metrics that differ only
by a ``.suffix`` share their base name's reader); nothing here changes
when a later change adds one of them.
"""

from __future__ import annotations

import importlib.util
import json
import re
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional

BENCH_DIR = Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parent

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")


class SpecError(Exception):
    """A name that resolves to no file, or an entry outside the contract."""


def load_benchmark(root: Path = ROOT) -> Dict[str, Any]:
    path = root / "BENCHMARK.json"
    if not path.is_file():
        raise SpecError(f"{path} not found")
    return json.loads(path.read_text())


def _load_json(path: Path) -> Dict[str, Any]:
    if not path.is_file():
        raise SpecError(f"{path} not found")
    return json.loads(path.read_text())


def load_module(path: Path, name: str):
    """Import a reader file by path (metric names carry dots)."""
    if not path.is_file():
        raise SpecError(f"{path} not found")
    spec = importlib.util.spec_from_file_location(
        "bench_" + re.sub(r"\W", "_", name), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def config_file(bench: Dict[str, Any], name: str) -> Dict[str, Any]:
    for c in bench["configs"]:
        if c["name"] == name:
            return _load_json(ROOT / c["file"])
    raise SpecError(f"no configuration named {name!r}")


def traffic_file(name: str) -> Dict[str, Any]:
    return _load_json(BENCH_DIR / "traffic" / f"{name}.json")


def limits_file(workload: str) -> Dict[str, Any]:
    return _load_json(BENCH_DIR / "limits" / f"{workload}.json")


def workload(bench: Dict[str, Any], name: str) -> Dict[str, Any]:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise SpecError(f"no workload named {name!r}")


def _applies(metric: Dict[str, Any], cell: str,
             e2e_names: List[str]) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    return metric.get("moves", metric["name"]) in e2e_names


def end_to_end_for(bench: Dict[str, Any], cell: str) -> List[Dict[str, Any]]:
    return [m for m in bench["end_to_end"]
            if "workloads" not in m or cell in m["workloads"]]


def per_layer_for(bench: Dict[str, Any], cell: str) -> List[Dict[str, Any]]:
    e2e = [m["name"] for m in end_to_end_for(bench, cell)]
    return [m for m in bench["per_layer"] if _applies(m, cell, e2e)]


def reader_path(kind: str, name: str) -> Path:
    """``bench/<kind>/<name>.py``, else the file of the name's base (the
    part before its first dot): ``mfu.offline`` and ``mfu.interactive``
    are one quantity, read by ``mfu.py``, in cells of different end-to-end
    metrics."""
    path = BENCH_DIR / kind / f"{name}.py"
    return path if path.is_file() else \
        BENCH_DIR / kind / f"{name.split('.')[0]}.py"


def reader(kind: str, name: str) -> Callable[[Any], Optional[float]]:
    """``read(ctx)`` of the metric's reader file."""
    return load_module(reader_path(kind, name), name).read


def check(bench: Dict[str, Any]) -> List[str]:
    """Every way ``bench`` breaks the names, units and files it must have;
    an empty list when it resolves whole."""
    errs: List[str] = []
    seen = set()

    def name_ok(kind: str, n: Any) -> None:
        if not isinstance(n, str) or not NAME_RE.match(n):
            errs.append(f"{kind} name {n!r} outside [A-Za-z0-9_.-]{{1,64}}")
        elif (kind, n) in seen:
            errs.append(f"duplicate {kind} name {n!r}")
        seen.add((kind, n))

    for c in bench["configs"]:
        name_ok("config", c["name"])
        for k in c["reduced"]:
            name_ok("reduced key", k)
        try:
            cfg = config_file(bench, c["name"])
        except SpecError as e:
            errs.append(str(e))
            continue
        if cfg.get("name") != c["name"]:
            errs.append(f"{c['file']} names {cfg.get('name')!r}")
        ref = BENCH_DIR / "configs" / f"{cfg.get('reference')}.py"
        if not ref.is_file():
            errs.append(f"{c['name']}: reference {ref} not found")
    metric_names = []
    for m in bench["end_to_end"] + bench["per_layer"]:
        name_ok("metric", m["name"])
        metric_names.append(m["name"])
        if not UNIT_RE.match(m["unit"]):
            errs.append(f"unit {m['unit']!r} of {m['name']}")
        if m["better"] not in ("lower", "higher"):
            errs.append(f"better {m['better']!r} of {m['name']}")
        if m["source"] not in SOURCES:
            errs.append(f"source {m['source']!r} of {m['name']}")
    for m in bench["end_to_end"]:
        if not reader_path("end_to_end", m["name"]).is_file():
            errs.append(f"no reader for end-to-end metric {m['name']}")
    e2e = {m["name"] for m in bench["end_to_end"]}
    for m in bench["per_layer"]:
        if m["moves"] not in e2e:
            errs.append(f"{m['name']} moves unknown {m['moves']!r}")
        if not reader_path("metrics", m["name"]).is_file():
            errs.append(f"no reader for per-layer metric {m['name']}")
    configs = {c["name"] for c in bench["configs"]}
    cells = {w["name"] for w in bench["workloads"]}
    for w in bench["workloads"]:
        name_ok("workload", w["name"])
        if not NAME_RE.match(w["traffic"]):
            errs.append(f"{w['name']}: traffic name {w['traffic']!r}")
        if w["config"] not in configs:
            errs.append(f"{w['name']}: unknown config {w['config']!r}")
        if w["chips"] not in (1, 4):
            errs.append(f"{w['name']}: chips {w['chips']}")
        for kind, path in (("traffic", BENCH_DIR / "traffic"
                            / f"{w['traffic']}.json"),
                           ("limits", BENCH_DIR / "limits"
                            / f"{w['name']}.json")):
            if not path.is_file():
                errs.append(f"{w['name']}: {kind} file {path} not found")
        if not any(m["name"] == "setup_s" for m in end_to_end_for(
                bench, w["name"])):
            errs.append(f"{w['name']} reports no setup_s")
        if not per_layer_for(bench, w["name"]):
            errs.append(f"{w['name']} reports no per-layer metric")
    for m in bench["end_to_end"] + bench["per_layer"]:
        for cell in m.get("workloads", []):
            if cell not in cells:
                errs.append(f"{m['name']} lists unknown workload {cell!r}")
    return errs
