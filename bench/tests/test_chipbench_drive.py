"""The drive loop of each traffic mix, whole, at a reduced size on the
CPU; and the command line, which refuses to run without a TPU."""

import json
import os
import subprocess
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

from chipbench_tiny import BENCH, MIXES, cell_for, run

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT / "bench"))

from harness import spec  # noqa: E402


@pytest.mark.parametrize("mix", MIXES)
def test_cell_runs_whole_and_correct(mix):
    out = run(mix)
    assert out["correct"] is True
    assert out["attempted"] > 0 and out["failed"] == 0
    assert set(out["metrics"]) == {m["name"] for m in
                                   spec.end_to_end_for(BENCH, cell_for(mix))}
    assert all(v["value"] > 0 for v in out["metrics"].values())
    assert out["checks"]["executables_in_window"] == 0
    gap = "score_gap" if spec.traffic_file(mix)["beam"] else "logit_gap"
    assert out["checks"][f"{gap}_tokens"] > 0
    assert list(out)[-1] == "compared"
    json.dumps(out)


def test_traced_run_reads_the_counters():
    out = run("offline-beam4", trace=True)
    assert out["correct"] is True
    m = out["metrics"]
    assert 1 <= m["steps_per_sync.offline"]["value"] <= 8
    assert 0 < m["grid_occupancy.offline"]["value"] <= 100
    # the CPU has no device plane: trace-read metrics stay out of the line
    assert "device_idle_share.offline" not in m
    assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}
    assert out["device"]["window_s"] > 0


def _ctx(walls, replica_tokens=None):
    calls = [SimpleNamespace(wall_s=w) for w in walls]
    return SimpleNamespace(window=SimpleNamespace(calls=calls),
                           replica_tokens=replica_tokens)


def test_latency_readers_take_every_call_of_the_window():
    ctx = _ctx([i / 1000 for i in range(1, 101)])
    assert spec.reader("end_to_end", "latency_p50_ms")(ctx) == \
        pytest.approx(50.5)
    assert spec.reader("end_to_end", "latency_p95_ms")(ctx) == \
        pytest.approx(95.05)


@pytest.mark.parametrize("name", ["replica_balance.replicas",
                                  "replica_balance"])
def test_router_balance_reader(name):
    read = spec.reader("metrics", name)
    assert read(_ctx([], [300, 400, 350, 200])) == pytest.approx(50.0)
    assert read(_ctx([], [100])) is None


def _cli(cwd, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "base-int8.offline-beam4", "--seed", "1", "--seconds", "1", *args],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120)


def test_command_line_needs_a_tpu():
    p = _cli(ROOT)
    assert p.returncode != 0 and p.stdout == ""
    assert "needs a TPU" in p.stderr


def test_command_line_fails_with_only_the_benchmark(tmp_path):
    (tmp_path / "BENCHMARK.json").write_text(
        (ROOT / "BENCHMARK.json").read_text())
    subprocess.run(["cp", "-r", str(ROOT / "bench"), str(tmp_path)],
                   check=True)
    p = _cli(tmp_path)
    assert p.returncode != 0 and p.stdout == ""
