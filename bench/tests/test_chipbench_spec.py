"""Every entry of BENCHMARK.json resolves to its files by name, with names
and units in the allowed characters, and the traffic generator gives
every seed the same work."""

import json
import re
import sys
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

from harness import spec, traffic  # noqa: E402

B = spec.load_benchmark()
CELLS = [w["name"] for w in B["workloads"]]
MODEL_KEYS = ("n_enc_layers", "n_layers", "d_model", "n_heads",
              "n_kv_heads", "head_dim", "d_ff", "vocab")


def test_benchmark_resolves_whole():
    assert spec.check(B) == []


def test_top_level_keys_and_command():
    assert set(B) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert B["command"] == ["python3", "bench/run.py"]
    assert B["paths"] == ["bench"]
    assert 1 <= B["run_seconds"] <= 51


@pytest.mark.parametrize("m", B["end_to_end"], ids=lambda m: m["name"])
def test_end_to_end_bounds(m):
    assert set(m) <= {"name", "unit", "better", "bound", "source",
                      "workloads"}
    assert 0.01 <= m["bound"] <= 0.25
    assert m["source"] in ("host_clock", "device_trace")


@pytest.mark.parametrize("m", B["per_layer"], ids=lambda m: m["name"])
def test_per_layer_entries(m):
    assert set(m) <= {"name", "unit", "better", "source", "layer", "moves",
                      "workloads"}
    assert re.fullmatch(r"[^\t\n]{1,200}", m["layer"])
    moved = next(e for e in B["end_to_end"] if e["name"] == m["moves"])
    for cell in m.get("workloads", CELLS):
        assert "workloads" not in moved or cell in moved["workloads"]


@pytest.mark.parametrize("c", B["configs"], ids=lambda c: c["name"])
def test_config_file_states_the_sizes(c):
    cfg = json.loads((spec.ROOT / c["file"]).read_text())
    assert c["file"].startswith("bench/configs/")
    assert cfg["reduced"] == c["reduced"] == []
    for k in MODEL_KEYS:
        assert isinstance(cfg[k], int) and cfg[k] > 0
    assert cfg["d_model"] == cfg["n_heads"] * cfg["head_dim"]


@pytest.mark.parametrize("cell", CELLS)
def test_cell_files_and_why(cell):
    w = spec.workload(B, cell)
    assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"]
    mix = spec.traffic_file(w["traffic"])
    assert mix["name"] == w["traffic"]
    assert mix["replicas"] <= w["chips"]
    lim = spec.limits_file(cell)
    assert lim and set(lim) <= {"logit_gap", "score_gap"}
    # the greedy gap reads greedy requests, the score gap beam requests
    assert set(lim) == ({"score_gap"} if mix["beam"] else {"logit_gap"})
    assert all(v["limit"] > 0 for v in lim.values())


@pytest.mark.parametrize("name", ["offline-beam4", "interactive-greedy"])
def test_two_seeds_get_the_same_sizes_in_the_same_order(name):
    mix = spec.traffic_file(name)
    if mix["kind"] == "offline":
        a = traffic.offline_job(mix, 37000, 1, 0)
        b = traffic.offline_job(mix, 37000, 2**31 + 11, 0)
    else:
        a = [s for c in traffic.closed_loop_calls(mix, 37000, 1, 0) for s in c]
        b = [s for c in traffic.closed_loop_calls(mix, 37000, 2**33, 0)
             for s in c]
    assert [len(s.src) for s in a] == [len(s.src) for s in b]
    assert any(not np.array_equal(x.src, y.src) for x, y in zip(a, b))
    again = traffic.offline_job(mix, 37000, 1, 0) if mix["kind"] == \
        "offline" else [s for c in traffic.closed_loop_calls(
            mix, 37000, 1, 0) for s in c]
    assert all(np.array_equal(x.src, y.src) for x, y in zip(a, again))


def test_lengths_follow_the_stated_distribution():
    mix = spec.traffic_file("offline-beam4")
    n = traffic.source_lengths(3003, mix["source_length"])
    assert n.min() == 3 and n.max() <= 128
    assert 27 <= n.mean() <= 31 and np.median(n) == 24
    assert 0.01 <= (n > 80).mean() <= 0.03
    assert {traffic.budget(int(x), mix["budget"]) for x in n} <= set(
        range(1, 129))


def test_paragraph_sizes_are_uniform_in_each_block():
    mix = spec.traffic_file("interactive-greedy")
    block = traffic.paragraph_block(mix)
    sizes = sorted(len(p) for p in block)
    assert sizes == sorted(list(range(1, 9)) * 8)
