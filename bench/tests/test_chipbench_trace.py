"""The trace reduction on records made by hand: busy union, idle share,
kernel time by name, and idle gaps labelled by the host's activity."""

import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from harness import trace  # noqa: E402
from harness.trace import DeviceOp, HostSpan  # noqa: E402

MS = 1e6          # ns


def op(t0, t1, name="fusion.1", device="TPU:0"):
    return DeviceOp(device, name, t0 * MS, (t1 - t0) * MS)


def test_union_merges_overlaps_and_touching():
    assert trace.union([(5, 7), (0, 2), (1, 3), (3, 4)]) == [(0, 4), (5, 7)]


def test_busy_and_idle_share_of_overlapping_ops():
    ops = [op(0, 4), op(2, 6), op(8, 9)]          # busy 6 + 1 = 7 of 10
    s = trace.reduce(ops, [], window_ns=(0, 10 * MS), kernels=())
    assert s.busy_s == {"TPU:0": pytest.approx(7e-3)}
    assert s.idle_share() == pytest.approx(0.3)
    assert s.window_s == pytest.approx(10e-3)


def test_ops_are_clipped_to_the_window():
    s = trace.reduce([op(-5, 2), op(9, 20)], [], window_ns=(0, 10 * MS),
                     kernels=())
    assert s.mean_busy_s == pytest.approx(3e-3)


def test_idle_share_is_the_mean_over_chips():
    ops = [op(0, 10, device="TPU:0"), op(0, 5, device="TPU:1")]
    s = trace.reduce(ops, [], window_ns=(0, 10 * MS), kernels=())
    assert s.devices == ["TPU:0", "TPU:1"]
    assert s.idle_share() == pytest.approx(0.25)


def test_instruction_name_of_a_trace_event():
    assert trace.instruction_name(
        "%decode_attention_paged_pallas.48 = bf16[512,8,1,64]{3,2,1,0} "
        "custom-call(s32[512,9]{1,0} %copy-done.65)") == \
        "decode_attention_paged_pallas.48"
    assert trace.instruction_name("fusion.3") == "fusion.3"


def test_kernel_time_by_the_name_of_its_jitted_wrapper():
    ops = [op(0, 1, "int8_matmul_pallas.3"),
           op(1, 3, "int8_matmul_pallas"),
           op(3, 4, "decode_attention_paged_pallas.48"),
           # a consumer that names the kernel among its operands is not it
           op(4, 5, "fusion.2")]
    s = trace.reduce(ops, [], window_ns=(0, 5 * MS),
                     kernels=("int8_matmul_pallas",
                              "decode_attention_paged_pallas"))
    assert s.kernel_s["int8_matmul_pallas"] == pytest.approx(3e-3)
    assert s.kernel_calls == {"int8_matmul_pallas": 2,
                              "decode_attention_paged_pallas": 1}
    assert s.kernel_s["decode_attention_paged_pallas"] == pytest.approx(1e-3)
    assert s.op_s["fusion"] == pytest.approx(1e-3)


def test_a_while_is_busy_but_not_an_op():
    ops = [op(0, 10, "while.6"), op(1, 2, "fusion.1"), op(3, 5, "copy.2")]
    s = trace.reduce(ops, [], window_ns=(0, 12 * MS), kernels=())
    assert s.mean_busy_s == pytest.approx(10e-3)
    assert s.op_s == {"fusion": pytest.approx(1e-3),
                      "copy": pytest.approx(2e-3)}


def test_gaps_take_the_innermost_harness_span():
    spans = [HostSpan("serve", 0, 10 * MS),
             HostSpan("result reading", 6 * MS, 3 * MS),
             HostSpan("job generation", 10 * MS, 3 * MS)]
    ops = [op(0, 2), op(5, 6), op(10, 10.5), op(13, 14)]
    s = trace.reduce(ops, spans, window_ns=trace.window_of(spans),
                     kernels=())
    assert s.window_s == pytest.approx(13e-3)
    assert [g[0] for g in s.idle_gaps] == [
        "result reading", "serve host", "job generation"]
    assert [g[1] for g in s.idle_gaps] == pytest.approx([4e-3, 3e-3, 2.5e-3])
    assert s.idle_share() == pytest.approx(9.5 / 13)


def test_activity_outside_every_span():
    assert trace.activity(5.0, [HostSpan("serve", 0, 1)]) == \
        "outside harness spans"


def test_window_of_spans():
    spans = [HostSpan("serve", 5, 10), HostSpan("job generation", 1, 2)]
    assert trace.window_of(spans) == (1, 15)
    assert trace.window_of([]) is None
