"""Time per layer from the program's scopes and spans, on records made by
hand: ops to their innermost scope, idle time to the innermost program
span, and the harness's own numbers unchanged by either."""

import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from harness import attribution, spec, trace  # noqa: E402
from harness.attribution import ProgramSpan, ScopedOp  # noqa: E402
from harness.trace import DeviceOp, HostSpan  # noqa: E402

MS = 1e6          # ns
KERNELS = ("int8_matmul_pallas", "decode_attention_paged_pallas")


def op(t0, t1, name="fusion.1", scopes=(), device="TPU:0"):
    return ScopedOp(device, name, t0 * MS, (t1 - t0) * MS, scopes)


def span(name, t0, t1, rnd=None):
    return ProgramSpan(name, t0 * MS, (t1 - t0) * MS, rnd)


def test_scopes_of_an_op_name():
    assert attribution.scopes_of(
        "jit(burst)/while/body/ffn/jit(int8_matmul_pallas)/pallas_call") == \
        ("ffn",)
    assert attribution.scopes_of(
        "jit(burst)/encoder/self_attention/dot_general") == \
        ("encoder", "self_attention")
    assert attribution.scopes_of("jit(burst)/while/body/add") == ()


def test_an_op_counts_to_its_innermost_scope_and_containers_to_none():
    ops = [op(0, 10, "while.3", ("kv_pool",)),     # a container: busy only
           op(0, 2, "fusion.1", ("encoder", "self_attention")),
           op(2, 3, "fusion.2", ("encoder",)),
           op(3, 6, "decode_attention_paged_pallas.4", ("self_attention",)),
           op(6, 7, "copy-done.1")]
    a = attribution.reduce(ops, [], [], window_ns=(0, 10 * MS))
    assert a.scope_s == {"self_attention": pytest.approx(5e-3),
                         "encoder": pytest.approx(1e-3)}
    assert a.within_s == {"self_attention": pytest.approx(5e-3),
                          "encoder": pytest.approx(3e-3)}
    assert a.unscoped_s == pytest.approx(1e-3)
    assert a.unscoped_share() == pytest.approx(1 / 7)


def test_ops_outside_the_window_count_to_nothing():
    a = attribution.reduce([op(-5, 2, scopes=("ffn",)),
                            op(12, 14, scopes=("ffn",))], [], [],
                           window_ns=(0, 10 * MS))
    assert a.scope_s == {"ffn": pytest.approx(2e-3)}


HARNESS = [HostSpan("job generation", 0, 1 * MS),
           HostSpan("serve", 1 * MS, 19 * MS),
           HostSpan("result reading", 20 * MS, 1 * MS)]
ROUND = [span("engine.setup", 1, 3, 0),
         span("engine.round", 4, 18, 0),
         span("engine.admit", 4, 7, 0), span("engine.dispatch", 7, 7.5, 0),
         span("engine.wait", 7.5, 15, 0), span("engine.drain", 15, 17.5, 0),
         span("engine.free", 17.5, 18, 0)]


def test_idle_time_takes_the_innermost_program_span():
    # the device runs 8..14 only: idle 0..8 and 14..21
    ops = [op(8, 14, "fusion.1", ("ffn",))]
    a = attribution.reduce(ops, ROUND, HARNESS,
                           window_ns=trace.window_of(HARNESS))
    assert a.idle_by_span == pytest.approx({
        "job generation": 1e-3, "engine.setup": 2e-3, "serve host": 3e-3,
        "engine.admit": 3e-3, "engine.dispatch": 0.5e-3,
        "engine.wait": 1.5e-3, "engine.drain": 2.5e-3, "engine.free": 0.5e-3,
        "result reading": 1e-3})
    # each listed gap takes the label that covers most of it
    assert a.idle_gaps == [("engine.admit", pytest.approx(8e-3)),
                           ("engine.drain", pytest.approx(7e-3))]
    assert a.idle_in_spans_share() == pytest.approx(10 / 15)


def test_idle_time_outside_program_spans_keeps_the_harness_label():
    ops = [op(2, 3)]
    program = attribution.reduce(ops, [], HARNESS,
                                 window_ns=trace.window_of(HARNESS))
    harness = trace.reduce(ops, HARNESS, window_ns=trace.window_of(HARNESS),
                           kernels=())
    # split where the harness's spans change, where trace.reduce labels
    # each gap by its middle alone
    assert program.idle_by_span == pytest.approx({
        "job generation": 1e-3, "serve host": 18e-3,
        "result reading": 1e-3})
    assert sum(program.idle_by_span.values()) == \
        pytest.approx(sum(harness.idle_by_activity.values()))
    assert program.idle_in_spans_share() == 0.0


def test_span_totals():
    a = attribution.reduce([], ROUND + [span("engine.round", 20, 21, 1)],
                           HARNESS, window_ns=(0, 21 * MS))
    assert a.span_n["engine.round"] == 2
    assert a.span_s["engine.round"] == pytest.approx(15e-3)
    assert a.span_s["engine.wait"] == pytest.approx(7.5e-3)


def _records():
    """One step's worth of kernels and fusions, with and without scopes."""
    scoped = [op(1, 3, "int8_matmul_pallas.3", ("ffn",)),
              op(3, 7, "decode_attention_paged_pallas.1",
                 ("self_attention",)),
              op(7, 8, "slice_bitcast_fusion.2", ("kv_pool",)),
              op(0, 9, "while.1"), op(9, 10, "copy-done.4"),
              op(12, 13, "fusion.9", ("beam_step",))]
    plain = [DeviceOp(o.device, o.name, o.start_ns, o.dur_ns) for o in scoped]
    return scoped, plain


def _metrics(summary):
    """Every per-layer metric the benchmark reads from a trace summary."""
    bench = spec.load_benchmark()
    ctx = SimpleNamespace(
        trace=summary,
        peaks={"int8_ops_per_s": 393e12, "hbm_bytes_per_s": 819e9},
        work=SimpleNamespace(gemm_ops=1e9, gemm_bytes=1e6, kv_bytes=1e6))
    out = {}
    for m in bench["per_layer"]:
        if m["source"] == "device_trace":
            out[m["name"]] = spec.reader("metrics", m["name"])(ctx)
    return out


def test_the_harness_reads_the_same_with_scopes_and_program_spans():
    scoped, plain = _records()
    window = trace.window_of(HARNESS)
    with_program = trace.reduce(
        scoped, HARNESS + [HostSpan(sp.name, sp.start_ns, sp.dur_ns)
                           for sp in ROUND],
        window_ns=window, kernels=KERNELS)
    without = trace.reduce(plain, HARNESS, window_ns=window, kernels=KERNELS)
    assert with_program == without
    metrics = _metrics(without)
    assert metrics and all(v is not None for v in metrics.values())
    assert _metrics(with_program) == metrics


@pytest.mark.parametrize("missing", ["spans", "scopes"])
def test_each_layer_number_is_none_without_its_span_or_scope(missing):
    scoped, plain = _records()
    enc = [op(10, 12, "fusion.7", ("encoder", "ffn")),
           op(13, 14, "fusion.8", ("logits_head",))]
    full = attribution.per_layer(
        attribution.reduce(scoped + enc, ROUND, HARNESS,
                           window_ns=(0, 21 * MS)),
        decode_steps=2, sentences=4)
    assert full == pytest.approx({
        "host_edge_ms": 6.5, "kv_pool_ms_per_step": 0.5,
        "logits_head_ms_per_step": 0.5, "beam_step_ms_per_step": 0.5,
        "encoder_us_per_sentence": 500.0})
    ops, spans = (scoped + enc, []) if missing == "spans" else (plain, ROUND)
    part = attribution.per_layer(
        attribution.reduce(ops, spans, HARNESS, window_ns=(0, 21 * MS)),
        decode_steps=2, sentences=4)
    if missing == "spans":
        assert part == {**full, "host_edge_ms": None}
    else:
        assert part == {**{k: None for k in full},
                        "host_edge_ms": full["host_edge_ms"]}


def test_an_attributed_run_reads_the_engine_spans(tmp_path):
    """The whole traced run on the CPU, which has no device plane: the
    engine's spans are there, the device-trace numbers are not, and the
    records written give the same attribution again."""
    import attribute
    from chipbench_tiny import TINY_CFG, TINY_LIMITS, cell_for, tiny_mix

    path = str(tmp_path / "records.json.gz")
    out = attribute.attributed_run(
        cell_for("offline-beam4"), 2**31 + 7, 0.2, records=path,
        t_start=0.0, require_chip=False, compile_cache=False,
        cfg_override=TINY_CFG, mix_override=tiny_mix("offline-beam4"),
        limits_override=TINY_LIMITS["beam"])
    assert out["correct"] is True
    attr = out.pop("attribution")
    assert attr["traced_tokens_per_s"] > 0
    n = attr["span_n"]
    assert n["engine.setup"] >= 1          # one a serve() call
    assert n["engine.round"] >= n["engine.wait"] > n["engine.setup"]
    layer = attr["per_layer"]
    assert layer["host_edge_ms"] > 0
    assert {k for k, v in layer.items() if v is None} == {
        "kv_pool_ms_per_step", "logits_head_ms_per_step",
        "beam_step_ms_per_step", "encoder_us_per_sentence"}
    again = attribute.summarize(*attribute.load_records(path),
                                decode_steps=1, sentences=1)
    assert again["span_s"] == attr["span_s"]
    assert again["idle_by_span"] == pytest.approx(attr["idle_by_span"])


HLO = """\
HloModule jit_burst, is_scheduled=true

%fused_computation.3 (param_0: f32[4,8]) -> f32[6,4,8] {
  %param_0 = f32[4,8]{1,0} parameter(0)
  %bitcast.1 = f32[1,4,8]{2,1,0} bitcast(%param_0), metadata={op_name="jit(burst)/while/body/kv_pool/broadcast_in_dim"}
  ROOT %dynamic-update-slice.2 = f32[6,4,8]{2,1,0} dynamic-update-slice(%bitcast.1), metadata={}
}

%body (p: (f32[4,8], f32[6,4,8])) -> (f32[4,8], f32[6,4,8]) {
  %p = (f32[4,8]{1,0}, f32[6,4,8]{2,1,0}) parameter(0)
  %get-tuple-element.1 = f32[4,8]{1,0} get-tuple-element(%p), index=0
  %copy-start.1 = (f32[4,8]{1,0}, f32[4,8]{1,0}, u32[]) copy-start(%get-tuple-element.1)
  %copy-done.1 = f32[4,8]{1,0} copy-done(%copy-start.1)
  %fusion.7 = f32[4,8]{1,0} fusion(%copy-done.1), kind=kLoop, calls=%fused_computation.9, metadata={op_name="jit(burst)/while/body/ffn/mul"}
  %bitcast_dynamic-update-slice_fusion.4 = f32[6,4,8]{2,1,0} fusion(%fusion.7), kind=kLoop, calls=%fused_computation.3
  %copy.5 = f32[6,4,8]{2,1,0} copy(%get-tuple-element.1)
  ROOT %tuple.2 = (f32[4,8]{1,0}, f32[6,4,8]{2,1,0}) tuple(%fusion.7, %copy.5)
}
"""


def test_hlo_text_gives_scopes_to_the_ops_xla_made():
    scopes = attribution.hlo_scopes(HLO)
    assert scopes["fusion.7"] == ("ffn",)                 # its own op_name
    # a fusion with no op_name of its own takes its fused computation's
    assert scopes["bitcast_dynamic-update-slice_fusion.4"] == ("kv_pool",)
    # an async copy takes the scopes of the op that uses its result
    assert scopes["copy-done.1"] == scopes["copy-start.1"] == ("ffn",)
    # an op whose only user is the loop's result keeps none
    assert scopes["copy.5"] == ()


def _ev(name, t0, t1, **stats):
    return SimpleNamespace(name=name, start_ns=t0 * MS,
                           duration_ns=(t1 - t0) * MS, stats=stats.items())


def test_an_op_takes_the_scopes_of_the_program_running_it():
    plane = SimpleNamespace(lines=[
        SimpleNamespace(name="XLA Modules", events=[
            _ev("jit_burst(7)", 0, 10), _ev("jit_burst(3)", 10, 20)]),
        SimpleNamespace(name="XLA Ops", events=[
            _ev("%fusion.2 = f32[8] fusion()", 2, 3),
            _ev("%fusion.2 = f32[8] fusion()", 11, 12),
            _ev("copy-done.4", 21, 22)])])        # under no execution
    programs = {7: {"fusion.2": ("kv_pool",)}, 3: {"fusion.2": ("ffn",)}}
    ops = attribution._device_ops(plane, "TPU:0", programs)
    assert [(o.name, o.scopes) for o in ops] == [
        ("fusion.2", ("kv_pool",)), ("fusion.2", ("ffn",)),
        ("copy-done.4", ())]


def test_the_trace_keeps_each_programs_hlo(tmp_path):
    """On the CPU too the profiler writes each program's optimized HLO
    into the trace; read back, it gives each instruction its scopes."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def burst(x):
        with jax.named_scope("ffn"):
            y = jnp.tanh(x @ x)
        with jax.named_scope("logits_head"):
            return jnp.argmax(y, axis=-1)

    x = jnp.ones((8, 8))
    burst(x).block_until_ready()
    with jax.profiler.trace(str(tmp_path)):
        burst(x).block_until_ready()
    programs = attribution.hlo_programs(trace.find_xplane(str(tmp_path)))
    scopes = {sc for p in programs.values() for sc in p.values()}
    assert {("ffn",), ("logits_head",)} <= scopes
