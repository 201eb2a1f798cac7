"""First direct unit tests for launch/roofline.py + hlo_analysis plumbing.

Term assembly is pure arithmetic over probe/dry-run inputs and the
collective-bytes pipeline is pure string parsing — both testable without
devices.  The sharded-serve sanity bound (prediction vs *measured*, on
the dim the host backend models faithfully) lives in
``benchmarks/bench_sharded_serve.py``; here we lock the algebra those
comparisons rest on.
"""

import json

import pytest

from repro.configs import get_config
from repro.launch import roofline
from repro.launch.hlo_analysis import (analyze_collectives,
                                       pallas_kernel_calls, shape_bytes)
from repro.launch.roofline import (HBM_BW, ICI_BW, PEAK_BF16, PEAK_INT8,
                                   decode_collective_bytes, model_flops,
                                   sharded_decode_cell)


# --------------------------------------------------------------- model_flops
def test_model_flops_kinds_scale_as_documented():
    cfg = get_config("transformer-base")
    n = cfg.n_active_params
    assert model_flops("transformer-base", "train_4k") == \
        pytest.approx(6.0 * n * 256 * 4096)
    assert model_flops("transformer-base", "prefill_32k") == \
        pytest.approx(2.0 * n * 32 * 32768)
    # decode: per emitted token — no seq_len factor
    assert model_flops("transformer-base", "decode_32k") == \
        pytest.approx(2.0 * n * 128)


# ------------------------------------------------- decode_collective_bytes
def test_collective_bytes_zero_without_tensor_parallelism():
    assert decode_collective_bytes(n_layers=6, d_model=512, rows=8,
                                   tp=1) == 0
    assert decode_collective_bytes(n_layers=6, d_model=512, rows=8,
                                   tp=0) == 0


def test_collective_bytes_ring_formula():
    # 3 all-reduces per decoder layer, ring wire bytes 2·b·(g-1)/g, plus
    # one logits all-gather b·(g-1)/g
    got = decode_collective_bytes(n_layers=2, d_model=128, rows=4, tp=2,
                                  act_bytes=4, vocab=64)
    act = 4 * 128 * 4
    want = 2 * 3 * (2 * act * 1 // 2) + 4 * 64 * 4 * 1 // 2
    assert got == want


def test_collective_bytes_monotone_in_layers_and_rows():
    base = dict(d_model=256, rows=4, tp=4, act_bytes=2)
    one = decode_collective_bytes(n_layers=1, **base)
    assert decode_collective_bytes(n_layers=5, **base) == 5 * one
    assert decode_collective_bytes(
        n_layers=1, d_model=256, rows=8, tp=4, act_bytes=2) == 2 * one


def test_collective_bytes_ring_factor_saturates():
    # 2(g-1)/g → 2 as g grows: tp=8 wire bytes < 2× tp=2 wire bytes
    kw = dict(n_layers=2, d_model=128, rows=4)
    assert decode_collective_bytes(tp=8, **kw) < \
        2 * decode_collective_bytes(tp=2, **kw)


# ------------------------------------------------------ sharded_decode_cell
def test_cell_terms_and_bound():
    cfg = get_config("transformer-base")
    cell = sharded_decode_cell(cfg, rows=8, tp=4, quantized=True)
    t = cell["terms_s"]
    assert set(t) == {"compute_s", "memory_s", "collective_s"}
    assert cell["step_time_bound_s"] == max(t.values())
    assert cell["dominant"] == max(t, key=t.get)
    assert t["compute_s"] == pytest.approx(
        2.0 * cfg.n_active_params * 8 / (4 * PEAK_INT8))
    assert t["collective_s"] == pytest.approx(
        cell["collective_bytes_per_device"] / ICI_BW)


def test_cell_compute_and_weights_shard_with_tp():
    cfg = get_config("transformer-base")
    c2 = sharded_decode_cell(cfg, rows=8, tp=2)["terms_s"]
    c4 = sharded_decode_cell(cfg, rows=8, tp=4)["terms_s"]
    assert c4["compute_s"] == pytest.approx(c2["compute_s"] / 2)
    assert c4["memory_s"] < c2["memory_s"]          # weights/tp stream
    assert c4["collective_s"] > c2["collective_s"]  # more ring hops


def test_cell_unsharded_has_no_collective_term():
    cfg = get_config("transformer-base")
    cell = sharded_decode_cell(cfg, rows=4, tp=1, quantized=False)
    assert cell["terms_s"]["collective_s"] == 0.0
    assert cell["collective_bytes_per_device"] == 0
    assert cell["terms_s"]["compute_s"] == pytest.approx(
        2.0 * cfg.n_active_params * 4 / PEAK_BF16)


# ------------------------------------------- collective-bytes HLO plumbing
HLO = """\
ENTRY %main (p0: f32[8,128]) -> f32[8,128] {
  %p0 = f32[8,128] parameter(0)
  %w = f32[8,128] while(%p0), condition=%cond, body=%body, backend_config={"known_trip_count":{"n":"10"}}
  ROOT %r = f32[8,128] copy(%w)
}

%body (bp: f32[8,128]) -> f32[8,128] {
  %bp = f32[8,128] parameter(0)
  %ar = f32[8,128] all-reduce(%bp), replica_groups=[1,4], to_apply=%add
  ROOT %br = f32[8,128] copy(%ar)
}

%cond (cp: f32[8,128]) -> pred[] {
  %cp = f32[8,128] parameter(0)
  ROOT %lt = pred[] constant(1)
}

%other (op: f32[16,64]) -> f32[16,64] {
  %op = f32[16,64] parameter(0)
  ROOT %ag = f32[16,64] all-gather(%op), replica_groups=[1,2], dimensions={0}
}
"""


def test_shape_bytes():
    assert shape_bytes("f32[8,128]") == 8 * 128 * 4
    assert shape_bytes("s8[4,16,2,32]") == 4 * 16 * 2 * 32
    assert shape_bytes("bf16[10]") == 20


def test_analyze_collectives_while_multiplier_and_ring_bytes():
    rec = analyze_collectives(HLO)
    # ring all-reduce of 4096B over g=4: 2·4096·3/4 = 6144, ×10 loop trips
    ar = 2 * 8 * 128 * 4 * 3 // 4
    # all-gather of 4096B over g=2 outside any loop: 4096·1/2 = 2048, ×1
    ag = 16 * 64 * 4 * 1 // 2
    assert rec["by_kind"]["all-reduce"] == ar * 10
    assert rec["by_kind"]["all-gather"] == ag
    assert rec["total_bytes"] == ar * 10 + ag
    assert rec["n_ops"] == 2
    assert rec["loop_multipliers"].get("body") == 10


def test_analyze_collectives_empty_module():
    rec = analyze_collectives("ENTRY %main () -> f32[] {\n  ROOT %c = "
                              "f32[] constant(0)\n}\n")
    assert rec["total_bytes"] == 0 and rec["n_ops"] == 0


def test_pallas_kernel_calls_names_each_call_site():
    def call(name, kernel):
        return (f'  %{name} = bf16[8,2048] custom-call(%a, %b), '
                f'custom_call_target="tpu_custom_call", metadata={{op_name='
                f'"jit(burst)/while/body/jit({kernel})/pallas_call"}}')
    hlo = "\n".join([
        call("int8_matmul_pallas.1", "int8_matmul_pallas"),
        call("int8_matmul_pallas.2", "int8_matmul_pallas"),
        call("decode_attention_paged_pallas.1",
             "decode_attention_paged_pallas"),
        '  %dot.1 = f32[8,8] dot(%a, %b), metadata={op_name="jit(f)/dot"}',
    ])
    assert pallas_kernel_calls(hlo) == {"int8_matmul_pallas": 2,
                                        "decode_attention_paged_pallas": 1}
    assert pallas_kernel_calls("") == {}


# ------------------------------------------------- build_cell term assembly
def test_build_cell_assembles_terms_from_record_and_probe(tmp_path,
                                                          monkeypatch):
    arch, shape = "transformer-base", "decode_32k"
    rec = {"n_devices": 8, "mesh": "data=1,model=8",
           "memory": {"argument_bytes": 2 * HBM_BW,     # memory_s = 2.0
                      "peak_per_device_gib": 1.5},
           "collectives": {"total_bytes": 3 * ICI_BW}}  # collective_s = 3.0
    d = tmp_path / "dryrun"
    d.mkdir()
    (d / f"{arch}__{shape}__1pod__int8.json").write_text(json.dumps(rec))
    monkeypatch.setattr(roofline, "DRYRUN_DIR", str(d))

    flops = 8 * PEAK_INT8                               # compute_s = 1.0
    import repro.launch.costs as costs
    monkeypatch.setattr(costs, "probe",
                        lambda *a, **kw: {"flops": flops, "bytes": 0})

    cell = roofline.build_cell(arch, shape, quantized=True)
    t = cell["terms_s"]
    assert t["compute_s"] == pytest.approx(1.0)
    assert t["memory_s"] == pytest.approx(2.0)
    assert t["collective_s"] == pytest.approx(3.0)
    assert cell["dominant"] == "collective_s"
    assert cell["step_time_bound_s"] == pytest.approx(3.0)
    assert cell["chips"] == 8
    assert cell["useful_compute_ratio"] == pytest.approx(
        model_flops(arch, shape) / flops)


def test_build_cell_skips_without_record(tmp_path, monkeypatch):
    monkeypatch.setattr(roofline, "DRYRUN_DIR", str(tmp_path / "none"))
    cell = roofline.build_cell("transformer-base", "decode_32k")
    assert "skipped" in cell


# ------------------------------------------------------- weight_stream_bytes
def test_weight_stream_bytes_exact_assembly():
    n = 1_000_000
    ws = roofline.weight_stream_bytes
    # FP streams act_bytes per weight; INT8 streams exactly one byte
    assert ws(n, quantized=False, act_bytes=4) == 4 * n
    assert ws(n, quantized=False, act_bytes=2) == 2 * n
    assert ws(n, weight_bits=8) == n
    # INT4 default layout: nibble + (scale, min) f16 pair per 128 weights
    # → 0.5 + 2·2/128 = 0.53125 bytes/weight
    assert ws(n, weight_bits=4) == int(n * (0.5 + 4.0 / 128))
    assert n / ws(n, weight_bits=4) == pytest.approx(1.0 / 0.53125)
    assert n / ws(n, weight_bits=4) >= 1.88  # the bench's byte-cut floor
    # group/scale knobs move the metadata overhead exactly
    assert ws(n, weight_bits=4, group_size=32, scale_bytes=4) == \
        int(n * (0.5 + 8.0 / 32))
    # fraction mixes linearly between INT8 and full-INT4
    assert ws(n, weight_bits=4, int4_fraction=0.0) == n
    half = ws(n, weight_bits=4, int4_fraction=0.5)
    assert half == int(n * (0.5 + 0.5 * 0.53125))
    with pytest.raises(ValueError):
        ws(n, weight_bits=3)


def test_cell_int4_memory_term():
    cfg = get_config("transformer-base")
    n = cfg.n_active_params
    c8 = sharded_decode_cell(cfg, rows=8, tp=2, kv_bytes_per_step=1000)
    c4 = sharded_decode_cell(cfg, rows=8, tp=2, kv_bytes_per_step=1000,
                             weight_bits=4)
    # memory term assembles exactly from weight_stream_bytes
    w4 = roofline.weight_stream_bytes(n, weight_bits=4)
    assert c4["weight_bytes_per_step"] == w4
    assert c4["terms_s"]["memory_s"] == \
        pytest.approx((w4 / 2 + 1000) / HBM_BW)
    # compute + collective terms are untouched (nibbles feed the same
    # s8×s8 MXU path); only the weight-stream bytes shrink ≥ 1.88×
    assert c4["terms_s"]["compute_s"] == c8["terms_s"]["compute_s"]
    assert c4["terms_s"]["collective_s"] == c8["terms_s"]["collective_s"]
    assert c8["weight_bytes_per_step"] / c4["weight_bytes_per_step"] >= 1.88
    assert c4["weight_bits"] == 4 and c8["weight_bits"] == 8


def test_cell_int4_fraction_interpolates():
    cfg = get_config("transformer-base")
    cells = [sharded_decode_cell(cfg, rows=4, tp=1, weight_bits=4,
                                 int4_fraction=f)
             for f in (0.0, 0.5, 1.0)]
    b = [c["weight_bytes_per_step"] for c in cells]
    assert b[0] > b[1] > b[2]
    assert b[1] == pytest.approx((b[0] + b[2]) / 2, abs=1)


def test_cell_unquantized_ignores_weight_bits():
    cfg = get_config("transformer-base")
    c = sharded_decode_cell(cfg, rows=4, tp=1, quantized=False,
                            weight_bits=4)
    act_bytes = int(cfg.activation_dtype.itemsize)
    assert c["weight_bytes_per_step"] == cfg.n_active_params * act_bytes
    assert c["weight_bits"] == 8 * act_bytes
