"""Operation and byte counts against values worked out by hand at the
transformer-base and transformer-big widths."""

import json
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

from harness import work  # noqa: E402


def shape(name):
    return work.Shape.of(json.loads(
        (BENCH / "configs" / f"{name}.json").read_text()))


@pytest.mark.parametrize("name,enc,dec,cross,dec_w", [
    # d*(q+2kv) + q*d + 2*d*ff, with q = kv = d
    ("transformer-base-int8", 3_145_728, 3_670_016, 3_145_728, 22_290_432),
    ("transformer-big-int8", 12_582_912, 14_680_064, 12_582_912, 88_621_056),
])
def test_per_token_multiply_adds_and_weight_bytes(name, enc, dec, cross,
                                                  dec_w):
    s = shape(name)
    assert s.enc_layer_macs == enc
    assert s.dec_layer_macs == dec
    assert s.cross_kv_macs == cross
    assert s.dec_weight_bytes == dec_w


def test_one_greedy_request_at_base():
    s = shape("transformer-base-int8")
    # source of 10 tokens, 4 decode steps, one row
    w = work.count(s, [work.Served(src_len=10, steps=4, rows=1)],
                   decode_steps=4, encodes=1)
    assert w.gemm_ops == 2 * 10 * (6 * 3_145_728 + 3_145_728) \
        + 2 * 4 * 6 * 3_670_016 == 616_562_688
    # encoder 2*L*2*S*S*q; decoder 2*L*2*q*(1+2+3+4 cached + 4*10 source)
    assert w.attn_ops == 2 * 6 * 2 * 100 * 512 + 2 * 6 * 2 * 512 * 50 \
        == 1_843_200
    assert w.head_ops == 2 * 4 * 512 * 37000 == 151_552_000
    # 6 layers * 10 cached positions * K and V * 8 heads * (64 + 4 scale)
    assert w.kv_bytes == 6 * 10 * 2 * 8 * 68 == 65_280
    assert w.model_ops == w.gemm_ops + w.attn_ops + w.head_ops
    assert w.gemm_bytes == 4 * s.dec_weight_bytes + s.enc_weight_bytes \
        + 4 * 6 * s.dec_layer_act_bytes + 10 * s.enc_token_act_bytes


def test_beam_rows_multiply_decoder_work_not_encoder_work():
    s = shape("transformer-big-int8")
    one = work.count(s, [work.Served(20, 8, 1)], decode_steps=8, encodes=1)
    four = work.count(s, [work.Served(20, 8, 4)], decode_steps=8, encodes=1)
    enc = 2 * 20 * (6 * s.enc_layer_macs + s.cross_kv_macs)
    assert four.gemm_ops - enc == pytest.approx(4 * (one.gemm_ops - enc))
    assert four.head_ops == 4 * one.head_ops
    assert four.kv_bytes == 4 * one.kv_bytes


def test_program_row_steps_cap_the_decoder_count():
    s = shape("transformer-base-int8")
    full = work.count(s, [work.Served(10, 8, 4)], decode_steps=8, encodes=1)
    half = work.count(s, [work.Served(10, 8, 4)], decode_steps=8, encodes=1,
                      row_steps_cap=16)
    assert half.head_ops == full.head_ops / 2
    assert half.kv_bytes == full.kv_bytes / 2


def test_roofline_takes_the_larger_bound():
    assert work.roofline_s(393e12, 0, 393e12, 819e9) == pytest.approx(1.0)
    assert work.roofline_s(1, 819e9 * 2, 393e12, 819e9) == pytest.approx(2.0)
