"""Operations and bytes of the work served, counted from the traffic.

Counts come from what the requests needed (real source tokens, decode
row-steps of live requests, live KV lengths), never from padded shapes,
so they stay the same whatever implements the model and a share of a
peak cannot pass 100% on a correct run.  One multiply-add is 2 ops.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Iterable, Sequence


@dataclasses.dataclass(frozen=True)
class Shape:
    """The sizes of one encoder-decoder configuration."""

    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    n_enc_layers: int
    n_layers: int

    @classmethod
    def of(cls, cfg: Dict) -> "Shape":
        return cls(**{f.name: int(cfg[f.name])
                      for f in dataclasses.fields(cls)})

    @property
    def q_width(self) -> int:
        return self.n_heads * self.head_dim

    @property
    def kv_width(self) -> int:
        return self.n_kv_heads * self.head_dim

    # multiply-adds of the linear layers, per token
    @property
    def enc_layer_macs(self) -> int:
        d = self.d_model
        return d * (self.q_width + 2 * self.kv_width) + self.q_width * d \
            + 2 * d * self.d_ff

    @property
    def cross_kv_macs(self) -> int:
        """Cross-attention K and V projections of one source token, all
        decoder layers."""
        return self.n_layers * 2 * self.d_model * self.kv_width

    @property
    def dec_layer_macs(self) -> int:
        d = self.d_model
        self_attn = d * (self.q_width + 2 * self.kv_width) + self.q_width * d
        cross_q_o = 2 * d * self.q_width
        return self_attn + cross_q_o + 2 * d * self.d_ff

    # weight bytes as served: int8 payload + f32 scale and f32 bias per
    # output column
    def _linear_bytes(self, d_in: int, d_out: int) -> int:
        return d_in * d_out + 8 * d_out

    @property
    def enc_weight_bytes(self) -> int:
        """Linear weights one encode reads: encoder layers plus the
        decoder's cross-attention K/V projections."""
        d, q, kv = self.d_model, self.q_width, self.kv_width
        layer = (self._linear_bytes(d, q) + 2 * self._linear_bytes(d, kv)
                 + self._linear_bytes(q, d) + self._linear_bytes(d, self.d_ff)
                 + self._linear_bytes(self.d_ff, d))
        return self.n_enc_layers * layer \
            + self.n_layers * 2 * self._linear_bytes(d, kv)

    @property
    def dec_weight_bytes(self) -> int:
        """Linear weights one decode step reads (all decoder layers)."""
        d, q, kv = self.d_model, self.q_width, self.kv_width
        layer = (self._linear_bytes(d, q) + 2 * self._linear_bytes(d, kv)
                 + 2 * self._linear_bytes(q, d) + self._linear_bytes(d, q)
                 + self._linear_bytes(d, self.d_ff)
                 + self._linear_bytes(self.d_ff, d))
        return self.n_layers * layer

    @property
    def dec_layer_act_bytes(self) -> int:
        """INT8 inputs and bf16 outputs of one row's decoder linears, one
        layer."""
        d, q, kv = self.d_model, self.q_width, self.kv_width
        io = [(d, q), (d, kv), (d, kv), (q, d), (d, q), (q, d),
              (d, self.d_ff), (self.d_ff, d)]
        return sum(i + 2 * o for i, o in io)

    @property
    def enc_token_act_bytes(self) -> int:
        d, q, kv = self.d_model, self.q_width, self.kv_width
        io = [(d, q), (d, kv), (d, kv), (q, d), (d, self.d_ff),
              (self.d_ff, d)]
        return self.n_enc_layers * sum(i + 2 * o for i, o in io) \
            + self.n_layers * 2 * (d + 2 * kv)


@dataclasses.dataclass(frozen=True)
class Served:
    """One request as served: real source tokens, decode steps its rows
    ran, and rows per request (the beam width)."""

    src_len: int
    steps: int
    rows: int


@dataclasses.dataclass
class Work:
    gemm_ops: float = 0.0            # INT8 linear layers
    attn_ops: float = 0.0            # attention score and value products
    head_ops: float = 0.0            # logits head
    gemm_bytes: float = 0.0          # INT8 linear weights + activations
    kv_bytes: float = 0.0            # live paged KV read (INT8 + f32 scales)

    @property
    def model_ops(self) -> float:
        return self.gemm_ops + self.attn_ops + self.head_ops


def count(shape: Shape, served: Iterable[Served], *, decode_steps: int,
          encodes: int, row_steps_cap: int = 0) -> Work:
    """Work of the requests ``served`` by serves that ran ``decode_steps``
    grid steps and ``encodes`` encoder calls in all.

    ``row_steps_cap``, the program's own count of busy decode row-steps,
    scales the decoder's row work down where requests stopped early (a
    beam group whose hypotheses all ended at EOS), so the count never
    exceeds what the program ran.
    """
    s = shape
    w = Work()
    served = list(served)
    row_steps = sum(r.rows * r.steps for r in served)
    scale = 1.0
    if row_steps_cap and row_steps > row_steps_cap:
        scale = row_steps_cap / row_steps
    dec_macs = dec_attn_macs = head_macs = 0.0
    kv_positions = 0.0
    for r in served:
        S, T, B = r.src_len, r.steps, r.rows
        # encoder: once per request (one source row per beam group)
        w.gemm_ops += 2 * S * (s.n_enc_layers * s.enc_layer_macs
                               + s.cross_kv_macs)
        w.attn_ops += 2 * s.n_enc_layers * 2 * S * S * s.q_width
        w.gemm_bytes += S * s.enc_token_act_bytes
        # decoder: T steps for each of B rows; step t attends t+1 cached
        # positions and S source positions
        dec_macs += B * T * s.n_layers * s.dec_layer_macs
        live = B * T * (T + 1) / 2
        dec_attn_macs += s.n_layers * 2 * s.q_width * (live + B * T * S)
        head_macs += B * T * s.d_model * s.vocab
        kv_positions += s.n_layers * live
    w.gemm_ops += 2 * scale * dec_macs
    w.attn_ops += 2 * scale * dec_attn_macs
    w.head_ops += 2 * scale * head_macs
    w.gemm_bytes += scale * row_steps * s.n_layers * s.dec_layer_act_bytes
    w.gemm_bytes += decode_steps * s.dec_weight_bytes \
        + encodes * s.enc_weight_bytes
    w.kv_bytes = scale * kv_positions * 2 * s.n_kv_heads * (s.head_dim + 4)
    return w


def roofline_s(ops: float, nbytes: float, ops_per_s: float,
               bytes_per_s: float) -> float:
    """Least time the chip could take: the larger of the two bounds."""
    return max(ops / ops_per_s, nbytes / bytes_per_s)


def summed(results: Sequence, field: str) -> int:
    return int(sum(getattr(r, field) for r in results))
