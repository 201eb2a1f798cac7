"""Post-training quantization: FP32/bf16 model → INT8 model (paper §4).

The transform is purely functional:

    calibrations = Calibrator(fwd).run(batches).compute(mode="symmetric")
    qparams, qctx = quantize_model(params, calibrations, policy)
    logits = model.apply(qparams, batch, quant=qctx)

``quantize_model`` walks the parameter pytree, finds linear nodes (dicts with
a ``"w"`` leaf of rank ≥ 2 — the repo-wide convention), and replaces approved
weights with per-output-channel symmetric :class:`QTensor`.  ``QuantContext``
is the runtime companion the model consults for activation thresholds and
kernel implementation choice.

Site naming convention
----------------------
A linear living at params path ``("decoder", "blocks.3", "attn", "q_proj")``
has site name ``decoder/blocks.3/attn/q_proj``.  Calibration taps record the
matmul *input* under exactly this name.  Scanned (stacked-layer) execution
uses the layer-agnostic name ``decoder/blocks.*/attn/q_proj``; the context
merges per-layer calibration records into a conservative envelope for it.
"""

from __future__ import annotations

import dataclasses
import re
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.calibration import SiteCalibration
from repro.core.histogram import HistogramClass
from repro.core.policy import QuantPolicy
from repro.core.qtensor import (
    BlockQTensor,
    QTensor,
    quantize_block,
    quantize_symmetric,
)
from repro.core.quantize import QuantMode, Thresholds

_LAYER_SEG = re.compile(r"blocks\.(\d+)")


def generic_site(site: str) -> str:
    """``decoder/blocks.3/attn/q_proj`` → ``decoder/blocks.*/attn/q_proj``."""
    return _LAYER_SEG.sub("blocks.*", site)


def merge_calibrations(records) -> SiteCalibration:
    """Conservative envelope across per-layer records of one generic site."""
    t_min = min(r.thresholds.t_min for r in records)
    t_max = max(r.thresholds.t_max for r in records)
    any_sparse = any(r.classification.kind == "sparse" for r in records)
    kind = "sparse" if any_sparse else records[0].classification.kind
    cls = HistogramClass(
        kind=kind,
        zero_fraction=max(r.classification.zero_fraction for r in records),
        occupancy=min(r.classification.occupancy for r in records),
        p999_over_amax=max(r.classification.p999_over_amax for r in records),
    )
    return SiteCalibration(
        name=generic_site(records[0].name),
        thresholds=Thresholds(t_min, t_max),
        classification=cls,
        quantize=all(r.quantize for r in records),
    )


@dataclasses.dataclass
class QuantContext:
    """Runtime quantization state consulted by the model's linear layers."""

    policy: QuantPolicy
    calibrations: Dict[str, SiteCalibration] = dataclasses.field(default_factory=dict)
    # kernel choice: "auto" (Pallas on a TPU, XLA elsewhere) | "pallas" |
    # "interpret" | "xla"
    impl: str = "auto"
    enabled: bool = True

    def __post_init__(self):
        # Pre-merge layer-indexed records into generic-site envelopes so
        # scanned execution can look them up without knowing layer indices.
        merged: Dict[str, list] = {}
        for name, rec in self.calibrations.items():
            g = generic_site(name)
            if g != name:
                merged.setdefault(g, []).append(rec)
        for g, records in merged.items():
            if g not in self.calibrations:
                self.calibrations[g] = merge_calibrations(records)

    # -- queries the model makes -------------------------------------------
    def lookup(self, site: str) -> Optional[SiteCalibration]:
        rec = self.calibrations.get(site)
        if rec is None:
            rec = self.calibrations.get(generic_site(site))
        return rec

    def activation_thresholds(self, site: str) -> Optional[Thresholds]:
        """Static calibrated thresholds, or None → dynamic quantization."""
        if self.policy.act_quant != "static":
            return None
        rec = self.lookup(site)
        if rec is not None:
            return rec.thresholds
        if self.policy.default_amax is not None:
            t = float(self.policy.default_amax)
            return Thresholds(-t, t)
        return None

    def quantize_activations(self, site: str) -> bool:
        if not self.enabled or self.policy.mode == QuantMode.NONE:
            return False
        return self.policy.should_quantize(site, self.lookup(site))

    @property
    def quantize_kv(self) -> bool:
        return self.enabled and self.policy.quantize_kv_cache


# A context that disables quantization everywhere (FP32/bf16 baseline).
FP_CONTEXT = QuantContext(policy=QuantPolicy(mode=QuantMode.NONE), enabled=False)


# ---------------------------------------------------------------------------
# Parameter transform
# ---------------------------------------------------------------------------

def _is_linear_node(node: Any) -> bool:
    return (
        isinstance(node, dict)
        and "w" in node
        and not isinstance(node["w"], (dict, QTensor, BlockQTensor))
        and getattr(node["w"], "ndim", 0) >= 2
    )


def quantize_weight(w: jax.Array) -> QTensor:
    """Per-output-channel symmetric weight quantization.

    Convention: every linear weight is ``(..., d_in, d_out)`` (leading dims
    are layer-stack / expert dims).  The contraction axis is ``-2``; scales
    keep dims so stacked weights slice cleanly inside ``lax.scan``.
    """
    amax = jnp.max(jnp.abs(w.astype(jnp.float32)), axis=-2, keepdims=True)
    amax = jnp.maximum(amax, 1e-12)
    q = jnp.clip(jnp.round(w.astype(jnp.float32) * (127.0 / amax)), -127, 127)
    return QTensor(
        data=q.astype(jnp.int8),
        scale=amax / 127.0,
        zero_point=jnp.zeros_like(amax),
        axis=None,  # scale is pre-broadcast (keepdims)
    )


def quantize_weight_block(
    w: jax.Array,
    group_size: int = 128,
    scale_dtype=jnp.float16,
) -> BlockQTensor:
    """Block-wise INT4 weight quantization (group scale/min along d_in)."""
    return quantize_block(w, group_size=group_size, scale_dtype=scale_dtype)


# Which sites may drop to INT4 (the paper's sensitivity result): decoder FFN
# and attention *output* projections only.  q/k/v projections feed the
# attention score path and the KV cache — those, all encoder weights, the
# logits head and every activation stay INT8/FP.
_INT4_FFN_LEAVES = ("in", "out", "gate", "up", "down")


def int4_eligible_site(site: str) -> bool:
    parts = site.split("/")
    if not any(p == "dec_blocks" or p.startswith("dec_blocks.")
               for p in parts):
        return False
    if parts[-1] == "o_proj":
        return True
    return (len(parts) >= 2 and parts[-2] == "ffn"
            and parts[-1] in _INT4_FFN_LEAVES)


def quantize_model(
    params: Dict[str, Any],
    calibrations: Optional[Dict[str, SiteCalibration]] = None,
    policy: Optional[QuantPolicy] = None,
    impl: str = "auto",
    *,
    weight_bits: int = 8,
    weight_group_size: int = 128,
    weight_scale_dtype=jnp.float16,
) -> Tuple[Dict[str, Any], QuantContext]:
    """PTQ transform: returns (quantized params, runtime QuantContext).

    ``weight_bits=4`` additionally drops the INT4-eligible weights (decoder
    FFN + attention output projections, :func:`int4_eligible_site`) to
    block-wise INT4 with ``weight_group_size`` rows per scale/min block;
    every other approved site keeps the paper's per-channel INT8.
    """
    if weight_bits not in (8, 4):
        raise ValueError(f"weight_bits must be 8 or 4, got {weight_bits}")
    policy = policy or QuantPolicy()
    calibrations = calibrations or {}
    ctx = QuantContext(policy=policy, calibrations=dict(calibrations), impl=impl)

    def walk(node, path):
        if _is_linear_node(node):
            site = "/".join(path)
            out = dict(node)
            if policy.mode != QuantMode.NONE and policy.should_quantize(
                site, ctx.lookup(site)
            ):
                if weight_bits == 4 and int4_eligible_site(site):
                    out["w"] = quantize_weight_block(
                        node["w"], group_size=weight_group_size,
                        scale_dtype=weight_scale_dtype)
                else:
                    out["w"] = quantize_weight(node["w"])
            return out
        if isinstance(node, dict):
            return {k: walk(v, path + (str(k),)) for k, v in node.items()}
        return node

    return walk(params, ()), ctx


def count_quantized(params: Dict[str, Any]) -> Dict[str, int]:
    stats = {"quantized_linears": 0, "fp_linears": 0, "int8_bytes": 0,
             "fp_bytes": 0, "int4_linears": 0, "int4_bytes": 0}

    def walk(node):
        if isinstance(node, QTensor):
            stats["quantized_linears"] += 1
            stats["int8_bytes"] += node.nbytes()
            return
        if isinstance(node, BlockQTensor):
            stats["quantized_linears"] += 1
            stats["int4_linears"] += 1
            stats["int4_bytes"] += node.nbytes()
            return
        if isinstance(node, dict):
            if _is_linear_node(node):
                stats["fp_linears"] += 1
            for v in node.values():
                walk(v)
            return
        if hasattr(node, "nbytes"):
            stats["fp_bytes"] += int(node.nbytes)

    walk(params)
    return stats


def weight_bytes_by_site(params: Dict[str, Any]) -> Dict[str, int]:
    """Per-site weight footprint (bytes actually streamed per decode step):
    quantized payload + scale metadata for Q/BlockQ tensors, raw array bytes
    for FP linears.  Keyed by the linear's site name."""
    out: Dict[str, int] = {}

    def walk(node, path):
        if isinstance(node, dict):
            if _is_linear_node(node) or (
                "w" in node and isinstance(node["w"], (QTensor, BlockQTensor))
            ):
                w = node["w"]
                site = "/".join(path)
                if isinstance(w, (QTensor, BlockQTensor)):
                    out[site] = w.nbytes()
                else:
                    out[site] = int(w.size) * jnp.dtype(w.dtype).itemsize
                return
            for k, v in node.items():
                walk(v, path + (str(k),))

    walk(params, ())
    return out
