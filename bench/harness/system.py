"""The system under test, built through the program's public entry points:
``launch.serve.serving_config`` and ``quantize_for_serving``,
``models.build_model``, ``serving.ServingEngine`` and ``ReplicaRouter``.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Sequence

import jax
import numpy as np

from harness import traffic, weights

MODEL_KEYS = ("n_enc_layers", "n_layers", "d_model", "n_heads",
              "n_kv_heads", "d_ff", "vocab", "norm", "ffn", "attn_bias",
              "tie_embeddings", "dtype", "param_dtype")


def program_config(cfg_file: Dict[str, Any]):
    """The program's ``ModelConfig`` for a configuration file, checked
    against every size the file states."""
    from repro.launch.serve import serving_config

    cfg = serving_config(cfg_file["program_arch"], published=True)
    cfg = dataclasses.replace(cfg, **{k: cfg_file[k] for k in MODEL_KEYS})
    if cfg.hd != cfg_file["head_dim"]:
        cfg = dataclasses.replace(cfg, head_dim=cfg_file["head_dim"])
    got = {k: getattr(cfg, k) for k in MODEL_KEYS}
    got["head_dim"] = cfg.hd
    want = {k: cfg_file[k] for k in (*MODEL_KEYS, "head_dim")}
    if got != want:
        raise ValueError(f"program config {got} differs from {want}")
    return cfg


class System:
    """One configuration, quantized and ready to serve a traffic mix on
    ``n_replicas`` devices."""

    def __init__(self, cfg_file: Dict[str, Any], mix: Dict[str, Any], *,
                 weight_bits: Optional[int] = None):
        from repro.models import build_model

        self.cfg_file = cfg_file
        self.mix = mix
        self.cfg = program_config(cfg_file)
        self.model = build_model(self.cfg)
        self.weight_bits = weight_bits or cfg_file["quant"]["weight_bits"]
        self.shapes = jax.eval_shape(self.model.init, jax.random.PRNGKey(0))
        self.engine = None
        self.router = None
        self.qparams = None
        self.qctx = None

    @property
    def vocab(self) -> int:
        return self.cfg.vocab

    def make_weights(self):
        return weights.make_weights(self.shapes,
                                    self.cfg_file["weights"]["seed"])

    def quantize(self) -> None:
        """Calibrate and quantize freshly made weights; the float weights
        are dropped once the INT8 ones exist."""
        from repro.data import Sentence
        from repro.launch.serve import quantize_for_serving

        cal = self.cfg_file["calibration"]
        pairs = traffic.calibration_sentences(
            cal["sentences"], cal["length"], self.vocab,
            self.cfg_file["weights"]["seed"])
        calib = [Sentence(src=s, tgt=t, n_words=len(s)) for s, t in pairs]
        params = self.make_weights()
        q = self.cfg_file["quant"]
        self.qparams, self.qctx, _ = quantize_for_serving(
            self.model, params, calib, mode=q["mode"],
            weight_bits=self.weight_bits)
        jax.block_until_ready(self.qparams)
        del params

    def _engine(self, device=None):
        from repro.serving import ServingEngine

        s = self.cfg_file["serving"]
        return ServingEngine(
            self.model, self.qparams, quant=self.qctx, max_len=s["max_len"],
            paged=s["paged"], page_size=s["page_size"],
            burst_len=s["burst_len"],
            admission_enc_bucket=s["admission_enc_bucket"], device=device)

    def start(self) -> None:
        from repro.serving import ReplicaRouter

        n = self.mix["replicas"]
        if n > 1:
            self.router = ReplicaRouter.on_devices(
                lambda device: self._engine(device), n)
        else:
            self.engine = self._engine(jax.devices()[0])

    def serve(self, sents: Sequence[traffic.Sentence]):
        from repro.serving import Request

        beam = self.mix["beam"]
        reqs = [Request(req_id=i, src=s.src, max_new_tokens=s.max_new_tokens,
                        beam=s.beam if beam else None)
                for i, s in enumerate(sents)]
        kw = dict(n_slots=self.mix["n_slots"],
                  max_new_tokens=max(s.max_new_tokens for s in sents),
                  fused_admission=self.cfg_file["serving"]["fused_admission"])
        if beam:
            # the grid's group width; each request runs its own
            kw.update(beam=beam, alpha=self.mix["alpha"])
        target = self.router if self.router is not None else self.engine
        return target.serve(reqs, **kw)

    def call_sizes(self) -> List[int]:
        """How many requests one serve() call of the mix carries."""
        mix = self.mix
        if mix["kind"] == "offline":
            return [mix["job_sentences"] // mix["replicas"]]
        p = mix["paragraph_sentences"]
        return list(range(p["min"], p["max"] + 1))

    def warm_up(self) -> int:
        """Build every burst program the mix's calls reach, and no other.

        A call's first admission takes ``next_pow2(min(size, groups))``
        requests; a call larger than the grid admits later rounds of any
        power of two up to the grid's groups, with inputs the previous
        burst left on the device, which is a program of its own.  Every
        warm-up source has the longest length, so the encoder bucket is
        the mix's largest, and a budget one step over a burst also reaches
        the burst without admission.  Returns the burst variants built."""
        from repro.data.sorting import next_pow2

        groups = self.mix["n_slots"] // (self.mix["beam"] or 1)
        src_len = self.mix["source_length"]["max"]
        steps = self.cfg_file["serving"]["burst_len"] + 1
        rng = np.random.default_rng(0)

        def serve(n: int) -> None:
            self.serve(traffic.sentences(
                [src_len] * (n * self.mix["replicas"]),
                dict(self.mix, budget={"factor": steps, "cap": steps}),
                self.vocab, rng))

        sizes = self.call_sizes()
        first = sorted({next_pow2(min(n, groups)) for n in sizes})
        later = [1 << i for i in range(groups.bit_length())] \
            if max(sizes) > groups else []
        for w in later:
            serve(groups + w)          # first admission at `groups`, then w
        for w in first:
            if not (later and w == groups):
                serve(w)
        return self.compiled_variants()

    def compiled_variants(self) -> int:
        engines = self.router.engines if self.router else [self.engine]
        return sum(e.compiled_variants() or 0 for e in engines)

    def stop(self) -> None:
        """Drop every device array the program holds."""
        self.engine = self.router = None
        self.qparams = self.qctx = None


def results_of(res) -> List[Any]:
    """The per-engine ``ServeResult``s of one serve (router or engine)."""
    return list(res.results) if hasattr(res, "results") else [res]
