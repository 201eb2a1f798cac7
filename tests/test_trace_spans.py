"""What a profiler trace of the engine shows: the host loop's spans and the
burst programs' named scopes.

A serve writes ``engine.setup`` once and, for every round, an
``engine.round`` span holding its ``engine.admit``, ``engine.dispatch``,
``engine.wait``, ``engine.drain`` and ``engine.free`` phases, each with the
round's index as ``round`` metadata.  The compiled burst programs carry
the model step's named scopes in their op metadata, in both layer
layouts.  Tracing changes no served token.
"""

import dataclasses
import glob
import os
import re
from collections import defaultdict

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.configs import get_config
from repro.core import QuantPolicy, quantize_model
from repro.data import make_corpus
from repro.models import build_model
from repro.serving import ServingEngine

PHASES = ("engine.admit", "engine.dispatch", "engine.wait", "engine.drain",
          "engine.free")
DECODE_SCOPES = {"self_attention", "cross_attention", "ffn", "kv_pool",
                 "logits_head"}
ADMISSION_SCOPES = {"encoder", "admission"}


def _engine(scan_layers: bool = False) -> ServingEngine:
    """A tiny paged INT8 engine; ``scan_layers=False`` is the published
    configuration's unrolled layer loop."""
    cfg = dataclasses.replace(get_config("transformer-base").reduced(
        vocab=32, d_model=48, n_layers=2, n_enc_layers=1, d_ff=96,
        n_heads=2, n_kv_heads=2, head_dim=24), scan_layers=scan_layers)
    model = build_model(cfg)
    params, quant = quantize_model(model.init(jax.random.PRNGKey(0)), {},
                                   QuantPolicy(act_quant="dynamic"))
    return ServingEngine(model, params, quant=quant, max_len=32, paged=True,
                         page_size=8, burst_len=4)


@pytest.fixture(scope="module")
def engine():
    return _engine()


@pytest.fixture(scope="module")
def requests():
    # more requests than groups, so later rounds admit into freed rows
    return make_corpus(6, 32, seed=3, max_words=6)


SERVES = {"beam": dict(n_slots=8, beam=4, max_new_tokens=7),
          "greedy": dict(n_slots=2, max_new_tokens=7)}


def _engine_spans(trace_dir):
    """``(name, round, start_ns, end_ns)`` of every ``engine.*`` host
    event in the trace."""
    path, = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("engine."):
                    out.append((ev.name, dict(ev.stats).get("round"),
                                ev.start_ns, ev.start_ns + ev.duration_ns))
    return out


@pytest.mark.parametrize("mode", sorted(SERVES))
def test_serve_writes_each_phase_with_its_round(engine, requests, tmp_path,
                                                mode):
    kw = SERVES[mode]
    plain = engine.serve(requests, **kw)
    with jax.profiler.trace(str(tmp_path)):
        traced = engine.serve(requests, **kw)
    assert [r.tokens for r in traced.requests] == \
        [r.tokens for r in plain.requests]
    assert [r.score for r in traced.requests] == \
        [r.score for r in plain.requests]

    spans = _engine_spans(tmp_path)
    by_name = defaultdict(list)
    for name, rnd, t0, t1 in spans:
        assert isinstance(rnd, int), (name, rnd)
        by_name[name].append((rnd, t0, t1))
    assert set(by_name) == {"engine.setup", "engine.round", *PHASES}
    assert [r for r, _, _ in by_name["engine.setup"]] == [0]
    rounds = {r: (t0, t1) for r, t0, t1 in by_name["engine.round"]}
    assert sorted(rounds) == list(range(len(rounds))) and len(rounds) > 2
    # one wait per burst: the engine's own count of host syncs
    assert len(by_name["engine.wait"]) == traced.host_syncs
    setup_end = by_name["engine.setup"][0][2]
    assert setup_end <= rounds[0][0]
    for name in PHASES:
        for rnd, t0, t1 in by_name[name]:
            lo, hi = rounds[rnd]
            assert lo <= t0 <= t1 <= hi, (name, rnd)


class _FailingChaos:
    """Chaos that raises at round 1's admission edge."""

    def victims_for(self, rnd, ids):
        if rnd == 1:
            raise RuntimeError("chaos at round 1")
        return []

    def slow_for(self, rnd):
        return 0.0


@pytest.mark.parametrize("mode", sorted(SERVES))
def test_a_round_that_raises_closes_its_span(engine, requests, tmp_path,
                                             mode):
    """The failing round's spans end where it failed, even while the
    error's traceback is still held."""
    with jax.profiler.trace(str(tmp_path)):
        with pytest.raises(RuntimeError, match="chaos at round 1") as err:
            engine.serve(requests, chaos=_FailingChaos(), **SERVES[mode])
    assert err.value.__traceback__ is not None
    rounds = {(name, rnd) for name, rnd, _, _ in _engine_spans(tmp_path)}
    assert {("engine.round", 0), ("engine.round", 1),
            ("engine.admit", 1)} <= rounds
    assert ("engine.dispatch", 1) not in rounds


def _scopes_of_bursts(eng, requests, monkeypatch, **kw):
    """The op-name components of every burst program a serve dispatches,
    keyed by the builder that made it, from the compiled HLO text."""
    calls = {}

    def recording(builder):
        make = getattr(ServingEngine, builder)

        def build(*key):
            fn = make(eng, *key)

            def call(*args):
                calls[builder] = (fn, jax.tree.map(
                    lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype)
                    if isinstance(x, jax.Array) else x, args))
                return fn(*args)
            return call
        monkeypatch.setattr(eng, builder, build)

    for builder in ("_make_greedy_burst", "_make_fused_greedy_burst",
                    "_make_beam_serve_burst", "_make_fused_beam_serve_burst"):
        recording(builder)
    eng.serve(requests, **kw)
    out = {}
    for builder, (fn, args) in calls.items():
        text = fn.lower(*args).compile().as_text()
        out[builder] = {part for name in re.findall(r'op_name="([^"]*)"', text)
                        for part in name.split("/")}
    return out


@pytest.mark.parametrize("scan_layers", [False, True])
@pytest.mark.parametrize("mode", sorted(SERVES))
def test_burst_programs_carry_every_scope(requests, monkeypatch, mode,
                                          scan_layers):
    """The first round admits, so the fused program always runs; a round
    without admission runs the plain one, which neither encodes nor
    splices."""
    step = DECODE_SCOPES | ({"beam_step"} if mode == "beam" else set())
    fused, plain = {"beam": ("_make_fused_beam_serve_burst",
                             "_make_beam_serve_burst"),
                    "greedy": ("_make_fused_greedy_burst",
                               "_make_greedy_burst")}[mode]
    scopes = _scopes_of_bursts(_engine(scan_layers), requests, monkeypatch,
                               **SERVES[mode])
    assert set(scopes) <= {fused, plain}
    assert step | ADMISSION_SCOPES <= scopes[fused]
    if plain in scopes:
        assert step <= scopes[plain]
        assert not ADMISSION_SCOPES & scopes[plain]
    if mode == "greedy":
        assert "beam_step" not in scopes[fused]
