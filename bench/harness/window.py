"""The measured window: whole calls timed by the host clock.

``offline`` runs jobs back to back until ``seconds`` have passed; every
job started counts.  ``closed_loop`` sends one paragraph after another
from one client and counts every call started before ``seconds``.
"""

from __future__ import annotations

import contextlib
import dataclasses
import time
from typing import Any, Callable, List

from harness import traffic
from harness.system import results_of


@dataclasses.dataclass
class Call:
    """One serve() call of the window."""

    sents: List[traffic.Sentence]
    result: Any                      # ServeResult or RouterResult
    wall_s: float
    n_tokens: int                    # output tokens of the call

    @property
    def engine_results(self) -> List[Any]:
        return results_of(self.result)


@dataclasses.dataclass
class Window:
    calls: List[Call]
    elapsed_s: float

    @property
    def serve_s(self) -> float:
        return sum(c.wall_s for c in self.calls)

    @property
    def n_tokens(self) -> int:
        return sum(c.n_tokens for c in self.calls)

    @property
    def n_requests(self) -> int:
        return sum(len(c.sents) for c in self.calls)


def no_spans(name: str):
    return contextlib.nullcontext()


def _serve(system, sents, annotate) -> Call:
    with annotate("serve"):
        t0 = time.perf_counter()
        res = system.serve(sents)
        wall = time.perf_counter() - t0
    with annotate("result reading"):
        return Call(sents, res, wall, int(res.n_tokens))


def offline(system, seed: int, seconds: float,
            annotate: Callable = no_spans) -> Window:
    mix = system.mix
    calls: List[Call] = []
    t0 = time.perf_counter()
    job = 0
    while not calls or time.perf_counter() - t0 < seconds:
        with annotate("job generation"):
            sents = traffic.offline_job(mix, system.vocab, seed, job)
        calls.append(_serve(system, sents, annotate))
        job += 1
    return Window(calls, time.perf_counter() - t0)


def closed_loop(system, seed: int, seconds: float,
                annotate: Callable = no_spans) -> Window:
    mix = system.mix
    composition = traffic.paragraph_block(mix)
    calls: List[Call] = []
    t0 = time.perf_counter()
    block = 0
    while True:
        with annotate("job generation"):
            paragraphs = traffic.closed_loop_calls(
                mix, system.vocab, seed, block, composition)
        block += 1
        for sents in paragraphs:
            if calls and time.perf_counter() - t0 >= seconds:
                return Window(calls, time.perf_counter() - t0)
            calls.append(_serve(system, sents, annotate))


KINDS = {"offline": offline, "closed_loop": closed_loop}


def run(system, seed: int, seconds: float,
        annotate: Callable = no_spans) -> Window:
    return KINDS[system.mix["kind"]](system, seed, seconds, annotate)


def served_requests(window: Window) -> List[Any]:
    """Every request of the window with its call's sentence, in order:
    ``(sentence, request, replica)``; ``request`` is None where the call's
    result holds no request of that id, ``replica`` 0 without a router."""
    out = []
    for call in window.calls:
        assign = getattr(call.result, "assignment", None)
        by_id = {r.req_id: r for r in call.result.requests}
        for i, s in enumerate(call.sents):
            out.append((s, by_id.get(i), assign[i] if assign else 0))
    return out

