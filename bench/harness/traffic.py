"""The one traffic generator: every mix is a data file it reads.

Sizes and their order of arrival are fixed by the mix (its
``composition_seed``) and the same for every seed; ``--seed`` draws only
the token ids.  The order matters: the order in which a job's lengths
arrive changes how the grid packs and so the decode steps.  On one TPU
v5e, six orders of one offline-beam4 job spread over 7% in tokens/s,
where two runs of one order differed by under 1%.
"""

from __future__ import annotations

import dataclasses
import statistics
from typing import Any, Dict, List, Optional

import numpy as np

SPECIALS = 3            # ids 0..2 are PAD/BOS, BOS, EOS; content starts at 3


@dataclasses.dataclass
class Sentence:
    """One request as the client sends it."""

    src: np.ndarray                 # (S,) int32 source ids
    max_new_tokens: int
    beam: int = 1                   # the request's beam width (1: greedy)


def source_lengths(n: int, spec: Dict[str, Any]) -> np.ndarray:
    """The ``n`` quantiles at ``(i + 0.5) / n`` of the mix's length
    distribution, rounded and clipped: the same multiset for every seed."""
    if spec["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    nd = statistics.NormalDist(np.log(spec["median"]), spec["sigma"])
    q = [np.exp(nd.inv_cdf((i + 0.5) / n)) for i in range(n)]
    return np.clip(np.rint(q), spec["min"], spec["max"]).astype(np.int64)


def budget(length: int, spec: Dict[str, Any]) -> int:
    return int(min(spec["cap"], round(spec["factor"] * length)))


def rng_for(seed: int, *stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) & (2**64 - 1), *stream])


def sentences(lengths: np.ndarray, mix: Dict[str, Any], vocab: int,
              rng: np.random.Generator) -> List[Sentence]:
    """Requests of the given source lengths, at the mix's beam width."""
    return [Sentence(src=rng.integers(SPECIALS, vocab, size=int(n),
                                      dtype=np.int32),
                     max_new_tokens=budget(int(n), mix["budget"]),
                     beam=mix["beam"] or 1)
            for n in lengths]


def offline_job(mix: Dict[str, Any], vocab: int, seed: int,
                job: int) -> List[Sentence]:
    """Job ``job`` of a run: the mix's fixed lengths in the mix's fixed
    order for that job, with ids from ``seed``."""
    order = np.random.default_rng([mix["composition_seed"], 1, job])
    lengths = source_lengths(mix["job_sentences"], mix["source_length"])
    return sentences(order.permutation(lengths), mix, vocab,
                     rng_for(seed, 1, job))


def paragraph_block(mix: Dict[str, Any]) -> List[np.ndarray]:
    """The fixed composition of one block of closed-loop calls: each call's
    source lengths, from the mix's ``composition_seed`` alone."""
    lo, hi = mix["paragraph_sentences"]["min"], \
        mix["paragraph_sentences"]["max"]
    n_calls = mix["block_calls"]
    sizes = np.resize(np.arange(lo, hi + 1), n_calls)
    lengths = source_lengths(int(sizes.sum()), mix["source_length"])
    rng = np.random.default_rng(mix["composition_seed"])
    lengths = rng.permutation(lengths)
    cuts = np.cumsum(sizes)[:-1]
    return np.split(lengths, cuts)


def closed_loop_calls(mix: Dict[str, Any], vocab: int, seed: int,
                      block: int,
                      composition: Optional[List[np.ndarray]] = None
                      ) -> List[List[Sentence]]:
    """Block ``block`` of calls: the fixed paragraphs in the mix's fixed
    order for that block, with ids from ``seed``."""
    composition = composition or paragraph_block(mix)
    order = np.random.default_rng([mix["composition_seed"], 2, block])
    rng = rng_for(seed, 2, block)
    return [sentences(composition[i], mix, vocab, rng)
            for i in order.permutation(len(composition))]


def calibration_sentences(n: int, length: int, vocab: int,
                          seed: int) -> List[np.ndarray]:
    """(src, tgt) pairs of one length for calibration, from ``seed``."""
    rng = rng_for(seed, 3)
    return [(rng.integers(SPECIALS, vocab, size=length, dtype=np.int32),
             rng.integers(SPECIALS, vocab, size=length, dtype=np.int32))
            for _ in range(n)]
