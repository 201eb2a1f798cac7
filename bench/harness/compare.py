"""The comparison that decides ``correct``.

Every request of the window must come back finished, with no more tokens
than its budget, all in the vocabulary.  A sample drawn from the seed,
with the longest request of each replica in it, is then run through the
plain float32 reference, teacher-forced on the served tokens, and two
numbers are read:

* ``logit_gap``, over greedy requests: the widest gap by which a served
  token's reference logit lies below the reference's best at its
  position.  A greedy token is the argmax of its own logits, so a correct
  program reads rounding noise only.
* ``score_gap``, over beam requests: for each request, the gap between
  the log-prob the program reports for its winning hypothesis
  (``Request.score`` times the length penalty) and the reference's
  log-prob of the same tokens, per token; the widest over the sample.
  A beam token is not its position's argmax, so the greedy gap does not
  apply; the score covers the tokens, their log-probs and the beam's
  reordering of its histories together.

Each is read on its own sample of ``sample_requests``.  A cell's limits
file names the numbers it compares; the others are reported beside
them.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from harness import traffic

EOS = 2
BOS = 0                  # the decoder's start token as the program serves it


@dataclasses.dataclass
class Served:
    src: np.ndarray
    tokens: np.ndarray           # served ids, EOS appended where it ended one
    replica: int
    beam: int = 1
    score: Optional[float] = None    # the program's beam score, if any
    ended_at_eos: bool = False

    def score_log_prob(self, alpha: float) -> float:
        """The program's summed log-prob of ``tokens``: its reported,
        length-penalized score times the penalty (the engine's
        ``((5 + L) / 6) ** alpha``, L the tokens before EOS)."""
        length = len(self.tokens) - int(self.ended_at_eos)
        return self.score * ((5.0 + length) / 6.0) ** alpha


def check_answers(rows: Sequence[Tuple[Any, Any, int]], vocab: int
                  ) -> Tuple[int, List[Served]]:
    """(failed, answers): a request fails unless it finished with at most
    its budget of in-vocabulary tokens."""
    failed, out = 0, []
    for sent, req, replica in rows:
        if req is None:
            failed += 1
            continue
        toks = np.asarray(req.tokens, np.int64)
        ok = (req.status == "finished" and len(toks) <= sent.max_new_tokens
              and bool(np.all((toks >= 0) & (toks < vocab))))
        if not ok:
            failed += 1
            continue
        eos = len(toks) < sent.max_new_tokens
        if eos:
            toks = np.append(toks, EOS)          # it ended at EOS
        out.append(Served(np.asarray(sent.src), toks, replica,
                          beam=sent.beam, score=req.score,
                          ended_at_eos=eos))
    return failed, out


def sample(answers: Sequence[Served], n: int, seed: int) -> List[Served]:
    """``n`` answers drawn from ``seed``, spread evenly over the replicas,
    each replica's longest first."""
    rng = traffic.rng_for(seed, 4)
    by_rep: Dict[int, List[Served]] = {}
    for a in answers:
        by_rep.setdefault(a.replica, []).append(a)
    per = -(-n // max(len(by_rep), 1))
    out: List[Served] = []
    for rep in sorted(by_rep):
        group = by_rep[rep]
        longest = max(range(len(group)), key=lambda i: len(group[i].tokens))
        rest = [i for i in range(len(group)) if i != longest]
        pick = [longest] + list(rng.choice(rest, size=min(per - 1, len(rest)),
                                           replace=False))
        out.extend(group[i] for i in pick)
    return out[:max(n, len(by_rep))]


@functools.partial(jax.jit, static_argnames=("reference", "cfg_items"))
def _gaps(params, src, src_len, dec_in, served, *, reference, cfg_items):
    logits = reference(params, dict(cfg_items), src, src_len, dec_in)
    got = jnp.take_along_axis(logits, served[..., None], axis=-1)[..., 0]
    log_z = jax.nn.logsumexp(logits, axis=-1)
    return logits.max(axis=-1) - got, got - log_z


@functools.partial(jax.jit, static_argnames=("reference", "cfg_items"))
def _control_gaps(params, low_params, src, src_len, dec_in, served, *,
                  reference, cfg_items):
    ref = reference(params, dict(cfg_items), src, src_len, dec_in)
    low = reference(low_params, dict(cfg_items), src, src_len, dec_in)
    first = jnp.argmax(low, axis=-1)[..., None]
    gap = ref.max(axis=-1) - jnp.take_along_axis(ref, first, axis=-1)[..., 0]
    got = jnp.take_along_axis(low, served[..., None], axis=-1)[..., 0]
    return gap, got - jax.nn.logsumexp(low, axis=-1)


def _cfg_items(cfg: Dict[str, Any]) -> tuple:
    keys = ("d_model", "n_heads", "head_dim", "n_layers", "n_enc_layers")
    return tuple((k, cfg[k]) for k in keys)


def _blocks(answers: Sequence[Served], src_pad: int, dec_pad: int,
            block: int):
    """``(rows, src, src_len, dec_in, served)`` for ``block`` answers at a
    time at fixed padded shapes (one compile per configuration)."""
    for i in range(0, len(answers), block):
        part = list(answers[i:i + block])
        rows = len(part)
        part += [part[0]] * (block - rows)
        src = np.zeros((block, src_pad), np.int32)
        src_len = np.zeros((block,), np.int32)
        dec_in = np.zeros((block, dec_pad), np.int32)
        served = np.zeros((block, dec_pad), np.int32)
        for j, a in enumerate(part):
            src[j, :len(a.src)] = a.src
            src_len[j] = len(a.src)
            T = len(a.tokens)
            dec_in[j, 0] = BOS
            dec_in[j, 1:T] = a.tokens[:T - 1]
            served[j, :T] = a.tokens
        yield part[:rows], src, src_len, dec_in, served


def reference_readings(reference, cfg: Dict[str, Any], params,
                       answers: Sequence[Served], *, src_pad: int,
                       dec_pad: int, block: int = 4
                       ) -> List[Tuple[np.ndarray, np.ndarray]]:
    """For each answer, at every served position, the gap below the
    reference's best logit and the reference's log-prob of the served
    token; ``block`` rows at a time."""
    out = []
    for part, *arrays in _blocks(answers, src_pad, dec_pad, block):
        g, lp = (np.asarray(x) for x in _gaps(
            params, *arrays, reference=reference, cfg_items=_cfg_items(cfg)))
        for j, a in enumerate(part):
            T = len(a.tokens)
            out.append((g[j, :T], lp[j, :T].astype(np.float64)))
    return out


def fake_quant(params, bits: int):
    """The weights rounded to ``bits``-bit symmetric integers, one scale per
    output column of a linear weight and per row of the embedding table;
    biases and norms stay float32.  The reference run on these is the
    control, in the precision below the configuration's."""
    qmax = 2.0 ** (bits - 1) - 1

    def q(path, x):
        last = str(getattr(path[-1], "key", path[-1]))
        axis = {"w": 0, "table": 1}.get(last)
        if axis is None:
            return x
        s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / qmax
        s = jnp.where(s > 0, s, 1.0)
        return jnp.round(x / s) * s

    return jax.jit(lambda p: jax.tree_util.tree_map_with_path(q, p))(params)


def control_readings(reference, cfg: Dict[str, Any], params, low_params,
                     answers: Sequence[Served], *, src_pad: int,
                     dec_pad: int, block: int = 4
                     ) -> List[Tuple[np.ndarray, np.ndarray]]:
    """The control put in the program's place, teacher-forced on the same
    prompts and served tokens: for each answer, at every position, the
    reference's gap of the token the control puts first, and the
    control's log-prob of the served token."""
    out = []
    for part, *arrays in _blocks(answers, src_pad, dec_pad, block):
        g, lp = (np.asarray(x) for x in _control_gaps(
            params, low_params, *arrays, reference=reference,
            cfg_items=_cfg_items(cfg)))
        for j, a in enumerate(part):
            T = len(a.tokens)
            out.append((g[j, :T], lp[j, :T].astype(np.float64)))
    return out


def widest_score_gap(answers: Sequence[Served],
                     readings: Sequence[Tuple[np.ndarray, np.ndarray]],
                     alpha: float) -> float:
    """The widest per-token gap between the program's and the reference's
    log-prob of a winning hypothesis."""
    return max(abs(a.score_log_prob(alpha) - float(lp.sum())) / len(lp)
               for a, (_, lp) in zip(answers, readings))


def widest_control_score_gap(
        readings: Sequence[Tuple[np.ndarray, np.ndarray]],
        control: Sequence[Tuple[np.ndarray, np.ndarray]]) -> float:
    """``widest_score_gap`` with the control's log-probs in place of the
    program's score."""
    return max(abs(float(c.sum()) - float(r.sum())) / len(r)
               for (_, r), (_, c) in zip(readings, control))


@dataclasses.dataclass
class Verdict:
    attempted: int
    failed: int
    compared: Dict[str, Dict[str, float]]      # name → {value, limit}
    detail: Dict[str, float]

    @property
    def correct(self) -> bool:
        return self.failed == 0 and all(
            np.isfinite(c["value"]) and c["value"] <= c["limit"]
            for c in self.compared.values())

    def lines(self) -> List[str]:
        return [f"compare {k}: {v['value']!r} limit {v['limit']!r}"
                for k, v in self.compared.items()]
