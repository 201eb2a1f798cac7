"""Faults planted in the program underneath a run, for the fault tests:
each is a context that patches the serving path the window drives."""

import contextlib

import jax.numpy as jnp

from repro.models.encdec import EncDecLM
from repro.serving import ReplicaRouter, ServeResult, ServingEngine


@contextlib.contextmanager
def patched(obj, name, make):
    orig = getattr(obj, name)
    setattr(obj, name, make(orig))
    try:
        yield
    finally:
        setattr(obj, name, orig)


def state_unchanged():
    """Each decode step hands back the state it was given: no KV written,
    no cursor advanced."""
    def make(orig):
        def step(self, params, tokens, state, **kw):
            logits, _ = orig(self, params, tokens, state, **kw)
            return logits, state
        return step
    return patched(EncDecLM, "decode_step_multi", make)


def token_altered():
    """Each decode step's logits shifted by one vocabulary id, so every
    token is altered where it is produced."""
    def make(orig):
        def step(self, params, tokens, state, **kw):
            logits, state = orig(self, params, tokens, state, **kw)
            return jnp.roll(logits, 1, axis=-1), state
        return step
    return patched(EncDecLM, "decode_step_multi", make)


def half_batch():
    """Each serve() call serves the first half of its requests only."""
    def make(orig):
        def serve(self, requests, **kw):
            return orig(self, list(requests)[:max(len(requests) // 2, 1)],
                        **kw)
        return serve
    return patched(ServingEngine, "serve", make)


def exchange_left_out():
    """The router hands every replica but the first its share and never
    gets an answer back: those requests stay waiting."""
    def make(orig):
        def serve(self, requests, **kw):
            for e in self.engines[1:]:
                e.serve = lambda reqs, n_slots=8, **k: ServeResult(
                    requests=list(reqs), n_slots=n_slots, decode_steps=0,
                    busy_slot_steps=0, prefill_rounds=0, wall_s=0.0)
            return orig(self, requests, **kw)
        return serve
    return patched(ReplicaRouter, "serve", make)
