"""Substrate tests: data pipeline/sorting, BLEU, checkpointing (fault
tolerance + elastic restore), optimizer, serving scheduler/streams,
gradient compression."""

import os
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.checkpoint import Checkpointer
from repro.data import (
    LMBatches,
    TranslationBatches,
    corpus_bleu,
    make_batches,
    make_corpus,
    padding_stats,
)
from repro.distributed import (
    StepWatchdog,
    run_with_restarts,
    tree_ef_compressed_mean,
    wire_bytes_fp32_allreduce,
    wire_bytes_int8_gather,
)
from repro.optim import AdamW, inverse_sqrt, warmup_cosine
from repro.serving import TokenSortedScheduler, simulate_streams


# ---------------------------------------------------------------------------
# data
# ---------------------------------------------------------------------------

def test_corpus_words_vs_tokens():
    corpus = make_corpus(100, vocab=64, seed=1)
    assert any(s.n_tokens != s.n_words for s in corpus)
    assert all(s.n_tokens >= s.n_words for s in corpus)


def test_token_sorting_reduces_padding():
    """Paper §5.4: token-sorted batching wastes less padding than unsorted,
    and at least as little as word-sorted."""
    corpus = make_corpus(600, vocab=256, seed=2)
    stats = {m: padding_stats(corpus, make_batches(corpus, 64, m))
             for m in ("none", "words", "tokens")}
    assert stats["tokens"]["pad_waste"] < stats["none"]["pad_waste"]
    assert stats["tokens"]["pad_waste"] <= stats["words"]["pad_waste"] + 1e-9


def test_translation_batches_resume_exactly():
    corpus = make_corpus(64, vocab=64, seed=3)
    a = TranslationBatches(corpus, 8, seed=5)
    for _ in range(3):
        a.next_batch()
    state = a.state_dict()
    want = a.next_batch()

    b = TranslationBatches(corpus, 8, seed=0)
    b.load_state_dict(state)
    got = b.next_batch()
    np.testing.assert_array_equal(want["src_tokens"], got["src_tokens"])


def test_bleu_properties():
    ref = [[3, 4, 5, 6, 7, 8]]
    assert corpus_bleu(ref, ref) == pytest.approx(100.0)
    assert corpus_bleu([[9, 10, 11, 12, 13, 14]], ref) == 0.0
    partial = corpus_bleu([[3, 4, 5, 6, 9, 10]], ref)
    assert 0.0 < partial < 100.0


# ---------------------------------------------------------------------------
# checkpointing
# ---------------------------------------------------------------------------

def test_checkpoint_roundtrip_and_retention(rng):
    tree = {"w": jnp.asarray(rng.normal(size=(4, 4)), jnp.float32),
            "nested": {"b": jnp.arange(3)}}
    with tempfile.TemporaryDirectory() as d:
        ck = Checkpointer(d, keep=2)
        for step in (1, 2, 3):
            ck.save(step, tree)
        assert ck.all_steps() == [2, 3]          # retention
        out = ck.restore(tree)
        np.testing.assert_allclose(np.asarray(out["w"]),
                                   np.asarray(tree["w"]))


def test_checkpoint_atomicity_tmp_never_visible(rng):
    tree = {"w": jnp.zeros((8,))}
    with tempfile.TemporaryDirectory() as d:
        ck = Checkpointer(d)
        ck.save(7, tree)
        assert not any(n.startswith("tmp") for n in os.listdir(d))
        assert ck.latest_step() == 7


def test_checkpoint_restores_quantized_tree(rng):
    from repro.core import QuantPolicy, quantize_model
    from repro.configs import get_config
    from repro.models import build_model
    cfg = get_config("yi-9b").reduced(n_layers=1)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    qp, _ = quantize_model(params, {}, QuantPolicy(act_quant="dynamic"))
    with tempfile.TemporaryDirectory() as d:
        ck = Checkpointer(d)
        ck.save(1, qp)
        out = ck.restore(qp)
        a = jax.tree_util.tree_leaves(out)
        b = jax.tree_util.tree_leaves(qp)
        for x, y in zip(a, b):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_run_with_restarts_retries():
    calls = {"n": 0}

    def flaky():
        calls["n"] += 1
        if calls["n"] < 3:
            raise RuntimeError("preempted")

    run_with_restarts(flaky, max_restarts=5)
    assert calls["n"] == 3


def test_watchdog_flags_straggler():
    wd = StepWatchdog(threshold=2.0)
    import time
    for _ in range(8):
        wd.start(); time.sleep(0.002); wd.stop()
    wd.start(); time.sleep(0.05)
    assert wd.stop() is True
    assert wd.summary()["stragglers"] >= 1


# ---------------------------------------------------------------------------
# optimizer
# ---------------------------------------------------------------------------

def test_adamw_reduces_quadratic():
    opt = AdamW(lr=0.1, weight_decay=0.0)
    params = {"w": jnp.asarray([3.0, -2.0])}
    state = opt.init(params)
    loss = lambda p: jnp.sum(p["w"] ** 2)
    for _ in range(100):
        g = jax.grad(loss)(params)
        params, state = opt.update(g, state, params)
    assert float(loss(params)) < 1e-2


def test_schedules_shapes():
    lr = inverse_sqrt(512)
    warm = float(lr(jnp.asarray(100)))
    peak = float(lr(jnp.asarray(4000)))
    late = float(lr(jnp.asarray(40000)))
    assert warm < peak and late < peak
    wc = warmup_cosine(1e-3, 10, 100)
    assert float(wc(jnp.asarray(5))) < 1e-3
    assert float(wc(jnp.asarray(100))) < float(wc(jnp.asarray(20)))


# ---------------------------------------------------------------------------
# serving scheduler / streams
# ---------------------------------------------------------------------------

def test_scheduler_plan_covers_all_requests():
    corpus = make_corpus(50, vocab=64, seed=4)
    sched = TokenSortedScheduler(batch_size=8)
    items = sched.plan(corpus)
    covered = sorted(i for item in items for i in item.indices)
    assert covered == list(range(50))
    # token-sorted: batch maxima non-increasing
    maxima = [max(corpus[i].n_tokens for i in item.indices)
              for item in items]
    assert maxima == sorted(maxima, reverse=True)


def test_simulate_streams_parallel_speedup():
    """Paper §5.6/Fig 6: mixed long/short batches gain from parallel
    streams; utilization stays ≤ 1."""
    costs = [8.0, 1.0] * 10
    serial = simulate_streams(costs, 1)
    par = simulate_streams(costs, 2)
    assert par["speedup_vs_serial"] > 1.6
    assert serial["utilization"] == pytest.approx(1.0)
    assert par["utilization"] <= 1.0


@given(st.lists(st.floats(min_value=0.1, max_value=10.0), min_size=2,
                max_size=40),
       st.integers(min_value=1, max_value=8))
@settings(max_examples=40, deadline=None)
def test_prop_stream_simulation_invariants(costs, n):
    out = simulate_streams(costs, n)
    assert out["makespan_s"] >= max(costs) - 1e-9          # critical path
    assert out["makespan_s"] <= sum(costs) + 1e-9          # never worse than serial
    assert out["speedup_vs_serial"] <= n + 1e-9            # bounded by streams


# ---------------------------------------------------------------------------
# gradient compression
# ---------------------------------------------------------------------------

def test_ef_compression_unbiased_over_steps(rng):
    """Error feedback: accumulated compressed updates converge to the true
    gradient sum over repeated steps (bias is pushed into the residual)."""
    mesh = jax.make_mesh((1,), ("data",),
                         axis_types=(jax.sharding.AxisType.Explicit,))

    def one_body(gx, err):
        return tree_ef_compressed_mean(gx, err, "data", 1)

    one = jax.shard_map(one_body, mesh=mesh,
                        in_specs=(jax.sharding.PartitionSpec(),
                                  jax.sharding.PartitionSpec()),
                        out_specs=(jax.sharding.PartitionSpec(),
                                   jax.sharding.PartitionSpec()),
                        check_vma=False)

    g = jnp.asarray(rng.normal(size=(64,)), jnp.float32)
    err = jnp.zeros_like(g)
    applied = jnp.zeros_like(g)
    for step in range(8):
        out, err = one(g, err)
        applied = applied + out
        # error feedback: applied-so-far + residual == true sum exactly
        np.testing.assert_allclose(np.asarray(applied + err),
                                   np.asarray(g * (step + 1)),
                                   rtol=1e-4, atol=1e-4)
    # per-step quantization error is bounded by one int8 step
    np.testing.assert_allclose(np.asarray(out), np.asarray(g),
                               atol=float(jnp.max(jnp.abs(g))) / 127 + 1e-6)


def test_compression_wire_math():
    n = 1_000_000
    fp32 = wire_bytes_fp32_allreduce(n, 16)
    int8 = wire_bytes_int8_gather(n, 16)
    assert fp32 / int8 == pytest.approx(8.0, rel=1e-6)
