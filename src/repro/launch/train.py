"""Training driver: ``python -m repro.launch.train --arch yi-9b --steps 100``.

Runs a REDUCED config end-to-end on local devices (this container: 1 CPU
core) with the full production substrate: checkpointed loop, watchdog,
restart wrapper, resumable data iterator.  On a real pod the same driver
runs the full config under ``make_production_mesh()`` with the sharded
specs from ``launch/specs.py`` (see ``--production`` which lowers but does
not execute here).
"""

from __future__ import annotations

import argparse
import logging

import jax
import jax.numpy as jnp

from repro.checkpoint import Checkpointer
from repro.configs import get_config
from repro.data import LMBatches, TranslationBatches, make_corpus
from repro.distributed.fault import StepWatchdog, run_with_restarts
from repro.launch.compile_cache import enable_compile_cache
from repro.models import build_model
from repro.optim import AdamW, warmup_cosine
from repro.train import make_train_step, train_loop


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="transformer-base")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch-size", type=int, default=16)
    ap.add_argument("--seq-len", type=int, default=64)
    ap.add_argument("--lr", type=float, default=2e-3)
    ap.add_argument("--accum", type=int, default=1)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--save-every", type=int, default=100)
    ap.add_argument("--max-restarts", type=int, default=2)
    args = ap.parse_args()
    logging.basicConfig(level=logging.INFO,
                        format="%(asctime)s %(name)s %(message)s")

    enable_compile_cache()
    cfg = get_config(args.arch).reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    opt = AdamW(lr=warmup_cosine(args.lr, args.steps // 10, args.steps))
    opt_state = opt.init(params)
    step = jax.jit(make_train_step(model, opt, accum_steps=args.accum))

    if cfg.enc_dec:
        corpus = make_corpus(800, cfg.vocab, seed=0)
        data = TranslationBatches(corpus, args.batch_size,
                                  sort_mode="tokens")
    else:
        data = LMBatches(cfg.vocab, args.batch_size, args.seq_len)

    ck = Checkpointer(args.ckpt_dir, keep=2) if args.ckpt_dir else None

    def job():
        out = train_loop(train_step=step, params=params,
                         opt_state=opt_state, batches=data,
                         steps=args.steps, checkpointer=ck,
                         save_every=args.save_every,
                         watchdog=StepWatchdog())
        hist = out["history"]
        print(f"final loss: {hist[-1]['loss']:.4f} "
              f"(first logged: {hist[0]['loss']:.4f})")
        print("watchdog:", out["watchdog"])

    run_with_restarts(job, max_restarts=args.max_restarts)


if __name__ == "__main__":
    main()
