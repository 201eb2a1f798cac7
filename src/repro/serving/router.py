"""Data-parallel replica router: fan requests across N serving engines.

Tensor parallelism (``ServingEngine(mesh=...)``) buys per-step latency;
this buys throughput: N independent engine replicas — each its own
params copy, page pool and scheduler — behind a host-side router that
assigns every request to the replica with the shallowest queue, breaking
ties by the most *estimated free pages* (a shadow
``kv_cache.PageAllocator`` per replica mirrors what that replica's serve
pool will reserve, using the engine's own worst-case
``pages_per_row(max_new_tokens)`` accounting).  Queue depth leads the
score so counts can never drift more than one apart — the page estimate
arbitrates which near-even replica absorbs a long request.

Replicas serve concurrently (one host thread each, ``parallel=True``):
every engine's burst loop alternates dispatch / host-drain, so the
threads interleave at burst edges — each replica's serve is untouched
and its output bit-identical to running that share alone.  The merged
:class:`RouterResult` restores submission order and re-exposes the
``ServeResult`` surface the benches read.
"""

from __future__ import annotations

import dataclasses
import threading
from typing import Any, Callable, Dict, List, Optional, Sequence

import jax
import numpy as np

from repro.models import kv_cache as kvc
from repro.serving.engine import ServeResult, ServingEngine
from repro.serving.scheduler import Request

__all__ = ["ReplicaRouter", "RouterResult"]


@dataclasses.dataclass
class RouterResult:
    """Merged outcome of one routed serve across all replicas."""

    results: List[ServeResult]        # one per replica, replica order
    assignment: List[int]             # replica index per request, submission order
    requests: List[Request]           # submission order, lifecycle filled in
    wall_s: float

    @property
    def replicas(self) -> int:
        return len(self.results)

    @property
    def n_tokens(self) -> int:
        return int(sum(len(r.tokens) for r in self.requests))

    @property
    def tokens_per_s(self) -> float:
        return self.n_tokens / max(self.wall_s, 1e-9)

    @property
    def peak_running_per_replica(self) -> List[int]:
        return [r.peak_running for r in self.results]

    @property
    def host_syncs(self) -> int:
        return int(sum(r.host_syncs for r in self.results))

    def tokens_for(self, req_id: int) -> np.ndarray:
        for r in self.requests:
            if r.req_id == req_id:
                return np.asarray(r.tokens, np.int32)
        raise KeyError(req_id)

    def metrics(self) -> Dict[str, float]:
        out = {"replicas": float(self.replicas),
               "n_requests": float(len(self.requests)),
               "n_tokens": float(self.n_tokens),
               "wall_s": self.wall_s,
               "tokens_per_s": self.tokens_per_s,
               "host_syncs": float(self.host_syncs)}
        for i, r in enumerate(self.results):
            out[f"replica{i}_peak_running"] = float(r.peak_running)
            out[f"replica{i}_n_tokens"] = float(
                sum(len(q.tokens) for q in r.requests))
        return out


class ReplicaRouter:
    def __init__(self, engines: Sequence[ServingEngine]):
        if not engines:
            raise ValueError("ReplicaRouter needs at least one engine")
        self.engines = list(engines)

    @classmethod
    def on_devices(cls, make_engine: Callable[..., ServingEngine],
                   n_replicas: int) -> "ReplicaRouter":
        """``n_replicas`` engines, replica i built by
        ``make_engine(device=jax.devices()[i % n_devices])``: each replica
        on its own device while there are enough, round-robin after."""
        devices = jax.devices()
        return cls([make_engine(device=devices[i % len(devices)])
                    for i in range(n_replicas)])

    # ------------------------------------------------------------- routing
    def route(self, reqs: Sequence[Request], *, n_slots: int = 8
              ) -> List[int]:
        """Replica index per request: shallowest queue, then free pages.

        The shadow allocators are sized like each replica's serve pool
        (``engine._make_allocator(n_slots)``) and charged the worst-case
        reservation the engine's admission would hold for the request —
        free-page *estimates*, not live pool state (the pools don't exist
        until the serves run), which is exactly what a front-end router
        has to work from.
        """
        shadows = []
        for eng in self.engines:
            if eng.paged:
                shadows.append(kvc.PageAllocator(
                    eng.n_pages or n_slots * eng._max_pages, eng.page_size))
            else:
                shadows.append(None)
        depth = [0] * len(self.engines)
        out = []
        for req in reqs:
            def score(i):
                free = shadows[i].n_free if shadows[i] is not None else 0
                return (depth[i], -free, i)
            best = min(range(len(self.engines)), key=score)
            out.append(best)
            depth[best] += 1
            if shadows[best] is not None:
                eng = self.engines[best]
                need = kvc.pages_per_row(
                    min(req.max_new_tokens, eng.max_len), eng.page_size)
                shadows[best].alloc(min(need, shadows[best].n_free))
            else:
                # unpaged replicas balance on token budget via queue depth
                pass
        return out

    # ------------------------------------------------------------- serving
    def serve(self, requests: Sequence[Any], *, n_slots: int = 8,
              max_new_tokens: int = 64, parallel: bool = True,
              chaos: Optional[Sequence] = None, **kw) -> RouterResult:
        """Route ``requests`` and serve every share, merging the results.

        ``kw`` is broadcast to every replica's ``ServingEngine.serve``;
        ``chaos`` may be a per-replica sequence of schedules (or one
        schedule applied to all).  Requests keep their submission-order
        ``req_id``s, so ``tokens_for`` works on the merged result.
        """
        reqs = self.engines[0]._as_requests(requests, max_new_tokens)
        assignment = self.route(reqs, n_slots=n_slots)
        # shares are Request objects carrying their own budgets — the
        # per-replica serves only see a scalar default
        mx_default = (int(np.max(max_new_tokens))
                      if isinstance(max_new_tokens, (list, tuple, np.ndarray))
                      else int(max_new_tokens))
        shares: List[List[Request]] = [[] for _ in self.engines]
        for req, idx in zip(reqs, assignment):
            shares[idx].append(req)

        per_chaos: List[Any] = [None] * len(self.engines)
        if chaos is not None:
            if isinstance(chaos, (list, tuple)):
                if len(chaos) != len(self.engines):
                    raise ValueError(
                        f"per-replica chaos needs {len(self.engines)} "
                        f"schedules, got {len(chaos)}")
                per_chaos = list(chaos)
            else:
                per_chaos = [chaos] * len(self.engines)

        import time
        t0 = time.perf_counter()
        results: List[Optional[ServeResult]] = [None] * len(self.engines)
        errors: List[Optional[BaseException]] = [None] * len(self.engines)

        def run(i: int) -> None:
            skw = dict(kw)
            if per_chaos[i] is not None:
                skw["chaos"] = per_chaos[i]
            try:
                results[i] = self.engines[i].serve(
                    shares[i], n_slots=n_slots,
                    max_new_tokens=mx_default, **skw)
            except BaseException as e:       # surfaced after join
                errors[i] = e

        if parallel and len(self.engines) > 1:
            threads = [threading.Thread(target=run, args=(i,), daemon=True)
                       for i in range(len(self.engines))]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
        else:
            for i in range(len(self.engines)):
                run(i)
        for e in errors:
            if e is not None:
                raise e

        done = [r for r in results if r is not None]
        for r in done:
            r.replicas = len(self.engines)
        return RouterResult(results=done, assignment=assignment,
                            requests=reqs, wall_s=time.perf_counter() - t0)
