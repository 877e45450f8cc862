"""Reorder-selection serving: async plan pipeline + legacy sync front-end.

    PYTHONPATH=src python -m repro.launch.serve_selector \
        --requests 256 --batch 16 --path device --model random_forest

Simulates the production traffic pattern the ROADMAP targets: a stream of
matrices (with repeat structures, as real workloads re-solve the same
pattern) hits an :class:`AsyncPlanServer`. Warm structures are answered at
submit time straight from the two-tier plan cache (no featurization, no
classifier, no symbolic analysis); misses flow through a deadline-based
micro-batching queue and the three cold stages —

    feature-batch → device inference → plan build

— where the batcher thread runs the padded-CSR featurizer + on-device
classifier (forest inference included, via ``forest_jnp``) over each
micro-batch, and a pool of build workers runs reorder + symbolic analysis
per structure and installs the finished :class:`ExecutionPlan` in the
cache. Per-request latency is recorded end-to-end (submit → plan ready),
and the cache's disk tier under ``artifacts/plan_cache/`` means a restarted
server starts warm.

:class:`SelectorServer` — the PR-1 synchronous, name-only front-end — is
kept for callers that only want the algorithm label.

The micro-batching pipeline itself lives in
:mod:`repro.core.dispatch` (:class:`~repro.core.dispatch.PlanDispatcher`);
:class:`AsyncPlanServer` is its in-process name, and the RPC front-end
(:mod:`repro.launch.rpc`) puts a socket protocol in front of the same
core for out-of-process clients.

The demo entrypoint drives everything through :class:`repro.engine
.SolverEngine` (``engine.train(ds)`` → ``engine.serve()``), whose
model-fingerprint cache versioning guarantees a retrained selector never
replays plans persisted by its predecessor.
"""
from __future__ import annotations

import argparse
import collections
import os
import time
from typing import Dict, List, Sequence

from repro.core.dispatch import PlanDispatcher
from repro.core.plan_cache import PlanCache, matrix_fingerprint
from repro.core.selector import ReorderSelector
from repro.sparse.csr import CSRMatrix

__all__ = ["SelectorServer", "AsyncPlanServer", "main"]


class SelectorServer:
    """Batched, cached front-end around a trained :class:`ReorderSelector`.

    ``handle(mats)`` answers a request batch: fingerprint every matrix,
    serve repeats from the LRU cache, group the misses into padded batches
    of ``batch_size`` for the selector, and install the fresh plans.
    Duplicate structures *within* one request batch are featurized once.
    """

    def __init__(self, selector: ReorderSelector, *, batch_size: int = 16,
                 cache_capacity: int = 4096, path: str = "device",
                 use_pallas: bool = False):
        self.selector = selector
        self.batch_size = batch_size
        self.cache = PlanCache(cache_capacity)
        self.path = path
        self.use_pallas = use_pallas
        self.select_seconds = 0.0
        self.requests = 0

    def handle(self, mats: Sequence[CSRMatrix]) -> List[str]:
        self.requests += len(mats)
        keys = [matrix_fingerprint(m) for m in mats]
        plans: List[str] = [None] * len(mats)  # type: ignore[list-item]
        miss_idx: List[int] = []
        pending: Dict[str, List[int]] = {}
        for i, key in enumerate(keys):
            hit = self.cache.get(key)
            if hit is not None:
                plans[i] = hit
            elif key in pending:
                pending[key].append(i)  # intra-batch duplicate: one featurize
            else:
                pending[key] = [i]
                miss_idx.append(i)
        # size-tiered batching: chunking a size-sorted miss list keeps the
        # padded (N, E) of each device batch near its members' true sizes
        miss_idx.sort(key=lambda i: (mats[i].nnz, mats[i].n))
        for lo in range(0, len(miss_idx), self.batch_size):
            chunk = miss_idx[lo : lo + self.batch_size]
            batch_mats = [mats[i] for i in chunk]
            if self.path == "device":
                # pad partial chunks to batch_size (repeating a member) so
                # the batch dim stays one jit bucket; extra results are
                # dropped. The host path has no shape buckets — padding
                # there would just featurize the filler for nothing.
                batch_mats += [batch_mats[0]] * (self.batch_size - len(chunk))
            names, dt = self.selector.select_batch(
                batch_mats, path=self.path, use_pallas=self.use_pallas)
            self.select_seconds += dt
            for i, name in zip(chunk, names):
                self.cache.put(keys[i], name)
                for j in pending[keys[i]]:
                    plans[j] = name
        return plans

    def stats(self) -> dict:
        s = self.cache.stats()
        s.update(requests=self.requests, select_seconds=self.select_seconds)
        return s


# ---------------------------------------------------------------------------
# Async plan pipeline — the in-process face of the dispatch core
# ---------------------------------------------------------------------------

class AsyncPlanServer(PlanDispatcher):
    """In-process async plan server.

    This is :class:`repro.core.dispatch.PlanDispatcher` under its serving
    name — the full deadline micro-batching pipeline (warm-path futures,
    batcher thread, in-flight dedup, plan-build worker pool) with requests
    submitted by direct method call. The RPC front-end
    (:class:`repro.launch.rpc.PlanRPCServer`) wraps this same class to
    serve out-of-process clients; keeping the name alive preserves every
    existing import and ``SolverEngine.serve()`` contract.
    """


# ---------------------------------------------------------------------------
# entrypoint
# ---------------------------------------------------------------------------

def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--requests", type=int, default=256)
    p.add_argument("--batch", type=int, default=16)
    p.add_argument("--cache", type=int, default=512)
    p.add_argument("--cache-dir", default=None,
                   help="persistent plan-cache dir (default "
                        "artifacts/plan_cache; pass '' to stay in-memory)")
    p.add_argument("--max-disk-mb", type=float, default=None,
                   help="disk-tier byte budget (LRU-by-mtime eviction)")
    p.add_argument("--max-disk-entries", type=int, default=None,
                   help="disk-tier file-count cap")
    p.add_argument("--max-wait-ms", type=float, default=5.0)
    p.add_argument("--build-workers", type=int, default=2)
    p.add_argument("--path", choices=["host", "device"], default="device")
    p.add_argument("--use-pallas", action="store_true")
    p.add_argument("--model", default="random_forest")
    p.add_argument("--distinct", type=int, default=48,
                   help="distinct structures in the request stream")
    p.add_argument("--campaign-count", type=int, default=36)
    p.add_argument("--campaign-scale", type=float, default=0.35)
    p.add_argument("--seed", type=int, default=7)
    args = p.parse_args()

    import numpy as np

    from repro.core.labeling import load_or_build
    from repro.core.plan_cache import DEFAULT_CACHE_DIR
    from repro.engine import EngineConfig, SolverEngine
    from repro.sparse.dataset import generate_suite

    # one facade: config → train → serve. The engine versions the plan
    # cache with the fitted model's fingerprint, so a retrained selector
    # never serves plans persisted by its predecessor — no manual
    # version= bump here or anywhere.
    cache_dir = (args.cache_dir if args.cache_dir is not None
                 else DEFAULT_CACHE_DIR)
    engine = SolverEngine(EngineConfig(
        model=args.model, cache_dir=cache_dir or None,
        cache_capacity=args.cache,
        cache_max_disk_bytes=(int(args.max_disk_mb * 2**20)
                              if args.max_disk_mb else None),
        cache_max_disk_entries=args.max_disk_entries,
        path=args.path, use_pallas=args.use_pallas, batch_size=args.batch,
        max_wait_ms=args.max_wait_ms, build_workers=args.build_workers,
        fast_grids=True, cv=3, seed=0))
    ds = load_or_build(cache_dir="artifacts", count=args.campaign_count,
                       seed=args.seed, size_scale=args.campaign_scale,
                       repeats=1, verbose=True)
    rep = engine.train(ds)
    print(f"[serve-selector] model={args.model} "
          f"test_acc={rep['test_accuracy']:.2f} "
          f"fingerprint={engine.fingerprint[:16]}")

    pool = list(generate_suite(count=args.distinct, seed=args.seed + 1,
                               size_scale=0.4))
    rng = np.random.default_rng(args.seed)
    # zipf-ish popularity: a few hot structures dominate, like real traffic
    pop = 1.0 / (1.0 + np.arange(len(pool)))
    pop /= pop.sum()
    stream = rng.choice(len(pool), size=args.requests, p=pop)

    server = engine.serve()
    # warm the jit/kernel compile outside the timed region, then zero the
    # metrics so the report reflects steady-state serving (on a later run
    # with a persistent cache dir this warm-up is just a disk hit)
    server.handle([pool[0]])
    server.reset_stats()

    t0 = time.perf_counter()
    futs = [server.submit(pool[i]) for i in stream]
    plans = [f.result(timeout=300) for f in futs]
    wall = time.perf_counter() - t0
    server.close()

    s = server.stats()
    print(f"[serve-selector] path={args.path} pallas={args.use_pallas} "
          f"batch={args.batch} wait={args.max_wait_ms}ms "
          f"workers={args.build_workers} "
          f"disk={'off' if not cache_dir else cache_dir}")
    print(f"[serve-selector] {args.requests} requests in {wall*1e3:.0f} ms "
          f"→ {args.requests / wall:.0f} plans/sec end-to-end")
    print(f"[serve-selector] cache: {s['hits']} hits / {s['misses']} misses "
          f"(hit rate {s['hit_rate']:.2f}), {s['evictions']} evictions, "
          f"size {s['size']}/{s['capacity']}"
          + (f", disk {s['disk_hits']} hits / {s['disk_entries']} entries"
             if "disk_hits" in s else ""))
    print(f"[serve-selector] latency: p50 {s.get('p50_ms', 0.0):.2f} ms, "
          f"p99 {s.get('p99_ms', 0.0):.2f} ms "
          f"({s['warm_hits']} warm submits)")
    print(f"[serve-selector] cold stages: select {s['select_calls']} calls "
          f"{s['select_seconds']*1e3:.0f} ms, "
          f"{s['plans_built']} plans built {s['build_seconds']*1e3:.0f} ms")
    if s.get("max_disk_bytes") or s.get("max_disk_entries"):
        print(f"[serve-selector] disk budget: {s['disk_bytes']} bytes / "
              f"{s['disk_entries']} files, {s['disk_evictions']} evictions")
    dist = collections.Counter(pl.algorithm for pl in plans)
    print(f"[serve-selector] plan distribution: {dict(sorted(dist.items()))}")


if __name__ == "__main__":
    from repro.launch.compile_cache import configure_compile_cache

    configure_compile_cache(os.path.join(os.path.dirname(__file__),
                                         "..", "..", ".."))
    main()
