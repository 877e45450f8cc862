"""ExecutionPlan: the selector's output as a cached end-to-end artifact.

The paper's 55.37% solve-time reduction is realized *downstream* of the
classifier — permutation, symbolic analysis, factorization — so caching
just the algorithm name (PR 1's serving path) still pays the expensive
symbolic analysis on every request. An :class:`ExecutionPlan` carries
everything that is a pure function of the sparsity structure:

    algorithm name + permutation + SymbolicFactor (etree, column counts,
    factor pattern, supernode partition) + predicted cost

so a cache hit skips straight to numeric factorization
(:func:`repro.sparse.multifrontal.multifrontal_cholesky` /
:func:`repro.sparse.numeric.sparse_cholesky` both accept the precomputed
``sym``). The plan also keeps, in the process only, the factorization's
pattern-only structure per numeric policy
(:class:`repro.sparse.multifrontal.FactorStructure`), so refactorizations
with new values skip the schedule, the extend-add routes and the compile.
:class:`PlanBuilder` composes ``ReorderSelector.select_batch`` (device
inference), ``repro.sparse.reorder`` and
``repro.sparse.symbolic.symbolic_cholesky`` into plans, front-ended by the
two-tier :class:`repro.core.plan_cache.TwoTierPlanCache`.
"""
from __future__ import annotations

import dataclasses
import threading
import time
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro.core.plan_cache import PlanCache, matrix_fingerprint
from repro.core.reqctx import RequestContext, span
from repro.sparse.csr import CSRMatrix, permute_symmetric
from repro.sparse.reorder import get_reordering
from repro.sparse.symbolic import SymbolicFactor, symbolic_cholesky

__all__ = ["ExecutionPlan", "PlanBuilder", "execute_plan", "SOLVE_STAGES"]


@dataclasses.dataclass
class ExecutionPlan:
    """Everything structure-determined about solving one sparsity pattern.

    Valid for *any* matrix sharing ``fingerprint`` (values don't enter any
    field), which is what makes the plan cacheable and persistable.

    ``structures`` is transient: the factorization's pattern-only
    structure (:class:`repro.sparse.multifrontal.FactorStructure`: level
    schedule, extend-add plans, compiled programs), one per
    :func:`repro.sparse.multifrontal.structure_key` policy, filled by the
    first :func:`execute_plan` under that policy and read by every later
    one. It is neither pickled (a plan from the disk tier or the RPC
    rebuilds it on first use) nor part of equality or repr.
    """

    fingerprint: str
    algorithm: str              # reordering that produced `perm`
    perm: np.ndarray            # perm[new] = old (repro.sparse.reorder convention)
    sym: SymbolicFactor         # symbolic analysis of the *permuted* pattern
    predicted_flops: int        # factorization cost model: sym.flops
    meta: Dict[str, float] = dataclasses.field(default_factory=dict)
    structures: Dict[tuple, object] = dataclasses.field(
        default_factory=dict, init=False, repr=False, compare=False)

    def __getstate__(self):
        state = self.__dict__.copy()
        del state["structures"]
        return state

    def __setstate__(self, state):
        self.__dict__.update(state)
        self.structures = {}

    @property
    def n(self) -> int:
        return int(self.perm.shape[0])

    @property
    def nnz_L(self) -> int:
        return self.sym.nnz_L

    @property
    def fill(self) -> int:
        return self.sym.fill


class PlanBuilder:
    """select → reorder → symbolic, cache-aware and batch-first.

    ``plan_batch`` is the serving entry point: fingerprints the request,
    answers repeats from the cache (two-tier if the cache persists), runs
    the selector's device path once over the deduplicated misses, and
    builds + installs fresh plans. Counters expose how much work each stage
    actually did, which the tests use to prove a warm hit does *no*
    feature extraction, classification, or symbolic analysis.
    """

    def __init__(self, selector=None, cache: Optional[PlanCache] = None, *,
                 path: str = "device", use_pallas: bool = False,
                 batch_size: int = 16, metrics=None):
        self.selector = selector
        self.cache = cache if cache is not None else PlanCache()
        self.path = path
        self.use_pallas = use_pallas
        self.batch_size = batch_size
        # optional structured-metrics mirror (repro.core.metrics registry):
        # the serving mesh's per-shard utilization lands under `mesh.*`
        self.metrics = metrics
        # stage counters; builds run concurrently in the async server's
        # worker pool, so updates go through _count
        self._stats_lock = threading.Lock()
        self.plans_built = 0
        self.sym_builds = 0
        self.select_calls = 0
        self.select_seconds = 0.0
        self.build_seconds = 0.0

    def _count(self, **deltas) -> None:
        with self._stats_lock:
            for k, d in deltas.items():
                setattr(self, k, getattr(self, k) + d)

    def reset_stats(self) -> None:
        """Zero the stage counters (and the cache's, via its own reset)."""
        with self._stats_lock:
            self.plans_built = self.sym_builds = self.select_calls = 0
            self.select_seconds = self.build_seconds = 0.0
        self.cache.reset_stats()

    # -- single-matrix ------------------------------------------------------
    def build(self, a: CSRMatrix, algorithm: Optional[str] = None,
              fingerprint: Optional[str] = None,
              ctx: Optional[RequestContext] = None) -> ExecutionPlan:
        """Build a plan from scratch (no cache involvement). Each stage
        (``select``, ``reorder``, ``symbolic``) is a :func:`span`, recorded
        into a :class:`RequestContext` when one is given."""
        t_sel = 0.0
        if algorithm is None:
            if self.selector is None:
                raise ValueError("no algorithm given and no selector set")
            with span(ctx, "select"):
                algorithm, t_sel = self.selector.select(a)
            self._count(select_calls=1, select_seconds=t_sel)
        # select_seconds and build_seconds are disjoint stages in reports
        t0 = time.perf_counter()
        with span(ctx, "reorder"):
            perm = get_reordering(algorithm)(a)
        with span(ctx, "symbolic"):
            sym = symbolic_cholesky(permute_symmetric(a, perm))
        dt = time.perf_counter() - t0
        self._count(sym_builds=1, plans_built=1, build_seconds=dt)
        return ExecutionPlan(
            fingerprint or matrix_fingerprint(a), algorithm,
            np.asarray(perm, dtype=np.int64), sym, sym.flops,
            meta=dict(t_build=dt, t_select=t_sel))

    def get_or_build(self, a: CSRMatrix,
                     ctx: Optional[RequestContext] = None
                     ) -> Tuple[ExecutionPlan, bool]:
        """(plan, was_hit) for one matrix through the cache."""
        with span(ctx, "fingerprint"):
            key = matrix_fingerprint(a)
        if ctx is not None:
            ctx.fingerprint = key
        with span(ctx, "cache"):
            plan = self.cache.get(key)
        if plan is not None:
            return plan, True
        plan = self.build(a, fingerprint=key, ctx=ctx)
        self.cache.put(key, plan)
        return plan, False

    # -- batched serving path ------------------------------------------------
    def select_names(self, mats: Sequence[CSRMatrix]) -> List[str]:
        """Device-batched selection in size-tiered chunks of ``batch_size``.

        Partial device chunks are padded to ``batch_size`` (repeating a
        member) so the batch dim stays one jit bucket; filler results are
        dropped.
        """
        if self.selector is None:
            raise ValueError("PlanBuilder has no selector for cache misses")
        order = sorted(range(len(mats)), key=lambda i: (mats[i].nnz,
                                                        mats[i].n))
        names: List[Optional[str]] = [None] * len(mats)
        for lo in range(0, len(order), self.batch_size):
            chunk = order[lo : lo + self.batch_size]
            batch = [mats[i] for i in chunk]
            if self.path == "device":
                batch += [batch[0]] * (self.batch_size - len(chunk))
            got, dt = self.selector.select_batch(
                batch, path=self.path, use_pallas=self.use_pallas)
            self._count(select_calls=1, select_seconds=dt)
            if self.metrics is not None and self.path == "device":
                # per-shard utilization of the serving mesh: how many rows
                # of this jit bucket were live requests vs pad-filler on
                # each shard
                from repro.distributed.meshctx import (
                    get_serving_mesh, record_shard_utilization)

                record_shard_utilization(self.metrics, get_serving_mesh(),
                                         len(chunk), len(batch))
            for i, name in zip(chunk, got):
                names[i] = name
        return names  # type: ignore[return-value]

    def plan_batch(self, mats: Sequence[CSRMatrix]) -> List[ExecutionPlan]:
        """Plans for a request batch; hits skip select+reorder+symbolic."""
        keys = [matrix_fingerprint(m) for m in mats]
        plans: List[Optional[ExecutionPlan]] = [None] * len(mats)
        pending: Dict[str, List[int]] = {}
        for i, key in enumerate(keys):
            hit = self.cache.get(key)
            if hit is not None:
                plans[i] = hit
            else:
                pending.setdefault(key, []).append(i)
        if pending:
            miss_idx = [idxs[0] for idxs in pending.values()]
            names = self.select_names([mats[i] for i in miss_idx])
            for i, name in zip(miss_idx, names):
                plan = self.build(mats[i], algorithm=name,
                                  fingerprint=keys[i])
                self.cache.put(keys[i], plan)
                for j in pending[keys[i]]:
                    plans[j] = plan
        return plans  # type: ignore[return-value]

    def stats(self) -> dict:
        s = self.cache.stats()
        with self._stats_lock:
            s.update(plans_built=self.plans_built,
                     sym_builds=self.sym_builds,
                     select_calls=self.select_calls,
                     select_seconds=self.select_seconds,
                     build_seconds=self.build_seconds)
        return s


#: the solve path's spans (RequestContext keys, trace event names and
#: ``stage.<name>`` histograms, seconds), each with the span it nests in
#: (None at the top level), parents first. ``factor.device`` less
#: ``factor.drain`` is the dispatch of the bucket launches.
SOLVE_STAGES: Dict[str, Optional[str]] = {
    "permute": None,
    "factor": None,
    "factor.schedule": "factor",        # supernodes + level schedule
    "factor.routes": "factor",          # extend-add routes and plans
    "factor.compile_ahead": "factor",   # lower + look up every program
    "factor.assemble": "factor",        # host scatter, drain slicing
    "factor.device": "factor",          # per bucket: dispatch + drain
    "factor.drain": "factor.device",    # the blocking fetch
    "solve": None,
    "solve.sweep": "solve",
    "solve.sweep.setup": "solve.sweep",  # device stacks + compile_ahead
    "solve.refine": "solve",            # fp64 residual + its sync
    "solve.check": None,                # host fp64 residual of the answer
}


def execute_plan(a: CSRMatrix, plan: ExecutionPlan,
                 b: Optional[np.ndarray] = None, *,
                 solver: str = "multifrontal",
                 backend: str = "numpy",
                 solve_dtype: str = "fp64",
                 pad: str = "pow2",
                 bs: Optional[int] = None,
                 sweep: str = "auto",
                 sweep_bs: Optional[int] = None,
                 rt: Optional[int] = None,
                 ctx: Optional[RequestContext] = None,
                 metrics=None) -> dict:
    """Numeric factor + solve of ``A x = b`` driven entirely by the plan.

    The only structure work left is applying the stored permutation; the
    symbolic factor is consumed as-is by the solver (no ``etree`` /
    ``column_counts`` / pattern recomputation — the warm-path guarantee).
    ``backend`` picks the front-math substrate (``numpy`` / per-front
    ``pallas`` / level-scheduled ``batched`` / async ``pipelined``) and
    ``solve_dtype`` the precision mode: ``fp64``, ``fp32``, or
    ``fp32_refine`` (fp32 factorization + fp64 iterative refinement). The
    f32-only device backends auto-promote ``fp64`` to ``fp32_refine`` so
    the residual still reaches the fp64 floor. ``pad``/``bs`` are the
    autotuned bucket/block policy (:mod:`repro.autotune.solve_tuner`);
    both the effective backend/precision and the applied policy are
    recorded in the result dict and in ``plan.meta`` (``solve_bs`` /
    ``solve_pad``) — a cached plan always tells which numeric path and
    policy last produced results from it.

    ``sweep`` picks the triangular-sweep substrate for the solve phase
    (``auto``/``seq``/``level``/``device`` — see
    :func:`repro.sparse.multifrontal.multifrontal_solve`), with
    ``sweep_bs``/``rt`` the device-sweep panel/RHS-tile knobs. The f32
    device sweeps auto-promote ``fp64`` to ``fp32_refine`` exactly like
    the device factor backends, and with ``sweep="device"`` the
    refinement loop itself runs device-resident
    (:func:`repro.sparse.refine.refine_solve_device`) on platforms whose
    :func:`~repro.sparse.refine.residual_path` is ``"device"``. ``b`` may be a
    single RHS ``(n,)`` or a block ``(n, k)``.

    Every stage is a :func:`repro.core.reqctx.span` opened where its work
    runs, here and in the backends, which receive the context: the
    :data:`SOLVE_STAGES` (``factor.schedule`` / ``factor.routes`` /
    ``factor.compile_ahead`` / ``factor.assemble`` / ``factor.device`` >
    ``factor.drain`` on the level-scheduled backends, ``solve.sweep`` >
    ``solve.sweep.setup`` / ``solve.refine`` in the solve) land in
    ``ctx.spans`` and, as annotations, in a device trace. Without a
    ``ctx`` the spans go to a private context. A
    :class:`repro.core.metrics.MetricsRegistry` passed as ``metrics``
    mirrors this call's spans into ``stage.<name>`` histograms and its
    counts into counters (``compile_ahead.programs``: the programs
    ``compile_ahead`` lowered; ``factor.structure.hits`` / ``.misses``:
    factorizations that found or built the plan's structure), and
    records the backend's ``solve.overlap_efficiency`` gauge, the sweep
    substrate (``solve.sweep.<mode>`` counters) and the refinement
    behavior (``solve.refine_iterations`` histogram plus per-count
    ``solve.refine_iters.<i>`` counters, ``solve.refine.residual.<path>``
    for where the fp64 residual ran, ``solve.refine.unconverged`` for a
    loop that stopped above its tolerance). The result dict carries the
    same as ``refine_iterations`` / ``refine_converged`` /
    ``refine_residual`` (``"device"`` or ``"host"``, see
    :func:`repro.sparse.refine.residual_path`).
    """
    assert a.data is not None, "numeric execution needs values"
    if solve_dtype not in ("fp64", "fp32", "fp32_refine"):
        raise ValueError(f"unknown solve_dtype {solve_dtype!r}")
    if sweep not in ("auto", "seq", "level", "device"):
        raise ValueError(f"unknown sweep {sweep!r}")
    if b is None:
        b = np.random.default_rng(0).standard_normal(a.n)
    perm = plan.perm
    own = ctx if isinstance(ctx, RequestContext) else RequestContext.mint()
    spans0, counts0 = dict(own.spans), dict(own.counts)
    with span(own, "permute"):
        pa = permute_symmetric(a, perm)

    refine_info = None
    refine_residual = None
    eff_dtype = solve_dtype
    eff_sweep = sweep
    fstats: dict = {}
    if solver == "multifrontal":
        from repro.sparse.multifrontal import (FactorStructure,
                                               multifrontal_cholesky,
                                               multifrontal_solve,
                                               structure_key)
        if (backend in ("pallas", "batched", "pipelined")
                or sweep == "device") and solve_dtype == "fp64":
            eff_dtype = "fp32_refine"  # f32 factor and/or f32 sweeps
        dtype = np.float64 if eff_dtype == "fp64" else np.float32
        # the plan's structure under this policy: one entry, also when two
        # requests race on a cold plan
        structure = plan.structures.setdefault(
            structure_key(backend, pad, bs), FactorStructure())
        # ctx rides into the numeric phase: the backends open their spans
        # on it, and the level-scheduled ones re-check the deadline at
        # level boundaries and abandon the factorization mid-flight with
        # DeadlineExceeded
        with span(own, "factor"):
            f = multifrontal_cholesky(pa, sym=plan.sym, backend=backend,
                                      dtype=dtype, pad=pad, bs=bs,
                                      ctx=own if ctx is None else ctx,
                                      structure=structure)
        fstats = f.stats
        with span(own, "solve"):
            if eff_sweep == "auto":
                eff_sweep = "seq" if f.schedule is None else "level"
            # hoisted: one permute + fp64 cast of the RHS, outside any
            # refinement loop (the closures below only ever see residuals)
            pb = np.ascontiguousarray(b[perm], dtype=np.float64)
            if eff_dtype == "fp32_refine":
                from repro.sparse.refine import residual_path
                # device sweeps keep the residual on the device where the
                # platform can run the f64 matvec; host sweeps always pair
                # with the host fp64 matvec
                refine_residual = (residual_path() if eff_sweep == "device"
                                   else "host")
            if refine_residual == "device":
                from repro.sparse.refine import refine_solve_device
                z, refine_info = refine_solve_device(
                    pa, f, pb, sweep_bs=sweep_bs, rt=rt, ctx=own)
            elif eff_dtype == "fp32_refine":
                from repro.sparse.refine import refine_solve
                z, refine_info = refine_solve(
                    pa.matvec,
                    lambda r: multifrontal_solve(f, r, mode=eff_sweep,
                                                 sweep_bs=sweep_bs, rt=rt,
                                                 ctx=own),
                    pb, ctx=own)
            else:
                with span(own, "solve.sweep"):
                    z = multifrontal_solve(f, pb, mode=eff_sweep,
                                           sweep_bs=sweep_bs, rt=rt,
                                           ctx=own)
    elif solver == "simplicial":
        from repro.sparse.numeric import cholesky_solve, sparse_cholesky
        eff_dtype = "fp64"  # simplicial path is host fp64 only
        eff_sweep = "seq"
        with span(own, "factor"):
            f = sparse_cholesky(pa, sym=plan.sym)
        with span(own, "solve"), span(own, "solve.sweep"):
            z = cholesky_solve(f, b[perm])
    else:
        raise ValueError(f"unknown solver {solver!r}")
    x = np.empty_like(z)
    x[perm] = z
    with span(own, "solve.check"):
        resid = float(np.linalg.norm(a.matvec(x) - b)
                      / max(np.linalg.norm(b), 1e-30))

    spans = own.spans_since(spans0)
    if metrics is not None:
        # the one place spans and counts reach the registry
        for stage, dt in spans.items():
            metrics.histogram(f"stage.{stage}").observe(dt)
        for name, n in own.counts.items():
            if n != counts0.get(name, 0):
                metrics.counter(name).inc(n - counts0.get(name, 0))
        if "overlap_efficiency" in fstats:
            metrics.gauge("solve.overlap_efficiency").set(
                fstats["overlap_efficiency"])
        metrics.counter("solve.requests").inc()
        metrics.counter(f"solve.sweep.{eff_sweep}").inc()
        if refine_info is not None:
            metrics.histogram("solve.refine_iterations").observe(
                float(refine_info.iterations))
            metrics.counter(
                f"solve.refine_iters.{min(refine_info.iterations, 8)}").inc()
            metrics.counter(f"solve.refine.residual.{refine_residual}").inc()
            if not refine_info.converged:
                metrics.counter("solve.refine.unconverged").inc()
    t_perm, t_fac, t_sol = (spans.get(k, 0.0)
                            for k in ("permute", "factor", "solve"))
    plan.meta["solve_backend"] = backend
    plan.meta["solve_dtype"] = eff_dtype
    plan.meta["solve_bs"] = bs
    plan.meta["solve_pad"] = pad
    plan.meta["solve_sweep"] = eff_sweep
    return dict(x=x, time=t_perm + t_fac + t_sol, t_permute=t_perm,
                t_factor=t_fac, t_solve=t_sol, residual=resid,
                algorithm=plan.algorithm, solver=solver,
                backend=backend, solve_dtype=eff_dtype, bs=bs, pad=pad,
                sweep=eff_sweep, rt=rt,
                overlap_efficiency=fstats.get("overlap_efficiency"),
                refine_iterations=(None if refine_info is None
                                   else refine_info.iterations),
                refine_converged=(None if refine_info is None
                                  else refine_info.converged),
                refine_residual=refine_residual,
                nnz_L=plan.nnz_L, flops=plan.predicted_flops,
                request_id=None if ctx is None else ctx.request_id)
