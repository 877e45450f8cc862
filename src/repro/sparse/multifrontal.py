"""Supernodal multifrontal Cholesky — the MUMPS analogue.

The multifrontal method [Duff & Reid 1983] converts sparse factorization into
a traversal of an assembly tree whose nodes are **dense frontal matrices**.
This is the TPU-native re-think of the paper's solver substrate: the
irregular sparsity is confined to host-side assembly (vectorized
scatter/extend-add index maps), while all heavy FLOPs are dense partial
factorizations of fronts — matmul-shaped work for the MXU. Four backends:

* ``numpy``   — host BLAS, front-at-a-time; used for dataset labeling
                wall-times and as the fp64 correctness reference.
* ``pallas``  — :func:`repro.kernels.ops.frontal_factor` per front (blocked
                right-looking Cholesky over 128-aligned VMEM tiles).
* ``batched`` — **level-scheduled**: fronts are grouped by assembly-tree
                level (:mod:`repro.sparse.schedule`), and every same-shape
                front of a level is partially factored in ONE
                :func:`repro.kernels.ops.frontal_factor_batch_ws` launch
                (grid over the batch dim, fused chol → tri-solve → Schur
                per front, f32 accumulate). nsup host round-trips become
                nlevels × nbuckets kernel calls.
* ``pipelined`` — **device-resident producer/consumer**: the host only ever
                scatters A's entries into fresh workspaces (the cheap,
                irregular part); the extend-add runs on device
                (:func:`repro.kernels.ops.extend_add_batch`), so Schur
                updates never round-trip through numpy between levels.
                Kernel launches are dispatched asynchronously and the host
                races ahead assembling the next level's buckets while the
                previous level factors — the only host↔device sync is one
                drain at the end. ``stats`` records where the wall time
                went (``t_factor_assemble`` / ``t_factor_dispatch`` /
                ``t_factor_sync``, read from the factorization's spans) and
                the resulting ``overlap_efficiency`` (host-busy fraction of
                the overlappable time).

Each stage of a factorization and of the device sweeps is a
:func:`repro.core.reqctx.span` on the caller's request context (or a
private one): ``factor.schedule``, ``factor.routes``,
``factor.compile_ahead``, per level-bucket ``factor.assemble`` and
``factor.device`` > ``factor.drain``, and ``solve.sweep.setup``.

The first three depend on the sparsity pattern alone. A
:class:`FactorStructure` handed to :func:`multifrontal_cholesky` keeps
what they build — the level schedule, the extend-add plans, the fact that
the kernel programs are compiled — so every later factorization of the
same pattern under the same policy only looks them up
(:class:`repro.core.plan.ExecutionPlan` keeps one per policy).

The triangular solves are level-batched too: :func:`multifrontal_solve`
stacks each level's factors into (B, P, P)/(B, R, P) tensors once and runs
batched substitution sweeps per level-bucket. Three sweep modes, all
native multi-RHS (``b`` of shape ``(n,)`` or ``(n, k)``):

* ``seq``    — per-front scipy loop (fp64 reference).
* ``level``  — host sweeps: one ``np.linalg.solve`` + einsum per
               level-bucket, cross-front updates accumulated per *level*
               with one ``np.bincount`` scatter-add.
* ``device`` — the solve-phase counterpart of the pipelined backend:
               per-level factor stacks stay device-resident (reused
               directly from a pipelined factorization's workspaces, no
               drain round-trip), each level-bucket is ONE asynchronously
               dispatched jit step (gather pivots → batched Pallas
               :func:`repro.kernels.ops.tri_solve_batch` → scatter +
               ``L21`` update), and the only host↔device sync is fetching
               the solution at the end. Factors and sweeps run in f32 —
               pair with :func:`repro.sparse.refine.refine_solve_device`
               (x/r stay device-resident too) to reach fp64 residuals.

Per-front cost is exactly the symbolic model of
:func:`repro.sparse.symbolic.cholesky_flops`, so measured label times and the
analytic cost model agree in ordering.
"""
from __future__ import annotations

import dataclasses
import threading
import time
from typing import List, Literal, Optional, Tuple

import numpy as np
import scipy.linalg as sla

from .csr import CSRMatrix
from .schedule import FrontPlan, LevelSchedule, build_schedule
from .symbolic import SymbolicFactor, supernodes, symbolic_cholesky

__all__ = ["MultifrontalFactor", "FactorStructure", "structure_key",
           "multifrontal_cholesky", "multifrontal_solve",
           "factor_and_solve_timed"]

Backend = Literal["numpy", "pallas", "batched", "pipelined"]

#: backends that factor fronts in f32 on device
DEVICE_BACKENDS = ("pallas", "batched", "pipelined")

#: supernode amalgamation of the numeric phase (``relax`` by default)
RELAX = 8


@dataclasses.dataclass
class FactorStructure:
    """The pattern-only part of a factorization under one policy.

    ``schedule`` (supernodes + level schedule) and its ``stats`` for every
    backend; for ``pipelined`` also the extend-add plans (``ea_plans``, one
    per destination bucket) and whether the schedule's kernel programs are
    compiled (``compiled``). The first factorization handed an empty
    structure fills it; every later one reads it. A concurrent first use
    waits on ``lock`` for the build instead of repeating it. Holds no
    coefficient, so it is valid for every matrix of the pattern; it lives
    in the process only (compiled programs do not travel).
    """

    schedule: Optional[LevelSchedule] = None
    stats: Optional[dict] = None
    ea_plans: Optional[dict] = None
    compiled: bool = False
    lock: threading.Lock = dataclasses.field(
        default_factory=threading.Lock, repr=False, compare=False)


def structure_key(backend: str, pad: str, bs: Optional[int],
                  relax: int = RELAX) -> tuple:
    """The inputs a :class:`FactorStructure` is built from: ``relax`` and
    ``pad`` shape the schedule, ``bs`` the compiled programs, and the
    backend family what is kept (only ``pipelined`` routes on the device;
    every other backend keeps the schedule alone)."""
    return (relax, pad, bs,
            "pipelined" if backend == "pipelined" else "schedule")


def _check_deadline(ctx, stage: str) -> None:
    """Deadline checkpoint at a level boundary of the numeric phase: a
    request whose :class:`repro.core.reqctx.RequestContext` deadline has
    passed raises :class:`DeadlineExceeded` *mid-factorization* instead of
    burning the remaining levels on an answer nobody is waiting for.
    ``ctx`` is duck-typed (anything with ``expired()``/``remaining()``);
    the import is lazy to keep this module free of a core dependency."""
    if ctx is None or not ctx.expired():
        return
    from repro.core.reqctx import DeadlineExceeded

    late_ms = -(ctx.remaining() or 0.0) * 1e3
    raise DeadlineExceeded(
        f"deadline exceeded {late_ms:.1f} ms ago at {stage} — "
        f"factorization abandoned")


@dataclasses.dataclass
class _Front:
    cols: Tuple[int, int]    # [c0, c1) pivot columns
    rows: np.ndarray         # global row indices of the front (sorted; first npiv are pivots)
    L11: np.ndarray          # (npiv, npiv) lower-triangular
    L21: np.ndarray          # (m - npiv, npiv)


@dataclasses.dataclass
class MultifrontalFactor:
    n: int
    fronts: List[_Front]
    sym: SymbolicFactor
    stats: dict
    schedule: Optional[LevelSchedule] = None
    dtype: np.dtype = np.float64
    _sweeps: Optional["_LevelSweeps"] = dataclasses.field(
        default=None, repr=False, compare=False)
    # pipelined backend: the factored per-(level, bucket) workspace stacks,
    # kept device-resident so sweep="device" reads L11/L21 straight from
    # them instead of re-uploading drained host fronts
    _device_stacks: Optional[dict] = dataclasses.field(
        default=None, repr=False, compare=False)
    _dev_sweeps: Optional["_DeviceSweeps"] = dataclasses.field(
        default=None, repr=False, compare=False)


# ---------------------------------------------------------------------------
# Host-side assembly: vectorized scatter + extend-add
# ---------------------------------------------------------------------------

def _scatter_entries(F: np.ndarray, a: CSRMatrix, fp: FrontPlan,
                     shift: int = 0) -> None:
    """Scatter A[rows, c0:c1] (lower triangle, via symmetry of the CSR rows)
    into the front workspace in one vectorized pass: global row indices map
    to local positions by ``np.searchsorted`` over the sorted front rows.
    ``shift`` displaces non-pivot rows by the pivot-padding width (the
    batched workspace layout); 0 means the dense unpadded front."""
    indptr, indices, data = a.indptr, a.indices, a.data
    c0, c1 = fp.c0, fp.c1
    start, end = int(indptr[c0]), int(indptr[c1])
    cols = indices[start:end]
    vals = data[start:end]
    colid = np.repeat(np.arange(c0, c1), np.diff(indptr[c0 : c1 + 1]))
    sel = cols >= colid            # keep the lower triangle (row ≥ col)
    loc = np.searchsorted(fp.rows, cols[sel])
    if shift:
        loc = np.where(loc >= fp.npiv, loc + shift, loc)
    F[loc, colid[sel] - c0] = vals[sel]


def _extend_add(F: np.ndarray, fp: FrontPlan, urows: np.ndarray,
                U: np.ndarray, shift: int = 0) -> None:
    """Add a child's Schur update (rows `urows`) into the front workspace."""
    idx = np.searchsorted(fp.rows, urows)
    if idx.size and (idx[-1] >= fp.rows.size
                     or not np.array_equal(fp.rows[idx], urows)):
        raise RuntimeError(
            "assembly-tree containment violated (supernode "
            f"{fp.k}: update rows not a subset of front rows)")
    if shift:
        idx = np.where(idx >= fp.npiv, idx + shift, idx)
    F[np.ix_(idx, idx)] += U


# ---------------------------------------------------------------------------
# Dense partial factorization backends (front-at-a-time)
# ---------------------------------------------------------------------------

def _partial_factor_numpy(F: np.ndarray, npiv: int):
    """Dense partial Cholesky: factor pivot block, panel solve, Schur update."""
    F11 = F[:npiv, :npiv]
    L11 = np.linalg.cholesky(F11)
    if F.shape[0] > npiv:
        L21 = sla.solve_triangular(L11, F[npiv:, :npiv].T, lower=True,
                                   trans="N").T
        S = F[npiv:, npiv:] - L21 @ L21.T
    else:
        L21 = np.empty((0, npiv), dtype=F.dtype)
        S = np.empty((0, 0), dtype=F.dtype)
    return L11, L21, S


def _partial_factor_pallas(F: np.ndarray, npiv: int):
    from repro.kernels import ops  # local import: keep numpy path jax-free
    L11, L21, S = ops.frontal_factor(F, npiv)
    return np.asarray(L11), np.asarray(L21), np.asarray(S)


# ---------------------------------------------------------------------------
# Numeric phase
# ---------------------------------------------------------------------------

def multifrontal_cholesky(
    a: CSRMatrix,
    sym: Optional[SymbolicFactor] = None,
    relax: int = RELAX,
    backend: Backend = "numpy",
    dtype: np.dtype | type = np.float64,
    pad: str = "pow2",
    bs: Optional[int] = None,
    ctx=None,
    structure: Optional[FactorStructure] = None,
) -> MultifrontalFactor:
    """Numeric supernodal factorization of an SPD CSR matrix.

    ``dtype`` selects the front-math precision on the ``numpy`` backend
    (fp64 or fp32); the device backends always accumulate in f32 (pair them
    with :mod:`repro.sparse.refine` to recover fp64-level residuals).
    ``pad`` and ``bs`` are the autotuned kernel-policy knobs: the bucket
    pad policy of the level schedule (``"pow2"`` / ``"mult8"``) and the
    panel block-size cap of the batched kernels (None → 32). The returned
    factor carries the :class:`LevelSchedule` used, so
    :func:`multifrontal_solve` can run level-batched sweeps.

    ``ctx`` is an optional :class:`repro.core.reqctx.RequestContext`: the
    factorization's stage spans are recorded into it (into a private one
    when it is None or only a deadline; the level-scheduled backends'
    ``t_factor_*`` stats are read from them either way), and the
    level-scheduled backends re-check its deadline at every assembly-tree
    level boundary and abandon the factorization with
    :class:`~repro.core.reqctx.DeadlineExceeded` once it has passed —
    serving-path deadline discipline extends into the numeric solve
    instead of stopping at plan build.

    ``structure`` is the :class:`FactorStructure` of ``sym`` under
    :func:`structure_key` of this call's ``backend``/``pad``/``bs``/
    ``relax``: an empty one is filled, a filled one is read, and its
    spans then time the look-ups (``factor.schedule`` carries the stat
    ``cached``, ``factor.compile_ahead`` ``programs=0``). It adds
    ``factor.structure.hits`` or ``factor.structure.misses`` to the
    context's counts. None builds everything for this call alone.
    """
    from repro.core.reqctx import RequestContext, span

    assert a.data is not None, "numeric factorization needs values"
    rec = ctx if isinstance(ctx, RequestContext) else RequestContext.mint()
    spans0 = dict(rec.spans)
    if sym is None:
        sym = symbolic_cholesky(a)
    st = FactorStructure() if structure is None else structure
    with st.lock:
        hit = st.schedule is not None
        if structure is not None:
            rec.add_count("factor.structure."
                          + ("hits" if hit else "misses"), 1)
        with span(rec, "factor.schedule", cached=int(hit)):
            if not hit:
                snode_ptr, snode_of = supernodes(sym, relax=relax)
                schedule = build_schedule(sym, snode_ptr, snode_of, pad=pad)
                st.stats = schedule.stats()
                st.schedule = schedule
        _check_deadline(ctx, "factorization start")
        if backend == "pipelined":
            _pipelined_structure(st, rec, bs)
    schedule = st.schedule
    eff_dtype = np.dtype(np.float32 if backend in DEVICE_BACKENDS else dtype)

    timings: dict = {}
    device_stacks = None
    if backend in ("batched", "pipelined"):
        if backend == "batched":
            fronts = _factor_batched(a, schedule, rec, bs=bs, ctx=ctx)
        else:
            fronts, device_stacks = _factor_pipelined(
                a, schedule, st.ea_plans, rec, bs=bs, ctx=ctx)
        d = rec.spans_since(spans0)
        t_drain = d.get("factor.drain", 0.0)
        timings = _overlap_timings(d.get("factor.assemble", 0.0),
                                   d.get("factor.device", 0.0) - t_drain,
                                   t_drain)
    else:
        fronts = _factor_sequential(a, schedule, backend, eff_dtype)

    stats = dict(st.stats)  # nsup, nlevels, widths, occupancy, flops
    stats.update(n=a.n,
                 peak_front=max((fp.m for fp in schedule.fronts), default=0),
                 nnz_L=sym.nnz_L, fill=sym.fill, sym_flops=sym.flops,
                 backend=backend, dtype=str(eff_dtype), bs=bs, **timings)
    return MultifrontalFactor(a.n, fronts, sym, stats, schedule=schedule,
                              dtype=eff_dtype,
                              _device_stacks=device_stacks)


def _factor_sequential(a: CSRMatrix, schedule: LevelSchedule,
                       backend: Backend, dtype: np.dtype) -> List[_Front]:
    """Front-at-a-time postorder traversal (numpy / per-front pallas)."""
    partial = (_partial_factor_numpy if backend == "numpy"
               else _partial_factor_pallas)
    nsup = schedule.nsup
    fronts: List[_Front] = []
    pending: List[List[Tuple[np.ndarray, np.ndarray]]] = [[] for _ in range(nsup)]
    for fp in schedule.fronts:
        F = np.zeros((fp.m, fp.m), dtype=dtype)
        _scatter_entries(F, a, fp)
        for (urows, U) in pending[fp.k]:
            _extend_add(F, fp, urows, U)
        pending[fp.k] = []
        L11, L21, S = partial(F, fp.npiv)
        fronts.append(_Front((fp.c0, fp.c1), fp.rows, L11, L21))
        if fp.nrest:
            pending[fp.parent].append((fp.rows[fp.npiv :], S))
    return fronts


def _overlap_timings(t_assemble: float, t_dispatch: float,
                     t_sync: float) -> dict:
    """Solve-stage timing record shared by the batched/pipelined backends.

    ``overlap_efficiency`` is the host-busy fraction of the overlappable
    time — assembly seconds over assembly + device-blocked seconds. A
    backend that hides its device waits under host assembly (the pipelined
    producer/consumer loop) pushes it toward 1; a backend that blocks on
    every kernel call (batched) is bounded by how its per-bucket assembly
    and kernel times happen to interleave.
    """
    denom = t_assemble + t_sync
    return dict(t_factor_assemble=t_assemble, t_factor_dispatch=t_dispatch,
                t_factor_sync=t_sync,
                overlap_efficiency=(t_assemble / denom) if denom > 0 else 1.0)


def _assemble_bucket(a: CSRMatrix, schedule: LevelSchedule,
                     bucket) -> np.ndarray:
    """Host side of one bucket's assembly: fresh padded f32 workspace stack
    with identity pivot-pad columns and A's entries scattered in. Pivot
    padding columns are decoupled identity columns; update-row padding is
    zero rows — both factor trivially and contribute nothing to L or the
    Schur complements."""
    B, P, M = len(bucket.members), bucket.P, bucket.M
    W = np.zeros((B, M, M), dtype=np.float32)
    for bi, k in enumerate(bucket.members):
        fp = schedule.fronts[k]
        shift = P - fp.npiv
        if shift:
            pad = np.arange(fp.npiv, P)
            W[bi, pad, pad] = 1.0
        _scatter_entries(W[bi], a, fp, shift)
    return W


def _factor_batched(a: CSRMatrix, schedule: LevelSchedule, rec,
                    bs: Optional[int] = None, ctx=None) -> List[_Front]:
    """Level-scheduled factorization: per (level, bucket), assemble every
    member front into one padded f32 workspace stack and factor the stack
    in a single batched kernel launch. Extend-add runs on the host (numpy
    scatter into the next level's workspaces) and every kernel call is a
    blocking round trip — the ``pipelined`` backend removes both — so
    each bucket's ``factor.device`` span is all ``factor.drain``. Spans go
    to ``rec``; ``ctx`` holds the deadline."""
    from repro.core.reqctx import span
    from repro.kernels import ops

    nsup = schedule.nsup
    fronts: List[Optional[_Front]] = [None] * nsup
    pending: List[List[Tuple[np.ndarray, np.ndarray]]] = [[] for _ in range(nsup)]
    for li in range(schedule.nlevels):
        _check_deadline(ctx, f"batched level {li}/{schedule.nlevels}")
        for bucket in schedule.buckets[li]:
            P = bucket.P
            with span(rec, "factor.assemble"):
                W = _assemble_bucket(a, schedule, bucket)
                for bi, k in enumerate(bucket.members):
                    fp = schedule.fronts[k]
                    shift = P - fp.npiv
                    for (urows, U) in pending[k]:
                        _extend_add(W[bi], fp, urows, U, shift)
                    pending[k] = []
            with span(rec, "factor.device"), span(rec, "factor.drain"):
                Wf = np.asarray(ops.frontal_factor_batch_ws(W, P, bs=bs))
            with span(rec, "factor.assemble"):
                for bi, k in enumerate(bucket.members):
                    fp = schedule.fronts[k]
                    npiv, nrest = fp.npiv, fp.nrest
                    L11 = np.tril(Wf[bi, :npiv, :npiv])
                    L21 = Wf[bi, P : P + nrest, :npiv]
                    fronts[k] = _Front((fp.c0, fp.c1), fp.rows, L11, L21)
                    if nrest:
                        S = Wf[bi, P : P + nrest, P : P + nrest]
                        pending[fp.parent].append((fp.rows[npiv:], S))
    return fronts  # type: ignore[return-value]


def _route_contributions(schedule: LevelSchedule) -> dict:
    """Precompute the device extend-add routing from the schedule alone.

    Returns ``{(dst_level, dst_bucket): {(src_level, src_bucket):
    [(src_slot, dst_slot, rowmap), ...]}}`` where ``rowmap`` maps the
    source bucket's (padded) update rows to local positions in the padded
    destination workspace (−1 = inactive pad row). Grouping by source
    bucket makes every group one uniform-shape kernel launch.
    """
    loc = {}
    for li in range(schedule.nlevels):
        for bj, bucket in enumerate(schedule.buckets[li]):
            for bi, k in enumerate(bucket.members):
                loc[k] = (li, bj, bi)
    routes: dict = {}
    for fp in schedule.fronts:
        if fp.parent < 0 or fp.nrest == 0:
            continue
        sli, sbj, sbi = loc[fp.k]
        dli, dbj, dbi = loc[fp.parent]
        pfp = schedule.fronts[fp.parent]
        urows = fp.rows[fp.npiv :]
        idx = np.searchsorted(pfp.rows, urows)
        if idx.size and (idx[-1] >= pfp.rows.size
                         or not np.array_equal(pfp.rows[idx], urows)):
            raise RuntimeError(
                "assembly-tree containment violated (supernode "
                f"{fp.k}: update rows not a subset of front rows)")
        shift = schedule.buckets[dli][dbj].P - pfp.npiv
        if shift:
            idx = np.where(idx >= pfp.npiv, idx + shift, idx)
        rowmap = np.full(schedule.buckets[sli][sbj].R, -1, dtype=np.int32)
        rowmap[: fp.nrest] = idx
        (routes.setdefault((dli, dbj), {})
               .setdefault((sli, sbj), []).append((sbi, dbi, rowmap)))
    return routes


def _pad_pow2(n: int) -> int:
    return 1 << (int(n) - 1).bit_length() if n > 1 else 1


@dataclasses.dataclass
class _ExtendAddPlan:
    """One destination bucket's extend-add launch, from the schedule
    alone: every child contribution, from every source bucket, merged in
    destination-slot order (the kernel's sequential accumulation
    contract), updates padded to the largest source ``R`` — so the launch
    (and its compile) is per destination bucket, not per (source,
    destination) pair."""

    sources: List[Tuple[int, int]]   # (level, bucket) of each source stack
    src_ids: List[np.ndarray]        # contributing slots per source, int32
    offsets: Tuple[int, ...]         # each source's pivot dim P
    order: np.ndarray                # (Cp,) concatenation → dst order
    dst: np.ndarray                  # (Cp,) destination slots, ascending
    rows: np.ndarray                 # (Cp, rmax) row maps, −1 inactive
    rmax: int


def _extend_add_plan(schedule: LevelSchedule, srcs) -> _ExtendAddPlan:
    """``srcs``: the sorted ``[((src_level, src_bucket), contribs), ...]``
    of :func:`_route_contributions` for one destination bucket."""
    rmax = max(schedule.buckets[sli][sbj].R for (sli, sbj), _ in srcs)
    src_ids, dst, rows = [], [], []
    for _, contribs in srcs:
        src_ids.append(np.array([c[0] for c in contribs], np.int32))
        for _, d, rowmap in contribs:
            dst.append(d)
            rows.append(np.pad(rowmap, (0, rmax - rowmap.size),
                               constant_values=-1))
    order = np.argsort(np.asarray(dst), kind="stable").astype(np.int32)
    dst = np.asarray(dst, np.int32)[order]
    rows = np.stack(rows)[order]
    # pad the contribution count to a power of two so jit shapes stay
    # bounded; pads are inert (rowmap −1 ⇒ all-zero one-hot ⇒ zero
    # contribution) and keep dst sorted
    C, Cp = len(order), _pad_pow2(len(order))
    if Cp != C:
        order = np.concatenate([order, np.zeros(Cp - C, np.int32)])
        dst = np.concatenate([dst, np.full(Cp - C, dst[-1], np.int32)])
        rows = np.concatenate([rows, np.full((Cp - C, rmax), -1, np.int32)])
    return _ExtendAddPlan(
        [key for key, _ in srcs], src_ids,
        tuple(schedule.buckets[sli][sbj].P for (sli, sbj), _ in srcs),
        order, dst, rows, rmax)


def _pipelined_calls(ops, schedule: LevelSchedule, ea_plans: dict,
                     bs: Optional[int]) -> list:
    """Every kernel program :func:`_factor_pipelined` will run, as
    ShapeDtypeStruct calls for :func:`repro.kernels.ops.compile_ahead`."""
    def stack(li, bj):
        b = schedule.buckets[li][bj]
        return ops.f32_spec((len(b.members), b.M, b.M))

    calls = []
    for li in range(schedule.nlevels):
        for bj, bucket in enumerate(schedule.buckets[li]):
            w = stack(li, bj)
            ea = ea_plans.get((li, bj))
            if ea is not None:
                calls.append(ops.extend_add_stacks_call(
                    w, [stack(*k) for k in ea.sources], ea.src_ids,
                    ea.order, ea.dst, ea.rows, offsets=ea.offsets,
                    rmax=ea.rmax))
            calls.append(ops.factor_call(w, bucket.P, bs))
    return calls


def _pipelined_structure(st: FactorStructure, rec,
                         bs: Optional[int]) -> None:
    """Fill in the pipelined backend's part of ``st`` where it is missing:
    the extend-add plans of ``st.schedule`` (span ``factor.routes``) and,
    compiled concurrently before the first dispatch
    (:func:`repro.kernels.ops.compile_ahead`), every kernel program it
    runs (span ``factor.compile_ahead``, the number of programs lowered as
    its ``programs`` stat and in ``rec.counts["compile_ahead.programs"]``;
    0 once ``st.compiled``)."""
    from repro.core.reqctx import span
    from repro.kernels import ops

    with span(rec, "factor.routes"):
        if st.ea_plans is None:
            st.ea_plans = {
                key: _extend_add_plan(st.schedule, sorted(srcs.items()))
                for key, srcs in _route_contributions(st.schedule).items()}
    with span(rec, "factor.compile_ahead") as sp:
        calls = ([] if st.compiled
                 else _pipelined_calls(ops, st.schedule, st.ea_plans, bs))
        ops.compile_ahead(calls)
        st.compiled = True
        sp.set_metadata(programs=len(calls))
    rec.add_count("compile_ahead.programs", len(calls))


def _factor_pipelined(a: CSRMatrix, schedule: LevelSchedule, ea_plans: dict,
                      rec, bs: Optional[int] = None, ctx=None
                      ) -> Tuple[List[_Front], dict]:
    """Pipelined device-resident factorization.

    Producer/consumer split: the host's only numeric work is scattering A's
    entries into fresh bucket workspaces (sparse, cheap); the extend-add
    and the partial factorization both run on device, dispatched
    asynchronously. JAX's async dispatch queues the level-*k* kernels and
    returns immediately, so the host assembles level *k+1* while the device
    factors level *k* — host work hides under kernel time. Schur updates
    stay device-resident between levels (each factored bucket stack is kept
    on device until its members' parents have consumed it via
    :func:`repro.kernels.ops.extend_add_batch`); the single blocking sync
    is the drain at the end that fetches the factored stacks for the
    host-side triangular sweeps. The factored device stacks are *also*
    returned (second element) and retained on the factor: ``sweep="device"``
    slices L11/L21 straight out of them, so device sweeps never re-upload
    the factors the drain just pulled down. ``ea_plans`` and the compiled
    programs come from :func:`_pipelined_structure`. Spans go to ``rec``;
    ``ctx`` holds the deadline.
    """
    import jax.numpy as jnp

    from repro.core.reqctx import span
    from repro.kernels import ops

    nsup = schedule.nsup
    fronts: List[Optional[_Front]] = [None] * nsup
    dev: dict = {}             # (level, bucket) -> factored device stack
    for li in range(schedule.nlevels):
        _check_deadline(ctx, f"pipelined dispatch level "
                             f"{li}/{schedule.nlevels}")
        for bj, bucket in enumerate(schedule.buckets[li]):
            with span(rec, "factor.assemble"):
                W = _assemble_bucket(a, schedule, bucket)
            with span(rec, "factor.device"):
                w = jnp.asarray(W)
                ea = ea_plans.get((li, bj))
                if ea is not None:
                    w = ops.extend_add_stacks(
                        w, [dev[k] for k in ea.sources], ea.src_ids,
                        ea.order, ea.dst, ea.rows, offsets=ea.offsets,
                        rmax=ea.rmax)
                dev[(li, bj)] = ops.frontal_factor_batch_ws(w, bucket.P,
                                                            bs=bs)
    # drain: the only host↔device sync — by now the host has assembled and
    # dispatched every level, so this wait is whatever device work is left
    for li in range(schedule.nlevels):
        _check_deadline(ctx, f"pipelined drain level "
                             f"{li}/{schedule.nlevels}")
        for bj, bucket in enumerate(schedule.buckets[li]):
            with span(rec, "factor.device"), span(rec, "factor.drain"):
                Wf = np.asarray(dev[(li, bj)])
            with span(rec, "factor.assemble"):
                P = bucket.P
                for bi, k in enumerate(bucket.members):
                    fp = schedule.fronts[k]
                    L11 = np.tril(Wf[bi, : fp.npiv, : fp.npiv])
                    L21 = Wf[bi, P : P + fp.nrest, : fp.npiv]
                    fronts[k] = _Front((fp.c0, fp.c1), fp.rows, L11, L21)
    return fronts, dev  # type: ignore[return-value]


# ---------------------------------------------------------------------------
# Triangular sweeps
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class _SweepGroup:
    """One level-bucket's factors stacked for batched substitution."""

    L11: np.ndarray        # (B, P, P) unit-diag padded, fp64
    L11T: np.ndarray       # (B, P, P) transposed copy (backward sweep)
    L21: np.ndarray        # (B, R, P)
    piv: np.ndarray        # (B, P) global pivot indices (0 at pads)
    pmask: np.ndarray      # (B, P) bool, True at real pivots
    rest: np.ndarray       # (B, R) global update rows (0 at pads)
    rmask: np.ndarray      # (B, R) bool


@dataclasses.dataclass
class _LevelSweeps:
    levels: List[List[_SweepGroup]]


def _build_sweeps(f: MultifrontalFactor) -> _LevelSweeps:
    sched = f.schedule
    assert sched is not None
    levels: List[List[_SweepGroup]] = []
    for li in range(sched.nlevels):
        groups: List[_SweepGroup] = []
        for bucket in sched.buckets[li]:
            B, P, R = len(bucket.members), bucket.P, bucket.R
            L11 = np.zeros((B, P, P))
            diag = np.arange(P)
            L11[:, diag, diag] = 1.0
            L21 = np.zeros((B, R, P))
            piv = np.zeros((B, P), dtype=np.int64)
            pmask = np.zeros((B, P), dtype=bool)
            rest = np.zeros((B, R), dtype=np.int64)
            rmask = np.zeros((B, R), dtype=bool)
            for bi, k in enumerate(bucket.members):
                fr = f.fronts[k]
                c0, c1 = fr.cols
                npiv = c1 - c0
                nrest = fr.L21.shape[0]
                L11[bi, :npiv, :npiv] = fr.L11
                L21[bi, :nrest, :npiv] = fr.L21
                piv[bi, :npiv] = np.arange(c0, c1)
                pmask[bi, :npiv] = True
                rest[bi, :nrest] = fr.rows[npiv:]
                rmask[bi, :nrest] = True
            groups.append(_SweepGroup(
                L11, np.ascontiguousarray(L11.transpose(0, 2, 1)), L21,
                piv, pmask, rest, rmask))
        levels.append(groups)
    return _LevelSweeps(levels)


def _solve_level(f: MultifrontalFactor, x: np.ndarray) -> None:
    """Level-batched forward/backward sweeps, in place on the (n, k) fp64
    RHS block: one batched triangular solve (``np.linalg.solve`` on the
    stacked unit-padded factors) plus one batched update einsum per
    level-bucket, instead of a scipy call per front. Update scatters within
    a level never collide with that level's pivots (parents live on
    strictly higher levels), so bucket order is free and every bucket's
    cross-front updates are deferred and applied in ONE ``np.bincount``
    scatter-add per level (a dense accumulate, much faster than the
    element-at-a-time ``np.subtract.at``)."""
    if f._sweeps is None:
        f._sweeps = _build_sweeps(f)
    sw = f._sweeps
    n, k = x.shape
    colk = np.arange(k)
    # forward: L y = b, leaves upward
    for groups in sw.levels:
        acc_idx: List[np.ndarray] = []
        acc_upd: List[np.ndarray] = []
        for g in groups:
            xb = np.where(g.pmask[..., None], x[g.piv], 0.0)
            y = np.linalg.solve(g.L11, xb)
            x[g.piv[g.pmask]] = y[g.pmask]
            if g.rest.shape[1]:
                upd = np.einsum("brp,bpk->brk", g.L21, y)
                acc_idx.append(g.rest[g.rmask])
                acc_upd.append(upd[g.rmask])
        if acc_idx:
            idx = np.concatenate(acc_idx)
            upd = np.concatenate(acc_upd)
            flat = (idx[:, None] * k + colk).ravel()
            x -= np.bincount(flat, weights=upd.ravel(),
                             minlength=n * k).reshape(n, k)
    # backward: Lᵀ x = y, roots downward
    for groups in reversed(sw.levels):
        for g in groups:
            rhs = np.where(g.pmask[..., None], x[g.piv], 0.0)
            if g.rest.shape[1]:
                xr = np.where(g.rmask[..., None], x[g.rest], 0.0)
                rhs = rhs - np.einsum("brp,brk->bpk", g.L21, xr)
            y = np.linalg.solve(g.L11T, rhs)
            x[g.piv[g.pmask]] = y[g.pmask]


def _solve_sequential(f: MultifrontalFactor, x: np.ndarray) -> None:
    """Per-front scipy sweeps, in place on the (n, k) fp64 RHS block (the
    pre-level-scheduling reference path)."""
    # forward: L y = b
    for fr in f.fronts:
        c0, c1 = fr.cols
        piv = slice(c0, c1)
        y = sla.solve_triangular(fr.L11, x[piv], lower=True)
        x[piv] = y
        if fr.L21.shape[0]:
            x[fr.rows[c1 - c0 :]] -= fr.L21 @ y
    # backward: Lᵀ x = y
    for fr in reversed(f.fronts):
        c0, c1 = fr.cols
        piv = slice(c0, c1)
        rhs = x[piv]
        if fr.L21.shape[0]:
            rhs = rhs - fr.L21.T @ x[fr.rows[c1 - c0 :]]
        x[piv] = sla.solve_triangular(fr.L11.T, rhs, lower=False)


# -- device-resident sweeps --------------------------------------------------

@dataclasses.dataclass
class _DeviceSweepGroup:
    """One level-bucket's factors as device arrays for batched Pallas
    substitution. Indices are int32 with every pad slot pointing at the
    trash row ``n`` of the (n + 1, K) RHS block — no masks needed on
    device: identity pad rows in L11 and zero pad rows/cols in L21 keep
    whatever garbage the trash row holds out of every real entry."""

    W: object              # (B, P + R, P + R) f32 device: L11 over L21
    piv: object            # (B, P) int32 device, pads -> n
    rest: object           # (B, R) int32 device, pads -> n


@dataclasses.dataclass
class _DeviceSweeps:
    levels: List[List[_DeviceSweepGroup]]
    # (K, sweep_bs, rt) settings whose sweep programs are compiled
    compiled: set = dataclasses.field(default_factory=set)


def _bucket_indices(sched: LevelSchedule, bucket, n: int
                    ) -> Tuple[np.ndarray, np.ndarray]:
    """(B, P) pivot and (B, R) update-row index stacks for one bucket,
    pads pointed at the trash row ``n``. Built from the schedule alone —
    no drained host fronts needed."""
    B, P, R = len(bucket.members), bucket.P, bucket.R
    piv = np.full((B, P), n, dtype=np.int32)
    rest = np.full((B, R), n, dtype=np.int32)
    for bi, k in enumerate(bucket.members):
        fp = sched.fronts[k]
        piv[bi, : fp.npiv] = np.arange(fp.c0, fp.c1, dtype=np.int32)
        rest[bi, : fp.nrest] = fp.rows[fp.npiv :]
    return piv, rest


def _build_device_sweeps(f: MultifrontalFactor) -> _DeviceSweeps:
    """Stack each level-bucket's factors as device arrays.

    After a ``pipelined`` factorization the factored workspace stacks are
    still device-resident (``f._device_stacks``) and already in the padded
    bucket layout — the sweeps read L11/L21 straight out of them (the
    identity pivot pads factored to unit-diagonal rows, update-row pads to
    zero rows, exactly the inert padding the sweeps need). Any other
    backend packs its host fronts into the same layout and uploads them
    once; repeated solves reuse the cached stacks.
    """
    import jax.numpy as jnp

    sched = f.schedule
    assert sched is not None
    if f._device_stacks is None and f._sweeps is None:
        f._sweeps = _build_sweeps(f)
    levels: List[List[_DeviceSweepGroup]] = []
    for li in range(sched.nlevels):
        groups: List[_DeviceSweepGroup] = []
        for bj, bucket in enumerate(sched.buckets[li]):
            if f._device_stacks is not None:
                W = f._device_stacks[(li, bj)]
            else:
                g = f._sweeps.levels[li][bj]
                P = bucket.P
                Wh = np.zeros((len(bucket.members), bucket.M, bucket.M),
                              np.float32)
                Wh[:, :P, :P] = g.L11
                Wh[:, P:, :P] = g.L21
                W = jnp.asarray(Wh)
            piv, rest = _bucket_indices(sched, bucket, f.n)
            groups.append(_DeviceSweepGroup(W, jnp.asarray(piv),
                                            jnp.asarray(rest)))
        levels.append(groups)
    return _DeviceSweeps(levels)


def _device_sweep_passes(f: MultifrontalFactor, x, *,
                         sweep_bs: Optional[int] = None,
                         rt: Optional[int] = None, ctx=None):
    """Forward + backward substitution on a device-resident (n + 1, K) f32
    RHS block. One asynchronously dispatched jit step per level-bucket; no
    host sync anywhere — callers decide when to pull the result. The first
    pass at a RHS width stacks the factors and compiles the sweep programs
    under the span ``solve.sweep.setup`` (stat ``programs``; the count
    also goes to ``ctx.counts["compile_ahead.programs"]``)."""
    from repro.core.reqctx import span
    from repro.kernels import ops

    key = (x.shape[1], sweep_bs, rt)
    if f._dev_sweeps is None or key not in f._dev_sweeps.compiled:
        with span(ctx, "solve.sweep.setup") as sp:
            if f._dev_sweeps is None:
                f._dev_sweeps = _build_device_sweeps(f)
            calls = [c for groups in f._dev_sweeps.levels for g in groups
                     for c in ops.sweep_calls(x, g.W, g.piv, g.rest,
                                              bs=sweep_bs, rt=rt)]
            ops.compile_ahead(calls)
            f._dev_sweeps.compiled.add(key)
            sp.set_metadata(programs=len(calls))
        if ctx is not None:
            ctx.add_count("compile_ahead.programs", len(calls))
    sw = f._dev_sweeps
    for groups in sw.levels:
        for g in groups:
            x = ops.sweep_forward(x, g.W, g.piv, g.rest, bs=sweep_bs,
                                  rt=rt)
    for groups in reversed(sw.levels):
        for g in groups:
            x = ops.sweep_backward(x, g.W, g.piv, g.rest, bs=sweep_bs,
                                   rt=rt)
    return x


def _solve_device(f: MultifrontalFactor, b2: np.ndarray, *,
                  sweep_bs: Optional[int] = None,
                  rt: Optional[int] = None, ctx=None) -> np.ndarray:
    """Device-resident sweeps for an (n, k) RHS block: upload once, one
    async dispatch per level-bucket, one sync to fetch the solution."""
    import jax.numpy as jnp

    from repro.kernels.ops import rhs_width

    n, k = b2.shape
    xb = np.zeros((n + 1, rhs_width(k)), dtype=np.float32)
    xb[:n, :k] = b2
    x = _device_sweep_passes(f, jnp.asarray(xb), sweep_bs=sweep_bs, rt=rt,
                             ctx=ctx)
    return np.asarray(x[:n, :k], dtype=np.float64)


SweepMode = Literal["auto", "level", "seq", "device"]


def multifrontal_solve(f: MultifrontalFactor, b: np.ndarray,
                       mode: SweepMode = "auto", *,
                       sweep_bs: Optional[int] = None,
                       rt: Optional[int] = None, ctx=None) -> np.ndarray:
    """Solve A x = b with the supernodal factor.

    ``b`` may be a single RHS ``(n,)`` or a block ``(n, k)`` — all sweep
    modes are natively multi-RHS and the result matches the input shape.
    ``mode="level"`` (the default when the factor carries a schedule) runs
    the host level-batched sweeps; ``"seq"`` keeps the per-front loop
    (reference and fallback); ``"device"`` runs the batched Pallas
    substitution kernels on device-resident factor stacks (f32 — pair
    with refinement for fp64 residuals). ``sweep_bs``/``rt`` are the
    autotuned device-sweep knobs (tri-solve panel cap and RHS tile width);
    both are ignored by the host modes. Repeated solves reuse the stacked
    sweep tensors cached on the factor. ``ctx`` (a
    :class:`repro.core.reqctx.RequestContext` or None) receives the device
    mode's ``solve.sweep.setup`` span.
    """
    b = np.asarray(b)
    single = b.ndim == 1
    if mode == "auto":
        mode = "seq" if f.schedule is None else "level"
    if mode in ("level", "device") and f.schedule is None:
        raise ValueError(f"mode={mode!r} needs a factor with a schedule")
    if mode == "device":
        x = _solve_device(f, b[:, None] if single else b,
                          sweep_bs=sweep_bs, rt=rt, ctx=ctx)
        return x[:, 0] if single else x
    x = np.array(b, dtype=np.float64)   # the one owned fp64 copy
    x2 = x[:, None] if single else x    # view — sweeps mutate in place
    if mode == "seq":
        _solve_sequential(f, x2)
    else:
        _solve_level(f, x2)
    return x


def factor_and_solve_timed(a: CSRMatrix, b: np.ndarray | None = None,
                           relax: int = 8,
                           sym: Optional[SymbolicFactor] = None,
                           backend: Backend = "numpy",
                           pad: str = "pow2",
                           bs: Optional[int] = None,
                           sweep: SweepMode = "auto",
                           sweep_bs: Optional[int] = None,
                           rt: Optional[int] = None) -> dict:
    """Measured factor+solve wall time — the per-(matrix, ordering) label
    signal, mirroring the paper's MUMPS timings.

    Passing a precomputed ``sym`` (e.g. from a cached
    :class:`repro.core.plan.ExecutionPlan`) skips the symbolic stage
    entirely; ``t_symbolic`` is then reported as 0. ``relax`` tunes the
    supernode amalgamation and ``backend`` picks the front-math substrate,
    so labeling can time the Pallas / batched / pipelined paths too;
    ``pad``/``bs`` are the autotuned bucket/block policy knobs and
    ``sweep``/``sweep_bs``/``rt`` the triangular-sweep mode and its
    device-kernel knobs (see :mod:`repro.autotune.solve_tuner`).
    """
    if b is None:
        rng = np.random.default_rng(0)
        b = rng.standard_normal(a.n)
    # hoist the fp64 cast out of the timed region (and out of any caller's
    # repeat loop): the sweeps get a ready-to-consume contiguous fp64 RHS
    b = np.ascontiguousarray(b, dtype=np.float64)
    if sym is None:
        t0 = time.perf_counter()
        sym = symbolic_cholesky(a)
        t_sym = time.perf_counter() - t0
    else:
        t_sym = 0.0
    t0 = time.perf_counter()
    f = multifrontal_cholesky(a, sym, relax=relax, backend=backend, pad=pad,
                              bs=bs)
    t_fac = time.perf_counter() - t0
    t0 = time.perf_counter()
    x = multifrontal_solve(f, b, mode=sweep, sweep_bs=sweep_bs, rt=rt)
    t_sol = time.perf_counter() - t0
    resid = float(np.linalg.norm(a.matvec(x) - b) / max(np.linalg.norm(b), 1e-30))
    return dict(time=t_sym + t_fac + t_sol, t_symbolic=t_sym, t_factor=t_fac,
                t_solve=t_sol, residual=resid, **f.stats)
