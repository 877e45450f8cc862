"""Mixed-precision iterative refinement for the direct solve.

The classic trick [Wilkinson 1963; Carson & Higham 2018]: factor once in low
precision (fp32 — half the memory traffic, double the MXU rate), then recover
working-precision accuracy with a short residual-correction loop in fp64:

    x₀ = L⁻ᵀ L⁻¹ b           (low-precision factor)
    rᵢ = b − A xᵢ            (fp64 sparse matvec — cheap, O(nnz))
    xᵢ₊₁ = xᵢ + L⁻ᵀ L⁻¹ rᵢ

Each sweep multiplies the error by ~κ(A)·ε₃₂, so a handful of iterations
reaches the fp64 floor whenever κ(A) ≪ 1/ε₃₂. The loop is
residual-controlled: it stops at ``tol``, at ``max_iter``, or when progress
stalls (guards ill-conditioned systems against cycling forever).

Two drivers share those stopping rules:

* :func:`refine_solve` — host loop around caller-supplied ``matvec`` /
  ``solve`` closures (any backend, any sweep mode).
* :func:`refine_solve_device` — the device-resident loop for
  ``sweep="device"``: x, r, and the factor stacks stay in device memory,
  the fp64 residual is a plain-XLA CSR matvec traced under
  ``jax.enable_x64`` (the TPU has no f64 vector unit, so no Pallas kernel
  can compute it; XLA emulates f64 there), and the only host↔device
  traffic per iteration is the residual-norm scalar.

:func:`residual_path` says which of the two serves ``sweep="device"`` on
the platform at hand (``jax.devices()[0].platform``); callers record it.

This is what makes the fp32 ``batched``/``pallas`` factorization backends of
:mod:`repro.sparse.multifrontal` usable as drop-in replacements for the fp64
numpy path: ``EngineConfig.solve_dtype = "fp32_refine"``.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable, List, Optional, Tuple

import numpy as np

__all__ = ["RefineInfo", "refine_solve", "refine_solve_device",
           "residual_path", "DEFAULT_TOL"]

DEFAULT_TOL = 1e-12
_STALL_FACTOR = 0.5   # require ≥ 2× residual reduction per sweep to continue


@dataclasses.dataclass
class RefineInfo:
    iterations: int          # correction sweeps applied (0 = first solve enough)
    residuals: List[float]   # relative residual after each evaluation
    converged: bool
    # where the solve-phase wall time went, read from the loop's
    # ``solve.sweep`` and ``solve.refine`` spans: triangular sweeps vs
    # residual evaluation (on the device loop the residual span includes
    # the one scalar sync per iteration, where queued sweep work completes)
    t_sweep: float = 0.0
    t_residual: float = 0.0

    @property
    def final_residual(self) -> float:
        return self.residuals[-1] if self.residuals else float("inf")


def _should_stop(residuals: List[float], tol: float, iters: int,
                 max_iter: int) -> Tuple[bool, bool]:
    """(stop, converged) under the shared stopping rules: tolerance
    reached, iteration budget spent, or progress stalled (conditioning
    beyond what low-precision corrections can fix)."""
    rel = residuals[-1]
    if rel <= tol:
        return True, True
    if iters >= max_iter:
        return True, False
    if len(residuals) >= 2 and rel > _STALL_FACTOR * residuals[-2]:
        return True, False
    return False, False


def refine_solve(matvec: Callable[[np.ndarray], np.ndarray],
                 solve: Callable[[np.ndarray], np.ndarray],
                 b: np.ndarray, *,
                 tol: float = DEFAULT_TOL,
                 max_iter: int = 10,
                 ctx=None) -> tuple[np.ndarray, RefineInfo]:
    """Solve A x = b to fp64 accuracy using a low-precision inner solver.

    ``matvec`` must be the fp64 operator of A; ``solve`` is the (possibly
    low-precision) factorization solve applied to an fp64 right-hand side.
    ``b`` may be ``(n,)`` or an ``(n, k)`` RHS block (both closures must
    then accept blocks; the residual norm is Frobenius over the block).
    Each solve is a ``solve.sweep`` span and each residual a
    ``solve.refine`` span on ``ctx`` (a private context when None).
    Returns ``(x, RefineInfo)``.
    """
    from repro.core.reqctx import RequestContext, span

    b = np.asarray(b, dtype=np.float64)
    nb = float(np.linalg.norm(b))
    if nb == 0.0:
        return np.zeros_like(b), RefineInfo(0, [0.0], True)
    own = ctx if ctx is not None else RequestContext.mint()
    spans0 = dict(own.spans)
    with span(own, "solve.sweep"):
        x = np.asarray(solve(b), dtype=np.float64)
    residuals: List[float] = []
    iters = 0
    while True:
        with span(own, "solve.refine"):
            r = b - np.asarray(matvec(x), dtype=np.float64)
            rel = float(np.linalg.norm(r)) / nb
        residuals.append(rel)
        stop, ok = _should_stop(residuals, tol, iters, max_iter)
        if stop:
            return x, _info(iters, residuals, ok, own, spans0)
        with span(own, "solve.sweep"):
            x = x + np.asarray(solve(r), dtype=np.float64)
        iters += 1


def _info(iters: int, residuals: List[float], ok: bool, ctx,
          spans0: dict) -> RefineInfo:
    d = ctx.spans_since(spans0)
    return RefineInfo(iters, residuals, ok, d.get("solve.sweep", 0.0),
                      d.get("solve.refine", 0.0))


#: where the fp64 residual of a ``sweep="device"`` refinement runs, per JAX
#: platform: ``"device"`` keeps x/r on the device (:func:`refine_solve_device`,
#: XLA f64 matvec), ``"host"`` runs :func:`refine_solve` with a NumPy fp64
#: matvec around the device sweeps. Platforms not listed take the host loop.
#: (On a TPU v5e, XLA's emulated f64 residual norm matched NumPy's to
#: 3.5e-15 relative.)
_RESIDUAL_PATHS = {"cpu": "device", "tpu": "device"}


def residual_path() -> str:
    """``"device"`` or ``"host"``: where this platform computes the fp64
    residual of a device-swept refinement."""
    import jax

    return _RESIDUAL_PATHS.get(jax.devices()[0].platform, "host")


@functools.cache
def _residual_dev_fn():
    """jit'd device residual step: r = b − A x for a CSR ``A`` (f64 segment
    sum over the row ids), returned as the f32 correction RHS together with
    the f64 norm ‖r‖. Trace and call it under ``jax.enable_x64``."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def step(data, rows, cols, x, b):
        ax = jax.ops.segment_sum(data[:, None] * x[cols], rows,
                                 num_segments=x.shape[0],
                                 indices_are_sorted=True)
        r = b - ax
        return r.astype(jnp.float32), jnp.sqrt(jnp.sum(r * r))

    return step


def refine_solve_device(a, f, b: np.ndarray, *,
                        tol: float = DEFAULT_TOL, max_iter: int = 10,
                        sweep_bs: Optional[int] = None,
                        rt: Optional[int] = None, ctx=None
                        ) -> tuple[np.ndarray, RefineInfo]:
    """Device-resident refinement for the ``sweep="device"`` solve path.

    ``a`` is the (permuted) fp64 :class:`repro.sparse.csr.CSRMatrix`, ``f``
    the schedule-carrying :class:`~repro.sparse.multifrontal.
    MultifrontalFactor`. The solution and residual live on device for the
    whole loop: the correction solve is the batched-Pallas sweep pass on
    the resident factor stacks (f32, traced outside the x64 context so the
    kernels stay 32-bit), the residual is an fp64 XLA CSR matvec under
    ``jax.enable_x64``, and the only per-iteration host↔device traffic is
    the residual-norm scalar — the ``float()`` that also serves as the sync
    point for the level-bucket dispatches queued by the sweep. Stopping
    rules (tol / max_iter / stall) are shared with :func:`refine_solve`,
    and so are the spans on ``ctx`` (``solve.sweep``, with the first
    pass's ``solve.sweep.setup`` inside it, and ``solve.refine``).
    ``b``: ``(n,)`` or ``(n, k)``; returns ``(x fp64 host, RefineInfo)``.
    """
    import jax
    import jax.numpy as jnp

    from repro.core.reqctx import RequestContext, span
    from repro.kernels.ops import rhs_width
    from repro.sparse.multifrontal import _device_sweep_passes

    b = np.asarray(b, dtype=np.float64)
    single = b.ndim == 1
    b2 = b[:, None] if single else b
    n, k = b2.shape
    nb = float(np.linalg.norm(b2))
    if nb == 0.0:
        return np.zeros_like(b), RefineInfo(0, [0.0], True)
    own = ctx if ctx is not None else RequestContext.mint()
    spans0 = dict(own.spans)
    residual_step = _residual_dev_fn()
    rows = np.repeat(np.arange(n, dtype=np.int32), np.diff(a.indptr))

    def sweep(r32):
        """f32 sweep pass on a device (n, k) block → device (n, k) f32."""
        x = jnp.zeros((n + 1, rhs_width(k)), jnp.float32).at[:n, :k].set(r32)
        return _device_sweep_passes(f, x, sweep_bs=sweep_bs, rt=rt,
                                    ctx=own)[:n, :k]

    with jax.enable_x64(True):
        data_d = jnp.asarray(a.data, jnp.float64)
        rows_d = jnp.asarray(rows)
        cols_d = jnp.asarray(a.indices, jnp.int32)
        b_d = jnp.asarray(b2, jnp.float64)
    with span(own, "solve.sweep"):
        dx = sweep(jnp.asarray(b2, jnp.float32))
        with jax.enable_x64(True):
            x = dx.astype(jnp.float64)
    residuals: List[float] = []
    iters = 0
    while True:
        with span(own, "solve.refine"), jax.enable_x64(True):
            r32, nrm = residual_step(data_d, rows_d, cols_d, x, b_d)
            rel = float(nrm) / nb       # the one per-iteration scalar sync
        residuals.append(rel)
        stop, ok = _should_stop(residuals, tol, iters, max_iter)
        if stop:
            break
        with span(own, "solve.sweep"):
            dx = sweep(r32)
            with jax.enable_x64(True):
                x = x + dx.astype(jnp.float64)
        iters += 1
    with jax.enable_x64(True):
        out = np.asarray(x, dtype=np.float64)
    return (out[:, 0] if single else out,
            _info(iters, residuals, ok, own, spans0))
