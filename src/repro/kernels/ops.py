"""Public jit'd wrappers around the Pallas kernels.

On CPU hosts (this container, unit tests) the kernels execute in
``interpret=True`` mode — the kernel body runs as traced JAX ops, which
validates BlockSpec indexing and numerics exactly. On TPU the same calls
compile through Mosaic. `_interpret()` picks automatically.

The LM model code keeps an XLA (einsum) attention path for CPU dry-runs and
uses :func:`attention` on real TPU — see `repro.models.layers.Attention`.
"""
from __future__ import annotations

import functools
import os
from concurrent.futures import ThreadPoolExecutor
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import ref
from .flash_attention import flash_attention
from .frontal_cholesky import (chol_tile, extend_add_batch as
                               _extend_add_batch_kernel, frontal_factor_batch
                               as _frontal_factor_batch_kernel, matmul_nt,
                               tri_inv_tile, tri_solve_batch as
                               _tri_solve_batch_kernel)
from .spmv_bell import bell_spmv, csr_to_bell

__all__ = ["attention", "frontal_factor", "frontal_factor_batch",
           "frontal_factor_batch_ws", "extend_add_batch",
           "extend_add_stacks", "pick_block_size", "rhs_width",
           "compile_ahead", "f32_spec", "factor_call", "extend_add_stacks_call",
           "sweep_calls",
           "spmv", "matmul_nt_padded", "tri_solve_batch", "rhs_tile",
           "sweep_forward", "sweep_backward"]


def _interpret() -> bool:
    return jax.default_backend() == "cpu"


# The sweeps' cross-front L21 products run in XLA; on a TPU its default f32
# matmul is one bf16 pass, which would cap the sweep's accuracy near 1e-3.
_HIGHEST = jax.lax.Precision.HIGHEST


def _pad_to(x: jax.Array, axis: int, mult: int) -> jax.Array:
    size = x.shape[axis]
    pad = (-size) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


@functools.partial(jax.jit, static_argnames=("causal", "block_q", "block_kv"))
def attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
              causal: bool = True, block_q: int = 128,
              block_kv: int = 128) -> jax.Array:
    """GQA flash attention. q: (B, Hq, Sq, D); k/v: (B, Hkv, Skv, D).

    Repeats KV heads to match Q heads, pads sequences to block multiples
    (padded keys are masked via kv_len), and restores the original shape.
    """
    b, hq, sq, d = q.shape
    hkv, skv = k.shape[1], k.shape[2]
    assert hq % hkv == 0
    rep = hq // hkv
    if rep > 1:
        k = jnp.repeat(k, rep, axis=1)
        v = jnp.repeat(v, rep, axis=1)
    qf = _pad_to(q.reshape(b * hq, sq, d), 1, block_q)
    kf = _pad_to(k.reshape(b * hq, skv, d), 1, block_kv)
    vf = _pad_to(v.reshape(b * hq, skv, d), 1, block_kv)
    out = flash_attention(qf, kf, vf, causal=causal, block_q=block_q,
                          block_kv=block_kv, kv_len=skv,
                          interpret=_interpret())
    return out[:, :sq].reshape(b, hq, sq, d)


def matmul_nt_padded(a: jax.Array, b: jax.Array, c: jax.Array, *,
                     alpha: float = 1.0, beta: float = 1.0,
                     bs: int = 128) -> jax.Array:
    """beta*c + alpha*a@bᵀ for arbitrary shapes (zero-pads to tiles)."""
    m, n = c.shape
    ap = _pad_to(_pad_to(a, 0, bs), 1, bs)
    bp = _pad_to(_pad_to(b, 0, bs), 1, bs)
    cp = _pad_to(_pad_to(c, 0, bs), 1, bs)
    out = matmul_nt(ap, bp, cp, alpha=alpha, beta=beta, bm=bs, bn=bs, bk=bs,
                    interpret=_interpret())
    return out[:m, :n]


def frontal_factor(f: jax.Array, npiv: int, *, bs: int = 128
                   ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Partial Cholesky of a frontal matrix (lower triangle of `f` is read).

    Returns (L11, L21, S) like :func:`repro.kernels.ref.partial_cholesky_ref`.
    Layout: the pivot block is padded to a tile multiple with identity
    columns (decoupled, factor to 1.0, contribute nothing), so tile loops
    stay 128-aligned regardless of npiv.
    """
    f = jnp.asarray(f, jnp.float32)
    m = f.shape[0]
    nrest = m - npiv
    P = ((npiv + bs - 1) // bs) * bs
    Rp = ((nrest + bs - 1) // bs) * bs if nrest else 0
    M = P + Rp
    interp = _interpret()

    W = jnp.zeros((M, M), jnp.float32)
    W = W.at[:npiv, :npiv].set(jnp.tril(f[:npiv, :npiv]))
    if P > npiv:
        pad_idx = jnp.arange(npiv, P)
        W = W.at[pad_idx, pad_idx].set(1.0)
    if nrest:
        W = W.at[P : P + nrest, :npiv].set(f[npiv:, :npiv])
        W = W.at[P : P + nrest, P : P + nrest].set(jnp.tril(f[npiv:, npiv:]))

    for t in range(P // bs):
        lo = t * bs
        tile = jax.lax.dynamic_slice(W, (lo, lo), (bs, bs))
        ltt = chol_tile(tile, interpret=interp)
        W = jax.lax.dynamic_update_slice(W, ltt, (lo, lo))
        rows_below = M - lo - bs
        if rows_below == 0:
            continue
        inv = tri_inv_tile(ltt, interpret=interp)
        panel = jax.lax.dynamic_slice(W, (lo + bs, lo), (rows_below, bs))
        lpanel = matmul_nt(panel, inv, jnp.zeros_like(panel), alpha=1.0,
                           beta=0.0, bm=bs, bn=bs, bk=bs, interpret=interp)
        W = jax.lax.dynamic_update_slice(W, lpanel, (lo + bs, lo))
        trail = jax.lax.dynamic_slice(W, (lo + bs, lo + bs),
                                      (rows_below, rows_below))
        trail = matmul_nt(lpanel, lpanel, trail, alpha=-1.0, beta=1.0,
                          bm=bs, bn=bs, bk=bs, interpret=interp)
        W = jax.lax.dynamic_update_slice(W, trail, (lo + bs, lo + bs))

    L11 = jnp.tril(W[:npiv, :npiv])
    L21 = W[P : P + nrest, :npiv]
    S = W[P : P + nrest, P : P + nrest]
    S = jnp.tril(S) + jnp.tril(S, -1).T  # lower is authoritative
    return L11, L21, S


@functools.partial(jax.jit, static_argnames=("npiv", "bs", "interpret"))
def _factor_batch_ws_jit(w, npiv, bs, interpret):
    return _frontal_factor_batch_kernel(w, npiv, bs=bs, interpret=interpret)


def pick_block_size(npiv: int, bs: int | None = None) -> int:
    """Largest panel width ≤ ``bs`` (default 32) that divides ``npiv``.

    Bucketed pivot dims are multiples of 8 (pow2 ≥ 8 under the default pad
    policy, next-multiple-of-8 under ``mult8``), so the descent over
    divisors terminates at 8 at the latest; tiny fronts (npiv < 8) run
    unblocked. 32 keeps the sequential chol-tile loop short while the
    rank-bs updates stay matmul-shaped."""
    cap = 32 if bs is None else max(1, int(bs))
    if npiv <= cap:
        return npiv
    for cand in range(cap, 0, -1):
        if npiv % cand == 0:
            return cand
    return npiv


_batch_block = pick_block_size  # back-compat alias


def frontal_factor_batch_ws(w: jax.Array, npiv: int, *,
                            bs: int | None = None) -> jax.Array:
    """Level-scheduled entry point: factor the leading ``npiv`` columns of
    every (M, M) front workspace in the (B, M, M) stack ``w`` in ONE kernel
    launch (grid over B). Calls jit-cache per (B, M, npiv, bs) — bucketed
    shapes are powers of two, so a handful of compilations cover a whole
    factorization. ``bs`` is a *cap* on the panel width (the autotuned
    policy knob); the effective width is the largest divisor of ``npiv``
    not exceeding it. Returns the factored workspaces (see
    :func:`repro.kernels.frontal_cholesky.frontal_factor_batch`)."""
    fn, args, kw = factor_call(jnp.asarray(w, jnp.float32), npiv, bs)
    return fn(*args, **kw)


# -- ahead-of-time compilation ------------------------------------------------
#
# A factorization compiles one program per distinct bucket shape — hundreds
# for a 10⁴-row grid, at up to seconds each on the TPU compiler. The
# ``*_call`` helpers below are the single place each wrapper turns its
# inputs into ``(jitted fn, args, kwargs)``; given ShapeDtypeStructs they
# describe the same call before any array exists, so the schedule's
# programs can be compiled concurrently up front (:func:`compile_ahead`).

def f32_spec(shape) -> jax.ShapeDtypeStruct:
    return jax.ShapeDtypeStruct(tuple(shape), jnp.float32)


def compile_ahead(calls) -> None:
    """Compile jit calls — ``(fn, args, kwargs)`` as the ``*_call``
    helpers build them, arrays or ShapeDtypeStructs alike — concurrently
    on the host's cores before they first run. The executables land in
    the caches the later calls look up, so those dispatch without
    compiling; XLA releases the GIL while it compiles."""
    calls = list(calls)
    if not calls:
        return
    workers = min(len(calls), os.cpu_count() or 1)
    with ThreadPoolExecutor(workers, thread_name_prefix="compile") as ex:
        futs = [ex.submit(lambda c=c: c[0].lower(*c[1], **c[2]).compile())
                for c in calls]
        for f in futs:
            f.result()


def factor_call(w, npiv: int, bs: int | None = None):
    """:func:`frontal_factor_batch_ws`'s jit call for the (B, M, M) f32
    stack ``w``."""
    return (_factor_batch_ws_jit,
            (w, npiv, pick_block_size(npiv, bs), _interpret()), {})


def _gather_updates(stacks, srcs, order, offsets, rmax):
    """The child update stack of one extend-add launch: ``stacks[i]`` is a
    factored (B_i, M_i, M_i) workspace stack whose trailing block from
    ``offsets[i]`` on holds its members' Schur updates and ``srcs[i]``
    picks the contributing members. Each update is zero-padded to ``rmax``
    rows/cols (the padding meets ``-1`` row-map entries, so it is inert)
    and the concatenation is permuted by ``order`` into the launch's
    destination-sorted order."""
    us = []
    for w, src, p in zip(stacks, srcs, offsets):
        u = jnp.take(w[:, p:, p:], src, axis=0)
        pad = rmax - u.shape[1]
        us.append(jnp.pad(u, ((0, 0), (0, pad), (0, pad))) if pad else u)
    return jnp.take(jnp.concatenate(us), order, axis=0)


def _extend_add_impl(w, stacks, srcs, order, dst, rows, offsets, rmax,
                     interpret):
    u = _gather_updates(stacks, srcs, order, offsets, rmax)
    return _extend_add_batch_kernel(w, u, dst, rows, interpret=interpret)


_EA_STATIC = ("offsets", "rmax", "interpret")
_extend_add_jit = jax.jit(_extend_add_impl, static_argnames=_EA_STATIC)
# donation realizes the kernel-level workspace aliasing as a true in-place
# update on TPU; CPU (interpret/test) has no donation support and would
# warn on every compile, so it gets the plain variant
_extend_add_jit_donated = jax.jit(_extend_add_impl,
                                  static_argnames=_EA_STATIC,
                                  donate_argnums=(0,))


def _extend_add_fn():
    return _extend_add_jit if _interpret() else _extend_add_jit_donated


def extend_add_batch(w: jax.Array, u: jax.Array, dst, rows) -> jax.Array:
    """On-device extend-add (see
    :func:`repro.kernels.frontal_cholesky.extend_add_batch`): accumulate the
    child update stack ``u`` (C, R, R) into the parent workspace stack ``w``
    (B, M, M) at slots ``dst`` (sorted ascending) and local rows ``rows``
    (-1 = inactive). ``w`` is donated on TPU — callers must treat it as
    consumed. Calls jit-cache per (B, M, C, R) shape."""
    u = jnp.asarray(u, jnp.float32)
    idx = np.arange(u.shape[0], dtype=np.int32)
    return extend_add_stacks(jnp.asarray(w, jnp.float32), [u], [idx], idx,
                             np.asarray(dst, np.int32),
                             np.asarray(rows, np.int32), offsets=(0,),
                             rmax=u.shape[1])


def extend_add_stacks_call(w, stacks, srcs, order, dst, rows, *, offsets,
                           rmax: int):
    """:func:`extend_add_stacks`'s jit call. ``w``/``stacks`` are f32,
    ``srcs``/``order``/``dst``/``rows`` int32 (NumPy arrays at run time)."""
    return (_extend_add_fn(),
            (w, tuple(stacks), tuple(srcs), order, dst, rows),
            dict(offsets=tuple(offsets), rmax=rmax, interpret=_interpret()))


def extend_add_stacks(w: jax.Array, stacks, srcs, order, dst, rows, *,
                      offsets, rmax: int) -> jax.Array:
    """:func:`extend_add_batch` with the update stack gathered from the
    children's factored workspace stacks in the same jit (see
    :func:`_gather_updates`), so one destination bucket's extend-add is
    one compiled program."""
    fn, args, kw = extend_add_stacks_call(w, stacks, srcs, order, dst, rows,
                                          offsets=offsets, rmax=rmax)
    return fn(*args, **kw)


def frontal_factor_batch(fs: jax.Array, npiv: int, *, bs: int | None = None
                         ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Batched analogue of :func:`frontal_factor` for a uniform stack.

    ``fs``: (B, m, m) SPD fronts sharing one pivot count. Pads the pivot
    block to a tile multiple with decoupled identity columns (like
    ``frontal_factor``), factors the stack in one launch, and returns
    (L11, L21, S) with shapes (B, npiv, npiv) / (B, m-npiv, npiv) /
    (B, m-npiv, m-npiv).
    """
    fs = jnp.asarray(fs, jnp.float32)
    b, m, _ = fs.shape
    nrest = m - npiv
    if bs is None:
        P = max(8, 1 << (npiv - 1).bit_length())
        bs = _batch_block(P)
    else:
        P = ((npiv + bs - 1) // bs) * bs
    M = P + nrest
    W = jnp.zeros((b, M, M), jnp.float32)
    W = W.at[:, :npiv, :npiv].set(jnp.tril(fs[:, :npiv, :npiv]))
    if P > npiv:
        pad_idx = jnp.arange(npiv, P)
        W = W.at[:, pad_idx, pad_idx].set(1.0)
    if nrest:
        W = W.at[:, P:, :npiv].set(fs[:, npiv:, :npiv])
        W = W.at[:, P:, P:].set(jnp.tril(fs[:, npiv:, npiv:]))
    W = frontal_factor_batch_ws(W, P, bs=bs)
    L11 = jnp.tril(W[:, :npiv, :npiv])
    L21 = W[:, P:, :npiv]
    S = W[:, P:, P:]
    S = jnp.tril(S) + jnp.swapaxes(jnp.tril(S, -1), 1, 2)
    return L11, L21, S


@functools.partial(jax.jit, static_argnames=("bs", "kt", "lower",
                                             "interpret"))
def _tri_solve_jit(l, x, bs, kt, lower, interpret):
    return _tri_solve_batch_kernel(l, x, bs=bs, kt=kt, lower=lower,
                                   interpret=interpret)


def rhs_tile(k: int, rt: int | None = None) -> int:
    """Effective RHS-tile width: ``rt`` when it divides the RHS count,
    else the whole slab (one tile). The autotuned ``rt`` policy knob only
    kicks in when the caller's padded RHS width actually tiles by it."""
    if rt is None or k <= 0:
        return max(k, 1)
    rt = max(1, int(rt))
    return rt if k % rt == 0 else k


def tri_solve_batch(l: jax.Array, x: jax.Array, *, bs: int | None = None,
                    rt: int | None = None, lower: bool = True) -> jax.Array:
    """Batched blocked triangular substitution (see
    :func:`repro.kernels.frontal_cholesky.tri_solve_batch`).

    ``l``: (B, P, P) lower factors, ``x``: (B, P, K) RHS slabs; solves
    ``L Y = X`` or ``Lᵀ Y = X``. ``bs`` caps the panel width (same
    divisor-descent policy as the factor kernels); ``rt`` tiles the RHS
    dim (K is zero-padded up to a multiple). Calls jit-cache per
    (B, P, K, bs, kt) — bucketed P's are powers of two, so a handful of
    compilations cover a whole sweep schedule.
    """
    l = jnp.asarray(l, jnp.float32)
    x = jnp.asarray(x, jnp.float32)
    B, P, _ = l.shape
    K = x.shape[2]
    bse = pick_block_size(P, bs)
    if rt is not None and K % max(1, int(rt)):
        x = _pad_to(x, 2, max(1, int(rt)))
    kt = rhs_tile(x.shape[2], rt)
    out = _tri_solve_jit(l, x, bse, kt, lower, _interpret())
    return out[:, :, :K] if out.shape[2] != K else out


def rhs_width(k: int) -> int:
    """Column count a device sweep runs ``k`` right-hand sides at: the
    next power of two ≥ 8. The TPU pads the minor dim to 128 lanes anyway,
    so up to 128 columns cost the same per vreg, and a few widths mean a
    few compiled sweep programs instead of one per RHS count."""
    return max(8, 1 << (max(int(k), 1) - 1).bit_length())


def _bucket_factors(w, P):
    """(L11, L21) views of a factored (B, P + R, P + R) bucket stack. Only
    L11's lower triangle is meaningful; the substitution kernel reads
    nothing else."""
    return w[:, :P, :P], w[:, P:, :P]


@functools.partial(jax.jit, static_argnames=("bs", "kt", "interpret"))
def _sweep_fwd_jit(x, w, piv, rest, bs, kt, interpret):
    k = x.shape[1]
    l11, l21 = _bucket_factors(w, piv.shape[1])
    xb = jnp.take(x, piv, axis=0)                         # (B, P, k)
    y = _tri_solve_batch_kernel(l11, xb, bs=bs, kt=kt, lower=True,
                                interpret=interpret)
    x = x.at[piv.reshape(-1)].set(y.reshape(-1, k))
    if l21.shape[1]:
        upd = jnp.einsum("brp,bpk->brk", l21, y, precision=_HIGHEST)
        x = x.at[rest.reshape(-1)].add(-upd.reshape(-1, k))
    return x


@functools.partial(jax.jit, static_argnames=("bs", "kt", "interpret"))
def _sweep_bwd_jit(x, w, piv, rest, bs, kt, interpret):
    k = x.shape[1]
    l11, l21 = _bucket_factors(w, piv.shape[1])
    rhs = jnp.take(x, piv, axis=0)                        # (B, P, k)
    if l21.shape[1]:
        xr = jnp.take(x, rest, axis=0)                    # (B, R, k)
        rhs = rhs - jnp.einsum("brp,brk->bpk", l21, xr, precision=_HIGHEST)
    y = _tri_solve_batch_kernel(l11, rhs, bs=bs, kt=kt, lower=False,
                                interpret=interpret)
    return x.at[piv.reshape(-1)].set(y.reshape(-1, k))


def _sweep_call(fwd: bool, x, w, piv, rest, bs, rt):
    return (_sweep_fwd_jit if fwd else _sweep_bwd_jit,
            (x, w, piv, rest, pick_block_size(piv.shape[1], bs),
             rhs_tile(x.shape[1], rt), _interpret()), {})


def sweep_calls(x, w, piv, rest, *, bs: int | None = None,
                rt: int | None = None) -> list:
    """The forward and backward sweep jit calls of one level-bucket."""
    return [_sweep_call(fwd, x, w, piv, rest, bs, rt)
            for fwd in (True, False)]


def sweep_forward(x: jax.Array, w: jax.Array, piv: jax.Array,
                  rest: jax.Array, *, bs: int | None = None,
                  rt: int | None = None) -> jax.Array:
    """One level-bucket's forward-substitution step on a device-resident
    RHS block.

    ``x``: (n + 1, K) f32 — the solution-in-progress with a trailing
    "trash row" that every padded index points at (garbage in, garbage
    confined: identity pad rows in L11 and zero pad rows/cols in L21 keep
    it inert). ``w``: the bucket's factored (B, P + R, P + R) stack, L11 in
    its leading block and L21 below it (``P = piv.shape[1]``). Gathers the
    bucket's pivot rows, runs the batched :func:`tri_solve_batch` lower
    sweep, scatters the solved pivots back, and scatter-subtracts the
    ``L21 y`` cross-front updates — all inside one jit, dispatched
    asynchronously.
    """
    fn, args, kw = _sweep_call(True, x, w, piv, rest, bs, rt)
    return fn(*args, **kw)


def sweep_backward(x: jax.Array, w: jax.Array, piv: jax.Array,
                   rest: jax.Array, *, bs: int | None = None,
                   rt: int | None = None) -> jax.Array:
    """One level-bucket's backward-substitution step (``Lᵀ x = y``):
    gathers pivot and update rows, subtracts the ``L21ᵀ`` coupling, runs
    the batched upper sweep, and scatters the solved pivots back."""
    fn, args, kw = _sweep_call(False, x, w, piv, rest, bs, rt)
    return fn(*args, **kw)


def spmv(indptr: np.ndarray, indices: np.ndarray, data: np.ndarray,
         x: np.ndarray, *, bs: int = 8) -> np.ndarray:
    """CSR SpMV through the block-ELL kernel (host-side layout conversion)."""
    n = x.shape[0]
    blocks, idx, npad = csr_to_bell(indptr, indices, data, n, bs)
    xp = np.zeros(npad, dtype=np.float32)
    xp[:n] = x
    y = bell_spmv(jnp.asarray(blocks, jnp.float32), jnp.asarray(idx),
                  jnp.asarray(xp), interpret=_interpret())
    return np.asarray(y)[:n]
