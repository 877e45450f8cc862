"""Dense frontal-matrix factorization kernels for the multifrontal solver.

The multifrontal method reduces sparse Cholesky to *partial factorizations*
of dense fronts — `repro.sparse.multifrontal` builds the assembly tree and
calls :func:`repro.kernels.ops.frontal_factor`, which orchestrates three
Pallas kernels over 128-aligned VMEM tiles:

* ``chol_tile``     — unblocked Cholesky of one diagonal tile (the only
                      sequential piece; O(bs) fori_loop steps on a VMEM tile).
* ``tri_inv_tile``  — forward-substitution inverse of the tile's L factor,
                      turning the panel triangular-solve into a matmul.
* ``matmul_nt``     — tiled C ± A·Bᵀ with f32 VMEM accumulator; carries both
                      the panel solve (W·L⁻ᵀ) and the Schur update
                      (S −= L21·L21ᵀ), i.e. all the MXU FLOPs.
* ``frontal_factor_batch`` — the level-scheduled workhorse: a grid over the
                      batch dim where each program runs the *whole* blocked
                      right-looking partial factorization of one front
                      (chol tile → panel tri-solve → Schur rank-bs update,
                      fused, f32 accumulate) entirely in VMEM. One launch
                      factors every same-shape front of an assembly-tree
                      level — no per-front host round trips.
* ``tri_solve_batch`` — the level-scheduled *substitution* workhorse: one
                      grid program runs the whole blocked forward (``L y =
                      b``) or backward (``Lᵀ x = y``) substitution of one
                      front's RHS slab, reusing ``tri_inv_tile``'s block
                      inverse so every panel step is matmul-shaped. The RHS
                      dim is tiled by the grid (multi-RHS solves stream
                      column slabs through the same factor block), which is
                      what makes ``sweep="device"`` in
                      :func:`repro.sparse.multifrontal.multifrontal_solve`
                      one async kernel dispatch per level-bucket.
* ``extend_add_batch`` — the on-device extend-add: accumulates a stack of
                      child Schur update blocks into parent front workspaces
                      from a precomputed row map. The irregular scatter is
                      expressed as two MXU matmuls per child (``Eᵀ U E``
                      with a one-hot embedding ``E`` built in-kernel from
                      the row map), the destination slot is a scalar-prefetch
                      index driving the output BlockSpec, and the workspace
                      stack is aliased in/out so sequential grid steps
                      accumulate. This is what lets the ``pipelined``
                      backend keep update matrices device-resident between
                      assembly-tree levels.

This is the TPU-native adaptation of the paper's MUMPS substrate: the
irregular sparse assembly stays on the host, the dense front math is
systolic-friendly tiles.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["chol_tile", "tri_inv_tile", "matmul_nt", "frontal_factor_batch",
           "extend_add_batch", "tri_solve_batch"]


# ---------------------------------------------------------------------------
# Shared single-tile bodies (used by both the tile kernels and the batched
# front kernel; operate on jnp values, lower triangle authoritative).
#
# Mosaic does not lower value-level dynamic slicing, so the sequential
# column loops below pick row/column ``j`` with iota masks and masked sums,
# and every multi-row access at a traced offset goes through a ref with
# ``pl.ds`` on the sublane axis. Every matmul asks for HIGHEST precision:
# the TPU's default f32 contraction is a single bf16 pass, which would put
# the factor's error near 1e-3 and stall the refinement built on it.
# ---------------------------------------------------------------------------

#: scoped-VMEM limit for the whole-front kernels. v5e holds 128 MiB of VMEM
#: per core; the default 16 MiB scope stops at ~M=900 fronts, and the
#: largest bucket the level schedule builds for the supported grids
#: (M = P + R = 1280) needs ~40 MiB with double-buffered in/out blocks.
VMEM_LIMIT_BYTES = 96 * 2**20

_ROW_CHUNK = 128   # rows per step of the looped Schur / extend-add updates


def _dot(a: jax.Array, b: jax.Array, contract) -> jax.Array:
    """f32 ``dot_general`` contracting ``contract = (lhs_dims, rhs_dims)``
    at full f32 precision."""
    return jax.lax.dot_general(a, b, (contract, ((), ())),
                               precision=jax.lax.Precision.HIGHEST,
                               preferred_element_type=jnp.float32)


_NT = ((1,), (1,))   # a @ bᵀ
_NN = ((1,), (0,))   # a @ b
_TN = ((0,), (0,))   # aᵀ @ b


def _iotas(bs: int):
    return (jax.lax.broadcasted_iota(jnp.int32, (bs, bs), 0),
            jax.lax.broadcasted_iota(jnp.int32, (bs, bs), 1))


def _chol_block(a: jax.Array) -> jax.Array:
    """Unblocked right-looking Cholesky of one (bs, bs) f32 block value."""
    bs = a.shape[0]
    r, c = _iotas(bs)

    def step(j, a):
        colj = jnp.sum(jnp.where(c == j, a, 0.0), axis=1, keepdims=True)
        d = jnp.sqrt(jnp.sum(jnp.where((r == j) & (c == j), a, 0.0),
                             keepdims=True))                     # (1, 1)
        ri = r[:, :1]
        l = jnp.where(ri == j, d, jnp.where(ri > j, colj / d, 0.0))
        lrow = jnp.sum(jnp.where(r == c, l, 0.0), axis=0,
                       keepdims=True)                            # lᵀ
        a = jnp.where((r > j) & (c > j), a - l * lrow, a)
        return jnp.where(c == j, l, a)

    return jnp.where(r >= c, jax.lax.fori_loop(0, bs, step, a), 0.0)


def _tri_inv_block(L: jax.Array) -> jax.Array:
    """Inverse of a lower-triangular (bs, bs) f32 block (forward
    substitution on the identity, one pivot row per step; only the lower
    triangle of ``L`` is read)."""
    bs = L.shape[0]
    r, c = _iotas(bs)

    def step(j, y):
        lcol = jnp.sum(jnp.where(c == j, L, 0.0), axis=1, keepdims=True)
        d = jnp.sum(jnp.where((r == j) & (c == j), L, 0.0), keepdims=True)
        yrow = jnp.sum(jnp.where(r == j, y, 0.0), axis=0, keepdims=True) / d
        return jnp.where(r == j, yrow, jnp.where(r > j, y - lcol * yrow, y))

    return jax.lax.fori_loop(0, bs, step, (r == c).astype(jnp.float32))


def _row_chunk(rows: int) -> int:
    """Largest power-of-two chunk ≤ ``_ROW_CHUNK`` (and ≥ 8) dividing
    ``rows``; ``rows`` itself when none does."""
    t = _ROW_CHUNK
    while t > 8 and rows % t:
        t //= 2
    return t if rows % t == 0 else rows


def _row_offset(start, step: int, i):
    """Traced sublane offset ``start + i * step``, tagged 8-aligned when it
    is (lets Mosaic use aligned loads/stores)."""
    off = start + i * step
    if start % 8 == 0 and step % 8 == 0:
        off = pl.multiple_of(off, 8)
    return off


# ---------------------------------------------------------------------------
# Diagonal-tile Cholesky (single block, right-looking, masked updates)
# ---------------------------------------------------------------------------

def _chol_kernel(a_ref, l_ref):
    a = a_ref[...].astype(jnp.float32)
    l_ref[...] = _chol_block(a).astype(l_ref.dtype)


def chol_tile(a: jax.Array, *, interpret: bool = False) -> jax.Array:
    """Cholesky of one (bs, bs) SPD tile; returns lower-triangular L."""
    bs = a.shape[0]
    assert a.shape == (bs, bs)
    return pl.pallas_call(
        _chol_kernel,
        in_specs=[pl.BlockSpec((bs, bs), lambda: (0, 0))],
        out_specs=pl.BlockSpec((bs, bs), lambda: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((bs, bs), a.dtype),
        interpret=interpret,
    )(a)


# ---------------------------------------------------------------------------
# Triangular inverse of a tile (L Y = I, row-by-row forward substitution)
# ---------------------------------------------------------------------------

def _tri_inv_kernel(l_ref, y_ref):
    L = l_ref[...].astype(jnp.float32)
    y_ref[...] = _tri_inv_block(L).astype(y_ref.dtype)


def tri_inv_tile(l: jax.Array, *, interpret: bool = False) -> jax.Array:
    bs = l.shape[0]
    return pl.pallas_call(
        _tri_inv_kernel,
        in_specs=[pl.BlockSpec((bs, bs), lambda: (0, 0))],
        out_specs=pl.BlockSpec((bs, bs), lambda: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((bs, bs), l.dtype),
        interpret=interpret,
    )(l)


# ---------------------------------------------------------------------------
# Tiled C = beta*C_in + alpha * A @ Bᵀ  (the MXU workhorse)
# ---------------------------------------------------------------------------

def _matmul_nt_kernel(a_ref, b_ref, c_ref, o_ref, acc_ref, *,
                      k_blocks: int, alpha: float, beta: float):
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = beta * c_ref[...].astype(jnp.float32)

    a = a_ref[...].astype(jnp.float32)
    b = b_ref[...].astype(jnp.float32)
    acc_ref[...] += alpha * _dot(a, b, _NT)

    @pl.when(ki == k_blocks - 1)
    def _finish():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def matmul_nt(a: jax.Array, b: jax.Array, c: jax.Array, *,
              alpha: float = 1.0, beta: float = 1.0,
              bm: int = 128, bn: int = 128, bk: int = 128,
              interpret: bool = False) -> jax.Array:
    """Returns beta*c + alpha * a @ bᵀ. Shapes: a (M,K), b (N,K), c (M,N);
    all dims must be multiples of the tile sizes (ops.py pads)."""
    m, k = a.shape
    n = b.shape[0]
    assert b.shape[1] == k and c.shape == (m, n)
    assert m % bm == 0 and n % bn == 0 and k % bk == 0, (m, n, k)
    grid = (m // bm, n // bn, k // bk)
    kernel = functools.partial(_matmul_nt_kernel, k_blocks=k // bk,
                               alpha=alpha, beta=beta)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bn, bk), lambda i, j, kk: (j, kk)),
            pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), c.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
    )(a, b, c)


# ---------------------------------------------------------------------------
# Batched partial factorization: one grid program = one whole front
# ---------------------------------------------------------------------------

def _frontal_batch_kernel(f_ref, o_ref, *, npanels: int, bs: int):
    """Blocked right-looking partial Cholesky of one (M, M) front workspace.

    Factors the leading ``npanels * bs`` columns; the trailing block ends up
    holding the Schur complement. The output block is the in-place VMEM
    workspace. The panel loop is a static unroll (npanels is a bucket
    constant); each panel fuses chol-tile → panel tri-solve (via the tile
    inverse, i.e. a matmul) → rank-bs Schur update. The Schur update runs
    as a loop over row chunks, so the kernel's code size grows with
    ``M · chunk`` rather than ``M²`` (which is what keeps the compile of
    M ≈ 1280 fronts at seconds). Lower triangle is authoritative
    throughout.
    """
    o_ref[...] = f_ref[...]
    M = o_ref.shape[1]
    for t in range(npanels):
        lo, hi = t * bs, (t + 1) * bs
        ltt = _chol_block(o_ref[0, lo:hi, lo:hi].astype(jnp.float32))
        o_ref[0, lo:hi, lo:hi] = ltt.astype(o_ref.dtype)
        if hi == M:
            continue
        lpanel = _dot(o_ref[0, hi:, lo:hi].astype(jnp.float32),
                      _tri_inv_block(ltt), _NT)
        o_ref[0, hi:, lo:hi] = lpanel.astype(o_ref.dtype)
        T = _row_chunk(M - hi)

        def schur(i, carry, lo=lo, hi=hi, T=T, lpanel=lpanel):
            rows = pl.ds(_row_offset(hi, T, i), T)
            upd = _dot(o_ref[0, rows, lo:hi].astype(jnp.float32), lpanel,
                       _NT)
            o_ref[0, rows, hi:] = (o_ref[0, rows, hi:] - upd
                                   ).astype(o_ref.dtype)
            return carry

        jax.lax.fori_loop(0, (M - hi) // T, schur, 0)


def _extend_add_kernel(dst_ref, u_ref, rows_ref, rows_t_ref, w_ref, o_ref):
    """Accumulate one child update into its parent front workspace.

    The scatter ``W[rows, rows] += U`` is recast as ``W += Eᵀ U E`` with
    ``E[i, m] = (rows[i] == m)`` — matmuls, no gather/scatter lowering
    needed. Row-map entries of ``-1`` (child padding, or a padded
    contribution slot) produce an all-zero one-hot row, so they contribute
    nothing. The product is formed per chunk of destination rows (row
    chunk ``Eᵀ[chunk] U E``), which bounds the kernel's code size. ``o_ref``
    aliases the workspace stack; the TPU grid is sequential and ``dst`` is
    sorted, so each slot's contributions are one contiguous run of grid
    steps: the run's first step loads the workspace from ``w_ref`` into the
    resident output block, and the following steps accumulate into it.
    """
    c = pl.program_id(0)

    @pl.when((c == 0) | (dst_ref[c] != dst_ref[jnp.maximum(c - 1, 0)]))
    def _load():
        o_ref[...] = w_ref[...]

    U = u_ref[0].astype(jnp.float32)                     # (R, R)
    R = U.shape[0]
    M = o_ref.shape[1]
    rows = rows_ref[0]                                    # (1, R) int32
    E = (rows_t_ref[0] == jax.lax.broadcasted_iota(jnp.int32, (R, M), 1)
         ).astype(jnp.float32)                            # (R, M) one-hot
    T = _row_chunk(M)

    def accumulate(j, carry):
        m0 = _row_offset(0, T, j)
        et = (jax.lax.broadcasted_iota(jnp.int32, (T, R), 0) + m0 == rows
              ).astype(jnp.float32)                       # Eᵀ row chunk
        upd = _dot(_dot(et, U, _NN), E, _NN)              # (T, M)
        o_ref[0, pl.ds(m0, T), :] = (o_ref[0, pl.ds(m0, T), :] + upd
                                     ).astype(o_ref.dtype)
        return carry

    jax.lax.fori_loop(0, M // T, accumulate, 0)


def extend_add_batch(w: jax.Array, u: jax.Array, dst: jax.Array,
                     rows: jax.Array, *, interpret: bool = False
                     ) -> jax.Array:
    """On-device extend-add: scatter-accumulate child Schur updates into
    parent front workspaces.

    ``w``: (B, M, M) f32 parent workspaces (host-scattered A entries +
    identity pads). ``u``: (C, R, R) f32 child update blocks (typically the
    trailing Schur block of a previously factored, still device-resident
    bucket). ``dst``: (C,) int32 destination batch slot per child, sorted
    ascending (the accumulation-ordering contract). ``rows``: (C, R) int32
    local row positions in the (padded) parent front; ``-1`` marks inactive
    rows. Returns the updated workspace stack (``w`` is consumed via
    aliasing).
    """
    B, M, M2 = w.shape
    C, R, R2 = u.shape
    assert M == M2 and R == R2 and dst.shape == (C,) and rows.shape == (C, R)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(C,),
        in_specs=[
            pl.BlockSpec((1, R, R), lambda c, dst: (c, 0, 0)),
            # the row map twice: as a row (1, R) for the Eᵀ chunks and as
            # a column (R, 1) for E — both full-extent trailing dims
            pl.BlockSpec((1, 1, R), lambda c, dst: (c, 0, 0)),
            pl.BlockSpec((1, R, 1), lambda c, dst: (c, 0, 0)),
            pl.BlockSpec((1, M, M), lambda c, dst: (dst[c], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, M, M), lambda c, dst: (dst[c], 0, 0)),
    )
    return pl.pallas_call(
        _extend_add_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, M, M), w.dtype),
        input_output_aliases={4: 0},  # w (5th operand incl. prefetch) → out
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(dst, u, rows.reshape(C, 1, R), rows.reshape(C, R, 1), w)


def _tri_solve_batch_kernel(l_ref, b_ref, o_ref, *, npanels: int, bs: int,
                            lower: bool):
    """Blocked triangular substitution of one (P, K) RHS slab.

    ``lower=True`` solves ``L X = B`` top-down; ``lower=False`` solves
    ``Lᵀ X = B`` bottom-up (``l_ref`` always holds the *lower* factor — the
    transpose lives in the contraction dims, not in memory). Each panel
    step inverts the (bs, bs) diagonal block via :func:`_tri_inv_block`
    and applies it as a matmul, so the only sequential work is the
    fori_loop inside the tiny block inverse. The output block is the
    in-place solution slab; the panel loop is a static unroll (npanels is
    a bucket constant). Unit-diagonal padding rows in the factor are
    decoupled identity rows: they pass their RHS entries through
    untouched, which is what lets padded slots carry garbage ("trash row"
    gathers) without contaminating real rows.
    """
    o_ref[...] = b_ref[...]
    P = l_ref.shape[1]
    panels = range(npanels) if lower else range(npanels - 1, -1, -1)
    for t in panels:
        lo, hi = t * bs, (t + 1) * bs
        inv = _tri_inv_block(l_ref[0, lo:hi, lo:hi].astype(jnp.float32))
        if lower:
            xp = _dot(inv, o_ref[0, lo:hi, :].astype(jnp.float32), _NN)
            o_ref[0, lo:hi, :] = xp.astype(o_ref.dtype)
            if hi < P:
                o_ref[0, hi:, :] = (
                    o_ref[0, hi:, :]
                    - _dot(l_ref[0, hi:, lo:hi].astype(jnp.float32), xp, _NN)
                ).astype(o_ref.dtype)
        else:
            rhs = o_ref[0, lo:hi, :].astype(jnp.float32)
            if hi < P:
                rhs = rhs - _dot(l_ref[0, hi:, lo:hi].astype(jnp.float32),
                                 o_ref[0, hi:, :].astype(jnp.float32), _TN)
            # (L_tt)⁻ᵀ rhs = invᵀ @ rhs
            o_ref[0, lo:hi, :] = _dot(inv, rhs, _TN).astype(o_ref.dtype)


def tri_solve_batch(l: jax.Array, x: jax.Array, *, bs: int,
                    kt: int | None = None, lower: bool = True,
                    interpret: bool = False) -> jax.Array:
    """Batched blocked triangular substitution over a stack of fronts.

    ``l``: (B, P, P) lower factors (unit-diagonal identity padding beyond
    each front's true pivot count). ``x``: (B, P, K) RHS slabs. Solves
    ``L Y = X`` (``lower=True``) or ``Lᵀ Y = X`` per batch member in one
    launch: the grid is (B, K // kt), so each program owns one front's
    (P, kt) RHS tile — ``kt`` (default: the whole K) is the RHS-tile policy
    knob that turns multi-RHS solves into independent column slabs.
    """
    B, P, P2 = l.shape
    K = x.shape[2]
    kt = K if kt is None else kt
    assert P == P2 and x.shape == (B, P, K), (l.shape, x.shape)
    assert P % bs == 0 and K % kt == 0, (P, bs, K, kt)
    kernel = functools.partial(_tri_solve_batch_kernel, npanels=P // bs,
                               bs=bs, lower=lower)
    return pl.pallas_call(
        kernel,
        grid=(B, K // kt),
        in_specs=[
            pl.BlockSpec((1, P, P), lambda b, j: (b, 0, 0)),
            pl.BlockSpec((1, P, kt), lambda b, j: (b, 0, j)),
        ],
        out_specs=pl.BlockSpec((1, P, kt), lambda b, j: (b, 0, j)),
        out_shape=jax.ShapeDtypeStruct((B, P, K), x.dtype),
        interpret=interpret,
    )(l, x)


def frontal_factor_batch(w: jax.Array, npiv: int, *, bs: int,
                         interpret: bool = False) -> jax.Array:
    """Batched partial Cholesky over a stack of front workspaces.

    ``w``: (B, M, M) f32, each front laid out with its (identity-padded)
    pivot block in the leading ``npiv`` columns. Returns the factored
    workspaces: tril of the leading block is L11, rows below it in the
    pivot columns are L21, and the trailing block is the Schur complement
    (lower triangle authoritative).
    """
    B, M, M2 = w.shape
    assert M == M2 and 0 < npiv <= M and npiv % bs == 0, (w.shape, npiv, bs)
    kernel = functools.partial(_frontal_batch_kernel,
                               npanels=npiv // bs, bs=bs)
    return pl.pallas_call(
        kernel,
        grid=(B,),
        in_specs=[pl.BlockSpec((1, M, M), lambda b: (b, 0, 0))],
        out_specs=pl.BlockSpec((1, M, M), lambda b: (b, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, M, M), w.dtype),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(w)
