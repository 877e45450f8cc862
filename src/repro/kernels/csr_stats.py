"""Pallas reduction kernels for batched CSR structure statistics.

The batched feature extractor (`repro.core.features.extract_features_batch_jnp`)
needs two flat reductions over a padded ``(B, E)`` entry batch — bandwidth
(max |i−j|) and profile (sum of first-column offsets) — and three over the
``(B, N)`` row batch — max/min row count and the squared deviation sum
behind nnz_std. Both are the serving hot loop: every request pays them
once per matrix, so they run as Pallas grid reductions here (VPU tiles, one
accumulator row per matrix) instead of XLA segment ops.

Layout: grid ``(num_tiles,)``; each step reduces one ``(B, tile)`` slab —
every matrix of the batch at once, so the block's sublane extent is the
whole batch dim, which the TPU's (8, 128) tiling rule accepts for any B —
and folds it into a ``(B, 128)`` accumulator, one row per matrix: the
leading lanes carry the statistics (max/min/sum folds), the rest stay zero.
The ``@pl.when(t == 0)`` init makes the output revisit-safe, the same idiom as
`spmv_bell`. On CPU hosts the kernels execute in ``interpret=True`` mode.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["entry_stats", "row_stats", "LANES"]

LANES = 128            # accumulator row width (TPU lane count)
_ROW_MIN_INIT = 3.4e38  # ~f32 max: min-accumulator identity


def _interpret() -> bool:
    return jax.default_backend() == "cpu"


def _pad_tiles(x: jnp.ndarray, tile: int) -> jnp.ndarray:
    pad = (-x.shape[1]) % tile
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad)))
    return x


def _lane_select(vals, b: int) -> jnp.ndarray:
    """(B, LANES) block holding column ``vals[i]`` (each (B, 1)) in lane i,
    0 elsewhere."""
    lanes = jax.lax.broadcasted_iota(jnp.int32, (b, LANES), 1)
    out = jnp.zeros((b, LANES), jnp.float32)
    for i, v in enumerate(vals):
        out = jnp.where(lanes == i, v, out)
    return out


def _entry_kernel(rows_ref, cols_ref, valid_ref, first_ref, out_ref):
    t = pl.program_id(0)
    b = out_ref.shape[0]

    @pl.when(t == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    r = rows_ref[...].astype(jnp.int32)
    c = cols_ref[...].astype(jnp.int32)
    valid = valid_ref[...] != 0
    first = first_ref[...] != 0

    absd = jnp.where(valid, jnp.abs(r - c), 0)
    bw = absd.max(axis=1, keepdims=True).astype(jnp.float32)
    prof = jnp.where(first & (c < r), r - c, 0).sum(
        axis=1, keepdims=True).astype(jnp.float32)

    lanes = jax.lax.broadcasted_iota(jnp.int32, (b, LANES), 1)
    tile_rows = _lane_select([bw, prof], b)
    cur = out_ref[...]
    # lane 0 folds by max, lane 1 by sum
    out_ref[...] = jnp.where(lanes == 0, jnp.maximum(cur, tile_rows),
                             cur + tile_rows)


def _row_kernel(row_nnz_ref, row_valid_ref, mean_ref, out_ref):
    t = pl.program_id(0)
    b = out_ref.shape[0]

    @pl.when(t == 0)
    def _init():
        out_ref[...] = _lane_select(
            [jnp.zeros((b, 1), jnp.float32),
             jnp.full((b, 1), _ROW_MIN_INIT, jnp.float32),
             jnp.zeros((b, 1), jnp.float32)], b)

    cnt = row_nnz_ref[...].astype(jnp.float32)
    valid = row_valid_ref[...] != 0
    mean = mean_ref[...].astype(jnp.float32)  # (B, 1) per-matrix mean

    mx = jnp.where(valid, cnt, 0.0).max(axis=1, keepdims=True)
    mn = jnp.where(valid, cnt, _ROW_MIN_INIT).min(axis=1, keepdims=True)
    dev = jnp.where(valid, cnt - mean, 0.0)
    sq = (dev * dev).sum(axis=1, keepdims=True)

    lanes = jax.lax.broadcasted_iota(jnp.int32, (b, LANES), 1)
    tile_rows = _lane_select([mx, mn, sq], b)
    cur = out_ref[...]
    # lane 0 folds by max, lane 1 by min, lane 2 by sum
    out_ref[...] = jnp.where(
        lanes == 0, jnp.maximum(cur, tile_rows),
        jnp.where(lanes == 1, jnp.minimum(cur, tile_rows), cur + tile_rows))


def entry_stats(rows, cols, valid, first, *, tile: int = 512,
                interpret=None):
    """Per-matrix [bandwidth, profile] over a padded entry batch.

    rows/cols: (B, E) int32; valid/first: (B, E) int32 masks (0/1).
    Returns (B, 2) float32.
    """
    if interpret is None:
        interpret = _interpret()
    rows = _pad_tiles(jnp.asarray(rows, jnp.int32), tile)
    cols = _pad_tiles(jnp.asarray(cols, jnp.int32), tile)
    valid = _pad_tiles(jnp.asarray(valid, jnp.int32), tile)
    first = _pad_tiles(jnp.asarray(first, jnp.int32), tile)
    b, e = rows.shape
    spec = pl.BlockSpec((b, tile), lambda t: (0, t))
    out = pl.pallas_call(
        _entry_kernel,
        grid=(e // tile,),
        in_specs=[spec, spec, spec, spec],
        out_specs=pl.BlockSpec((b, LANES), lambda t: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, LANES), jnp.float32),
        interpret=interpret,
    )(rows, cols, valid, first)
    return out[:, :2]


def row_stats(row_nnz, row_valid, mean, *, tile: int = 512, interpret=None):
    """Per-matrix [max, min, Σ(x−mean)²] of valid per-row nonzero counts.

    row_nnz/row_valid: (B, N) int32; mean: (B,) float32 (= nnz/n, computed
    by the caller so the deviation sum is single-pass).
    Returns (B, 3) float32.
    """
    if interpret is None:
        interpret = _interpret()
    row_nnz = _pad_tiles(jnp.asarray(row_nnz, jnp.int32), tile)
    row_valid = _pad_tiles(jnp.asarray(row_valid, jnp.int32), tile)
    b, npad = row_nnz.shape
    mean2 = jnp.asarray(mean, jnp.float32).reshape(b, 1)
    spec = pl.BlockSpec((b, tile), lambda t: (0, t))
    out = pl.pallas_call(
        _row_kernel,
        grid=(npad // tile,),
        in_specs=[spec, spec, pl.BlockSpec((b, 1), lambda t: (0, 0))],
        out_specs=pl.BlockSpec((b, LANES), lambda t: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, LANES), jnp.float32),
        interpret=interpret,
    )(row_nnz, row_valid, mean2)
    return out[:, :3]
