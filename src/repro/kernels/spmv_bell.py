"""Block-ELLPACK SpMV Pallas kernel.

TPU-native sparse matvec: the matrix is stored as dense (bs×bs) blocks in an
ELL layout — every block-row holds exactly ``max_k`` blocks (zero-padded) and
a scalar-prefetched index vector names each block's column block. Scalar
prefetch feeds the x-block index_map, so the gather happens in the pipeline's
address generation rather than as vector gather ops (the standard Pallas TPU
sparse idiom). The index table is prefetched flat: SMEM pads a 2-D table's
minor dim to 128 words, which at ``max_k`` ≈ 9 inflates a 22k-row matrix's
table past SMEM's 1 MiB. f32 only — the TPU has no f64 vector unit, so the
refinement's f64 residual is a plain-XLA matvec
(:mod:`repro.sparse.refine`).
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["bell_spmv", "csr_to_bell"]


def csr_to_bell(indptr: np.ndarray, indices: np.ndarray, data: np.ndarray,
                n: int, bs: int = 8) -> Tuple[np.ndarray, np.ndarray, int]:
    """Convert CSR to block-ELL: (blocks (R, K, bs, bs), idx (R, K), n_pad)."""
    npad = ((n + bs - 1) // bs) * bs
    nrb = npad // bs
    # bucket nonzeros into (row_block, col_block)
    rows = np.repeat(np.arange(n), np.diff(indptr))
    rb, cb = rows // bs, indices // bs
    keys = rb * nrb + cb
    order = np.argsort(keys, kind="stable")
    rows_s, cols_s, data_s, keys_s = rows[order], indices[order], data[order], keys[order]
    uniq, starts = np.unique(keys_s, return_index=True)
    starts = np.append(starts, keys_s.size)
    per_row: list[list[tuple[int, np.ndarray]]] = [[] for _ in range(nrb)]
    for u, s0, s1 in zip(uniq, starts[:-1], starts[1:]):
        r, c = int(u) // nrb, int(u) % nrb
        blk = np.zeros((bs, bs))
        blk[rows_s[s0:s1] - r * bs, cols_s[s0:s1] - c * bs] = data_s[s0:s1]
        per_row[r].append((c, blk))
    max_k = max(1, max(len(p) for p in per_row))
    blocks = np.zeros((nrb, max_k, bs, bs))
    idx = np.zeros((nrb, max_k), dtype=np.int32)
    for r, plist in enumerate(per_row):
        for k, (c, blk) in enumerate(plist):
            blocks[r, k] = blk
            idx[r, k] = c
    return blocks, idx, npad


def _bell_kernel(idx_ref, blocks_ref, x_ref, o_ref, *, max_k: int):
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    blk = blocks_ref[0, 0].astype(jnp.float32)        # (bs, bs)
    xb = x_ref[...].astype(jnp.float32)               # (bs, kk)
    o_ref[...] += jnp.dot(blk, xb, precision=jax.lax.Precision.HIGHEST,
                          preferred_element_type=jnp.float32
                          ).astype(o_ref.dtype)


def bell_spmv(blocks: jax.Array, idx: jax.Array, x: jax.Array, *,
              interpret: bool = False) -> jax.Array:
    """y = A @ x with A in block-ELL form (f32).

    x: ``(n_pad,)`` or an RHS block ``(n_pad, k)``; the result matches x's
    shape.
    """
    nrb, max_k, bs, _ = blocks.shape
    single = x.ndim == 1
    x2 = x[:, None] if single else x
    kk = x2.shape[1]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(nrb, max_k),
        in_specs=[
            pl.BlockSpec((1, 1, bs, bs), lambda r, k, idx_ref: (r, k, 0, 0)),
            pl.BlockSpec((bs, kk),
                         lambda r, k, idx_ref: (idx_ref[r * max_k + k], 0)),
        ],
        out_specs=pl.BlockSpec((bs, kk), lambda r, k, idx_ref: (r, 0)),
        scratch_shapes=[],
    )
    out = pl.pallas_call(
        functools.partial(_bell_kernel, max_k=max_k),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((nrb * bs, kk), jnp.float32),
        interpret=interpret,
    )(jnp.reshape(idx, (-1,)), blocks, x2)
    return out[:, 0] if single else out
