"""The solver's Pallas kernels compile for a TPU v5e at real widths.

No chip is needed: the TPU compiler is installed, and it compiles for a
v5e that is described (``jax.experimental.topologies``) and not attached.
This catches what interpret mode cannot — Mosaic lowering gaps, block
shapes that break the (8, 128) tiling rule, SMEM and VMEM overflows —
before any chip time is spent. The topology is described inside a
module-scoped fixture (never at import), so every test worker collects the
same tests and only the worker that runs this file loads the TPU library.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import csr_stats, frontal_cholesky as fc, spmv_bell


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "no TPU lib"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def compile_v5e(one_chip):
    """``compile(fn, *(shape, dtype))`` → the v5e executable of
    ``jit(fn)``, with the persistent compilation cache off (an entry
    compiled for a described chip cannot be read back without one)."""
    from jax.experimental.compilation_cache import compilation_cache

    def compile_(fn, *args):
        avals = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
                 for s, dt in args]
        prev = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            compiled = jax.jit(fn).lower(*avals).compile()
        finally:
            jax.config.update("jax_enable_compilation_cache", prev)
        assert "tpu_custom_call" in compiled.as_text()  # a Mosaic kernel
        return compiled

    return compile_


F32, I32 = jnp.float32, jnp.int32


@pytest.mark.parametrize("B,P,R,bs", [(8, 256, 256, 32),   # M = 512
                                      (2, 256, 1024, 32)])  # largest bucket
def test_frontal_factor_batch(compile_v5e, B, P, R, bs):
    M = P + R
    compile_v5e(lambda w: fc.frontal_factor_batch(w, P, bs=bs),
                ((B, M, M), F32))


def test_extend_add_batch(compile_v5e):
    B, M, C, R = 8, 512, 8, 256
    compile_v5e(fc.extend_add_batch, ((B, M, M), F32), ((C, R, R), F32),
                ((C,), I32), ((C, R), I32))


@pytest.mark.parametrize("lower", [True, False])
@pytest.mark.parametrize("K", [1, 8])
def test_tri_solve_batch(compile_v5e, lower, K):
    B, P, bs = 8, 256, 32
    compile_v5e(lambda l, x: fc.tri_solve_batch(l, x, bs=bs, lower=lower),
                ((B, P, P), F32), ((B, P, K), F32))


def test_entry_stats(compile_v5e):
    B, E = 8, 1 << 17
    compile_v5e(lambda r, c, v, f: csr_stats.entry_stats(r, c, v, f,
                                                         interpret=False),
                *[((B, E), I32)] * 4)


def test_row_stats(compile_v5e):
    B, N = 8, 1 << 15
    compile_v5e(lambda n, v, m: csr_stats.row_stats(n, v, m,
                                                    interpret=False),
                ((B, N), I32), ((B, N), I32), ((B,), F32))


@pytest.mark.parametrize("K", [1, 8])
def test_bell_spmv_f32(compile_v5e, K):
    # a 150x150 grid's block-ELL layout: 2,813 block rows of up to 9 blocks
    nrb, max_k, bs = 2813, 9, 8
    compile_v5e(spmv_bell.bell_spmv, ((nrb, max_k, bs, bs), F32),
                ((nrb, max_k), I32), ((nrb * bs, K), F32))
