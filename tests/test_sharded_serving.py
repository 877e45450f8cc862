"""Distributed serving plane: shard_map featurization/inference identity.

Two layers of coverage:

* in-process tests run on the degenerate 1-device serving mesh (tier-1
  sees one CPU device) — they prove the shard_map path *is* the production
  path and matches the raw unsharded impl bit-for-bit, including a
  hypothesis property sweep over ragged batch sizes;
* subprocess tests re-launch with ``--xla_force_host_platform_device_count=4``
  (the `test_distributed.py` idiom) and prove multi-shard runs are
  element-wise identical to the 1-device run for batch sizes that do and
  do not divide the device count — the acceptance criterion of the
  distributed-serving refactor.
"""
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro.core.features import (FEATURE_NAMES, extract_features_batch,
                                 extract_features_batch_jnp, pad_csr_batch)
from repro.core.ml import RandomForestClassifier
from repro.core.scaling import StandardScaler
from repro.core.selector import ReorderSelector
from repro.distributed.meshctx import (ServingMesh, get_serving_mesh,
                                       make_serving_mesh, serving_mesh,
                                       set_serving_mesh)
from repro.sparse.dataset import generate_suite

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def run_py(code: str, devices: int = 4, timeout=420):
    env = dict(os.environ)
    env["XLA_FLAGS"] = (f"--xla_force_host_platform_device_count={devices}")
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, timeout=timeout,
                       env=env)
    assert r.returncode == 0, r.stdout + "\n" + r.stderr
    return r.stdout


@pytest.fixture(scope="module")
def mats():
    return list(generate_suite(count=9, seed=3, size_scale=0.25))


@pytest.fixture(scope="module")
def selector(mats):
    feats = extract_features_batch(mats)
    labels = (feats[:, FEATURE_NAMES.index("bandwidth")]
              / np.maximum(feats[:, 0], 1) > 0.5).astype(int)
    scaler = StandardScaler().fit(feats)
    rf = RandomForestClassifier(n_estimators=8).fit(
        scaler.transform(feats), labels)
    return ReorderSelector(rf, scaler, ["amd", "rcm"])


# ---------------------------------------------------------------------------
# mesh context plumbing (single device)
# ---------------------------------------------------------------------------

def test_default_mesh_is_degenerate():
    sm = get_serving_mesh()
    assert isinstance(sm, ServingMesh)
    assert sm.num_devices == 1
    assert sm.axis == "batch"


def test_serving_mesh_context_restores():
    outer = get_serving_mesh()
    with serving_mesh(make_serving_mesh(1)) as sm:
        assert get_serving_mesh() is sm
    assert get_serving_mesh() == outer
    set_serving_mesh(None)


def test_make_serving_mesh_rejects_bad_width():
    import jax

    with pytest.raises(ValueError):
        make_serving_mesh(len(jax.devices()) + 1)
    with pytest.raises(ValueError):
        make_serving_mesh(0)


def test_serving_mesh_is_hashable_jit_key():
    a, b = make_serving_mesh(1), make_serving_mesh(1)
    assert hash(a) == hash(b) and a == b  # same devices → one jit bucket


# ---------------------------------------------------------------------------
# sharded featurizer == raw impl (degenerate mesh, tier-1)
# ---------------------------------------------------------------------------

def test_sharded_path_matches_unsharded_impl(mats):
    batch = pad_csr_batch(mats, bucket=True)
    raw = np.asarray(extract_features_batch_jnp(batch, jit=False))
    via_mesh = np.asarray(extract_features_batch_jnp(batch))
    assert np.array_equal(raw, via_mesh)


def test_sharded_path_matches_host_features(mats):
    batch = pad_csr_batch(mats, bucket=True)
    dev = np.asarray(extract_features_batch_jnp(batch))
    host = extract_features_batch(mats)
    np.testing.assert_allclose(dev, host, rtol=1e-5, atol=1e-5)


# Features of one matrix computed by differently compiled programs (eager
# vs jit + shard_map, XLA vs the Pallas csr_stats kernels, 1 vs N shards)
# may sum nnz_std's squared deviations in a different order: they agree to
# this relative tolerance (a few f32 ulps), and the choices stay identical.
FEATURE_RTOL = 1e-6


def test_ragged_batches_all_sizes(mats):
    """Every prefix size B=1..len(mats) through the sharded path — the
    pad-to-multiple logic must be invisible at every raggedness."""
    for b in range(1, len(mats) + 1):
        sub = mats[:b]
        batch = pad_csr_batch(sub, bucket=True)
        raw = np.asarray(extract_features_batch_jnp(batch, jit=False))
        out = np.asarray(extract_features_batch_jnp(batch))
        assert out.shape == (b, len(FEATURE_NAMES))
        np.testing.assert_allclose(out, raw, rtol=FEATURE_RTOL, atol=0,
                                   err_msg=f"mismatch at B={b}")


def test_select_batch_device_path_on_mesh(mats, selector):
    names_dev, _ = selector.select_batch(mats, path="device")
    names_host, _ = selector.select_batch(mats, path="host")
    assert names_dev == names_host


def test_property_sharded_featurization_identity():
    hypothesis = pytest.importorskip("hypothesis")
    from hypothesis import given, settings
    from hypothesis import strategies as st

    pool = list(generate_suite(count=12, seed=5, size_scale=0.2))

    @settings(max_examples=20, deadline=None)
    @given(idx=st.lists(st.integers(0, len(pool) - 1), min_size=1,
                        max_size=7))
    def prop(idx):
        sub = [pool[i] for i in idx]
        batch = pad_csr_batch(sub, bucket=True)
        raw = np.asarray(extract_features_batch_jnp(batch, jit=False))
        out = np.asarray(extract_features_batch_jnp(batch))
        assert np.array_equal(raw, out)

    prop()


# ---------------------------------------------------------------------------
# multi-device identity (4 virtual host devices, subprocess)
# ---------------------------------------------------------------------------

def test_multidevice_featurize_and_infer_identity():
    """Mesh widths 1/2/3/4 over ragged batch sizes (including B < ndev and
    B % ndev != 0) must produce the same features (to FEATURE_RTOL, with
    and without the Pallas kernels) and identical selections."""
    out = run_py(f"""
        RTOL = {FEATURE_RTOL!r}
        import numpy as np
        from repro.core.features import (FEATURE_NAMES,
            extract_features_batch, extract_features_batch_jnp,
            pad_csr_batch)
        from repro.core.ml import RandomForestClassifier
        from repro.core.scaling import StandardScaler
        from repro.core.selector import ReorderSelector
        from repro.distributed.meshctx import (make_serving_mesh,
                                               serving_mesh)
        from repro.sparse.dataset import generate_suite

        pool = list(generate_suite(count=13, seed=3, size_scale=0.25))
        feats = extract_features_batch(pool)
        labels = (feats[:, FEATURE_NAMES.index("bandwidth")]
                  / np.maximum(feats[:, 0], 1) > 0.5).astype(int)
        scaler = StandardScaler().fit(feats)
        rf = RandomForestClassifier(n_estimators=8).fit(
            scaler.transform(feats), labels)
        sel = ReorderSelector(rf, scaler, ["amd", "rcm"])

        for b in (1, 2, 3, 5, 7, 8, 13):   # 5, 7, 13 don't divide 4
            sub = pool[:b]
            batch = pad_csr_batch(sub, bucket=True)
            ref = np.asarray(extract_features_batch_jnp(batch))  # 1-device
            ref_names, _ = sel.select_batch(sub, path="device")
            for nd in (2, 3, 4):
                with serving_mesh(make_serving_mesh(nd)):
                    out = np.asarray(extract_features_batch_jnp(batch))
                    outp = np.asarray(extract_features_batch_jnp(
                        batch, use_pallas=True))
                    names, _ = sel.select_batch(sub, path="device")
                np.testing.assert_allclose(out, ref, rtol=RTOL, atol=0,
                                           err_msg=str((b, nd)))
                np.testing.assert_allclose(outp, ref, rtol=RTOL, atol=0,
                                           err_msg=str((b, nd, "pallas")))
                assert names == ref_names, (b, nd)
        print("IDENTITY-OK")
    """)
    assert "IDENTITY-OK" in out


def test_multidevice_engine_serving_mesh():
    """EngineConfig(serving_devices=4) installs the mesh and the async
    server plans correctly through the sharded cold path."""
    out = run_py("""
        import numpy as np
        from repro.core.features import FEATURE_NAMES, extract_features_batch
        from repro.core.ml import RandomForestClassifier
        from repro.core.scaling import StandardScaler
        from repro.core.selector import ReorderSelector
        from repro.distributed.meshctx import get_serving_mesh
        from repro.engine import EngineConfig, SolverEngine
        from repro.sparse.dataset import generate_suite

        pool = list(generate_suite(count=10, seed=3, size_scale=0.25))
        feats = extract_features_batch(pool)
        labels = (feats[:, FEATURE_NAMES.index("bandwidth")]
                  / np.maximum(feats[:, 0], 1) > 0.5).astype(int)
        scaler = StandardScaler().fit(feats)
        rf = RandomForestClassifier(n_estimators=8).fit(
            scaler.transform(feats), labels)
        sel = ReorderSelector(rf, scaler, ["amd", "rcm"])

        engine = SolverEngine(EngineConfig(
            cache_dir=None, serving_devices=4, batch_size=4,
            max_wait_ms=2.0), selector=sel)
        server = engine.serve()
        plans = server.handle(pool)
        server.close()
        assert get_serving_mesh().num_devices == 4
        for m, p in zip(pool, plans):
            assert p.algorithm in ("amd", "rcm")
            assert sorted(p.perm.tolist()) == list(range(m.n))
        # warm identity: same structures come back from cache
        engine2_plans = engine.plan_batch(pool)
        assert [p.fingerprint for p in engine2_plans] == [
            p.fingerprint for p in plans]
        print("ENGINE-MESH-OK")
    """)
    assert "ENGINE-MESH-OK" in out
